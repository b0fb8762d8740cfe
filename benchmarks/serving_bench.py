"""Fig. 9 / Fig. 10 / Fig. 13: serving-system benchmarks on the DES
(deterministic stand-in for the paper's HTTP/RPC testbed) plus real
wall-clock jitted-inference costs measured on this machine, the
bucketed-serving microbench (``bench_fused_serving``), and
the multi-device placement sweep (``bench_placement_sweep``) — both
tracked in ``BENCH_serving.json``.

On the CPU the placement sweep needs forced host devices; run it
standalone as ``JAX_PLATFORMS=cpu python benchmarks/serving_bench.py``
(which then sets ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
before jax initialises) or under the CI multi-device lane's env.  On an
accelerator it sweeps the chips JAX finds.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List

import numpy as np

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_serving.json")

from repro.serving.latency import LatencyProfiler, queueing_bound
from repro.serving.simulator import SimConfig, simulate


def _merge_bench_json(updates: Dict) -> None:
    """Update BENCH_serving.json in place: each bench owns its keys and
    must not clobber the others' tracked trajectories."""
    merged = {}
    if os.path.exists(BENCH_JSON):
        with open(BENCH_JSON) as f:
            merged = json.load(f)
    merged.update(updates)
    with open(BENCH_JSON, "w") as f:
        json.dump(merged, f, indent=2)


def bench_fig9(model_cost: float = 0.02, batch_period: float = 3600.0,
               verbose=True) -> Dict:
    """Online (every 30 s) vs offline hourly batch, single patient."""
    dur = 2 * batch_period
    online = simulate([model_cost],
                      SimConfig(n_patients=1, n_devices=2,
                                duration_seconds=dur, window_seconds=30))
    offline = simulate([model_cost],
                       SimConfig(n_patients=1, n_devices=2,
                                 duration_seconds=dur, window_seconds=30,
                                 batch_period=batch_period))
    # inference-only latency (excludes staleness): queue wait + service
    inf_online = online.p(95)
    inf_offline = float(np.percentile(
        [q.t_done - q.t_start for q in offline.queries], 95)) \
        + 0.0  # service-side only
    staleness = float(np.mean(
        [q.t_start - q.t_window for q in offline.queries]))
    out = {"online_p95_s": inf_online,
           "offline_batch_p95_s": offline.p(95),
           "offline_inference_only_p95_s": inf_offline,
           "offline_mean_staleness_s": staleness,
           "staleness_ratio": offline.p(95) / max(inf_online, 1e-9)}
    if verbose:
        print(f"\nFig 9: online p95 {inf_online * 1000:.1f}ms vs "
              f"offline-batch p95 {offline.p(95):.0f}s "
              f"(mean staleness {staleness:.0f}s, "
              f"{out['staleness_ratio']:.0f}x)")
    return out


def bench_fig10(costs: List[float] = (0.01, 0.02, 0.015),
                patients=(8, 16, 32, 64, 100, 128),
                devices=(1, 2, 4, 8), verbose=True) -> Dict:
    left = {}
    for n in patients:
        r = simulate(list(costs), SimConfig(
            n_patients=n, n_devices=2, duration_seconds=120,
            window_seconds=30, seed=2))
        left[n] = {"p95_s": r.p(95), "p50_s": r.p(50),
                   "utilization": r.utilization,
                   "ingest_qps": n * 250}
    right = {}
    for d in devices:
        r = simulate(list(costs), SimConfig(
            n_patients=64, n_devices=d, duration_seconds=120,
            window_seconds=30, seed=2))
        right[d] = {"p95_s": r.p(95), "utilization": r.utilization}
    if verbose:
        print("\nFig 10 (left): latency vs #patients @2 devices")
        for n, v in left.items():
            print(f"  {n:4d} patients ({v['ingest_qps']:6d} qps ingest): "
                  f"p95 {v['p95_s'] * 1000:7.1f}ms "
                  f"util {v['utilization']:.2f}")
        print("Fig 10 (right): latency vs #devices @64 patients")
        for d, v in right.items():
            print(f"  {d} devices: p95 {v['p95_s'] * 1000:7.1f}ms")
    return {"vs_patients": left, "vs_devices": right}


def bench_fig13(windows=(5, 10, 30, 60), model_cost_per_s: float = 7e-4,
                verbose=True) -> Dict:
    """Larger observation window => more samples per query => larger
    T_s, and fewer-but-burstier queries => T_q effect (A.4)."""
    out = {}
    for w in windows:
        cost = model_cost_per_s * w          # inference cost grows w/ clip
        cfg = SimConfig(n_patients=64, n_devices=2,
                        duration_seconds=40 * w, window_seconds=float(w),
                        seed=3)
        r = simulate([cost], cfg)
        mu = cfg.n_devices / cost
        tq = queueing_bound(r.arrivals, mu, cost)
        out[w] = {"ts_s": cost, "tq_bound_s": tq,
                  "e2e_p95_s": r.p(95),
                  "tq_emp_max_s": float(r.queue_delays().max())}
        if verbose:
            v = out[w]
            print(f"Fig 13 window {w:3d}s: Ts {v['ts_s'] * 1000:6.1f}ms  "
                  f"Tq_bound {v['tq_bound_s'] * 1000:6.1f}ms  "
                  f"e2e_p95 {v['e2e_p95_s'] * 1000:6.1f}ms")
    return out


def bench_fused_serving(n_patients: int = 16, reps: int = 10,
                        input_len: int = 750, verbose=True,
                        write_json: bool = True) -> Dict:
    """Microbench of the bucketed serving hot path on the reduced
    12-member zoo x ``n_patients`` streaming patients:

    * ``fused``            — architecture-bucketed stacked execution,
                             one dispatch per bucket (4/query);
    * ``fused_microbatch`` — fused + cross-patient micro-batching: one
                             flush serves all ``n_patients`` windows
                             (4 dispatches per FLUSH, 4/P per query).

    Writes the result to BENCH_serving.json so the perf trajectory is
    tracked across PRs.
    """
    import jax
    from repro.configs.ecg_zoo import zoo_specs
    from repro.models.ecg_resnext import init_ecg
    from repro.serving.pipeline import EnsembleService, ZooMember

    specs = zoo_specs(reduced=True, input_len=input_len)
    members = [ZooMember(s, init_ecg(jax.random.PRNGKey(i), s))
               for i, s in enumerate(specs)]
    rng = np.random.default_rng(0)
    windows = [{"ecg": rng.standard_normal((3, input_len))
                .astype(np.float32)} for _ in range(n_patients)]

    modes = (("fused", False), ("fused_microbatch", True))
    out: Dict = {"n_patients": n_patients, "n_members": len(members),
                 "reps": reps, "input_len": input_len, "modes": {}}
    for name, microbatch in modes:
        svc = EnsembleService(members)
        out["n_buckets"] = svc.n_buckets
        if microbatch:
            svc.predict_batch(windows)                 # warmup/compile
        else:
            svc.predict(windows[0])
        d0, n_q = svc.dispatch_count, 0
        t0 = time.perf_counter()
        for _ in range(reps):
            if microbatch:
                svc.predict_batch(windows)
            else:
                for w in windows:
                    svc.predict(w)
            n_q += n_patients
        dt = time.perf_counter() - t0
        out["modes"][name] = {
            "per_query_ms": dt / n_q * 1e3,
            "sustained_qps": n_q / dt,
            "dispatches_per_query": (svc.dispatch_count - d0) / n_q,
        }
    if verbose:
        print(f"\nfused serving bench (reduced zoo x {n_patients} "
              f"patients, CPU):")
        for name, m in out["modes"].items():
            print(f"  {name:17s}: {m['per_query_ms']:7.2f} ms/query  "
                  f"{m['sustained_qps']:7.1f} q/s  "
                  f"{m['dispatches_per_query']:5.2f} dispatches/query")
    if write_json:
        _merge_bench_json(out)
    return out


INGEST_MODE_KEYS = ("per_query_ms", "sustained_qps",
                    "h2d_bytes_per_query", "marshal_ms_per_flush",
                    "dispatches_per_query")
INGEST_TOP_KEYS = ("n_patients", "n_members", "reps", "input_len",
                   "modes", "bitwise_device_vs_packed")


def check_ingest_schema(out: Dict) -> None:
    """Schema guard for ``BENCH_serving.json["ingest"]`` — run by the
    ``--smoke`` CI invocation so the tracked section can't silently
    rot as the bench evolves."""
    for k in INGEST_TOP_KEYS:
        assert k in out, f"ingest bench missing key {k!r}"
    for mode in ("packed_host", "device_resident"):
        assert mode in out["modes"], f"ingest bench missing mode {mode}"
        for k in INGEST_MODE_KEYS:
            assert k in out["modes"][mode], \
                f"ingest mode {mode} missing key {k!r}"
    assert out["bitwise_device_vs_packed"] is True


def bench_ingest(n_patients: int = 64, reps: int = 5,
                 input_len: int = 750, verbose=True,
                 write_json: bool = True) -> Dict:
    """Ingest-side microbench of the flush's two input forms on the
    reduced zoo x ``n_patients`` streaming patients:

    * ``packed_host``      — one [Ppad, 3, L] window pack per flush,
                             shipped once per device, lead-expanded to
                             the stacked view ON device (3 x L floats
                             of H2D per patient);
    * ``device_resident``  — windows live in ``DeviceIngest`` ring
                             buffers; the flush gathers them on device
                             and only (patient, end, valid) int32
                             triples cross the host boundary.

    Scores are compared bitwise across the two modes.  Merged into
    ``BENCH_serving.json`` under ``"ingest"``.
    """
    import jax
    from repro.configs.ecg_zoo import ECG_LEADS, zoo_specs
    from repro.models.ecg_resnext import init_ecg
    from repro.serving.aggregator import DeviceIngest, ModalitySpec
    from repro.serving.pipeline import EnsembleService, ZooMember

    specs = zoo_specs(reduced=True, input_len=input_len)
    members = [ZooMember(s, init_ecg(jax.random.PRNGKey(i), s))
               for i, s in enumerate(specs)]
    rng = np.random.default_rng(0)
    windows = [{"ecg": rng.standard_normal((ECG_LEADS, input_len))
                .astype(np.float32)} for _ in range(n_patients)]

    # stream the same windows into the device rings (mixed chunk sizes
    # exercise the pow2 ingest ladder), then serve them as refs
    di = DeviceIngest([ModalitySpec("ecg", float(input_len), ECG_LEADS)],
                      n_patients, window_seconds=1.0)
    refs = []
    for p in range(n_patients):
        ecg, off = windows[p]["ecg"], 0
        for k in (200, 250, 150, 100):
            di.ingest(off / input_len, p, "ecg", ecg[:, off:off + k])
            off += k
        while off < input_len:
            di.ingest(off / input_len, p, "ecg",
                      ecg[:, off:off + 250])
            off += 250
        refs.append(di.close_window(p, 1.0))

    feeds = {"packed_host": (EnsembleService(members), windows),
             "device_resident": (EnsembleService(members), refs)}
    out: Dict = {"n_patients": n_patients, "n_members": len(members),
                 "reps": reps, "input_len": input_len, "modes": {}}
    scores = {}
    for name, (svc, feed) in feeds.items():
        scores[name] = svc.predict_batch(feed)     # warmup/compile
        d0, h0, m0 = (svc.dispatch_count, svc.h2d_bytes,
                      svc.marshal_seconds)
        t0 = time.perf_counter()
        for _ in range(reps):
            svc.predict_batch(feed)
        dt = time.perf_counter() - t0
        n_q = reps * n_patients
        out["modes"][name] = {
            "per_query_ms": dt / n_q * 1e3,
            "sustained_qps": n_q / dt,
            "h2d_bytes_per_query": (svc.h2d_bytes - h0) / n_q,
            "marshal_ms_per_flush":
                (svc.marshal_seconds - m0) / reps * 1e3,
            "dispatches_per_query": (svc.dispatch_count - d0) / n_q,
        }
    # the modes must agree on the answers, not just the speed: BITWISE
    # (same program, device-gathered inputs)
    out["bitwise_device_vs_packed"] = bool(np.array_equal(
        np.asarray(scores["device_resident"]),
        np.asarray(scores["packed_host"])))
    if verbose:
        print(f"\ningest bench (reduced zoo x {n_patients} patients, "
              f"L={input_len}):")
        for name, m in out["modes"].items():
            print(f"  {name:16s}: {m['per_query_ms']:7.2f} ms/query  "
                  f"{m['h2d_bytes_per_query']:9.0f} H2D B/query  "
                  f"marshal {m['marshal_ms_per_flush']:6.2f} ms/flush")
        print(f"  device bitwise == packed: "
              f"{out['bitwise_device_vs_packed']}")
    if write_json:
        _merge_bench_json({"ingest": out})
    return out


SLOTS_TOP_KEYS = ("n_slots", "n_members", "n_buckets", "tick_reps",
                  "n_reads", "input_len", "tick_ms",
                  "dispatches_per_tick", "dispatches_per_query",
                  "reads_per_sec", "read_us", "flush_per_query_ms",
                  "read_vs_flush_ratio", "bitwise_equal")


def check_slots_schema(out: Dict) -> None:
    """Schema + invariant guard for ``BENCH_serving.json["slots"]``:
    queries must be free of device dispatch entirely, and a query read
    must cost at most a tenth of a flush-path query."""
    for k in SLOTS_TOP_KEYS:
        assert k in out, f"slots bench missing key {k!r}"
    assert out["bitwise_equal"] is True, \
        "slot engine diverged from the flush oracle"
    assert out["dispatches_per_query"] == 0.0, \
        "slot reads must not dispatch device work"
    assert out["read_vs_flush_ratio"] <= 0.10, \
        (f"slot read latency {out['read_us']:.1f}us is more than 10% "
         f"of a flush query ({out['flush_per_query_ms']:.3f}ms)")


def check_slots_file(path: str = BENCH_JSON) -> None:
    """CI gate on the committed BENCH_serving.json["slots"] section."""
    with open(path) as f:
        data = json.load(f)
    assert "slots" in data, "BENCH_serving.json missing 'slots'"
    check_slots_schema(data["slots"])
    print(f"slots schema OK ({path})")


def bench_slots(n_slots: int = 64, tick_reps: int = 20,
                n_reads: int = 200_000, input_len: int = 750,
                verbose=True, write_json: bool = True) -> Dict:
    """Slot-engine continuous serving vs the flush path on the reduced
    zoo x ``n_slots`` occupied beds:

    * ``tick_ms``             — one fused tick scoring ALL occupied
                                slots (ring gathers + the flush path's
                                cached bucket dispatches + one donated
                                masked update);
    * ``reads_per_sec``       — query cost once scores are resident:
                                ``read()`` is a host int read of the
                                mirror, zero H2D and zero dispatch;
    * ``flush_per_query_ms``  — the flush path serving the same refs,
                                for the read-vs-flush latency ratio.

    The engine's scores are asserted BITWISE equal to the flush oracle
    (same cached XLA programs, both at the ``n_slots`` pow2 pad).
    Merged into ``BENCH_serving.json`` under ``"slots"``.
    """
    import jax
    from repro.configs.ecg_zoo import ECG_LEADS, zoo_specs
    from repro.models.ecg_resnext import init_ecg
    from repro.serving.aggregator import DeviceIngest, ModalitySpec
    from repro.serving.pipeline import EnsembleService, ZooMember
    from repro.serving.slots import SlotEngine

    specs = zoo_specs(reduced=True, input_len=input_len)
    members = [ZooMember(s, init_ecg(jax.random.PRNGKey(i), s))
               for i, s in enumerate(specs)]
    rng = np.random.default_rng(0)
    di = DeviceIngest([ModalitySpec("ecg", float(input_len), ECG_LEADS)],
                      n_slots, window_seconds=1.0)
    svc = EnsembleService(members)
    eng = SlotEngine(svc, di)

    refs = []
    for p in range(n_slots):
        sig = rng.standard_normal(
            (ECG_LEADS, input_len)).astype(np.float32)
        off = 0
        for k in (250, 250, input_len - 500):
            di.ingest(off / input_len, p, "ecg", sig[:, off:off + k])
            off += k
        ref = di.close_window(p, 1.0)
        refs.append(ref)
        eng.update(ref)

    eng.warm()
    eng.tick()                                     # first-tick residue
    d0 = eng.dispatch_count
    t0 = time.perf_counter()
    for _ in range(tick_reps):
        eng.tick()
    tick_dt = time.perf_counter() - t0
    dispatches_per_tick = (eng.dispatch_count - d0) / tick_reps

    d0 = eng.dispatch_count
    t0 = time.perf_counter()
    for i in range(n_reads):
        eng.read(i % n_slots)
    read_dt = time.perf_counter() - t0
    read_dispatches = (eng.dispatch_count - d0) / n_reads

    oracle = np.asarray(svc.predict_batch(refs), np.float64)
    svc.predict_batch(refs)                        # flush-path warm
    t0 = time.perf_counter()
    for _ in range(max(2, tick_reps // 4)):
        svc.predict_batch(refs)
    flush_dt = time.perf_counter() - t0
    flush_per_query_ms = (flush_dt / (max(2, tick_reps // 4) * n_slots)
                          * 1e3)

    read_us = read_dt / n_reads * 1e6
    out: Dict = {
        "n_slots": n_slots, "n_members": len(members),
        "n_buckets": svc.n_buckets, "tick_reps": tick_reps,
        "n_reads": n_reads, "input_len": input_len,
        "tick_ms": tick_dt / tick_reps * 1e3,
        "dispatches_per_tick": dispatches_per_tick,
        "dispatches_per_query": read_dispatches,
        "reads_per_sec": n_reads / read_dt,
        "read_us": read_us,
        "flush_per_query_ms": flush_per_query_ms,
        "read_vs_flush_ratio": (read_us * 1e-3) / flush_per_query_ms,
        "bitwise_equal": bool(np.array_equal(
            eng.scores(), oracle, equal_nan=True)),
    }
    if verbose:
        print(f"\nslot engine bench ({n_slots} occupied slots, "
              f"L={input_len}):")
        print(f"  tick: {out['tick_ms']:7.2f} ms for all {n_slots} "
              f"slots ({dispatches_per_tick:.1f} dispatches/tick)")
        print(f"  read: {read_us:7.2f} us/query  "
              f"{out['reads_per_sec']:10.0f} reads/s  "
              f"{read_dispatches:.2f} dispatches/query")
        print(f"  flush path: {flush_per_query_ms:7.3f} ms/query  "
              f"-> read/flush ratio {out['read_vs_flush_ratio']:.4f}")
        print(f"  bitwise vs flush oracle: {out['bitwise_equal']}")
    if write_json:
        _merge_bench_json({"slots": out})
    return out


def bench_placement_sweep(device_counts=(1, 2, 4, 8),
                          n_patients: int = 16, reps: int = 5,
                          input_len: int = 750, verbose=True,
                          write_json: bool = True) -> Dict:
    """Sharded-vs-unsharded serving on the reduced zoo: for each device
    count, LPT-place the measured bucket costs, run the sharded
    ``predict_batch`` hot path, and record

    * ``makespan_s``     — the plan's per-query service latency model
                           (slowest device's bucket-cost total), which
                           must fall strictly below
    * ``serial_s``       — the unsharded sum-of-buckets cost, for every
                           sweep point with >= 2 devices;
    * wall-clock per-query latency and shard/dispatch counts.

    Merged into ``BENCH_serving.json`` under ``"placement_sweep"`` so
    the multi-device trajectory is tracked alongside the fused-serving
    numbers."""
    import jax
    from repro.configs.ecg_zoo import zoo_specs
    from repro.models.ecg_resnext import init_ecg
    from repro.serving.pipeline import EnsembleService, ZooMember

    avail = jax.device_count()
    device_counts = [d for d in device_counts if d <= avail]
    specs = zoo_specs(reduced=True, input_len=input_len)
    members = [ZooMember(s, init_ecg(jax.random.PRNGKey(i), s))
               for i, s in enumerate(specs)]
    rng = np.random.default_rng(0)
    windows = [{"ecg": rng.standard_normal((3, input_len))
                .astype(np.float32)} for _ in range(n_patients)]

    base = EnsembleService(members)
    bucket_costs = base.measured_bucket_costs(reps=reps,
                                              batch=n_patients)
    serial = float(sum(bucket_costs))
    out: Dict = {"n_devices_available": avail,
                 "n_patients": n_patients, "reps": reps,
                 "input_len": input_len,
                 "bucket_costs_ms": [c * 1e3 for c in bucket_costs],
                 "serial_s": serial, "sweep": {}}
    if verbose:
        print(f"\nplacement sweep (reduced zoo, {avail} host devices, "
              f"serial sum-of-buckets {serial * 1e3:.1f} ms):")
    for d in device_counts:
        pl = base.plan_placement(d, bucket_costs=bucket_costs)
        svc = EnsembleService(members, placement=pl,
                              devices=jax.devices()[:d])
        svc.predict_batch(windows)                 # warmup/compile
        d0 = svc.dispatch_count
        t0 = time.perf_counter()
        for _ in range(reps):
            svc.predict_batch(windows)
        dt = time.perf_counter() - t0
        n_q = reps * n_patients
        rec = {"makespan_s": pl.makespan,
               "imbalance": pl.imbalance,
               "n_shards": svc.n_buckets,
               "per_query_ms": dt / n_q * 1e3,
               "dispatches_per_query":
                   (svc.dispatch_count - d0) / n_q,
               # relative epsilon: at 1 device makespan == serial up to
               # float summation order, which must not read as "below"
               "makespan_below_serial":
                   bool(pl.makespan < serial * (1.0 - 1e-9))}
        out["sweep"][d] = rec
        if verbose:
            print(f"  {d} devices: makespan {pl.makespan * 1e3:6.1f} ms"
                  f" (imb {pl.imbalance:.2f}, {rec['n_shards']} shards)"
                  f"  wall {rec['per_query_ms']:6.2f} ms/query"
                  f"  {'<' if rec['makespan_below_serial'] else '>='}"
                  f" serial")
    # never clobber a tracked multi-device trajectory with a degenerate
    # sweep: a process launched without forced devices only covers d=1
    if write_json and len(device_counts) > 1:
        _merge_bench_json({"placement_sweep": out})
    elif write_json and verbose:
        print("  (single-device process: sweep NOT written to "
              "BENCH_serving.json — run benchmarks/serving_bench.py "
              "standalone for the tracked 8-device sweep)")
    return out


HETERO_TOP_KEYS = ("n_devices", "speeds", "bucket_costs_ms",
                   "makespan_blind_s", "makespan_aware_s",
                   "imbalance_blind", "imbalance_aware",
                   "aware_below_blind", "bitwise_equal")


def check_placement_hetero_schema(out: Dict) -> None:
    """Schema + invariant guard for
    ``BENCH_serving.json["placement_hetero"]``: on a 1x/4x split the
    speed-aware plan must land strictly below the speed-blind plan
    re-scored under the true speeds, and sharded serving under the
    aware plan must stay bitwise equal to the unsharded oracle."""
    for k in HETERO_TOP_KEYS:
        assert k in out, f"placement_hetero bench missing key {k!r}"
    assert out["bitwise_equal"] is True, \
        "hetero-placed sharded serving diverged from the oracle"
    assert out["aware_below_blind"] is True, \
        (f"speed-aware makespan {out['makespan_aware_s']:.4f}s not "
         f"below speed-blind {out['makespan_blind_s']:.4f}s")
    assert out["makespan_aware_s"] < out["makespan_blind_s"]


def check_placement_hetero_file(path: str = BENCH_JSON) -> None:
    """CI gate on the committed BENCH_serving.json["placement_hetero"]
    section."""
    with open(path) as f:
        data = json.load(f)
    assert "placement_hetero" in data, \
        "BENCH_serving.json missing 'placement_hetero'"
    check_placement_hetero_schema(data["placement_hetero"])
    print(f"placement_hetero schema OK ({path})")


def bench_placement_hetero(n_devices: int = 4,
                           speeds=(1.0, 1.0, 4.0, 4.0),
                           n_patients: int = 16, reps: int = 5,
                           input_len: int = 750, verbose=True,
                           write_json: bool = True) -> Dict:
    """Heterogeneous-pool placement on the reduced zoo: a synthetic
    1x/4x device-speed split (slow devices FIRST, so a speed-blind LPT
    plan is maximally unlucky — its heaviest buckets land on the slow
    half).  Records

    * ``makespan_blind_s`` — the speed-blind plan RE-SCORED under the
      true speed vector (``Placement(assignment, loads, speeds)``),
      i.e. what the pool would actually deliver if planned blind;
    * ``makespan_aware_s`` — the speed-vector LPT plan's finish time,
      which must land strictly below blind;
    * ``bitwise_equal``    — sharded serving under the aware plan vs
      the unsharded oracle (placement must never change scores).

    Merged into ``BENCH_serving.json`` under ``"placement_hetero"``."""
    import jax
    from repro.configs.ecg_zoo import zoo_specs
    from repro.models.ecg_resnext import init_ecg
    from repro.serving.pipeline import EnsembleService, ZooMember
    from repro.serving.placement import Placement

    avail = jax.device_count()
    if avail < n_devices:
        if verbose:
            print(f"\nplacement hetero bench skipped: {avail} host "
                  f"devices < {n_devices} (force with XLA_FLAGS)")
        return {}
    speeds = [float(s) for s in speeds]
    assert len(speeds) == n_devices
    specs = zoo_specs(reduced=True, input_len=input_len)
    members = [ZooMember(s, init_ecg(jax.random.PRNGKey(i), s))
               for i, s in enumerate(specs)]
    rng = np.random.default_rng(0)
    windows = [{"ecg": rng.standard_normal((3, input_len))
                .astype(np.float32)} for _ in range(n_patients)]

    base = EnsembleService(members)
    oracle = np.asarray(base.predict_batch(windows), np.float64)
    bucket_costs = base.measured_bucket_costs(reps=reps,
                                              batch=n_patients)
    blind = base.plan_placement(n_devices, bucket_costs=bucket_costs)
    # what the blind plan actually costs on the heterogeneous pool
    blind_true = Placement(blind.assignment, blind.loads, speeds=speeds)
    aware = base.plan_placement(n_devices, bucket_costs=bucket_costs,
                                speeds=speeds)

    svc = EnsembleService(members, placement=aware,
                          devices=jax.devices()[:n_devices])
    got = np.asarray(svc.predict_batch(windows), np.float64)
    out: Dict = {
        "n_devices": n_devices, "speeds": speeds,
        "n_patients": n_patients, "reps": reps,
        "input_len": input_len,
        "bucket_costs_ms": [c * 1e3 for c in bucket_costs],
        "makespan_blind_s": blind_true.makespan,
        "makespan_aware_s": aware.makespan,
        "imbalance_blind": blind_true.imbalance,
        "imbalance_aware": aware.imbalance,
        "aware_below_blind":
            bool(aware.makespan < blind_true.makespan * (1.0 - 1e-9)),
        "bitwise_equal": bool(np.array_equal(got, oracle,
                                             equal_nan=True)),
    }
    if verbose:
        print(f"\nplacement hetero bench ({n_devices} devices, speeds "
              f"{speeds}):")
        print(f"  speed-blind plan under true speeds: "
              f"{blind_true.makespan * 1e3:6.1f} ms "
              f"(imb {blind_true.imbalance:.2f})")
        print(f"  speed-aware plan:                   "
              f"{aware.makespan * 1e3:6.1f} ms "
              f"(imb {aware.imbalance:.2f})")
        print(f"  aware below blind: {out['aware_below_blind']}   "
              f"bitwise vs oracle: {out['bitwise_equal']}")
    if write_json:
        _merge_bench_json({"placement_hetero": out})
    return out


def bench_measured_costs(verbose=True) -> Dict:
    """Real wall-clock per-member inference cost (timeit analogue of
    A.4's 'Time in PyTorch' curve) for a few zoo members."""
    import jax
    from repro.configs.ecg_zoo import zoo_specs
    from repro.models.ecg_resnext import init_ecg
    from repro.serving.pipeline import EnsembleService, ZooMember
    specs = zoo_specs(reduced=True, input_len=750)[:4]
    members = [ZooMember(s, init_ecg(jax.random.PRNGKey(i), s))
               for i, s in enumerate(specs)]
    svc = EnsembleService(members)
    costs = svc.measured_costs(reps=5)
    out = {s.name: c for s, c in zip(specs, costs)}
    if verbose:
        print("\nmeasured per-member inference cost (CPU, jitted):")
        for k, v in out.items():
            print(f"  {k}: {v * 1000:.2f} ms/query")
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-size CI invocation: run the fused + "
                         "ingest benches at toy sizes, validate the "
                         "BENCH_serving.json['ingest'] schema, write "
                         "nothing")
    args = ap.parse_args()
    # on the CPU backend, force host devices before jax initialises
    # (jax is imported lazily): the placement benches need a
    # multi-device pool in BOTH modes.  On an accelerator they use the
    # chips JAX finds.
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        bench_fused_serving(n_patients=4, reps=2, input_len=250,
                            write_json=False)
        out = bench_ingest(n_patients=8, reps=2, input_len=250,
                           write_json=False)
        check_ingest_schema(out)
        print("ingest schema OK")
        out = bench_slots(n_slots=8, tick_reps=3, n_reads=20_000,
                          input_len=250, write_json=False)
        check_slots_schema(out)
        print("slots schema OK")
        out = bench_placement_hetero(n_patients=4, reps=2,
                                     input_len=250, write_json=False)
        check_placement_hetero_schema(out)
        print("placement_hetero schema OK")
    else:
        bench_fused_serving()
        bench_ingest()
        bench_slots()
        check_slots_file()
        bench_placement_sweep()
        bench_placement_hetero()
        check_placement_hetero_file()
