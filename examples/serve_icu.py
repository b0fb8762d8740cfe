"""End-to-end ICU serving driver: 64-bed discrete-event simulation of the
served ensemble (Fig. 10 conditions) + a real wall-clock fused-serving
demo (bucketed stacked dispatch + cross-patient micro-batching through
the batch-aware ``EnsembleServer``).

``--adaptive`` additionally exercises the online control plane against
a census spike (beds tripling mid-run): per-epoch telemetry drives the
controller (shed / warm-started recompose / climb) with the trained zoo
and measured member costs, and a real hot-swap segment shows selector
swaps mid-stream with zero dropped queries.

``--chaos`` runs a fault drill against the live fused server: a
deterministic ``FaultPlane`` schedule injects a transient device loss,
a worker stall, and a backpressure episode; the drill prints how each
fault was absorbed — served late, NaN-failed by the watchdog, or
counted rejected — with full query conservation.

    PYTHONPATH=src:. python examples/serve_icu.py [--beds 64] [--adaptive]
"""
import argparse
import sys
import os
import time
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from benchmarks.zoo_setup import (binding_budget, build_zoo,
                                  make_profilers)
from repro.core.composer import ComposerParams, compose
from repro.core.profiles import SystemConfig
from repro.serving.latency import queueing_bound
from repro.serving.pipeline import EnsembleService, ZooMember
from repro.serving.server import EnsembleServer
from repro.serving.simulator import SimConfig, simulate
from repro.training.data import ecg_clip, sample_patient


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--beds", type=int, default=64)
    ap.add_argument("--devices", type=int, default=2)
    ap.add_argument("--minutes", type=float, default=3.0)
    ap.add_argument("--adaptive", action="store_true",
                    help="run the online control plane against a "
                         "census spike (beds tripling mid-run)")
    ap.add_argument("--tiered", action="store_true",
                    help="run the per-acuity-tier control plane: "
                         "stable beds shed first under the spike, "
                         "critical beds hold the rich ensemble")
    ap.add_argument("--chaos", action="store_true",
                    help="run a deterministic fault drill against the "
                         "live server: transient device loss, worker "
                         "stall, backpressure — every query accounted")
    ap.add_argument("--metrics", action="store_true",
                    help="attach the observability plane to the fused "
                         "serving demo: per-stage span attribution, a "
                         "live /metrics scrape, and a JSONL span dump")
    args = ap.parse_args()

    zoo, extras = build_zoo(n_patients=16, clips=8, steps=120)
    sysconf = SystemConfig(n_devices=args.devices, n_patients=args.beds)
    f_a, f_l = make_profilers(zoo, sysconf, extras)
    budget = binding_budget(zoo, f_l)
    res = compose(len(zoo), f_a, f_l, budget,
                  ComposerParams(N=8, K=6, seed=0))
    sel = np.flatnonzero(res.b_star)
    costs = [extras["measured_costs"][i] for i in sel]
    print(f"ensemble: {[zoo.profiles[i].name for i in sel]}")
    print(f"predicted latency {res.latency * 1000:.1f} ms "
          f"(budget {budget * 1000:.1f} ms)")

    cfg = SimConfig(n_patients=args.beds, n_devices=args.devices,
                    duration_seconds=args.minutes * 60,
                    window_seconds=30.0)
    r = simulate(costs, cfg)
    mu = args.devices / sum(costs)
    tq = queueing_bound(r.arrivals, mu, max(costs))
    print(f"\n{args.beds}-bed simulation, {args.minutes:.0f} min, "
          f"{args.beds * 250} qps ingest:")
    print(f"  queries served     : {len(r.queries)}")
    if len(r.queries):
        print(f"  p50 / p95 / max    : {r.p(50) * 1000:.1f} / "
              f"{r.p(95) * 1000:.1f} / "
              f"{r.latencies().max() * 1000:.1f} ms")
        print(f"  device utilization : {r.utilization:.2%}")
        print(f"  empirical max Tq   : "
              f"{r.queue_delays().max() * 1000:.1f} ms"
              f"  (network-calculus bound {tq * 1000:.1f} ms)")
        print(f"  sub-second p95     : {r.p(95) < 1.0}")
    else:
        print("  (duration shorter than one observation window — "
              "no sim queries)")

    # real wall-clock fused serving: the composed ensemble behind the
    # batch-aware server, windows from many beds coalesced per flush
    members = [ZooMember(extras["specs"][i],
                         extras["params"][zoo.profiles[i].name])
               for i in sel]
    svc = EnsembleService(members)
    svc.warmup(batch_sizes=(1, 2, 4, 8))      # pow2-padded flush sizes
    tracer = telem = None
    if args.metrics:
        from repro.control.telemetry import SloTelemetry
        from repro.obs.spans import SpanRecorder
        tracer = SpanRecorder()
        telem = SloTelemetry(slo_seconds=1.0, window_seconds=30.0)
    srv = EnsembleServer(batch_handler=svc.predict_batch,
                         n_workers=args.devices, max_batch=8,
                         max_wait_ms=2.0, telemetry=telem,
                         tracer=tracer).start()
    rng = np.random.default_rng(0)
    n_demo = min(args.beds, 16)
    d0 = svc.dispatch_count
    for bed in range(n_demo):
        pp = sample_patient(rng, bed % 2)
        srv.submit(bed, {"ecg": ecg_clip(rng, pp, seconds=3)})
    stats = srv.stop()
    print(f"\nfused wall-clock serving ({len(members)} members -> "
          f"{svc.n_buckets} buckets, {n_demo} beds):")
    print(f"  served             : {stats.served}")
    print(f"  p50 / p95          : {stats.p(50) * 1000:.1f} / "
          f"{stats.p(95) * 1000:.1f} ms")
    print(f"  jit dispatches     : {svc.dispatch_count - d0} "
          f"({(svc.dispatch_count - d0) / max(stats.served, 1):.2f}"
          f"/query; mean batch "
          f"{srv.batcher.stats.mean_batch:.1f})")

    if args.metrics:
        # where did each query's latency go? — the span recorder
        # attributed every retired query across queue / coalesce /
        # marshal / dispatch / gather, and the exporter publishes the
        # same numbers as Prometheus text + JSONL traces
        import tempfile
        import urllib.request
        from repro.obs.export import (MetricsExporter,
                                      start_metrics_server,
                                      write_spans_jsonl)
        att = tracer.attribution()
        stage_ms = {k: 1e3 * v / max(att["n_spans"], 1)
                    for k, v in att["stage_seconds"].items()}
        print(f"\nobservability plane ({att['n_spans']} spans, "
              f"coverage {att['coverage']:.3f}):")
        print("  per-query stage ms : "
              + "  ".join(f"{k} {v:.2f}" for k, v in stage_ms.items()))
        exporter = MetricsExporter(server=srv, telemetry=telem,
                                   tracer=tracer, service=svc)
        httpd = start_metrics_server(exporter, port=0)
        try:
            url = f"http://127.0.0.1:{httpd.server_port}/metrics"
            with urllib.request.urlopen(url, timeout=10) as resp:
                body = resp.read().decode()
        finally:
            httpd.shutdown()
        n_series = sum(1 for ln in body.splitlines()
                       if ln and not ln.startswith("#"))
        print(f"  /metrics scrape    : {n_series} series from {url}")
        with tempfile.NamedTemporaryFile(suffix=".jsonl",
                                         delete=False) as f:
            n = write_spans_jsonl(tracer, f.name)
            print(f"  JSONL span dump    : {n} spans -> {f.name}")

    # device-resident ingest: the same beds stream 250-sample chunks
    # into on-device ring buffers; a closed window is submitted as a
    # DeviceWindowRef (three host ints) and the flush gathers + lead-
    # expands it on device — no per-member H2D marshaling at all
    from repro.configs.ecg_zoo import ECG_LEADS
    from repro.serving.aggregator import DeviceIngest, ModalitySpec
    clip_len = members[0].spec.input_len
    di = DeviceIngest([ModalitySpec("ecg", float(clip_len), ECG_LEADS)],
                      n_patients=n_demo, window_seconds=1.0)
    di.warm_gather(lens=tuple({m.spec.input_len for m in members}))
    h0, q0 = svc.h2d_bytes, svc.dispatch_count
    srv2 = EnsembleServer(batch_handler=svc.predict_batch,
                          n_workers=args.devices, max_batch=8,
                          max_wait_ms=2.0).start()
    for bed in range(n_demo):
        pp = sample_patient(rng, bed % 2)
        ecg = ecg_clip(rng, pp, seconds=3)
        for off in range(0, ecg.shape[-1], 250):
            di.ingest(off / 250.0, bed, "ecg", ecg[:, off:off + 250])
        srv2.submit(bed, di.close_window(bed, 1.0))
    stats2 = srv2.stop()
    print(f"\ndevice-resident ingest ({n_demo} beds, ring-buffered "
          f"250 Hz chunks, on-device lead-gather):")
    print(f"  served             : {stats2.served}")
    print(f"  p50 / p95          : {stats2.p(50) * 1000:.1f} / "
          f"{stats2.p(95) * 1000:.1f} ms")
    print(f"  jit dispatches     : {svc.dispatch_count - q0} "
          f"({(svc.dispatch_count - q0) / max(stats2.served, 1):.2f}"
          f"/query)")
    print(f"  flush H2D          : "
          f"{(svc.h2d_bytes - h0) / max(stats2.served, 1):.0f} B/query"
          f" (vs {ECG_LEADS * clip_len * 4} B/query packed, "
          f"{len(members) * clip_len * 4} B/query pre-refactor)")

    if args.chaos:
        # chaos drill: the same fused service behind a watchdogged,
        # priority-bounded server, with a seeded fault schedule fired
        # against it.  The transient device loss is ridden out by the
        # protect() retry loop (queries served LATE, heart-beating so
        # the watchdog knows they are alive); the injected stall never
        # heart-beats, so the watchdog NaN-fails that co-batch and
        # respawns the worker; the backpressure episode floods stable
        # beds and the priority queue sheds them first.
        from repro.control.faults import FaultEvent, FaultPlane
        schedule = [
            FaultEvent(t=0.2, kind="device_loss", target=0, duration=0.6),
            FaultEvent(t=1.0, kind="worker_stall", duration=0.8),
            FaultEvent(t=1.6, kind="backpressure", duration=0.5),
        ]
        plane = FaultPlane(schedule)
        guarded = plane.protect(lambda ws, *_tier: svc.predict_batch(ws),
                                heartbeat=lambda: srv3.heartbeat())
        srv3 = EnsembleServer(
            batch_handler=guarded, n_workers=2, max_batch=4,
            max_wait_ms=2.0, max_queue=8,
            tier_of=lambda bed: "critical" if bed % 4 == 0 else "stable",
            tier_priority={"critical": 1.0, "stable": 0.0},
            deadline_seconds=0.5).start()
        svc.dispatch_guard = plane.guard
        plane.arm()           # clock starts AFTER all compilation above
        submitted = 0
        while plane.now() < 2.5 or not plane.done():
            bed = submitted % n_demo
            pp = sample_patient(rng, bed % 2)
            win = {"ecg": ecg_clip(rng, pp, seconds=3)}
            srv3.submit(bed, win)
            submitted += 1
            if plane.backpressure_active():   # overrun the stable tier
                for b in range(n_demo):
                    if b % 4 != 0:
                        srv3.submit(b, win)
                        submitted += 1
            time.sleep(0.03)
        stats3 = srv3.stop(join_timeout=5.0)
        svc.dispatch_guard = None
        rej = sum(stats3.rejected.values())
        print(f"\nchaos drill (transient device loss, worker stall, "
              f"backpressure):")
        print(f"  submitted / served : {submitted} / {stats3.served}")
        print(f"  NaN-failed (stall) : {stats3.failed}  "
              f"(watchdog stalls {stats3.stalls})")
        print(f"  rejected           : {rej} "
              f"(critical {stats3.rejected.get('critical', 0)}, "
              f"stable {stats3.rejected.get('stable', 0)})")
        print(f"  conservation       : "
              f"{stats3.served + stats3.shed == submitted} "
              f"(served + shed == submitted)")
        for r in plane.recoveries:
            print(f"  recovery           : t={r['t']:.2f}s "
                  f"{r['kind']} device {r['target']}")
        print(f"  leaked threads     : {srv3.leaked or 'none'}")

    if args.tiered:
        # per-acuity-tier degradation: the same spike, but the unit of
        # actuation is a TIER — stable beds shed first (and climb
        # last), critical beds keep the composed rich ensemble
        from benchmarks.adaptive_bench import run_tiered_sim
        schedule = [(3, args.beds), (4, 3 * args.beds), (3, args.beds)]
        print(f"\ntiered control plane (census "
              f"{' -> '.join(str(c) for _, c in schedule)}, "
              f"SLO {budget * 1000:.0f} ms):")
        td = run_tiered_sim(zoo=zoo, costs=extras["measured_costs"],
                            f_a=f_a, slo=budget, schedule=schedule,
                            n_devices=args.devices, verbose=True)
        crit = list(td["tier_fracs"])[-1]
        stab = list(td["tier_fracs"])[0]
        print(f"  critical: viol "
              f"{td['per_tier'][crit]['violation_rate']:.2f}  "
              f"acc {td['per_tier'][crit]['mean_accuracy']:.3f}  "
              f"min rung {td['per_tier'][crit]['min_rung']}")
        print(f"  stable  : viol "
              f"{td['per_tier'][stab]['violation_rate']:.2f}  "
              f"acc {td['per_tier'][stab]['mean_accuracy']:.3f}  "
              f"min rung {td['per_tier'][stab]['min_rung']}")

    if not args.adaptive:
        return

    # ------------------------------------------- online control plane
    # the same closed loop as benchmarks/adaptive_bench, but with the
    # TRAINED zoo and its measured per-member costs: census triples
    # mid-run, the static selector from above stays frozen, the
    # adaptive one sheds / recomposes / climbs
    from benchmarks.adaptive_bench import (run_adaptive_sim,
                                           wallclock_hot_swap)

    schedule = [(3, args.beds), (4, 3 * args.beds), (3, args.beds)]
    print(f"\nadaptive control plane (census "
          f"{' -> '.join(str(c) for _, c in schedule)}, "
          f"SLO {budget * 1000:.0f} ms):")
    common = dict(zoo=zoo, costs=extras["measured_costs"], f_a=f_a,
                  slo=budget, schedule=schedule,
                  n_devices=args.devices, verbose=True)
    st = run_adaptive_sim(adaptive=False, **common)
    ad = run_adaptive_sim(adaptive=True, **common)
    print(f"  static  : viol {st['violation_rate']:.2f}  "
          f"p99@spike {st['p99_final_spike_s'] * 1000:.0f} ms")
    print(f"  adaptive: viol {ad['violation_rate']:.2f}  "
          f"p99@spike {ad['p99_final_spike_s'] * 1000:.0f} ms  "
          f"({ad['n_recomposes']} recomposes)")

    # real hot-swap mid-stream on the trained members: the full zoo is
    # the pool, selectors toggle between the composed ensemble and its
    # cheapest member; every submitted query is served across the swaps
    pool = [ZooMember(extras["specs"][i],
                      extras["params"][zoo.profiles[i].name])
            for i in range(len(zoo))]
    cheap = np.zeros(len(zoo), np.int8)
    cheap[int(np.argmin(extras["measured_costs"]))] = 1
    swap = wallclock_hot_swap(
        n_queries=3 * n_demo, n_swaps=2, pool=pool,
        sel_a=res.b_star, sel_b=cheap, n_workers=args.devices,
        window_fn=lambda r_, i: {"ecg": ecg_clip(
            r_, sample_patient(r_, i % 2), seconds=3)},
        verbose=False)
    print(f"  hot-swap mid-stream: {swap['served']}/{swap['submitted']} "
          f"served across {swap['swaps']} swaps "
          f"({swap['dropped']} dropped)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
