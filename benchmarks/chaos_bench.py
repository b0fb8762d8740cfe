"""Chaos soak harness: replay a streamed ICU trace through the FULL
device-ingest serving stack while a seeded ``FaultPlane`` injects
device loss, a worker stall, and an ingest-backpressure episode — then
hold the whole run to four invariants:

1. **conservation** — every submitted query is accounted exactly once:
   real-scored + NaN-failed + rejected == submitted (nothing silently
   dropped, nothing double-served);
2. **bitwise-vs-oracle** — every query that delivered a REAL score is
   bitwise-identical to a fault-free oracle rescoring of the exact same
   flush composition (window snapshot + member selection), so a fault
   can delay or fail a score but never silently change one;
3. **bounded recovery** — after each fault clears, the sliding-window
   p99 is back under the SLO within ``recovery_slo_s``;
4. **no leaked threads** — server workers/watchdog and controller
   monitor/recompose/replace threads (all ``repro-`` named) are gone
   after shutdown.

The run drives the real wiring end to end: ``DeviceIngest`` rings ->
``DeviceWindowRef`` submit -> bounded priority-aware ``ShedQueue`` ->
batch workers + watchdog -> ``HotSwapper`` facade armed by the fault
plane -> live ``AdaptiveController`` monitor loop
(``control.faults.wire_controller``) actuating on wall-clock telemetry.

``BENCH_chaos.json`` records both lanes: ``single_device`` (transient
device loss — the only recoverable shape without a survivor) and
``forced_8_device`` (permanent loss -> quarantine + re-place onto
survivors, on the CPU backend under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  Every lane
runs in a child process of its own, one after the other, so the
parent never imports JAX and never holds the accelerator a child needs.

``--smoke`` is the CI tier1-chaos entry: tiny trace, fixed seed and
schedule, both lanes, schema-gated, writes nothing.

The SLOT lane (``BENCH_chaos.json["slots"]``) soaks the continuous
slot engine instead of the flush path: a compressed-time MIMIC-style
cohort trace (each driver step is ``step_logical_s`` of ICU time, so
minutes of wall clock replay tens of logical hours of census churn —
Poisson admissions through ``SlotEngine.acquire_slot`` growing the
census past its initial ``n_slots``, lognormal length-of-stay
discharges, escalated beds closing windows faster than stable ones)
under ``slot_compound_schedule`` (a ticker-stall cascade the
``TickerWatchdog`` must respawn through, plus overlapping device
losses inside a backpressure episode).  Its bitwise oracle is the
TICK REPORT: every ``(slot, close-version, pad-rung)`` a tick ever
stamped is re-scored offline by an unsharded fault-free
``EnsembleService`` at exactly that pad rung, and every REAL score a
query served must be one of its slot's stamped scores — a fault can
delay a tick or NaN a read, never alter a score.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_chaos.json")
N_FORCED = 8

CHAOS_LANE_KEYS = (
    "n_devices", "n_patients", "windows_per_patient", "seed", "slo_s",
    "deadline_s", "schedule", "submitted", "ring_rejected", "served",
    "served_real", "failed", "rejected", "rejected_by_tier",
    "critical_rejected", "stalls", "quarantined", "recoveries",
    "controller", "faults", "p50_ms", "p99_ms",
    "conservation_ok", "bitwise_ok", "n_bitwise_checked",
    "recovery_ok", "no_leaked_threads", "leaked_threads",
)
FAULT_KINDS_REQUIRED = ("device_loss", "worker_stall", "backpressure")


def default_schedule(n_devices: int, t0: float = 0.45):
    """One of each fault kind.  With survivors the device loss is
    PERMANENT (recovery == quarantine + re-place); on a lone device it
    is transient (recovery == the device coming back) — the only
    recoverable shape there."""
    from repro.control.faults import FaultEvent
    if n_devices >= 2:
        loss = FaultEvent(t0, "device_loss", target=1, duration=0.0)
    else:
        loss = FaultEvent(t0, "device_loss", target=0, duration=0.35)
    return [loss,
            FaultEvent(t0 + 0.55, "worker_stall", duration=0.5),
            FaultEvent(t0 + 1.25, "backpressure", duration=0.4)]


def run_chaos(n_patients: int = 6, windows_per_patient: int = 10,
              input_len: int = 250, n_devices: int = 1, seed: int = 0,
              slo: float = 1.0, deadline: float = 0.25,
              max_queue: int = 32, window_wall_s: float = 0.25,
              recovery_slo_s: Optional[float] = None, schedule=None,
              use_controller: bool = True, verbose: bool = True) -> Dict:
    """One soak lane.  Returns the result dict (see CHAOS_LANE_KEYS)."""
    import jax

    if recovery_slo_s is None:
        # a PERMANENT loss on the sharded lane recovers by failover
        # restage — the moved buckets recompile, which on the forced
        # host-device rig costs real seconds; transient recovery on the
        # single-device lane is bounded by the fault duration itself
        recovery_slo_s = 30.0 if n_devices >= 2 else 5.0

    from repro.configs.ecg_zoo import ECG_LEADS, zoo_specs
    from repro.control.faults import FaultPlane, wire_controller
    from repro.control.swap import HotSwapper
    from repro.control.telemetry import SloTelemetry
    from repro.models.ecg_resnext import init_ecg
    from repro.obs.spans import SpanRecorder
    from repro.serving.aggregator import DeviceIngest, ModalitySpec
    from repro.serving.pipeline import EnsembleService, ZooMember
    from repro.serving.server import EnsembleServer

    if n_devices > jax.device_count():
        raise ValueError(f"lane needs {n_devices} devices, JAX has "
                         f"{jax.device_count()}")
    rng = np.random.default_rng(seed)
    specs = zoo_specs(reduced=True, input_len=input_len)
    pool = [ZooMember(s, init_ecg(jax.random.PRNGKey(i), s))
            for i, s in enumerate(specs)]
    n = len(pool)
    rich = np.ones(n, np.int8)
    mid = np.zeros(n, np.int8)
    mid[::2] = 1
    cheap = np.zeros(n, np.int8)
    cheap[0] = 1

    member_costs = EnsembleService(pool).measured_costs(reps=1) \
        if use_controller else None

    swapper = HotSwapper(pool, rich, n_devices=n_devices,
                         warmup_batch_sizes=(1, 2, 4, 8))
    swapper.set_ladder([cheap, mid, rich])
    telemetry = SloTelemetry(slo_seconds=slo, window_seconds=3.0)

    schedule = schedule if schedule is not None \
        else default_schedule(n_devices)
    plane = FaultPlane(schedule, seed=seed)

    # the member identity of each flush's service keys the oracle: a
    # controller shed/climb or fault re-place mid-run changes WHICH
    # selector scored a query, and the oracle must rescore with exactly
    # that selector (placement is bitwise-irrelevant: bucket-granular
    # plans reproduce the single-device scores exactly)
    pool_ids = {id(m): i for i, m in enumerate(pool)}
    flush_log: List = []            # (member_key, [qid], [score])
    log_lock = threading.Lock()

    def scoring(windows):
        svc = swapper.facade.current
        scores = list(svc.predict_batch(windows))
        key = tuple(pool_ids[id(m)] for m in svc.members)
        with log_lock:
            flush_log.append(
                (key, [w.extra["qid"] for w in windows], scores))
        return scores

    # heartbeat: the retry/failover wait inside protect() refreshes the
    # watchdog deadline (late-bound; srv is created just below)
    handler = plane.protect(scoring, swapper,
                            heartbeat=lambda: srv.heartbeat())

    def tier_of(patient):
        return "critical" if patient % 3 == 0 else "stable"

    tracer = SpanRecorder()
    srv = EnsembleServer(
        batch_handler=lambda ws, tier=None: handler(ws),
        n_workers=2, slo_seconds=slo, max_queue=max_queue,
        max_batch=8, max_wait_ms=2.0, telemetry=telemetry,
        tier_of=tier_of, tier_priority={"critical": 2, "stable": 0},
        deadline_seconds=deadline, tracer=tracer).start()

    ctl = wire_controller(telemetry, swapper, member_costs=member_costs,
                          period_seconds=0.2) if use_controller else None

    # logical ingest time: 1.0 "second" per window round (input_len
    # samples at input_len Hz), decoupled from window_wall_s wall pacing
    # vitals ride along so ring backpressure reflects the TIGHTEST
    # modality, not just ecg: headroom(p) aggregates min across rings
    # in window units (< 1.0 = can't absorb one more window)
    vitals_hz, vitals_ch = 5.0, 6
    di = DeviceIngest([ModalitySpec("ecg", float(input_len), ECG_LEADS),
                       ModalitySpec("vitals", vitals_hz, vitals_ch)],
                      n_patients, window_seconds=1.0,
                      capacity_windows=4.0)
    di.warm_gather(sorted({s.input_len for s in specs}))

    # arm LAST: the schedule clock starts when traffic starts, not while
    # warmup is still compiling (at 8 forced devices warm-up alone can
    # outlast the first scheduled fault, which would make every query in
    # the run land on an already-lost device)
    plane.arm(swapper)

    qid = 0
    oracle_windows: Dict[int, np.ndarray] = {}
    submitted = 0
    ring_rejected = 0
    fault_recovery: Dict[int, Optional[float]] = {
        i: None for i in range(len(schedule))}

    def check_recoveries():
        t_now = plane.now()
        for i, ev in enumerate(schedule):
            if fault_recovery[i] is not None:
                continue
            end = ev.t + ev.duration
            if t_now <= end + 0.05:
                continue
            snap = telemetry.snapshot(
                since=plane._armed_at + end + deadline)
            # recovered = REAL scores flowing again under the SLO;
            # NaN-failed retires also hit record_served, so subtract
            # them — a watchdog NaN storm must not count as recovery
            if snap.n_served - snap.n_failed >= 2 and snap.p99 <= slo:
                fault_recovery[i] = t_now - end

    zero_win = np.zeros((ECG_LEADS, input_len), np.float32)

    def submit_ref(p, ref):
        """Snapshot the ref's window AT SUBMIT TIME (the ring moves on;
        the oracle must see what a timely flush would have gathered).
        A ref closed with no fresh samples (the flood path) gathers the
        zero-filled dropout window — no device round-trip needed, which
        keeps the flood fast enough to actually overrun the queue."""
        nonlocal submitted
        qid_ = ref.extra["qid"]
        if all(v == 0 for v in ref.valid.values()):
            oracle_windows[qid_] = zero_win
        else:
            oracle_windows[qid_] = ref.host_window("ecg")
        submitted += 1
        srv.submit(p, ref)

    def maybe_flood():
        """During a backpressure episode, overrun the bounded queue with
        stable-tier queries: the priority-aware ShedQueue must shed
        these, never a critical.  (Re-closing an unchanged ring yields
        the valid=0 all-zeros dropout window — a legitimate degenerate
        query the oracle rescores like any other.)"""
        nonlocal qid
        if not plane.backpressure_active():
            return
        flood = [p for p in range(n_patients) if p % 3 != 0]
        for _ in range(max(2, (2 * max_queue) // max(1, len(flood)))):
            for p in flood:
                ref = di.close_window(p, t_logical + 1.0,
                                      extra={"qid": qid})
                qid += 1
                submit_ref(p, ref)

    t_logical = 0.0
    chunks = (100, 75, 75)
    for _round in range(windows_per_patient):
        for p in range(n_patients):
            if di.headroom(p) < 1.0:
                # ring backpressure: feeding would push outstanding
                # windows past the staleness guard in SOME modality —
                # reject up front (aggregate min, window units)
                ring_rejected += 1
                continue
            sig = rng.standard_normal(
                (ECG_LEADS, input_len)).astype(np.float32)
            off = 0
            for k in chunks:
                di.ingest(t_logical + off / input_len, p, "ecg",
                          sig[:, off:off + k])
                off += k
            di.ingest(t_logical, p, "vitals", rng.standard_normal(
                (vitals_ch, int(vitals_hz))).astype(np.float32))
            ref = di.close_window(p, t_logical + 1.0,
                                  extra={"qid": qid})
            qid += 1
            submit_ref(p, ref)
        maybe_flood()
        t_logical += 1.0
        check_recoveries()
        time.sleep(window_wall_s)

    # keep a light pulse flowing until the schedule has fully fired and
    # every fault has had its recovery window measured
    t_wait = time.monotonic() + recovery_slo_s + 2.0
    while (not plane.done()
           or any(v is None for v in fault_recovery.values())) \
            and time.monotonic() < t_wait:
        for p in range(min(2, n_patients)):
            if srv.q.qsize() >= max(2, max_queue // 2):
                break       # polite pulse: recovery measurement traffic
                #             must not re-trigger backpressure shedding
            if di.headroom(p) < 1.0:
                ring_rejected += 1
                continue
            sig = rng.standard_normal(
                (ECG_LEADS, input_len)).astype(np.float32)
            di.ingest(t_logical, p, "ecg", sig)
            di.ingest(t_logical, p, "vitals", rng.standard_normal(
                (vitals_ch, int(vitals_hz))).astype(np.float32))
            ref = di.close_window(p, t_logical + 1.0,
                                  extra={"qid": qid})
            qid += 1
            submit_ref(p, ref)
        maybe_flood()      # a late-scheduled backpressure episode must
        #                    still be exercised after the main trace
        t_logical += 1.0
        check_recoveries()
        time.sleep(window_wall_s)

    srv.drain(timeout=30.0)
    check_recoveries()
    stats = srv.stop()
    ctl_ok = ctl.stop() if ctl is not None else True
    leaked = sorted({t.name for t in threading.enumerate()
                     if t.is_alive() and t.name.startswith("repro-")})

    # ---------------------------------------------------- invariants
    results = []
    while True:
        batch = srv.results()
        if not batch:
            break
        results.extend(batch)
    n_real = sum(1 for _, s, _, _ in results if s == s)
    n_nan = sum(1 for _, s, _, _ in results if s != s)
    conservation_ok = (stats.served + stats.shed == submitted
                       and len(results) == stats.served
                       and n_real + n_nan == stats.served
                       and n_nan == stats.failed)

    # fault-free oracle: rescore each logged flush (same windows, same
    # member selection, unsharded, no faults) and demand bitwise
    # equality for every query that DELIVERED a real score
    qid_flush: Dict[int, tuple] = {}
    with log_lock:
        for key, qids, scores in flush_log:
            for q, s in zip(qids, scores):
                qid_flush[q] = (key, qids, s)
    oracle_cache: Dict[tuple, EnsembleService] = {}
    oracle_scores: Dict[tuple, Dict[int, float]] = {}
    bitwise_ok = True
    n_checked = 0
    for patient, score, _lat, ref in results:
        if score != score:
            continue                      # NaN-failed: conservation's job
        q = ref.extra["qid"]
        ent = qid_flush.get(q)
        if ent is None:
            bitwise_ok = False
            break
        key, qids, logged = ent
        flush_id = (key, tuple(qids))
        if flush_id not in oracle_scores:
            svc = oracle_cache.get(key)
            if svc is None:
                svc = EnsembleService([pool[i] for i in key])
                oracle_cache[key] = svc
            want = svc.predict_batch(
                [{"ecg": oracle_windows[x]} for x in qids])
            oracle_scores[flush_id] = dict(zip(qids, want))
        ok = (score == logged == oracle_scores[flush_id][q])
        bitwise_ok = bitwise_ok and ok
        n_checked += 1
        if not ok:
            break

    recovery_s = [fault_recovery[i] for i in range(len(schedule))]
    recovery_ok = all(r is not None and r <= recovery_slo_s
                      for r in recovery_s)
    no_leaked = (not leaked) and (not srv.leaked) and ctl_ok

    out = {
        "n_devices": n_devices, "n_patients": n_patients,
        "windows_per_patient": windows_per_patient, "seed": seed,
        "slo_s": slo, "deadline_s": deadline,
        "schedule": [ev.to_dict() for ev in schedule],
        "submitted": submitted, "ring_rejected": ring_rejected,
        "served": stats.served, "served_real": n_real,
        "failed": stats.failed, "rejected": stats.shed,
        "rejected_by_tier": {str(k): v
                             for k, v in stats.rejected.items()},
        "critical_rejected": stats.rejected.get("critical", 0),
        "stalls": stats.stalls,
        "quarantined": [str(d) for d in swapper.quarantined],
        "recoveries": plane.recoveries,
        "controller": {
            "enabled": use_controller,
            "actions": [[round(t, 3), d.name] for t, d in ctl.log]
            if ctl is not None else [],
            "n_recomposes": ctl.n_recomposes if ctl is not None else 0},
        "faults": [{**ev.to_dict(),
                    "recovery_s": recovery_s[i]}
                   for i, ev in enumerate(schedule)],
        "p50_ms": stats.p(50) * 1e3, "p99_ms": stats.p(99) * 1e3,
        "conservation_ok": bool(conservation_ok),
        "bitwise_ok": bool(bitwise_ok), "n_bitwise_checked": n_checked,
        "recovery_ok": bool(recovery_ok),
        "no_leaked_threads": bool(no_leaked),
        "leaked_threads": leaked + list(srv.leaked)
        + (list(ctl.leaked) if ctl is not None else []),
    }
    # span-trace digest (optional key — not part of the gated schema):
    # under chaos the by_status mix is the interesting bit, e.g. the
    # watchdog-killed co-batch shows up as status="watchdog" spans
    att = tracer.attribution()
    out["obs"] = {
        "n_spans": att["n_spans"], "by_status": att["by_status"],
        "coverage": round(att["coverage"], 4),
        "stage_ms": {k: round(1e3 * v / max(att["n_spans"], 1), 3)
                     for k, v in att["stage_seconds"].items()},
    }
    if verbose:
        print(f"\nchaos soak ({n_devices} device(s), "
              f"{n_patients} patients x {windows_per_patient} windows):")
        print(f"  submitted {submitted}  real {n_real}  failed "
              f"{stats.failed}  rejected {stats.shed} "
              f"(ring {ring_rejected})  stalls {stats.stalls}  "
              f"quarantined {out['quarantined']}")
        print(f"  conservation {conservation_ok}  bitwise {bitwise_ok} "
              f"({n_checked} checked)  recovery {recovery_ok} "
              f"{[None if r is None else round(r, 2) for r in recovery_s]}"
              f"  no_leaked_threads {no_leaked}")
    return out


# ------------------------------------------------- slot-engine lane
SLOT_LANE_KEYS = (
    "n_devices", "seed", "slo_s", "slot_wait_s", "ticker_deadline_s",
    "schedule", "trace", "n_slots_initial", "n_slots_final",
    "spad_final", "submitted", "ring_rejected", "served", "served_real",
    "failed", "rejected", "ticks", "tick_skips", "tick_faults",
    "tick_aborts", "rebinds", "ticker_respawns", "watchdog_events",
    "grows", "admits", "discharges", "stale_ticks", "quarantined",
    "recoveries", "controller", "faults", "p50_ms", "p99_ms",
    "conservation_ok", "bitwise_ok", "n_bitwise_checked",
    "recovery_ok", "no_leaked_threads", "leaked_threads",
)
SLOT_FAULT_KINDS_REQUIRED = ("device_loss", "ticker_stall",
                             "backpressure")


def run_slot_chaos(n_beds: int = 5, n_steps: int = 240,
                   step_wall_s: float = 0.05,
                   step_logical_s: float = 120.0,
                   input_len: int = 250, n_devices: int = 1,
                   seed: int = 0, slo: float = 2.0,
                   slot_wait: float = 0.5,
                   ticker_deadline: float = 0.35,
                   tick_interval: float = 0.02,
                   max_queue: int = 32,
                   lam_admit: float = 0.05,
                   los_median_steps: float = 60.0,
                   recovery_slo_s: Optional[float] = None,
                   schedule=None, verbose: bool = True) -> Dict:
    """One slot-engine soak lane (see module doc).  The driver clock is
    COMPRESSED: each step is ``step_logical_s`` of ICU time but only
    ``step_wall_s`` of wall clock, so a default full run replays
    ``n_steps * step_logical_s / 3600`` logical hours of cohort churn
    in under a minute.  Returns the result dict (SLOT_LANE_KEYS)."""
    import jax

    if recovery_slo_s is None:
        # recovery here is queue-drain bound: queries queued during an
        # outage each burn up to ``slot_wait`` before NaN-retiring, and
        # a permanent loss additionally restages + rebinds (the moved
        # buckets recompile) before fresh ticks can stamp real scores
        recovery_slo_s = 45.0 if n_devices >= 2 else 15.0

    from repro.configs.ecg_zoo import ECG_LEADS, zoo_specs
    from repro.control.faults import (FaultPlane, slot_compound_schedule,
                                      wire_controller)
    from repro.control.swap import HotSwapper
    from repro.control.telemetry import SloTelemetry
    from repro.models.ecg_resnext import init_ecg
    from repro.obs.spans import SpanRecorder
    from repro.serving.aggregator import DeviceIngest, ModalitySpec
    from repro.serving.pipeline import EnsembleService, ZooMember
    from repro.serving.server import EnsembleServer
    from repro.serving.slots import SlotEngine, TickLadder

    if n_devices > jax.device_count():
        raise ValueError(f"lane needs {n_devices} devices, JAX has "
                         f"{jax.device_count()}")
    rng = np.random.default_rng(seed)
    specs = zoo_specs(reduced=True, input_len=input_len)
    pool = [ZooMember(s, init_ecg(jax.random.PRNGKey(i), s))
            for i, s in enumerate(specs)]
    rich = np.ones(len(pool), np.int8)

    swapper = HotSwapper(pool, rich, n_devices=n_devices,
                         warmup_batch_sizes=(8,))
    # single-rung MEMBER ladder: a controller shed falls through to the
    # aux TickLadder (freshness degrades before accuracy) and a
    # failover restage keeps the composition rebind-compatible
    swapper.set_ladder([rich])
    telemetry = SloTelemetry(slo_seconds=slo, window_seconds=3.0)

    di = DeviceIngest([ModalitySpec("ecg", float(input_len), ECG_LEADS)],
                      n_beds, window_seconds=1.0, capacity_windows=4.0)
    eng = SlotEngine(swapper.facade.current, di)
    # respawned ticker generations skip a held tick lock FAST, so they
    # beat well inside the watchdog deadline during a long failover
    # (no respawn pile-up behind a recovering tick)
    eng.tick_lock_timeout = 0.2
    n_slots_initial = eng.n_slots

    tracer = SpanRecorder()
    srv = EnsembleServer(engine="slots", slot_engine=eng, n_workers=4,
                         slo_seconds=slo, max_queue=max_queue,
                         tick_interval=tick_interval,
                         slot_wait_timeout=slot_wait,
                         ticker_deadline_seconds=ticker_deadline,
                         telemetry=telemetry, tracer=tracer)
    ladder = TickLadder(srv.ticker,
                        intervals=(4 * tick_interval, 2 * tick_interval,
                                   tick_interval))
    ctl = wire_controller(telemetry, swapper, aux_ladder=ladder,
                          period_seconds=0.2)

    schedule = schedule if schedule is not None \
        else slot_compound_schedule(n_devices, seed=seed)
    plane = FaultPlane(schedule, seed=seed)

    # the tick-report oracle log: every (slot, close-version, pad-rung)
    # a tick ever STAMPED, with its combined score.  The same key must
    # score identically every time it is stamped (same window, same
    # members — placement is bitwise-irrelevant even across a rebind).
    rec: Dict[tuple, float] = {}
    rec_lock = threading.Lock()
    restamp_consistent = [True]
    stale_ticks = [0]           # occupied slots skipped on ring overrun

    def on_tick(r):
        stale_ticks[0] += r.n_stale
        if r.stamped is None or not len(r.stamped):
            return
        with rec_lock:
            for s, v, sc in zip(r.stamped, r.versions, r.scores):
                key = (int(s), int(v), int(r.spad))
                prev = rec.get(key)
                if prev is None:
                    rec[key] = float(sc)
                elif prev != float(sc):
                    restamp_consistent[0] = False

    eng.on_tick = on_tick

    eng.warm()
    # pre-warm the NEXT pad rung too: the census provably outgrows its
    # initial slots mid-soak, and the bucket recompile at the grown
    # rung should not masquerade as fault-recovery latency
    swapper.facade.current.warmup(
        batch_sizes=(2 * eng._Spad,))
    srv.start()
    # arm AFTER warmup (schedule clock starts with traffic), then wire
    # tick-path recovery: ticker-stall injection, device-loss
    # quarantine + TickLadder shed + rebind, flush-quarantine rebinds
    plane.arm(swapper)
    plane.protect_engine(eng, swapper, ticker=srv.ticker,
                         tick_ladder=ladder)

    # ------------------------------------------------ cohort trace
    beds: Dict[int, Dict] = {}          # slot -> bed state
    row_t: Dict[int, float] = {}        # slot -> ring close clock (kept
    #                                     across occupants: ring time is
    #                                     monotonic per ROW, not per bed)
    verc: Dict[int, int] = {}           # slot -> close version counter
    snaps: Dict[tuple, np.ndarray] = {}  # (slot, version) -> ecg window
    zero_win = np.zeros((ECG_LEADS, input_len), np.float32)
    qid = 0
    submitted = 0
    ring_rejected = 0
    n_admissions = 0

    def admit_bed(step: int) -> None:
        nonlocal n_admissions
        slot = eng.acquire_slot()       # lowest free, grows the census
        esc = bool(rng.random() < 0.25)
        los = max(3, int(rng.lognormal(np.log(los_median_steps), 0.5)))
        beds[slot] = {"esc": esc, "period": 1 if esc else 4,
                      "next": step + 1, "until": step + los}
        n_admissions += 1

    def close_and_submit(slot: int, fresh: bool = True) -> None:
        """One closed observation window -> one slot query.  The window
        is snapshotted AT CLOSE keyed by (slot, close version) — what a
        timely tick gathers — for the tick-report oracle.  ``fresh=
        False`` (the flood path) re-closes an unchanged ring: valid=0,
        the gather yields the all-zeros dropout window."""
        nonlocal qid, submitted
        t_row = row_t.get(slot, 0.0)
        if fresh:
            sig = rng.standard_normal(
                (ECG_LEADS, input_len)).astype(np.float32)
            di.ingest(t_row, slot, "ecg", sig)
            t_row += 1.0
            row_t[slot] = t_row
        ref = di.close_window(slot, t_row, extra={"qid": qid})
        qid += 1
        v = verc.get(slot, 0) + 1       # mirrors SlotEngine's close
        verc[slot] = v                  # version (one update per close)
        if all(x == 0 for x in ref.valid.values()):
            snaps[(slot, v)] = zero_win
        else:
            snaps[(slot, v)] = ref.host_window("ecg")
        submitted += 1
        srv.submit(slot, ref)

    def maybe_flood() -> None:
        """During a backpressure episode, overrun the bounded queue
        with re-closes of unchanged rings (cheap degenerate queries the
        oracle rescores like any other) — the ShedQueue must shed."""
        targets = [s for s in beds if s in row_t]
        if not plane.backpressure_active() or not targets:
            return
        # one invocation must overrun the queue BY ITSELF: the episode
        # can overlap as little as one driver step when a compile pause
        # stretches the step it lands on
        for _ in range(max(2, (2 * max_queue + 8) // len(targets))):
            for s in targets:
                close_and_submit(s, fresh=False)

    fault_recovery: Dict[int, Optional[float]] = {
        i: None for i in range(len(schedule))}

    def check_recoveries() -> None:
        t_now = plane.now()
        for i, ev in enumerate(schedule):
            if fault_recovery[i] is not None:
                continue
            end = ev.t + ev.duration
            if t_now <= end + 0.05:
                continue
            snap = telemetry.snapshot(
                since=plane._armed_at + end + slot_wait)
            if snap.n_served - snap.n_failed >= 2 and snap.p99 <= slo:
                fault_recovery[i] = t_now - end

    for _ in range(n_beds):
        admit_bed(0)

    for step in range(n_steps):
        for slot in [s for s, b in beds.items() if b["until"] <= step]:
            eng.discharge(slot)
            del beds[slot]
        # Poisson arrivals, plus a deterministic two-bed escalation
        # wing early on so the census provably outgrows n_slots on
        # every seed
        n_new = int(rng.poisson(lam_admit)) + (2 if step == 5 else 0)
        for _ in range(n_new):
            admit_bed(step)
        for slot, b in list(beds.items()):
            if step >= b["next"]:
                if di.headroom(slot) < 1.0:
                    ring_rejected += 1
                else:
                    close_and_submit(slot)
                b["next"] = step + b["period"]
        maybe_flood()
        check_recoveries()
        time.sleep(step_wall_s)

    # keep a light pulse flowing until the schedule has fully fired
    # and every fault's recovery window is measured
    t_wait = time.monotonic() + recovery_slo_s + 2.0
    while (not plane.done()
           or any(v is None for v in fault_recovery.values())) \
            and time.monotonic() < t_wait:
        if not beds:
            admit_bed(n_steps)
        for slot in list(beds)[:2]:
            if srv.q.qsize() >= max(2, max_queue // 2):
                break       # polite pulse: must not re-trigger shedding
            if di.headroom(slot) < 1.0:
                ring_rejected += 1
                continue
            close_and_submit(slot)
        maybe_flood()
        check_recoveries()
        time.sleep(step_wall_s)

    srv.drain(timeout=30.0)
    check_recoveries()
    stats = srv.stop()
    ctl_ok = ctl.stop()
    leaked = sorted({t.name for t in threading.enumerate()
                     if t.is_alive() and t.name.startswith("repro-")})

    # ---------------------------------------------------- invariants
    results = []
    while True:
        batch = srv.results()
        if not batch:
            break
        results.extend(batch)
    n_real = sum(1 for _, s, _, _ in results if s == s)
    n_nan = sum(1 for _, s, _, _ in results if s != s)
    conservation_ok = (stats.served + stats.shed == submitted
                       and len(results) == stats.served
                       and n_real + n_nan == stats.served
                       and n_nan == stats.failed)

    # tick-report oracle: re-score every stamped (slot, version) with
    # an UNSHARDED fault-free service in batches of exactly the pad
    # rung the tick dispatched at (bucket rows are independent, so
    # zero-window pad rows cannot perturb the real rows)
    oracle = EnsembleService(pool)
    bitwise_ok = restamp_consistent[0]
    n_checked = 0
    with rec_lock:
        entries = sorted(rec.items())
    by_spad: Dict[int, List] = {}
    for (s, v, spad), sc in entries:
        by_spad.setdefault(spad, []).append((s, v, sc))
    for spad, ents in sorted(by_spad.items()):
        for i in range(0, len(ents), spad):
            chunk = ents[i:i + spad]
            wins = []
            for s, v, _sc in chunk:
                w = snaps.get((s, v))
                if w is None:           # stamped a version the driver
                    bitwise_ok = False  # never closed: impossible
                    w = zero_win
                wins.append(w)
            while len(wins) < spad:
                wins.append(zero_win)
            want = oracle.predict_batch([{"ecg": w} for w in wins])
            for (s, v, sc), wsc in zip(chunk, want):
                bitwise_ok = bitwise_ok and (sc == wsc)
                n_checked += 1

    # ...and every REAL score a query served must be one of its slot's
    # stamped scores (reads come from the mirror, the mirror only ever
    # holds stamped ticks — NaN-or-stale during gaps, never invented)
    slot_scores: Dict[int, set] = {}
    for (s, _v, _spad), sc in entries:
        slot_scores.setdefault(s, set()).add(sc)
    for patient, score, _lat, _ref in results:
        if score == score and score not in slot_scores.get(patient, ()):
            bitwise_ok = False

    recovery_s = [fault_recovery[i] for i in range(len(schedule))]
    recovery_ok = all(r is not None and r <= recovery_slo_s
                      for r in recovery_s)
    no_leaked = (not leaked) and (not srv.leaked) and ctl_ok

    out = {
        "n_devices": n_devices, "seed": seed, "slo_s": slo,
        "slot_wait_s": slot_wait, "ticker_deadline_s": ticker_deadline,
        "schedule": [ev.to_dict() for ev in schedule],
        "trace": {
            "n_beds": n_beds, "n_steps": n_steps,
            "step_wall_s": step_wall_s,
            "step_logical_s": step_logical_s,
            "sim_hours": round(n_steps * step_logical_s / 3600.0, 2),
            "compression": round(step_logical_s / step_wall_s, 1),
            "lam_admit": lam_admit,
            "los_median_steps": los_median_steps,
            "admissions": n_admissions},
        "n_slots_initial": n_slots_initial,
        "n_slots_final": eng.n_slots, "spad_final": eng._Spad,
        "submitted": submitted, "ring_rejected": ring_rejected,
        "served": stats.served, "served_real": n_real,
        "failed": stats.failed, "rejected": stats.shed,
        "ticks": eng.tick_count, "tick_skips": eng.n_tick_skips,
        "tick_faults": eng.n_tick_faults,
        "tick_aborts": eng.n_tick_aborts, "rebinds": eng.n_rebinds,
        "ticker_respawns": srv.ticker.n_respawns,
        "watchdog_events": list(srv.ticker_watchdog.events),
        "grows": eng.n_grows, "admits": eng.n_admits,
        "discharges": eng.n_discharges,
        "stale_ticks": stale_ticks[0],
        "quarantined": [str(d) for d in swapper.quarantined],
        "recoveries": plane.recoveries,
        "controller": {
            "actions": [[round(t, 3), d.name] for t, d in ctl.log],
            "n_recomposes": ctl.n_recomposes},
        "faults": [{**ev.to_dict(), "recovery_s": recovery_s[i]}
                   for i, ev in enumerate(schedule)],
        "p50_ms": stats.p(50) * 1e3, "p99_ms": stats.p(99) * 1e3,
        "conservation_ok": bool(conservation_ok),
        "bitwise_ok": bool(bitwise_ok), "n_bitwise_checked": n_checked,
        "recovery_ok": bool(recovery_ok),
        "no_leaked_threads": bool(no_leaked),
        "leaked_threads": leaked + list(srv.leaked)
        + list(ctl.leaked),
    }
    att = tracer.attribution()
    out["obs"] = {"n_spans": att["n_spans"],
                  "by_status": att["by_status"],
                  "coverage": round(att["coverage"], 4)}
    if verbose:
        print(f"\nslot chaos soak ({n_devices} device(s), "
              f"{out['trace']['sim_hours']}h logical / "
              f"{n_steps * step_wall_s:.0f}s wall):")
        print(f"  submitted {submitted}  real {n_real}  failed "
              f"{stats.failed}  rejected {stats.shed}  slots "
              f"{n_slots_initial}->{eng.n_slots}  ticks "
              f"{eng.tick_count} (faults {eng.n_tick_faults} aborts "
              f"{eng.n_tick_aborts})  respawns "
              f"{srv.ticker.n_respawns}  rebinds {eng.n_rebinds}  "
              f"quarantined {out['quarantined']}")
        print(f"  conservation {conservation_ok}  bitwise {bitwise_ok} "
              f"({n_checked} checked)  recovery {recovery_ok} "
              f"{[None if r is None else round(r, 2) for r in recovery_s]}"
              f"  no_leaked_threads {no_leaked}")
    return out


# ------------------------------------------------------------- schema
def check_chaos_schema(lane: Dict) -> None:
    """Gate one lane's result: every tracked key present, all four
    whole-run invariants TRUE, and the schedule actually contained at
    least one fault of each required kind."""
    for k in CHAOS_LANE_KEYS:
        assert k in lane, f"missing chaos lane key: {k}"
    kinds = {ev["kind"] for ev in lane["schedule"]}
    for k in FAULT_KINDS_REQUIRED:
        assert k in kinds, f"schedule missing fault kind {k}"
    for inv in ("conservation_ok", "bitwise_ok", "recovery_ok",
                "no_leaked_threads"):
        assert lane[inv] is True, f"invariant failed: {inv} ({lane})"
    assert lane["n_bitwise_checked"] > 0, "oracle checked nothing"
    assert lane["stalls"] >= 1, "worker stall never detected"
    assert lane["rejected"] >= 1, "backpressure never shed anything"
    assert lane["critical_rejected"] == 0, \
        "a critical query was rejected"


def check_slot_lane_schema(lane: Dict) -> None:
    """Gate one slot-engine lane: every tracked key, all four
    invariants, the compound fault kinds actually scheduled, and the
    chaos machinery provably EXERCISED (watchdog respawned, ticks
    faulted, census grew past its initial slots, queue shed)."""
    for k in SLOT_LANE_KEYS:
        assert k in lane, f"missing slot lane key: {k}"
    kinds = {ev["kind"] for ev in lane["schedule"]}
    for k in SLOT_FAULT_KINDS_REQUIRED:
        assert k in kinds, f"slot schedule missing fault kind {k}"
    for inv in ("conservation_ok", "bitwise_ok", "recovery_ok",
                "no_leaked_threads"):
        assert lane[inv] is True, f"slot invariant failed: {inv} ({lane})"
    assert lane["n_bitwise_checked"] > 0, "slot oracle checked nothing"
    assert lane["ticker_respawns"] >= 1, \
        "ticker watchdog never respawned through the stall cascade"
    assert lane["tick_faults"] >= 1, \
        "no tick ever hit an injected device loss"
    assert lane["grows"] >= 1 \
        and lane["n_slots_final"] > lane["n_slots_initial"], \
        "census never outgrew the initial slot count"
    assert lane["rejected"] >= 1, "backpressure never shed anything"


def check_chaos_file(path: str = BENCH_JSON) -> None:
    """CI gate on the committed BENCH_chaos.json: flush lanes AND slot
    lanes present and individually valid."""
    with open(path) as f:
        data = json.load(f)
    for lane_name in ("single_device", "forced_8_device"):
        assert lane_name in data, f"missing lane {lane_name}"
        check_chaos_schema(data[lane_name])
    assert data["forced_8_device"]["n_devices"] >= 2
    assert data["forced_8_device"]["quarantined"], \
        "multi-device lane never quarantined the lost device"
    assert "slots" in data, "missing slot-engine lanes"
    for lane_name in ("single_device", "forced_8_device"):
        assert lane_name in data["slots"], \
            f"missing slot lane {lane_name}"
        check_slot_lane_schema(data["slots"][lane_name])
    s8 = data["slots"]["forced_8_device"]
    assert s8["n_devices"] >= 2, "slot lane ran single-device"
    assert s8["quarantined"], \
        "slot lane never quarantined the lost device"
    assert s8["rebinds"] >= 1, \
        "slot engine never rebound onto the survivor facade"
    print(f"chaos schema OK ({path})")


# ----------------------------------------------------- lane dispatch
def _child_lane(n_patients: int, windows: int, seed: int = 0,
                lane: str = "flush", devices: int = 1) -> Dict:
    """Run one lane in a child process and return its result dict.
    Every lane runs in a child, one after the other, so the parent
    never imports JAX: a parent holding the accelerator would leave
    the child without one.  ``devices > 1`` is a forced-HOST-device
    lane (the XLA device count is fixed at JAX start-up): the child
    runs on the CPU backend with that many host devices.  ``lane``
    picks the flush soak or the slot-engine soak; for the slot lane
    ``n_patients``/``windows`` mean initial beds / driver steps."""
    import tempfile
    env = dict(os.environ)
    if devices > 1:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count"
                            f"={devices}")
        env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTEST_CURRENT_TEST", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        out_path = f.name
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--emit",
             out_path, "--lane", lane, "--devices", str(devices),
             "--n-patients", str(n_patients),
             "--windows", str(windows), "--seed", str(seed)],
            cwd=repo, env=env, capture_output=True, text=True,
            timeout=1200)
        if r.returncode != 0:
            raise RuntimeError(f"{lane} lane on {devices} device(s) "
                               f"failed:\n" + (r.stdout or "")[-2000:]
                               + (r.stderr or "")[-4000:])
        with open(out_path) as f:
            return json.load(f)
    finally:
        os.unlink(out_path)


def _merge_bench_json(updates: Dict) -> None:
    merged = {}
    if os.path.exists(BENCH_JSON):
        with open(BENCH_JSON) as f:
            merged = json.load(f)
    merged.update(updates)
    with open(BENCH_JSON, "w") as f:
        json.dump(merged, f, indent=2)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-trace CI invocation: both lanes, schema "
                         "gates, writes nothing")
    ap.add_argument("--emit", default=None,
                    help="run ONE lane in this process and write its "
                         "result dict to this path (subprocess entry)")
    ap.add_argument("--lane", choices=("flush", "slots"),
                    default="flush",
                    help="which soak --emit runs (flush path or the "
                         "continuous slot engine)")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--n-patients", type=int, default=None)
    ap.add_argument("--windows", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    SLOT_SMOKE_STEPS = 150
    SLOT_FULL_STEPS = 900

    if args.emit:
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
        if args.lane == "slots":
            out = run_slot_chaos(n_beds=args.n_patients or 5,
                                 n_steps=args.windows or SLOT_FULL_STEPS,
                                 n_devices=args.devices, seed=args.seed)
            check_slot_lane_schema(out)
        else:
            out = run_chaos(n_patients=args.n_patients or 6,
                            windows_per_patient=args.windows or 10,
                            n_devices=args.devices, seed=args.seed)
            check_chaos_schema(out)
        with open(args.emit, "w") as f:
            json.dump(out, f, indent=2)
    else:
        n_pat = args.n_patients or (4 if args.smoke else 6)
        n_win = args.windows or (8 if args.smoke else 10)
        slot_steps = SLOT_SMOKE_STEPS if args.smoke else SLOT_FULL_STEPS
        lane1 = _child_lane(n_pat, n_win, seed=args.seed)
        check_chaos_schema(lane1)
        lane8 = _child_lane(n_pat, n_win, seed=args.seed,
                            devices=N_FORCED)
        check_chaos_schema(lane8)
        slane1 = _child_lane(5, slot_steps, seed=args.seed, lane="slots")
        check_slot_lane_schema(slane1)
        slane8 = _child_lane(5, slot_steps, seed=args.seed, lane="slots",
                             devices=N_FORCED)
        check_slot_lane_schema(slane8)
        if args.smoke:
            assert lane8["n_devices"] >= 2 and lane8["quarantined"]
            assert slane8["n_devices"] >= 2 and slane8["quarantined"] \
                and slane8["rebinds"] >= 1
            print("chaos smoke OK (flush + slot lanes, single-device + "
                  "forced-8-device)")
        else:
            # the committed, replayable fault traces the soaks survived
            # (FaultPlane.to_json / from_json round-trips these)
            from repro.control.faults import (FaultPlane,
                                              slot_compound_schedule)
            tdir = os.path.join(os.path.dirname(__file__), "traces")
            os.makedirs(tdir, exist_ok=True)
            for nd, fname in ((1, "slot_compound_1dev.json"),
                              (N_FORCED, "slot_compound_8dev.json")):
                FaultPlane(slot_compound_schedule(nd, seed=args.seed),
                           seed=args.seed).to_json(
                    os.path.join(tdir, fname))
            _merge_bench_json({"single_device": lane1,
                               "forced_8_device": lane8,
                               "slots": {"single_device": slane1,
                                         "forced_8_device": slane8}})
            check_chaos_file()
