"""Chaos-hardening invariants: the deterministic fault plane, the
bounded priority queue, the watchdog, and device-loss failover.

Units here run against toy handlers (no zoo training) so the fault
semantics — exactly-once retirement, conservation under eviction,
heartbeat vs. silent stall, minimal-move failover plans — are checked
fast and deterministically; the end-to-end soak (real zoo, real
ingest, bitwise oracle) lives in ``benchmarks/chaos_bench.py`` and its
smoke test below.
"""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax

from repro.control.faults import (DeviceLostError, FaultEvent, FaultPlane)
from repro.serving.queues import ShedQueue
from repro.serving.server import EnsembleServer

N_FORCED = 8
IN_LANE = jax.device_count() >= N_FORCED

needs_devices = pytest.mark.skipif(
    not IN_LANE,
    reason=f"needs {N_FORCED} forced host devices (CI lane or the "
           f"subprocess wrapper below)")
multi_device = pytest.mark.multi_device


# ---------------------------------------------------------- FaultPlane
class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_fault_event_rejects_unknown_kind():
    with pytest.raises(ValueError):
        FaultEvent(0.0, "meteor_strike")


def test_fault_plane_fires_in_schedule_order():
    clk = FakeClock()
    plane = FaultPlane([FaultEvent(2.0, "worker_stall", duration=0.3),
                        FaultEvent(1.0, "backpressure", duration=0.5)],
                       clock=clk)
    plane.arm(devices=[object()])
    assert not plane.done()
    assert plane.stall_pending() == 0.0       # nothing due yet
    clk.t = 1.1
    assert plane.backpressure_active()
    assert plane.stall_pending() == 0.0
    clk.t = 1.7
    assert not plane.backpressure_active()    # episode over
    clk.t = 2.1
    assert plane.stall_pending() == 0.3
    assert plane.stall_pending() == 0.0       # token consumed exactly once
    assert plane.done()
    assert [ev.kind for _, ev in plane.fired] == ["backpressure",
                                                  "worker_stall"]


def test_fault_plane_guard_raises_for_lost_device_only():
    clk = FakeClock()
    d0, d1 = object(), object()
    plane = FaultPlane([FaultEvent(1.0, "device_loss", target=1)],
                       clock=clk)
    plane.arm(devices=[d0, d1])
    plane.guard(d0)
    plane.guard(d1)                           # not lost yet
    clk.t = 1.0
    plane.guard(d0)                           # survivor stays fine
    with pytest.raises(DeviceLostError) as ei:
        plane.guard(d1)
    assert ei.value.index == 1
    assert ei.value.device is d1


def test_fault_plane_transient_loss_expires():
    clk = FakeClock()
    d0 = object()
    plane = FaultPlane(
        [FaultEvent(1.0, "device_loss", target=0, duration=0.5)],
        clock=clk)
    plane.arm(devices=[d0])
    clk.t = 1.2
    with pytest.raises(DeviceLostError):
        plane.guard(d0)                       # None also targets idx 0
    clk.t = 1.6
    plane.guard(d0)                           # device "rebooted"
    assert [r["kind"] for r in plane.recoveries] == ["device_restored"]


def test_protect_transient_loss_serves_late_and_heartbeats():
    clk = FakeClock()
    d0 = object()
    plane = FaultPlane(
        [FaultEvent(0.0, "device_loss", target=0, duration=0.2)],
        clock=clk)
    plane.arm(devices=[d0])
    calls = {"n": 0}
    beats = []

    def score(windows):
        calls["n"] += 1
        clk.t += 0.06                   # wall time passes per attempt
        plane.guard(d0)
        return [1.0] * len(windows)

    guarded = plane.protect(score, heartbeat=lambda: beats.append(1)
                            or True, retry_sleep=0.0)
    assert guarded([{}, {}]) == [1.0, 1.0]
    assert calls["n"] > 1               # really retried through the loss
    assert beats                        # and heart-beat while waiting


def test_protect_gives_up_after_budget():
    clk = FakeClock()
    d0 = object()
    plane = FaultPlane(
        [FaultEvent(0.0, "device_loss", target=0, duration=0.0)],
        clock=clk)
    plane.arm(devices=[d0])             # permanent, no swapper: hopeless

    calls = {"n": 0}

    def score(windows):
        calls["n"] += 1
        clk.t += 0.02                   # injected time passes per try
        plane.guard(d0)
        return [1.0]

    guarded = plane.protect(score, retry_budget_s=0.05, retry_sleep=0.0)
    with pytest.raises(DeviceLostError):
        guarded([{}])
    assert 2 <= calls["n"] <= 10        # retried, then gave up on the
    #                                     INJECTED clock's budget


def test_protect_abandoned_cobatch_stops_retrying():
    """heartbeat() returning False (watchdog gave up) must end the
    retry loop immediately — the scores would be discarded anyway."""
    clk = FakeClock()
    d0 = object()
    plane = FaultPlane(
        [FaultEvent(0.0, "device_loss", target=0, duration=1.0)],
        clock=clk)
    plane.arm(devices=[d0])

    def score(windows):
        plane.guard(d0)
        return [1.0]

    guarded = plane.protect(score, heartbeat=lambda: False,
                            retry_budget_s=30.0, retry_sleep=0.0)
    t0 = time.monotonic()
    with pytest.raises(DeviceLostError):
        guarded([{}])
    assert time.monotonic() - t0 < 5.0


# ----------------------------------------------------------- ShedQueue
def test_shed_queue_bounds_unfinished_not_just_queued():
    import queue as _queue
    q = ShedQueue(maxsize=2)
    q.put_nowait("a")
    q.put_nowait("b")
    with pytest.raises(_queue.Full):
        q.put_nowait("c")
    q.get(timeout=0.1)                  # popped but NOT task_done yet:
    with pytest.raises(_queue.Full):    # in-flight still holds the slot
        q.put_nowait("c")
    q.task_done()
    q.put_nowait("c")                   # slot released


def test_shed_queue_eviction_priority_and_order():
    q = ShedQueue(maxsize=3)
    q.put_nowait("s1", priority=0.0, tag="stable")
    q.put_nowait("c1", priority=2.0, tag="critical")
    q.put_nowait("s2", priority=0.0, tag="stable")
    # full; a critical newcomer evicts the OLDEST strictly-lower item
    ok, victim = q.put_evicting("c2", priority=2.0, tag="critical")
    assert ok and victim == ("s1", "stable")
    assert q.qsize() == 3
    ok, victim = q.put_evicting("c3", priority=2.0, tag="critical")
    assert ok and victim == ("s2", "stable")       # next-oldest stable
    # all-critical queue: equal priority is NOT strictly lower — no
    # victim, newcomer not admitted
    ok, victim = q.put_evicting("c4", priority=2.0, tag="critical")
    assert not ok and victim is None
    assert [q.get(timeout=0.1) for _ in range(3)] == ["c1", "c2", "c3"]


def test_shed_queue_eviction_conserves_unfinished():
    q = ShedQueue(maxsize=2)
    q.put_nowait("s1", priority=0.0)
    q.put_nowait("s2", priority=0.0)
    ok, victim = q.put_evicting("c1", priority=1.0)
    assert ok and victim is not None
    # the victim's slot transferred to the newcomer: still 2 unfinished
    assert q.unfinished_tasks == 2
    q.get(timeout=0.1), q.get(timeout=0.1)
    q.task_done(), q.task_done()
    assert q.unfinished_tasks == 0
    with pytest.raises(ValueError):
        q.task_done()                   # underflow must be loud


# ---------------------------------------------- watchdog + NaN-isolation
def test_watchdog_fails_stalled_cobatch_and_respawns():
    """A silently hung handler: the watchdog NaN-fails the in-flight
    co-batch within the deadline, respawns the worker, and later
    queries are served by the replacement — with exactly-once
    retirement (conservation) throughout."""
    stall_once = threading.Event()

    def batch_handler(windows):
        if not stall_once.is_set():
            stall_once.set()
            time.sleep(1.2)             # silent: no heartbeat
        return [1.0] * len(windows)

    srv = EnsembleServer(batch_handler=batch_handler, n_workers=1,
                         max_batch=2, max_wait_ms=1.0,
                         deadline_seconds=0.15,
                         watchdog_interval=0.01).start()
    srv.submit(0, {})
    time.sleep(0.5)                     # watchdog fires mid-stall
    for i in range(1, 5):
        srv.submit(i, {})
    stats = srv.stop()
    assert stats.served == 5
    assert stats.stalls >= 1
    assert stats.failed >= 1
    scores = {p: s for p, s, *_ in srv.results()}
    assert np.isnan(scores[0])          # the stalled co-batch: NaN
    assert all(scores[i] == 1.0 for i in range(1, 5))
    assert not srv.leaked               # replacement + stalled worker


def test_watchdog_survives_thread_ident_reuse(monkeypatch):
    """Regression: watchdog bookkeeping used to be keyed by
    ``threading.get_ident()``.  The OS reuses idents once a thread
    exits, so a replacement worker could inherit its stalled
    predecessor's ``_abandoned`` entry and silently DISCARD a healthy
    co-batch — the query never retired and conservation broke.  Force
    the worst case (every thread reports the SAME ident) and run a
    stall-then-healthy sequence: the healthy query must still retire
    with its real score."""
    import repro.serving.server as server_mod

    class _SameIdent:
        """``threading`` facade whose get_ident collides for everyone
        (deterministic stand-in for OS-level ident reuse)."""

        def __getattr__(self, name):
            if name == "get_ident":
                return lambda: 0xDEAD
            return getattr(threading, name)

    monkeypatch.setattr(server_mod, "threading", _SameIdent())
    stalled = threading.Event()
    release = threading.Event()

    def batch_handler(windows):
        if not stalled.is_set():
            stalled.set()
            release.wait(5.0)           # silent stall: no heartbeat
        return [1.0] * len(windows)

    srv = EnsembleServer(batch_handler=batch_handler, n_workers=1,
                         max_batch=1, max_wait_ms=0.5,
                         deadline_seconds=0.1,
                         watchdog_interval=0.01).start()
    srv.submit(0, {})                   # stalls worker 1
    deadline = time.monotonic() + 2.0
    while srv.stats.stalls < 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert srv.stats.stalls == 1        # watchdog fired, worker 2 up
    srv.submit(1, {})                   # healthy query on the new worker
    while srv.stats.served < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    # pre-fix: worker 2 shares worker 1's ident, finds itself in
    # _abandoned, discards the healthy scores and exits — served stays 1
    assert srv.stats.served == 2
    release.set()
    stats = srv.stop()
    assert stats.served == 2 and stats.failed == 1
    scores = {p: s for p, s, *_ in srv.results()}
    assert np.isnan(scores[0]) and scores[1] == 1.0
    assert not srv.leaked


def test_heartbeat_keeps_slow_recovery_alive():
    """A handler WAITING (and heart-beating) past the deadline is not a
    stall: the co-batch must be served late and REAL, the watchdog must
    not fire."""
    def batch_handler(windows):
        t_end = time.monotonic() + 0.5  # 'recovery' far past deadline
        while time.monotonic() < t_end:
            assert srv.heartbeat()
            time.sleep(0.02)
        return [1.0] * len(windows)

    srv = EnsembleServer(batch_handler=batch_handler, n_workers=1,
                         max_batch=2, max_wait_ms=1.0,
                         deadline_seconds=0.15,
                         watchdog_interval=0.01).start()
    srv.submit(0, {})
    stats = srv.stop()
    assert stats.served == 1
    assert stats.stalls == 0
    assert stats.failed == 0
    (_, score, *_), = srv.results()
    assert score == 1.0


def test_heartbeat_reports_abandonment():
    """If the handler only starts heart-beating AFTER the watchdog gave
    up, heartbeat() returns False — the late scores are discarded and
    the query has already been NaN-retired exactly once."""
    seen = []
    release = threading.Event()

    def batch_handler(windows):
        release.wait(timeout=5.0)       # silent past the deadline
        seen.append(srv.heartbeat())
        return [1.0] * len(windows)

    srv = EnsembleServer(batch_handler=batch_handler, n_workers=1,
                         max_batch=2, max_wait_ms=1.0,
                         deadline_seconds=0.1,
                         watchdog_interval=0.01).start()
    srv.submit(0, {})
    time.sleep(0.4)                     # watchdog abandons the co-batch
    release.set()
    stats = srv.stop()
    assert seen == [False]
    assert stats.served == 1 and stats.failed == 1
    (_, score, *_), = srv.results()
    assert np.isnan(score)


# ------------------------------------- stale/fresh co-batch isolation
def test_safe_batch_mixed_stale_fresh_cobatch(zoo_members):
    """Satellite: a STALE DeviceWindowRef co-batched with fresh ones —
    the flush raises on the stale ref, the NaN-retry isolates it, and
    every fresh co-batched query still scores bitwise-identically to
    the same window scored without the fault (the retry path scores
    survivors singly, so the oracle is the single-query flush)."""
    from repro.configs.ecg_zoo import ECG_LEADS
    from repro.serving.aggregator import DeviceIngest, ModalitySpec
    from repro.serving.pipeline import EnsembleService

    members = zoo_members[:3]
    L = members[0].spec.input_len
    svc = EnsembleService(members)
    di = DeviceIngest([ModalitySpec("ecg", float(L), ECG_LEADS)],
                      n_patients=3, window_seconds=1.0,
                      capacity_windows=2.0)
    rng = np.random.default_rng(0)
    refs, wins = [], []
    for p in range(3):
        sig = rng.standard_normal((ECG_LEADS, L)).astype(np.float32)
        di.ingest(float(p), p, "ecg", sig)
        refs.append(di.close_window(p, float(p) + 1.0))
        wins.append(sig)
    want = {p: svc.predict_batch([{"ecg": wins[p]}])[0] for p in (0, 2)}

    # age OUT patient 1's ref: stream enough fresh samples that the
    # ring guard must refuse the overwritten window
    cap = di.states["ecg"].buf.shape[-1]
    for _ in range(int(np.ceil(cap / L)) + 1):
        di.ingest(99.0, 1, "ecg",
                  rng.standard_normal((ECG_LEADS, L)).astype(np.float32))

    srv = EnsembleServer(batch_handler=svc.predict_batch, n_workers=1,
                         max_batch=4, max_wait_ms=50.0).start()
    for p, r in enumerate(refs):
        srv.submit(p, r)
    stats = srv.stop()
    assert stats.served == 3 and stats.failed == 1
    scores = {p: s for p, s, *_ in srv.results()}
    assert np.isnan(scores[1])          # stale: refused, never mis-scored
    assert scores[0] == want[0] and scores[2] == want[2]   # bitwise


# -------------------------------------------- priority backpressure
def test_priority_backpressure_critical_never_rejected():
    """Overrun a bounded server with stable-tier floods: sheds are
    stable-only, every critical admission succeeds (by eviction when
    full), and the rejection ledger conserves every submission."""
    release = threading.Event()

    def batch_handler(windows):
        release.wait(timeout=10.0)      # hold workers: queue must fill
        return [1.0] * len(windows)

    # few criticals relative to the queue bound: priority admission
    # must cover them all by evicting queued stables
    tier_of = lambda p: "critical" if p % 10 == 0 else "stable"
    srv = EnsembleServer(batch_handler=batch_handler, n_workers=1,
                         max_batch=2, max_wait_ms=1.0, max_queue=8,
                         tier_of=tier_of,
                         tier_priority={"critical": 2,
                                        "stable": 0}).start()
    critical_admitted = 0
    submitted = 0
    for p in list(range(30)):
        ok = srv.submit(p, {"p": p})
        submitted += 1
        if ok and tier_of(p) == "critical":
            critical_admitted += 1
    release.set()
    stats = srv.stop()
    assert stats.rejected.get("critical", 0) == 0
    assert critical_admitted == sum(1 for p in range(30)
                                    if tier_of(p) == "critical")
    assert stats.shed == stats.rejected.get("stable", 0) > 0
    # conservation across the whole ledger: every submit either served
    # or counted shed (an evicted victim is shed; its slot was reused)
    assert stats.served + stats.shed == submitted


# ------------------------------------------------- failover placement
def test_failover_placement_minimal_move():
    from repro.control.swap import HotSwapper
    from repro.serving.placement import Placement
    old = Placement(assignment=[[0, 1], [2], [3, 4]],
                    loads=[2.0, 5.0, 1.0])
    pl = HotSwapper._failover_placement(old, dead_slot=1)
    # survivors untouched, dead slot's members on the least-loaded
    assert pl.assignment == [[0, 1], [3, 4, 2]]
    assert pl.loads == [2.0, 6.0]
    assert pl.n_members == old.n_members
    # degenerate shapes fall back to full re-derivation
    assert HotSwapper._failover_placement(None, 0) is None
    assert HotSwapper._failover_placement(old, 7) is None
    assert HotSwapper._failover_placement(
        Placement(assignment=[[0]], loads=[1.0]), 0) is None


@multi_device
@needs_devices
def test_quarantine_failover_serves_bitwise(zoo_members):
    """Permanent device loss on the sharded lane: quarantine swaps the
    active selector onto the minimal-move survivor plan, the dead
    device leaves the pool, and post-failover scores stay bitwise equal
    to the unsharded reference."""
    from repro.control.swap import HotSwapper
    from repro.serving.pipeline import EnsembleService

    members = zoo_members
    sel = np.ones(len(members), np.int8)
    sw = HotSwapper(members, sel, n_devices=4,
                    warmup_batch_sizes=(1, 2))
    L = members[0].spec.input_len
    rng = np.random.default_rng(0)
    batch = [{"ecg": rng.standard_normal((3, L)).astype(np.float32)}
             for _ in range(2)]
    want = EnsembleService.for_selector(members, sel).predict_batch(batch)
    assert sw.facade.predict_batch(batch) == want

    dead = jax.devices()[1]
    old_gen = sw._devices_gen
    assert sw.quarantine_device(dead) is True
    assert dead not in (sw.devices or [])
    assert dead in sw.quarantined
    assert sw._devices_gen == old_gen + 1
    assert sw.facade.predict_batch(batch) == want      # bitwise across
    # second loss of the same device is a no-op refusal
    assert sw.quarantine_device(dead) is False


def test_quarantine_refuses_unsharded():
    from repro.control.swap import HotSwapper

    # unsharded swapper: nothing to fail over to
    sw = HotSwapper.__new__(HotSwapper)
    sw.placement_fn = None
    sw.n_devices = 1
    assert sw.quarantine_device(object()) is False


@pytest.mark.skipif(IN_LANE, reason="already in the multi-device lane")
def test_multi_device_chaos_subprocess():
    """Default lane: re-run this module's ``multi_device`` selection in
    a child with 8 forced host devices (mirrors the placement suite's
    wrapper) so quarantine failover is covered on every tier-1 run."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count"
                        f"={N_FORCED}")
    env["JAX_PLATFORMS"] = "cpu"          # forced HOST devices
    env.pop("PYTEST_CURRENT_TEST", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         os.path.abspath(__file__), "-m", "multi_device"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=900)
    tail = (r.stdout or "") + (r.stderr or "")
    assert r.returncode == 0, tail[-4000:]
    assert " passed" in r.stdout, tail[-2000:]
    assert " skipped" not in r.stdout, tail[-2000:]


# ------------------------------------------------------ soak smoke
@pytest.mark.slow
def test_chaos_soak_single_device_smoke():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.chaos_bench import check_chaos_schema, run_chaos
    out = run_chaos(n_patients=4, windows_per_patient=6, n_devices=1,
                    seed=0, verbose=False)
    check_chaos_schema(out)
