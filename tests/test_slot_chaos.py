"""Chaos-hardening of the continuous slot engine (serving/slots.py +
control/faults.py): tick-path fault recovery, the generational
ticker/watchdog plane, churn-safe slot lifecycle, monotonic fault
clocks, and replayable compound fault traces.

* tick-path device loss: every gather and bucket dispatch is guarded
  and all guards fire BEFORE the donated fold, so an aborted tick
  leaves every score state untouched — reads stay stale-never-wrong
  through the outage and the post-recovery tick is bitwise the oracle;
* ``FaultPlane.protect_engine`` (multi_device lane): a PERMANENT loss
  quarantines the device, sheds the TickLadder during failover (undone
  after), rebinds the engine onto the survivor facade and re-runs the
  tick — bitwise the unsharded oracle afterwards;
* ``SlotTicker``/``TickerWatchdog``: stall and death respawns, every
  generation ever spawned joined by ``stop()`` (the leak-accounting
  regression: a watchdog-respawned ticker must never orphan a thread
  past the checker), slow TickLadder rungs never misread as stalls;
* churn: a mid-tick close must skip the stamp (version guard), and an
  adversarial admit/discharge/update hammer with census growth past
  the initial ``n_slots`` never stamps a score its own tick report
  cannot reproduce bitwise offline;
* ``FaultPlane`` rides an injectable MONOTONIC clock — schedules and
  retry budgets are immune to wall-clock steps — and round-trips its
  schedule through ``to_json``/``from_json`` trace files.
"""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax

from repro.control.faults import (DeviceLostError, FaultEvent,
                                  FaultPlane, compound_schedule,
                                  slot_compound_schedule)
from repro.serving.aggregator import DeviceIngest, ModalitySpec
from repro.serving.pipeline import EnsembleService
from repro.serving.server import EnsembleServer
from repro.serving.slots import (SlotEngine, SlotTicker, TickLadder,
                                 TickerWatchdog)

N_FORCED = 8
IN_LANE = jax.device_count() >= N_FORCED
multi_device = pytest.mark.multi_device
needs_devices = pytest.mark.skipif(
    not IN_LANE,
    reason=f"needs {N_FORCED} forced host devices (CI lane or the "
           "subprocess wrapper below)")


# ---------------------------------------------------------------- helpers
class FakeClock:
    """Injectable monotonic clock: the schedule and every deadline in
    a ``FaultPlane`` advance exactly when the test says so."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += float(dt)


def _make_ingest(n_patients):
    return DeviceIngest([ModalitySpec("ecg", 250.0, 3)],
                        n_patients=n_patients, window_seconds=1.0)


def _close_round(di, rng, patients, t0):
    refs = {}
    for p in patients:
        ecg = rng.standard_normal((3, 250)).astype(np.float32)
        di.ingest(t0, p, "ecg", ecg)
        refs[p] = di.close_window(p, t0 + 1.0)
    return refs


def _oracle(svc, refs, patients):
    return np.asarray(svc.predict_batch([refs[p] for p in patients]))


def _reads(eng, patients):
    return np.asarray([eng.read(p) for p in patients])


class _StubEngine:
    """Duck-typed engine for pure ticker/watchdog mechanics."""

    def __init__(self, die_first: bool = False):
        self.n = 0
        self._die = die_first

    def tick(self):
        if self._die:
            self._die = False
            raise SystemExit       # kills the generation's thread
        self.n += 1


# ------------------------------------------- tick-path fault recovery
def test_tick_device_loss_aborts_before_fold(zoo_members, rng):
    """A DeviceLostError mid-tick aborts BEFORE any donated fold: the
    mirror keeps its last good scores (stale, never wrong), no version
    stamps, and the post-restore tick is bitwise the oracle."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(4)
    eng = SlotEngine(svc, di)
    pts = [0, 1, 2, 3]
    refs = _close_round(di, rng, pts, t0=0.0)
    for p in pts:
        eng.update(refs[p])
    eng.tick()
    before = _reads(eng, pts)
    assert np.array_equal(before, _oracle(svc, refs, pts))

    clk = FakeClock()
    plane = FaultPlane([FaultEvent(1.0, "device_loss", target=0,
                                   duration=5.0)], clock=clk)
    plane.arm(devices=jax.devices())
    svc.dispatch_guard = plane.guard
    clk.advance(2.0)                          # loss active
    refs2 = _close_round(di, rng, pts, t0=1.0)
    vers = {p: eng.update(refs2[p]) for p in pts}
    with pytest.raises(DeviceLostError):
        eng.tick()
    assert eng.n_tick_faults == 1 and eng.n_tick_aborts == 1
    assert np.array_equal(_reads(eng, pts), before)   # stale, not wrong
    assert not eng.wait_scored(0, vers[0], timeout=0.05)

    clk.advance(10.0)                         # device restored
    rep = eng.tick()
    assert sorted(map(int, rep.stamped)) == pts
    assert np.array_equal(_reads(eng, pts), _oracle(svc, refs2, pts))


def test_on_device_lost_recovery_reruns_tick(zoo_members, rng):
    """When the recovery hook reports success the aborted tick re-runs
    in the SAME tick() call and lands bitwise-correct scores."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(4)
    eng = SlotEngine(svc, di)
    clk = FakeClock()
    plane = FaultPlane([FaultEvent(1.0, "device_loss", target=0,
                                   duration=3.0)], clock=clk)
    plane.arm(devices=jax.devices())
    svc.dispatch_guard = plane.guard
    pts = [0, 1, 2, 3]
    refs = _close_round(di, rng, pts, t0=0.0)
    for p in pts:
        eng.update(refs[p])
    clk.advance(1.5)                          # loss active
    calls = []

    def recover(err):
        calls.append(err.index)
        clk.advance(10.0)                     # "the device reboots"
        return True

    eng.on_device_lost = recover
    rep = eng.tick()
    assert calls == [0]
    assert eng.n_tick_faults == 1 and eng.n_tick_aborts == 0
    assert sorted(map(int, rep.stamped)) == pts
    assert np.array_equal(_reads(eng, pts), _oracle(svc, refs, pts))


def test_request_rebind_applied_at_next_tick(zoo_members, rng):
    """The async rebind (quarantine-hook form) is queued and applied at
    the next tick entry — same member composition, scores bitwise."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(2)
    eng = SlotEngine(svc, di)
    svc2 = EnsembleService(zoo_members)
    eng.request_rebind(svc2)
    refs = _close_round(di, rng, [0, 1], t0=0.0)
    for p in (0, 1):
        eng.update(refs[p])
    rep = eng.tick()
    assert eng.service is svc2 and eng.n_rebinds == 1
    assert len(rep.stamped) == 2
    assert np.array_equal(_reads(eng, [0, 1]),
                          _oracle(svc2, refs, [0, 1]))


# --------------------------------------- ticker generations + watchdog
def test_ticker_stop_joins_all_generations(zoo_members, rng):
    """Satellite regression: every respawned generation stays tracked
    and ``stop()`` joins them ALL (pre-fix, a respawn replaced the
    thread handle and the old generation escaped the leak checker)."""
    svc = EnsembleService(zoo_members)
    eng = SlotEngine(svc, _make_ingest(2))
    t = SlotTicker(eng, interval=0.01).start()
    assert t.respawn() and t.respawn()
    assert len(t._threads) == 3
    assert len({th.name for th in t._threads}) == 3
    assert t.stop(join_timeout=2.0) is True
    assert t.alive_threads() == []
    assert not t.respawn()                    # stopped for good


def test_ticker_wedged_generation_surfaces_in_leak_accounting():
    """A generation wedged inside a tick past the join timeout is
    REPORTED (stop() False + alive_threads names it), never silently
    dropped; once the tick releases, a second stop() joins it."""
    release = threading.Event()

    class Wedge:
        def tick(self):
            release.wait(10.0)

    t = SlotTicker(Wedge(), interval=0.01).start()
    time.sleep(0.1)                 # generation 0 is inside the tick
    assert t.respawn()
    assert t.stop(join_timeout=0.2) is False
    assert t.alive_threads()        # the zombie is named, not lost
    release.set()
    assert t.stop(join_timeout=2.0) is True
    assert t.alive_threads() == []


def test_watchdog_respawns_stalled_ticker():
    """An injected ticker stall starves the beat; the watchdog
    respawns a fresh generation that ticks on through."""
    stub = _StubEngine()
    t = SlotTicker(stub, interval=0.01)
    stalls = [1.0]
    t.before_tick = lambda: stalls.pop() if stalls else 0.0
    t.start()
    wd = TickerWatchdog(t, deadline_seconds=0.15, poll=0.02).start()
    deadline = time.monotonic() + 5.0
    while wd.n_respawns < 1 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert wd.n_respawns >= 1
    assert any(e["cause"] == "stall" for e in wd.events)
    n0 = stub.n
    time.sleep(0.2)
    assert stub.n > n0              # the fresh generation is ticking
    assert wd.stop()
    # gen 0 notices its stale epoch right after the stall and exits
    assert t.stop(join_timeout=3.0)
    assert t.alive_threads() == []


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_watchdog_respawns_dead_ticker():
    """A generation KILLED outright (tick raising SystemExit) is
    detected as dead and respawned."""
    stub = _StubEngine(die_first=True)
    t = SlotTicker(stub, interval=0.01).start()
    wd = TickerWatchdog(t, deadline_seconds=0.15, poll=0.02).start()
    deadline = time.monotonic() + 5.0
    while stub.n < 2 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert stub.n >= 2
    assert wd.n_respawns >= 1
    assert any(e["cause"] == "dead" for e in wd.events)
    assert wd.stop() and t.stop()


def test_watchdog_slow_rung_is_not_a_stall():
    """The quiet threshold reads ``ticker.interval`` LIVE: a TickLadder
    shed to a slow rung must not read as a stall."""
    stub = _StubEngine()
    t = SlotTicker(stub, interval=0.3).start()
    wd = TickerWatchdog(t, deadline_seconds=0.15, poll=0.02).start()
    time.sleep(0.8)                 # two slow ticks' worth of quiet
    assert wd.n_respawns == 0
    assert wd.stop() and t.stop()


def test_server_slots_watchdog_lifecycle(zoo_members, rng):
    """EnsembleServer wires the ticker watchdog: a stall mid-serve is
    respawned through, queries score real (bitwise) after the gap, and
    shutdown leaks nothing — respawned generations included."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(4)
    eng = SlotEngine(svc, di)
    srv = EnsembleServer(engine="slots", slot_engine=eng, n_workers=2,
                         tick_interval=0.01, slot_wait_timeout=5.0,
                         ticker_deadline_seconds=0.1).start()
    stalls = [1.0]
    srv.ticker.before_tick = lambda: stalls.pop() if stalls else 0.0
    pts = [0, 1, 2, 3]
    refs = _close_round(di, rng, pts, t0=0.0)
    for p in pts:
        assert srv.submit(p, refs[p])
    srv.drain(timeout=30.0)
    got = {p: s for p, s, _, _ in srv.results()}
    assert srv.ticker.n_respawns >= 1
    assert srv.ticker_watchdog.n_respawns >= 1
    want = _oracle(svc, refs, pts)
    for p in pts:
        assert got[p] == want[p]
    srv.stop()
    assert srv.leaked == []
    left = [th.name for th in threading.enumerate()
            if th.is_alive() and th.name.startswith("repro-")]
    assert left == []


# --------------------------------------------------- churn-safe slots
def test_midtick_close_skips_stamp(zoo_members, rng):
    """A close landing between a tick's gather and its stamp bumps the
    close version, so the stamp is SKIPPED (the gather may already
    have seen the newer samples) — the next tick scores the new
    window bitwise."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(2)
    eng = SlotEngine(svc, di)
    refs = _close_round(di, rng, [0, 1], t0=0.0)
    for p in (0, 1):
        eng.update(refs[p])
    newref = {}

    def hook():
        newref.update(_close_round(di, rng, [0], t0=1.0))
        eng.update(newref[0])
        eng._pre_stamp_hook = None

    eng._pre_stamp_hook = hook
    rep = eng.tick()
    assert 0 not in rep.stamped and 1 in rep.stamped
    assert np.isnan(eng.read(0))          # never scored; not wrong
    want1 = _oracle(svc, {0: refs[0], 1: refs[1]}, [0, 1])[1]
    assert eng.read(1) == want1
    rep2 = eng.tick()
    assert 0 in rep2.stamped
    want = _oracle(svc, {0: newref[0], 1: refs[1]}, [0, 1])
    assert np.array_equal(_reads(eng, [0, 1]), want)


def test_churn_hammer_never_wrong(zoo_members, rng):
    """Adversarial lifecycle hammer: one closer thread (the ingest
    plane's single feeder) closing windows and GROWING the census past
    its initial slots, a churn thread admitting/discharging at random,
    and a fast ticker.  Every (slot, version, rung) the engine ever
    stamped must rescore bitwise offline; re-stamps of the same key
    must agree."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(4)
    eng = SlotEngine(svc, di)
    eng.warm()
    rec, snaps, bad = {}, {}, []
    lock = threading.Lock()

    def on_tick(r):
        with lock:
            for s, v, sc in zip(r.stamped, r.versions, r.scores):
                key = (int(s), int(v), int(r.spad))
                prev = rec.get(key)
                if prev is not None and prev != float(sc):
                    bad.append(key)
                rec[key] = float(sc)

    eng.on_tick = on_tick
    stop = threading.Event()
    verc = {}

    def closer():
        rng2 = np.random.default_rng(11)
        t_row = {}
        rounds = 0
        while not stop.is_set():
            rounds += 1
            if rounds == 5:         # census outgrows the initial slots
                for _ in range(64):
                    if eng.n_grows:
                        break
                    eng.acquire_slot()
            slots = [int(s) for s in np.flatnonzero(eng.occupied)][:8]
            for s in slots:
                t0 = t_row.get(s, 0.0)
                di.ingest(t0, s, "ecg", rng2.standard_normal(
                    (3, 250)).astype(np.float32))
                ref = di.close_window(s, t0 + 1.0)
                t_row[s] = t0 + 1.0
                with lock:
                    v = verc.get(s, 0) + 1
                    verc[s] = v
                    snaps[(s, v)] = ref.host_window("ecg")
                eng.update(ref)
            # leave room for ticks to STAMP between close rounds: a
            # close mid-tick skips that slot's stamp (version guard),
            # so a closer outrunning the ticker stamps nothing
            time.sleep(0.05)

    def churner():
        rng3 = np.random.default_rng(7)
        while not stop.is_set():
            s = int(rng3.integers(0, eng.n_slots))
            try:
                if rng3.random() < 0.5:
                    eng.discharge(s)
                else:
                    eng.admit(s)
            except KeyError:
                pass
            time.sleep(0.001)

    ticker = SlotTicker(eng, interval=0.005).start()
    threads = [threading.Thread(target=closer, daemon=True),
               threading.Thread(target=churner, daemon=True)]
    for th in threads:
        th.start()
    # hammer for 2 s, and on until a tick has stamped: the first tick
    # at the grown rung compiles for seconds on a CPU, and before it
    # the churner leaves few slots alive through a whole tick
    time.sleep(2.0)
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        with lock:
            if rec:
                break
        time.sleep(0.05)
    stop.set()
    for th in threads:
        th.join(timeout=5.0)
    # the growth tick recompiles at the new rung — join generously
    assert ticker.stop(join_timeout=60.0)
    assert eng.n_grows >= 1 and eng.n_slots > 4
    assert not bad                  # re-stamps of a key always agree

    with lock:
        entries = sorted(rec.items())
    assert entries                  # the hammer actually stamped ticks
    zero = np.zeros((3, 250), np.float32)
    by_spad = {}
    for (s, v, spad), sc in entries:
        by_spad.setdefault(spad, []).append((s, v, sc))
    for spad, ents in by_spad.items():
        for i in range(0, len(ents), spad):
            chunk = ents[i:i + spad]
            wins = [snaps[(s, v)] for s, v, _ in chunk]
            wins += [zero] * (spad - len(wins))
            want = svc.predict_batch([{"ecg": w} for w in wins])
            for (s, v, sc), wsc in zip(chunk, want):
                assert sc == wsc, (s, v, spad)


# ------------------------------------------------- TickLadder + reads
def test_tickladder_swap_races_inflight_tick(zoo_members, rng):
    """``swap_to`` actuating mid-tick (the controller racing the
    ticker) must neither deadlock nor perturb the tick's scores."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(2)
    eng = SlotEngine(svc, di)
    ticker = SlotTicker(eng, interval=0.01)
    lad = TickLadder(ticker, intervals=(0.5, 0.05, 0.01))
    refs = _close_round(di, rng, [0, 1], t0=0.0)
    for p in (0, 1):
        eng.update(refs[p])
    hit = []

    def hook():
        lad.swap_to(0)
        hit.append(ticker.interval)
        eng._pre_stamp_hook = None

    eng._pre_stamp_hook = hook
    rep = eng.tick()
    assert hit == [0.5] and lad.ladder_pos == 0
    assert len(rep.stamped) == 2
    assert np.array_equal(_reads(eng, [0, 1]),
                          _oracle(svc, refs, [0, 1]))


def test_wait_scored_dead_ticker_times_out(zoo_members, rng):
    """With the ticker dead, a version-gated read times out cleanly to
    the NaN path — bounded wait, no hang, no invented score."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(2)
    eng = SlotEngine(svc, di)
    ticker = SlotTicker(eng, interval=0.01).start()
    assert ticker.stop()
    v = eng.update(_close_round(di, rng, [0], t0=0.0)[0])
    t0 = time.monotonic()
    assert not eng.wait_scored(0, v, timeout=0.2)
    assert time.monotonic() - t0 < 1.0
    assert np.isnan(eng.read(0))


# ------------------------------------------ monotonic clock + traces
def test_fault_plane_schedule_on_injected_clock(monkeypatch):
    """The schedule advances ONLY on the plane's injected monotonic
    clock: a wall-clock step (time.time jumping 30k years) changes
    nothing."""
    clk = FakeClock()
    plane = FaultPlane([FaultEvent(1.0, "device_loss", target=0,
                                   duration=2.0)], clock=clk)
    plane.arm(devices=[object()])
    monkeypatch.setattr(time, "time", lambda: 1e12)  # wall jump
    assert plane.active_losses() == {}
    clk.advance(1.5)
    assert 0 in plane.active_losses()
    clk.advance(2.0)                                 # t = 3.5 > 3.0
    assert plane.active_losses() == {}
    assert plane.done()
    assert any(r["kind"] == "device_restored" for r in plane.recoveries)


def test_protect_retry_budget_on_injected_clock():
    """``protect()``'s retry budget rides the SAME injected clock as
    the schedule — it expires when the plane's timeline says so, not
    wall time."""
    clk = FakeClock()
    plane = FaultPlane([FaultEvent(0.1, "device_loss", target=0,
                                   duration=0.0)], clock=clk)
    plane.arm(devices=[object()])
    clk.advance(0.2)                     # permanent loss, no swapper
    calls = []

    def fn(windows):
        calls.append(1)
        clk.advance(1.0)
        raise DeviceLostError(None, 0)

    guarded = plane.protect(fn, swapper=None, retry_budget_s=5.0,
                            retry_sleep=0.0)
    with pytest.raises(DeviceLostError):
        guarded([])
    assert 2 <= len(calls) <= 8          # retried, then gave up on the
    #                                      injected budget — not wall


def test_fault_trace_roundtrip(tmp_path):
    """to_json/from_json round-trips the schedule byte-for-byte, as
    text and as a committed trace file."""
    plane = FaultPlane(slot_compound_schedule(8, seed=3), seed=3)
    text = plane.to_json()
    p2 = FaultPlane.from_json(text)
    assert [e.to_dict() for e in p2.schedule] \
        == [e.to_dict() for e in plane.schedule]
    assert p2.seed == 3
    path = str(tmp_path / "trace.json")
    plane.to_json(path)
    p3 = FaultPlane.from_json(path)
    assert [e.to_dict() for e in p3.schedule] \
        == [e.to_dict() for e in plane.schedule]


def test_compound_schedule_shapes():
    """The compound generators keep their guaranteed shape on every
    seed: stall cascades, loss-inside-backpressure, permanent +
    transient overlap with survivors, transient-only without."""
    for nd in (1, 8):
        ev = compound_schedule(nd, seed=0)
        kinds = [e.kind for e in ev]
        assert kinds.count("worker_stall") == 2
        assert kinds.count("device_loss") == 2
        assert "backpressure" in kinds
        sev = slot_compound_schedule(nd, seed=0)
        skinds = [e.kind for e in sev]
        assert skinds.count("ticker_stall") == 2
        assert "worker_stall" not in skinds
        assert [e.t for e in sev] == sorted(e.t for e in sev)
    ev8 = compound_schedule(8, seed=0)
    losses = [e for e in ev8 if e.kind == "device_loss"]
    bp = next(e for e in ev8 if e.kind == "backpressure")
    perm = [e for e in losses if e.duration == 0]
    assert len(perm) == 1 and any(e.duration > 0 for e in losses)
    assert bp.t <= perm[0].t < bp.t + bp.duration
    assert all(e.duration > 0
               for e in compound_schedule(1, seed=0)
               if e.kind == "device_loss")
    a = [e.to_dict() for e in slot_compound_schedule(8, seed=1)]
    b = [e.to_dict() for e in slot_compound_schedule(8, seed=1)]
    assert a == b                        # deterministic in (n, seed)
    c = [e.to_dict() for e in slot_compound_schedule(8, seed=2)]
    assert a != c                        # the seed jitters timings


# ------------------------------------------------- multi-device lane
@needs_devices
@multi_device
def test_protect_engine_permanent_loss_rebind(zoo_members, rng):
    """Permanent device loss mid-tick on a sharded plan: quarantine,
    TickLadder shed during failover (undone after), rebind onto the
    survivor facade, re-tick — bitwise the UNSHARDED oracle."""
    from repro.control.swap import HotSwapper
    devices = jax.devices()
    pool = zoo_members
    rich = np.ones(len(pool), np.int8)
    swapper = HotSwapper(pool, rich, n_devices=4,
                         warmup_batch_sizes=(4,))
    di = _make_ingest(4)
    eng = SlotEngine(swapper.facade.current, di)
    ticker = SlotTicker(eng, interval=0.02)
    lad = TickLadder(ticker, intervals=(0.08, 0.02))
    clk = FakeClock()
    plane = FaultPlane([FaultEvent(0.1, "device_loss", target=1,
                                   duration=0.0)], clock=clk)
    plane.arm(swapper)
    plane.protect_engine(eng, swapper, ticker=ticker, tick_ladder=lad)
    pts = [0, 1, 2, 3]
    refs = _close_round(di, rng, pts, t0=0.0)
    for p in pts:
        eng.update(refs[p])
    eng.tick()                            # pre-loss baseline
    clk.advance(1.0)                      # permanent loss fires
    rep = eng.tick()                      # recover INSIDE the tick
    assert eng.n_tick_faults >= 1 and eng.n_tick_aborts == 0
    assert eng.n_rebinds >= 1
    assert devices[1] in swapper.quarantined
    assert sorted(map(int, rep.stamped)) == pts
    assert lad.ladder_pos == len(lad.ladder) - 1   # shed undone
    oracle = EnsembleService(pool)        # unsharded, fault-free
    assert np.array_equal(_reads(eng, pts), _oracle(oracle, refs, pts))


def test_multi_device_lane_subprocess():
    """Single-device lane: re-run this module's ``multi_device``
    selection under 8 forced host devices."""
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
