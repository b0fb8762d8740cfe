"""Slot-based continuous serving engine (serving/slots.py):

* THE acceptance property: a ticked slot's score is BITWISE-identical
  to the flush oracle (``EnsembleService.predict_batch`` over the same
  refs), across partial occupancy, sensor dropout / short windows,
  ring wraparound, occupancy churn, CPU-side vitals/labs models, and
  (via the ``multi_device`` lane) a sharded 8-device placement;
* zero per-query device work: reads are host int indexing and the
  tick's dispatch count is exactly ``n_buckets`` per tick;
* version-gated reads (``wait_scored``), the tick-age staleness guard,
  and slot admin (admit idempotence, discharge semantics, ABA churn);
* ``EnsembleServer(engine="slots")`` end-to-end: conservation, bitwise
  scores, no leaked threads;
* ``StreamingPipeline(engine="slots")`` vs the flush-engine pipeline;
* ``TickLadder``: tick rate as a controller-actuated degradation rung
  (shed slows the tick, climb speeds it up), driven standalone and
  through ``control.controller.AdaptiveController``.

Oracle caveat (see slots.py module doc): a flush of exactly ONE window
compiles a batch-1-specialized XLA program with different float
numerics, and different pow2 pads are different programs — so every
oracle flush here uses the SAME pow2 rung as the engine's slot batch.
"""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax

from repro.serving.aggregator import (DeviceIngest, ModalitySpec,
                                      pow2_rung)
from repro.serving.pipeline import EnsembleService, StreamingPipeline
from repro.serving.server import EnsembleServer
from repro.serving.slots import SlotEngine, SlotTicker, TickLadder

N_FORCED = 8
IN_LANE = jax.device_count() >= N_FORCED
multi_device = pytest.mark.multi_device
needs_devices = pytest.mark.skipif(
    not IN_LANE,
    reason=f"needs {N_FORCED} forced host devices (CI lane or the "
           "subprocess wrapper below)")


# ---------------------------------------------------------------- helpers
def _make_ingest(n_patients, vitals=False):
    mods = [ModalitySpec("ecg", 250.0, 3)]
    if vitals:
        mods.append(ModalitySpec("vitals", 1.0, 7))
    return DeviceIngest(mods, n_patients=n_patients, window_seconds=1.0)


def _close_round(di, rng, patients, t0, n_samples=250, extra=None):
    """Feed one ECG window per patient (mixed chunk sizes exercise the
    pow2 ingest ladder) and close it; returns {patient: ref}."""
    refs = {}
    for p in patients:
        ecg = rng.standard_normal((3, n_samples)).astype(np.float32)
        off = 0
        for k in (100, 75, 75, 250):
            if off >= n_samples:
                break
            di.ingest(t0 + off / 250.0, p, "ecg", ecg[:, off:off + k])
            off += k
        refs[p] = di.close_window(p, t0 + 1.0,
                                  extra=dict(extra or {}))
    return refs


def _oracle(svc, refs, patients):
    return np.asarray(svc.predict_batch([refs[p] for p in patients]))


def _reads(eng, patients):
    return np.asarray([eng.read(p) for p in patients])


# ----------------------------------------------- tick bitwise equivalence
def test_tick_bitwise_vs_flush_oracle(zoo_members, rng):
    """Full house, two rounds (the second overwrites ring heads): every
    slot's read equals the flush oracle bit for bit, at n_buckets
    dispatches per tick and ZERO per-read."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(8)
    eng = SlotEngine(svc, di)
    patients = list(range(8))
    for rnd in range(2):
        refs = _close_round(di, rng, patients, t0=float(rnd))
        for p in patients:
            eng.update(refs[p])
        rep = eng.tick()
        assert rep.n_scored == 8 and rep.n_stale == 0
        assert sorted(map(int, rep.scored)) == patients
        want = _oracle(svc, refs, patients)
        d0 = eng.dispatch_count
        got = _reads(eng, patients)
        assert eng.dispatch_count == d0        # reads dispatch nothing
        assert np.array_equal(got, want), f"round {rnd}"
    assert eng.dispatch_count == 2 * svc.n_buckets
    assert eng.tick_count == 2
    np.testing.assert_array_equal(eng.scores(), got)


def test_tick_partial_occupancy(zoo_members, rng):
    """Only 5 of 8 slots occupied: the occupancy mask drops the garbage
    columns; occupied reads stay bitwise, empty reads raise."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(8)
    eng = SlotEngine(svc, di)
    occ = [0, 2, 3, 5, 7]                    # pow2_rung(5) == Spad == 8
    refs = _close_round(di, rng, occ, t0=0.0)
    for p in occ:
        eng.update(refs[p])
    rep = eng.tick()
    assert rep.n_scored == len(occ)
    assert np.array_equal(_reads(eng, occ), _oracle(svc, refs, occ))
    for p in (1, 4, 6):
        with pytest.raises(KeyError):
            eng.read(p)
    s = eng.scores()
    assert np.isnan(s[[1, 4, 6]]).all() and np.isfinite(s[occ]).all()


def test_tick_bitwise_dropout_short_windows_and_wraparound(zoo_members,
                                                           rng):
    """Windows with missing samples (sensor dropout -> left-zero pad)
    and rings that wrapped several times still read bitwise."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(2)
    cap = di.states["ecg"].buf.shape[-1]
    eng = SlotEngine(svc, di)
    refs = {}
    for w in range(4):                       # 4 windows > cap=2 windows
        n = 120 if w == 3 else 250           # last window: dropout
        refs = _close_round(di, rng, [0, 1], t0=float(w), n_samples=n)
        for p in (0, 1):
            eng.update(refs[p])
    assert int(di.fed["ecg"][0]) == 3 * 250 + 120 > cap
    eng.tick()
    assert refs[0].valid["ecg"] == 120
    assert np.array_equal(_reads(eng, [0, 1]),
                          _oracle(svc, refs, [0, 1]))


def test_tick_bitwise_with_cpu_side_models(zoo_members, rng):
    """Vitals/labs CPU-side models join the slot's combined score with
    the flush path's exact float64 _combine numerics."""
    class Const:
        def __init__(self, v):
            self.v = v

        def predict_proba(self, x):
            return np.full(len(x), self.v)

    svc = EnsembleService(zoo_members, vitals_model=Const(0.9),
                          labs_model=Const(0.1))
    di = _make_ingest(2, vitals=True)
    eng = SlotEngine(svc, di)
    labs = rng.standard_normal(8).astype(np.float32)
    refs = {}
    for p in (0, 1):
        di.ingest(0.0, p, "vitals",
                  rng.standard_normal((7, 1)).astype(np.float32))
    refs = _close_round(di, rng, [0, 1], t0=0.0,
                        extra={"labs": labs})
    for p in (0, 1):
        eng.update(refs[p])
    eng.tick()
    assert np.array_equal(_reads(eng, [0, 1]),
                          _oracle(svc, refs, [0, 1]))


class _CountingModel:
    """A side model that counts the rows it scores and answers a float
    that depends on every input value (``bias`` tells two apart)."""

    def __init__(self, bias=0.0):
        self.bias = bias
        self.rows = 0

    def predict_proba(self, x):
        x = np.asarray(x, np.float64)
        self.rows += len(x)
        z = x.reshape(len(x), -1).sum(axis=1) + self.bias
        return 1.0 / (1.0 + np.exp(-z))


def _side_round(di, rng, patients, t):
    """One vitals sample per patient, then a closed window whose labs
    vector is new too: every side-model input of the close is fresh."""
    for p in patients:
        di.ingest(t, p, "vitals",
                  rng.standard_normal((7, 1)).astype(np.float32))
    labs = rng.standard_normal(8).astype(np.float32)
    return _close_round(di, rng, patients, t0=t, extra={"labs": labs})


def _side_engine(zoo_members, rng, n, bias=0.0):
    models = (_CountingModel(bias), _CountingModel(bias - 1.0))
    svc = EnsembleService(zoo_members, vitals_model=models[0],
                          labs_model=models[1])
    di = _make_ingest(n, vitals=True)
    eng = SlotEngine(svc, di)
    refs = _side_round(di, rng, range(n), 0.0)
    for p in range(n):
        eng.update(refs[p])
    return svc, di, eng, models, refs


def _side_rows(models):
    return tuple(m.rows for m in models)


def test_side_models_run_once_per_close(zoo_members, rng):
    """Over ticks in which only some beds close a new window, each side
    model scores one row per (slot, close) and no more; every tick's
    reads stay bitwise the flush oracle over the current refs, and the
    report's two counters split the scored slots."""
    n = 4
    svc, di, eng, models, refs = _side_engine(zoo_members, rng, n)
    patients = list(range(n))
    closes = n
    for t, closing in enumerate([[], [], [1, 3], [0], []], start=1):
        if closing:
            new = _side_round(di, rng, closing, float(t))
            for p in closing:
                eng.update(new[p])
            refs.update(new)
            closes += len(closing)
        rep = eng.tick()
        fresh = n if t == 1 else len(closing)
        assert (rep.side_calls, rep.side_reused) == (fresh, n - fresh)
        assert rep.side_calls + rep.side_reused == rep.n_scored == n
        assert _side_rows(models) == (closes, closes)
        assert np.array_equal(_reads(eng, patients),
                              _oracle(svc, refs, patients)), f"tick {t}"
        for m in models:                 # the oracle's own rows
            m.rows = closes
    assert (eng.side_calls, eng.side_reused) == (closes, 5 * n - closes)


def _event_new_close(eng, di, rng, refs, zoo_members):
    refs.update(_side_round(di, rng, [0], 1.0))
    eng.update(refs[0])
    return eng.service, [0]


def _event_readmit(eng, di, rng, refs, zoo_members):
    eng.discharge(0)
    refs.update(_side_round(di, rng, [0], 1.0))
    eng.update(refs[0])
    return eng.service, [0]


def _event_ensure_slots(eng, di, rng, refs, zoo_members):
    eng.ensure_slots(eng.n_slots + 1)
    return eng.service, list(refs)


def _event_rebind(eng, di, rng, refs, zoo_members):
    svc2 = EnsembleService(zoo_members, vitals_model=_CountingModel(0.5),
                           labs_model=_CountingModel(-0.5))
    eng.rebind(svc2)
    return svc2, list(refs)


def _event_stale_then_close(eng, di, rng, refs, zoo_members):
    # slot 0's close scrolls out of its ECG ring: masked out of the
    # next tick, whose combine never consults its record
    for w in range(1, 4):
        for off in range(0, 250, 50):
            di.ingest(w + off / 250.0, 0, "ecg",
                      rng.standard_normal((3, 50)).astype(np.float32))
    before = _side_rows((eng.service.vitals_model,
                         eng.service.labs_model))
    rep = eng.tick()
    assert rep.n_stale == 1
    assert (rep.side_calls, rep.side_reused) == (0, len(refs) - 1)
    assert _side_rows((eng.service.vitals_model,
                       eng.service.labs_model)) == before
    refs.update(_side_round(di, rng, [0], 4.0))
    eng.update(refs[0])
    return eng.service, [0]


@pytest.mark.parametrize("event", [
    _event_new_close, _event_readmit, _event_ensure_slots,
    _event_rebind, _event_stale_then_close])
def test_side_scores_recomputed_when_the_close_changes(event, zoo_members,
                                                       rng):
    """A new close, discharge then readmission, census growth, a rebind
    to other side models, and a close after a stale ring each run the
    side models again for the slots they touch, and the reads equal
    the flush oracle over the current refs and the current service."""
    n = 4
    _, di, eng, _, refs = _side_engine(zoo_members, rng, n)
    eng.tick()
    rep = eng.tick()
    assert (rep.side_calls, rep.side_reused) == (0, n)
    svc, redo = event(eng, di, rng, refs, zoo_members)
    models = (svc.vitals_model, svc.labs_model)
    before = _side_rows(models)
    rep = eng.tick()
    assert (rep.side_calls, rep.side_reused) == (len(redo), n - len(redo))
    assert _side_rows(models) == tuple(b + len(redo) for b in before)
    # the oracle at the engine's pad rung (growth doubles it)
    patients = list(range(n))
    rung = pow2_rung(eng.n_slots) // n
    want = _oracle(svc, refs, patients * rung)[:n]
    assert np.array_equal(_reads(eng, patients), want)


def test_no_side_models_no_side_counts(zoo_members, rng):
    """A service without side models combines exactly as before: both
    counters stay 0 and the reads are the flush oracle's."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(4)
    eng = SlotEngine(svc, di)
    patients = list(range(4))
    refs = _close_round(di, rng, patients, t0=0.0)
    for p in patients:
        eng.update(refs[p])
    for _ in range(2):
        rep = eng.tick()
        assert rep.n_scored == 4
        assert (rep.side_calls, rep.side_reused) == (0, 0)
        assert np.array_equal(_reads(eng, patients),
                              _oracle(svc, refs, patients))
    assert (eng.side_calls, eng.side_reused) == (0, 0)


def test_occupancy_churn_discharge_and_readmit(zoo_members, rng):
    """Slot insert/free mid-serving: a discharged slot's read raises
    and its score never leaks into survivors; re-admission serves the
    NEW occupant's window bitwise."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(8)
    eng = SlotEngine(svc, di)
    patients = list(range(8))
    refs = _close_round(di, rng, patients, t0=0.0)
    for p in patients:
        eng.update(refs[p])
    eng.tick()
    eng.discharge(3)
    with pytest.raises(KeyError):
        eng.read(3)
    with pytest.raises(KeyError):
        eng.discharge(3)                     # double-free
    rest = [p for p in patients if p != 3]   # 7 -> same pow2 rung
    eng.tick()                               # survivors rescore fine
    assert np.array_equal(_reads(eng, rest), _oracle(svc, refs, rest))
    assert eng.n_discharges == 1
    # a new patient takes bed 3: fresh window, fresh score
    refs2 = _close_round(di, rng, [3], t0=2.0)
    v = eng.update(refs2[3])
    assert eng.n_admits == 9                 # 8 first-window + re-admit
    assert np.isnan(eng.read(3))             # admitted, not yet ticked
    eng.tick()
    assert eng.scored_version[3] == v
    all_refs = {**refs, 3: refs2[3]}
    assert np.array_equal(_reads(eng, patients),
                          _oracle(svc, all_refs, patients))


def test_admit_is_idempotent_and_prescore_reads_nan(zoo_members, rng):
    svc = EnsembleService(zoo_members)
    eng = SlotEngine(svc, _make_ingest(2))
    eng.admit(0)
    eng.admit(0)
    assert eng.n_admits == 1
    assert np.isnan(eng.read(0))             # occupied, never scored
    rep = eng.tick()                         # no window yet: no-op tick
    assert rep.n_scored == 0 and eng.dispatch_count == 0


# ------------------------------------------- versions + staleness guards
def test_wait_scored_is_version_gated(zoo_members, rng):
    svc = EnsembleService(zoo_members)
    di = _make_ingest(2)
    eng = SlotEngine(svc, di)
    refs = _close_round(di, rng, [0, 1], t0=0.0)
    v = eng.update(refs[0])
    eng.update(refs[1])
    assert not eng.wait_scored(0, v, timeout=0.05)   # no tick yet
    eng.tick()
    assert eng.wait_scored(0, v, timeout=0.05)
    assert not eng.wait_scored(0, v + 1, timeout=0.05)  # future close
    eng.discharge(0)
    assert not eng.wait_scored(0, v, timeout=0.05)   # gone: wake False


def test_stale_ring_skipped_and_tick_age_guard(zoo_members, rng):
    """A slot whose closed window was overwritten before the tick could
    gather it is SKIPPED (never scored with wrong-window samples): its
    mirror keeps the last good score, its version stops advancing, and
    the read-side tick-age guard turns it NaN."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(2)
    cap = di.states["ecg"].buf.shape[-1]
    eng = SlotEngine(svc, di)
    refs = _close_round(di, rng, [0, 1], t0=0.0)
    for p in (0, 1):
        eng.update(refs[p])
    eng.tick()
    good = eng.read(0)
    # over-feed slot 0 WITHOUT closing: its last closed window scrolls
    # out of the ring (fed - oldest > cap)
    for w in range(1, 4):
        for off in range(0, 250, 50):
            di.ingest(w + off / 250.0, 0, "ecg",
                      rng.standard_normal((3, 50)).astype(np.float32))
    assert int(di.fed["ecg"][0]) == 1000 > cap
    rep = eng.tick()
    assert rep.n_stale == 1 and rep.n_scored == 1
    assert eng.read(0) == good               # last good score, kept
    assert np.isnan(eng.read(0, max_age_ticks=0))    # age guard: NaN
    assert np.isfinite(eng.read(1, max_age_ticks=0))  # rescored fine
    v2 = eng.update(_close_round(di, rng, [0], t0=4.0)[0])
    eng.tick()                               # fresh close: recovers
    assert rep.n_stale == 1
    assert eng.scored_version[0] == v2
    assert np.isfinite(eng.read(0, max_age_ticks=0))


def test_engine_rejects_wrong_service_or_ingest(zoo_members, rng):
    di = _make_ingest(2)
    with pytest.raises(ValueError, match="ecg"):
        SlotEngine(EnsembleService(zoo_members),
                   DeviceIngest([ModalitySpec("vitals", 1.0, 7)],
                                n_patients=2, window_seconds=1.0))
    eng = SlotEngine(EnsembleService(zoo_members), di)
    other = _make_ingest(2)
    ref = _close_round(other, rng, [0], t0=0.0)[0]
    with pytest.raises(ValueError, match="different DeviceIngest"):
        eng.update(ref)


# ----------------------------------------------------- ticker + server
def test_ticker_scores_in_background(zoo_members, rng):
    svc = EnsembleService(zoo_members)
    di = _make_ingest(2)
    eng = SlotEngine(svc, di)
    ticker = SlotTicker(eng, interval=0.01).start()
    try:
        refs = _close_round(di, rng, [0, 1], t0=0.0)
        vs = {p: eng.update(refs[p]) for p in (0, 1)}
        for p in (0, 1):
            assert eng.wait_scored(p, vs[p], timeout=2.0)
        assert np.array_equal(_reads(eng, [0, 1]),
                              _oracle(svc, refs, [0, 1]))
    finally:
        assert ticker.stop()
    assert not ticker.alive


def test_server_slots_engine_end_to_end(zoo_members, rng):
    """EnsembleServer(engine='slots'): conservation (served == submitted,
    zero failed), bitwise scores vs the flush oracle, no leaked threads
    (workers + ticker), and zero per-query dispatches."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(8)
    eng = SlotEngine(svc, di)
    patients = list(range(8))
    refs = _close_round(di, rng, patients, t0=0.0)
    srv = EnsembleServer(engine="slots", slot_engine=eng,
                         tick_interval=0.01, n_workers=2).start()
    for p in patients:
        assert srv.submit(p, refs[p])
    stats = srv.stop()
    assert stats.served == 8 and stats.failed == 0
    assert srv.leaked == []
    want = _oracle(svc, refs, patients)
    got = {p: s for p, s, *_ in srv.results()}
    assert np.array_equal(np.asarray([got[p] for p in patients]), want)
    # the whole run's device work came from ticks, none from queries
    assert eng.dispatch_count % svc.n_buckets == 0


def test_server_slots_stale_read_retires_nan_not_blocks(zoo_members,
                                                        rng):
    """A query whose covering tick never lands (ticker too slow /
    stopped) must retire NaN within slot_wait_timeout, not hang
    drain()."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(2)
    eng = SlotEngine(svc, di)
    refs = _close_round(di, rng, [0], t0=0.0)
    srv = EnsembleServer(engine="slots", slot_engine=eng,
                         tick_interval=60.0,       # never ticks in test
                         slot_wait_timeout=0.1,
                         n_workers=1).start()
    assert srv.submit(0, refs[0])
    stats = srv.stop()
    assert stats.served == 1 and stats.failed == 1
    assert srv.leaked == []


def test_server_slots_ctor_validation(zoo_members):
    eng = SlotEngine(EnsembleService(zoo_members), _make_ingest(2))
    with pytest.raises(ValueError, match="slot_engine"):
        EnsembleServer(engine="slots")
    with pytest.raises(ValueError, match="handlers"):
        EnsembleServer(engine="slots", slot_engine=eng,
                       batch_handler=lambda w: [0.0])
    with pytest.raises(ValueError, match="untiered"):
        EnsembleServer(engine="slots", slot_engine=eng,
                       tier_of=lambda p: "stable")
    with pytest.raises(ValueError, match='engine="slots"'):
        EnsembleServer(handler=lambda w: 0.0, slot_engine=eng)
    with pytest.raises(ValueError, match="unknown engine"):
        EnsembleServer(handler=lambda w: 0.0, engine="nope")


# ----------------------------------------------------- pipeline engine
def test_pipeline_slots_engine_vs_flush(zoo_members, rng):
    """StreamingPipeline(engine='slots') serves every closed window the
    flush-engine pipeline serves, same windows, equivalent scores (the
    flush pipeline scores windows singly — a different XLA pad — so
    this comparison is float-tolerance; bitwise is covered at the
    engine level above)."""
    svc = EnsembleService(zoo_members)
    flush = StreamingPipeline(svc, n_patients=2, window_seconds=1.0,
                              device_ingest=True)
    slots = StreamingPipeline(svc, n_patients=2, window_seconds=1.0,
                              device_ingest=True, engine="slots")
    rng2 = np.random.default_rng(7)
    for j in range(7):                       # 3 windows/patient @0.5 s
        t = j * 0.5
        for p in range(2):
            c = rng2.standard_normal((3, 125)).astype(np.float32)
            flush.feed(t, p, "ecg", c)
            slots.feed(t, p, "ecg", c)
    slots.tick_now(3.5)                      # drain pending closes
    assert len(flush.records) == len(slots.records) == 6
    want = {(r.patient, r.t_window): r.score for r in flush.records}
    for r in slots.records:
        assert r.score == pytest.approx(want[(r.patient, r.t_window)],
                                        abs=1e-6)
    with pytest.raises(ValueError):
        flush.tick_now(0.0)                  # flush engine has no ticks


def test_pipeline_slots_ctor_validation(zoo_members):
    svc = EnsembleService(zoo_members)
    with pytest.raises(ValueError, match="device_ingest"):
        StreamingPipeline(svc, n_patients=2, engine="slots")
    with pytest.raises(ValueError, match="untiered"):
        StreamingPipeline(svc, n_patients=2, device_ingest=True,
                          engine="slots", tier_of=lambda p: "stable")
    with pytest.raises(ValueError, match="unknown engine"):
        StreamingPipeline(svc, n_patients=2, engine="nope")


# -------------------------------------------------------- tick ladder
def test_tick_ladder_shed_slows_climb_speeds(zoo_members):
    eng = SlotEngine(EnsembleService(zoo_members), _make_ingest(2))
    ticker = SlotTicker(eng, interval=0.05)   # never started: knob only
    lad = TickLadder(ticker, intervals=[0.01, 0.05, 0.2])
    assert lad.ladder == [0.2, 0.05, 0.01]    # rung 0 = slowest
    assert lad.ladder_pos == 2                # starts richest
    assert ticker.interval == 0.01
    assert lad.can_shed() and not lad.can_climb()
    assert lad.shed() and ticker.interval == 0.05
    assert lad.shed() and ticker.interval == 0.2
    assert not lad.shed() and not lad.can_shed()   # floor holds
    assert lad.climb() and ticker.interval == 0.05
    lad.swap_to(0)
    assert lad.active_interval == ticker.interval == 0.2
    with pytest.raises(ValueError):
        lad.swap_to(3)
    with pytest.raises(ValueError):
        TickLadder(ticker, intervals=[])
    with pytest.raises(ValueError):
        TickLadder(ticker, intervals=[0.1, -0.1])
    with pytest.raises(ValueError):
        TickLadder(ticker, intervals=[0.1], start=5)


def test_tick_ladder_actuated_by_adaptive_controller(zoo_members):
    """Tick rate joins the controller's knobs: SLO violations SHED the
    tick ladder (interval slows), a healthy window climbs back."""
    from repro.control.controller import (AdaptiveController,
                                          ControllerConfig, Decision)
    from repro.control.telemetry import SloTelemetry
    eng = SlotEngine(EnsembleService(zoo_members), _make_ingest(2))
    ticker = SlotTicker(eng, interval=0.01)
    lad = TickLadder(ticker, intervals=[0.01, 0.1])
    t = [100.0]
    tel = SloTelemetry(slo_seconds=0.5, clock=lambda: t[0])
    ctl = AdaptiveController(
        tel, lad, sync=True, clock=lambda: t[0],
        config=ControllerConfig(slo_seconds=0.5, cooldown_seconds=0.0,
                                drift_factor=1e9))  # isolate shed/climb
    for k in range(30):                       # violating traffic
        tel.record_arrival(99.0)
        tel.record_served(0.9, 99.0 + k / 100.0)
    assert ctl.step() is Decision.SHED
    assert lad.ladder_pos == 0 and ticker.interval == 0.1
    t[0] += 100.0                             # violations age out
    for k in range(30):
        tel.record_arrival(t[0] - 1.0)
        tel.record_served(0.05, t[0] - 1.0 + k / 100.0)
    assert ctl.step() is Decision.CLIMB
    assert lad.ladder_pos == 1 and ticker.interval == 0.01


# ------------------------------------------------- multi-device lane
@multi_device
@needs_devices
def test_slot_tick_bitwise_sharded_8_devices(zoo_members, rng):
    """The forced-8-device lane: slot ticks through an LPT-sharded
    placement (one donated state per device group, cross-group fleet
    mean) still read bitwise-equal to the UNSHARDED flush oracle."""
    from repro.configs.ecg_zoo import bucket_zoo
    from repro.serving.placement import grouped_lpt_placement
    groups = list(bucket_zoo([m.spec for m in zoo_members]).values())
    pl = grouped_lpt_placement(groups, [1.0 + 0.1 * j for j in
                                        range(len(groups))], N_FORCED)
    sharded = EnsembleService(zoo_members, placement=pl,
                              devices=jax.devices()[:N_FORCED])
    flat = EnsembleService(zoo_members)
    di = _make_ingest(8)
    eng = SlotEngine(sharded, di)
    assert len(eng.groups) > 1               # actually sharded
    patients = list(range(8))
    refs = _close_round(di, rng, patients, t0=0.0)
    for p in patients:
        eng.update(refs[p])
    rep = eng.tick()
    assert rep.n_scored == 8
    assert np.array_equal(_reads(eng, patients),
                          _oracle(flat, refs, patients))


@pytest.mark.skipif(IN_LANE, reason="already in the multi-device lane")
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


# ------------------------------------------------------ warm + compile
def test_warm_precompiles_tick_path(zoo_members, rng):
    """After ``warm()`` a tick compiles nothing new on the bucket
    dispatches (the gather/update programs are shared with the flush
    path's caches)."""
    svc = EnsembleService(zoo_members)
    di = _make_ingest(4)
    eng = SlotEngine(svc, di)
    eng.warm()
    sizes = {id(b.fn): b.fn._cache_size() for b in svc._buckets}
    refs = _close_round(di, rng, [0, 1, 2, 3], t0=0.0)
    for p in range(4):
        eng.update(refs[p])
    eng.tick()
    for b in svc._buckets:
        assert b.fn._cache_size() == sizes[id(b.fn)]
