"""The slot tick on the profiler's clock (serving/slots.py with
obs/spans.phase): a tick's phases cover its wall time, each slot-mode
span carries the instants of the tick that scored it, and a profiler
trace holds the program's own spans beside JAX's dispatches."""
import glob
import time

import numpy as np

from repro.obs.spans import SpanRecorder, collect, phase
from repro.serving.aggregator import DeviceIngest, ModalitySpec
from repro.serving.pipeline import EnsembleService
from repro.serving.server import EnsembleServer
from repro.serving.slots import SlotEngine, SlotTicker

TICK_PHASES = ("slots.tick.snapshot", "slots.tick.gather",
               "slots.tick.dispatch", "slots.tick.fold",
               "slots.tick.readback", "slots.tick.combine",
               "slots.tick.stamp")


def _census(zoo_members, rng, n=8):
    di = DeviceIngest([ModalitySpec("ecg", 250.0, 3)], n_patients=n,
                      window_seconds=1.0)
    refs = {}
    for p in range(n):
        di.ingest(0.0, p, "ecg",
                  rng.standard_normal((3, 250)).astype(np.float32))
        refs[p] = di.close_window(p, 1.0)
    return di, refs


def test_phase_notes_into_the_active_sink_only():
    with phase("outside"):
        pass
    with collect() as acc:
        with phase("a"):
            time.sleep(0.002)
        with phase("a"):
            pass
    assert set(acc) == {"a"} and acc["a"] >= 0.002


def test_phase_notes_a_block_that_raises():
    with collect() as acc:
        try:
            with phase("boom"):
                raise ValueError("x")
        except ValueError:
            pass
        with phase("after"):            # the next span opens as usual
            pass
    assert set(acc) == {"boom", "after"}


def test_tick_phases_cover_the_tick(zoo_members, rng):
    di, refs = _census(zoo_members, rng)
    eng = SlotEngine(EnsembleService(zoo_members), di)
    for r in refs.values():
        eng.update(r)
    for _ in range(3):
        rep = eng.tick()
        assert rep.n_scored == 8
        assert set(rep.phases) == set(TICK_PHASES)
        # ``seconds`` ends where the stamp begins
        total = sum(rep.phases.values())
        assert total - rep.phases["slots.tick.stamp"] <= rep.seconds
        assert total >= 0.9 * rep.seconds


def test_tick_with_nothing_to_score_reports_snapshot_and_stamp(
        zoo_members, rng):
    di, _ = _census(zoo_members, rng)
    eng = SlotEngine(EnsembleService(zoo_members), di)
    rep = eng.tick()
    assert rep.n_scored == 0 and not rep.skipped
    assert set(rep.phases) == {"slots.tick.snapshot", "slots.tick.stamp"}
    assert rep.phases["slots.tick.snapshot"] <= rep.seconds


def test_read_stamped_keeps_the_first_tick_of_each_close(zoo_members,
                                                         rng):
    di, refs = _census(zoo_members, rng)
    eng = SlotEngine(EnsembleService(zoo_members), di)
    eng.update(refs[0])
    score, t0, t1 = eng.read_stamped(0)
    assert np.isnan(score) and t0 is None and t1 is None
    before = time.monotonic()
    eng.tick()
    score, t0, t1 = eng.read_stamped(0)
    assert score == eng.read(0)
    assert before <= t0 <= t1 <= time.monotonic()
    eng.tick()                          # re-scores the same close
    assert eng.read_stamped(0)[1:] == (t0, t1)
    di.ingest(1.0, 0, "ecg",
              rng.standard_normal((3, 250)).astype(np.float32))
    eng.update(di.close_window(0, 2.0))
    eng.tick()                          # a newer close: new instants
    _, t0b, t1b = eng.read_stamped(0)
    assert t1 < t0b <= t1b


def test_slot_spans_carry_their_tick_and_flush_spans_do_not(zoo_members,
                                                           rng):
    di, refs = _census(zoo_members, rng)
    eng = SlotEngine(EnsembleService(zoo_members), di)
    rec = SpanRecorder()
    srv = EnsembleServer(engine="slots", slot_engine=eng, tracer=rec,
                         tick_interval=0.01, n_workers=2).start()
    for p, r in refs.items():
        assert srv.submit(p, r)
    assert srv.stop().failed == 0
    spans = rec.spans()
    assert len(spans) == 8
    for s in spans:
        assert s.t_submit <= s.t_tick0 <= s.t_tick1 <= s.t_retire
        assert {"t_tick0", "t_tick1"} <= set(s.to_json())

    flush = SpanRecorder()
    srv = EnsembleServer(batch_handler=lambda batch: [0.5] * len(batch),
                         n_workers=1, max_batch=4, max_wait_ms=1.0,
                         tracer=flush).start()
    for p in range(4):
        srv.submit(p, {})
    srv.stop()
    assert len(flush.spans()) == 4
    for s in flush.spans():
        assert s.t_tick0 is None and s.t_tick1 is None
        assert "t_tick0" not in s.to_json()


def test_profiler_trace_holds_the_program_spans(zoo_members, rng,
                                                tmp_path):
    import jax
    from jax.profiler import ProfileData
    di, refs = _census(zoo_members, rng)
    eng = SlotEngine(EnsembleService(zoo_members), di)
    for r in refs.values():
        eng.update(r)
    eng.tick()                              # compile outside the trace
    eng.on_tick = lambda r: None
    ticker = SlotTicker(eng, interval=0.01)
    jax.profiler.start_trace(str(tmp_path))
    try:
        ticker.start()
        deadline = time.monotonic() + 30.0
        while eng.tick_count < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert ticker.stop()
        di.ingest(2.0, 0, "ecg", np.zeros((3, 125), np.float32))
    finally:
        jax.profiler.stop_trace()
    assert eng.tick_count >= 3
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    want = {"slots.tick", "slots.ticker.sleep", "slots.tick.on_tick",
            "ingest.ecg", "PjitFunction(fn)"} | set(TICK_PHASES)
    assert want <= names, sorted(want - names)
