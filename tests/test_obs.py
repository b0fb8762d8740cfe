"""Observability plane: windowed-sketch telemetry vs the exact deque
oracle, per-query span tracing, and the Prometheus/JSONL export layer.

The sketch's contract (obs/sketch.py) is precise, so these tests gate
it precisely: event counts and violation rate EXACT, quantiles within
the log-histogram's relative-error bound, T_q within one sub-window
bucket, merges associative with the flat feed — on randomized
out-of-order traces, not hand-picked ones.
"""
import json
import time
import urllib.request

import numpy as np
import pytest

from repro.control.telemetry import SloTelemetry, TieredTelemetry
from repro.obs.sketch import REL_ERR_BOUND, WindowedSketch
from repro.obs.spans import SpanRecord, SpanRecorder, collect, note

SLO = 0.3
WINDOW = 20.0


def _feed(rng, engines, n=3000, jitter=0.5):
    """Randomized trace with OUT-OF-ORDER timestamps (within-window
    jitter): every engine sees the identical event stream."""
    t = 0.0
    last = 0.0
    for _ in range(n):
        t += float(rng.exponential(0.01))
        tt = t + float(rng.uniform(-jitter, 0.0))   # late arrivals
        tt = max(tt, last - jitter)
        lat = float(rng.lognormal(-2.0, 0.7))
        kind = rng.uniform()
        for eng in engines:
            eng.record_arrival(tt)
            if kind < 0.85:
                eng.record_served(lat, tt)
            elif kind < 0.95:
                eng.record_shed(tt)
            else:
                eng.record_failure(tt)
        last = max(last, tt)
    return t


def _pair(clock):
    sk = SloTelemetry(SLO, WINDOW, clock=clock, exact=False)
    ex = SloTelemetry(SLO, WINDOW, clock=clock, exact=True)
    return sk, ex


# ------------------------------------------------- sketch equivalence
def test_sketch_counts_and_violation_rate_exact():
    """Counts and violation rate are EXACT (not approximate): the
    sketch's counters are plain sums, only quantiles are coarsened."""
    t = 0.0
    sk, ex = _pair(lambda: t)
    rng = np.random.default_rng(0)
    t = _feed(rng, (sk, ex))
    s, e = sk.snapshot(), ex.snapshot()
    assert s.n_arrivals == e.n_arrivals > 0
    assert s.n_served == e.n_served > 0
    assert s.n_shed == e.n_shed > 0
    assert s.n_failed == e.n_failed > 0
    assert s.violation_rate == pytest.approx(e.violation_rate, abs=1e-12)
    assert s.arrival_rate == pytest.approx(e.arrival_rate, rel=1e-9)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sketch_quantiles_within_histogram_bound(seed):
    t = 0.0
    sk, ex = _pair(lambda: t)
    rng = np.random.default_rng(seed)
    t = _feed(rng, (sk, ex))
    s, e = sk.snapshot(), ex.snapshot()
    assert s.p50 == pytest.approx(e.p50, rel=REL_ERR_BOUND)
    assert s.p99 == pytest.approx(e.p99, rel=REL_ERR_BOUND)


def test_sketch_tq_bound_within_one_bucket():
    """|sketch T_q - exact T_q| <= one sub-window bucket width, both
    directions (the sketch's mean-grouped trace can under- or
    over-state a burst by at most its within-bucket spread)."""
    t = 0.0
    sk, ex = _pair(lambda: t)
    rng = np.random.default_rng(3)
    t = _feed(rng, (sk, ex), n=2000)
    bw = sk.window / sk.n_buckets
    for mu in (50.0, 100.0, 200.0, 500.0):
        d = sk.queueing_bound(mu, 0.01) - ex.queueing_bound(mu, 0.01)
        assert abs(d) <= bw + 1e-9, (mu, d, bw)


def test_sketch_since_cut_matches_exact_within_one_bucket():
    """snapshot(since=...) on the sketch cuts on bucket boundaries:
    counts differ from the exact cut by at most the events of ONE
    bucket."""
    t = 0.0
    sk, ex = _pair(lambda: t)
    rng = np.random.default_rng(4)
    t = _feed(rng, (sk, ex), n=2000, jitter=0.0)
    since = t - 5.0
    s = sk.snapshot(since=since)
    e = ex.snapshot(since=since)
    bw = sk.window / sk.n_buckets
    # events in one bucket ~ n / (span/bw); be generous: 3 buckets
    slack = 3 * max(1, int(e.n_arrivals * bw / 5.0))
    assert abs(s.n_arrivals - e.n_arrivals) <= slack
    assert s.violation_rate == pytest.approx(e.violation_rate, abs=0.05)


def test_sketch_merge_equals_flat_feed():
    """merge(tier slices) == one flat-fed sketch: same counters, same
    histogram — the fleet view is a real reduction, not an estimate."""
    t = 0.0
    clock = lambda: t
    parts = [SloTelemetry(SLO, WINDOW, clock=clock) for _ in range(3)]
    flat = SloTelemetry(SLO, WINDOW, clock=clock)
    rng = np.random.default_rng(5)
    for _ in range(2000):
        t += float(rng.exponential(0.01))
        lat = float(rng.lognormal(-2.0, 0.7))
        p = parts[int(rng.integers(3))]
        for eng in (p, flat):
            eng.record_arrival(t)
            eng.record_served(lat, t)
    merged = SloTelemetry.merge(parts)
    m, f = merged.snapshot(), flat.snapshot()
    assert m.n_arrivals == f.n_arrivals
    assert m.n_served == f.n_served
    assert m.p50 == pytest.approx(f.p50, rel=1e-9)
    assert m.p99 == pytest.approx(f.p99, rel=1e-9)
    np.testing.assert_allclose(merged.latency_histogram(),
                               flat.latency_histogram())


def test_merge_rejects_mismatched_config():
    a = SloTelemetry(SLO, WINDOW)
    b = SloTelemetry(SLO, WINDOW * 2)
    with pytest.raises(ValueError):
        SloTelemetry.merge([a, b])
    with pytest.raises(ValueError):
        SloTelemetry.merge([a, SloTelemetry(SLO, WINDOW, exact=True)])


def test_tiered_fleet_is_derived_merge():
    t = 0.0
    tel = TieredTelemetry(lambda p: "crit" if p % 2 else "stable",
                          ("stable", "crit"), slo_seconds=SLO,
                          window_seconds=WINDOW, clock=lambda: t)
    rng = np.random.default_rng(6)
    for _ in range(500):
        t += float(rng.exponential(0.02))
        p = int(rng.integers(8))
        tel.record_arrival(t, patient=p)
        tel.record_served(float(rng.lognormal(-2.0, 0.5)), t, patient=p)
    fleet = tel.snapshot()
    by_tier = [tel.tier_snapshot(x) for x in ("stable", "crit")]
    assert fleet.n_arrivals == sum(s.n_arrivals for s in by_tier) == 500
    assert fleet.n_served == sum(s.n_served for s in by_tier)


# --------------------------------------------------------- O(1) memory
def test_sketch_memory_constant_over_100x_window():
    """A trace >= 100x the window leaves the sketch's arrays at their
    construction shape — O(1) in trace length, O(n_buckets) in space —
    while the exact oracle's logs would hold the full window."""
    sk = WindowedSketch(window_seconds=10.0, n_buckets=64)
    shape0 = (sk.counts.shape, sk.hist.shape)
    nbytes0 = sk.counts.nbytes + sk.hist.nbytes
    rng = np.random.default_rng(7)
    t = 0.0
    from repro.obs.sketch import ARRIVALS, SERVED
    for _ in range(20000):                       # ~200x the window
        t += float(rng.exponential(0.05))
        sk.add(ARRIVALS, t)
        sk.add(SERVED, t, latency=float(rng.lognormal(-2.0, 0.5)))
    assert (sk.counts.shape, sk.hist.shape) == shape0
    assert sk.counts.nbytes + sk.hist.nbytes == nbytes0
    # and it still answers: only ~window/mean_gap events remain live
    tot = sk.totals(t)
    assert 0 < tot[0] <= 10.0 / 0.05 * 1.5


def test_telemetry_sketch_mode_has_no_event_logs():
    tel = SloTelemetry(SLO, WINDOW, exact=False)
    with pytest.raises(AttributeError):
        tel._arrivals                      # oracle-only introspection
    assert SloTelemetry(SLO, WINDOW, exact=True)._arrivals is not None


# ------------------------------------------- exact engine (since cuts)
def test_exact_engine_since_cut_is_bisect_correct():
    """The head-offset/bisect since-cut must agree with brute-force
    filtering for arbitrary since positions."""
    t = 0.0
    tel = SloTelemetry(SLO, 1000.0, clock=lambda: t, exact=True)
    rng = np.random.default_rng(8)
    ts = np.sort(rng.uniform(0, 100, 500))
    for x in ts:
        t = float(x)
        tel.record_arrival(t)
        tel.record_served(0.1, t)
    for since in (-1.0, 0.0, 17.3, 50.0, 99.9, 200.0):
        snap = tel.snapshot(since=since)
        want = int(np.sum(ts > since))
        assert snap.n_arrivals == want, since
        assert snap.n_served == want, since


# ------------------------------------------------------------- spans
def test_note_outside_collect_is_noop():
    note("marshal", 1.0)                           # must not raise
    with collect() as acc:
        note("marshal", 0.25)
        note("marshal", 0.25)
        note("gather", 0.1)
    assert acc == {"marshal": 0.5, "gather": 0.1}


def test_collect_reentrancy_folds_into_outer():
    with collect() as outer:
        with collect() as inner:
            note("dispatch", 0.2)
        assert inner is outer
    assert outer == {"dispatch": 0.2}


def _span(status="ok", t0=0.0):
    return SpanRecord(patient=1, tier=None, status=status,
                      t_submit=t0, t_dequeue=t0 + 0.1,
                      t_flush=t0 + 0.15, t_retire=t0 + 0.55, batch_n=4,
                      marshal_s=0.05, dispatch_s=0.25, gather_s=0.08)


def test_span_record_telescopes():
    s = _span()
    assert s.queue_s == pytest.approx(0.1)
    assert s.coalesce_s == pytest.approx(0.05)
    assert s.service_s == pytest.approx(0.4)
    assert s.e2e_s == pytest.approx(0.55)
    # service stages are a subset of service_s
    assert s.marshal_s + s.dispatch_s + s.gather_s <= s.service_s + 1e-9


def test_recorder_attribution_and_coverage():
    rec = SpanRecorder(keep=16)
    for i in range(40):                     # > keep: ring must bound
        rec.record(_span(t0=float(i)))
    assert rec.n_spans == 40
    assert len(rec.spans()) == 16
    att = rec.attribution()
    assert att["n_spans"] == 40
    assert att["by_status"] == {"ok": 40}
    # every stage measured -> coverage explains e2e fully here
    measured = sum(att["stage_seconds"].values())
    assert att["coverage"] == pytest.approx(measured / att["e2e_seconds"])
    assert 0.0 < att["coverage"] <= 1.0 + 1e-9


def test_server_emits_spans_with_failure_statuses():
    """End-to-end through a real EnsembleServer: ok spans from normal
    queries, a 'failed' span for a NaN score, and a 'watchdog' span for
    a stalled co-batch."""
    from repro.serving.server import EnsembleServer

    rec = SpanRecorder()
    stall = {"on": False}

    def handler(batch):
        with collect():
            note("marshal", 0.001)
        if stall["on"]:
            time.sleep(1.0)                      # > deadline
        return [float("nan") if w.get("poison") else 1.0
                for w in batch]

    srv = EnsembleServer(batch_handler=handler, n_workers=1,
                         max_batch=4, max_wait_ms=1.0,
                         deadline_seconds=0.2, watchdog_interval=0.02,
                         tracer=rec).start()
    for p in range(4):
        srv.submit(p, {})
    srv.submit(99, {"poison": True})
    srv.drain(timeout=10.0)
    stall["on"] = True
    srv.submit(7, {})
    deadline = time.monotonic() + 5.0
    while "watchdog" not in rec.n_by_status \
            and time.monotonic() < deadline:
        time.sleep(0.02)
    stall["on"] = False
    srv.stop()
    statuses = rec.attribution()["by_status"]
    assert statuses.get("ok", 0) >= 4
    assert statuses.get("failed", 0) >= 1
    assert statuses.get("watchdog", 0) >= 1


# ------------------------------------------------------------- export
def _traced_server():
    from repro.obs.export import MetricsExporter
    from repro.serving.server import EnsembleServer

    tel = SloTelemetry(1.0, 10.0)
    rec = SpanRecorder()
    srv = EnsembleServer(batch_handler=lambda b: [1.0] * len(b),
                         n_workers=1, telemetry=tel, tracer=rec).start()
    for p in range(6):
        srv.submit(p, {})
    srv.drain(timeout=10.0)
    srv.stop()
    return MetricsExporter(server=srv, telemetry=tel, tracer=rec), rec


def test_prometheus_render_format():
    exporter, _ = _traced_server()
    text = exporter.render()
    lines = text.splitlines()
    assert any(l.startswith("# TYPE holmes_served_total counter")
               for l in lines)
    assert any(l.startswith("holmes_served_total 6") for l in lines)
    assert any(l.startswith("holmes_window_p99{tier=\"fleet\"}")
               for l in lines)
    assert any("holmes_latency_seconds_bucket{le=" in l for l in lines)
    assert any(l.startswith("holmes_span_stage_seconds_total"
                            "{stage=\"queue\"}") for l in lines)
    # exposition discipline: every non-comment line is "name value"
    for l in lines:
        if not l or l.startswith("#"):
            continue
        name, _, val = l.rpartition(" ")
        assert name and (val == "NaN" or float(val) == float(val))


def test_metrics_http_endpoint_scrapes():
    from repro.obs.export import start_metrics_server
    exporter, _ = _traced_server()
    httpd = start_metrics_server(exporter, port=0)
    try:
        base = f"http://127.0.0.1:{httpd.server_port}"
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            assert r.status == 200
            body = r.read().decode()
        assert "holmes_served_total 6" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope", timeout=10)
    finally:
        httpd.shutdown()


def test_jsonl_span_export_round_trips(tmp_path):
    from repro.obs.export import write_spans_jsonl
    _, rec = _traced_server()
    path = tmp_path / "spans.jsonl"
    n = write_spans_jsonl(rec, str(path))
    assert n == 6
    rows = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(rows) == 6
    for row in rows:
        assert row["status"] == "ok"
        assert row["e2e_s"] >= row["queue"] >= 0.0


# ---------------------------------------- controller decisions parity
@pytest.mark.slow
def test_controller_decisions_identical_under_sketch():
    """The acceptance criterion end-to-end: seeded DES runs driven by
    the sketch take the SAME action log as under the exact oracle."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.adaptive_bench import (run_adaptive_sim,
                                           synthetic_testbed)
    zoo, costs, f_a = synthetic_testbed(seed=0)
    sched = [(3, 16), (4, 48), (3, 16)]
    runs = [run_adaptive_sim(zoo, costs, f_a, 1.0, sched, adaptive=True,
                             seed=0, telemetry_exact=exact)
            for exact in (False, True)]
    assert runs[0]["actions"] == runs[1]["actions"]
    assert runs[0]["actions"], "run took no actions — nothing compared"
