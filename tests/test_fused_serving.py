"""Fused stacked-member ensemble serving + cross-patient micro-batching:

* bucketed/stacked ``predict`` must match the per-member loop oracle
  (``conftest.MemberLoop``) to 1e-5;
* ``predict_batch`` must match per-patient ``predict``;
* dispatch counts collapse from n_members to n_buckets;
* ``MicroBatcher`` flush semantics (max_batch / max_wait bounds);
* batch-aware ``EnsembleServer`` workers and the ``drain()`` race fix.
"""
import threading
import time

import numpy as np
import pytest

from repro.configs.ecg_zoo import bucket_key, bucket_zoo, zoo_specs
from repro.serving.pipeline import EnsembleService
from repro.serving.queues import MicroBatcher
from repro.serving.server import EnsembleServer


# ------------------------------------------------------------- bucketing
def test_reduced_zoo_buckets_4():
    specs = zoo_specs(reduced=True)
    buckets = bucket_zoo(specs)
    assert len(buckets) == 4                    # 2 widths x 2 block counts
    assert sorted(i for idx in buckets.values() for i in idx) \
        == list(range(12))
    for key, idx in buckets.items():
        assert len(idx) == 3                    # the 3 leads fold in
        assert {bucket_key(specs[i]) for i in idx} == {key}


def test_full_zoo_buckets_20():
    assert len(bucket_zoo(zoo_specs(reduced=False))) == 20


# ----------------------------------------------------------- equivalence
@pytest.fixture(scope="module")
def services(zoo_members, member_loop):
    return EnsembleService(zoo_members), member_loop(zoo_members)


def _windows(rng, n=1, L=250):
    return [{"ecg": rng.standard_normal((3, L)).astype(np.float32)}
            for _ in range(n)]


def test_fused_predict_matches_member_loop(services, rng):
    fused, loop = services
    for w in _windows(rng, n=3):
        assert fused.predict(w) == pytest.approx(loop.predict(w),
                                                 abs=1e-5)


def test_predict_batch_matches_per_patient_predict(services, rng):
    fused, _ = services
    batch = _windows(rng, n=5)
    got = fused.predict_batch(batch)
    want = [fused.predict(w) for w in batch]
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_fused_dispatch_count_is_n_buckets(services, rng):
    fused, loop = services
    batch = _windows(rng, n=4)
    d0 = fused.dispatch_count
    fused.predict_batch(batch)
    assert fused.dispatch_count - d0 == fused.n_buckets == 4
    d0 = loop.dispatch_count
    loop.predict(batch[0])
    assert loop.dispatch_count - d0 == len(loop.members) == 12


def test_fused_with_cpu_side_models(zoo_members, member_loop, rng):
    class Const:
        def __init__(self, v):
            self.v = v

        def predict_proba(self, x):
            return np.full(len(x), self.v)

    svc = EnsembleService(zoo_members, vitals_model=Const(0.9),
                          labs_model=Const(0.1))
    ref = member_loop(zoo_members, vitals_model=Const(0.9),
                      labs_model=Const(0.1))
    w = _windows(rng)[0]
    w["vitals"] = rng.standard_normal((7, 3)).astype(np.float32)
    w["labs"] = rng.standard_normal(8).astype(np.float32)
    assert svc.predict(w) == pytest.approx(ref.predict(w), abs=1e-5)
    no_labs = {k: v for k, v in w.items() if k != "labs"}
    assert svc.predict(no_labs) == pytest.approx(ref.predict(no_labs),
                                                 abs=1e-5)


def test_measured_costs_builds_member_programs_on_first_call(zoo_members):
    """Serving never runs a member alone, so a service holds no
    per-member program until ``measured_costs`` first asks for one;
    later calls reuse them."""
    svc = EnsembleService(zoo_members)
    assert svc._member_fns is None
    costs = svc.measured_costs(reps=1)
    assert len(costs) == len(zoo_members) and min(costs) > 0
    fns = svc._member_fns
    assert len(fns) == len(zoo_members)
    svc.measured_costs(reps=1)
    assert svc._member_fns is fns


def test_empty_batch():
    assert EnsembleService([]).predict_batch([]) == []


def test_short_window_zero_padded_both_paths(services, rng):
    """ECG windows shorter than input_len are left-zero-filled (the
    aggregator convention) by the service and by the member-loop
    oracle, and they still agree."""
    fused, loop = services
    w = {"ecg": rng.standard_normal((3, 100)).astype(np.float32)}
    got = fused.predict(w)
    assert 0.0 <= got <= 1.0
    assert got == pytest.approx(loop.predict(w), abs=1e-5)


# ---------------------------------------------------------- MicroBatcher
def test_microbatcher_flushes_on_max_batch():
    t = [0.0]
    mb = MicroBatcher(max_batch=3, max_wait_ms=1e6, clock=lambda: t[0])
    mb.push("a"), mb.push("b")
    assert not mb.ready()
    mb.push("c")
    assert mb.ready()
    assert mb.pop_batch() == ["a", "b", "c"]
    assert not mb.ready() and len(mb) == 0


def test_microbatcher_flushes_on_max_wait():
    t = [0.0]
    mb = MicroBatcher(max_batch=100, max_wait_ms=5.0, clock=lambda: t[0])
    mb.push("a")
    assert not mb.ready()
    t[0] = 0.006                              # oldest waited > 5 ms
    assert mb.ready()
    assert mb.pop_batch() == ["a"]


def test_microbatcher_pop_bounded_and_stats():
    t = [0.0]
    mb = MicroBatcher(max_batch=2, max_wait_ms=0.0, clock=lambda: t[0])
    for i in range(5):
        mb.push(i)
    assert mb.pop_batch() == [0, 1]
    assert mb.pop_batch() == [2, 3]
    assert mb.pop_batch() == [4]
    assert mb.pop_batch() == []
    assert mb.stats.n_items == 5
    assert mb.stats.n_flushes == 3
    assert mb.stats.max_batch_seen == 2
    assert mb.stats.mean_batch == pytest.approx(5 / 3)


# -------------------------------------------------- batch-aware server
def test_server_batched_handler_serves_all():
    seen_batches = []

    def batch_handler(windows):
        seen_batches.append(len(windows))
        time.sleep(0.002)
        return [float(w["x"]) for w in windows]

    srv = EnsembleServer(batch_handler=batch_handler, n_workers=2,
                         max_batch=4, max_wait_ms=2.0).start()
    n = 32
    for i in range(n):
        assert srv.submit(i, {"x": i})
    stats = srv.stop()
    assert stats.served == n
    assert sum(seen_batches) == n
    got = sorted(srv.results())
    assert [p for p, *_ in got] == list(range(n))
    for p, score, *_ in got:
        assert score == float(p)              # right answer to right query
    assert max(seen_batches) > 1              # coalescing actually happened


def test_server_batched_poison_query_isolated():
    """One bad query must not kill the worker, drop its co-batched
    healthy queries, or hang stop() on un-retired tasks."""
    def batch_handler(windows):
        if any(w.get("bad") for w in windows):
            raise ValueError("poison window")
        return [1.0] * len(windows)

    srv = EnsembleServer(batch_handler=batch_handler, n_workers=1,
                         max_batch=4, max_wait_ms=50.0).start()
    for i in range(8):
        srv.submit(i, {"bad": i == 3})
    t0 = time.monotonic()
    stats = srv.stop()
    assert time.monotonic() - t0 < 5.0        # no drain-timeout hang
    assert stats.served == 8
    scores = {p: s for p, s, *_ in srv.results()}
    assert np.isnan(scores[3])
    assert all(scores[p] == 1.0 for p in scores if p != 3)


def test_server_scalar_handler_still_works():
    srv = EnsembleServer(handler=lambda w: 0.5, n_workers=2).start()
    for i in range(8):
        srv.submit(i, {})
    stats = srv.stop()
    assert stats.served == 8


def test_server_drain_waits_for_inflight_handler():
    """A slow handler must be COUNTED by stop(): drain() waits for
    unfinished tasks, not just an empty ingest queue."""
    release = threading.Event()

    def handler(w):
        release.wait(timeout=5.0)
        return 1.0

    srv = EnsembleServer(handler=handler, n_workers=1).start()
    srv.submit(0, {})
    time.sleep(0.2)              # worker popped it; queue now empty
    assert srv.q.empty()
    threading.Timer(0.1, release.set).start()
    stats = srv.stop()           # must wait for the in-flight handler
    assert stats.served == 1


def test_server_drain_timeout_returns():
    srv = EnsembleServer(handler=lambda w: time.sleep(1.0) or 0.0,
                         n_workers=1).start()
    srv.submit(0, {})
    t0 = time.monotonic()
    srv.drain(timeout=0.05)
    assert time.monotonic() - t0 < 0.5
    srv.stop()
