"""Serving layer: network calculus, DES simulator, aggregators, queues,
placement — including the property that the network-calculus T_q bound
dominates empirical queueing delay."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.profiles import ModelProfile, ModelZoo, SystemConfig
from repro.serving.aggregator import (AggState, ModalitySpec,
                                      PatientAggregator, agg_init,
                                      ingest_step, read_window_static)
from repro.serving.latency import (LatencyProfiler, arrival_curve,
                                   max_horizontal_distance, queueing_bound,
                                   service_curve)
from repro.serving.placement import (Placement, lpt_placement,
                                     plan_pod_ensemble)
from repro.serving.queues import TimestampedQueue
from repro.serving.simulator import SimConfig, simulate


# ------------------------------------------------------ network calculus
def test_arrival_curve_monotone():
    arr = np.sort(np.random.default_rng(0).uniform(0, 10, 50))
    dts = np.linspace(0, 10, 20)
    a = arrival_curve(arr, dts)
    assert np.all(np.diff(a) >= 0)
    assert a[-1] >= 50 - 1       # window of full span catches everything


def test_service_curve():
    dts = np.asarray([0.0, 1.0, 2.0])
    np.testing.assert_allclose(service_curve(2.0, 0.5, dts),
                               [0.0, 1.0, 3.0])


@given(st.integers(2, 40), st.floats(5.0, 100.0), st.integers(0, 10 ** 5))
@settings(max_examples=25, deadline=None)
def test_tq_bound_dominates_empirical(n_patients, mu, seed):
    """Property: the network-calculus bound >= the DES-observed max
    queueing delay, for a single-server queue at rate mu."""
    cfg = SimConfig(n_patients=n_patients, n_devices=1,
                    window_seconds=10.0, duration_seconds=60.0, seed=seed,
                    dispatch_overhead=0.0)
    cost = 1.0 / mu
    lam = n_patients / cfg.window_seconds
    if lam >= mu * 0.9:          # keep the queue stable
        return
    res = simulate([cost], cfg)
    if not len(res.queries):
        return
    bound = queueing_bound(res.arrivals, mu, cost)
    assert res.queue_delays().max() <= bound + 1e-6


def test_horizontal_distance_closed_form():
    dts = np.linspace(0, 10, 101)
    alpha = np.minimum(5 + 2 * dts, 40.0)
    h = max_horizontal_distance(dts, alpha, mu=4.0, T0=0.1)
    want = max(0.1 + alpha / 4.0 - dts)
    assert h == pytest.approx(want)


# ------------------------------------------------------------ simulator
def test_simulator_latency_scales_with_patients():
    lat = []
    for n in (8, 64, 256):
        cfg = SimConfig(n_patients=n, n_devices=2, duration_seconds=90,
                        window_seconds=10, seed=1)
        r = simulate([0.02, 0.03], cfg)
        lat.append(r.p(95))
    assert lat[2] >= lat[0]       # more load, no faster


def test_simulator_more_devices_not_slower():
    cfg1 = SimConfig(n_patients=128, n_devices=1, duration_seconds=60,
                     window_seconds=10)
    cfg2 = SimConfig(n_patients=128, n_devices=4, duration_seconds=60,
                     window_seconds=10)
    c = [0.02, 0.02, 0.02]
    assert simulate(c, cfg2).p(95) <= simulate(c, cfg1).p(95) + 1e-9


def test_offline_batching_order_of_magnitude_slower():
    costs = [0.02]
    online = simulate(costs, SimConfig(n_patients=1, duration_seconds=600,
                                       window_seconds=30))
    offline = simulate(costs, SimConfig(n_patients=1, duration_seconds=600,
                                        window_seconds=30,
                                        batch_period=600))
    assert offline.p(95) > 10 * online.p(95)


# ------------------------------------------------------------ aggregator
def test_patient_aggregator_alignment():
    mods = [ModalitySpec("ecg", 10.0, 2), ModalitySpec("vitals", 1.0, 3)]
    agg = PatientAggregator(mods, window_seconds=5.0)
    for t in range(50):                   # 10 Hz ecg
        agg.ingest(t * 0.1, "ecg", np.ones((2, 1)) * t)
    for t in range(5):                    # 1 Hz vitals
        agg.ingest(float(t), "vitals", np.ones((3, 1)) * t)
    assert agg.window_ready(5.0)
    w = agg.pop_window(5.0)
    assert w["ecg"].shape == (2, 50)
    assert w["vitals"].shape == (3, 5)


def test_patient_aggregator_missing_data_zero_fill():
    mods = [ModalitySpec("ecg", 10.0, 1)]
    agg = PatientAggregator(mods, window_seconds=2.0)
    agg.ingest(0.0, "ecg", np.ones((1, 3)))
    agg.ingest(2.0, "ecg", np.ones((1, 1)))
    w = agg.pop_window(2.0)
    assert w["ecg"].shape == (1, 20)      # padded to nominal count


def test_jit_ring_buffer_roundtrip():
    import jax.numpy as jnp
    st_ = agg_init(n_patients=2, channels=1, capacity=8)
    for i in range(12):                   # wraps the ring
        st_ = ingest_step(st_, jnp.asarray(0),
                          jnp.asarray([[float(i)]]))
    w = read_window_static(st_, 0, 4)
    np.testing.assert_allclose(np.asarray(w)[0], [8.0, 9.0, 10.0, 11.0])


# ------------------------------------------------------------ queues
def test_queue_wait_stats():
    q = TimestampedQueue()
    q.push(0.0, "a")
    q.push(1.0, "b")
    assert q.pop(2.0) == "a"
    assert q.pop(2.5) == "b"
    assert q.stats.mean_wait == pytest.approx((2.0 + 1.5) / 2)
    assert q.pop(3.0) is None


# ------------------------------------------------------------ placement
@given(st.lists(st.floats(0.001, 1.0), min_size=1, max_size=20),
       st.integers(1, 8))
@settings(max_examples=30, deadline=None)
def test_lpt_makespan_bound(costs, k):
    pl = lpt_placement(costs, k)
    # list-scheduling guarantee: makespan <= sum/k + (1 - 1/k) * max
    assert pl.makespan <= sum(costs) / k \
        + (1 - 1 / k) * max(costs) + 1e-9
    assert pl.makespan >= max(max(costs), sum(costs) / k) - 1e-9
    placed = sorted(i for dev in pl.assignment for i in dev)
    assert placed == list(range(len(costs)))


def test_plan_pod_ensemble():
    out = plan_pod_ensemble({"a": 1.0, "b": 0.9, "c": 0.1}, 2)
    assert set(out.values()) <= {0, 1}
    assert out["a"] != out["b"]           # two heavy members split


# ------------------------------------------------------------ profiler
def _tiny_zoo():
    profs = [ModelProfile(f"m{i}", depth=2, width=8, macs=1e6 * (i + 1),
                          memory_bytes=1e6, modality=0, input_len=100,
                          val_auc=0.8) for i in range(4)]
    return ModelZoo(profs)


def test_latency_profiler_monotone_in_ensemble_size():
    prof = LatencyProfiler(_tiny_zoo(), SystemConfig(n_devices=2,
                                                     n_patients=16))
    l1 = prof(np.asarray([1, 0, 0, 0]))
    l2 = prof(np.asarray([1, 1, 1, 1]))
    assert l2 >= l1


def test_latency_profiler_memory_infeasible():
    zoo = _tiny_zoo()
    cfgc = SystemConfig(n_devices=1, device_mem_bytes=2e6)
    prof = LatencyProfiler(zoo, cfgc)
    assert prof(np.asarray([1, 1, 1, 1])) >= prof.infeasible_latency


def test_latency_profiler_unstable_queue():
    prof = LatencyProfiler(
        _tiny_zoo(), SystemConfig(n_devices=1, n_patients=10_000,
                                  window_seconds=1.0),
        cost_fn=lambda i: 0.01)
    assert prof(np.asarray([1, 1, 1, 1])) >= prof.infeasible_latency


def test_latency_profiler_call_threads_active_placement():
    """REGRESSION: ``__call__`` used to compute T_s from a FRESH LPT
    plan even when the caller held the ACTIVE placement — e.g. the
    deliberately unbalanced interim plan installed by failover — so the
    estimate understated latency exactly when the controller's risk
    prediction mattered most.  Pre-fix this call raised TypeError
    (no ``placement=`` parameter)."""
    cfg = SystemConfig(n_devices=2, n_patients=4, window_seconds=10.0)
    prof = LatencyProfiler(_tiny_zoo(), cfg,
                           cost_fn=lambda i: 0.01 * (i + 1))
    b = np.asarray([1, 1, 1, 1])
    skewed = Placement(assignment=[[0, 1, 2, 3], []], loads=[0.1, 0.0])
    assert prof.serving_latency(b, placement=skewed) \
        > prof.serving_latency(b)
    assert prof(b, placement=skewed) > prof(b)


def test_latency_profiler_hetero_speeds():
    """Heterogeneous pool: mu = sum(speeds)/sum(costs), and a
    speed-aware T_s plan beats the homogeneous one when one device is
    4x faster.  Unit speeds reduce to the default exactly."""
    cfg = SystemConfig(n_devices=2, n_patients=4, window_seconds=10.0)
    cost = lambda i: 0.1 * (i + 1)                      # noqa: E731
    b = np.asarray([1, 1, 1, 1])
    base = LatencyProfiler(_tiny_zoo(), cfg, cost_fn=cost)
    fast = LatencyProfiler(_tiny_zoo(), cfg, cost_fn=cost,
                           device_speeds=[1.0, 4.0])
    unit = LatencyProfiler(_tiny_zoo(), cfg, cost_fn=cost,
                           device_speeds=[1.0, 1.0])
    assert base.throughput(b) == pytest.approx(2.0 / 1.0)
    assert fast.throughput(b) == pytest.approx(5.0 / 1.0)
    assert fast.serving_latency(b) < base.serving_latency(b)
    assert unit.serving_latency(b) == base.serving_latency(b)
    assert unit.throughput(b) == base.throughput(b)


def test_latency_profiler_rejects_bad_speed_length():
    prof = LatencyProfiler(_tiny_zoo(), SystemConfig(n_devices=2),
                           device_speeds=[1.0])
    with pytest.raises(ValueError):
        prof.throughput(np.asarray([1, 0, 0, 0]))
