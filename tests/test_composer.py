"""Ensemble composer (Algorithm 1/2) unit + property tests."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.baselines import (accuracy_first, latency_first, npo,
                                  random_baseline)
from repro.core.bagging import bagging_predict, roc_auc
from repro.core.composer import ComposerParams, compose
from repro.core.genetic import explore, mutation, recombination
from repro.core.objective import (AccuracyConstrainedObjective,
                                  LatencyConstrainedObjective, hard_delta,
                                  soft_delta)


def make_testbed(n=16, n_val=300, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 2, n_val)
    quality = rng.uniform(0.3, 2.0, n)
    scores = np.stack([
        1 / (1 + np.exp(-(q * (2 * y - 1) + rng.normal(0, 2.0, n_val))))
        for q in quality])
    lat = rng.uniform(0.02, 0.12, n)

    def f_a(b):
        return roc_auc(y, bagging_predict(scores, b))

    def f_l(b):
        b = np.asarray(b, bool)
        return float(lat[b].sum() * 0.7 + 0.01)
    return n, f_a, f_l, lat, scores, y


# ------------------------------------------------------------ genetic
@given(st.integers(1, 30), st.integers(1, 5), st.integers(0, 10 ** 6))
@settings(max_examples=40, deadline=None)
def test_mutation_manhattan_distance(n, S, seed):
    rng = np.random.default_rng(seed)
    b = rng.integers(0, 2, n).astype(np.int8)
    out = mutation(b, S, rng)
    d = int(np.abs(out - b).sum())
    assert d == min(S, n)
    assert set(np.unique(out)) <= {0, 1}


@given(st.integers(2, 30), st.integers(0, 10 ** 6))
@settings(max_examples=40, deadline=None)
def test_recombination_prefix_suffix(n, seed):
    rng = np.random.default_rng(seed)
    b1 = rng.integers(0, 2, n).astype(np.int8)
    b2 = rng.integers(0, 2, n).astype(np.int8)
    out = recombination(b1, b2, rng)
    # every position comes from one parent
    assert np.all((out == b1) | (out == b2))


@given(st.integers(4, 20), st.integers(1, 50), st.integers(0, 10 ** 5))
@settings(max_examples=30, deadline=None)
def test_explore_no_duplicates(n, m, seed):
    rng = np.random.default_rng(seed)
    B = rng.integers(0, 2, (5, n)).astype(np.int8)
    out = explore(B, m, 2, 0.8, 0.5, rng)
    seen = {row.tobytes() for row in B}
    for row in out:
        key = row.tobytes()
        assert key not in seen
        seen.add(key)


# ------------------------------------------------------------ objective
def test_hard_delta():
    assert hard_delta(-0.01) == -np.inf
    assert hard_delta(0.0) == 0.0
    obj = LatencyConstrainedObjective(0.2)
    assert obj(0.9, 0.25) == -np.inf
    assert obj(0.9, 0.15) == 0.9


def test_soft_delta_one_sided():
    d = soft_delta(2.0)
    assert d(0.5) == 0.0          # slack is not rewarded
    assert d(-0.1) == pytest.approx(-0.2)


def test_accuracy_constrained_dual():
    obj = AccuracyConstrainedObjective(0.9)
    assert obj(0.95, 0.3) == pytest.approx(-0.3)
    assert obj(0.85, 0.1) == -np.inf


# ------------------------------------------------------------ bagging
@given(st.integers(1, 8), st.integers(5, 40), st.integers(0, 10 ** 5))
@settings(max_examples=30, deadline=None)
def test_bagging_bounds(n_models, n_samples, seed):
    rng = np.random.default_rng(seed)
    scores = rng.uniform(0, 1, (n_models, n_samples))
    b = rng.integers(0, 2, n_models)
    out = bagging_predict(scores, b)
    assert out.shape == (n_samples,)
    assert np.all(out >= 0) and np.all(out <= 1)
    if b.sum() == 1:
        np.testing.assert_allclose(out, scores[b.astype(bool)][0])


def test_roc_auc_perfect_and_random():
    y = np.asarray([0, 0, 1, 1])
    assert roc_auc(y, np.asarray([0.1, 0.2, 0.8, 0.9])) == 1.0
    assert roc_auc(y, np.asarray([0.9, 0.8, 0.2, 0.1])) == 0.0
    assert roc_auc(y, np.asarray([0.5, 0.5, 0.5, 0.5])) == 0.5


# ------------------------------------------------------------ composer
def test_composer_respects_hard_constraint():
    n, f_a, f_l, lat, _, _ = make_testbed()
    res = compose(n, f_a, f_l, latency_budget=0.15,
                  params=ComposerParams(N=6, M=60, K=4, N0=8, seed=3))
    assert res.feasible
    assert res.latency <= 0.15 + 1e-9
    assert f_l(res.b_star) == pytest.approx(res.latency)


def test_composer_beats_or_matches_singles():
    n, f_a, f_l, lat, scores, y = make_testbed(seed=2)
    budget = 0.2
    res = compose(n, f_a, f_l, budget,
                  params=ComposerParams(N=10, M=100, K=6, seed=2))
    best_single = max(
        f_a(np.eye(n, dtype=np.int8)[i]) for i in range(n)
        if lat[i] * 0.7 + 0.01 <= budget)
    assert res.accuracy >= best_single - 1e-9


def test_composer_infeasible_budget():
    n, f_a, f_l, *_ = make_testbed()
    res = compose(n, f_a, f_l, latency_budget=1e-6,
                  params=ComposerParams(N=3, M=30, K=3, seed=0))
    assert not res.feasible


@pytest.mark.parametrize("seed", [0, 1])
def test_baselines_and_composer_ordering(seed):
    """The paper's qualitative claim: HOLMES >= NPO on the final
    feasible accuracy, with the same profiler budget."""
    n, f_a, f_l, lat, scores, y = make_testbed(n=18, seed=seed)
    budget = 0.18
    single_acc = np.array([f_a(np.eye(n, dtype=np.int8)[i])
                           for i in range(n)])
    rd = random_baseline(n, f_a, f_l, budget, seed=seed)
    af = accuracy_first(n, f_a, f_l, budget, single_acc)
    lf = latency_first(n, f_a, f_l, budget, lat)
    warm = [r.b_star for r in (rd, af, lf)]
    calls = 10 * 6 + 12
    nr = npo(n, f_a, f_l, budget, max_subset=max(1, int(lf.b_star.sum())),
             n_calls=calls, seed=seed, warm_start=warm)
    hb = compose(n, f_a, f_l, budget,
                 ComposerParams(N=10, K=6, N0=12, seed=seed),
                 warm_start=warm)
    for r in (rd, af, lf, nr, hb):
        if r.feasible:
            assert r.latency <= budget + 1e-9
    assert hb.accuracy >= nr.accuracy - 0.005


def test_surrogate_r2_improves():
    """Fig. 8: surrogate R2 on an independent UNexplored validation set
    (drawn from the same small-ensemble regime the search explores —
    random forests cannot extrapolate outside the visited size range)."""
    n, f_a, f_l, *_ = make_testbed(n=16, seed=4)
    rng = np.random.default_rng(0)
    held = []
    for _ in range(50):
        size = int(rng.integers(1, max(2, n // 2)))
        b = np.zeros(n, np.int8)
        b[rng.choice(n, size=size, replace=False)] = 1
        held.append(b)
    held = np.stack(held)
    ha = np.asarray([f_a(b) for b in held])
    hl = np.asarray([f_l(b) for b in held])
    res = compose(n, f_a, f_l, 0.2,
                  ComposerParams(N=12, M=80, K=8, seed=0),
                  heldout_B=held, heldout_acc=ha, heldout_lat=hl)
    r2_last = max(h["r2_lat"] for h in res.history[-3:])
    r2_acc_last = max(h["r2_acc"] for h in res.history[-3:])
    assert r2_last > 0.5                  # latency surrogate is good
    assert r2_acc_last > max(
        h["r2_acc"] for h in res.history[:1]) - 0.1
