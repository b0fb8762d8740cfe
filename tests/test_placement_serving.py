"""Multi-device sharded ensemble placement: the controller-actuated
placement dimension, verified by a device-count-parametrized harness.

Three layers:

* pure LPT invariants (always run): member conservation, load/cost
  accounting, imbalance >= 1, makespan <= serial cost, monotone
  non-increasing makespan in device count, stability under duplicate
  costs — property-based via hypothesis;
* ``multi_device``-marked wall-clock tests (need 8 forced host
  devices): sharded ``predict``/``predict_batch`` bitwise-equal to the
  single-device path for every ladder selector at 1/2/4/8 devices,
  shard params actually pinned per plan, ``(selector, placement)``
  staging cache semantics, and zero-drop hot-swaps across placement
  changes with post-swap bitwise equality;
* a subprocess wrapper that, in the default single-device lane,
  re-runs the ``multi_device`` selection in a child process with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — so the
  sharded hot path is exercised on every tier-1 run, not only in the
  CI multi-device lane.
"""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import jax

from repro.configs.ecg_zoo import bucket_zoo
from repro.serving.placement import (Placement, finish_imbalance,
                                     grouped_lpt_placement,
                                     lpt_placement, placement_signature,
                                     plan_pod_ensemble)
from repro.serving.pipeline import PLAN_BATCH, EnsembleService

N_FORCED = 8
IN_LANE = jax.device_count() >= N_FORCED
multi_device = pytest.mark.multi_device
needs_devices = pytest.mark.skipif(
    not IN_LANE,
    reason=f"needs {N_FORCED} forced host devices (CI lane or the "
           "subprocess wrapper below)")


# ---------------------------------------------------- LPT property tests
@given(st.lists(st.floats(0.001, 1.0), min_size=1, max_size=24),
       st.integers(1, 8))
@settings(max_examples=40, deadline=None)
def test_lpt_conserves_members_and_loads(costs, k):
    pl = lpt_placement(costs, k)
    # every member assigned exactly once
    placed = sorted(i for slot in pl.assignment for i in slot)
    assert placed == list(range(len(costs)))
    # per-slot loads are exactly the sums of the assigned costs
    for slot, load in zip(pl.assignment, pl.loads):
        assert load == pytest.approx(sum(costs[i] for i in slot))
    assert sum(pl.loads) == pytest.approx(sum(costs))


@given(st.lists(st.floats(0.001, 1.0), min_size=1, max_size=24),
       st.integers(1, 8))
@settings(max_examples=40, deadline=None)
def test_lpt_makespan_invariants(costs, k):
    pl = lpt_placement(costs, k)
    assert pl.imbalance >= 1.0 - 1e-12
    # parallelism can never be worse than serial execution...
    assert pl.makespan <= sum(costs) + 1e-9
    # ...nor better than the critical path / perfect split
    assert pl.makespan >= max(max(costs), sum(costs) / k) - 1e-9


@given(st.lists(st.floats(0.001, 1.0), min_size=1, max_size=16))
@settings(max_examples=25, deadline=None)
def test_lpt_makespan_monotone_in_device_count(costs):
    """More devices never hurt: the re-place actuator relies on this to
    treat device-count growth as strictly-no-worse."""
    spans = [lpt_placement(costs, k).makespan for k in range(1, 9)]
    assert spans[0] == pytest.approx(sum(costs))
    for a, b in zip(spans, spans[1:]):
        assert b <= a + 1e-9


@given(st.integers(1, 12), st.integers(1, 8),
       st.floats(0.001, 1.0))
@settings(max_examples=25, deadline=None)
def test_lpt_stable_under_duplicate_costs(n, k, c):
    """All-equal costs: ties must break deterministically (stable sort +
    first-min slot), so two runs agree and staging caches stay hot."""
    costs = [c] * n
    p1, p2 = lpt_placement(costs, k), lpt_placement(costs, k)
    assert p1.assignment == p2.assignment
    assert p1.signature() == p2.signature()
    placed = sorted(i for slot in p1.assignment for i in slot)
    assert placed == list(range(n))
    # slot sizes differ by at most one member (round-robin under ties)
    sizes = sorted(len(s) for s in p1.assignment)
    assert sizes[-1] - sizes[0] <= 1


@given(st.lists(st.floats(0.01, 1.0), min_size=1, max_size=8),
       st.integers(1, 6), st.integers(1, 4))
@settings(max_examples=25, deadline=None)
def test_grouped_lpt_keeps_groups_atomic(group_costs, k, group_size):
    """Bucket-granularity planning: a stacked bucket is never split
    across devices, and the expansion covers every member once."""
    groups = [list(range(g * group_size, (g + 1) * group_size))
              for g in range(len(group_costs))]
    pl = grouped_lpt_placement(groups, group_costs, k)
    placed = sorted(m for slot in pl.assignment for m in slot)
    assert placed == list(range(len(group_costs) * group_size))
    for g in groups:                      # group lands on ONE slot whole
        owners = {i for i, slot in enumerate(pl.assignment)
                  if set(g) & set(slot)}
        assert len(owners) == 1
        assert set(g) <= set(pl.assignment[owners.pop()])
    assert pl.makespan <= sum(group_costs) + 1e-9


@given(st.lists(st.floats(0.01, 1.0), min_size=1, max_size=10),
       st.integers(1, 8))
@settings(max_examples=25, deadline=None)
def test_plan_pod_ensemble_assigns_every_member(costs, k):
    member_costs = {f"m{i}": c for i, c in enumerate(costs)}
    out = plan_pod_ensemble(member_costs, k)
    assert sorted(out) == sorted(member_costs)
    assert set(out.values()) <= set(range(max(1, k)))


# ------------------------------------------ speed-vector LPT properties
# Speeds are drawn from a pow2 grid: real pools come in speed CLASSES
# (a CPU node, a 2x accelerator, a 4x accelerator), and on that space
# the greedy planner's monotonicity properties hold exhaustively (for
# arbitrary continuous speeds pure greedy LPT admits rare sub-0.1%
# makespan regressions under a speed increase — a planner swap this
# repo deliberately avoids to keep unit-speed plans bitwise-stable).
SPEED_GRID = (0.5, 1.0, 2.0, 4.0)
SPEED_UPS = (2.0, 4.0, 8.0)


@given(st.lists(st.floats(0.001, 1.0), min_size=1, max_size=16),
       st.integers(1, 8), st.floats(0.25, 4.0))
@settings(max_examples=40, deadline=None)
def test_speed_lpt_uniform_speeds_reduce_bitwise(costs, k, s):
    """Unit (and any all-equal) speed vector yields EXACTLY today's
    speed-blind plan — assignment, loads, signature — so enabling the
    heterogeneity API on a homogeneous pool changes nothing, including
    staging-cache keys."""
    blind = lpt_placement(costs, k)
    for sp in ([1.0] * max(1, k), [s] * max(1, k)):
        pl = lpt_placement(costs, k, speeds=sp)
        assert pl.assignment == blind.assignment
        assert pl.loads == blind.loads
        assert pl.signature() == blind.signature()
        assert pl.speeds == sp
    assert blind.speeds is None
    assert blind.finish_times == blind.loads


@given(st.lists(st.floats(0.001, 1.0), min_size=1, max_size=12),
       st.integers(1, 8),
       st.lists(st.sampled_from(SPEED_GRID), min_size=8, max_size=8))
@settings(max_examples=40, deadline=None)
def test_speed_lpt_conserves_members_and_work(costs, k, speeds8):
    """Heterogeneity moves work, never creates or destroys it: every
    member placed once, loads stay cost sums (work units), finish
    times are loads normalized by slot speed."""
    sp = speeds8[:max(1, k)]
    pl = lpt_placement(costs, k, speeds=sp)
    placed = sorted(i for slot in pl.assignment for i in slot)
    assert placed == list(range(len(costs)))
    assert sum(pl.loads) == pytest.approx(sum(costs))
    for slot, load in zip(pl.assignment, pl.loads):
        assert load == pytest.approx(sum(costs[i] for i in slot))
    for f, l, s in zip(pl.finish_times, pl.loads, sp):
        assert f == pytest.approx(l / s)
    assert pl.makespan == pytest.approx(max(pl.finish_times))


@given(st.lists(st.floats(0.001, 1.0), min_size=1, max_size=12),
       st.integers(2, 6),
       st.lists(st.sampled_from(SPEED_GRID), min_size=6, max_size=6),
       st.integers(0, 5), st.sampled_from(SPEED_UPS))
@settings(max_examples=60, deadline=None)
def test_speed_lpt_makespan_monotone_in_speedup(costs, k, speeds6,
                                                which, factor):
    """A device getting FASTER never worsens the planned makespan (on
    the pow2 speed-class grid) — the invariant that lets RE-PLACE
    treat a recovered/upgraded device as strictly-no-worse."""
    sp = speeds6[:k]
    base = lpt_placement(costs, k, speeds=sp).makespan
    up = list(sp)
    up[which % k] *= factor
    assert lpt_placement(costs, k, speeds=up).makespan <= base + 1e-9


@given(st.lists(st.floats(0.001, 1.0), min_size=1, max_size=12),
       st.integers(1, 6),
       st.lists(st.sampled_from(SPEED_GRID), min_size=7, max_size=7))
@settings(max_examples=60, deadline=None)
def test_speed_lpt_makespan_monotone_in_added_device(costs, k, speeds7):
    """Adding a device (of any grid speed) never worsens the planned
    makespan."""
    sp = speeds7[:k]
    base = lpt_placement(costs, k, speeds=sp).makespan
    grown = lpt_placement(costs, k + 1, speeds=sp + [speeds7[k]])
    assert grown.makespan <= base + 1e-9


@given(st.integers(1, 12), st.integers(1, 6), st.floats(0.01, 1.0),
       st.lists(st.sampled_from(SPEED_GRID), min_size=6, max_size=6))
@settings(max_examples=25, deadline=None)
def test_speed_lpt_stable_under_duplicates(n, k, c, speeds6):
    """Duplicate costs AND duplicate speeds: ties break
    deterministically, so repeated derivations agree bitwise and the
    staging cache stays hot."""
    costs = [c] * n
    sp = speeds6[:k]
    p1 = lpt_placement(costs, k, speeds=sp)
    p2 = lpt_placement(costs, k, speeds=list(sp))
    assert p1.assignment == p2.assignment
    assert p1.signature() == p2.signature()


def test_speed_lpt_puts_heavy_work_on_fast_devices():
    """The point of the whole exercise: with a 4x device available the
    heavy bucket lands there, and the speed-aware plan strictly beats
    the speed-blind plan evaluated under the TRUE speeds."""
    costs, speeds = [4.0, 1.0, 1.0, 1.0, 1.0], [1.0, 4.0]
    aware = lpt_placement(costs, 2, speeds=speeds)
    assert 0 in aware.assignment[1]       # heaviest item on the 4x slot
    blind = lpt_placement(costs, 2)
    blind_true = Placement(assignment=blind.assignment,
                           loads=blind.loads, speeds=speeds)
    assert aware.makespan < blind_true.makespan - 1e-9


def test_speed_lpt_rejects_bad_speed_vectors():
    with pytest.raises(ValueError):
        lpt_placement([1.0, 2.0], 2, speeds=[1.0])          # wrong len
    with pytest.raises(ValueError):
        lpt_placement([1.0, 2.0], 2, speeds=[1.0, 0.0])     # nonpositive
    with pytest.raises(ValueError):
        Placement(assignment=[[0], [1]], loads=[1.0, 1.0],
                  speeds=[1.0, -2.0])


def test_grouped_lpt_carries_speeds():
    groups = [[0, 1], [2], [3, 4]]
    pl = grouped_lpt_placement(groups, [2.0, 1.0, 1.0], 2,
                               speeds=[1.0, 2.0])
    assert pl.speeds == [1.0, 2.0]
    placed = sorted(m for slot in pl.assignment for m in slot)
    assert placed == list(range(5))


# ------------------------------------------- bugfix regression: imbalance
def test_imbalance_counts_stranded_slots():
    """REGRESSION (pre-fix: imbalance averaged over nonzero slots only,
    so a plan leaving a device fully idle reported 1.0 — 'perfectly
    balanced' — and the controller's RE-PLACE trigger could never fire
    on it)."""
    stranded = Placement(assignment=[[0, 1], []], loads=[3.0, 0.0])
    assert stranded.imbalance == pytest.approx(2.0)
    # well above the controller's default imbalance_high=1.25 gate
    assert stranded.imbalance > 1.25
    balanced = Placement(assignment=[[0], [1]], loads=[1.0, 1.0])
    assert balanced.imbalance == pytest.approx(1.0)
    assert Placement(assignment=[[]], loads=[0.0]).imbalance == 0.0


def test_imbalance_is_finish_time_weighted():
    """Equal LOADS on unequal devices are imbalanced: the slow device
    finishes late.  max(1.0, 0.25) / mean = 1.0 / 0.625 = 1.6."""
    pl = Placement(assignment=[[0], [1]], loads=[1.0, 1.0],
                   speeds=[1.0, 4.0])
    assert pl.finish_times == pytest.approx([1.0, 0.25])
    assert pl.imbalance == pytest.approx(1.6)
    assert pl.makespan == pytest.approx(1.0)


def test_finish_imbalance_helper():
    assert finish_imbalance([1.0, 0.0, 0.0, 0.0]) == pytest.approx(4.0)
    assert finish_imbalance([2.0, 2.0]) == pytest.approx(1.0)
    assert finish_imbalance([]) == 0.0
    assert finish_imbalance([0.0, 0.0]) == 0.0


def test_placement_signature_distinguishes_plans():
    a = Placement(assignment=[[0, 1], [2]], loads=[2.0, 1.0])
    b = Placement(assignment=[[0], [1, 2]], loads=[1.0, 2.0])
    assert a.signature() != b.signature()
    # slot-internal order is irrelevant (same device->members map)
    c = Placement(assignment=[[1, 0], [2]], loads=[2.0, 1.0])
    assert a.signature() == c.signature()
    assert placement_signature(None) not in (a.signature(),
                                             b.signature())


def test_signature_ignores_speeds():
    """Speeds are planner input, not actuated state: a re-speeded but
    identically-assigned plan must hit the same staging-cache entry
    (no recompile churn when only the speed estimate moves)."""
    a = Placement(assignment=[[0, 1], [2]], loads=[2.0, 1.0])
    b = Placement(assignment=[[0, 1], [2]], loads=[2.0, 1.0],
                  speeds=[1.0, 4.0])
    assert a.signature() == b.signature()


def test_failover_placement_keeps_survivor_speeds():
    """Quarantining a device must preserve the SURVIVORS' speed
    sub-vector, and the orphaned members land on the least-FINISH-TIME
    survivor (the least-loaded slot may be the slowest device)."""
    from repro.control.swap import HotSwapper
    old = Placement(assignment=[[0], [1], [2]], loads=[1.0, 1.0, 1.2],
                    speeds=[1.0, 1.0, 4.0])
    pl = HotSwapper._failover_placement(old, 1)
    assert pl.speeds == [1.0, 4.0]
    # slot 1 (speed 4, finish 0.3) absorbs, not slot 0 (finish 1.0)
    assert pl.assignment == [[0], [2, 1]]
    assert pl.loads == pytest.approx([1.0, 2.2])
    # homogeneous plans stay speed-free
    pl0 = HotSwapper._failover_placement(
        Placement(assignment=[[0], [1]], loads=[1.0, 2.0]), 0)
    assert pl0.speeds is None


# ------------------------------------------- sharded-serving equivalence
def _sel(n, idx):
    b = np.zeros(n, np.int8)
    b[list(idx)] = 1
    return b


def _ladder(n):
    """Cheapest -> richest selector family over the reduced zoo."""
    return {"cheap": _sel(n, [0]),
            "mid": _sel(n, range(0, n, 2)),
            "full": _sel(n, range(n))}


def _bucket_plan(pool, selector, n_devices, seed=0):
    """Deterministic bucket-granularity LPT plan for a selector (synthetic
    distinct costs: correctness must hold for ANY valid plan)."""
    idx = np.flatnonzero(np.asarray(selector, bool))
    specs = [pool[i].spec for i in idx]
    groups = list(bucket_zoo(specs).values())
    costs = [float(len(g) + 1 + 0.1 * ((seed + j) % 3))
             for j, g in enumerate(groups)]
    return grouped_lpt_placement(groups, costs, n_devices)


@pytest.fixture(scope="module")
def batch(rng):
    return [{"ecg": rng.standard_normal((3, 250)).astype(np.float32)}
            for _ in range(5)]


@pytest.fixture(scope="module")
def references(zoo_members, batch):
    """Single-device fused outputs per ladder selector — the oracle the
    sharded path must reproduce bitwise."""
    out = {}
    for name, sel in _ladder(len(zoo_members)).items():
        svc = EnsembleService.for_selector(zoo_members, sel)
        out[name] = (sel, svc.predict_batch(batch),
                     svc.predict(batch[0]))
    return out


@multi_device
@needs_devices
@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
@pytest.mark.parametrize("rung", ["cheap", "mid", "full"])
def test_sharded_predict_matches_single_device(zoo_members, batch,
                                               references, rung,
                                               n_devices):
    """THE acceptance property: for every ladder selector and every
    device count, the sharded service is numerically IDENTICAL (same
    dtype, np.array_equal) to the single-device path."""
    sel, want_batch, want_one = references[rung]
    pl = _bucket_plan(zoo_members, sel, n_devices)
    svc = EnsembleService.for_selector(
        zoo_members, sel, placement=pl,
        devices=jax.devices()[:n_devices])
    got_batch = svc.predict_batch(batch)
    ga, wa = np.asarray(got_batch), np.asarray(want_batch)
    assert ga.dtype == wa.dtype
    assert np.array_equal(ga, wa)
    assert svc.predict(batch[0]) == want_one


@multi_device
@needs_devices
def test_shard_params_pinned_to_planned_devices(zoo_members, batch):
    """Every (bucket, device) shard's stacked params live on exactly the
    device its placement slot names, and one dispatch is issued per
    shard (not per member)."""
    sel = _ladder(len(zoo_members))["full"]
    pl = _bucket_plan(zoo_members, sel, 4)
    devs = jax.devices()[:4]
    svc = EnsembleService.for_selector(zoo_members, sel, placement=pl,
                                       devices=devs)
    slot_of = {m: d for d, slot in enumerate(pl.assignment)
               for m in slot}
    seen_devices = set()
    for b in svc._buckets:
        want_dev = devs[slot_of[b.idx[0]]]
        assert b.device is want_dev
        for leaf in jax.tree.leaves(b.stacked):
            assert leaf.devices() == {want_dev}
        seen_devices.add(want_dev)
    assert len(seen_devices) > 1          # genuinely multi-device
    d0 = svc.dispatch_count
    svc.predict_batch(batch)
    assert svc.dispatch_count - d0 == svc.n_buckets == len(svc._buckets)


@multi_device
@needs_devices
def test_member_level_split_close_to_oracle(zoo_members, batch,
                                            references):
    """A member-level plan (bucket split across devices) is still valid:
    stacking group sizes change, so it matches to float tolerance."""
    sel, want_batch, _ = references["full"]
    pl = lpt_placement(list(range(12, 0, -1)), 3)    # splits buckets
    svc = EnsembleService.for_selector(zoo_members, sel, placement=pl,
                                       devices=jax.devices()[:3])
    assert svc.n_buckets > 4              # buckets really were split
    np.testing.assert_allclose(svc.predict_batch(batch), want_batch,
                               atol=1e-6)


@multi_device
@needs_devices
def test_placement_must_cover_members():
    import jax as _jax
    from repro.configs.ecg_zoo import zoo_specs
    from repro.models.ecg_resnext import init_ecg
    from repro.serving.pipeline import ZooMember
    specs = zoo_specs(reduced=True, input_len=250)[:2]
    members = [ZooMember(s, init_ecg(_jax.random.PRNGKey(i), s))
               for i, s in enumerate(specs)]
    bad = Placement(assignment=[[0]], loads=[1.0])          # missing 1
    with pytest.raises(ValueError):
        EnsembleService(members, placement=bad)
    dup = Placement(assignment=[[0, 1], [1]], loads=[1.0, 1.0])
    with pytest.raises(ValueError):
        EnsembleService(members, placement=dup)


@multi_device
@needs_devices
def test_stage_caches_selector_placement_pairs(zoo_members):
    from repro.control.swap import HotSwapper
    n = len(zoo_members)
    sel = _ladder(n)["mid"]
    pl2 = _bucket_plan(zoo_members, sel, 2)
    pl4 = _bucket_plan(zoo_members, sel, 4)
    sw = HotSwapper(zoo_members, sel, warmup_batch_sizes=(1,),
                    placement_fn=lambda s: _bucket_plan(zoo_members,
                                                        s, 2))
    assert sw.sharded
    a1 = sw.stage(sel, pl2)
    a2 = sw.stage(sel, pl2)
    b1 = sw.stage(sel, pl4)
    assert a1 is a2                       # pair cache hit
    assert a1 is not b1                   # same selector, new placement
    assert a1.placement.signature() == pl2.signature()
    assert b1.placement.signature() == pl4.signature()


@multi_device
@needs_devices
def test_hot_swap_zero_drop_across_placement_changes(zoo_members, rng):
    """Placement changes are hot-swaps too: toggling the active plan
    mid-stream must drop zero queries, and post-swap scores must be
    bitwise-equal to a cold-started service on the new plan."""
    from repro.control.swap import HotSwapper
    from repro.serving.server import EnsembleServer
    n = len(zoo_members)
    sel = _ladder(n)["full"]
    plans = [_bucket_plan(zoo_members, sel, d, seed=d)
             for d in (2, 4, 8)]
    sw = HotSwapper(zoo_members, sel, warmup_batch_sizes=(1,),
                    placement_fn=lambda s: plans[0])
    for pl in plans:                      # pre-stage every plan
        sw.stage(sel, pl)
    srv = EnsembleServer(batch_handler=sw.facade.predict_batch,
                         n_workers=2, max_batch=1,
                         max_wait_ms=0.5).start()
    windows = [{"ecg": rng.standard_normal((3, 250)).astype(np.float32)}
               for _ in range(24)]
    for i in range(24):
        if i in (8, 16):                  # re-place mid-stream
            assert sw.re_place(plans[i // 8])
        assert srv.submit(i, windows[i])
    stats = srv.stop()
    assert stats.served == 24             # zero dropped
    assert sw.facade.swap_count == 2
    assert placement_signature(sw.active_placement) \
        == plans[2].signature()
    scores = {p: s for p, s, *_ in srv.results()}
    cold = EnsembleService.for_selector(zoo_members, sel,
                                        placement=plans[2],
                                        devices=jax.devices())
    for i in range(16, 24):
        assert scores[i] == cold.predict_batch([windows[i]])[0]


@multi_device
@needs_devices
def test_re_place_noop_when_plan_unchanged(zoo_members):
    from repro.control.swap import HotSwapper
    n = len(zoo_members)
    sel = _ladder(n)["cheap"]
    pl = _bucket_plan(zoo_members, sel, 2)
    sw = HotSwapper(zoo_members, sel, warmup_batch_sizes=(1,),
                    placement_fn=lambda s: pl)
    svc = sw.facade.current
    assert sw.re_place() is False         # same signature: no swap
    assert sw.facade.current is svc
    assert sw.facade.swap_count == 0


@multi_device
@needs_devices
@pytest.mark.parametrize("speeds", [(4.0, 2.0, 1.0, 1.0),
                                    (1.0, 1.0, 4.0, 0.5)])
@pytest.mark.parametrize("rung", ["mid", "full"])
def test_sharded_hetero_speeds_bitwise(zoo_members, batch, references,
                                       rung, speeds):
    """Speeds move work, never change math: for NON-UNIFORM synthetic
    speed vectors the speed-aware sharded service stays bitwise-equal
    to the single-device oracle, and the aware plan's finish-time
    makespan never exceeds the speed-blind plan's under the true
    speeds."""
    sel, want_batch, want_one = references[rung]
    idx = np.flatnonzero(np.asarray(sel, bool))
    groups = list(bucket_zoo([zoo_members[i].spec for i in idx]).values())
    costs = [float(len(g) + 0.25 * j) for j, g in enumerate(groups)]
    pl = grouped_lpt_placement(groups, costs, len(speeds),
                               speeds=list(speeds))
    blind = grouped_lpt_placement(groups, costs, len(speeds))
    blind_true = Placement(assignment=blind.assignment,
                           loads=blind.loads, speeds=list(speeds))
    assert pl.makespan <= blind_true.makespan + 1e-9
    svc = EnsembleService.for_selector(
        zoo_members, sel, placement=pl,
        devices=jax.devices()[:len(speeds)])
    got = np.asarray(svc.predict_batch(batch))
    want = np.asarray(want_batch)
    assert got.dtype == want.dtype
    assert np.array_equal(got, want)
    assert svc.predict(batch[0]) == want_one


@multi_device
@needs_devices
def test_quarantine_drops_dead_device_speed(zoo_members):
    """Device loss on a heterogeneous pool: the swapper's speed vector
    loses the dead device's entry, and the failover plan carries the
    survivor speed sub-vector."""
    from repro.control.swap import HotSwapper
    n = len(zoo_members)
    sel = _ladder(n)["mid"]
    devs = jax.devices()[:2]
    sw = HotSwapper(zoo_members, sel, warmup_batch_sizes=(1,),
                    n_devices=2, devices=devs, speeds=[1.0, 3.0],
                    plan_batch=1, cost_reps=1)
    assert sw.active_placement is not None
    assert sw.active_placement.speeds == [1.0, 3.0]
    assert sw.quarantine_device(devs[0])
    assert sw.speeds == [3.0]
    assert sw.active_placement.speeds == [3.0]
    assert sw.active_placement.n_slots == 1


@multi_device
@needs_devices
def test_retire_drift_feeds_replace(zoo_members, rng):
    """ISSUE 9 acceptance lane: an injected per-device slowdown drifts
    the live shard retire EWMAs; the controller sees the measured
    finish-time imbalance, fires RE-PLACE, and ``re_place`` re-derives
    the plan FROM THE DRIFT (live costs, not a fresh offline
    measurement on the healthy reference device) — landing a plan that
    splits the slowed device's buckets, without dropping a query."""
    from repro.control.controller import ControllerConfig, Decision
    from repro.control.faults import wire_controller
    from repro.control.swap import HotSwapper
    from repro.control.telemetry import SloTelemetry
    from repro.serving.server import EnsembleServer

    n = len(zoo_members)
    sel = _ladder(n)["full"]
    groups = list(bucket_zoo([m.spec for m in zoo_members]).values())
    assert len(groups) >= 4
    g_costs = [1.0, 1.0] + [0.5] * (len(groups) - 2)
    pl_init = grouped_lpt_placement(groups, g_costs, 2)
    devs = jax.devices()[:2]
    sw = HotSwapper(zoo_members, sel, warmup_batch_sizes=(1,),
                    n_devices=2, devices=devs,
                    placement_fn=lambda s: pl_init)
    # hand planning back to the measured/drift path: placement_fn only
    # pinned a deterministic INITIAL plan for the scenario
    sw.placement_fn = None
    assert placement_signature(sw.active_placement) \
        == pl_init.signature()
    slow_dev = devs[0]
    slow_keys = {tuple(sorted(b.idx))
                 for b in sw.facade.current._buckets
                 if b.device is slow_dev}
    assert len(slow_keys) >= 2            # a co-resident pair to split

    def guard(dev):
        if dev is slow_dev:
            time.sleep(0.02)              # the injected slowdown

    sw.service_hook = lambda svc: setattr(svc, "dispatch_guard", guard)
    sw.facade.current.dispatch_guard = guard

    tel = SloTelemetry(slo_seconds=2.0, window_seconds=60.0)
    ctl = wire_controller(
        tel, sw, member_costs=[0.01] * n,
        config=ControllerConfig(slo_seconds=2.0, cooldown_seconds=0.0,
                                min_samples=5),
        sync=True, start=False)
    srv = EnsembleServer(batch_handler=sw.facade.predict_batch,
                         n_workers=2, max_batch=1, max_wait_ms=0.5,
                         telemetry=tel).start()
    windows = [{"ecg": rng.standard_normal((3, 250)).astype(np.float32)}
               for _ in range(24)]
    for i in range(12):                   # drift phase
        assert srv.submit(i, windows[i])
    deadline = time.monotonic() + 30.0
    while srv.stats.served < 12 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert srv.stats.served == 12
    # the live EWMAs must already show the slowdown on slot 0's buckets
    live = sw.facade.current.live_bucket_costs()
    assert live is not None
    fin = sw.facade.current.measured_finish_times()
    assert fin is not None and fin[0] > 3.0 * max(fin[1], 1e-9)
    assert ctl.step() is Decision.REPLACE
    new_pl = sw.active_placement
    assert placement_signature(new_pl) != pl_init.signature()
    # the formerly co-resident slowed buckets are now split: no slot
    # hosts every one of them
    for slot in new_pl.assignment:
        keys_on_slot = set()
        for b in sw.facade.current._buckets:
            if set(b.idx) <= set(slot):
                keys_on_slot.add(tuple(sorted(b.idx)))
        assert not slow_keys <= keys_on_slot
    for i in range(12, 24):               # rebalanced phase
        assert srv.submit(i, windows[i])
    stats = srv.stop()
    assert stats.served == 24             # zero dropped
    assert stats.failed == 0


# ---------------------------- bugfix regression: plan at the flush rung
def test_plan_placement_measures_at_flush_rung(zoo_members):
    """REGRESSION (pre-fix: ``plan_placement`` measured bucket costs at
    batch=1 and took no ``batch=`` at all, while serving flushes pad to
    the pow2 rung ladder — cost RATIOS differ between the two, so the
    derived plan could be wrong for the traffic it serves).  With
    synthetic batch-dependent timings the batch-1 plan and the
    flush-rung plan genuinely flip, and the default must be the
    flush-rung one."""
    svc = EnsembleService(zoo_members)
    groups = list(bucket_zoo([m.spec for m in zoo_members]).values())
    n = len(groups)
    assert n >= 3
    # batch 1: fixed dispatch overhead dominates -> bucket 0 looks
    # heaviest; at the flush rung the compute-heavy rest dominate
    fake = {1: [0.4] + [0.1] * (n - 1),
            PLAN_BATCH: [0.1] + [0.4] * (n - 1)}
    svc.measured_bucket_costs = \
        lambda reps=3, batch=1, warmup=1: list(fake[batch])
    plan_default = svc.plan_placement(2)
    plan_flush = svc.plan_placement(2, batch=PLAN_BATCH)
    plan_b1 = svc.plan_placement(2, batch=1)
    assert plan_b1.signature() != plan_flush.signature()   # plans flip
    assert plan_default.signature() == plan_flush.signature()


# ------------------------------------------- live shard retire EWMAs
def test_flush_records_shard_retire_ewmas(zoo_members, batch):
    """Every fused flush folds per-shard dispatch->retire wall-clock
    into an O(1) EWMA; the snapshot covers every bucket, the live cost
    vector lines up with the planner's groups, and state never grows
    with the number of flushes."""
    svc = EnsembleService.for_selector(zoo_members,
                                       _ladder(len(zoo_members))["full"])
    svc.warmup(batch_sizes=(8,))      # keep compile out of the EWMAs
    assert svc.shard_cost_snapshot() == {}
    assert svc.live_bucket_costs() is None       # nothing observed yet
    for _ in range(3):
        svc.predict_batch(batch)
    snap = svc.shard_cost_snapshot()
    groups = list(bucket_zoo([m.spec for m in zoo_members]).values())
    assert set(snap) == {tuple(sorted(g)) for g in groups}
    assert all(v > 0 for v in snap.values())
    live = svc.live_bucket_costs()
    assert live is not None and len(live) == len(groups)
    fin = svc.measured_finish_times()
    assert fin is not None and len(fin) == 1     # unsharded: one slot
    assert fin[0] == pytest.approx(max(snap.values()))
    svc.predict_batch(batch)
    assert len(svc.shard_cost_snapshot()) == len(snap)   # O(1) state


def test_retire_ewma_tracks_injected_slowdown(zoo_members, batch):
    """A dispatch_guard stall on the (single) device shows up in the
    retire EWMAs within a few flushes — the drift signal RE-PLACE
    consumes."""
    svc = EnsembleService.for_selector(zoo_members,
                                       _ladder(len(zoo_members))["full"])
    svc.warmup(batch_sizes=(8,))      # keep compile out of the EWMAs
    for _ in range(3):
        svc.predict_batch(batch)
    fast = dict(svc.shard_cost_snapshot())
    # 50ms stall: large vs per-shard compute, so every shard's EWMA
    # must drift well past its fast baseline even though the stalls
    # also absorb some cross-shard gather wait
    svc.dispatch_guard = lambda dev: time.sleep(0.05)
    for _ in range(5):
        svc.predict_batch(batch)
    slow = svc.shard_cost_snapshot()
    assert set(slow) == set(fast)
    assert all(slow[k] > fast[k] + 0.01 for k in fast)


# ------------------------------------------------- subprocess lane
@pytest.mark.skipif(IN_LANE, reason="already in the multi-device lane")
def test_multi_device_lane_subprocess():
    """Default single-device lane: re-run this module's ``multi_device``
    selection in a child process with 8 forced host devices, so the
    sharded hot path is verified on every tier-1 run."""
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
    # the lane must have RUN the tests, not collected zero / skipped all
    assert " passed" in r.stdout, tail[-2000:]
    assert " skipped" not in r.stdout, tail[-2000:]
