"""Tiered-serving invariants: per-acuity-tier degradation ladders with
priority-aware shedding.

Four layers:

* DES conservation properties (hypothesis): under census churn
  AND mid-stay acuity escalation, every query is served exactly once,
  by exactly its birth-tier's ensemble (never dropped, double-served,
  or answered by the wrong tier's selector), per-tier counts sum to the
  fleet totals, and tiered backlog carry preserves tiers across epoch
  edges;
* controller policy properties: shed-order monotonicity — after ANY
  sequence of controller actions a stable bed is never on a richer
  rung than a critical bed — plus the critical-tier holdout (sheds only
  when the predicted bound leaves no alternative) and the cross-tier
  climb budget;
* data-plane routing: tier-keyed micro-batching never mixes tiers in a
  flush, and each query's score is bitwise-equal to a cold service on
  its tier's selector;
* shared staging: zero-drop tier-pair hot swaps mid-stream, and
  eviction with tier-keyed composite cache keys never evicts another
  tier's active pair (T tiers x R rungs stage R services, not T*R).

Everything here is device-count-agnostic: the file must pass unchanged
in the default single-device lane and the forced-8-device CI lane.
"""
import threading
import time
from collections import Counter

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.control.controller import (Decision, TieredController,
                                      TieredControllerConfig)
from repro.control.swap import SelectorLadder
from repro.control.telemetry import TelemetrySnapshot, TieredTelemetry
from repro.control.tiers import TIER_ORDER, TieredEnsemble, TierRegistry
from repro.serving.queues import NO_LANE, KeyedMicroBatcher
from repro.serving.simulator import SimConfig, simulate

TIERS = TIER_ORDER                    # ("stable", "elevated", "critical")
FRACS = {"stable": 0.5, "elevated": 0.3, "critical": 0.2}
COSTS = {"stable": [0.01], "elevated": [0.01, 0.02],
         "critical": [0.01, 0.02, 0.03]}


def _sel(n, idx):
    b = np.zeros(n, np.int8)
    b[list(idx)] = 1
    return b


def _tier_at(tier_log, patient, t):
    """Tier of ``patient`` at time ``t`` per the acuity trail (None if
    never admitted by then)."""
    cur = None
    for tt, p, _old, new in tier_log:
        if p == patient and tt <= t:
            cur = new
    return cur


# ----------------------------------------------------------- registry
def test_registry_assign_escalate_default():
    reg = TierRegistry()
    assert reg.tier_of(7) == "stable"          # unknown -> lowest acuity
    reg.assign(7, "critical")
    assert reg.tier_of(7) == "critical"
    assert reg.escalate(3) == "elevated"       # one step up from default
    assert reg.escalate(3) == "critical"
    assert reg.escalate(3) == "critical"       # top is sticky
    assert reg.census() == {"stable": 0, "elevated": 0, "critical": 2}
    reg.discharge(7)
    assert reg.tier_of(7) == "stable"
    with pytest.raises(ValueError):
        reg.assign(1, "nonexistent")


# ------------------------------------------- DES per-tier conservation
@given(st.integers(0, 10**6), st.integers(1, 3),
       st.floats(0.0, 0.4))
@settings(max_examples=8, deadline=None)
def test_tiered_churn_conserves_queries_per_tier(seed, devices, hazard):
    """Under churn + escalation: every query carries a real tier, is
    served with exactly its tier's ensemble size, per-tier counts sum
    to the totals, and the stamped tier matches the acuity trail at
    birth (no query answered by the wrong tier's selector)."""
    cfg = SimConfig(window_seconds=5.0, duration_seconds=60.0,
                    census=[(0.0, 6), (20.0, 14), (40.0, 4)],
                    seed=seed, n_devices=devices,
                    tiers=FRACS, escalate_hazard=hazard)
    r = simulate(COSTS, cfg)
    assert len(r.queries) == len(r.arrivals)   # drain mode: all served
    per = {t: 0 for t in FRACS}
    for q in r.queries:
        assert q.tier in FRACS
        assert q.n_models == len(COSTS[q.tier])
        assert q.t_done > q.t_window           # served exactly once
        assert q.tier == _tier_at(r.tier_log, q.patient, q.t_window)
        per[q.tier] += 1
    assert sum(per.values()) == len(r.queries)
    # the acuity trail only admits (old == "") or escalates one step up
    order = list(FRACS)
    for _t, _p, old, new in r.tier_log:
        if old:
            assert order.index(new) == order.index(old) + 1


def test_tiered_churn_deterministic_under_seed():
    cfg = SimConfig(window_seconds=5.0, duration_seconds=60.0,
                    census=[(0.0, 8), (30.0, 16)], seed=11,
                    tiers=FRACS, escalate_hazard=0.25)
    r1, r2 = simulate(COSTS, cfg), simulate(COSTS, cfg)
    assert r1.tier_log == r2.tier_log
    assert [q.tier for q in r1.queries] == [q.tier for q in r2.queries]
    np.testing.assert_array_equal(r1.arrivals, r2.arrivals)


def test_mid_stay_escalation_conservation():
    """The acceptance property: acuity escalating mid-stay moves the
    patient's NEXT queries to the higher tier — queries before the
    escalation keep the old tier, queries after carry the new one, and
    nothing is lost or double-served along the way."""
    cfg = SimConfig(window_seconds=4.0, duration_seconds=80.0,
                    census=[(0.0, 10)], seed=2,
                    tiers=FRACS, escalate_hazard=0.3)
    r = simulate(COSTS, cfg)
    esc = [e for e in r.tier_log if e[2]]
    assert esc                                 # escalations did happen
    assert len(r.queries) == len(r.arrivals)
    by_patient = {}
    for q in r.queries:
        by_patient.setdefault(q.patient, []).append(q)
    # some patient really straddled tiers mid-stay...
    multi = sum(1 for qs in by_patient.values()
                if len({q.tier for q in qs}) > 1)
    assert multi > 0
    # ...tiers only ever move UP along a patient's own query stream
    # (this DES models escalation, not de-escalation)...
    order = list(FRACS)
    for qs in by_patient.values():
        idx = [order.index(q.tier)
               for q in sorted(qs, key=lambda q: q.t_window)]
        assert idx == sorted(idx)
    # ...and every query's tier matches the acuity trail at its birth
    for q in r.queries:
        assert q.tier == _tier_at(r.tier_log, q.patient, q.t_window)


@given(st.integers(0, 10**6))
@settings(max_examples=6, deadline=None)
def test_tiered_backlog_preserves_tiers_across_epochs(seed):
    """Epoch-edge conservation, per tier: born = served + carried, and
    a carried query enters the next epoch with its birth tier."""
    slow = {t: [0.25] for t in FRACS}
    cfg = SimConfig(n_patients=24, n_devices=1, window_seconds=5.0,
                    duration_seconds=40.0, seed=seed,
                    carry_backlog=True, tiers=FRACS)
    r1 = simulate(slow, cfg)
    assert len(r1.backlog) > 0
    assert len(r1.backlog_tiers) == len(r1.backlog)
    # epoch-1 conservation, per tier: born = served + carried out
    born1 = Counter(q.tier for q in r1.queries) \
        + Counter(r1.backlog_tiers)
    assert sum(born1.values()) == len(r1.arrivals)
    r2 = simulate({t: [0.02] for t in FRACS}, cfg,
                  backlog=r1.backlog, backlog_tiers=r1.backlog_tiers)
    from_backlog = [q for q in r2.queries if q.t_window < 0]
    # every carried query either retired in epoch 2 or carried again
    assert len(from_backlog) + sum(
        1 for a in r2.backlog if a > cfg.duration_seconds) \
        == len(r1.backlog)
    # tiers preserved: the multiset of retired-backlog tiers is a
    # sub-multiset of what was carried in
    cin = Counter(r1.backlog_tiers)
    cout = Counter(q.tier for q in from_backlog)
    assert all(cout[t] <= cin[t] for t in cout)
    # and the carried queries were served with their OWN tier's costs
    for q in from_backlog:
        assert q.n_models == len(slow[q.tier])


def test_escalation_requires_tiers():
    with pytest.raises(ValueError):
        simulate([0.01], SimConfig(n_patients=2, escalate_hazard=0.5))


# ---------------------------------------------- controller: shed order
class _NoopLadder(SelectorLadder):
    def _activate(self, selector):
        pass


def _family(n_rungs=3, n=8):
    return [_sel(n, range(k + 1)) for k in range(n_rungs)]


def _lanes(pos=None):
    fam = _family()
    lanes = {}
    for i, t in enumerate(TIERS):
        p = (len(fam) - 1) if pos is None else pos[i]
        lane = _NoopLadder(fam[p])
        lane.set_ladder(fam)
        lanes[t] = lane
    return lanes, fam


class _ScriptedTelemetry:
    """Controller-facing stub: the test scripts the fleet snapshot and
    per-tier arrival rates directly."""

    def __init__(self, rates=None):
        self.tiers = TIERS
        self.slo = 1.0
        self.fleet = None
        self.rates = dict(rates or {t: 1.0 for t in TIERS})

    def snapshot(self, **kw):
        return self.fleet

    def tier_snapshot(self, tier, **kw):
        return _snap(arrival_rate=self.rates[tier])


def _snap(**kw):
    base = dict(t=0.0, window_seconds=30.0, n_arrivals=100, n_served=100,
                n_shed=0, arrival_rate=2.0, p50=0.1, p99=0.2,
                violation_rate=0.0)
    base.update(kw)
    return TelemetrySnapshot(**base)


def _assert_monotone(lanes):
    pos = [lanes[t].ladder_pos for t in TIERS]
    assert all(p >= 0 for p in pos)
    assert all(a <= b for a, b in zip(pos, pos[1:])), pos


@given(st.lists(st.booleans(), min_size=1, max_size=30))
@settings(max_examples=20, deadline=None)
def test_shed_order_monotone_under_any_action_sequence(overloads):
    """THE invariant: whatever sequence of overloaded/healthy evidence
    the controller sees, a stable bed is never on a richer rung than an
    elevated bed, nor an elevated bed richer than a critical bed."""
    lanes, _fam = _lanes()
    tel = _ScriptedTelemetry()
    ctl = TieredController(
        tel, lanes, tier_order=TIERS,
        config=TieredControllerConfig(slo_seconds=1.0,
                                      cooldown_seconds=0.0,
                                      min_samples=1))
    for k, overloaded in enumerate(overloads):
        tel.fleet = _snap(violation_rate=0.5 if overloaded else 0.0,
                          p99=1.5 if overloaded else 0.1)
        ctl.step(now=float(k))
        _assert_monotone(lanes)
        assert ctl.monotone()
        # priority: if the critical tier ever shed, every lower tier
        # must already be at (or have stayed at) the floor
        if lanes["critical"].ladder_pos < len(_fam) - 1:
            assert lanes["stable"].ladder_pos == 0
            assert lanes["elevated"].ladder_pos == 0


def test_critical_holds_while_floor_restores_capacity():
    """With a cost model showing that flooring the lower tiers restores
    feasibility (rho_floor < 1), the critical tier NEVER sheds no
    matter how long the observed overload persists."""
    lanes, fam = _lanes()
    tel = _ScriptedTelemetry(rates={t: 1.0 for t in TIERS})
    costs = np.linspace(0.01, 0.05, 8)
    ctl = TieredController(
        tel, lanes, tier_order=TIERS,
        config=TieredControllerConfig(slo_seconds=1.0,
                                      cooldown_seconds=0.0,
                                      min_samples=1, rho_max=0.5),
        cost_fn=lambda s: float(costs[np.asarray(s, bool)].sum()),
        n_devices=1)
    for k in range(12):
        tel.fleet = _snap(violation_rate=0.9, p99=3.0)
        ctl.step(now=float(k))
        _assert_monotone(lanes)
    assert lanes["stable"].ladder_pos == 0         # floored
    assert lanes["elevated"].ladder_pos == 0       # floored
    assert lanes["critical"].ladder_pos == len(fam) - 1   # held rich


def test_critical_sheds_when_no_alternative():
    """rho >= 1 even with every lower tier floored: the predicted bound
    leaves no alternative, so the critical tier finally sheds too."""
    lanes, fam = _lanes()
    tel = _ScriptedTelemetry(rates={"stable": 1.0, "elevated": 1.0,
                                    "critical": 40.0})
    costs = np.linspace(0.01, 0.05, 8)
    ctl = TieredController(
        tel, lanes, tier_order=TIERS,
        config=TieredControllerConfig(slo_seconds=1.0,
                                      cooldown_seconds=0.0,
                                      min_samples=1, rho_max=0.5),
        cost_fn=lambda s: float(costs[np.asarray(s, bool)].sum()),
        n_devices=1)
    for k in range(12):
        tel.fleet = _snap(violation_rate=0.9, p99=3.0)
        ctl.step(now=float(k))
        _assert_monotone(lanes)
    assert lanes["stable"].ladder_pos == 0
    assert lanes["elevated"].ladder_pos == 0
    assert lanes["critical"].ladder_pos == 0       # forced all the way
    sheds = [(t, tier) for t, tier, d in ctl.log if d is Decision.SHED]
    first_critical = next(i for i, (_, tier) in enumerate(sheds)
                          if tier == "critical")
    # every stable/elevated shed happened BEFORE the first critical one
    assert all(tier != "critical"
               for _, tier in sheds[:first_critical])


def test_queries_already_dropping_is_no_alternative():
    """n_shed > 0 (the ingest queue is rejecting) counts as 'no
    alternative' even without a cost model."""
    lanes, fam = _lanes(pos=[0, 0, len(_family()) - 1])
    tel = _ScriptedTelemetry()
    ctl = TieredController(
        tel, lanes, tier_order=TIERS,
        config=TieredControllerConfig(slo_seconds=1.0,
                                      cooldown_seconds=0.0,
                                      min_samples=1))
    tel.fleet = _snap(violation_rate=0.0, p99=0.9, n_shed=5)
    acts = ctl.step(now=0.0)
    assert (Decision.SHED, "critical") in acts


def test_climb_order_critical_first_and_budget_gated():
    """Recovery: the critical tier climbs back FIRST; a lower tier may
    never climb past a higher tier's rung; and when the cross-tier
    budget is tight, low-acuity climbs are denied so they cannot eat
    the critical tier's headroom."""
    lanes, fam = _lanes(pos=[0, 0, 0])
    tel = _ScriptedTelemetry(rates={t: 1.0 for t in TIERS})
    costs = np.linspace(0.01, 0.05, 8)
    cost_fn = lambda s: float(costs[np.asarray(s, bool)].sum())
    ctl = TieredController(
        tel, lanes, tier_order=TIERS,
        config=TieredControllerConfig(slo_seconds=1.0,
                                      cooldown_seconds=0.0,
                                      min_samples=1, rho_max=10.0),
        cost_fn=cost_fn, n_devices=1)
    climbs = []
    for k in range(12):
        tel.fleet = _snap(violation_rate=0.0, p99=0.1)
        acts = ctl.step(now=float(k))
        climbs.extend(tier for d, tier in acts if d is Decision.CLIMB)
        _assert_monotone(lanes)
    # critical reaches the top before elevated starts, elevated before
    # stable (priority order holds throughout by monotonicity)
    assert climbs[:2] == ["critical", "critical"]
    assert lanes["critical"].ladder_pos == len(fam) - 1
    assert lanes["stable"].ladder_pos == len(fam) - 1   # budget is loose

    # tight budget: from the floor, only the critical tier fits
    lanes2, _ = _lanes(pos=[0, 0, 0])
    rates = {t: 10.0 for t in TIERS}
    tel2 = _ScriptedTelemetry(rates=rates)
    base_rho = sum(rates[t] * cost_fn(lanes2[t].active_selector)
                   for t in TIERS)
    rho_max = base_rho + 10.0 * (cost_fn(_family()[2]) * 1.1)
    ctl2 = TieredController(
        tel2, lanes2, tier_order=TIERS,
        config=TieredControllerConfig(slo_seconds=1.0,
                                      cooldown_seconds=0.0,
                                      min_samples=1, rho_max=rho_max),
        cost_fn=cost_fn, n_devices=1)
    for k in range(12):
        tel2.fleet = _snap(violation_rate=0.0, p99=0.1)
        ctl2.step(now=float(k))
        _assert_monotone(lanes2)
    assert lanes2["critical"].ladder_pos == len(fam) - 1
    assert lanes2["stable"].ladder_pos == 0    # denied: no headroom


# -------------------------------------------------- per-tier telemetry
def test_tiered_telemetry_slices_and_fleet():
    reg = TierRegistry()
    reg.assign(1, "critical")
    tel = TieredTelemetry(tier_of=reg.tier_of, tiers=TIERS,
                          slo_seconds=0.5, window_seconds=60.0,
                          clock=lambda: 10.0)
    tel.record_arrival(1.0, patient=1)            # -> critical
    tel.record_arrival(1.5, patient=99)           # unknown -> stable
    tel.record_arrival(2.0, tier="elevated")      # explicit tier wins
    tel.record_served(0.1, 2.5, patient=1)
    tel.record_served(0.9, 3.0, tier="stable")    # violates
    assert tel.tier_snapshot("critical").n_arrivals == 1
    assert tel.tier_snapshot("stable").n_arrivals == 1
    assert tel.tier_snapshot("elevated").n_arrivals == 1
    assert tel.tier_snapshot("critical").n_served == 1
    assert tel.tier_snapshot("stable").violation_rate == 1.0
    assert tel.tier_snapshot("critical").violation_rate == 0.0
    fleet = tel.snapshot()
    assert fleet.n_arrivals == 3 and fleet.n_served == 2
    # explicit tier unknown -> default slice, never lost
    tel.record_arrival(4.0, tier="no-such-tier")
    assert tel.tier_snapshot("stable").n_arrivals == 2


# ------------------------------------------------ tier-keyed batching
def test_keyed_batcher_never_mixes_keys():
    t = [0.0]
    kb = KeyedMicroBatcher(max_batch=3, max_wait_ms=1000.0,
                           clock=lambda: t[0])
    for i in range(3):
        kb.push("a", ("a", i))
    kb.push("b", ("b", 0))
    assert len(kb) == 4
    assert kb.ready() == "a"                   # a hit max_batch
    batch = kb.pop_batch("a")
    assert [k for k, _ in batch] == ["a", "a", "a"]
    assert kb.ready() is NO_LANE               # b neither full nor old
    t[0] = 2.0
    assert kb.ready() == "b"                   # b aged past max_wait
    assert [k for k, _ in kb.pop_batch("b")] == ["b"]
    assert kb.stats.n_flushes == 2 and kb.stats.n_items == 4


def test_keyed_batcher_oldest_due_first():
    t = [0.0]
    kb = KeyedMicroBatcher(max_batch=8, max_wait_ms=100.0,
                           clock=lambda: t[0])
    kb.push("late", 1, t=0.5)
    kb.push("early", 2, t=0.1)
    t[0] = 1.0                                 # both lanes are due
    assert kb.ready() == "early"
    kb.pop_batch("early")
    assert kb.ready() == "late"


def test_keyed_batcher_stats_never_torn_under_concurrent_pops():
    """Regression: ``KeyedMicroBatcher.stats``/``lane_stats`` used to
    expose the LIVE per-lane stats objects, which ``pop_batch`` mutates
    field by field under the lane lock the reader never takes.  Any
    consumer that combines two fields read at different moments — the
    aggregate loop, a metrics exporter formatting one line per field —
    sees values from different flushes.  With max_batch=1 every flush
    carries exactly one item, so ANY consistent view has
    ``n_flushes <= n_items``; a live object read across an ongoing pop
    stream violates it (``n_items`` from before a flush, ``n_flushes``
    from after).  Both surfaces must return internally-consistent
    snapshots no matter how slowly the caller consumes the fields."""
    kb = KeyedMicroBatcher(max_batch=1, max_wait_ms=0.0)
    stop = threading.Event()
    torn = []

    def popper(lane):
        i = 0
        while not stop.is_set():
            kb.push(lane, i)
            kb.pop_batch(lane)
            i += 1

    def reader():
        while not stop.is_set():
            # Field reads deliberately straddle a delay: a snapshot is
            # immutable so this is safe; a live lane object tears.
            views = [("agg", kb.stats)]
            views += [(k, ls) for k, ls in kb.lane_stats().items()]
            items = [(k, v.n_items) for k, v in views]
            time.sleep(0.002)          # pops keep landing in between
            for (k, v), (_, n_it) in zip(views, items):
                if v.n_flushes > n_it:
                    torn.append((k, n_it, v.n_flushes))

    threads = ([threading.Thread(target=popper, args=(ln,))
                for ln in ("a", "b")]
               + [threading.Thread(target=reader) for _ in range(2)])
    for th in threads:
        th.start()
    time.sleep(1.0)
    stop.set()
    for th in threads:
        th.join()
    assert torn == []
    s = kb.stats                       # quiescent: exact equality
    assert s.n_flushes == s.n_items > 0


def test_server_coalesces_within_tier_only():
    from repro.serving.server import EnsembleServer
    reg = TierRegistry()
    for p in range(30):
        reg.assign(p, TIERS[p % 3])
    flushes = []

    def handler(windows, tier):
        flushes.append((tier, [w["p"] for w in windows]))
        return [float(tier == "critical")] * len(windows)

    srv = EnsembleServer(batch_handler=handler, tier_of=reg.tier_of,
                         n_workers=2, max_batch=4, max_wait_ms=2.0)
    for p in range(30):                        # enqueue before starting
        assert srv.submit(p, {"p": p})         # so batches can coalesce
    srv.start()
    stats = srv.stop()
    assert stats.served == 30                  # zero dropped
    for tier, pids in flushes:
        assert all(reg.tier_of(p) == tier for p in pids)
    assert any(len(pids) > 1 for _, pids in flushes)   # did coalesce
    scores = {p: s for p, s, *_ in srv.results()}
    for p in range(30):                        # answered by its tier
        assert scores[p] == float(reg.tier_of(p) == "critical")


class _StubService:
    def __init__(self, v):
        self.v = v

    def predict(self, windows):
        return self.v

    def predict_batch(self, batch):
        return [self.v] * len(batch)


def test_tier_router_dispatch_and_fallback():
    from repro.serving.pipeline import TierRouter
    router = TierRouter({"stable": _StubService(0.1),
                         "critical": _StubService(0.9)},
                        default="stable")
    assert router.predict({}, "critical") == 0.9
    assert router.predict({}) == 0.1               # no tier -> default
    assert router.predict({}, "unknown") == 0.1    # unknown -> default
    assert router.predict_batch([{}, {}], "critical") == [0.9, 0.9]
    with pytest.raises(ValueError):
        TierRouter({})
    with pytest.raises(ValueError):
        TierRouter({"stable": _StubService(0.0)}, default="missing")


def test_streaming_pipeline_routes_through_tier_router():
    """Each closed window is answered by the patient's CURRENT tier's
    service — the StreamingPipeline face of tier routing."""
    from repro.serving.pipeline import StreamingPipeline, TierRouter
    reg = TierRegistry()
    reg.assign(1, "critical")
    router = TierRouter({"stable": _StubService(0.1),
                         "critical": _StubService(0.9)},
                        default="stable")
    pipe = StreamingPipeline(router, n_patients=2, window_seconds=1.0,
                             tier_of=reg.tier_of)
    recs = {}
    for patient in (0, 1):
        pipe.feed(0.0, patient, "ecg", np.zeros((3, 10), np.float32))
        recs[patient] = pipe.feed(1.5, patient, "ecg",
                                  np.zeros((3, 10), np.float32))
    assert recs[0].score == 0.1                # stable bed, stable svc
    assert recs[1].score == 0.9                # critical bed, its svc
    reg.escalate(0)                            # mid-stay deterioration
    assert reg.escalate(0) == "critical"       # stable -> elev -> crit
    pipe.feed(3.0, 0, "ecg", np.zeros((3, 10), np.float32))
    rec = pipe.feed(4.6, 0, "ecg", np.zeros((3, 10), np.float32))
    assert rec.score == 0.9                    # next window: new tier


def test_staging_unregister_releases_dead_lane_pins(zoo_members):
    """A lane retired from a shared StagingCache stops pinning its
    pairs: a later eviction pass may finally drop them."""
    from repro.control.swap import HotSwapper
    n = len(zoo_members)
    rungs = _rungs(n)
    te = TieredEnsemble(zoo_members,
                        initial={"stable": rungs[0],
                                 "elevated": rungs[1],
                                 "critical": rungs[2]},
                        warmup_batch_sizes=(1,))
    te.set_ladder(rungs)
    dead = HotSwapper(zoo_members, _sel(n, [3, 5]),
                      staging=te.staging, warmup_batch_sizes=(1,))
    assert len(te.staging.lanes) == 4
    assert len(te.staging.staged) == len(rungs) + 1
    te.staging.unregister(dead)
    assert len(te.staging.lanes) == 3
    te.lane("stable").swap_to(_sel(n, [4]))    # triggers an evict pass
    te.lane("stable").swap_to(rungs[0])
    assert _sel(n, [3, 5]).tobytes() not in {
        k.split(b"|", 1)[0] for k in te.staging.staged}


def test_tiered_controller_rejects_mismatched_slo():
    lanes, _ = _lanes()
    tel = _ScriptedTelemetry()                 # slo = 1.0
    with pytest.raises(ValueError):
        TieredController(
            tel, lanes, tier_order=TIERS,
            config=TieredControllerConfig(slo_seconds=2.0))


def test_tier_of_requires_batch_handler():
    from repro.serving.server import EnsembleServer
    with pytest.raises(ValueError):
        EnsembleServer(handler=lambda w: 0.0,
                       tier_of=lambda p: "stable")


def test_failing_tier_of_routes_to_default_not_dead_worker():
    """A tier_of callback raising on an unknown patient must not kill
    the worker or strand the query: it routes to the default lane and
    every submitted query is still served."""
    from repro.serving.server import EnsembleServer
    seen = []

    def bad_tier(p):
        if p == 3:
            raise KeyError(p)
        return TIERS[p % 3]

    def handler(windows, tier):
        seen.append((tier, [w["p"] for w in windows]))
        return [0.0] * len(windows)

    srv = EnsembleServer(batch_handler=handler, tier_of=bad_tier,
                         n_workers=1, max_batch=2,
                         max_wait_ms=1.0).start()
    for p in range(6):
        assert srv.submit(p, {"p": p})
    stats = srv.stop()
    assert stats.served == 6                  # nothing stranded
    tier_of_3 = [t for t, pids in seen if 3 in pids]
    assert tier_of_3 == [None]                # default-lane fallback


# ------------------------------------- shared staging + zero-drop swap
def _rungs(n):
    return [_sel(n, [0]), _sel(n, range(0, n, 2)), _sel(n, range(n))]


def test_tier_staging_shares_pairs_across_tiers(zoo_members):
    """T tiers x R rungs stage R services, not T*R: tiers standing on
    the same (selector, placement) pair serve through the SAME staged
    object."""
    n = len(zoo_members)
    rungs = _rungs(n)
    te = TieredEnsemble(zoo_members,
                        initial={"stable": rungs[0],
                                 "elevated": rungs[1],
                                 "critical": rungs[2]},
                        warmup_batch_sizes=(1,))
    te.set_ladder(rungs)
    assert len(te.staging.staged) == len(rungs)
    assert te.rungs() == {"stable": 0, "elevated": 1, "critical": 2}
    assert te.monotone()
    # a tier moving onto another tier's rung reuses its staged service
    te.lane("stable").climb()
    assert te.lane("stable").facade.current \
        is te.lane("elevated").facade.current
    assert len(te.staging.staged) == len(rungs)


def test_tier_eviction_never_evicts_other_tiers_active(zoo_members):
    """Satellite acceptance (seeded, deterministic): one tier churning
    through novel off-ladder pairs triggers evictions, but no other
    tier's ACTIVE pair (nor any ladder rung) is ever evicted."""
    n = len(zoo_members)
    rungs = _rungs(n)
    te = TieredEnsemble(zoo_members,
                        initial={"stable": rungs[0],
                                 "elevated": rungs[1],
                                 "critical": rungs[2]},
                        warmup_batch_sizes=(1,))
    te.set_ladder(rungs)
    crit_svc = te.lane("critical").facade.current
    elev_svc = te.lane("elevated").facade.current
    for k in range(1, 5):                     # novel off-ladder pairs
        te.lane("stable").swap_to(_sel(n, [k, (k + 3) % n]))
        # other tiers' live services survived the eviction pass
        assert te.lane("critical").facade.current is crit_svc
        assert te.lane("elevated").facade.current is elev_svc
        # and every ladder rung stayed staged (shed/climb stays warm)
        staged_sels = {key.split(b"|", 1)[0] for key in te.staging.staged}
        for s in rungs:
            assert s.tobytes() in staged_sels
    # evicted down to: 3 rungs + stable's current novel pair
    assert len(te.staging.staged) == len(rungs) + 1


def test_tiered_hot_swap_zero_drop_mid_stream(zoo_members, rng):
    """Zero-drop tier-pair hot swaps: shedding one tier and escalating
    a patient mid-stream drops no queries, and post-swap scores are
    bitwise-equal to cold services on the right tier's selector."""
    from repro.serving.pipeline import EnsembleService
    from repro.serving.server import EnsembleServer
    n = len(zoo_members)
    rungs = _rungs(n)
    te = TieredEnsemble(zoo_members,
                        initial={"stable": rungs[2],
                                 "elevated": rungs[2],
                                 "critical": rungs[2]},
                        warmup_batch_sizes=(1,))
    te.set_ladder(rungs)
    for p in range(24):
        te.registry.assign(p, TIERS[p % 3])
    # max_batch=1: singleton flushes, so scores compare 1:1 to cold
    srv = EnsembleServer(batch_handler=te.predict_batch,
                         tier_of=te.tier_of, n_workers=2,
                         max_batch=1, max_wait_ms=0.5).start()
    windows = [{"ecg": rng.standard_normal((3, 250)).astype(np.float32)}
               for _ in range(24)]
    for i in range(12):
        assert srv.submit(i, windows[i])
    assert te.lane("stable").shed()            # tier-pair swap mid-stream
    te.registry.escalate(0)                    # stable bed deteriorates
    for i in range(12, 24):
        assert srv.submit(i, windows[i])
    stats = srv.stop()
    assert stats.served == 24                  # zero dropped
    assert te.lane("stable").facade.swap_count == 1
    assert te.monotone()
    # fresh post-drain queries land bitwise on the right tier's rung
    cold_mid = EnsembleService.for_selector(zoo_members, rungs[1])
    cold_full = EnsembleService.for_selector(zoo_members, rungs[2])
    w = windows[0]
    assert te.predict(w, "stable") == cold_mid.predict_batch([w])[0]
    assert te.predict(w, "critical") == cold_full.predict_batch([w])[0]
    # the escalated patient now routes to the elevated (rich) lane
    assert te.tier_of(0) == "elevated"
    assert te.predict(w, te.tier_of(0)) == cold_full.predict_batch([w])[0]
