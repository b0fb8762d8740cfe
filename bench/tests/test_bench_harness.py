"""The harness end to end on the CPU at a tiny size (reduced zoo, 4
beds, a 2 s window), with the chip check bypassed: a sound run is
correct, the lower-precision control and each fault the cells can have
are not, and the command refuses to run without a TPU."""
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

import harness
import reference

SEED = 2 ** 32 + 17
WORKLOAD = "zoo60_unit64"


def tiny(config):
    with open(os.path.join(harness.HERE, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    cfg.update(widths=[8, 16], blocks=[2, 4], window_s=3)
    return cfg


def tiny_mix(traffic):
    mix = harness._traffic.load_mix(traffic)
    mix.update(beds=4, hop_s=1.0, preroll_s=1.0)
    return mix


def run_tiny(workload=WORKLOAD, config="holmes_zoo60", traffic="unit64_hop5",
             chips=1, cfg=None, **kw):
    return harness.run(workload, SEED, 2.0, False, platform="cpu",
                       cfg=cfg if cfg is not None else tiny(config),
                       mix=tiny_mix(traffic), chips=chips, compare=8, **kw)


def test_tiny_run_is_correct_and_the_control_departs(monkeypatch):
    # limits for this size on the CPU, where the program runs exact
    # float32: the cell's own limits are set from chip readings
    monkeypatch.setattr(harness, "load_limits",
                        lambda w: {"combine_gap": 0.0,
                                   "member_mean_gap": 1e-4})
    out = run_tiny(control=True)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True
    assert out["attempted"] == 8 and out["failed"] == 0
    assert set(out["metrics"]) == {"score_p50_ms", "score_p95_ms",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == 1
    checks = out["checks"]
    assert checks["compared"]["value"] == 8
    assert checks["members_compared"]["value"] == 8
    assert checks["combine_gap"]["value"] == 0.0
    assert checks["member_mean_gap"]["value"] < 1e-5
    # the reference in bfloat16, put in the program's place, goes
    # through the same checks and comes out not correct
    ctl = out["control"]
    assert ctl["correct"] is False
    assert ctl["checks"]["member_mean_gap"]["value"] > \
        ctl["checks"]["member_mean_gap"]["limit"]


def _state_unchanged(mp):
    import jax.numpy as jnp
    from repro.serving import slots
    mp.setattr(slots, "_masked_update",
               lambda prev, cands, occ: (prev, jnp.mean(prev, axis=0)))


def _half_the_ensemble(mp):
    from repro.serving.slots import SlotEngine
    orig = SlotEngine._host_combine
    mp.setattr(SlotEngine, "_host_combine",
               lambda self, col, extra, vit: orig(self, col[:len(col) // 2],
                                                  extra, vit))


def _wrap_bucket_fn(mp, alter):
    from repro.serving import pipeline
    orig = pipeline._make_bucket_fn

    def make(*a, **k):
        fn = orig(*a, **k)
        return lambda stacked, win: alter(fn(stacked, win))
    mp.setattr(pipeline, "_make_bucket_fn", make)


def _answer_altered(mp):
    import jax.numpy as jnp
    _wrap_bucket_fn(mp, lambda s: jnp.clip(s + 0.05, 0, 1))


def _half_the_batch(mp):
    # the second half of the slots' rows left out, given the mean of
    # the rows that were scored
    import jax.numpy as jnp

    def alter(s):                                  # [members, rows]
        half = s.shape[1] // 2
        return s.at[:, half:].set(jnp.mean(s[:, :half], axis=1,
                                           keepdims=True))
    _wrap_bucket_fn(mp, alter)


FAULTS = [_state_unchanged, _half_the_ensemble, _half_the_batch,
          _answer_altered]


def _fails_a_gap(out):
    return out["correct"] is False and any(
        c["value"] is None or c["value"] > c["limit"]
        for name, c in out["checks"].items()
        if name.split(".")[0] in harness.GAPS)


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    assert _fails_a_gap(run_tiny())


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct_with_side_models(fault, monkeypatch):
    # the vitals and labs configuration and its stream, on one device
    # (the exchange is the four-device test's)
    fault(monkeypatch)
    assert _fails_a_gap(run_tiny(WORKLOAD, "holmes_zoo60_vitals_labs",
                                 "stream100_hop2"))


def _stalled_run(config, traffic, mp, stall=0.6):
    # closes every 0.5 s per bed, and one tick held between its snapshot
    # and its stamp for longer than that, at the first close of the
    # window: the next close of a bed it scored arrives first, so no
    # tick stamps the earlier close, and the slot engine answers it with
    # the newer close's score, well inside the server's 1 s wait
    from repro.serving.slots import SlotEngine
    mix = tiny_mix(traffic)
    mix.update(hop_s=0.5)
    first = mix["beds"] * (1 + round(mix["preroll_s"] / mix["hop_s"]))
    orig = SlotEngine._host_combine
    held = []

    def combine(self, col, extra, vit):
        if not held and extra.get("qid", -1) >= first:
            held.append(extra["qid"])
            time.sleep(stall)
        return orig(self, col, extra, vit)
    mp.setattr(SlotEngine, "_host_combine", combine)
    # every close of the window compared
    return harness.run(WORKLOAD, SEED, 2.0, False, platform="cpu",
                       cfg=tiny(config), mix=mix, chips=1, compare=64)


SUPERSEDED = re.compile(r"(\d+) of them served with a later close's score")


@pytest.mark.parametrize("config,traffic", [
    ("holmes_zoo60", "unit64_hop5"),
    ("holmes_zoo60_vitals_labs", "stream100_hop2")])
def test_a_superseded_close_is_judged_by_the_close_that_served_it(
        config, traffic, monkeypatch, capsys):
    out = _stalled_run(config, traffic, monkeypatch)
    assert int(SUPERSEDED.search(capsys.readouterr().err).group(1)) >= 1
    assert out["correct"] is True
    assert out["failed"] == 0
    assert out["checks"]["members_compared"]["value"] == out["attempted"]
    assert out["checks"]["combine_gap"]["value"] == 0.0


def test_a_superseded_close_served_wrong_is_not_correct(monkeypatch,
                                                        capsys):
    _half_the_ensemble(monkeypatch)
    out = _stalled_run("holmes_zoo60_vitals_labs", "stream100_hop2",
                       monkeypatch)
    assert int(SUPERSEDED.search(capsys.readouterr().err).group(1)) >= 1
    assert _fails_a_gap(out)


EXCHANGE = r"""
import sys, json
sys.path[:0] = [{tests!r}, {bench!r}, {src!r}]
import test_bench_harness as t
import jax, jax.numpy as jnp
from repro.serving.pipeline import EnsembleService
orig = EnsembleService._ship_packs
def ship(self, packs):
    wins, n = orig(self, packs)
    d0 = jax.devices()[0]
    return {{k: (v if k[1] in (None, d0) else jnp.zeros_like(v))
            for k, v in wins.items()}}, n
if {broken}:
    EnsembleService._ship_packs = ship
out = t.run_tiny(t.WORKLOAD, "holmes_zoo60_vitals_labs", "stream100_hop2",
                 chips=4, control=not {broken})
print(json.dumps(out))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_four_chips_and_the_exchange_left_out(broken):
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
               "--xla_cpu_multi_thread_eigen=false")
    code = EXCHANGE.format(tests=here, bench=harness.HERE,
                           src=os.path.join(harness.ROOT, "src"),
                           broken=broken)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=here,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"] is (not broken)
    if not broken:
        # the side models' rows are in Eq. 5 on both sides, and the
        # bfloat16 control still departs
        assert out["checks"]["combine_gap"]["value"] == 0.0
        assert out["control"]["correct"] is False


def test_traced_run_reports_host_metrics():
    out = harness.run(WORKLOAD, SEED, 2.0, True, platform="cpu",
                      cfg=tiny("holmes_zoo60"),
                      mix=tiny_mix("unit64_hop5"), chips=1, compare=8,
                      peak_flops=1e12)
    assert out["correct"] is True
    for name in ("gen_lag_ms_p95", "queue_wait_ms_p95", "tick_ms",
                 "tick_mfu_pct"):
        assert out["metrics"][name]["value"] >= 0
    assert "setup_s" not in out["metrics"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_command_refuses_a_machine_without_tpu(capsys):
    import run
    code = run.main(["--workload", WORKLOAD, "--seed", "1", "--seconds",
                     "1", "--trace", "0"])
    assert code != 0
    assert capsys.readouterr().out == ""


def test_weights_are_made_from_the_seed():
    import jax
    members = reference.member_specs(tiny("holmes_zoo60"))
    a = reference.init_zoo(members, SEED)
    b = reference.init_zoo(members, SEED)
    c = reference.init_zoo(members, SEED + 1)
    la, lb, lc = (jax.tree.leaves(x) for x in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not np.array_equal(la[0], lc[0])
    assert all(x.dtype == np.float32 for x in la)
