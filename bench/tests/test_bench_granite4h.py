"""The zoo-plus-Granite-4.0-H configuration on the benchmark's side: its
family has every role and counts the hybrid's work as the program does,
a tiny run of it is correct while the control, an off reference for the
hybrid alone and the cells' faults are not, and its three readers read
what a trace of the hybrid program holds and nothing where it is
absent."""
import json
import os

import pytest

import flops_granite4h
import harness
import test_bench_harness as tbh

CONFIG = "holmes_zoo60_granite4h"
WORKLOAD = "zoo60g4h_unit64"
LIMITS = {"combine_gap": 0.0, "member_mean_gap.b2": 1e-4,
          "member_mean_gap.granite": 1e-4}


def tiny_cfg():
    """The zoo's tiny size and a four-layer hybrid at hidden 64 (8
    Mamba-2 heads of 16, d_state 16, chunk 8: 30 tokens of a 3 s
    window, the last chunk partial)."""
    cfg = tbh.tiny(CONFIG)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
               mamba_chunk_size=8, shared_intermediate_size=128,
               num_hidden_layers=4,
               layer_types=["mamba", "mamba", "attention", "mamba"])
    return cfg


def run_tiny(**kw):
    return harness.run(WORKLOAD, tbh.SEED, 2.0, False, platform="cpu",
                       cfg=tiny_cfg(), mix=tbh.tiny_mix("unit64_hop5"),
                       chips=1, compare=8, **kw)


@pytest.fixture
def tiny_limits(monkeypatch):
    # limits for this size on the CPU, where the program runs exact
    # float32: the cell's own limits are set from chip readings
    monkeypatch.setattr(harness, "load_limits", lambda w: dict(LIMITS))


def test_family_has_every_role_and_counts_the_hybrid_by_hand():
    fam = harness.load_family(tiny_cfg())
    members = fam.members(tiny_cfg())
    assert len(members) == 13 and members[-1].kind == "hybrid"
    assert fam.PROGRAMS[:2] == ("fn", "jit(fn)")
    assert "hybrid_member" in fam.PROGRAMS
    groups = fam.gap_groups(members)
    assert groups["granite"] == [12]
    assert sorted(i for t in ("b2", "b4") for i in groups[t]) == \
        list(range(12))
    kf = fam.kernel_flops(members)
    # by hand, d 64, T 30, ff 128, d_inner 128 (8 x 16), N 16, chunks
    # 8, 8, 8, 6: embed 144,000 + head 128; MLPs 4 x 737,280; Mamba-2
    # 3 x (568,320 + 245,760 + conv 19,200 + SSD 181,248); attention
    # 245,760 + 122,880 + 115,200 MACs
    assert kf["hybrid"] == 2 * 6_620_672
    assert kf["ssd"] == 2 * 3 * 181_248
    zoo = harness.load_family(tbh.tiny("holmes_zoo60"))
    zmembers = zoo.members(tbh.tiny("holmes_zoo60"))
    assert kf["conv"] == zoo.kernel_flops(zmembers)["conv"]
    assert fam.step_flops(members) == zoo.step_flops(zmembers) \
        + kf["hybrid"]
    assert fam.cost(members[-1]) == kf["hybrid"] / 2
    assert fam.input_len(members) == 750


@pytest.mark.parametrize("tiny", [False, True], ids=["published", "tiny"])
def test_the_flops_copy_counts_as_the_program(tiny):
    from repro.configs.granite_4h import GraniteHybridSpec
    from repro.models.granite_hybrid import granite_macs
    cfg = tiny_cfg() if tiny else json.load(open(os.path.join(
        harness.HERE, "configs", f"{CONFIG}.json")))
    fam = harness.load_family(cfg)
    m = fam.members(cfg)[-1]
    prog = GraniteHybridSpec(
        layer_types=m.layer_types, d_model=m.d_model, n_heads=m.n_heads,
        n_kv_heads=m.n_kv_heads, head_dim=m.head_dim, d_ff=m.d_ff,
        ssm_heads=m.ssm_heads, ssm_head_dim=m.ssm_head_dim,
        d_state=m.d_state, chunk=m.chunk, input_len=m.input_len)
    assert flops_granite4h.hybrid_macs(m) == granite_macs(prog)


def test_tiny_run_is_correct_and_the_control_departs(tiny_limits):
    out = run_tiny(control=True)
    assert out["correct"] is True
    checks = out["checks"]
    assert checks["members_compared"]["value"] == 8
    assert checks["member_mean_gap.granite"]["value"] < 1e-5
    ctl = out["control"]
    assert ctl["correct"] is False
    assert ctl["checks"]["member_mean_gap.granite"]["value"] > \
        LIMITS["member_mean_gap.granite"]


OFF_HYBRID = '''
import granite4h_reference as _base


def member_scores(params, members, windows, **kw):
    out = _base.member_scores(params, members, windows, **kw)
    out[-1] += 0.05
    return out
'''


def test_a_reference_off_on_the_hybrid_alone_is_not_correct(tmp_path,
                                                            tiny_limits):
    path = tmp_path / "off_hybrid.py"
    path.write_text(OFF_HYBRID)
    cfg = tiny_cfg()
    cfg["reference"] = str(path)
    out = harness.run(WORKLOAD, tbh.SEED, 2.0, False, platform="cpu",
                      cfg=cfg, mix=tbh.tiny_mix("unit64_hop5"), chips=1,
                      compare=8)
    assert out["correct"] is False
    assert out["checks"]["member_mean_gap.granite"]["value"] > 0.04
    assert out["checks"]["member_mean_gap.b2"]["value"] < 1e-5


@pytest.mark.parametrize("fault", [tbh._state_unchanged,
                                   tbh._half_the_ensemble])
def test_fault_is_not_correct(fault, monkeypatch, tiny_limits):
    fault(monkeypatch)
    assert tbh._fails_a_gap(run_tiny())


# ------------------------------------------------------------- readers
def _rec(hybrid=True):
    dev = {"busy_s": 2.5, "program_s": {"jit_fn": 1.0},
           "layer_s": {"bucket": 1.0},
           "layer_op_s": {"bucket": {"fusion/kOutput": 0.5}}}
    if hybrid:
        dev["program_s"]["jit_hybrid_member"] = 1.0
        dev["layer_op_s"]["other"] = {"ssd_scan": 0.05,
                                      "fusion/kOutput": 0.7}
    # 40 zoo bucket programs started: two ticks of 20 beside the hybrid
    return {"trace": {"devices": {0: dev}, "program_n": {"bucket": 40},
                      "window_s": 3.0},
            "kernel_flops": {"conv": 1e9, "hybrid": 1e12, "ssd": 1e10},
            "n_buckets": 21, "spad": 64, "peak_flops": 2.56e14,
            "chips": 1}


@pytest.mark.parametrize("name,want", [
    ("hybrid_member_ms_per_tick", 500.0),        # 1 s over 2 runs
    ("hybrid_member_roofline", 50.0),            # 2 x 1e12 x 64 / 2.56e14
    ("ssd_roofline", 10.0),                      # 2 x 1e10 x 64 / 2.56e14
])
def test_reader_reads_the_hybrid_program(name, want):
    read = harness.load_reader(name)
    assert read(_rec()) == pytest.approx(want)
    assert read(_rec(hybrid=False)) is None
