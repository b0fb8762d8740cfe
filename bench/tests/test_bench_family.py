"""A configuration names its member family and its reference, and the
harness takes both from there: every configuration's family has every
role, the ECG zoo's family counts the work it always counted, the
scores are judged against the configuration's own reference, and a
family added as a new file runs the tiny CPU path with no other
change."""
import glob
import json
import os
import shutil

import pytest

import flops
import harness
import reference
import test_bench_harness as tbh
import traffic

CONFIGS = sorted(glob.glob(os.path.join(harness.HERE, "configs", "*.json")))


@pytest.mark.parametrize("path", CONFIGS,
                         ids=[os.path.basename(p) for p in CONFIGS])
def test_every_configuration_loads_its_family_and_reference(path):
    with open(path) as f:
        cfg = json.load(f)
    fam = harness.load_family(cfg)
    assert all(hasattr(fam, role) for role in harness.FAMILY_ROLES)
    assert callable(harness.load_reference(cfg).member_scores)
    members = fam.members(cfg)
    assert members
    groups = fam.gap_groups(members)
    assert groups and all(rows for rows in groups.values())
    assert 0 < fam.input_len(members) <= cfg["window_s"] * traffic.ECG_HZ
    assert fam.step_flops(members) > 0
    assert fam.kernel_flops(members) and all(
        v > 0 for v in fam.kernel_flops(members).values())
    assert all(fam.cost(m) > 0 for m in members)
    assert fam.PROGRAMS


def test_ecg_resnext_counts_the_zoo_as_before():
    with open(os.path.join(harness.HERE, "configs", "holmes_zoo60.json")) as f:
        cfg = json.load(f)
    fam = harness.load_family(cfg)
    members = fam.members(cfg)
    # the record's old ``zoo_flops`` and ``conv_flops`` on the full zoo
    assert fam.step_flops(members) == flops.zoo_flops(members) \
        == 4765840966.5
    assert fam.kernel_flops(members) == {"conv": flops.conv_flops(members)}
    assert flops.conv_flops(members) == 4765829062.5
    assert fam.PROGRAMS == ("fn", "jit(fn)")
    groups = fam.gap_groups(members)
    assert list(groups) == ["w8", "w16", "w32", "w64", "w128", "b2", "b4",
                            "b8", "b16"]
    assert sorted(i for t in ("b2", "b4", "b8", "b16")
                  for i in groups[t]) == list(range(60))


def test_unknown_family_is_refused_before_any_device_work(monkeypatch):
    cfg = tbh.tiny("holmes_zoo60")
    cfg["family"] = "no_such_family"
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a: pytest.fail("device work began"))
    with pytest.raises(harness.BenchError, match="no_such_family"):
        tbh.run_tiny(cfg=cfg)
    del cfg["family"]
    with pytest.raises(harness.BenchError, match="member family"):
        harness.load_family(cfg)


OFF_REFERENCE = '''
import reference as _base


def member_scores(*args, **kwargs):
    return _base.member_scores(*args, **kwargs) + 0.05
'''


def test_a_reference_that_is_off_makes_the_run_not_correct(tmp_path):
    path = tmp_path / "off_reference.py"
    path.write_text(OFF_REFERENCE)
    cfg = tbh.tiny("holmes_zoo60")
    cfg["reference"] = str(path)
    out = tbh.run_tiny(cfg=cfg)
    assert out["correct"] is False
    assert out["checks"]["member_mean_gap.b2"]["value"] > 0.04


# a family of its own, in a directory of its own: the first lead's
# members only, reported as one group, costed by their widths
LEAD1 = '''
import reference

PROGRAMS = ("fn", "jit(fn)")
init = reference.init_zoo


def members(cfg):
    return [m for m in reference.member_specs(cfg) if m.lead == 0]


def program(members, params):
    from repro.configs.ecg_zoo import EcgModelSpec, bucket_zoo
    from repro.serving.pipeline import ZooMember
    specs = [EcgModelSpec(m.name, m.lead, m.width, m.blocks, m.input_len,
                          m.cardinality, m.kernel_size) for m in members]
    return ([ZooMember(s, p) for s, p in zip(specs, params)],
            list(bucket_zoo(specs).values()))


def input_len(members):
    return members[0].input_len


def step_flops(members):
    return 1.0e6 * len(members)


def kernel_flops(members):
    return {"lead1": step_flops(members)}


def cost(member):
    return float(member.width)


def gap_groups(members):
    return {"lead1": list(range(len(members)))}
'''


def test_a_family_added_as_a_file_runs(tmp_path, monkeypatch):
    (tmp_path / "lead1_resnext.py").write_text(LEAD1)
    shutil.copy(reference.__file__, tmp_path / "lead1_reference.py")
    monkeypatch.setattr(harness, "FAMILIES", str(tmp_path))
    monkeypatch.setattr(harness, "load_limits",
                        lambda w: {"combine_gap": 0.0,
                                   "member_mean_gap.lead1": 1e-4})
    cfg = tbh.tiny("holmes_zoo60")
    cfg.update(family="lead1_resnext",
               reference=str(tmp_path / "lead1_reference.py"))
    out = tbh.run_tiny(cfg=cfg, control=True)
    assert out["correct"] is True
    assert set(out["checks"]) == {"compared", "bucket_compiles_in_window",
                                  "combine_gap", "member_mean_gap.lead1",
                                  "members_compared"}
    assert out["checks"]["member_mean_gap.lead1"]["value"] < 1e-5
    assert out["readings"]["member_mean_gap.lead1"] is not None
    assert not any(k.startswith("member_mean_gap.b") for k in out["readings"])
    assert out["control"]["correct"] is False
