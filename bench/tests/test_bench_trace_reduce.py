"""The trace reduction, on a hand-made extract and on one tick recorded
on a TPU v5e chip (two full-width buckets, 64 beds)."""
import gzip
import json
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
LAYERS = {"ingest": ["jit__ingest_padded"], "bucket": ["jit_fn"]}

CONV = ("%fusion.1 = f32[3,64,8] fusion(f32[3,8] %a), kind=kOutput, "
        "calls=%fused_computation.1")
LOOP = "%fusion.2 = f32[3,64,8] fusion(f32[3,8] %a), kind=kLoop, calls=%f"
COPY = "%copy.3 = f32[64,3] copy(f32[64,3] %b)"


def _conv_s(d):
    return sum(s for cls, s in d["layer_op_s"].get("bucket", {}).items()
               if tr.is_conv(cls))


def _made():
    return {"devices": {0: {
        "modules": [["jit_fn(1)", 100, 400], ["jit__ingest_padded(2)", 500,
                                               600],
                    ["jit_fn(1)", 900, 1300]],
        "ops": [[CONV, 100, 250], [LOOP, 240, 400], [COPY, 500, 600],
                [CONV, 900, 1100], [LOOP, 1150, 1300]]},
        1: {"modules": [["jit_fn(3)", 200, 300]],
            "ops": [[CONV, 200, 300]]}},
        "host": [["python", tr.WINDOW, 0, 1000],
                 ["python", "gen.ecg", 650, 850],
                 ["python", "client.read", 420, 480]]}


def test_reduce_hand_made():
    r = tr.reduce(_made(), layers=LAYERS)
    assert r["window_s"] == pytest.approx(1000e-9)
    d0, d1 = r["devices"][0], r["devices"][1]
    # busy: union of [100,400], [500,600], [900,1000] (clipped)
    assert d0["busy_s"] == pytest.approx(500e-9)
    assert d0["layer_s"]["bucket"] == pytest.approx(400e-9)
    assert d0["layer_s"]["ingest"] == pytest.approx(100e-9)
    assert _conv_s(d0) == pytest.approx(250e-9)
    assert _conv_s(d1) == pytest.approx(100e-9)
    # every op's time, by layer and op class
    assert d0["layer_op_s"] == {
        "bucket": {"fusion/kOutput": pytest.approx(250e-9),
                   "fusion/kLoop": pytest.approx(160e-9)},
        "ingest": {"copy": pytest.approx(100e-9)}}
    assert r["program_n"] == {"bucket": 3, "ingest": 1}
    assert r["unmatched_layers"] == []
    gaps = sorted(r["idle_gaps"], key=lambda g: -g[1])
    assert gaps[0][0].startswith("TPU:1")           # 300 -> 1000
    assert gaps[0][1] == pytest.approx(700e-9)
    dev0 = [g for g in gaps if g[0].startswith("TPU:0")]
    assert dev0[0][1] == pytest.approx(300e-9)      # 600 -> 900
    assert "gen.ecg" in dev0[0][0]
    ops = dict(r["device_ops"])
    assert ops["jit_fn fusion/kOutput"] == pytest.approx(350e-9)
    assert ops["jit__ingest_padded copy"] == pytest.approx(100e-9)


def test_unmatched_layer_is_reported():
    r = tr.reduce(_made(), layers={"bucket": ["jit_fn"],
                                   "fold": ["jit__masked_update"]})
    assert r["unmatched_layers"] == ["fold"]


def test_op_classes():
    assert tr.op_class(CONV) == "fusion/kOutput" and tr.is_conv(CONV)
    assert not tr.is_conv(LOOP) and not tr.is_conv(COPY)
    # a class is its own class, so a reader can select classes
    assert tr.is_conv(tr.op_class(CONV)) and not tr.is_conv("fusion/kLoop")
    assert tr.is_conv("%convolution.4 = f32[2] convolution(f32[2] %x)")


def test_reduce_recorded_tpu_tick():
    with gzip.open(os.path.join(DATA, "probe_tick.json.gz"), "rt") as f:
        ex = json.load(f)
    r = tr.reduce(ex)
    d = r["devices"][0]
    assert r["unmatched_layers"] == []
    assert 0 < d["busy_s"] < r["window_s"]
    # one tick of two bucket programs and one gather, 64 packets
    assert r["program_n"]["bucket"] == 2
    assert r["program_n"]["gather"] == 1
    assert r["program_n"]["ingest"] == 64
    assert 0 < _conv_s(d) < d["layer_s"]["bucket"]
    # op time by layer adds up to the busy time where ops do not overlap
    assert sum(s for ops in d["layer_op_s"].values()
               for s in ops.values()) >= d["busy_s"] * 0.999
    # programs span their ops and the short gaps between them
    assert d["busy_s"] <= sum(d["program_s"].values()) < r["window_s"]
    assert len(r["idle_gaps"]) == 10 and len(r["device_ops"]) == 10
