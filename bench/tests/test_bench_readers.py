"""Per-layer readers on a synthetic record of one traced run: what each
reads, and that each stays silent where there is nothing to read."""
import pytest

import harness


def _rec(**kw):
    rec = {
        "t0": 10.0, "t1": 13.0, "chips": 4, "spad": 128, "n_buckets": 20,
        # (end, seconds, n_scored, spad): two ticks inside, one after
        "ticks": [(10.5, 0.2, 100, 128), (11.0, 0.3, 100, 128),
                  (13.5, 0.2, 100, 128)],
        # (start, seconds) per side-model call
        "side": [(10.2, 0.001), (10.7, 0.003), (9.0, 1.0), (13.2, 1.0)],
        "step_flops": 4.0e9, "kernel_flops": {"conv": 3.0e9},
        "peak_flops": 1.0e14,
        "trace": {"window_s": 3.0, "program_n": {"bucket": 40},
                  "devices": {
                      d: {"busy_s": 1.5,
                          "layer_s": {"bucket": s},
                          "layer_op_s": {"bucket": {"fusion/kOutput": s / 2,
                                                    "fusion/kLoop": s / 2}}}
                      for d, s in enumerate((0.4, 0.2, 0.2, 0.0))}},
    }
    rec.update(kw)
    return rec


def test_side_models_ms_per_tick():
    read = harness.load_reader("side_models_ms_per_tick")
    # 4 ms of calls inside the window over its 2 ticks
    assert read(_rec()) == pytest.approx(2.0)
    assert read(_rec(side=[])) is None
    assert read(_rec(ticks=[])) is None


def test_roofline_and_mfu_read_the_generic_keys():
    rec = _rec()
    # two ticks' worth of bucket programs; conv ops 0.4 s over the chips
    least = 2 * 3.0e9 * 128 / 1.0e14
    assert harness.load_reader("conv_roofline")(rec) == \
        pytest.approx(least / 0.4 * 100)
    assert harness.load_reader("conv_roofline")(
        _rec(kernel_flops={"other": 1.0})) is None
    assert harness.load_reader("tick_mfu_pct")(rec) == \
        pytest.approx(200 * 4.0e9 / 0.5 / (4 * 1.0e14) * 100)


def test_placement_skew():
    read = harness.load_reader("placement_skew")
    # 0.4 s on the busiest of four chips, 0.8 s in all
    assert read(_rec()) == pytest.approx(2.0)
    # a chip whose trace holds no bucket program still counts in the mean
    rec = _rec()
    del rec["trace"]["devices"][3]["layer_s"]["bucket"]
    assert read(rec) == pytest.approx(2.0)
    assert read(_rec(chips=1)) is None
    for d in rec["trace"]["devices"].values():
        d["layer_s"].pop("bucket", None)
    assert read(rec) is None
