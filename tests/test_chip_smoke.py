"""``chip_smoke.run`` end to end on the CPU at the reduced zoo: the same
phases the chip run takes (zoo build, device ingest, slot engine and
flush path, float32 reference, stripe-conv kernel bucket), steered to a
small size and the CPU backend through its arguments."""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402


def test_run_reduced_zoo_four_beds():
    lines = []
    device = chip_smoke.run(reduced=True, n_beds=4, platform="cpu",
                            pallas_impl="pallas_interpret",
                            log=lines.append)
    assert device == {"platform": "cpu",
                      "kind": jax.devices()[0].device_kind,
                      "count": len(jax.devices())}
    text = "\n".join(lines)
    assert "12 members in 4 buckets, 4 beds" in text
    assert "slot scores == flush scores bitwise" in text
    assert "kernel vs xla" in text


def test_run_refuses_another_platform():
    with pytest.raises(chip_smoke.SmokeFailure, match="platform"):
        chip_smoke.run(reduced=True, n_beds=4, platform="tpu",
                       log=lambda _: None)
