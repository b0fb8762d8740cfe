"""The benchmark's MAC counter is a copy of the program's; they agree
on every member of the paper's full zoo."""
import json
import os

import flops
import reference


def _full_zoo():
    with open(os.path.join(os.path.dirname(flops.__file__), "configs",
                           "holmes_zoo60.json")) as f:
        return reference.member_specs(json.load(f))


def test_member_macs_equal_program_on_full_zoo():
    from repro.configs.ecg_zoo import EcgModelSpec, zoo_specs
    from repro.models.ecg_resnext import ecg_macs
    ours = _full_zoo()
    theirs = zoo_specs(reduced=False)
    assert len(ours) == len(theirs) == 60
    for m, s in zip(ours, theirs):
        assert (m.name, m.lead, m.width, m.blocks, m.input_len,
                m.cardinality, m.kernel_size) == (
            s.name, s.lead, s.width, s.blocks, s.input_len,
            s.cardinality, s.kernel_size)
        assert flops.member_macs(m) == ecg_macs(s)


def test_zoo_work_per_window():
    zoo = _full_zoo()
    # 2.383 GMAC per bed-window; the convolutions are all but the heads
    assert abs(flops.zoo_flops(zoo) / 2 / 2.383e9 - 1) < 1e-3
    heads = 2 * sum(m.width * 2 for m in zoo)
    assert flops.zoo_flops(zoo) - flops.conv_flops(zoo) == heads
