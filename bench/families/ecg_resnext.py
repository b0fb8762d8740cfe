"""The member family of the HOLMES ECG zoo: 1-D stripe ResNeXt
classifiers, one per lead x width x depth (KDD 2020, §4.1.1).

A configuration names its family (``"family": "ecg_resnext"``) and the
harness loads this file by that name.  A family supplies, from the
configuration alone:

* ``members(cfg)``: the member specs, in the program's zoo order;
* ``init(members, seed, device)``: every member's weights from the
  seed, made on the device;
* ``program(members, params)``: the objects the program's
  ``EnsembleService`` takes, and the groups of members it stacks into
  one program (placed whole on one chip).  Only this imports the
  program;
* ``input_len(members)``: the ECG samples each member reads per close;
* ``step_flops(members)``, ``kernel_flops(members)``: FLOPs of the
  whole step and of each kernel, per scored row; ``cost(member)``: a
  member's cost for LPT placement;
* ``gap_groups(members)``: the groups of members whose mean gap from the
  reference ``member_mean_gap.<tag>`` reports;
* ``PROGRAMS``: the names under which the program compiles the family's
  bucket programs, counted by ``bucket_compiles_in_window``.

Specs and weights come from ``reference.py`` and work counts from
``flops.py``, so the reference, the weights and the roofline all follow
one description of the zoo.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import flops
import reference

PROGRAMS = ("fn", "jit(fn)")

members = reference.member_specs
init = reference.init_zoo
cost = flops.member_macs


def program(members: Sequence, params: Sequence):
    from repro.configs.ecg_zoo import EcgModelSpec, bucket_zoo
    from repro.serving.pipeline import ZooMember
    specs = [EcgModelSpec(m.name, m.lead, m.width, m.blocks, m.input_len,
                          m.cardinality, m.kernel_size) for m in members]
    zoo = [ZooMember(s, p) for s, p in zip(specs, params)]
    return zoo, list(bucket_zoo(specs).values())


def input_len(members: Sequence) -> int:
    return members[0].input_len


def step_flops(members: Sequence) -> float:
    return flops.zoo_flops(members)


def kernel_flops(members: Sequence) -> Dict[str, float]:
    return {"conv": flops.conv_flops(members)}


def gap_groups(members: Sequence) -> Dict[str, List[int]]:
    """Members of each width (``w8``) and of each depth (``b16``)."""
    out = {}
    for key, tag in (("width", "w"), ("blocks", "b")):
        for v in sorted({getattr(m, key) for m in members}):
            out[f"{tag}{v}"] = [i for i, m in enumerate(members)
                                if getattr(m, key) == v]
    return out
