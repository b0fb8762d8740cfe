"""The HOLMES ECG zoo with a Granite-4.0-H hybrid member beside it.

The 60 stacked 1-D ResNeXt members come from ``ecg_resnext.py`` (same
specs, weights, work counts, groups and program names); the hybrid
(Mamba-2 + NoPE GQA at the published widths of granite-4.0-h-micro,
over 0.1 s x 3-lead patches) is the last member.  Its spec and weights
come from ``granite4h_reference.py`` and its work from
``flops_granite4h.py``, so the reference, the weights and the rooflines
follow one description.  Roles as ``ecg_resnext.py`` lists them; the
kernel FLOPs per row are ``conv`` (the zoo's convolutions), ``hybrid``
(the hybrid's whole program, ``jit_hybrid_member`` in the trace) and
``ssd`` (its SSD scans, op class ``ssd_scan``).
"""
from __future__ import annotations

import importlib.util
import os
from typing import Dict, List, Sequence

import flops_granite4h
import granite4h_reference as g4h


def _load_zoo():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "ecg_resnext.py")
    spec = importlib.util.spec_from_file_location(
        "bench_family_ecg_resnext", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_zoo = _load_zoo()

PROGRAMS = _zoo.PROGRAMS + ("hybrid_member", "jit(hybrid_member)")


def _split(members: Sequence):
    hyb = [i for i, m in enumerate(members)
           if getattr(m, "kind", None) == "hybrid"]
    return [m for i, m in enumerate(members) if i not in hyb], hyb


def members(cfg: Dict) -> List:
    return _zoo.members(cfg) + [g4h.hybrid_spec(cfg)]


def init(members: Sequence, seed: int, device=None) -> List:
    zoo, hyb = _split(members)
    params = iter(_zoo.init(zoo, seed, device=device))
    out = []
    for i, m in enumerate(members):
        out.append(g4h.init_hybrid(m, seed, device) if i in hyb
                   else next(params))
    return out


def program(members: Sequence, params: Sequence):
    from repro.configs.granite_4h import GraniteHybridSpec
    from repro.serving.pipeline import HybridMember
    zoo, hyb = _split(members)
    served, stacks = _zoo.program(zoo, [p for i, p in enumerate(params)
                                        if i not in hyb])
    for i in hyb:
        m = members[i]
        spec = GraniteHybridSpec(
            name=m.name, layer_types=m.layer_types, d_model=m.d_model,
            n_heads=m.n_heads, n_kv_heads=m.n_kv_heads,
            head_dim=m.head_dim, d_ff=m.d_ff, ssm_heads=m.ssm_heads,
            ssm_head_dim=m.ssm_head_dim,
            d_state=m.d_state, n_groups=m.n_groups, d_conv=m.d_conv,
            chunk=m.chunk, attention_multiplier=m.attention_multiplier,
            residual_multiplier=m.residual_multiplier,
            embedding_multiplier=m.embedding_multiplier,
            norm_eps=m.norm_eps, leads=m.leads, patch=m.patch,
            input_len=m.input_len)
        served.insert(i, HybridMember(spec, params[i]))
        stacks.append([i])
    return served, stacks


def input_len(members: Sequence) -> int:
    lens = {m.input_len for m in members}
    if len(lens) != 1:
        raise ValueError(f"members read windows of {sorted(lens)} samples")
    return lens.pop()


def _hybrid_flops(members: Sequence, fn) -> float:
    return 2.0 * sum(fn(m) for m in members
                     if getattr(m, "kind", None) == "hybrid")


def step_flops(members: Sequence) -> float:
    zoo, _ = _split(members)
    return _zoo.step_flops(zoo) + _hybrid_flops(members,
                                                flops_granite4h.hybrid_macs)


def kernel_flops(members: Sequence) -> Dict[str, float]:
    zoo, _ = _split(members)
    return {**_zoo.kernel_flops(zoo),
            "hybrid": _hybrid_flops(members, flops_granite4h.hybrid_macs),
            "ssd": _hybrid_flops(members, flops_granite4h.ssd_macs)}


def cost(member) -> float:
    if getattr(member, "kind", None) == "hybrid":
        return flops_granite4h.hybrid_macs(member)
    return _zoo.cost(member)


def gap_groups(members: Sequence) -> Dict[str, List[int]]:
    """The zoo's groups (by width, by depth) and ``granite``."""
    zoo, hyb = _split(members)
    rows = [i for i in range(len(members)) if i not in hyb]
    out = {tag: [rows[j] for j in idx]
           for tag, idx in _zoo.gap_groups(zoo).items()}
    out["granite"] = hyb
    return out
