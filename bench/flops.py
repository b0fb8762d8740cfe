"""Analytic work of the HOLMES zoo, from its shapes alone.

``member_macs`` is a copy of the program's ``models/ecg_resnext.ecg_macs``
(the MACS column of the paper's Table 3): multiply-accumulates of one
member on one window, convolutions plus the 2-way head.
``conv_flops`` counts only the convolutions (stem, 1x1 reduce, grouped
stripe, 1x1 expand), at 2 FLOPs per multiply-accumulate: the numerator
of ``conv_roofline``.  ``tests/test_bench_flops.py`` checks the copy
against the program on all 60 members of the full zoo.
"""
from __future__ import annotations

from typing import Iterable


def _shapes(m):
    return (m.input_len, m.width, m.kernel_size, m.cardinality, m.blocks)


def _conv_macs(m) -> float:
    L_in, W, K, card, blocks = _shapes(m)
    L = L_in / 2                                        # after stem stride
    macs = L_in / 2 * K * W                             # stem
    for i in range(blocks):
        stride = 2 if i % 2 == 0 else 1
        inner = max(card, W // 2)
        inner -= inner % card
        macs += L * W * inner                           # reduce 1x1
        L = L / stride
        macs += L * K * inner * inner / card            # grouped stripe
        macs += L * inner * W                           # expand 1x1
    return float(macs)


def member_macs(m) -> float:
    """Multiply-accumulates of one member on one window."""
    return _conv_macs(m) + m.width * 2


def zoo_flops(members: Iterable) -> float:
    """FLOPs of the whole zoo on one window (2 per MAC)."""
    return 2.0 * sum(member_macs(m) for m in members)


def conv_flops(members: Iterable) -> float:
    """Convolution FLOPs of the whole zoo on one window."""
    return 2.0 * sum(_conv_macs(m) for m in members)
