"""Analytic work of the Granite-4.0-H hybrid member, from its shapes alone.

``hybrid_macs`` is a copy of the program's
``models/granite_hybrid.granite_macs``: multiply-accumulates of one
member on one window (the patch embedding, every projection and MLP, the
depthwise convolutions, the SSD's chunked form, the attention's scores
and mixing over all 300 x 300 positions, as the program computes them,
and the head).  ``ssd_macs`` counts the SSD scans alone, per head and
chunk ``C.B^T``, ``W.x``, ``C.h^T`` and ``(x w)^T.B`` over the window's
tokens, the last chunk partial: the numerator of ``ssd_roofline``.
``bench/tests/test_bench_family.py`` checks the copy against the
program.  Two FLOPs per multiply-accumulate.
"""
from __future__ import annotations


def _chunks(m):
    T = m.tokens
    return [min(m.chunk, T - t0) for t0 in range(0, T, m.chunk)]


def _ssd_layer_macs(m) -> float:
    N, P = m.d_state, m.ssm_head_dim
    return m.ssm_heads * sum(c * c * (N + P) + 2 * c * N * P
                             for c in _chunks(m))


def ssd_macs(m) -> float:
    """The SSD scans of one member on one window."""
    return float(sum(_ssd_layer_macs(m) for k in m.layer_types
                     if k == "mamba"))


def hybrid_macs(m) -> float:
    """Everything the member's program multiplies on one window."""
    d, T, f = m.d_model, m.tokens, m.d_ff
    di, H = m.d_inner, m.ssm_heads
    gn = m.n_groups * m.d_state
    q, kv = m.n_heads * m.head_dim, m.n_kv_heads * m.head_dim
    macs = T * m.leads * m.patch * d + d * 2
    for kind in m.layer_types:
        macs += T * 3 * d * f
        if kind == "mamba":
            macs += T * d * (2 * di + 2 * gn + H) + T * di * d
            macs += T * m.d_conv * (di + 2 * gn)
            macs += _ssd_layer_macs(m)
        else:
            macs += T * d * (q + 2 * kv) + T * q * d
            macs += 2 * m.n_heads * T * T * m.head_dim
    return float(macs)
