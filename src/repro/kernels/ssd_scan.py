"""Mamba-2 SSD (state-space duality) chunked-scan Pallas TPU kernel.

The SSD insight: within a chunk the recurrence is a (masked, decay-weighted)
attention-like quadratic form that maps onto the MXU; across chunks only a
small [P, N] state is carried.  We put the chunk axis innermost in the grid
so the carried state lives in VMEM scratch across sequential grid steps —
the TPU-native replacement for the CUDA warp-parallel scan.

Grid: (batch, heads, chunks).  The wrapper lays the operands out head-major
so every block's last two dims are whole or lane-aligned, as the chip's
compiler requires: x [C,P], dt as a column [C,1] and a row [1,C], B/C
[C,N] (groups broadcast to heads in the index map), carried h [P,N] in
scratch; A and D are per-head scalars read from SMEM.  The within-chunk
prefix sums are masked row/column reductions (no cumsum on the chip).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dtc_ref, dtr_ref, A_ref, B_ref, C_ref, D_ref, h0_ref,
            y_ref, hT_ref, h_ref, *, chunk: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0, 0].astype(jnp.float32)       # [P, N]

    x = x_ref[0, 0].astype(jnp.float32)                     # [C, P]
    dtc = dtc_ref[0, 0].astype(jnp.float32)                 # [C, 1]
    dtr = dtr_ref[0, 0].astype(jnp.float32)                 # [1, C]
    Bm = B_ref[0, 0].astype(jnp.float32)                    # [C, N]
    Cm = C_ref[0, 0].astype(jnp.float32)                    # [C, N]
    A = A_ref[hi]                                           # scalar (<0)
    D = D_ref[hi]

    # inclusive prefix sums of dt*A, as a column and as a row
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    low = ii >= jj
    seg_c = jnp.sum(jnp.where(low, dtr * A, 0.0), axis=1,
                    keepdims=True)                          # [C, 1]
    seg_r = jnp.sum(jnp.where(ii <= jj, dtc * A, 0.0), axis=0,
                    keepdims=True)                          # [1, C]
    total = jnp.sum(dtr * A)

    # within-chunk quadratic term (the "duality" matmul)
    L = jnp.where(low, jnp.exp(seg_c - seg_r), 0.0)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [C, C]
    W = CB * L * dtr
    y = jax.lax.dot_general(W, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # contribution of the carried state
    h = h_ref[...]                                          # [P, N]
    y += jnp.exp(seg_c) * jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: h' = exp(total) h + sum_j decay_j dt_j x_j B_j^T
    xw = (x * (jnp.exp(total - seg_c) * dtc)).T             # [P, C]
    h_new = jnp.exp(total) * h + jax.lax.dot_general(
        xw, Bm, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    h_ref[...] = h_new

    y_ref[0, 0] = (y + D * x).astype(y_ref.dtype)

    @pl.when(ci == pl.num_programs(2) - 1)
    def _finish():
        hT_ref[0, 0] = h_new.astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B_: jax.Array,
        C: jax.Array, D: jax.Array, chunk: int,
        h0: Optional[jax.Array] = None, *, interpret: bool = False):
    """Same contract as ref.ssd_chunked.  x: [B,S,H,P]; dt: [B,S,H];
    A,D: [H]; B_,C: [B,S,G,N]; h0: [B,H,P,N] -> (y [B,S,H,P], hT)."""
    b, S, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    rep = H // G
    S0 = S
    if S % chunk:
        pad = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))   # dt=0 -> no-op steps
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S += pad
    if h0 is None:
        h0 = jnp.zeros((b, H, P, N), jnp.float32)
    xt = jnp.transpose(x, (0, 2, 1, 3))                     # [B,H,S,P]
    dtt = jnp.transpose(dt.astype(jnp.float32), (0, 2, 1))  # [B,H,S]
    Bt = jnp.transpose(B_, (0, 2, 1, 3))                    # [B,G,S,N]
    Ct = jnp.transpose(C, (0, 2, 1, 3))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    grid = (b, H, S // chunk)
    y, hT = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bi, h, ci: (bi, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, h, ci: (bi, h, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, h, ci: (bi, h, 0, ci)),
            smem,
            pl.BlockSpec((1, 1, chunk, N),
                         lambda bi, h, ci, _r=rep: (bi, h // _r, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda bi, h, ci, _r=rep: (bi, h // _r, ci, 0)),
            smem,
            pl.BlockSpec((1, 1, P, N), lambda bi, h, ci: (bi, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bi, h, ci: (bi, h, ci, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bi, h, ci: (bi, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((b, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(xt, dtt[..., None], dtt[:, :, None, :], A.astype(jnp.float32), Bt,
      Ct, D.astype(jnp.float32), h0)
    return jnp.transpose(y, (0, 2, 1, 3))[:, :S0], hT
