"""1-D "stripe" grouped convolution Pallas TPU kernel.

The hot op of the paper's ECG ResNeXt zoo (and the Mamba short conv).
TPU adaptation (DESIGN.md §2): instead of an im2col buffer, the conv is a
K-tap sum of shifted [L_out, Cin] x [Cin, Cout] matmuls — MXU-shaped
without materializing patches.

Each grid step holds one window's full (padded) length and FULL channel
width in VMEM (7506 x 128 floats = 3.8 MB at the zoo's widest layer).
Every tap is read straight from the ref with a static, possibly
strided, ``pl.ds`` — the TPU lowering has no ``dynamic_slice`` on a
loaded value.  Channel groups are done inside the step: the grouped
weight is expanded to its block-diagonal dense ``[K, Cin, Cout]`` form,
so a block never splits the lane axis into ``Cin/groups``-wide pieces
(the zoo's cardinality-8 stripes have 8-lane groups, which the (8, 128)
block rule refuses) and one matmul per tap covers every group.  The
off-diagonal zeros cost MXU passes that the 128-wide systolic array
would spend on padding anyway at these widths.  A single-channel input
(the stem) takes a VPU outer product instead of a contraction of size 1.

``conv1d_stripe_stacked`` is the ensemble-serving form: a leading MEMBER
axis on activations and weights (grid ``(member, batch)``) so one kernel
launch covers a whole architecture bucket of stacked zoo members.
``conv1d_stripe`` is the same kernel with one member.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# scoped-VMEM ceiling for one step: the stem's lane-padded [Lp, 1] input
# plus the [L_out, Cout] output, double-buffered, with the f32
# accumulator, exceeds the compiler's 16 MiB default at L=7500
_VMEM_LIMIT = 64 * 1024 * 1024


def _kernel(x_ref, w_ref, y_ref, *, K: int, stride: int, L_out: int):
    cin = x_ref.shape[-1]
    acc = jnp.zeros((L_out, y_ref.shape[-1]), jnp.float32)
    for k in range(K):                            # K is small (1 or 7)
        xk = x_ref[0, 0, pl.ds(k, L_out, stride=stride), :]  # [L_out, Cin]
        wk = w_ref[0, k]                                     # [Cin, Cout]
        if cin == 1:
            acc += xk.astype(jnp.float32) * wk.astype(jnp.float32)
        else:
            acc += jax.lax.dot_general(
                xk, wk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    y_ref[0, 0] = acc.astype(y_ref.dtype)


def _same_padding(L: int, K: int, stride: int):
    """(lo, hi, L_out) for lax-convention SAME padding."""
    L_out = -(-L // stride)                       # ceil, as in SAME
    pad_total = max((L_out - 1) * stride + K - L, 0)
    lo = pad_total // 2
    return lo, pad_total - lo, L_out


def _block_diagonal(w: jax.Array, groups: int) -> jax.Array:
    """[M, K, Cin/groups, Cout] grouped weight -> [M, K, Cin, Cout] dense
    weight that is zero outside each group's diagonal block."""
    if groups == 1:
        return w
    cin_g, cout = w.shape[2], w.shape[3]
    g_in = jnp.arange(cin_g * groups) // cin_g
    g_out = jnp.arange(cout) // (cout // groups)
    same = (g_in[:, None] == g_out[None, :])
    return jnp.where(same, jnp.tile(w, (1, 1, groups, 1)),
                     jnp.zeros((), w.dtype))


@functools.partial(jax.jit, static_argnames=("stride", "groups", "padding",
                                             "interpret"))
def conv1d_stripe_stacked(x: jax.Array, w: jax.Array,
                          b: Optional[jax.Array] = None,
                          stride: int = 1, groups: int = 1,
                          padding: str = "SAME", *,
                          interpret: bool = False) -> jax.Array:
    """Member-stacked stripe conv for bucketed ensemble serving.

    x: [M, B, L, Cin]; w: [M, K, Cin//groups, Cout]; b: [M, Cout].
    One launch computes all M stacked members on the shared micro-batch:
    grid (member, batch), each step one window at full length and full
    channel width.  Matches ``jax.vmap(ref.conv1d_stripe)``.
    """
    M, B, L, Cin = x.shape
    Mw, K, _, Cout = w.shape
    if Mw != M:
        raise ValueError(f"weight stacks {Mw} members, input {M}")

    if padding == "CAUSAL":
        L_out = -(-L // stride)
        lo, hi = K - 1, 0
    else:
        lo, hi, L_out = _same_padding(L, K, stride)
    # both paddings leave the last tap's read, (L_out - 1) * stride + K,
    # inside the padded length
    xp = jnp.pad(x, ((0, 0), (0, 0), (lo, hi), (0, 0)))
    Lp = xp.shape[2]
    wd = _block_diagonal(w, groups)

    y = pl.pallas_call(
        functools.partial(_kernel, K=K, stride=stride, L_out=L_out),
        grid=(M, B),
        in_specs=[
            pl.BlockSpec((1, 1, Lp, Cin), lambda m, bi: (m, bi, 0, 0)),
            pl.BlockSpec((1, K, Cin, Cout), lambda m, bi: (m, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, L_out, Cout),
                               lambda m, bi: (m, bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, B, L_out, Cout), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(xp, wd)
    if b is not None:
        y = y + b[:, None, None, :]
    return y


def conv1d_stripe(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
                  stride: int = 1, groups: int = 1, padding: str = "SAME",
                  *, interpret: bool = False) -> jax.Array:
    """x: [B, L, Cin]; w: [K, Cin//groups, Cout]; SAME or CAUSAL padding.
    Matches ref.conv1d_stripe / lax.conv_general_dilated."""
    y = conv1d_stripe_stacked(x[None], w[None], None, stride=stride,
                              groups=groups, padding=padding,
                              interpret=interpret)[0]
    return y if b is None else y + b
