"""Granite-4.0-H hybrid stack as an ECG member (configs/granite_4h.py).

win [P, 3, L] -> patches [P, T, 75] -> x 2048 linear, x 12 -> the layers
in published order, each ``h += 0.22 * mixer(rms(h))`` (Mamba-2 via
``models/ssm.mamba2_apply``, or NoPE GQA via ``models/attention.gqa_apply``
scaled by the attention multiplier) then ``h += 0.22 * swiglu(rms(h))``
-> final RMSNorm -> last token -> 2048 x 2 head -> logits [P, 2].

Params: ``{"embed": {w, b}, "layers": [{ln1, mixer, ln2, mlp}, ...],
"final_norm", "head": {w, b}}``; a layer's ``mixer`` holds Mamba-2 or GQA
weights as its ``layer_types`` entry says.  ``ssd_impl`` picks the SSD
scan (``kernels/ops.ssd``: the Pallas kernel on the chip, its jnp form
elsewhere); every other op is plain XLA.
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from repro.configs.granite_4h import GraniteHybridSpec
from repro.models import attention as attn
from repro.models import ssm as ssm_mod
from repro.models.layers import (init_linear, init_rmsnorm, init_swiglu,
                                 linear, rms_norm, swiglu)


def init_granite(key, spec: GraniteHybridSpec, dtype=jnp.float32) -> Dict:
    cfg = spec.arch()
    keys = jax.random.split(key, len(spec.layer_types) + 2)
    layers = []
    for kind, k in zip(spec.layer_types, keys[2:]):
        km, kf = jax.random.split(k)
        mixer = (ssm_mod.init_mamba2(km, cfg, dtype) if kind == "mamba"
                 else attn.init_gqa(km, cfg, dtype))
        layers.append({"ln1": init_rmsnorm(spec.d_model, dtype),
                       "mixer": mixer,
                       "ln2": init_rmsnorm(spec.d_model, dtype),
                       "mlp": init_swiglu(kf, spec.d_model, spec.d_ff,
                                          dtype)})
    return {"embed": init_linear(keys[0], spec.patch_dim, spec.d_model,
                                 dtype, bias=True),
            "layers": layers,
            "final_norm": init_rmsnorm(spec.d_model, dtype),
            "head": init_linear(keys[1], spec.d_model, spec.n_classes,
                                dtype, bias=True)}


def patches(win: jax.Array, spec: GraniteHybridSpec) -> jax.Array:
    """[P, leads, L] -> [P, T, leads * patch]: token t holds samples
    [t*patch, (t+1)*patch) of every lead, lead-major."""
    P = win.shape[0]
    x = win[:, :, -spec.input_len:].reshape(P, spec.leads, spec.tokens,
                                             spec.patch)
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(P, spec.tokens,
                                                  spec.patch_dim)


def granite_apply(params: Dict, win: jax.Array, spec: GraniteHybridSpec,
                  *, ssd_impl: str = "xla") -> jax.Array:
    """win: [P, leads, L] -> logits [P, n_classes]."""
    cfg = spec.arch()
    h = linear(params["embed"], patches(win, spec)) \
        * spec.embedding_multiplier
    pos = jnp.arange(spec.tokens, dtype=jnp.int32)
    r = spec.residual_multiplier
    for kind, lp in zip(spec.layer_types, params["layers"]):
        u = rms_norm(h, lp["ln1"], spec.norm_eps)
        if kind == "mamba":
            y, _ = ssm_mod.mamba2_apply(lp["mixer"], u, cfg, impl="xla",
                                        ssd_impl=ssd_impl)
        else:
            y, _ = attn.gqa_apply(lp["mixer"], u, pos, cfg, rope=False,
                                  scale=spec.attention_multiplier)
        h = h + r * y
        h = h + r * swiglu(lp["mlp"], rms_norm(h, lp["ln2"], spec.norm_eps))
    last = rms_norm(h[:, -1], params["final_norm"], spec.norm_eps)
    return linear(params["head"], last)


def granite_apply_stacked(members: Sequence[Dict], win: jax.Array,
                          spec: GraniteHybridSpec, *,
                          ssd_impl: str = "xla") -> jax.Array:
    """A bucket of hybrid members (a tuple of param trees, held as they
    are: stacking 3 GB of weights would copy them) -> logits [M, P, 2]."""
    return jnp.stack([granite_apply(p, win, spec, ssd_impl=ssd_impl)
                      for p in members])


def granite_macs(spec: GraniteHybridSpec) -> float:
    """Multiply-accumulates of one member on one window: every matmul of
    the stack, the SSD's chunked quadratic form and state passing over
    the window's tokens (the last chunk partial), the attention scores
    and mixing, the depthwise convolutions."""
    d, T, f = spec.d_model, spec.tokens, spec.d_ff
    H = spec.ssm_heads
    di = H * spec.ssm_head_dim
    P, N, gn = spec.ssm_head_dim, spec.d_state, spec.n_groups * spec.d_state
    # the chunks of the scan, the last one partial
    chunks = [min(spec.chunk, T - t0) for t0 in range(0, T, spec.chunk)]
    q, kv = spec.n_heads * spec.head_dim, spec.n_kv_heads * spec.head_dim
    macs = T * spec.patch_dim * d + d * spec.n_classes
    for kind in spec.layer_types:
        macs += T * 3 * d * f                                  # swiglu
        if kind == "mamba":
            macs += T * d * (2 * di + 2 * gn + H) + T * di * d  # in / out
            macs += T * spec.d_conv * (di + 2 * gn)             # conv
            # per head and chunk: C.B^T, W.x, C.h^T, (x w)^T.B
            macs += H * sum(c * c * (N + P) + 2 * c * N * P
                            for c in chunks)
        else:
            macs += T * d * (q + 2 * kv) + T * q * d            # projections
            macs += 2 * spec.n_heads * T * T * spec.head_dim    # QK^T, PV
    return float(macs)
