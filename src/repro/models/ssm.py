"""Mamba-2 block (SSD — state-space duality) and the pure-SSM language model.

Block layout follows arXiv:2405.21060 with one sharding-driven deviation
(DESIGN.md §5): the fused in_proj is stored as SEPARATE projections
(z, x, B, C, dt) so each output dim shards cleanly over the model axis —
a fused projection's post-split slices would cross shard boundaries and
force resharding collectives.  Numerics are identical.

Decode keeps {conv_x, conv_B, conv_C, ssm} states; no KV cache, O(1)
memory in sequence length (why SSM/hybrid archs run long_500k natively).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops, ref
from repro.models.layers import (init_rmsnorm, rms_norm,
                                 truncated_normal_init)


def init_mamba2(key, cfg: ArchConfig, dtype=jnp.float32):
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    H = s.n_heads(d)
    G, N, K = s.n_groups, s.d_state, s.conv_width
    gn = G * N
    ks = jax.random.split(key, 9)
    return {
        "z_proj": truncated_normal_init(ks[0], (d, di), 1.0, dtype),
        "x_proj": truncated_normal_init(ks[1], (d, di), 1.0, dtype),
        "B_proj": truncated_normal_init(ks[2], (d, gn), 1.0, dtype),
        "C_proj": truncated_normal_init(ks[3], (d, gn), 1.0, dtype),
        "dt_proj": truncated_normal_init(ks[4], (d, H), 1.0, dtype),
        "conv_x": truncated_normal_init(ks[5], (K, 1, di), 1.0, dtype),
        "conv_B": truncated_normal_init(ks[6], (K, 1, gn), 1.0, dtype),
        "conv_C": truncated_normal_init(ks[7], (K, 1, gn), 1.0, dtype),
        "conv_bx": jnp.zeros((di,), dtype),
        "conv_bB": jnp.zeros((gn,), dtype),
        "conv_bC": jnp.zeros((gn,), dtype),
        "A_log": jnp.zeros((H,), jnp.float32),           # A = -exp(A_log)=-1
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": init_rmsnorm(di, dtype),
        "out_proj": truncated_normal_init(ks[8], (di, d), 1.0, dtype),
    }


def ssm_cache_init(cfg: ArchConfig, batch: int, dtype=jnp.float32):
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    gn = s.n_groups * s.d_state
    K = s.conv_width
    return {
        "conv_x": jnp.zeros((batch, K - 1, di), dtype),
        "conv_B": jnp.zeros((batch, K - 1, gn), dtype),
        "conv_C": jnp.zeros((batch, K - 1, gn), dtype),
        "ssm": jnp.zeros((batch, s.n_heads(d), s.head_dim, s.d_state),
                         jnp.float32),
    }


def _conv_step(hist: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """hist: [B, K, ch] -> causal conv output at the last step [B, ch]."""
    return jnp.einsum("bkc,kc->bc", hist, w[:, 0, :]) + b


def mamba2_apply(p, u: jax.Array, cfg: ArchConfig, *,
                 cache: Optional[dict] = None,
                 return_cache: bool = False,
                 impl: str = "xla", ssd_impl: Optional[str] = None
                 ) -> Tuple[jax.Array, Optional[dict]]:
    """u: [B, S, d].  cache given (decode) requires S == 1.
    return_cache=True on the full-sequence path emits the post-prefill
    conv/ssm state.  ``ssd_impl`` runs the SSD scan on another path than
    the convolutions (default: ``impl``)."""
    s = cfg.ssm
    B, S, d = u.shape
    di = s.d_inner(d)
    H, P, G, N, K = s.n_heads(d), s.head_dim, s.n_groups, s.d_state, \
        s.conv_width

    z = u @ p["z_proj"]
    x_raw = u @ p["x_proj"]
    B_raw = u @ p["B_proj"]
    C_raw = u @ p["C_proj"]
    dt = jax.nn.softplus((u @ p["dt_proj"]).astype(jnp.float32)
                         + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    if cache is None:
        xc = jax.nn.silu(ops.conv1d(x_raw, p["conv_x"], p["conv_bx"],
                                    groups=di, padding="CAUSAL", impl=impl))
        Bc = jax.nn.silu(ops.conv1d(B_raw, p["conv_B"], p["conv_bB"],
                                    groups=G * N, padding="CAUSAL",
                                    impl=impl))
        Cc = jax.nn.silu(ops.conv1d(C_raw, p["conv_C"], p["conv_bC"],
                                    groups=G * N, padding="CAUSAL",
                                    impl=impl))
        x = xc.reshape(B, S, H, P)
        Bmat = Bc.reshape(B, S, G, N)
        Cmat = Cc.reshape(B, S, G, N)
        y, hT = ops.ssd(x, dt, A, Bmat, Cmat, p["D"], s.chunk,
                        impl=ssd_impl or impl)
        new_cache = None
        if return_cache:
            new_cache = {"conv_x": x_raw[:, S - (K - 1):, :],
                         "conv_B": B_raw[:, S - (K - 1):, :],
                         "conv_C": C_raw[:, S - (K - 1):, :],
                         "ssm": hT.astype(jnp.float32)}
        y = y.reshape(B, S, di)
    else:
        hx = jnp.concatenate([cache["conv_x"], x_raw], axis=1)
        hB = jnp.concatenate([cache["conv_B"], B_raw], axis=1)
        hC = jnp.concatenate([cache["conv_C"], C_raw], axis=1)
        x = jax.nn.silu(_conv_step(hx, p["conv_x"], p["conv_bx"]))
        Bm = jax.nn.silu(_conv_step(hB, p["conv_B"], p["conv_bB"]))
        Cm = jax.nn.silu(_conv_step(hC, p["conv_C"], p["conv_bC"]))
        y, h_new = ref.ssd_decode_step(
            cache["ssm"], x.astype(jnp.float32).reshape(B, H, P), dt[:, 0],
            A, Bm.astype(jnp.float32).reshape(B, G, N),
            Cm.astype(jnp.float32).reshape(B, G, N), p["D"])
        new_cache = {"conv_x": hx[:, 1:], "conv_B": hB[:, 1:],
                     "conv_C": hC[:, 1:], "ssm": h_new}
        y = y.astype(u.dtype).reshape(B, 1, di)

    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    return y @ p["out_proj"], new_cache
