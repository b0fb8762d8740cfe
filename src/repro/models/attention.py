"""Attention variants: GQA/MQA (with KV-head duplication for sharding),
sliding-window masking, ring-buffer decode caches, MLA (DeepSeek-V2),
and encoder/decoder cross-attention.

Cache convention (per layer; the transformer scans these stacked over L):
  gqa:  {"k": [B, M, kvH, hd], "v": [B, M, kvH, hd]}
  mla:  {"ckv": [B, M, lora], "krope": [B, M, rope_dim]}
plus a model-level {"pos": [M] int32 (-1 = empty), "idx": int32 scalar}.
M = min(seq_len, window or seq_len); decode writes slot idx % M.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models.layers import (apply_rope, init_linear, init_rmsnorm,
                                 linear, rms_norm, truncated_normal_init)


# ===================================================================== GQA
def init_gqa(key, cfg: ArchConfig, dtype=jnp.float32, kv_mult: int = 1):
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads * kv_mult
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_linear(ks[0], d, nq * hd, dtype, cfg.attn_bias),
        "wk": init_linear(ks[1], d, nkv * hd, dtype, cfg.attn_bias),
        "wv": init_linear(ks[2], d, nkv * hd, dtype, cfg.attn_bias),
        "wo": init_linear(ks[3], nq * hd, d, dtype, cfg.attn_bias),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dtype)
        p["k_norm"] = init_rmsnorm(hd, dtype)
    return p


def _project_qkv(p, x, cfg: ArchConfig, kv_mult: int):
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = linear(p["wq"], x).reshape(B, S, cfg.n_heads, hd)
    k = linear(p["wk"], x).reshape(B, S, cfg.n_kv_heads * kv_mult, hd)
    v = linear(p["wv"], x).reshape(B, S, cfg.n_kv_heads * kv_mult, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def gqa_apply(p, x: jax.Array, positions: jax.Array, cfg: ArchConfig, *,
              cache: Optional[dict] = None,
              cache_pos: Optional[jax.Array] = None,
              cache_idx: Optional[jax.Array] = None,
              window: int = 0, causal: bool = True,
              kv_mult: int = 1, impl: str = "xla",
              chunk: int = 0, unroll: bool = False,
              rope: bool = True, scale: Optional[float] = None
              ) -> Tuple[jax.Array, Optional[dict]]:
    """positions: [S] int32 absolute positions of the inputs.

    ``rope=False`` gives NoPE attention (positions then only mask);
    ``scale`` replaces the 1/sqrt(head_dim) score scale (Granite-4.0-H
    scales by its ``attention_multiplier``).

    * cache=None: full-sequence attention (train/prefill); returns
      (out, {"k","v"}) with M=S so the caller may build a cache.
    * cache given: decode — S==1; writes slot cache_idx % M, attends to the
      whole buffer using cache_pos validity.
    """
    q, k, v = _project_qkv(p, x, cfg, kv_mult)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None:
        out = ops.attention(q, k, v, positions, positions,
                            causal=causal, window=window, scale=scale,
                            impl=impl, chunk=chunk, unroll=unroll)
        new_kv = {"k": k, "v": v}
    else:
        M = cache["k"].shape[1]
        slot = cache_idx % M
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
        kpos = jax.lax.dynamic_update_slice_in_dim(
            cache_pos, positions.astype(cache_pos.dtype), slot, axis=0)
        out = ops.attention(q, ck, cv, positions, kpos,
                            causal=causal, window=window, scale=scale,
                            impl=impl, chunk=chunk, unroll=unroll)
        new_kv = {"k": ck, "v": cv}
    B, S = x.shape[:2]
    out = linear(p["wo"], out.reshape(B, S, cfg.n_heads * cfg.head_dim))
    return out, new_kv


# ===================================================================== MLA
def init_mla(key, cfg: ArchConfig, dtype=jnp.float32):
    m = cfg.mla
    d, nq = cfg.d_model, cfg.n_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq": init_linear(ks[0], d, nq * qk_dim, dtype),
        "w_dkv": init_linear(ks[1], d, m.kv_lora_rank + m.qk_rope_head_dim,
                             dtype),
        "ckv_norm": init_rmsnorm(m.kv_lora_rank, dtype),
        "w_uk": truncated_normal_init(
            ks[2], (m.kv_lora_rank, nq, m.qk_nope_head_dim), 1.0, dtype),
        "w_uv": truncated_normal_init(
            ks[3], (m.kv_lora_rank, nq, m.v_head_dim), 1.0, dtype),
        "wo": init_linear(ks[4], nq * m.v_head_dim, d, dtype),
    }


def _mla_compress(p, x, cfg: ArchConfig, positions):
    """x -> (q_nope, q_rope, ckv, k_rope) for this segment."""
    m = cfg.mla
    B, S, _ = x.shape
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    q = linear(p["wq"], x).reshape(B, S, cfg.n_heads, qk_dim)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    dkv = linear(p["w_dkv"], x)
    ckv = rms_norm(dkv[..., :m.kv_lora_rank], p["ckv_norm"], cfg.norm_eps)
    k_rope = dkv[..., m.kv_lora_rank:][:, :, None, :]       # [B,S,1,rope]
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0, :]
    return q_nope, q_rope, ckv, k_rope


def mla_apply(p, x: jax.Array, positions: jax.Array, cfg: ArchConfig, *,
              cache: Optional[dict] = None,
              cache_pos: Optional[jax.Array] = None,
              cache_idx: Optional[jax.Array] = None,
              window: int = 0, causal: bool = True,
              absorbed: bool = False, impl: str = "xla",
              chunk: int = 0, unroll: bool = False
              ) -> Tuple[jax.Array, Optional[dict]]:
    """Multi-head Latent Attention.  Cache holds the COMPRESSED kv
    (kv_lora_rank + rope_dim per token, shared across heads).

    absorbed=False materializes per-head K/V from the latent (simple);
    absorbed=True runs attention in the latent space (the memory-optimal
    decode path — see EXPERIMENTS.md §Perf).
    """
    m = cfg.mla
    B, S, _ = x.shape
    q_nope, q_rope, ckv, k_rope = _mla_compress(p, x, cfg, positions)

    if cache is None:
        ckv_all, krope_all, kpos = ckv, k_rope, positions
        new_cache = {"ckv": ckv, "krope": k_rope}
    else:
        M = cache["ckv"].shape[1]
        slot = cache_idx % M
        ckv_all = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv, slot, axis=1)
        krope_all = jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], k_rope, slot, axis=1)
        kpos = jax.lax.dynamic_update_slice_in_dim(
            cache_pos, positions.astype(cache_pos.dtype), slot, axis=0)
        new_cache = {"ckv": ckv_all, "krope": krope_all}

    scale = 1.0 / (m.qk_nope_head_dim + m.qk_rope_head_dim) ** 0.5
    if absorbed:
        # q~ = q_nope absorbed through w_uk: [B,S,H,lora]
        q_lat = jnp.einsum("bshn,lhn->bshl", q_nope, p["w_uk"])
        logits = (jnp.einsum("bshl,btl->bhst", q_lat, ckv_all)
                  + jnp.einsum("bshr,btr->bhst", q_rope, krope_all)) * scale
        logits = logits + _mask_bias(positions, kpos, causal, window)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1
                               ).astype(ckv_all.dtype)
        v_lat = jnp.einsum("bhst,btl->bshl", probs, ckv_all)
        out = jnp.einsum("bshl,lhv->bshv", v_lat, p["w_uv"]
                         ).astype(x.dtype)
    else:
        k_nope = jnp.einsum("btl,lhn->bthn", ckv_all, p["w_uk"])
        v = jnp.einsum("btl,lhv->bthv", ckv_all, p["w_uv"])
        k_rope_b = jnp.broadcast_to(
            krope_all[:, :, None, :],
            (B, ckv_all.shape[1], cfg.n_heads, m.qk_rope_head_dim))
        k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = ops.attention(q, k, v, positions, kpos,
                            causal=causal, window=window, impl=impl,
                            chunk=chunk, unroll=unroll)
    out = linear(p["wo"], out.reshape(B, S, cfg.n_heads * m.v_head_dim))
    return out, new_cache


def _mask_bias(qpos, kpos, causal: bool, window: int):
    """Additive [S,T] mask bias from 1-D position vectors."""
    qp = qpos[:, None].astype(jnp.int32)
    kp = kpos[None, :].astype(jnp.int32)
    ok = kp >= 0
    if causal:
        ok &= kp <= qp
    if window:
        ok &= (qp - kp) < window
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


# ============================================================ cross-attn
def init_cross(key, cfg: ArchConfig, dtype=jnp.float32, kv_mult: int = 1):
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads * kv_mult
    ks = jax.random.split(key, 4)
    return {
        "wq": init_linear(ks[0], d, nq * hd, dtype),
        "wk": init_linear(ks[1], d, nkv * hd, dtype),
        "wv": init_linear(ks[2], d, nkv * hd, dtype),
        "wo": init_linear(ks[3], nq * hd, d, dtype),
    }


def cross_apply(p, x: jax.Array, enc: jax.Array, cfg: ArchConfig, *,
                kv_mult: int = 1, impl: str = "xla") -> jax.Array:
    """Decoder cross-attention over encoder output (no mask, no rope)."""
    B, S, _ = x.shape
    T = enc.shape[1]
    hd = cfg.head_dim
    q = linear(p["wq"], x).reshape(B, S, cfg.n_heads, hd)
    k = linear(p["wk"], enc).reshape(B, T, cfg.n_kv_heads * kv_mult, hd)
    v = linear(p["wv"], enc).reshape(B, T, cfg.n_kv_heads * kv_mult, hd)
    qpos = jnp.zeros((S,), jnp.int32)
    kpos = jnp.zeros((T,), jnp.int32)
    out = ops.attention(q, k, v, qpos, kpos, causal=False, window=0,
                        impl=impl)
    return linear(p["wo"], out.reshape(B, S, cfg.n_heads * hd))
