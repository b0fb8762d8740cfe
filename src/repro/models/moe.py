"""Mixture-of-Experts layer: top-k router + capacity-based grouped dispatch.

Dispatch is sort-free scatter into a per-sequence capacity buffer
[B, E, C, d] (groups = batch, DESIGN.md §5): routing and scatter stay local
to the data shard, expert weights are f-sharded over the model axis
(tensor-parallel-within-expert).  The expert-parallel all-to-all variant is
the shard_map path in ``repro/launch/expert_parallel.py`` (§Perf).

FLOP-faithful: each token is computed by exactly its top-k experts
(capacity_factor controls drop rate, as in GShard/Switch).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models.layers import init_swiglu, swiglu, truncated_normal_init


def init_moe(key, cfg: ArchConfig, dtype=jnp.float32):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    E, f = m.n_routed_experts, m.expert_d_ff

    def ew(k, shape):
        return truncated_normal_init(k, shape, 1.0, dtype)

    p = {
        "router": ew(ks[0], (d, E)),
        "w_gate": ew(ks[1], (E, d, f)),
        "w_up": ew(ks[2], (E, d, f)),
        "w_down": ew(ks[3], (E, f, d)),
    }
    if m.n_shared_experts:
        p["shared"] = init_swiglu(ks[4], d, m.shared_d_ff, dtype)
    return p


def _capacity(S: int, top_k: int, E: int, cf: float) -> int:
    c = int(S * top_k / E * cf) + 1
    return max(top_k, (c + 3) // 4 * 4)


def moe_apply_sharded(p, x: jax.Array, cfg: ArchConfig, mesh, *,
                      capacity_factor: float = 1.25,
                      impl: str = "xla") -> Tuple[jax.Array, jax.Array]:
    """§Perf lever A: shard_map MoE with an EXPLICIT collective schedule.

    GSPMD's auto-partitioning of the capacity-buffer formulation emits
    all-reduce/all-gather traffic proportional to the [B,E,C,d] dispatch
    buffers (the roofline baseline shows ~1e13 B/device/step on
    deepseek-v2-lite train_4k).  Here every step of routing, dispatch and
    expert compute is shard-LOCAL by construction (batch on data axes,
    expert f on the model axis), and the ONLY collectives are:
      * one token-space psum of the combined output [B_loc, S, d]
        (row-parallel down-proj, merged with the shared expert's), and
      * a scalar pmean for the aux loss.
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    bt = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    bt_spec = bt if len(bt) > 1 else bt[0]
    m = cfg.moe

    pspec = {
        "router": P(),
        "w_gate": P(None, None, "model"),
        "w_up": P(None, None, "model"),
        "w_down": P(None, "model", None),
    }
    if m.n_shared_experts:
        pspec["shared"] = {
            "gate": {"w": P(None, "model")},
            "up": {"w": P(None, "model")},
            "down": {"w": P("model", None)},
        }

    def local(p_l, x_l):
        y_routed, aux = _moe_local(p_l, x_l, cfg, capacity_factor, impl)
        if m.n_shared_experts:
            y_routed = y_routed + swiglu(p_l["shared"], x_l)
        y = jax.lax.psum(y_routed, "model")
        aux = jax.lax.pmean(aux, bt)
        return y, aux

    fn = shard_map(local, mesh=mesh,
                   in_specs=(pspec, P(bt_spec, None, None)),
                   out_specs=(P(bt_spec, None, None), P()))
    return fn(p, x)


def _moe_local(p, x, cfg, capacity_factor, impl):
    """Routed-expert compute on local tokens with f-sharded weights.
    Output is the PARTIAL (pre-psum) token-space result."""
    y, aux = _moe_dispatch_compute(p, x, cfg, capacity_factor, impl)
    return y, aux


def moe_apply(p, x: jax.Array, cfg: ArchConfig, *,
              capacity_factor: float = 1.25,
              impl: str = "xla") -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (y [B, S, d], aux_loss scalar).  GSPMD path."""
    y, aux = _moe_dispatch_compute(p, x, cfg, capacity_factor, impl)
    if cfg.moe.n_shared_experts:
        y = y + swiglu(p["shared"], x)
    return y, aux


def _moe_dispatch_compute(p, x: jax.Array, cfg: ArchConfig,
                          capacity_factor: float,
                          impl: str) -> Tuple[jax.Array, jax.Array]:
    """Routing + capacity dispatch + grouped expert SwiGLU (no shared
    expert, no collectives — callable from both the GSPMD path and the
    shard_map local body)."""
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.n_routed_experts, m.top_k
    C = _capacity(S, K, E, capacity_factor)

    logits = (x @ p["router"].astype(jnp.float32).astype(x.dtype)
              ).astype(jnp.float32)                      # [B,S,E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)               # [B,S,K]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # ---- load-balance auxiliary loss (Switch-style) --------------------
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=(1, 2))  # [B,E]
    mean_prob = jnp.mean(probs, axis=1)                            # [B,E]
    aux = E * jnp.mean(jnp.sum(frac_tokens * mean_prob, axis=-1))

    # ---- position-in-expert via stable sort over choices ---------------
    flat_e = top_e.reshape(B, S * K)                     # [B, SK]
    order = jnp.argsort(flat_e, axis=-1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    counts = jnp.sum(jax.nn.one_hot(flat_e, E, dtype=jnp.int32), axis=1)
    offsets = jnp.cumsum(counts, axis=-1) - counts       # [B, E] exclusive
    rank_sorted = (jnp.arange(S * K)[None, :]
                   - jnp.take_along_axis(offsets, sorted_e, axis=-1))
    inv = jnp.argsort(order, axis=-1)
    pos_in_e = jnp.take_along_axis(rank_sorted, inv, axis=-1)  # [B, SK]
    keep = pos_in_e < C
    slot = jnp.where(keep, pos_in_e, 0)

    # ---- scatter tokens into [E, C, d] per sequence ---------------------
    tok = jnp.repeat(jnp.arange(S), K)[None, :].repeat(B, 0)   # [B, SK]

    def scatter_one(xb, eb, sb, kb, tb):
        buf = jnp.zeros((E, C, d), xb.dtype)
        vals = xb[tb] * kb[:, None].astype(xb.dtype)
        return buf.at[eb, sb].add(vals)

    xbuf = jax.vmap(scatter_one)(x, flat_e, slot, keep, tok)   # [B,E,C,d]

    # ---- expert compute (grouped matmul kernel) -------------------------
    xe = xbuf.transpose(1, 0, 2, 3).reshape(E, B * C, d)
    ye = ops.moe_gmm(xe, p["w_gate"], p["w_up"], p["w_down"], impl=impl)
    ybuf = ye.reshape(E, B, C, d).transpose(1, 0, 2, 3)        # [B,E,C,d]

    # ---- gather back + combine ------------------------------------------
    def gather_one(yb, eb, sb, kb):
        return yb[eb, sb] * kb[:, None].astype(yb.dtype)       # [SK, d]

    y_choice = jax.vmap(gather_one)(ybuf, flat_e, slot, keep)
    y_choice = y_choice.reshape(B, S, K, d)
    y = jnp.sum(y_choice * top_p[..., None].astype(y_choice.dtype), axis=2)
    return y, aux.astype(jnp.float32)
