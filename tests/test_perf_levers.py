"""Numerics tests for the §Perf beyond-paper levers: they must be exact
(or float-tolerance) rewrites of the baseline semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.registry import get_config
from repro.kernels import ref
from repro.launch.mesh import make_host_mesh
from repro.models import moe as moe_mod
from repro.models.api import get_model
from repro.models.runtime import RuntimeOptions

KEY = jax.random.PRNGKey(0)


@given(st.integers(1, 3), st.sampled_from([16, 48, 64]),
       st.sampled_from([1, 2, 4]), st.sampled_from([16, 32]),
       st.booleans(), st.sampled_from([0, 24]))
@settings(max_examples=20, deadline=None)
def test_chunked_attention_matches_plain(B, S, Hkv, D, causal, window):
    Hq = Hkv * 2
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    pos = jnp.arange(S)
    a = ref.attention(q, k, v, pos, pos, causal=causal, window=window)
    b = ref.attention_chunked(q, k, v, pos, pos, causal=causal,
                              window=window, chunk=16)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ["phi3.5-moe-42b-a6.6b",
                                  "deepseek-v2-lite-16b"])
def test_moe_shard_map_matches_gspmd(arch):
    cfg = get_config(arch).reduced()
    p = moe_mod.init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model)) * 0.1
    y1, a1 = moe_mod.moe_apply(p, x, cfg)
    y2, a2 = moe_mod.moe_apply_sharded(p, x, cfg, make_host_mesh())
    np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(a1, a2, rtol=1e-5, atol=1e-6)


def test_moe_shard_map_grads_match():
    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()
    p = moe_mod.init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (1, 8, cfg.d_model)) * 0.1
    mesh = make_host_mesh()

    def loss_g(p):
        y, aux = moe_mod.moe_apply(p, x, cfg)
        return jnp.sum(y ** 2) + aux

    def loss_s(p):
        y, aux = moe_mod.moe_apply_sharded(p, x, cfg, mesh)
        return jnp.sum(y ** 2) + aux

    g1 = jax.grad(loss_g)(p)
    g2 = jax.grad(loss_s)(p)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_absorbed_mla_matches_materialized():
    cfg = get_config("deepseek-v2-lite-16b").reduced()
    model = get_model(cfg)
    toks = jax.random.randint(KEY, (2, 24), 0, cfg.vocab_size)
    outs = {}
    for absorbed in (False, True):
        rt = RuntimeOptions(absorbed_mla=absorbed)
        params = model.init(KEY, cfg, rt)
        outs[absorbed], _ = model.forward(params, toks, cfg, rt)
    np.testing.assert_allclose(outs[False], outs[True], rtol=2e-3,
                               atol=2e-3)


def test_chunked_attention_in_model_forward():
    cfg = get_config("qwen3-4b").reduced()
    model = get_model(cfg)
    toks = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    params = model.init(KEY, cfg, RuntimeOptions())
    base, _ = model.forward(params, toks, cfg, RuntimeOptions())
    chunked, _ = model.forward(params, toks, cfg,
                               RuntimeOptions(attn_chunk=16))
    np.testing.assert_allclose(base, chunked, rtol=1e-4, atol=1e-4)
