"""The Granite-4.0-H hybrid member (configs/granite_4h.py,
models/granite_hybrid.py) and the member protocol of
``serving/pipeline.EnsembleService``, at tiny sizes on the CPU:

* the hybrid stack equals the benchmark's plain reference (sequential
  per-token SSD recurrence, plain masked softmax), with the SSD scan in
  its jnp form and in the Pallas kernel (interpret mode); a bfloat16 run
  of the reference misses the same tolerance;
* NoPE GQA with an explicit score scale is a plain causal softmax;
* ``SlotEngine`` serving the zoo and a hybrid member scores each close
  as Eq. 5 does over the members' own programs, and the hybrid's column
  as the reference does;
* zoo-only services build the same buckets, keys and jit objects as the
  ResNeXt-only code did; the hybrid runs in its own ``hybrid_member``
  program; paths that run ResNeXt programs only refuse it clearly.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.configs.ecg_zoo import bucket_zoo
from repro.configs.granite_4h import LAYER_TYPES, GraniteHybridSpec
from repro.models import attention as attn
from repro.models.granite_hybrid import granite_apply, granite_macs
from repro.serving import pipeline
from repro.serving.aggregator import DeviceIngest, ModalitySpec
from repro.serving.pipeline import EnsembleService, HybridMember
from repro.serving.slots import SlotEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
sys.path.insert(0, BENCH)
import granite4h_reference as g4h  # noqa: E402

# a tiny stack: hidden 64, 4 Mamba-2 heads of 16,
# d_state 16, chunk 8, 20 tokens (the last chunk partial)
TINY = dict(layer_types=("mamba", "mamba", "attention", "mamba"),
            d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
            ssm_heads=4, ssm_head_dim=16, d_state=16, chunk=8)

# Float32 on the CPU is exact per product, so program and reference
# differ only by the order of sums: the chunked scan against the per-token
# recurrence, the fused patch reshape, XLA's reassociation.  Over four
# layers that stays below 1e-5 of the logits' scale; 1e-4 leaves ten
# times that, and a bfloat16 run (8-bit mantissa, 4e-3 per rounding)
# lands far outside it.
LOGIT_TOL = 1e-4


def _spec(input_len):
    return GraniteHybridSpec(input_len=input_len, **TINY)


def _ref_member(spec):
    return g4h.Hybrid(
        name=spec.name, layer_types=spec.layer_types, d_model=spec.d_model,
        n_heads=spec.n_heads, n_kv_heads=spec.n_kv_heads,
        head_dim=spec.head_dim, d_ff=spec.d_ff, ssm_heads=spec.ssm_heads,
        ssm_head_dim=spec.ssm_head_dim, d_state=spec.d_state,
        n_groups=spec.n_groups, d_conv=spec.d_conv, chunk=spec.chunk,
        attention_multiplier=spec.attention_multiplier,
        residual_multiplier=spec.residual_multiplier,
        embedding_multiplier=spec.embedding_multiplier,
        norm_eps=spec.norm_eps, leads=spec.leads, patch=spec.patch,
        input_len=spec.input_len, head_std=1.0)


def _ref_logits(params, win, m, dtype=jnp.float32):
    f = jax.jit(lambda p, x: g4h.forward(p, x, m, dtype, "highest"))
    return np.asarray(f(params, jnp.asarray(win)), np.float32)


@pytest.fixture(scope="module")
def tiny():
    spec = _spec(500)
    assert spec.tokens == 20 and spec.tokens % spec.chunk
    m = _ref_member(spec)
    params = g4h.init_hybrid(m, 2 ** 32 + 5)
    win = np.random.default_rng(3).standard_normal(
        (3, 3, spec.input_len)).astype(np.float32)
    return spec, m, params, win


@pytest.mark.parametrize("ssd_impl", ["xla", "pallas_interpret"])
def test_hybrid_stack_equals_plain_reference(tiny, ssd_impl):
    spec, m, params, win = tiny
    got = np.asarray(jax.jit(lambda p, x: granite_apply(
        p, x, spec, ssd_impl=ssd_impl))(params, jnp.asarray(win)))
    want = _ref_logits(params, win, m)
    scale = np.abs(want).max()
    assert scale > 0.1
    np.testing.assert_allclose(got, want, rtol=0, atol=LOGIT_TOL * scale)


def test_a_bfloat16_run_misses_the_tolerance(tiny):
    spec, m, params, win = tiny
    want = _ref_logits(params, win, m)
    low = _ref_logits(params, win, m, jnp.bfloat16)
    assert np.abs(low - want).max() > 10 * LOGIT_TOL * np.abs(want).max()


def test_nope_gqa_with_a_scale_is_a_plain_softmax():
    cfg = ArchConfig(name="t", family="dense", source="", num_layers=1,
                     d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                     vocab_size=0, head_dim=8)
    p = attn.init_gqa(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 32))
    pos = jnp.arange(7, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = attn.gqa_apply(p, x, pos, cfg, rope=False, scale=0.3)
        q = (x @ p["wq"]["w"]).reshape(2, 7, 4, 8)
        k = jnp.repeat((x @ p["wk"]["w"]).reshape(2, 7, 2, 8), 2, axis=2)
        v = jnp.repeat((x @ p["wv"]["w"]).reshape(2, 7, 2, 8), 2, axis=2)
        s = jnp.einsum("bthd,bshd->bhts", q, k) * 0.3
        s = jnp.where(np.tril(np.ones((7, 7), bool)), s, -jnp.inf)
        o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
        want = o.reshape(2, 7, 32) @ p["wo"]["w"]
        roped, _ = attn.gqa_apply(p, x, pos, cfg, scale=0.3)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # rotary stays the default: the same call without rope=False differs
    assert np.abs(np.asarray(roped) - np.asarray(want)).max() > 1e-3


def test_macs_count_the_published_member():
    # some 235 G multiply-accumulates a window at the published widths
    assert 2.3e11 < granite_macs(GraniteHybridSpec()) < 2.4e11
    assert granite_macs(GraniteHybridSpec()) < granite_macs(
        GraniteHybridSpec(layer_types=LAYER_TYPES[:20]))


# ------------------------------------------------------ served with the zoo
def _hybrid(input_len, seed=7):
    spec = _spec(input_len)
    m = _ref_member(spec)
    return HybridMember(spec, g4h.init_hybrid(m, seed)), m


def _close(di, rng, patients, t0):
    refs = {}
    for p in patients:
        ecg = rng.standard_normal((3, 250)).astype(np.float32)
        di.ingest(t0, p, "ecg", ecg)
        refs[p] = di.close_window(p, t0 + 1.0)
    return refs


def test_slot_engine_scores_zoo_and_hybrid_as_eq5(zoo_members, rng):
    hyb, m = _hybrid(250)
    members = list(zoo_members) + [hyb]
    svc = EnsembleService(members)
    assert [b.kind for b in svc._buckets][-1] == "hybrid"
    di = DeviceIngest([ModalitySpec("ecg", 250.0, 3)], n_patients=4,
                      window_seconds=1.0)
    eng = SlotEngine(svc, di)
    patients = [0, 1, 2, 3]
    refs = _close(di, rng, patients, 0.0)
    for p in patients:
        eng.update(refs[p])
    rep = eng.tick()
    assert rep.n_scored == 4
    assert rep.hybrid_tokens == 4 * hyb.tokens
    assert "slots.tick.dispatch.hybrid" in rep.phases
    # Eq. 5 over every member's column of the tick's state
    mat = np.zeros((len(members), rep.spad))
    for g in eng.groups:
        mat[g.rows] = np.asarray(g.state)
    got = np.asarray([eng.read(p) for p in patients])
    np.testing.assert_array_equal(
        got, [float(np.mean(list(mat[:, p]))) for p in patients])
    # the flush path, fed the same refs at the same rung, is bitwise
    assert np.array_equal(got, svc.predict_batch([refs[p]
                                                  for p in patients]))
    # the hybrid's column is the plain reference's P(stable)
    wins = np.stack([refs[p].host_window("ecg") for p in patients])
    logits = _ref_logits(hyb.params, wins, m)
    want = 1.0 / (1.0 + np.exp(logits[:, 0] - logits[:, 1]))
    np.testing.assert_allclose(mat[-1, :4], want, rtol=0, atol=1e-5)


def test_zoo_only_services_build_the_same_buckets(zoo_members):
    svc = EnsembleService(zoo_members)
    specs = [m.spec for m in zoo_members]
    groups = list(bucket_zoo(specs).values())
    assert [b.idx for b in svc._buckets] == groups
    assert list(pipeline.bucket_members(zoo_members)) == \
        list(bucket_zoo(specs))
    for b in svc._buckets:
        assert b.kind == "resnext" and b.tokens == 0
        assert b.fn is pipeline._make_bucket_fn(b.spec, b.leads, "xla")
        assert b.fn.__name__ == "fn"
        assert b.leads == [specs[i].lead for i in b.idx]
    assert svc.analytic_bucket_costs() == [
        sum(zoo_members[i].cost() for i in g) for g in groups]


def test_the_hybrid_runs_in_its_own_program(zoo_members):
    hyb, _ = _hybrid(250)
    svc = EnsembleService(list(zoo_members) + [hyb])
    b = svc._buckets[-1]
    assert b.idx == [len(zoo_members)] and b.tokens == hyb.tokens
    assert b.fn.__name__ == "hybrid_member"
    text = b.fn.lower(b.stacked, jnp.zeros((4, 3, 250))).as_text()
    assert "jit_hybrid_member" in text
    # a second member of the same shape joins the same bucket
    hyb2, _ = _hybrid(250, seed=8)
    svc2 = EnsembleService([hyb, hyb2])
    assert [b.idx for b in svc2._buckets] == [[0, 1]]
    assert svc2._buckets[0].fn is b.fn


def test_resnext_only_paths_refuse_a_hybrid_member(zoo_members, rng):
    hyb, _ = _hybrid(250)
    with pytest.raises(ValueError, match="fused=False"):
        EnsembleService([hyb], fused=False)
    svc = EnsembleService(list(zoo_members) + [hyb], marshal="legacy")
    window = {"ecg": rng.standard_normal((3, 250)).astype(np.float32)}
    with pytest.raises(ValueError, match="legacy"):
        svc.predict_batch([window, window])
    with pytest.raises(ValueError, match="legacy marshal"):
        pipeline._make_bucket_fn_legacy_cached(hyb.spec, "xla")
    svc = EnsembleService([hyb])
    with pytest.raises(ValueError, match="unfused"):
        svc._predict_one_unfused(window)
    with pytest.raises(ValueError, match="measured_costs"):
        svc.measured_costs()
