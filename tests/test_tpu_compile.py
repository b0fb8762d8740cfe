"""Compile the serving path's device programs for a TPU v5e chip that is
described, not attached: the chip's own compiler refuses what interpret
mode accepts (unaligned blocks, primitives with no TPU lowering, more
VMEM than a kernel may use).  Nothing runs, so these say nothing about
results or times.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler's library, and the
test workers all import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.ecg_zoo import bucket_zoo, zoo_specs
from repro.kernels.conv1d_stripe import conv1d_stripe_stacked
from repro.kernels.ssd_scan import ssd
from repro.kernels.window_gather import window_gather
from repro.launch.ensemble_parallel import stack_members
from repro.models.ecg_resnext import init_ecg
from repro.serving.pipeline import _make_bucket_fn

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used


def test_window_gather_compiles(one_chip):
    """The Pallas ring gather at the reduced zoo's window (L=750) over
    a 2048-sample ring, 64 beds."""
    i32 = _shape(one_chip, (64,), jnp.int32)
    compiled = jax.jit(
        lambda buf, p, e, v: window_gather(buf, p, e, v, 750)).lower(
        _shape(one_chip, (64, 3, 2048)), i32, i32, i32).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


# width-128 bucket of the full zoo: 3 members, 64 beds, 30 s windows;
# stripes have cardinality 8 over 64 inner channels (8-lane groups)
_W, _INNER, _CARD, _L = 128, 64, 8, 7500


@pytest.mark.parametrize("x_shape,w_shape,stride,groups", [
    ((3, 64, _L, 1), (3, 7, 1, _W), 2, 1),                       # stem
    ((3, 64, _L // 2, _W), (3, 1, _W, _INNER), 1, 1),            # reduce
    ((3, 64, _L // 2, _INNER), (3, 7, _INNER // _CARD, _INNER),
     2, _CARD),                                                  # stripe /2
    ((3, 64, _L // 4, _INNER), (3, 7, _INNER // _CARD, _INNER),
     1, _CARD),                                                  # stripe /1
    ((3, 64, _L // 4, _INNER), (3, 1, _INNER, _W), 1, 1),        # expand
], ids=["stem", "reduce", "stripe_s2", "stripe_s1", "expand"])
def test_conv1d_stripe_stacked_compiles(one_chip, x_shape, w_shape, stride,
                                        groups):
    compiled = jax.jit(lambda x, w: conv1d_stripe_stacked(
        x, w, None, stride=stride, groups=groups)).lower(
        _shape(one_chip, x_shape), _shape(one_chip, w_shape)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_xla_bucket_program_compiles(one_chip):
    """One stacked bucket dispatch of the full zoo (width 128, 2 blocks,
    three leads) as the served path jits it, at a 64-bed tick."""
    specs = zoo_specs(reduced=False)
    idx = next(i for k, i in bucket_zoo(specs).items()
               if k[:2] == (128, 2))
    spec = specs[idx[0]]
    stacked = jax.eval_shape(lambda: stack_members(
        [init_ecg(jax.random.PRNGKey(0), specs[i]) for i in idx]))
    stacked = jax.tree.map(
        lambda s: _shape(one_chip, s.shape, s.dtype), stacked)
    fn = _make_bucket_fn(spec, [specs[i].lead for i in idx], "xla")
    compiled = fn.lower(
        stacked, _shape(one_chip, (64, 3, spec.input_len))).compile()
    _fits(compiled)


def test_ssd_scan_compiles_at_the_hybrid_members_widths(one_chip):
    """The Pallas SSD scan of the Granite-4.0-H member at its published
    widths (64 heads of 64, d_state 128, one group, chunk 256) over a
    64-bed tick of 300 tokens: the last chunk is partial."""
    b, S, H, P, N = 64, 300, 64, 64, 128
    compiled = jax.jit(lambda x, dt, A, B_, C, D: ssd(
        x, dt, A, B_, C, D, 256)).lower(
        _shape(one_chip, (b, S, H, P)), _shape(one_chip, (b, S, H)),
        _shape(one_chip, (H,)), _shape(one_chip, (b, S, 1, N)),
        _shape(one_chip, (b, S, 1, N)), _shape(one_chip, (H,))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)
