"""Compile-only checks for the TPU v5e: the serving kernels and the
full-width decode step, compiled for a described (not attached) chip.

Nothing here runs on a device, so these tests say nothing about results
or speed; they catch what the TPU compiler refuses (misaligned slices,
more VMEM than a kernel may use, a kernel that falls off the serving
path) at no chip time.  The topology is described inside a fixture,
never at import: only one process may hold the TPU compiler library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.config import PUMConfig
from repro.kernels import registry
from repro.kernels.bitslice_mvm.kernel import (bitslice_mvm_pallas,
                                               bitslice_mvm_scaled_pallas)
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.models import lm
from repro.serve import kv_pool
from repro.serve.scheduler import make_slot_step

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # executables compiled for a described chip cannot be read back
    # without one: keep them out of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, donate=()):
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


# qwen2.5-3b's widest MVM (d_model 2048 -> d_ff 11008) at the decode
# tile's 32-row floor: int8 serving (1 plane, unscaled) and the fused
# tile at 1 and 4 planes (pum, 2 bits per slice)
@pytest.mark.parametrize("planes,fused", [(1, False), (1, True), (4, True)])
def test_mvm_tile_compiles_at_qwen_widths(one_chip, chip_smoke, planes,
                                          fused):
    m, k, n = 32, 2048, 11008
    x = _sds(one_chip, (m, k), jnp.int8)
    w = _sds(one_chip, (planes, k, n), jnp.int8)
    if fused:
        s = _sds(one_chip, (m, 1), jnp.float32)
        c = _compile(lambda x, w, s: bitslice_mvm_scaled_pallas(
            x, w, s, bits_per_slice=2, block_m=m, interpret=False), x, w, s)
        name = "bitslice_mvm_scaled"
    else:
        c = _compile(lambda x, w: bitslice_mvm_pallas(
            x, w, bits_per_slice=8, block_m=m, interpret=False), x, w)
        name = "bitslice_mvm"
    assert chip_smoke.kernel_counts(c.as_text()) == {name: 1}


# 8 slots x 2048 tokens of qwen2.5-3b KV (2 kv heads, 8 queries each,
# head_dim 128) in 16-token blocks: 1025 pool blocks, 128 table columns;
# S=1 is decode, S=16 a chunked-prefill step
@pytest.mark.parametrize("s", [1, 16])
def test_paged_attention_compiles_at_serving_pool_size(one_chip, chip_smoke,
                                                       s):
    b, kvh, g, hd, bs, w = 8, 2, 8, 128, 16, 128
    nb = b * w + 1
    bf = jnp.bfloat16
    args = (_sds(one_chip, (b, s, kvh, g, hd), bf),
            _sds(one_chip, (b, s, kvh, hd), bf),
            _sds(one_chip, (b, s, kvh, hd), bf),
            _sds(one_chip, (nb, bs, kvh, hd), bf),
            _sds(one_chip, (nb, bs, kvh, hd), bf),
            _sds(one_chip, (b, w), jnp.int32),
            _sds(one_chip, (b, w), jnp.int32),
            _sds(one_chip, (b,), jnp.int32))
    c = _compile(lambda *a: paged_attention_pallas(
        *a, kv_len=w * bs, interpret=False), *args, donate=(3, 4))
    assert chip_smoke.kernel_counts(c.as_text()) == {"paged_attention": 1}


def test_full_width_decode_step_compiles_with_both_kernels(one_chip,
                                                          chip_smoke):
    """qwen2.5-3b at 36 layers, int8, 8 slots over a 1024-token paged
    window: the scanned layer body holds the 7 MVMs and the attention
    kernel, and the step fits the chip's 16 GB."""
    cfg = configs.get("qwen2.5-3b").replace(
        pum=PUMConfig(mode="int8", inference=True))
    slots, max_len, bs = 8, 1024, 16
    width = kv_pool.table_width(max_len, bs)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = place(jax.eval_shape(
        lambda key: lm.prepack_for_serving(lm.init_params(cfg, key), cfg),
        jax.random.PRNGKey(0)))
    states = place(jax.eval_shape(lambda: lm.init_paged_state(
        cfg, slots, max_len, num_blocks=slots * width, block_size=bs)))
    i32 = jnp.int32
    lanes = [((slots, 1), i32), ((slots,), i32), ((slots, 2), jnp.uint32),
             ((slots,), jnp.bool_), ((slots,), jnp.float32), ((slots,), i32),
             ((slots,), i32), ((slots,), i32), ((slots, width), i32),
             ((slots,), i32)]
    with registry.use_backend("pallas"):
        c = _compile(make_slot_step(cfg, kv_len=max_len), params, states,
                     *(_sds(one_chip, *a) for a in lanes), donate=(1,))
    assert chip_smoke.kernel_counts(c.as_text()) == {"bitslice_mvm": 7,
                                            "paged_attention": 1}
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
