"""Compile-only checks for the TPU v5e: the serving kernels and the
full-width decode step, compiled for a described (not attached) chip.

Nothing here runs on a device, so these tests say nothing about results
or speed; they catch what the TPU compiler refuses (misaligned slices,
more VMEM than a kernel may use, a kernel that falls off the serving
path) at no chip time.  The topology is described inside a fixture,
never at import: only one process may hold the TPU compiler library.
"""
import math
import re

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
from repro.serve.scheduler import make_chunk_prefill, make_slot_step

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
# head_dim 128) in 16-token blocks: 1025 pool blocks, 128 table columns,
# in the stacked lane-dense [36, NB, bs, KV * hd] pool the kernel
# addresses by layer; S=1 is decode, S=16 a chunked-prefill step
@pytest.mark.parametrize("s", [1, 16])
def test_paged_attention_compiles_at_serving_pool_size(one_chip, chip_smoke,
                                                       s):
    b, kvh, g, hd, bs, w, layers = 8, 2, 8, 128, 16, 128, 36
    nb = b * w + 1
    bf = jnp.bfloat16
    args = (_sds(one_chip, (b, s, kvh, g, hd), bf),
            _sds(one_chip, (b, s, kvh, hd), bf),
            _sds(one_chip, (b, s, kvh, hd), bf),
            _sds(one_chip, (layers, nb, bs, kvh * hd), bf),
            _sds(one_chip, (layers, nb, bs, kvh * hd), bf),
            _sds(one_chip, (b, w), jnp.int32),
            _sds(one_chip, (b, w), jnp.int32),
            _sds(one_chip, (b,), jnp.int32),
            _sds(one_chip, (), jnp.int32))
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


# ops that hand a buffer on without writing one of their own
_PASS_THROUGH = {"parameter", "get-tuple-element", "tuple", "while",
                 "bitcast"}


def _pool_sized_ops(hlo_text: str, pool_shape) -> list[str]:
    """Instructions other than the attention kernel (and the pass-through
    ops above) whose result holds a bf16 array of the stacked pool's or
    of one layer's element count: a slice, relayout or copy of the
    pool."""
    full = math.prod(pool_shape)
    sizes = {full, full // pool_shape[0]}
    found = []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?(\S+)\s*=\s*(.+?)\s+([\w\-]+)\(",
                     line)
        if not m or m.group(3) in _PASS_THROUGH:
            continue
        if m.group(3) == "custom-call" and "/paged_attention/" in line:
            continue
        dims = re.findall(r"bf16\[([\d,]*)\]", m.group(2))
        if any(math.prod(int(d) for d in ds.split(",") if d) in sizes
               for ds in dims):
            found.append(f"{m.group(3)} {m.group(1)}")
    return found


# the two paged-pool cells' shapes: minicpm-2b-int8.decode-long (MHA,
# 36 heads of 64; 16 slots, a 1024 window, 768 blocks) and
# qwen2.5-3b-int8.chat (2 KV heads of 128; 32 slots, 2048, 4096 blocks)
_CELLS = {"decode-long": ("minicpm-2b", 16, 1024, 768),
          "chat": ("qwen2.5-3b", 32, 2048, 4096)}


@pytest.mark.parametrize("step", ["decode", "chunk"])
@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_serving_steps_keep_the_pool_in_place(one_chip, chip_smoke, cell,
                                              step):
    """The decode step and the 16-token chunk step at a benchmark cell's
    shapes: the stacked pool passes through the layer loop in one
    buffer, so no instruction but the attention kernel produces an
    array of the pool's or one layer's size, the step holds its usual
    kernels, and its scratch is a small fraction of the pool."""
    arch, slots, max_len, blocks = _CELLS[cell]
    cfg = configs.get(arch).replace(
        pum=PUMConfig(mode="int8", inference=True))
    bs = 16
    width = kv_pool.table_width(max_len, bs)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = place(jax.eval_shape(
        lambda key: lm.prepack_for_serving(lm.init_params(cfg, key), cfg),
        jax.random.PRNGKey(0)))
    states = place(jax.eval_shape(lambda: lm.init_paged_state(
        cfg, slots, max_len, num_blocks=blocks, block_size=bs)))
    pool = states[0]["k_pool"]
    assert pool.shape == (cfg.num_layers, blocks + 1, bs,
                          cfg.num_kv_heads * cfg.resolved_head_dim)
    i32 = jnp.int32
    if step == "decode":
        fn = make_slot_step(cfg, kv_len=max_len)
        lanes = [((slots, 1), i32), ((slots,), i32),
                 ((slots, 2), jnp.uint32), ((slots,), jnp.bool_),
                 ((slots,), jnp.float32), ((slots,), i32), ((slots,), i32),
                 ((slots,), i32), ((slots, width), i32), ((slots,), i32)]
    else:
        fn = make_chunk_prefill(cfg, max_len)
        lanes = [((1, bs), i32), ((), i32), ((1, width), i32), ((), i32),
                 ((1,), i32)]
    with registry.use_backend("pallas"):
        c = _compile(fn, params, states,
                     *(_sds(one_chip, *a) for a in lanes), donate=(1,))
    hlo = c.as_text()
    assert chip_smoke.kernel_counts(hlo) == {"bitslice_mvm": 7,
                                            "paged_attention": 1}
    assert _pool_sized_ops(hlo, pool.shape) == []
    pool_bytes = 2 * pool.size * pool.dtype.itemsize        # K and V
    assert c.memory_analysis().temp_size_in_bytes < pool_bytes / 4
