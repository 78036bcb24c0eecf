"""Tensor-parallel serving: oracle-equivalence suite.

The load-bearing guarantee of the TP serve stack: for tp in {1, 2, 4},
every completion served by a mesh-sharded engine/scheduler is
**bit-identical** to the solo single-device oracle, across state
families (dense / xlstm / hybrid attention+Mamba), execution modes
(bf16 / int8 / pum), and KV layouts (contiguous / paged+chunked).

Two mechanisms make this hold (and these tests pin them):

  * integer contractions may split K — per-shard partial MVMs are exact
    integers, so the closing psum (``tp_replicate`` on the accumulator)
    reproduces the single-tile sum bit-for-bit, and activation quant
    scales are per-input-row (max over K is order-independent);
  * float (bf16) weights only ever shard N, and serving mode pins bf16
    rounding points with ``optimization_barrier`` so XLA's f32 cluster
    boundaries cannot differ between the solo and the partitioned graph.

This module needs multiple devices; run it under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
``multidevice`` CI job / ``make test-tp``).  On a bare 1-device run it
skips wholesale, keeping tier-1 cost unchanged.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import PUMConfig, small_test_config
from repro.dist import sharding as shd
from repro.launch.mesh import make_tp_mesh
from repro.models import lm
from repro.serve import (ContinuousBatchingScheduler, Request, ServeEngine,
                         oracle_completion)

if len(jax.devices()) < 4:
    pytest.skip(
        "tensor-parallel suite needs >= 4 devices; run under "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
        "(make test-tp)", allow_module_level=True)

# num_kv_heads=4 so the KV-head axis divides every tp in the sweep
FAMILIES = {
    "dense": dict(num_kv_heads=4),
    "xlstm": dict(num_kv_heads=4, xlstm_slstm_every=2),
    "hybrid": dict(num_kv_heads=4, attn_period=2),
}
MODES = ["bf16", "int8", "pum"]
TPS = [1, 2, 4]

MAX_LEN = 24
# two prompt lengths only (each novel length costs a prefill compile),
# staggered arrivals, greedy + sampled, more requests than slots so
# slots and blocks get recycled mid-trace
TRACE = [
    Request([1, 2, 3], max_tokens=5, seed=1),
    Request([4] * 7, max_tokens=4, temperature=0.8, seed=2, arrival=1),
    Request([5, 6, 7], max_tokens=6, seed=3, arrival=2),
]

_ORACLE_CACHE = {}


def _oracle(family, mode):
    """Solo single-device oracle completions (cached per family x mode:
    the same oracle serves every tp / layout cell)."""
    key = (family, mode)
    if key not in _ORACLE_CACHE:
        cfg = small_test_config(**FAMILIES[family], pum=PUMConfig(mode=mode))
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServeEngine(cfg, params, max_len=MAX_LEN)
        _ORACLE_CACHE[key] = (
            cfg, params,
            {i: oracle_completion(eng, r) for i, r in enumerate(TRACE)})
    return _ORACLE_CACHE[key]


@pytest.mark.parametrize("tp", TPS)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_tp_scheduler_bit_identical_contiguous(family, mode, tp):
    cfg, params, want = _oracle(family, mode)
    sched = ContinuousBatchingScheduler(
        cfg, params, num_slots=2, max_len=MAX_LEN, mesh=make_tp_mesh(tp))
    out = sched.run(TRACE)
    for i in range(len(TRACE)):
        assert out[i].tokens == want[i], (
            f"{family}/{mode}/tp{tp}/contiguous request {i}: "
            f"served {out[i].tokens}, solo oracle {want[i]}")


@pytest.mark.parametrize("tp", TPS)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_tp_scheduler_bit_identical_paged(family, mode, tp):
    """Paged KV pool sharded on the KV-head axis + chunked prefill
    streaming through the sharded pool — same bit-equality bar."""
    cfg, params, want = _oracle(family, mode)
    sched = ContinuousBatchingScheduler(
        cfg, params, num_slots=2, max_len=MAX_LEN, kv_block_size=4,
        chunked_prefill=True, mesh=make_tp_mesh(tp))
    out = sched.run(TRACE)
    for i in range(len(TRACE)):
        assert out[i].tokens == want[i], (
            f"{family}/{mode}/tp{tp}/paged request {i}: "
            f"served {out[i].tokens}, solo oracle {want[i]}")


def test_tp_engine_fused_scan_matches_solo():
    """The static-batch engine (jitted prefill + fused-scan decode)
    under tp=2: token-identical to the solo engine, greedy and
    sampled."""
    cfg, params, _ = _oracle("dense", "int8")
    solo = ServeEngine(cfg, params, max_len=MAX_LEN)
    tpe = ServeEngine(cfg, params, max_len=MAX_LEN, mesh=make_tp_mesh(2))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                                cfg.vocab_size)
    for temp in (0.0, 0.7):
        a = np.asarray(solo.generate(prompt, 8, temperature=temp, seed=5))
        b = np.asarray(tpe.generate(prompt, 8, temperature=temp, seed=5))
        np.testing.assert_array_equal(a, b)


def test_tp_no_prepack_engine_matches_solo():
    """--no-prepack serving (per-call weight quantisation) under tp=2:
    the raw float weights shard N-only and serving inference mode pins
    bf16 rounding, so the path is bit-identical too — the prepacked
    grid must not be the only covered configuration."""
    cfg = small_test_config(num_kv_heads=4, pum=PUMConfig(mode="int8"))
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    solo = ServeEngine(cfg, params, max_len=MAX_LEN, prepack=False)
    tpe = ServeEngine(cfg, params, max_len=MAX_LEN, prepack=False,
                      mesh=make_tp_mesh(2))
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 5), 0,
                                cfg.vocab_size)
    a = np.asarray(solo.generate(prompt, 8, temperature=0.6, seed=9))
    b = np.asarray(tpe.generate(prompt, 8, temperature=0.6, seed=9))
    np.testing.assert_array_equal(a, b)


def test_tp_params_actually_sharded():
    """tp=2 must genuinely distribute the weights: a packed linear's wq
    lives on 2 devices with half the columns (or rows) per shard, and
    the paged KV pool splits its head axis."""
    cfg, params, _ = _oracle("dense", "int8")
    mesh = make_tp_mesh(2)
    sched = ContinuousBatchingScheduler(
        cfg, params, num_slots=2, max_len=MAX_LEN, kv_block_size=4,
        chunked_prefill=True, mesh=mesh)
    wq = sched.params["blocks"][0]["mlp"]["wg"]["w"].wq
    assert len(wq.sharding.device_set) == 2
    shard_shape = wq.sharding.shard_shape(wq.shape)
    assert shard_shape[-1] == wq.shape[-1] // 2          # column-parallel
    wd = sched.params["blocks"][0]["mlp"]["wd"]["w"].wq
    assert wd.sharding.shard_shape(wd.shape)[-2] == wd.shape[-2] // 2
    pool = sched.states[0]["k_pool"]
    assert pool.sharding.shard_shape(pool.shape)[-1] == \
        pool.shape[-1] // 2                              # folded KV heads


def test_tp_row_sharded_pum_linear_psum_is_exact():
    """The micro-invariant under the whole suite: a K-split packed MVM
    closed by tp_replicate equals the single-tile contraction bitwise
    (integer partials; per-input-row activation scales)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import prepack
    from repro.core.pum_linear import pum_linear
    mesh = make_tp_mesh(4)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 64)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(64, 96)) * 0.05, jnp.float32)
    for mode in ("int8", "pum"):
        pcfg = PUMConfig(mode=mode, inference=True)
        packed = prepack.pack_weight(w, pcfg)
        solo = jax.jit(lambda a, b, c=pcfg: pum_linear(a, b, c))(x, packed)
        row = packed.with_arrays(
            None if packed.planes is None else jax.device_put(
                packed.planes, NamedSharding(mesh, P(None, "model", None))),
            jax.device_put(packed.wq, NamedSharding(mesh, P("model", None))),
            jax.device_put(packed.scale, NamedSharding(mesh, P())))
        with shd.use_mesh(mesh, tp_serving=True):
            got = jax.jit(lambda a, b, c=pcfg: pum_linear(a, b, c))(x, row)
        np.testing.assert_array_equal(np.asarray(solo, np.float32),
                                      np.asarray(got, np.float32))


def test_tp_indivisible_heads_raises():
    """kv_heads=2 cannot shard over tp=4: loud ValueError at engine
    construction, not a silent replicated fallback."""
    cfg = small_test_config(num_kv_heads=2, pum=PUMConfig(mode="int8"))
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="num_kv_heads"):
        ServeEngine(cfg, params, max_len=MAX_LEN, mesh=make_tp_mesh(4))


def test_tp_quantize_invariance_under_k_sharding():
    """Per-input-row activation scales: quantising a K-sharded operand
    gives the same (q, scale) as the replicated one — max over the
    contraction axis is order-independent."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.pum_linear import _quantize_act
    mesh = make_tp_mesh(4)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(5, 64)),
                    jnp.bfloat16)
    q0, s0 = jax.jit(lambda a: _quantize_act(a, 8))(x)
    xs = jax.device_put(x, NamedSharding(mesh, P(None, "model")))
    with shd.use_mesh(mesh, tp_serving=True):
        q1, s1 = jax.jit(lambda a: _quantize_act(a, 8))(xs)
    np.testing.assert_array_equal(np.asarray(q0), np.asarray(q1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
