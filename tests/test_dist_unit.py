"""Direct unit tests for repro.dist (no subprocess, 1 device).

The subprocess tests in test_distributed.py prove end-to-end behavior on
8 forced devices; these pin the API contract pieces individually."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.config import ShardingConfig
from repro.dist import compress
from repro.dist import sharding as shd
from repro.launch.mesh import make_test_mesh
from repro.models import lm


def test_param_specs_default_arity():
    cfg = configs.get_reduced("glm4-9b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    specs = shd.param_specs(params)
    # same tree structure (PartitionSpec leaves)
    s1 = jax.tree_util.tree_structure(
        specs, is_leaf=lambda x: isinstance(x, P))
    s2 = jax.tree_util.tree_structure(params)
    assert s1 == s2
    # default scfg has FSDP on: stacked column-parallel weight
    wg = specs["blocks"][0]["mlp"]["wg"]["w"]
    assert wg == P(None, "data", "model"), wg
    # row-parallel attention output projection
    wo = specs["blocks"][0]["attn"]["wo"]["w"]
    assert wo == P(None, "model", "data"), wo
    # norm scales stay replicated
    assert specs["final_norm"]["scale"] == P(None)


def test_param_specs_scfg_arity():
    cfg = configs.get_reduced("glm4-9b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    specs = shd.param_specs(params, ShardingConfig(fsdp=False))
    wg = specs["blocks"][0]["mlp"]["wg"]["w"]
    assert wg == P(None, None, "model"), wg
    wo = specs["blocks"][0]["attn"]["wo"]["w"]
    assert wo == P(None, "model", None), wo


def test_shard_act_noop_without_mesh():
    x = jnp.ones((4, 8, 16))
    assert shd.current_mesh() is None
    y = shd.shard_act(x, "data", "model", None)
    assert y is x


def test_shard_act_divisibility_guard():
    mesh = make_test_mesh((1,), ("data",))
    with shd.use_mesh(mesh):
        # 3 not divisible by ... axis size 1 divides everything; spec
        # referencing an absent axis is dropped instead of erroring
        x = jnp.ones((3, 5))
        y = shd.shard_act(x, "model", "data")
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    assert shd.current_mesh() is None


def test_use_mesh_restores_on_exception():
    mesh = make_test_mesh((1,), ("data",))
    with pytest.raises(RuntimeError), shd.use_mesh(mesh):
        assert shd.current_mesh() is mesh
        raise RuntimeError("boom")
    assert shd.current_mesh() is None


def test_residual_spec_modes():
    try:
        shd.set_seq_shard("hidden")
        assert shd.residual_spec() == ("data", None, "model")
        shd.set_seq_shard(False)
        assert shd.residual_spec() == ("data", None, None)
        shd.set_seq_shard(True)
        assert shd.residual_spec() == ("data", "model", None)
    finally:
        shd.set_seq_shard("seq")


def test_compressed_psum_single_device_error_bound():
    mesh = make_test_mesh((1,), ("data",))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 256)), jnp.float32)
    got = compress.compressed_psum(x, mesh, "data")
    # sum over one shard == identity up to int8 quantisation error:
    # |err| <= scale/2 with scale = max|x| / 127
    bound = float(jnp.max(jnp.abs(x))) / 127.0
    err = float(jnp.abs(got - x).max())
    assert err <= bound + 1e-6, (err, bound)


def test_ef_compression_is_lossless_in_aggregate():
    rng = np.random.default_rng(1)
    g = {"w": jnp.asarray(rng.normal(size=(32, 32)), jnp.float32)}
    res = compress.zeros_like_residual(g)
    dec, res = compress.ef_compress_grads(g, res)
    # one step: dec + residual reconstructs the gradient exactly
    np.testing.assert_allclose(np.asarray(dec["w"] + res["w"]),
                               np.asarray(g["w"]), rtol=1e-6, atol=1e-6)
    # quantisation error bounded by half an int8 step
    bound = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    assert float(jnp.abs(res["w"]).max()) <= bound + 1e-6


def test_decode_state_specs_non_divisible_heads_stay_replicated():
    mesh = make_test_mesh((1,), ("data",))  # no model axis at all
    cfg = configs.get_reduced("glm4-9b")
    st = lm.init_state(cfg, 4, 32, abstract=True)
    specs = shd.decode_state_specs(st, mesh)
    assert specs[0]["k"] == P(None, "data", None, None, None)


# ---------------------------------------------------------------------------
# Tensor-parallel serving specs (PackedLinear + KV pool) — pure spec
# tests; the structural ones need no mesh at all, the guard tests need a
# real 2-wide mesh (they run in the multidevice CI leg / make test-tp)
# ---------------------------------------------------------------------------

_needs2 = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs a 2-device mesh (XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


def _packed_params(mode="pum"):
    from repro.config import PUMConfig, small_test_config
    cfg = small_test_config(num_kv_heads=4, pum=PUMConfig(mode=mode))
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, lm.prepack_for_serving(params, cfg)


def test_serve_param_specs_packed_column_and_row():
    """Column-parallel packs shard N (planes slice axis replicated,
    scales replicated); row-parallel names (wo/wd/out_proj) shard K."""
    from repro.core.prepack import PackedLinear
    _, packed = _packed_params("pum")
    specs = shd.serve_param_specs(packed)
    wg = specs["blocks"][0]["mlp"]["wg"]["w"]       # column-parallel
    assert isinstance(wg, PackedLinear)
    assert wg.wq == P(None, None, "model"), wg.wq   # [G, K, N]
    assert wg.planes == P(None, None, None, "model"), wg.planes
    assert wg.scale == P(None, None, None), wg.scale
    wd = specs["blocks"][0]["mlp"]["wd"]["w"]       # row-parallel
    assert wd.wq == P(None, "model", None), wd.wq
    assert wd.planes == P(None, None, "model", None), wd.planes
    assert wd.scale == P(None, None, None), wd.scale
    wo = specs["blocks"][0]["attn"]["wo"]["w"]
    assert wo.wq == P(None, "model", None), wo.wq
    # lm_head shards vocab; embedding and norms stay replicated
    assert specs["lm_head"] == P(None, "model")
    assert specs["embed"] == P(None, None)
    assert specs["final_norm"]["scale"] == P(None)


def test_serve_param_specs_int8_single_plane():
    """int8 packs have no planes (None stays None) and per-out-channel
    scales stay replicated."""
    _, packed = _packed_params("int8")
    specs = shd.serve_param_specs(packed)
    wg = specs["blocks"][0]["mlp"]["wg"]["w"]
    assert wg.planes is None
    assert wg.wq == P(None, None, "model")
    assert wg.scale == P(None, None, None)


def test_serve_param_specs_raw_float_never_shards_k():
    """bf16 serving (raw float weights): column-parallel only — no K
    axis ever carries ``model``, the float-contraction bitwise rule."""
    from repro.config import small_test_config
    cfg = small_test_config(num_kv_heads=4)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    specs = shd.serve_param_specs(params)
    for p in (specs["blocks"][0]["mlp"]["wd"]["w"],
              specs["blocks"][0]["attn"]["wo"]["w"]):
        assert p == P(None, None, "model"), p        # N-sharded, K free


@_needs2
def test_serve_param_specs_divide_evenly_under_mesh_guard():
    """With an active mesh, every sharded spec dimension divides the
    axis size; an indivisible one is dropped, never an error."""
    from repro.core.prepack import PackedLinear
    _, packed = _packed_params("pum")
    mesh = make_test_mesh((2,), ("model",))
    with shd.use_mesh(mesh):
        specs = shd.serve_param_specs(packed)

    def leaves(tree):
        return jax.tree_util.tree_leaves(
            tree, is_leaf=lambda v: isinstance(v, (P, PackedLinear)))

    for leaf, spec in zip(leaves(packed), leaves(specs)):
        arrs = [leaf] if not isinstance(leaf, PackedLinear) else \
            [a for a in (leaf.planes, leaf.wq, leaf.scale) if a is not None]
        sps = [spec] if not isinstance(spec, PackedLinear) else \
            [s for s in (spec.planes, spec.wq, spec.scale) if s is not None]
        for a, s in zip(arrs, sps):
            for dim, ax in zip(a.shape, tuple(s)):
                if ax is not None:
                    assert dim % mesh.shape[ax] == 0, (a.shape, s)


@_needs2
def test_serve_state_specs_pool_and_cache_head_axis():
    from repro.config import small_test_config
    mesh = make_test_mesh((2,), ("model",))
    cfg = small_test_config(num_kv_heads=4)
    paged = lm.init_paged_state(cfg, 2, 32, num_blocks=6, block_size=4)
    specs = shd.serve_state_specs(paged, mesh, kv_heads=4)
    # [G, NB, bs, KV * hd]: the folded lanes split into whole heads
    assert specs[0]["k_pool"] == P(None, None, None, "model")
    assert specs[0]["v_pool"] == P(None, None, None, "model")
    contig = lm.init_state(cfg, 2, 32)
    specs = shd.serve_state_specs(contig, mesh, kv_heads=4)
    assert specs[0]["k"] == P(None, None, None, "model", None)
    # recurrent rows replicate (no data axis on the 1-D serving mesh)
    cfg_x = small_test_config(num_kv_heads=4, xlstm_slstm_every=2)
    st = lm.init_state(cfg_x, 2, 32)
    specs = shd.serve_state_specs(st, mesh, kv_heads=4)
    # mlstm c is [G, B, heads, hd, hd]: fully replicated
    assert specs[1]["c"] == P(*([None] * st[1]["c"].ndim))


@_needs2
def test_serve_state_specs_indivisible_heads_drop():
    mesh = make_test_mesh((2,), ("model",))
    from repro.config import small_test_config
    cfg = small_test_config(num_kv_heads=3)   # 3 % 2 != 0
    paged = lm.init_paged_state(cfg, 2, 32, num_blocks=6, block_size=4)
    # 3 heads of 16 make 48 lanes, which 2 divides: the guard goes by
    # whole heads, not by the lane count
    specs = shd.serve_state_specs(paged, mesh, kv_heads=3)
    assert specs[0]["k_pool"] == P(None, None, None, None)


def test_validate_tp_raises_on_indivisible():
    from repro.config import small_test_config
    cfg = small_test_config(num_kv_heads=2)
    with pytest.raises(ValueError, match="num_kv_heads"):
        shd.validate_tp(cfg, 4)
    with pytest.raises(ValueError, match="d_ff"):
        shd.validate_tp(small_test_config(num_kv_heads=4, d_ff=130), 4)
    # tp=1 and a clean divide pass silently; pure-recurrent stacks have
    # no KV-head constraint
    shd.validate_tp(cfg, 1)
    shd.validate_tp(small_test_config(num_kv_heads=4), 4)
    shd.validate_tp(small_test_config(num_kv_heads=2,
                                      xlstm_slstm_every=2), 4)
