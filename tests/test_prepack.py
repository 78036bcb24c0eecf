"""Prepacked PUM weights: packed forward == raw-weight oracle bit-exactly,
round-trip property, param-tree walking, and the jaxpr proof that the
serving path skips the dense bf16 shadow matmul."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config import PUMConfig, small_test_config
from repro.core import bitslice, prepack
from repro.core.prepack import PackedLinear
from repro.core.pum_linear import pum_linear


def _data(seed=0, m=8, k=64, n=32):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)) / np.sqrt(k), jnp.float32)
    return x, w


# ---------------------------------------------------------------------------
# Packed forward == raw-weight oracle (bit-exact)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits_per_slice", [1, 2, 4])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_pum_packed_forward_bit_exact(bits_per_slice, use_kernel):
    x, w = _data(bits_per_slice)
    cfg = PUMConfig(mode="pum", weight_bits=8,
                    bits_per_slice=bits_per_slice, use_kernel=use_kernel)
    y_raw = pum_linear(x, w, cfg)                      # QAT forward value
    y_packed = pum_linear(x, prepack.pack_weight(w, cfg), cfg)
    np.testing.assert_array_equal(np.asarray(y_raw), np.asarray(y_packed))


def test_int8_packed_forward_bit_exact():
    x, w = _data(7)
    cfg = PUMConfig(mode="int8")
    y_raw = pum_linear(x, w, cfg)
    y_packed = pum_linear(x, prepack.pack_weight(w, cfg), cfg)
    np.testing.assert_array_equal(np.asarray(y_raw), np.asarray(y_packed))


def test_inference_flag_matches_qat_forward_value():
    """``inference=True`` with a raw weight: same forward, no STE/shadow."""
    x, w = _data(9)
    for mode in ("int8", "pum"):
        cfg = PUMConfig(mode=mode)
        y_qat = pum_linear(x, w, cfg)
        y_inf = pum_linear(x, w, dataclasses.replace(cfg, inference=True))
        np.testing.assert_array_equal(np.asarray(y_qat), np.asarray(y_inf))


def test_packed_noise_path_runs():
    from repro.config import ADCConfig, NoiseConfig
    x, w = _data(5, m=2, k=32, n=8)
    cfg = PUMConfig(mode="pum", weight_bits=8, bits_per_slice=2,
                    noise=NoiseConfig(enable=True, prog_sigma=0.01),
                    adc=ADCConfig("sar", bits=10))
    y = pum_linear(x, prepack.pack_weight(w, cfg), cfg,
                   key=jax.random.PRNGKey(0))
    ref = np.asarray(x @ w)
    err = np.abs(np.asarray(y) - ref).max() / (np.abs(ref).max() + 1e-9)
    assert err < 0.2


# ---------------------------------------------------------------------------
# The packed path provably skips the dense bf16 shadow matmul
# ---------------------------------------------------------------------------

def _dot_count(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)

    def walk(jx):
        total = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "dot_general":
                total += 1
            for p in eqn.params.values():
                if type(p).__name__ == "ClosedJaxpr":
                    total += walk(p.jaxpr)
                elif type(p).__name__ == "Jaxpr":
                    total += walk(p)
        return total

    return walk(jaxpr.jaxpr)


def test_packed_path_skips_shadow_matmul():
    x, w = _data(1)
    for mode in ("int8", "pum"):
        cfg = PUMConfig(mode=mode)
        packed = prepack.pack_weight(w, cfg)
        # QAT path: the dense shadow matmul + the quantised contraction
        # (pum's vmapped plane matmuls lower to one batched dot_general)
        assert _dot_count(lambda a, b: pum_linear(a, b, cfg), x, w) == 2
        # packed serving path: exactly the one quantised contraction
        assert _dot_count(lambda a, b: pum_linear(a, b, cfg), x, packed) == 1


# ---------------------------------------------------------------------------
# Forward-equivalence property: packed forward == QAT forward, bit-exact,
# over random shapes / slicings / weights
# ---------------------------------------------------------------------------

@given(seed=st.integers(0, 2**31 - 1),
       mode=st.sampled_from(["int8", "pum"]),
       bits_per_slice=st.sampled_from([1, 2, 4]),
       m=st.integers(1, 6), k=st.integers(1, 48), n=st.integers(1, 24))
@settings(max_examples=20, deadline=None)
def test_packed_forward_matches_qat_property(seed, mode, bits_per_slice,
                                             m, k, n):
    """``pack_weight`` then forward == the per-call QAT forward value,
    bit-exactly, for random int8 weights and arbitrary MVM shapes.

    The weight is built *from* random int8 values times a scale, so the
    QAT path's quantiser must land on exactly those integers and the
    packed planes must recombine to them — any off-by-one in slicing,
    differential encoding or scale handling breaks exact equality."""
    rng = np.random.default_rng(seed)
    wq = rng.integers(-127, 128, size=(k, n))
    w = jnp.asarray(wq * (np.max(np.abs(wq)) or 1) / 127.0 * 0.01,
                    jnp.float32)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    cfg = PUMConfig(mode=mode, weight_bits=8, bits_per_slice=bits_per_slice)
    y_qat = pum_linear(x, w, cfg)
    y_packed = pum_linear(x, prepack.pack_weight(w, cfg), cfg)
    np.testing.assert_array_equal(np.asarray(y_qat), np.asarray(y_packed))


@given(seed=st.integers(0, 2**31 - 1),
       bits_per_slice=st.sampled_from([1, 2, 4]))
@settings(max_examples=15, deadline=None)
def test_pack_planes_recombine_to_wq_property(seed, bits_per_slice):
    """The packed crossbar image is lossless: ``combine_planes`` over the
    stored planes reproduces the stored recombined int8 weight exactly."""
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(17, 11)) * 0.2, jnp.float32)
    cfg = PUMConfig(mode="pum", weight_bits=8,
                    bits_per_slice=bits_per_slice)
    p = prepack.pack_weight(w, cfg)
    back = bitslice.combine_planes(
        jnp.moveaxis(p.planes.astype(jnp.int32), -3, 0), bits_per_slice)
    np.testing.assert_array_equal(np.asarray(back),
                                  np.asarray(p.wq, np.int32))


# ---------------------------------------------------------------------------
# Round-trip property (shim-compatible hypothesis)
# ---------------------------------------------------------------------------

@given(seed=st.integers(0, 2**31 - 1),
       mode=st.sampled_from(["int8", "pum"]),
       bits_per_slice=st.sampled_from([1, 2, 4]),
       stacked=st.booleans())
@settings(max_examples=12, deadline=None)
def test_prepack_unpack_roundtrip(seed, mode, bits_per_slice, stacked):
    """unpack(prepack(p)) ~= p within half a quantisation step."""
    rng = np.random.default_rng(seed)
    shape = (3, 24, 16) if stacked else (24, 16)
    w = jnp.asarray(rng.normal(size=shape) * 0.1, jnp.float32)
    cfg = PUMConfig(mode=mode, weight_bits=8, bits_per_slice=bits_per_slice)
    packed = prepack.pack_weight(w, cfg)
    back = prepack.unpack_weight(packed)
    tol = np.broadcast_to(np.asarray(packed.scale), w.shape) * 0.5 + 1e-7
    assert (np.abs(np.asarray(back) - np.asarray(w)) <= tol).all()


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=5, deadline=None)
def test_prepack_params_tree_roundtrip(seed):
    """Tree walk packs every {"w": ...} linear (and only those) and
    unpacks back to floats of the original structure."""
    from repro.models import lm
    cfg = small_test_config(pum=PUMConfig(mode="pum"))
    params = lm.init_params(cfg, jax.random.PRNGKey(seed % 997))
    packed = prepack.prepack_params(params, cfg.pum)

    packed_leaves = [p for p in jax.tree_util.tree_leaves(
        packed, is_leaf=lambda v: isinstance(v, PackedLinear))
        if isinstance(p, PackedLinear)]
    assert packed_leaves, "no linear weights were packed"
    # embeddings / norms / lm_head stay raw
    assert not isinstance(packed["embed"], PackedLinear)
    assert not isinstance(packed.get("lm_head"), PackedLinear)

    back = prepack.unpack_params(packed)
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(back)):
        assert a.shape == b.shape
        if jnp.issubdtype(a.dtype, jnp.floating) and a.ndim >= 2:
            # quantisation error bounded by the per-slice scale
            assert float(jnp.abs(a - b).max()) <= \
                float(jnp.abs(a).max()) / 127 + 1e-6


def test_prepack_skips_moe_router():
    """The MoE router always runs in fp32 (models/moe.py); packing it
    would crash every prepacked MoE serve."""
    from repro.config import MoEConfig
    from repro.models import lm
    from repro.serve import ServeEngine
    cfg = small_test_config(moe=MoEConfig(num_experts=4, top_k=2),
                            pum=PUMConfig(mode="int8"))
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    packed = prepack.prepack_params(params, cfg.pum)
    for blk in packed["blocks"]:
        if "moe" in blk:
            assert not isinstance(blk["moe"]["router"]["w"], PackedLinear)
    # end to end: prepacked MoE engine decodes token-identically to raw
    eng = ServeEngine(cfg, params, max_len=24)
    raw = ServeEngine(cfg, params, max_len=24, prepack=False)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                                cfg.vocab_size)
    np.testing.assert_array_equal(np.asarray(eng.generate(prompt, 4)),
                                  np.asarray(raw.generate_loop(prompt, 4)))


def test_pack_weight_rejects_wide_weights():
    _, w = _data(0)
    with pytest.raises(AssertionError):
        prepack.pack_weight(w, PUMConfig(mode="pum", weight_bits=12,
                                         bits_per_slice=2))


def test_prepack_params_bf16_noop():
    from repro.models import lm
    cfg = small_test_config()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    assert prepack.prepack_params(params, PUMConfig(mode="bf16")) is params


def test_prepacked_model_forward_matches_raw():
    """Full tiny-model forward: packed params == raw params bit-exactly."""
    from repro.models import lm
    cfg = small_test_config(pum=PUMConfig(mode="pum"))
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0,
                              cfg.vocab_size)
    logits_raw, _, _ = lm.forward(params, toks, cfg)
    packed = prepack.prepack_params(params, cfg.pum)
    icfg = cfg.replace(pum=dataclasses.replace(cfg.pum, inference=True))
    logits_packed, _, _ = lm.forward(packed, toks, icfg)
    np.testing.assert_array_equal(np.asarray(logits_raw),
                                  np.asarray(logits_packed))


@pytest.mark.parametrize("mode", ["int8", "pum"])
def test_init_served_params_matches_init_then_prepack(mode):
    """The one-program load path packs what the two-step path (init in
    f32, then prepack) does.  Fusing init with packing may round the
    random draws' scaling differently by an ulp, so scales and float
    leaves agree to f32 rounding and quantised values to one step."""
    from repro.models import lm
    cfg = small_test_config(pum=PUMConfig(mode=mode))
    key = jax.random.PRNGKey(3)
    fused = lm.init_served_params(cfg, key)
    two_step = lm.prepack_for_serving(lm.init_params(cfg, key), cfg)
    is_packed = lambda x: isinstance(x, PackedLinear)  # noqa: E731
    flat_f, tree_f = jax.tree_util.tree_flatten(fused, is_leaf=is_packed)
    flat_t, tree_t = jax.tree_util.tree_flatten(two_step, is_leaf=is_packed)
    assert tree_f == tree_t
    assert sum(map(is_packed, flat_f)) > 0
    for a, b in zip(flat_f, flat_t):
        if is_packed(a):
            np.testing.assert_allclose(np.asarray(a.scale),
                                       np.asarray(b.scale), rtol=1e-6)
            for qa, qb in ((a.wq, b.wq), (a.planes, b.planes)):
                if qa is not None:
                    assert qa.dtype == qb.dtype == jnp.int8
                    assert np.abs(np.asarray(qa, np.int32)
                                  - np.asarray(qb, np.int32)).max() <= 1
        else:
            assert a.dtype == b.dtype
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-9)


def test_encoder_app_prepack_matches_raw():
    from repro.apps import encoder_app
    cfg = PUMConfig(mode="int8")
    p = encoder_app.encoder_init(jax.random.PRNGKey(0), layers=2,
                                 d_model=32, d_ff=64, heads=2, vocab=50)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, 50)
    h_raw = encoder_app.encoder_apply(p, toks, cfg, heads=2)
    packed = encoder_app.encoder_prepack(p, cfg)
    h_packed = encoder_app.encoder_apply(packed, toks, cfg, heads=2)
    np.testing.assert_array_equal(np.asarray(h_raw), np.asarray(h_packed))
