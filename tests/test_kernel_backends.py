"""The kernel-backend registry and the serving hot-path kernels.

Pins (1) the registry's selection semantics (nesting, per-kernel
overrides, the typed sub-floor tile error, one release of deprecation
grace for the old kwargs), (2) bitwise equality ``interpret == xla``
for every kernel family over random shapes / bit widths / block sizes
(the pallas leg needs a real TPU and is exercised there via the same
parametrisation), and (3) the scheduler leg: completions are
bit-identical whichever backend serves the decode steps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bitslice
from repro.kernels import registry
from repro.kernels.bitslice_mvm import (bitslice_mvm, bitslice_mvm_planes,
                                        bitslice_mvm_planes_scaled)
from repro.kernels.gf2_mvm import gf2_mvm
from repro.kernels.paged_attention import paged_attention
from repro.kernels.registry import KernelBackend, KernelTileError

# the non-XLA backend that runs on this host: compiled pallas on TPU,
# the interpreter elsewhere — the property tests below pin it to the
# oracle, so on TPU CI the same suite checks the compiled kernel
KERNEL = registry.native_backend()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_backend_selection_nesting_and_overrides():
    assert registry.get_backend() is None
    assert registry.get_backend("bitslice_mvm") is None
    with registry.use_backend("pallas"):
        assert registry.get_backend() is KernelBackend.PALLAS
        assert registry.get_backend("gf2_mvm") is KernelBackend.PALLAS
        with registry.use_backend(gf2_mvm="xla"):
            # inner frame's override wins for its kernel only
            assert registry.get_backend("gf2_mvm") is KernelBackend.XLA
            assert registry.get_backend("bitslice_mvm") \
                is KernelBackend.PALLAS
        with registry.use_backend("interpret"):
            assert registry.get_backend("gf2_mvm") \
                is KernelBackend.INTERPRET
    assert registry.get_backend() is None


def test_coerce_backend_accepts_enum_string_none_and_rejects_junk():
    assert registry.coerce_backend(None) is None
    assert registry.coerce_backend("XLA") is KernelBackend.XLA
    assert registry.coerce_backend(KernelBackend.PALLAS) \
        is KernelBackend.PALLAS
    with pytest.raises(ValueError, match="unknown kernel backend"):
        registry.coerce_backend("cuda")


def test_resolve_backend_precedence():
    # explicit beats ambient beats default beats native
    with registry.use_backend("xla"):
        assert registry.resolve_backend("interpret") \
            is KernelBackend.INTERPRET
        assert registry.resolve_backend() is KernelBackend.XLA
    assert registry.resolve_backend(default="xla") is KernelBackend.XLA
    assert registry.resolve_backend() is registry.native_backend()


def test_explicit_subfloor_block_m_raises_typed_error():
    with pytest.raises(KernelTileError, match="sublane floor"):
        registry.choose_block_m(1, 4, KernelBackend.INTERPRET)
    with pytest.raises(KernelTileError):
        registry.choose_block_m(64, 16, KernelBackend.PALLAS)
    # ...and through the public op
    x = jnp.zeros((4, 64), jnp.int32)
    w = jnp.zeros((64, 64), jnp.int32)
    with pytest.raises(KernelTileError), \
            pytest.warns(DeprecationWarning, match="block_m"):
        bitslice_mvm(x, w, backend=KERNEL, block_m=2)


def test_deprecated_kwargs_warn_but_work():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(-100, 101, size=(4, 64)), jnp.int32)
    w = jnp.asarray(rng.integers(-127, 128, size=(64, 32)), jnp.int32)
    want = np.asarray(x, np.int64) @ np.asarray(w, np.int64)
    with pytest.warns(DeprecationWarning, match="interpret="):
        got = bitslice_mvm(x, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)
    with pytest.warns(DeprecationWarning, match="block_m"):
        got = bitslice_mvm(x, w, backend="interpret", block_m=64)
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)
    with pytest.warns(DeprecationWarning, match="interpret="):
        gf2_mvm((x > 0).astype(jnp.int8), (w > 0).astype(jnp.int8),
                interpret=True)


def test_ambient_selection_reaches_the_op():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(-100, 101, size=(3, 48)), jnp.int32)
    w = jnp.asarray(rng.integers(-127, 128, size=(48, 24)), jnp.int32)
    want = np.asarray(x, np.int64) @ np.asarray(w, np.int64)
    with registry.use_backend(KERNEL):
        got = bitslice_mvm(x, w)
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)
    with registry.use_backend("xla"):
        got = bitslice_mvm(x, w)
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)


# ---------------------------------------------------------------------------
# property tests: kernel backends == xla oracle, bit for bit
# ---------------------------------------------------------------------------

@given(seed=st.integers(0, 2**31 - 1),
       m=st.sampled_from([1, 4, 16, 33, 130]),
       k=st.sampled_from([24, 64, 200]),
       n=st.sampled_from([16, 100, 129]),
       bits=st.sampled_from([(8, 2), (8, 1), (4, 1), (8, 7)]),
       block=st.sampled_from([None, 64, 128]))
@settings(max_examples=16, deadline=None)
def test_bitslice_mvm_backends_bit_identical(seed, m, k, n, bits, block):
    wb, bps = bits
    rng = np.random.default_rng(seed)
    qmax = (1 << (wb - 1)) - 1
    x = jnp.asarray(rng.integers(-127, 128, size=(m, k)), jnp.int32)
    w = jnp.asarray(rng.integers(-qmax, qmax + 1, size=(k, n)), jnp.int32)
    ref = bitslice_mvm(x, w, weight_bits=wb, bits_per_slice=bps,
                       backend="xla")
    if block is None:
        got = bitslice_mvm(x, w, weight_bits=wb, bits_per_slice=bps,
                           backend=KERNEL)
    else:
        with pytest.warns(DeprecationWarning, match="block_m/block_n"):
            got = bitslice_mvm(x, w, weight_bits=wb, bits_per_slice=bps,
                               backend=KERNEL, block_n=block,
                               block_k=block)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@given(seed=st.integers(0, 2**31 - 1),
       m=st.sampled_from([1, 4, 16, 130]),
       k=st.sampled_from([40, 128]),
       n=st.sampled_from([24, 96]),
       bps=st.sampled_from([1, 2, 7]))
@settings(max_examples=12, deadline=None)
def test_planes_and_fused_scale_backends_bit_identical(seed, m, k, n, bps):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-127, 128, size=(m, k)), jnp.int32)
    w = jnp.asarray(rng.integers(-127, 128, size=(k, n)), jnp.int32)
    planes = bitslice.slice_planes_signed(w, 8, bps).astype(jnp.int8)
    scale = jnp.asarray(rng.uniform(0.01, 2.0, size=(m, 1)), jnp.float32)
    ref = bitslice_mvm_planes(x, planes, bits_per_slice=bps, backend="xla")
    got = bitslice_mvm_planes(x, planes, bits_per_slice=bps, backend=KERNEL)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # the fused decode tile == unfused accumulate-then-scale, bitwise
    fused = bitslice_mvm_planes_scaled(x, planes, scale,
                                       bits_per_slice=bps, backend=KERNEL)
    fused_ref = bitslice_mvm_planes_scaled(x, planes, scale,
                                           bits_per_slice=bps,
                                           backend="xla")
    unfused = np.asarray(ref, np.float32) * np.asarray(scale)
    np.testing.assert_array_equal(np.asarray(fused), unfused)
    np.testing.assert_array_equal(np.asarray(fused_ref), unfused)


@given(seed=st.integers(0, 2**31 - 1), m=st.sampled_from([1, 16, 130]),
       k=st.sampled_from([64, 200]), n=st.sampled_from([32, 129]))
@settings(max_examples=10, deadline=None)
def test_gf2_mvm_backends_bit_identical(seed, m, k, n):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, 2, size=(m, k)), jnp.int8)
    a = jnp.asarray(rng.integers(0, 2, size=(k, n)), jnp.int8)
    ref = gf2_mvm(x, a, backend="xla")
    got = gf2_mvm(x, a, backend=KERNEL)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def _paged_case(rng, *, b, s, w, bs, kvh, g, hd, layers=2, layer=1,
                dtype=jnp.bfloat16):
    """A scheduler-realistic paged-attention state: every *active* row's
    causally visible positions map to allocated (non-trash) blocks in
    both tables — the invariant the real block allocator maintains, and
    the boundary of the kernel's bit-identity guarantee (trash content
    is not part of the contract; inactive rows are discarded).  The
    pools are the stacked lane-dense ``[layers, NB, bs, KV * hd]``
    store, addressed at ``layer``."""
    nb = 1 + b * w                       # block 0 = trash
    q = jnp.asarray(rng.standard_normal((b, s, kvh, g, hd)), dtype)
    kn = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), dtype)
    vn = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), dtype)
    kp = jnp.asarray(rng.standard_normal((layers, nb, bs, kvh * hd)), dtype)
    vp = jnp.asarray(rng.standard_normal((layers, nb, bs, kvh * hd)), dtype)
    # disjoint per-row block ranges; depths keep every visible position
    # (and every write) inside the row's allocated columns
    table = np.arange(1, 1 + b * w).reshape(b, w)
    ci = np.asarray([int(rng.integers(0, w * bs - s + 1))
                     for _ in range(b)])
    wtable = table.copy()
    # prefix-cache sharing: row 0's first column is read-only (its write
    # route is trash) whenever no write lands there
    if ci[0] >= bs:
        wtable[0, 0] = 0
    return (q, kn, vn, kp, vp, jnp.asarray(table, jnp.int32),
            jnp.asarray(wtable, jnp.int32), jnp.asarray(ci, jnp.int32),
            jnp.int32(layer))


@given(seed=st.integers(0, 2**31 - 1),
       s=st.sampled_from([1, 4, 16]),
       bs=st.sampled_from([4, 16]),
       geom=st.sampled_from([(2, 1, 2, 8), (3, 2, 1, 16), (2, 2, 4, 8)]),
       softcap=st.sampled_from([0.0, 30.0]),
       crop=st.booleans())
@settings(max_examples=16, deadline=None)
def test_paged_attention_backends_bit_identical(seed, s, bs, geom,
                                                softcap, crop):
    kvh, g, w, hd = geom
    if s > w * bs:
        s = w * bs
    rng = np.random.default_rng(seed)
    b = 3
    args = _paged_case(rng, b=b, s=s, w=w, bs=bs, kvh=kvh, g=g, hd=hd)
    kv_len = (w * bs - bs // 2) if crop else None
    kx = paged_attention(*args, kv_len=kv_len, softcap=softcap,
                         backend="xla")
    kk = paged_attention(*args, kv_len=kv_len, softcap=softcap,
                         backend=KERNEL)
    # pools: every real block of every layer identical (trash, id 0, is
    # outside the contract); outputs: all rows are active here, all
    # identical
    for got, ref in zip(kk[:2], kx[:2]):
        np.testing.assert_array_equal(np.asarray(got)[:, 1:],
                                      np.asarray(ref)[:, 1:])
    np.testing.assert_array_equal(np.asarray(kk[2]), np.asarray(kx[2]))


def test_paged_attention_ambient_backend_and_pool_update():
    rng = np.random.default_rng(7)
    args = _paged_case(rng, b=2, s=1, w=2, bs=4, kvh=2, g=2, hd=8)
    with registry.use_backend(KERNEL):
        kp, vp, out = paged_attention(*args)
    ref = paged_attention(*args, backend="xla")
    np.testing.assert_array_equal(np.asarray(kp)[:, 1:],
                                  np.asarray(ref[0])[:, 1:])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref[2]))
    # the write actually landed: the pool changed at the written slot of
    # the addressed layer
    ci, table, layer = args[7], args[5], int(args[8])
    b0_blk = int(table[0, int(ci[0]) // 4])
    assert not np.array_equal(np.asarray(kp)[layer, b0_blk],
                              np.asarray(args[3])[layer, b0_blk])


@pytest.mark.parametrize("s", [1, 16])
def test_paged_attention_addresses_one_layer_of_the_stack(s):
    """The kernel called at layer 1 of a 3-layer stacked lane-dense pool:
    that layer is bit-identical to ``ref.py``'s (trash block aside),
    every other layer and every block no write lands in are
    bit-identical to the input.  Row 0 attends a prefix-shared first
    column (trash-routed in its write table); row 2's second column is
    trash-routed while its writes cross it, so those tokens land in the
    trash block and the row reads the column's cached K/V, as the
    composition does."""
    from repro.kernels.paged_attention import paged_attention_ref
    b, w, bs, kvh, g, hd, layers, layer = 3, 4, 8, 2, 2, 64, 3, 1
    rng = np.random.default_rng(16 + s)
    q, kn, vn, kp, vp, table, _, _, _ = _paged_case(
        rng, b=b, s=s, w=w, bs=bs, kvh=kvh, g=g, hd=hd, layers=layers,
        layer=layer)
    ci = np.asarray([bs + 4, 0, bs + 2] if s == 1 else [bs + 4, 16, 4])
    wtable = np.asarray(table).copy()
    wtable[0, 0] = 0                 # shared prefix: read-only
    wtable[2, 1] = 0                 # written across, routed to trash
    wtable = jnp.asarray(wtable, jnp.int32)
    args = (q, kn, vn, kp, vp, table, wtable, jnp.asarray(ci, jnp.int32),
            jnp.int32(layer))
    got = paged_attention(*args, backend=KERNEL)
    ref = paged_attention_ref(*args)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(ref[2]))
    written = {int(wtable[r, p // bs]) for r in range(b)
               for p in range(ci[r], ci[r] + s)}
    untouched = [blk for blk in range(1 + b * w) if blk not in written]
    assert 0 in written and untouched
    for new, want, old in zip(got[:2], ref[:2], (kp, vp)):
        new, want, old = (np.asarray(a) for a in (new, want, old))
        np.testing.assert_array_equal(new[layer, 1:], want[layer, 1:])
        for other in range(layers):
            if other != layer:
                np.testing.assert_array_equal(new[other], old[other])
        np.testing.assert_array_equal(new[layer, untouched],
                                      old[layer, untouched])
    # and the writes that were not trash-routed landed
    blk = int(table[0, (bs + 4) // bs])
    assert not np.array_equal(np.asarray(got[0])[layer, blk],
                              np.asarray(kp)[layer, blk])


# ---------------------------------------------------------------------------
# the serving stack under each backend
# ---------------------------------------------------------------------------

# family kwargs mirror tests/test_scheduler.py's grids; block sizes
# {1, 4, 16} are the acceptance sweep — 1 maximises table-walk steps,
# 16 puts whole prompts in one block
@pytest.mark.parametrize("family,mode,block", [
    ("dense", "pum", 4),
    ("dense", "int8", 1),
    ("dense", "bf16", 4),        # attention kernel alone, no MVM kernel
    ("xlstm", "pum", 4),
    ("hybrid", "int8", 16),
])
def test_scheduler_completions_identical_across_backends(family, mode,
                                                         block):
    from repro.config import PUMConfig, small_test_config
    from repro.models import lm
    from repro.serve import ContinuousBatchingScheduler, synthetic_workload

    fam = {"dense": {}, "xlstm": dict(xlstm_slstm_every=2),
           "hybrid": dict(attn_period=2)}[family]
    cfg = small_test_config(**fam, pum=PUMConfig(mode=mode))
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    reqs = synthetic_workload(5, cfg.vocab_size, max_prompt=10, max_new=6,
                              mean_interarrival=0.0, seed=2)
    outs = {}
    for kb in ("xla", KERNEL.value):
        sched = ContinuousBatchingScheduler(
            cfg, params, num_slots=2, max_len=20, kv_block_size=block,
            chunked_prefill=True, kernel_backend=kb)
        outs[kb] = {rid: (c.tokens, c.finish_reason)
                    for rid, c in sched.run(reqs).items()}
    assert outs["xla"] == outs[KERNEL.value]
