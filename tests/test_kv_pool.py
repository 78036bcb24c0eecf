"""Paged KV-cache pool: allocator properties, paged-attention unit
equivalence, and capacity accounting.

The scheduler-level oracle-equivalence suite lives in
``test_scheduler.py``; this file pins the pieces underneath it — the
block allocator can never double-assign, the paged attention path is
bit-identical to the contiguous cache, and the memory accounting the
benchmarks report is real.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config import MoEConfig, small_test_config
from repro.models import attention, lm
from repro.serve import kv_pool
from repro.serve.errors import (BlockAllocatorError, BlockNotLive,
                                BlockOutOfRange)


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------

def test_allocator_basic_alloc_free_cycle():
    a = kv_pool.BlockAllocator(4)
    ids = a.alloc(3)
    assert ids is not None and len(ids) == 3
    assert len(set(ids)) == 3
    assert a.free_blocks == 1 and a.live_blocks == 3
    assert 0 not in ids                      # trash block never handed out
    a.free(ids)
    assert a.free_blocks == 4 and a.live_blocks == 0


def test_allocator_all_or_nothing():
    a = kv_pool.BlockAllocator(3)
    assert a.alloc(2) is not None
    # 2 blocks requested, 1 free: refuse without touching the free list
    assert a.alloc(2) is None
    assert a.free_blocks == 1
    assert a.alloc(1) is not None


def test_allocator_rejects_double_free_and_foreign_ids():
    a = kv_pool.BlockAllocator(4)
    ids = a.alloc(2)
    a.free(ids)
    with pytest.raises(ValueError, match="not live"):
        a.free(ids)                          # double free
    with pytest.raises(ValueError, match="not a pool block"):
        a.free([99])                         # never allocated
    # typed: both are BlockAllocatorError subclasses AND ValueErrors,
    # so legacy except-ValueError callers still catch them
    with pytest.raises(BlockNotLive):
        a.free(ids)
    with pytest.raises(BlockOutOfRange):
        a.free([99])
    with pytest.raises(BlockAllocatorError):
        a.free([kv_pool.TRASH_BLOCK])        # trash block is never freeable
    assert a.free_blocks == 4                # errors moved nothing


def test_allocator_refcounts_share_and_release():
    """acquire/release semantics: a block returns to the free list only
    when its LAST reference drops; acquire validates before mutating."""
    a = kv_pool.BlockAllocator(4)
    ids = a.alloc(2)
    a.acquire(ids)                           # refcount 2 each
    assert all(a.refcount(i) == 2 for i in ids)
    a.release(ids)                           # back to 1 — still live
    assert a.free_blocks == 2 and a.live_blocks == 2
    a.release(ids)                           # last refs — freed
    assert a.free_blocks == 4 and a.live_blocks == 0
    with pytest.raises(BlockNotLive, match="not live"):
        a.acquire(ids)                       # can't acquire a free block
    with pytest.raises(BlockOutOfRange):
        a.acquire([kv_pool.TRASH_BLOCK])
    # acquire validates ALL ids before incrementing ANY refcount
    live = a.alloc(1)
    with pytest.raises(BlockNotLive):
        a.acquire(live + [live[0] + 1])      # second id is free
    assert a.refcount(live[0]) == 1          # first id untouched


@given(seed=st.integers(0, 2**31 - 1),
       num_blocks=st.sampled_from([1, 3, 8, 17]))
@settings(max_examples=20, deadline=None)
def test_allocator_never_double_assigns(seed, num_blocks):
    """Random admit/retire traces: at every point, live block ids are
    unique, disjoint across owners, within range, and conserved."""
    rng = np.random.default_rng(seed)
    a = kv_pool.BlockAllocator(num_blocks)
    owned = {}                               # owner -> ids
    next_owner = 0
    for _ in range(200):
        if owned and rng.random() < 0.45:
            owner = rng.choice(sorted(owned))
            a.free(owned.pop(owner))
        else:
            want = int(rng.integers(1, num_blocks + 1))
            ids = a.alloc(want)
            if ids is None:
                assert want > a.free_blocks
                continue
            owned[next_owner] = ids
            next_owner += 1
        live = [i for ids in owned.values() for i in ids]
        assert len(live) == len(set(live)), "block assigned twice"
        assert all(1 <= i <= num_blocks for i in live)
        assert a.live_blocks == len(live)
        assert a.free_blocks == num_blocks - len(live)


@given(seed=st.integers(0, 2**31 - 1),
       num_blocks=st.sampled_from([1, 3, 8, 17]))
@settings(max_examples=20, deadline=None)
def test_allocator_refcount_property(seed, num_blocks):
    """Random admit/acquire/release traces against a reference refcount
    model: ids stay unique and in range, block 0 is never handed out or
    freed, and free/live accounting matches the model at every step."""
    rng = np.random.default_rng(seed)
    a = kv_pool.BlockAllocator(num_blocks)
    refs: dict[int, int] = {}               # reference model
    for _ in range(300):
        op = rng.random()
        if refs and op < 0.3:               # drop one ref somewhere
            blk = int(rng.choice(sorted(refs)))
            a.release([blk])
            refs[blk] -= 1
            if refs[blk] == 0:
                del refs[blk]
        elif refs and op < 0.5:             # share an existing block
            blk = int(rng.choice(sorted(refs)))
            a.acquire([blk])
            refs[blk] += 1
        else:
            want = int(rng.integers(1, num_blocks + 1))
            ids = a.alloc(want)
            if ids is None:
                assert want > a.free_blocks
                continue
            assert len(set(ids)) == len(ids)
            assert all(i in range(1, num_blocks + 1) and i not in refs
                       for i in ids), "re-assigned a live block"
            for i in ids:
                refs[i] = 1
        assert kv_pool.TRASH_BLOCK not in refs
        assert kv_pool.TRASH_BLOCK not in a._free
        assert a.live_blocks == len(refs)
        assert a.free_blocks == num_blocks - len(refs)
        for blk, n in refs.items():
            assert a.refcount(blk) == n
    # releasing every outstanding ref drains the pool completely
    for blk, n in list(refs.items()):
        a.release([blk] * n)
    assert a.free_blocks == num_blocks and a.live_blocks == 0


def test_blocks_needed_accounting():
    # prompt 1 + 1 generated token: only the prompt position is written
    assert kv_pool.blocks_needed(1, 1, 4) == 1
    # 8 prompt + 8 generated -> positions 0..14 -> 15 slots
    assert kv_pool.blocks_needed(8, 8, 4) == 4
    assert kv_pool.blocks_needed(8, 9, 4) == 4    # 16 positions exactly
    assert kv_pool.blocks_needed(8, 10, 4) == 5
    assert kv_pool.blocks_needed(5, 3, 1) == 7
    assert kv_pool.table_width(32, 4) == 8
    assert kv_pool.table_width(33, 4) == 9


# ---------------------------------------------------------------------------
# Prefix cache: chain hashing, match/attach/register lifecycle, eviction
# ---------------------------------------------------------------------------

def test_prefix_chain_hashes_identify_whole_prefixes():
    h1 = kv_pool.prefix_chain_hashes([1, 2, 3, 4, 5, 6, 7], 4)
    assert len(h1) == 1                      # only FULL blocks hash
    h2 = kv_pool.prefix_chain_hashes([1, 2, 3, 4, 9, 9, 9, 9], 4)
    assert h2[0] == h1[0]                    # same first block
    h3 = kv_pool.prefix_chain_hashes([1, 2, 3, 5, 9, 9, 9, 9], 4)
    assert h3[0] != h1[0] and h3[1] != h2[1]  # divergence chains forward
    # the root folds in engine identity: same tokens, different engine
    assert kv_pool.prefix_chain_hashes([1, 2, 3, 4], 4, root="a") \
        != kv_pool.prefix_chain_hashes([1, 2, 3, 4], 4, root="b")
    # block geometry changes the chunking, hence the hashes
    assert kv_pool.prefix_chain_hashes([1, 2, 3, 4], 2) \
        != kv_pool.prefix_chain_hashes([1, 2, 3, 4], 4)


def test_prefix_cache_match_attach_register_lifecycle():
    a = kv_pool.BlockAllocator(8)
    c = kv_pool.PrefixCache(a, 4, capacity=8)
    toks = list(range(12))                   # 3 full blocks
    hs = c.hashes(toks)
    assert c.match(hs) == 0
    # a request prefills blocks 1..3 and registers them
    ids = a.alloc(3)
    c.register(hs, ids)
    assert len(c) == 3 and c.cached_blocks == 3
    assert all(a.refcount(i) == 2 for i in ids)   # owner + cache
    a.release(ids)                                # owner retires
    assert a.live_blocks == 3                     # cache keeps them live
    assert c.evictable_blocks == 3
    # a second request matches and attaches the full prefix
    assert c.match(hs) == 3
    assert c.match(hs[:2]) == 2
    assert c.match(hs, limit=1) == 1
    got = c.attach(hs)
    assert got == ids and all(a.refcount(i) == 2 for i in ids)
    assert c.evictable_blocks == 0                # in use -> not evictable
    assert c.evictable_margin(exclude=hs) == 0
    a.release(got)
    # divergent prompt shares only the common prefix
    hs2 = c.hashes(toks[:4] + [99] * 8)
    assert c.match(hs2) == 1


def test_prefix_cache_lru_eviction_and_flush():
    a = kv_pool.BlockAllocator(4)
    c = kv_pool.PrefixCache(a, 2, capacity=2)
    h1, h2, h3 = (c.hashes(t) for t in ([1, 2], [3, 4], [5, 6]))
    b1 = a.alloc(1)
    c.register(h1, b1)
    a.release(b1)                            # owner retires; cache holds it
    b2 = a.alloc(1)
    c.register(h2, b2)
    a.release(b2)
    assert a.live_blocks == 2 and c.evictable_blocks == 2
    a.release(c.attach(h1))                  # LRU-touch h1 -> h2 is LRU
    b3 = a.alloc(1)
    c.register(h3, b3)                       # at capacity: evicts h2
    a.release(b3)
    assert c.match(h2) == 0 and c.match(h1) == 1 and c.match(h3) == 1
    assert a.live_blocks == 2
    # in-use entries are never evicted, even under block pressure
    pinned = c.attach(h1)
    assert c.evict_blocks(10) == 1           # only h3's block can go
    assert c.match(h1) == 1 and c.match(h3) == 0
    a.release(pinned)
    assert c.flush() == 1 and len(c) == 0
    assert a.live_blocks == 0 and a.free_blocks == 4


def test_prefix_cache_snapshot_gating():
    """Recurrent stacks can only resume where a snapshot exists:
    ``need_snapshot`` shrinks the match to the deepest snapshot-bearing
    entry, and blockless (pure-recurrent) entries never touch the
    allocator."""
    a = kv_pool.BlockAllocator(4)
    c = kv_pool.PrefixCache(a, 2, capacity=8)
    hs = c.hashes(list(range(6)))            # 3 full blocks
    c.register(hs, [None, None, None], snapshots={0: "snap0", 1: "snap1"})
    assert a.live_blocks == 0                # blockless entries
    assert c.match(hs) == 3
    assert c.match(hs, need_snapshot=True) == 2
    assert c.match(hs, need_snapshot=True, limit=1) == 1
    assert c.snapshot_at(hs[1]) == "snap1"
    assert c.attach(hs) == []                # nothing to pin
    c.flush()
    assert len(c) == 0


# ---------------------------------------------------------------------------
# Paged attention unit equivalence: one layer, paged vs contiguous
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block_size", [1, 4, 16])
def test_paged_attention_decode_matches_contiguous(block_size):
    """Slot-wise decode at staggered depths: the paged path (scatter
    through a shuffled block table + gather + crop) is bit-identical to
    the contiguous per-row cache."""
    cfg = small_test_config()
    max_len = 16
    b = 3
    key = jax.random.PRNGKey(0)
    p = attention.init_attention(key, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, 1, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    index = jnp.asarray([0, 5, 11], jnp.int32)
    positions = index[:, None]

    cache = attention.make_cache(cfg, b, max_len)
    # pre-populate with random history so the gathered reads matter
    hist = jax.random.normal(jax.random.PRNGKey(2),
                             cache["k"].shape).astype(jnp.bfloat16)
    cache = {"k": hist, "v": hist * 0.5}

    w = kv_pool.table_width(max_len, block_size)
    nb = b * w
    pool = attention.make_paged_cache(cfg, 1, nb + 1, block_size)
    # interleaved block assignment (slot i owns blocks i, i+b, ...) so a
    # row's logical positions are physically scattered
    table = np.zeros((b, w), np.int32)
    for i in range(b):
        table[i] = 1 + i + b * np.arange(w)
    # mirror the contiguous history into the pool through the table
    # (the pool folds the KV heads into its lanes)
    kf = np.zeros(pool["k_pool"].shape, np.float32)
    vf = np.zeros(pool["v_pool"].shape, np.float32)
    hist_np = np.asarray(hist, np.float32)
    for i in range(b):
        for t in range(max_len):
            blk, off = table[i][t // block_size], t % block_size
            kf[0, blk, off] = hist_np[i, t].reshape(-1)
            vf[0, blk, off] = hist_np[i, t].reshape(-1) * 0.5
    pool = {"k_pool": jnp.asarray(kf).astype(jnp.bfloat16),
            "v_pool": jnp.asarray(vf).astype(jnp.bfloat16)}

    out_c, cache_c = attention.attention(
        p, x, cfg, positions=positions, cache=cache, cache_index=index)
    out_p, cache_p = attention.attention(
        p, x, cfg, positions=positions, cache=pool, cache_index=index,
        block_table=jnp.asarray(table), kv_len=max_len, pool_layer=0)
    np.testing.assert_array_equal(np.asarray(out_c, np.float32),
                                  np.asarray(out_p, np.float32))

    # and the writes landed at the right (block, offset) translations
    kc = np.asarray(cache_c["k"], np.float32)
    kp = np.asarray(cache_p["k_pool"], np.float32)
    for i in range(b):
        t = int(index[i])
        blk, off = table[i][t // block_size], t % block_size
        np.testing.assert_array_equal(kc[i, t].reshape(-1), kp[0, blk, off])


@pytest.mark.parametrize("scan_layers", [True, False])
def test_paged_pools_carried_through_the_layer_loop(scan_layers):
    """A period with two attention positions (dense and MoE FFNs
    alternating: 2 groups of 2): each position's stacked pool rides
    whole through the layer loop, scanned or unrolled, and group g
    writes layer g of it.  Logits are bit-identical to the contiguous
    cache, and every written cell holds the contiguous cache's K/V at
    the same (layer, row, position)."""
    cfg = small_test_config(num_layers=4, moe_layer_period=2,
                            moe=MoEConfig(num_experts=4, top_k=2))
    b, s, max_len, bs = 2, 3, 16, 4
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                cfg.vocab_size)
    index = jnp.asarray([0, 5], jnp.int32)
    w = kv_pool.table_width(max_len, bs)
    table = 1 + np.arange(b * w, dtype=np.int32).reshape(b, w)
    paged = lm.init_paged_state(cfg, b, max_len, num_blocks=b * w,
                                block_size=bs)
    assert [kv_pool.is_paged_cache(st) for st in paged] == [True, True]
    logits_c, st_c, _ = lm.forward(
        params, tokens, cfg, states=lm.init_state(cfg, b, max_len),
        cache_index=index, scan_layers=scan_layers)
    logits_p, st_p, _ = lm.forward(
        params, tokens, cfg, states=paged, cache_index=index,
        block_table=jnp.asarray(table), kv_len=max_len,
        scan_layers=scan_layers)
    np.testing.assert_array_equal(np.asarray(logits_c, np.float32),
                                  np.asarray(logits_p, np.float32))
    for cont, pool in zip(st_c, st_p):
        assert pool["k_pool"].shape == (2, b * w + 1, bs, 2 * 16)
        for name in ("k", "v"):
            c = np.asarray(cont[name], np.float32)
            p = np.asarray(pool[f"{name}_pool"], np.float32)
            for g in range(2):
                for i in range(b):
                    for t in range(int(index[i]), int(index[i]) + s):
                        blk, off = table[i, t // bs], t % bs
                        np.testing.assert_array_equal(
                            c[g, i, t].reshape(-1), p[g, blk, off])
                        assert np.abs(p[g, blk, off]).sum() > 0


def test_paged_state_memory_footprint():
    """The paged tree's KV bytes follow the block count, not
    slots * max_len."""
    cfg = small_test_config()
    b, max_len, bs = 8, 64, 4
    contiguous = lm.init_state(cfg, b, max_len)
    w = kv_pool.table_width(max_len, bs)
    half = (b * w) // 2
    paged = lm.init_paged_state(cfg, b, max_len, num_blocks=half,
                                block_size=bs)
    cb = kv_pool.kv_cache_bytes(contiguous)
    pb = kv_pool.kv_cache_bytes(paged)
    assert cb > 0 and pb > 0
    # half the blocks (+1 trash) -> about half the bytes
    assert pb < 0.6 * cb


def test_trash_block_isolation():
    """Writes through an all-zero block table (retired/empty rows) land
    in the trash block and never alias a live block."""
    cfg = small_test_config()
    block_size, w = 4, 4
    pool = attention.make_paged_cache(cfg, 1, 6, block_size)
    p = attention.init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    # row 0 live (blocks 1..4), row 1 retired (all-zero table)
    table = jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32)
    index = jnp.asarray([6, 9], jnp.int32)
    _, cache = attention.attention(
        p, x, cfg, positions=index[:, None], cache=pool,
        cache_index=index, block_table=table, kv_len=16, pool_layer=0)
    kp = np.asarray(cache["k_pool"], np.float32)[0]
    # row 0's write: position 6 -> table column 1 -> block 2, offset 2
    assert np.abs(kp[2, 2]).sum() > 0
    # row 1's write went to trash block 0; block 5 untouched
    assert np.abs(kp[0]).sum() > 0
    assert np.abs(kp[5]).sum() == 0


# ---------------------------------------------------------------------------
# Slot state view/merge round trip (chunked prefill's splice helpers)
# ---------------------------------------------------------------------------

def test_slot_view_merge_roundtrip_recurrent():
    cfg = small_test_config(xlstm_slstm_every=2)
    states = lm.init_paged_state(cfg, 3, 32, num_blocks=4, block_size=8)
    # salt the rows so the roundtrip is observable
    states = jax.tree_util.tree_map(
        lambda l: l + jnp.arange(l.size, dtype=l.dtype).reshape(l.shape)
        if jnp.issubdtype(l.dtype, jnp.floating) else l, states)
    one = kv_pool.slot_states_view(cfg, states, jnp.int32(1))
    for st, st1 in zip(states, one):
        if kv_pool.is_paged_cache(st):
            continue
        jax.tree_util.tree_map(
            lambda f, o: np.testing.assert_array_equal(
                np.asarray(f[:, 1:2], np.float32),
                np.asarray(o, np.float32)), st, st1)
    bumped = jax.tree_util.tree_map(lambda l: l + 1.0, one)
    merged = kv_pool.slot_states_merge(cfg, states, bumped, jnp.int32(1))
    for st, stm in zip(states, merged):
        if kv_pool.is_paged_cache(st):
            continue
        jax.tree_util.tree_map(
            lambda f, m: (
                np.testing.assert_array_equal(
                    np.asarray(m[:, 1], np.float32),
                    np.asarray(f[:, 1] + 1.0, np.float32)),
                np.testing.assert_array_equal(          # other rows kept
                    np.asarray(m[:, 0], np.float32),
                    np.asarray(f[:, 0], np.float32))),
            st, stm)
