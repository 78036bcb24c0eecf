"""Oracle-equivalence suite for the continuous-batching scheduler.

The invariant under test: for ANY interleaved arrival trace, every
request's generated tokens from the slot-based scheduler are bit-identical
to running that request *alone* through ``ServeEngine.generate_loop``
(truncated at its EOS).  Property-tested via the hypothesis shim over
random prompt lengths, arrival orders, slot counts and EOS positions,
across state families (dense KV, xlstm) and execution modes
(bf16 / int8 / pum).
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config import PUMConfig, small_test_config
from repro.models import lm
from repro.serve import (ContinuousBatchingScheduler, InvalidRequest,
                         Request, RequestTooLarge, oracle_completion,
                         synthetic_workload)

FAMILIES = {
    "dense": dict(),
    "xlstm": dict(xlstm_slstm_every=2),     # stateful mLSTM/sLSTM stack
}

_SCHED_CACHE = {}


# the prefix-cache grid additionally covers the jamba-style hybrid
# stack; kept out of FAMILIES so the base grids stay the same size
ALL_FAMILIES = dict(FAMILIES, hybrid=dict(attn_period=2))


def _sched(family="dense", mode="bf16", num_slots=3, max_len=32,
           kv_block_size=0, num_kv_blocks=0, chunked_prefill=False,
           prefix_cache=False):
    """Schedulers are expensive to warm up (prefill compiles per prompt
    length); cache them per configuration across tests."""
    key = (family, mode, num_slots, max_len, kv_block_size, num_kv_blocks,
           chunked_prefill, prefix_cache)
    if key not in _SCHED_CACHE:
        cfg = small_test_config(**ALL_FAMILIES[family],
                                pum=PUMConfig(mode=mode))
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        _SCHED_CACHE[key] = ContinuousBatchingScheduler(
            cfg, params, num_slots=num_slots, max_len=max_len,
            kv_block_size=kv_block_size, num_kv_blocks=num_kv_blocks,
            chunked_prefill=chunked_prefill, prefix_cache=prefix_cache)
    return _SCHED_CACHE[key]


def _check_trace(sched, reqs):
    import dataclasses
    reqs = [dataclasses.replace(r, rid=i) if r.rid is None else r
            for i, r in enumerate(reqs)]
    out = sched.run(reqs)
    assert set(out) == {r.rid for r in reqs}
    for r in reqs:
        want = oracle_completion(sched.engine, r)
        got = out[r.rid].tokens
        assert got == want, (
            f"request {r.rid} (prompt_len={len(r.prompt)}, "
            f"temp={r.temperature}, eos={r.eos_id}, "
            f"arrival={r.arrival}): scheduler produced {got}, "
            f"solo oracle produced {want}")
    return out


# ---------------------------------------------------------------------------
# Deterministic traces across families x modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("mode", ["bf16", "int8", "pum"])
def test_scheduler_matches_oracle(family, mode):
    """Staggered arrivals, mixed greedy/sampled, more requests than
    slots — every request token-identical to its solo run."""
    sched = _sched(family, mode)
    v = sched.cfg.vocab_size
    reqs = [
        Request([1, 2, 3], max_tokens=6, temperature=0.0, seed=1),
        Request([4] * 6, max_tokens=4, temperature=0.8, seed=2, arrival=1),
        Request([5, 6], max_tokens=7, temperature=0.0, seed=3, arrival=1),
        Request([7, 8, 9, 10, 11], max_tokens=3, temperature=0.6, seed=4,
                arrival=3),
        Request([v - 1], max_tokens=5, temperature=0.0, seed=5, arrival=8),
    ]
    _check_trace(sched, reqs)


def test_scheduler_matches_oracle_hybrid_ssm():
    """Hybrid attention+Mamba stack (jamba-style): the ssm state family
    threads the per-slot decode too (recurrent state is per-row; only
    the attention layers consume the cache_index vector)."""
    cfg = small_test_config(attn_period=2)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    sched = ContinuousBatchingScheduler(cfg, params, num_slots=2,
                                        max_len=24)
    reqs = synthetic_workload(4, cfg.vocab_size, max_prompt=5, max_new=6,
                              mean_interarrival=1.0, eos_rate=0.4, seed=3)
    _check_trace(sched, reqs)


def test_scheduler_eos_frees_slot_for_queued_request():
    """A request stopped early by EOS hands its slot to the queue; both
    the early-stopped and the follow-on request match their oracles."""
    sched = _sched(num_slots=1)
    # find a greedy continuation token whose FIRST occurrence is
    # mid-stream, so the EOS stop actually triggers during decode
    probe = Request([3, 1, 4, 1, 5], max_tokens=6, temperature=0.0, seed=0)
    tokens = oracle_completion(sched.engine, probe)
    eos = next((t for t in tokens[1:-1] if t != tokens[0]), None)
    if eos is None:
        pytest.skip("greedy rollout is constant; no mid-stream stop")
    stop = tokens.index(eos)
    reqs = [
        Request([3, 1, 4, 1, 5], max_tokens=6, eos_id=eos, seed=0),
        Request([2, 7], max_tokens=5, temperature=0.9, seed=42),
    ]
    out = _check_trace(sched, reqs)
    assert out[0].finish_reason == "eos"
    assert out[0].tokens == tokens[:stop + 1]
    assert out[1].finish_reason == "length"
    # with one slot, request 1 decodes only after request 0 retired
    assert out[1].finished_step > out[0].finished_step


def test_scheduler_single_token_and_instant_eos_requests():
    """max_tokens=1 and EOS-at-prefill complete without occupying a
    decode slot, and still match the oracle."""
    sched = _sched(num_slots=2)
    probe = Request([9, 9, 9], max_tokens=1, temperature=0.0, seed=7)
    first = oracle_completion(sched.engine, probe)[0]
    reqs = [
        Request([9, 9, 9], max_tokens=1, temperature=0.0, seed=7),
        Request([9, 9, 9], max_tokens=8, eos_id=first, seed=7),
        Request([1, 2], max_tokens=4, temperature=0.5, seed=8),
    ]
    out = _check_trace(sched, reqs)
    assert out[0].tokens == [first] and out[0].finish_reason == "length"
    assert out[1].tokens == [first] and out[1].finish_reason == "eos"


def test_scheduler_determinism_across_runs():
    """The same trace served twice (warm scheduler, slots reused) yields
    identical outputs — slot recycling leaks no state."""
    sched = _sched(num_slots=2)
    reqs = synthetic_workload(5, sched.cfg.vocab_size, max_prompt=5,
                              max_new=6, mean_interarrival=1.0, seed=21)
    a = sched.run(reqs)
    b = sched.run(reqs)
    for rid in a:
        assert a[rid].tokens == b[rid].tokens


def test_scheduler_rejects_oversized_request():
    sched = _sched(num_slots=2, max_len=16)
    # typed (RequestTooLarge) but still a ValueError for legacy callers
    with pytest.raises(RequestTooLarge, match="max_len"):
        sched.run([Request(list(range(10)), max_tokens=10)])
    with pytest.raises(ValueError, match="max_len"):
        sched.run([Request(list(range(10)), max_tokens=10)])


def test_scheduler_serves_far_future_arrival():
    """The runaway guard counts decode work, not the simulated clock:
    a request arriving far in the future is still served (the clock
    jumps over the idle gap)."""
    sched = _sched(num_slots=2)
    req = Request([1, 2, 3], max_tokens=3, arrival=500_000)
    out = sched.run([req], max_steps=100)
    assert out[0].tokens == oracle_completion(sched.engine, req)
    assert out[0].admitted_step >= 500_000


def test_scheduler_rid_autoassignment_skips_explicit_rids():
    """Auto-assigned rids never collide with caller-chosen ones."""
    sched = _sched(num_slots=2)
    reqs = [Request([1, 2, 3], max_tokens=2),             # auto
            Request([4, 5], max_tokens=2, rid=0),         # explicit 0
            Request([6], max_tokens=2)]                   # auto
    out = sched.run(reqs)
    assert len(out) == 3 and 0 in out
    assert out[0].prompt == [4, 5]                        # explicit wins
    # true duplicates among explicit rids still rejected
    with pytest.raises(InvalidRequest, match="duplicate"):
        sched.run([Request([1], max_tokens=2, rid=5),
                   Request([2], max_tokens=2, rid=5)])


def test_scheduler_validates_whole_trace_before_admitting():
    """A bad request anywhere in the trace rejects the WHOLE trace up
    front — no slot is admitted, no work is stranded, and the scheduler
    serves the next trace cleanly."""
    sched = _sched(num_slots=2, max_len=16)
    good = Request([1, 2, 3], max_tokens=4, seed=1)
    bad = Request(list(range(10)), max_tokens=10, arrival=2)
    with pytest.raises(RequestTooLarge, match="max_len"):
        sched.run([good, bad])
    assert not sched._active.any()          # nothing admitted
    out = sched.run([good])                 # next trace is unaffected
    assert sorted(out) == [0]
    assert out[0].tokens == oracle_completion(sched.engine, good)


# ---------------------------------------------------------------------------
# Property tests: random traces (hypothesis shim — deterministic draws)
# ---------------------------------------------------------------------------

@given(seed=st.integers(0, 2**31 - 1),
       num_slots=st.sampled_from([1, 2, 3]),
       interarrival=st.sampled_from([0.0, 0.7, 2.0]))
@settings(max_examples=6, deadline=None)
def test_scheduler_oracle_equivalence_property(seed, num_slots,
                                               interarrival):
    """Random prompt lengths, arrival orders, slot counts, temperatures
    and EOS ids: every request equals its solo generate_loop run."""
    sched = _sched(num_slots=num_slots)
    reqs = synthetic_workload(6, sched.cfg.vocab_size, max_prompt=6,
                              max_new=7, mean_interarrival=interarrival,
                              eos_rate=0.4, seed=seed)
    _check_trace(sched, reqs)


@given(seed=st.integers(0, 2**31 - 1),
       family=st.sampled_from(sorted(FAMILIES)),
       mode=st.sampled_from(["bf16", "int8", "pum"]))
@settings(max_examples=4, deadline=None)
def test_scheduler_oracle_equivalence_property_families(seed, family,
                                                        mode):
    """The same property across the family x mode grid (fewer examples:
    each cell owns a separate compiled engine)."""
    sched = _sched(family, mode, num_slots=2)
    reqs = synthetic_workload(4, sched.cfg.vocab_size, max_prompt=5,
                              max_new=6, mean_interarrival=1.0,
                              eos_rate=0.4, seed=seed)
    _check_trace(sched, reqs)


# ---------------------------------------------------------------------------
# Paged KV cache + chunked prefill: the same oracle invariant must hold
# with the block-pool layout, any block size, and streamed prompts
# ---------------------------------------------------------------------------

def test_paged_scheduler_matches_oracle_dense_modes():
    """Paged KV + chunked prefill across execution modes, prompts both
    shorter and (much) longer than one block, staggered arrivals."""
    for mode in ["bf16", "int8", "pum"]:
        sched = _sched("dense", mode, num_slots=2, kv_block_size=4,
                       chunked_prefill=True)
        v = sched.cfg.vocab_size
        reqs = [
            Request([1, 2, 3], max_tokens=5, seed=1),
            Request([4] * 11, max_tokens=4, temperature=0.8, seed=2,
                    arrival=1),                      # 3 chunks: 4+4+3
            Request([5, 6, 7, 8, 9], max_tokens=6, seed=3, arrival=2),
            Request([v - 1], max_tokens=4, temperature=0.5, seed=4,
                    arrival=2),
        ]
        _check_trace(sched, reqs)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_paged_scheduler_chunked_prefill_families(family):
    """Chunked prefill across state families: dense pages its KV; the
    xlstm recurrences accumulate prompt state chunk-by-chunk (per-token
    scans, so chunk boundaries cannot move numerics)."""
    sched = _sched(family, num_slots=2, kv_block_size=4,
                   chunked_prefill=True)
    reqs = synthetic_workload(5, sched.cfg.vocab_size, max_prompt=10,
                              max_new=6, mean_interarrival=1.0,
                              eos_rate=0.4, seed=17)
    _check_trace(sched, reqs)


@pytest.mark.parametrize("block_size", [1, 4, 16])
def test_paged_scheduler_block_size_sweep(block_size):
    """Oracle equivalence for block sizes 1/4/16 with prompt lengths
    deliberately not multiples of the block size (ragged final chunks,
    including 1-token tails)."""
    sched = _sched(num_slots=2, kv_block_size=block_size,
                   chunked_prefill=True)
    reqs = [
        Request([7], max_tokens=5, seed=1),
        Request([1, 2, 3, 4, 5], max_tokens=6, temperature=0.7, seed=2),
        Request([9] * 7, max_tokens=4, seed=3, arrival=1),
        Request([3, 1, 4, 1, 5, 9, 2, 6, 5], max_tokens=5, seed=4,
                arrival=2),
    ]
    _check_trace(sched, reqs)


def test_paged_scheduler_hybrid_ssm_chunked():
    """Jamba-style attention+Mamba stack under paging: attention layers
    page through block tables, the Mamba conv window and SSM state
    thread the chunk boundary (the carried-conv fix in models/ssm)."""
    cfg = small_test_config(attn_period=2)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    sched = ContinuousBatchingScheduler(cfg, params, num_slots=2,
                                        max_len=32, kv_block_size=4,
                                        chunked_prefill=True)
    reqs = synthetic_workload(5, cfg.vocab_size, max_prompt=9, max_new=6,
                              mean_interarrival=1.0, eos_rate=0.4,
                              seed=11)
    _check_trace(sched, reqs)


def test_paged_scheduler_block_starvation_queues_requests():
    """A pool too small to co-host every request: admission waits for
    blocks (slots idle while the pool is full), yet every request still
    matches its oracle and all blocks drain back."""
    sched = _sched(num_slots=3, kv_block_size=4, num_kv_blocks=6,
                   chunked_prefill=True)
    reqs = [
        Request([1, 2, 3, 4, 5, 6, 7], max_tokens=6, seed=1),   # 3 blocks
        Request([8] * 9, max_tokens=6, seed=2),                 # 4 blocks
        Request([2, 7, 1], max_tokens=8, temperature=0.6, seed=3,
                arrival=1),                                     # 3 blocks
    ]
    _check_trace(sched, reqs)
    assert sched._alloc.live_blocks == 0
    assert sched._alloc.free_blocks == sched.num_kv_blocks
    assert not sched._block_table.any()


def test_paged_scheduler_reuses_slots_and_blocks_cleanly():
    """More requests than slots: retired slots/blocks are recycled and
    recycled state never leaks into later requests (fresh recurrent
    rows, trash-masked stale blocks)."""
    sched = _sched(num_slots=2, kv_block_size=4, chunked_prefill=True)
    reqs = synthetic_workload(7, sched.cfg.vocab_size, max_prompt=8,
                              max_new=6, mean_interarrival=0.5,
                              eos_rate=0.3, seed=23)
    a = _check_trace(sched, reqs)
    b = _check_trace(sched, reqs)          # re-entrant, warm
    for rid in a:
        assert a[rid].tokens == b[rid].tokens


def test_paged_scheduler_monolithic_prefill():
    """kv_block_size alone (no chunked prefill): prompts land in one
    batch-1 paged prefill call; same invariant."""
    sched = _sched(num_slots=2, kv_block_size=4)
    reqs = synthetic_workload(4, sched.cfg.vocab_size, max_prompt=8,
                              max_new=6, mean_interarrival=1.0,
                              eos_rate=0.4, seed=5)
    _check_trace(sched, reqs)


def test_paged_scheduler_rejects_request_exceeding_pool_capacity():
    """Admission raises (instead of silently truncating) when
    prompt_len + max_tokens cannot ever fit the pool — mirroring the
    decode-window overflow ValueError."""
    sched = _sched(num_slots=2, max_len=32, kv_block_size=4,
                   num_kv_blocks=3, chunked_prefill=True)
    good = Request([1, 2, 3], max_tokens=4, seed=1)
    bad = Request(list(range(8)), max_tokens=8, arrival=1)   # needs 4 > 3
    with pytest.raises(RequestTooLarge, match="pool capacity"):
        sched.run([good, bad])
    # whole-trace validation: nothing was admitted, next trace clean
    assert not sched._active.any() and not sched._prefills
    assert sched._alloc.live_blocks == 0
    out = sched.run([good])
    assert out[0].tokens == oracle_completion(sched.engine, good)


def test_chunked_prefill_requires_paged_pool():
    cfg = small_test_config()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="kv_block_size"):
        ContinuousBatchingScheduler(cfg, params, chunked_prefill=True)


@given(seed=st.integers(0, 2**31 - 1),
       block_size=st.sampled_from([1, 4, 16]),
       chunked=st.sampled_from([False, True]))
@settings(max_examples=5, deadline=None)
def test_paged_scheduler_oracle_equivalence_property(seed, block_size,
                                                     chunked):
    """Random traces over the paged layout: block sizes 1/4/16, chunked
    and monolithic prefill, random prompt lengths (ragged vs the block
    size), arrivals, temperatures and EOS ids."""
    sched = _sched(num_slots=2, kv_block_size=block_size,
                   chunked_prefill=chunked)
    reqs = synthetic_workload(5, sched.cfg.vocab_size, max_prompt=9,
                              max_new=6, mean_interarrival=0.7,
                              eos_rate=0.4, seed=seed)
    _check_trace(sched, reqs)


# ---------------------------------------------------------------------------
# Prefix caching: sharing ON must be bit-identical to sharing OFF and to
# the solo oracle, and the pool must stay leak-free (every live block is
# either a slot's private block or a cache-owned shared block)
# ---------------------------------------------------------------------------

def _assert_prefix_clean(sched):
    """After a drain, the only live blocks are the prefix cache's."""
    assert sched._alloc.live_blocks == sched.prefix_cached_blocks
    stats = sched.prefix_stats()
    assert stats["cached_blocks"] == sched.prefix_cached_blocks


@pytest.mark.parametrize("family", sorted(ALL_FAMILIES))
@pytest.mark.parametrize("mode", ["bf16", "int8", "pum"])
def test_prefix_cache_matches_oracle_families_modes(family, mode):
    """The full family x mode grid with shared-prefix traffic: cached
    prefixes attach read-only (dense KV) or restore from snapshots
    (recurrent rows), and every completion still equals its solo run —
    including a warm re-serve where every prefix hits."""
    sched = _sched(family, mode, num_slots=2, kv_block_size=4,
                   chunked_prefill=True, prefix_cache=True)
    reqs = synthetic_workload(5, sched.cfg.vocab_size, max_prompt=10,
                              max_new=6, mean_interarrival=1.0,
                              eos_rate=0.3, shared_prefix_len=8, seed=29)
    _check_trace(sched, reqs)
    _check_trace(sched, reqs)          # warm cache: hits, same tokens
    assert sched.prefix_stats()["hits"] > 0
    _assert_prefix_clean(sched)


def test_prefix_cache_on_equals_off_and_oracle():
    """Three-way: sharing on == sharing off == solo oracle on the same
    shared-prefix trace (the off scheduler is the cached plain paged
    one, so this is a genuine independent run)."""
    on = _sched(num_slots=2, kv_block_size=4, chunked_prefill=True,
                prefix_cache=True)
    off = _sched(num_slots=2, kv_block_size=4, chunked_prefill=True)
    reqs = synthetic_workload(6, on.cfg.vocab_size, max_prompt=9,
                              max_new=6, mean_interarrival=0.7,
                              eos_rate=0.4, shared_prefix_len=6, seed=31)
    a = _check_trace(on, reqs)         # == oracle
    b = _check_trace(off, reqs)        # == oracle, sharing disabled
    for rid in a:
        assert a[rid].tokens == b[rid].tokens
    assert on.prefix_stats()["tokens_skipped"] > 0
    assert all(v == 0 for v in off.prefix_stats().values())  # off: zeros
    _assert_prefix_clean(on)


@pytest.mark.parametrize("block_size", [1, 4, 16])
def test_prefix_cache_cow_full_prompt_repeats(block_size):
    """Identical prompts re-served: with the ENTIRE prompt cached the
    scheduler re-runs only the final position after copy-on-writing the
    last block into a private copy — across block sizes whose final
    block is exactly full (the COW-eligible shape)."""
    sched = _sched(num_slots=2, kv_block_size=block_size,
                   chunked_prefill=True, prefix_cache=True)
    plen = 16                          # full blocks at bs 1, 4 and 16
    prompt = [(i * 7 + 3) % sched.cfg.vocab_size for i in range(plen)]
    _check_trace(sched, [Request(prompt, max_tokens=5, seed=9, rid=0)])
    base = sched.prefix_stats()
    # the repeat (same prompt, different sampling) must COW, not mutate
    # the shared block the first request registered
    reqs = [Request(prompt, max_tokens=5, seed=9, rid=0),
            Request(prompt, max_tokens=4, temperature=0.6, seed=10,
                    rid=1, arrival=1)]
    _check_trace(sched, reqs)
    stats = sched.prefix_stats()
    assert stats["hits"] > base["hits"]
    assert stats["tokens_skipped"] >= base["tokens_skipped"] + plen - 1
    _assert_prefix_clean(sched)
    sched.flush_prefix_cache()         # leak-freedom: cache owns it all
    assert sched._alloc.live_blocks == 0
    assert sched.prefix_cached_blocks == 0


def test_prefix_cache_cancellation_mid_decode_leaks_nothing():
    """Cancelling a request that is decoding against attached shared
    blocks releases only its references: the survivor sharing the same
    prefix still matches its oracle and the pool partitions cleanly."""
    sched = _sched(num_slots=2, kv_block_size=4, chunked_prefill=True,
                   prefix_cache=True)
    shared = [3, 1, 4, 1, 5, 9, 2, 6]              # two full blocks
    r0 = Request(shared + [5], max_tokens=12, seed=41, rid=0)
    r1 = Request(shared + [8, 9], max_tokens=12, seed=42, rid=1)
    assert sched.start_request(r0, 0) is None
    for step in range(4):
        sched.tick(step)
    assert sched.start_request(r1, 4) is None      # attaches r0's prefix
    assert sched.prefix_stats()["hits"] >= 1
    for step in range(4, 8):
        sched.tick(step)
    comp0 = sched.cancel(0, 8, reason="cancelled")
    want0 = oracle_completion(sched.engine, r0)
    assert comp0.truncated and comp0.tokens == want0[:len(comp0.tokens)]
    assert len(comp0.tokens) > 0
    out = sched.drain(9)                           # r1 still mid-decode
    want1 = oracle_completion(sched.engine, r1)
    assert out[1].tokens == want1[:len(out[1].tokens)]
    assert len(out[1].tokens) > 0
    _assert_prefix_clean(sched)
    sched.flush_prefix_cache()
    assert sched._alloc.live_blocks == 0


def _aligned(a):
    """A copy of ``a`` at a 64-byte-aligned address."""
    buf = np.zeros(a.nbytes + 64, np.uint8)
    off = -buf.ctypes.data % 64
    out = buf[off:off + a.nbytes].view(a.dtype).reshape(a.shape)
    out[...] = a
    return out


def test_chunk_inputs_are_snapshots_of_the_host_tables(monkeypatch):
    """A chunk dispatch uploads copies of its slot's table rows: the host
    rewrites them before the chunk has run (prefix registration widens
    ``shared_cols`` right after a prompt's last chunk is dispatched),
    and on the CPU an upload of the host array itself aliases it."""
    sched = _sched(num_slots=1, kv_block_size=4, chunked_prefill=True,
                   prefix_cache=True)
    uploads = []
    chunk = sched._chunk_prefill

    def spy(params, states, tokens, start, table_row, slot, shared):
        uploads.append((table_row, shared))
        return chunk(params, states, tokens, start, table_row, slot, shared)

    monkeypatch.setattr(sched, "_chunk_prefill", spy)
    assert sched.start_request(Request(list(range(1, 9)), max_tokens=3,
                                       seed=3, rid=0), 0) is None
    # 64-byte-aligned host tables, which the CPU backend uploads without
    # a copy
    sched._block_table = _aligned(sched._block_table)
    sched._shared_cols = _aligned(sched._shared_cols)
    sched.tick(0)
    table, shared = (np.array(x) for x in uploads[0])
    saved = sched._block_table.copy(), sched._shared_cols.copy()
    sched._block_table += 1
    sched._shared_cols += 1
    try:
        assert (np.array(uploads[0][0]) == table).all()
        assert (np.array(uploads[0][1]) == shared).all()
    finally:
        sched._block_table[:] = saved[0]
        sched._shared_cols[:] = saved[1]
    sched.drain(1)
    _assert_prefix_clean(sched)


def test_prefix_cache_requires_paged_pool():
    cfg = small_test_config()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="prefix_cache"):
        ContinuousBatchingScheduler(cfg, params, prefix_cache=True)


# ---------------------------------------------------------------------------
# EOS-position sweep: force stops at every possible decode step
# ---------------------------------------------------------------------------

def test_scheduler_eos_at_every_position():
    """Pin the EOS to each successive token of a known greedy rollout —
    the scheduler must stop exactly there, every time, while co-batched
    with another live request."""
    sched = _sched(num_slots=2)
    base = Request([6, 2, 8], max_tokens=6, temperature=0.0, seed=13)
    rollout = oracle_completion(sched.engine, base)
    for _pos, eos in enumerate(rollout):
        reqs = [
            Request([6, 2, 8], max_tokens=6, eos_id=int(eos), seed=13),
            Request([5, 5, 5, 5], max_tokens=6, temperature=0.7, seed=99),
        ]
        out = _check_trace(sched, reqs)
        stop = rollout.index(int(eos))        # first occurrence wins
        assert out[0].tokens == rollout[:stop + 1]
        assert out[0].finish_reason == "eos"


# ---------------------------------------------------------------------------
# synthetic workload: Poisson arrival mode (shared by benches + chaos)
# ---------------------------------------------------------------------------

def test_synthetic_workload_poisson_mode():
    """``poisson_rate`` stamps float wall-clock arrivals (monotone, with
    an integer-step shadow) plus front-end metadata, deterministically
    per seed — and the same trace still serves through ``run``."""
    reqs = synthetic_workload(12, 50, max_prompt=6, max_new=5,
                              poisson_rate=40.0, priority_choices=(0, 1, 2),
                              deadline_ms=250.0, seed=11)
    times = [r.arrival_time for r in reqs]
    assert all(t is not None and t > 0.0 for t in times)
    assert times == sorted(times)                  # arrivals never reorder
    for r in reqs:
        assert r.arrival == int(r.arrival_time)    # integer-step shadow
        assert r.priority in (0, 1, 2)
        assert r.deadline_ms == 250.0
    # seeded: the whole trace (prompts, seeds, arrivals) replays exactly
    again = synthetic_workload(12, 50, max_prompt=6, max_new=5,
                               poisson_rate=40.0, priority_choices=(0, 1, 2),
                               deadline_ms=250.0, seed=11)
    assert reqs == again
    assert synthetic_workload(12, 50, poisson_rate=40.0, seed=12) != reqs
    # legacy mode keeps arrival_time unset (run()'s simulated clock only)
    legacy = synthetic_workload(4, 50, mean_interarrival=1.0, seed=11)
    assert all(r.arrival_time is None for r in legacy)
    # the Poisson trace drives the step-clock scheduler unchanged
    sched = _sched(num_slots=2)
    reqs = synthetic_workload(4, sched.cfg.vocab_size, max_prompt=5,
                              max_new=4, poisson_rate=3.0, seed=5)
    _check_trace(sched, reqs)
