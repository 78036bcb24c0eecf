"""Paged KV-cache pool: fixed-size token blocks over one shared store.

DARTH-PUM treats the memory arrays as a pooled compute+storage resource
the coordinator allocates per kernel (PUMA's tile-granular allocation);
the serving analogue is the KV cache.  The contiguous layout reserves a
whole ``[max_len]`` window per decode slot, so one long request strands
``slots * max_len`` worth of storage however short its co-tenants are.
Here the cache is a single pool of ``num_blocks`` fixed-size token
blocks (``[n_groups, num_blocks, block_size, kv_heads * head_dim]`` per
attention period-position: the heads folded into the lanes) and each
request owns just the blocks its tokens actually touch, mapped through
a per-slot *block table*.

Layout conventions
------------------
* Physical block 0 is the **trash block**: rows whose slot is empty or
  retired carry an all-zero block table, so their masked decode writes
  land there instead of corrupting live data.  :class:`BlockAllocator`
  therefore hands out ids ``1 .. num_blocks`` over a pool allocated
  with ``num_blocks + 1`` physical blocks.
* A request admitted with ``prompt_len`` and ``max_tokens`` owns
  ``blocks_needed(prompt_len, max_tokens, block_size)`` blocks for its
  whole lifetime (positions ``0 .. prompt_len + max_tokens - 2``; the
  final sampled token is never written back).  Allocation is up-front,
  so a request never runs out of blocks mid-decode.
* The block table is host state (a small ``[slots, table_width]`` int32
  array shipped with every step); the pools live inside the donated
  decode-state tree, so per-token writes are in-place scatters.

Why gathers stay bit-exact: the gathered per-row view is sliced back to
the engine's ``max_len`` (``kv_len`` in ``models.attention``), so the
attention reduction shapes — and therefore the compiled reduction order
— match the contiguous cache exactly; masked lanes contribute exact
zeros either way.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict, deque
from collections.abc import Sequence
from typing import Any

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import transformer
from repro.models.attention import is_paged_cache, paged_write_cells
from repro.serve.errors import BlockNotLive, BlockOutOfRange

TRASH_BLOCK = 0


def blocks_needed(prompt_len: int, max_tokens: int, block_size: int) -> int:
    """Blocks a request owns for its lifetime.

    KV is written for every prompt token and for every *fed-back*
    generated token; the last of ``max_tokens`` sampled tokens is never
    fed back, so the deepest written position is
    ``prompt_len + max_tokens - 2``.
    """
    positions = prompt_len + max_tokens - 1
    return -(-positions // block_size)


def table_width(max_len: int, block_size: int) -> int:
    """Block-table columns needed to address ``max_len`` positions."""
    return -(-max_len // block_size)


class BlockAllocator:
    """Host-side refcounted free-list allocator over block ids
    ``first_id .. first_id + num_blocks - 1`` (id 0 stays reserved for
    the trash block under the default ``first_id=1``).

    FIFO reuse keeps allocation order deterministic for a given
    admit/retire trace.  ``alloc`` is all-or-nothing: a request that
    does not fit leaves the free list untouched (the scheduler keeps it
    queued rather than admitting it half-funded).

    Prefix caching shares blocks between requests, so ownership is a
    *refcount*: ``alloc`` hands out blocks at refcount 1, ``acquire``
    takes an extra reference on an already-live block (a cache hit
    attaching a shared prefix, or the prefix index pinning a block it
    just registered), and ``release`` drops one — a block returns to
    the free list only when its last reference goes.  Misuse raises
    typed errors (:class:`~repro.serve.errors.BlockOutOfRange` for ids
    the pool never owned — the trash block included —
    :class:`~repro.serve.errors.BlockNotLive` for double-frees), both
    ``ValueError``-compatible.
    """

    def __init__(self, num_blocks: int, first_id: int = TRASH_BLOCK + 1):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.num_blocks = num_blocks
        self.first_id = first_id
        self._free = deque(range(first_id, first_id + num_blocks))
        self._ref: dict[int, int] = {}     # live block id -> refcount >= 1

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def live_blocks(self) -> int:
        return len(self._ref)

    def refcount(self, block: int) -> int:
        """Live references on ``block`` (0 = free)."""
        self._check_range(block)
        return self._ref.get(block, 0)

    def _check_range(self, block: int) -> None:
        if not (self.first_id <= block < self.first_id + self.num_blocks):
            raise BlockOutOfRange(
                f"block {block} is not a pool block id (valid range "
                f"{self.first_id}..{self.first_id + self.num_blocks - 1}; "
                f"id {TRASH_BLOCK} is the reserved trash block)")

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        """Claim ``n`` blocks at refcount 1, or return None (not
        partial) if the pool cannot fund the request right now."""
        if n < 0:
            raise ValueError(f"cannot alloc {n} blocks")
        if n > len(self._free):
            return None
        ids = [self._free.popleft() for _ in range(n)]
        for i in ids:
            self._ref[i] = 1
        return ids

    def acquire(self, ids: Sequence[int]) -> None:
        """Take one extra reference on each (already live) block."""
        for i in ids:
            self._check_range(i)
            if i not in self._ref:
                raise BlockNotLive(
                    f"acquiring block {i} that is not live")
        for i in ids:
            self._ref[i] += 1

    def release(self, ids: Sequence[int]) -> None:
        """Drop one reference per block; the last reference returns the
        block to the free list (FIFO, deterministic reuse order)."""
        for i in ids:
            self._check_range(i)
            if i not in self._ref:
                raise BlockNotLive(
                    f"releasing block {i} that is not live (double-free "
                    f"or foreign id)")
            self._ref[i] -= 1
            if self._ref[i] == 0:
                del self._ref[i]
                self._free.append(i)

    def free(self, ids: Sequence[int]) -> None:
        """Alias of :meth:`release` kept for pre-refcount call sites."""
        self.release(ids)


# ---------------------------------------------------------------------------
# Block-granular prefix caching
# ---------------------------------------------------------------------------

def prefix_chain_hashes(tokens: Sequence[int], block_size: int,
                        root: str = "") -> list[str]:
    """Chain content hashes of every FULL ``block_size``-token prefix
    chunk of ``tokens``: ``h_i = H(h_{i-1}, tokens[i*bs:(i+1)*bs])``
    rooted at ``H(root)``.

    Chaining makes ``h_i`` identify the whole prefix ``tokens[:(i+1) *
    bs]``, not just chunk ``i`` — two prompts share cache entry ``i``
    iff their first ``(i+1)*bs`` tokens are identical.  ``root`` folds
    in model/config identity so entries can never match across engines
    with different numerics."""
    h = hashlib.sha256(root.encode()).hexdigest()
    out = []
    for i in range(len(tokens) // block_size):
        chunk = tokens[i * block_size:(i + 1) * block_size]
        h = hashlib.sha256(
            (h + ":" + ",".join(str(int(t)) for t in chunk)).encode()
        ).hexdigest()
        out.append(h)
    return out


@dataclasses.dataclass
class _PrefixEntry:
    """One cached full prompt-prefix block.

    ``block`` is the physical pool block holding its K/V (None for
    pure-recurrent stacks, which cache only the resume snapshot);
    ``snapshot`` is the per-slot recurrent-state rows *after* consuming
    the prefix this entry identifies (None when the family has no
    recurrent state, or when the registering prefill's chunk boundaries
    never landed on this block edge)."""
    block: int | None
    snapshot: Any = None


class PrefixCache:
    """Bounded content-addressed index of full prompt-prefix blocks.

    Entries are keyed by :func:`prefix_chain_hashes` digests and kept in
    LRU order (an ``OrderedDict`` touched on every hit).  The cache owns
    one allocator reference per block-bearing entry, so a cached block
    stays live after its registering request retires; eviction —
    LRU-first, only entries whose block has no *other* reference —
    releases that reference and the block returns to the free list.
    Capacity is counted in entries, so pure-recurrent snapshot entries
    are bounded too.
    """

    def __init__(self, alloc: BlockAllocator, block_size: int,
                 capacity: int, root: str = ""):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.alloc = alloc
        self.block_size = block_size
        self.capacity = capacity
        self.root = root
        self._entries: OrderedDict[str, _PrefixEntry] = OrderedDict()
        self.hits = 0               # admissions that attached >= 1 block
        self.tokens_skipped = 0     # prompt tokens whose prefill was skipped
        self.blocks_shared = 0      # shared block attachments (lifetime)

    def hashes(self, tokens: Sequence[int]) -> list[str]:
        return prefix_chain_hashes(tokens, self.block_size, self.root)

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, h: str) -> bool:
        return h in self._entries

    @property
    def cached_blocks(self) -> int:
        """Pool blocks currently pinned by the cache (one ref each)."""
        return sum(1 for e in self._entries.values()
                   if e.block is not None)

    @property
    def evictable_blocks(self) -> int:
        """Cached blocks only the cache still references — the pool
        capacity admission could reclaim on demand."""
        return self.evictable_margin()

    def evictable_margin(self, exclude: Sequence[str] = ()) -> int:
        """Evictable blocks outside ``exclude`` — admission passes the
        hashes it is about to attach, so the funding estimate never
        counts a block as both attachable and reclaimable."""
        ex = set(exclude)
        return sum(1 for h, e in self._entries.items()
                   if h not in ex and e.block is not None
                   and self.alloc.refcount(e.block) == 1)

    def _usable(self, h: str, need_snapshot: bool) -> bool:
        e = self._entries.get(h)
        if e is None:
            return False
        return not (need_snapshot and e.snapshot is None)

    def match(self, hashes: Sequence[str], *, need_snapshot: bool = False,
              limit: int | None = None) -> int:
        """Longest usable cached prefix, in blocks.  Pure peek: no
        refcounts move, no LRU touch.  ``limit`` caps the match length
        (recurrent stacks cannot resume past ``(prompt_len - 1) //
        block_size`` — at least one tail token must run for first-token
        logits, and KV-free rows have no copy-on-write escape).  With
        ``need_snapshot`` the match ends at the deepest entry carrying a
        recurrent-state snapshot (the resume point must restore one)."""
        n = 0
        for h in hashes:
            if h not in self._entries:
                break
            n += 1
        if limit is not None:
            n = min(n, limit)
        if need_snapshot:
            while n > 0 and self._entries[hashes[n - 1]].snapshot is None:
                n -= 1
        return n

    def attach(self, hashes: Sequence[str]) -> list[int]:
        """Take a reference on every block of the matched prefix
        ``hashes`` (all must be cached) and return the block ids in
        prefix order.  LRU-touches the entries."""
        blocks = []
        for h in hashes:
            e = self._entries[h]
            self._entries.move_to_end(h)
            if e.block is not None:
                blocks.append(e.block)
        self.alloc.acquire(blocks)
        return blocks

    def snapshot_at(self, h: str) -> Any:
        return self._entries[h].snapshot

    def register(self, hashes: Sequence[str],
                 blocks: Sequence[int | None],
                 snapshots: dict[int, Any] | None = None) -> int:
        """Insert the prefix blocks of a completed prefill.

        ``blocks[i]`` is the physical block holding chunk ``i`` (None
        for pure-recurrent stacks); ``snapshots`` maps chunk index ->
        recurrent rows after consuming ``(i+1)*block_size`` tokens.
        Already-cached hashes are deduped (the existing entry wins —
        the registering request's identical private copy simply retires
        with the request).  Each newly inserted block takes one cache
        reference.  Returns entries inserted."""
        snapshots = snapshots or {}
        inserted = 0
        for i, h in enumerate(hashes):
            if h in self._entries:
                self._entries.move_to_end(h)
                continue
            if len(self._entries) >= self.capacity \
                    and self._evict_lru(1) == 0:
                break              # full of in-use entries; stop inserting
            blk = blocks[i]
            if blk is not None:
                self.alloc.acquire([blk])
            self._entries[h] = _PrefixEntry(blk, snapshots.get(i))
            inserted += 1
        return inserted

    def _evict_lru(self, n_entries: int) -> int:
        """Drop up to ``n_entries`` LRU entries whose block is not in
        use elsewhere; returns entries evicted."""
        victims = []
        for h, e in self._entries.items():
            if e.block is None or self.alloc.refcount(e.block) == 1:
                victims.append(h)
                if len(victims) == n_entries:
                    break
        for h in victims:
            e = self._entries.pop(h)
            if e.block is not None:
                self.alloc.release([e.block])
        return len(victims)

    def evict_blocks(self, n_blocks: int,
                     exclude: Sequence[str] = ()) -> int:
        """Release at least ``n_blocks`` cached blocks back to the free
        list if possible (LRU-first, in-use blocks skipped); returns
        blocks actually freed.  Admission calls this when the free list
        alone cannot fund a request the evictable margin could —
        ``exclude`` protects the entries it is about to attach."""
        ex = set(exclude)
        freed = 0
        while freed < n_blocks:
            before = self.alloc.free_blocks
            # evict entries one at a time until a block-bearing one goes
            progressed = False
            for h, e in list(self._entries.items()):
                if h not in ex and e.block is not None \
                        and self.alloc.refcount(e.block) == 1:
                    self._entries.pop(h)
                    self.alloc.release([e.block])
                    progressed = True
                    break
            if not progressed:
                break
            freed += self.alloc.free_blocks - before
        return freed

    def flush(self) -> int:
        """Evict every entry not pinned by a live request; returns
        blocks released.  (Leak-freedom checks call this: after a full
        drain + flush the allocator must be back to zero live blocks.)"""
        freed = self.evict_blocks(self.cached_blocks)
        # snapshot-only / blockless entries go too
        for h, e in list(self._entries.items()):
            if e.block is None:
                del self._entries[h]
        return freed


def _mask_shared_cols(block_table: jax.Array,
                      shared_cols: jax.Array) -> jax.Array:
    """Route writes addressed through a slot's leading ``shared_cols``
    table columns to the trash block.

    Shared prefix blocks are attached *read-only*: gathers go through
    the real ``block_table``, but the write path uses this masked copy,
    so no scatter can ever land in a block another request (or the
    prefix index) also references — whatever ``cache_index`` claims.
    Lives inside the jitted steps so the auditor's shared-read-only
    rule can statically see every pool-write's indices depend on the
    shared-column count.
    """
    with jax.named_scope("mask_shared"):
        cols = jnp.arange(block_table.shape[1], dtype=shared_cols.dtype)
        return jnp.where(cols[None, :] < shared_cols[:, None],
                         jnp.asarray(TRASH_BLOCK, block_table.dtype),
                         block_table)


# ---------------------------------------------------------------------------
# State-tree helpers: paged pools are shared (no slot axis); recurrent
# states keep their per-slot rows
# ---------------------------------------------------------------------------

def slot_states_view(cfg: ModelConfig, states: list[Any],
                     slot: jax.Array) -> list[Any]:
    """A batch-1 view of ``slot`` for chunked prefill: recurrent leaves
    (axis 1 = slots under the group stacking) are sliced to one row;
    shared paged pools pass through whole."""
    out = []
    for st in states:
        if is_paged_cache(st) or not st:
            out.append(st)
        else:
            out.append(jax.tree_util.tree_map(
                lambda l: jax.lax.dynamic_slice_in_dim(l, slot, 1, axis=1),
                st))
    return out


def slot_states_merge(cfg: ModelConfig, states: list[Any], one: list[Any],
                      slot: jax.Array) -> list[Any]:
    """Inverse of :func:`slot_states_view`: write the updated batch-1
    recurrent rows back at ``slot``; adopt the updated pools whole."""
    out = []
    for st, st1 in zip(states, one):
        if is_paged_cache(st) or not st:
            out.append(st1)
        else:
            out.append(jax.tree_util.tree_map(
                lambda f, o: jax.lax.dynamic_update_slice_in_dim(
                    f, o.astype(f.dtype), slot, axis=1),
                st, st1))
    return out


def reset_slot_recurrent(cfg: ModelConfig, states: list[Any],
                         slot: jax.Array, max_len: int) -> list[Any]:
    """Return ``states`` with slot ``slot``'s recurrent rows restored to
    their init values (paged pools pass through: stale blocks are
    handled by allocation + masking).

    Chunked prefill accumulates prompt state *in place* in the slot's
    rows, so admission into a reused slot must start from the same fresh
    state a solo prefill initialises — the retired occupant's final
    state must not leak in.
    """
    out = []
    for j, st in enumerate(states):
        if is_paged_cache(st) or not st:
            out.append(st)
            continue
        one = transformer.make_block_state(cfg, j, 1, max_len)
        n_groups = st[next(iter(st))].shape[0]
        fresh = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (n_groups,) + a.shape), one)
        out.append(jax.tree_util.tree_map(
            lambda f, o: jax.lax.dynamic_update_slice_in_dim(
                f, o.astype(f.dtype), slot, axis=1),
            st, fresh))
    return out


def freeze_inactive_rows(states_old: list[Any], states_new: list[Any],
                         active: jax.Array) -> list[Any]:
    """Keep recurrent-state rows of inactive slots at their pre-step
    values (leaves are [n_groups, B, ...]; ``active`` is [B] bool).

    The slot-wise decode step runs every row — including slots whose
    prompt is still streaming in chunk-by-chunk — and recurrent states
    update unconditionally.  Paged pools need no masking (inactive rows
    write to the trash block via their zeroed block table), but a
    recurrent row mutated between prefill chunks would corrupt the
    prompt state the chunks are accumulating.
    """
    out = []
    with jax.named_scope("freeze_inactive"):
        for st_old, st_new in zip(states_old, states_new):
            if is_paged_cache(st_old) or not st_old:
                out.append(st_new)
            else:
                out.append(jax.tree_util.tree_map(
                    lambda o, n: jnp.where(
                        active.reshape((1, -1) + (1,) * (n.ndim - 2)), n, o),
                    st_old, st_new))
    return out


def _every_layer(pool: jax.Array, phys: jax.Array, off: jax.Array
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Index arrays addressing cells ``(phys, off)`` ([B, S]) in every
    layer of a stacked ``[G, NB, bs, lanes]`` pool: a gather or scatter
    whose only window is the lane axis, which leaves the pool in the
    layout the layer loop carries it in (a ``pool[:, phys, off]``
    window spanning the layers makes XLA relayout the whole pool)."""
    layers = jnp.arange(pool.shape[0], dtype=phys.dtype)[:, None, None]
    return layers, phys[None], off[None]


def spec_save_cells(states: list[Any], write_table: jax.Array,
                    cache_index: jax.Array, s: int) -> list[Any]:
    """Gather the pool cells a speculative verify step is about to
    overwrite (each row's next ``s`` positions through ``write_table``).

    Returns one entry per layer group: ``None`` for recurrent groups, a
    ``{"k_pool", "v_pool"}`` dict of [n_groups, B, S, KV * hd] gathered
    values for paged ones.  Together with :func:`spec_restore_cells`
    this makes draft writes transactional: after restore, the pool is
    bit-identical to one that only ever saw the accepted tokens."""
    saved = []
    for st in states:
        if not is_paged_cache(st):
            saved.append(None)
            continue
        phys, off = paged_write_cells(write_table, cache_index, s,
                                      st["k_pool"].shape[2])
        cells = _every_layer(st["k_pool"], phys, off)
        saved.append({name: st[name][cells]
                      for name in ("k_pool", "v_pool")})
    return saved


def spec_restore_cells(states: list[Any], saved: list[Any],
                       write_table: jax.Array, cache_index: jax.Array,
                       s: int, advance: jax.Array) -> list[Any]:
    """Roll back the rejected suffix of a speculative verify step's pool
    writes: of each row's ``s`` probed cells, the first ``advance[b]``
    are committed (kept), the rest get their :func:`spec_save_cells`
    values scattered back.  Committed cells re-route their (redundant)
    restore scatter to the trash block, exactly like inactive rows."""
    out = []
    rel = jnp.arange(s, dtype=jnp.int32)[None, :]
    for st, sv in zip(states, saved):
        if sv is None:
            out.append(st)
            continue
        phys, off = paged_write_cells(write_table, cache_index, s,
                                      st["k_pool"].shape[2])
        committed = rel < advance[:, None]
        rphys = jnp.where(committed,
                          jnp.asarray(TRASH_BLOCK, phys.dtype), phys)
        cells = _every_layer(st["k_pool"], rphys, off)
        st = dict(st)
        with jax.named_scope("spec_restore"):
            for name in ("k_pool", "v_pool"):
                st[name] = st[name].at[cells].set(sv[name])
        out.append(st)
    return out


def spec_select_recurrent(states_old: list[Any], states_new: list[Any],
                          advance: jax.Array,
                          active: jax.Array) -> list[Any]:
    """Collapse a verify step's per-position recurrent states to each
    row's accepted depth.

    ``states_new`` recurrent leaves come from a ``collect_states``
    forward: [n_groups, B, S, ...] with the state *after* consuming
    position ``j`` at index j.  A row advancing by ``advance[b]`` tokens
    has consumed positions 0..advance-1, so it adopts index
    ``advance - 1``; inactive rows (advance 0) keep their pre-step
    values, like :func:`freeze_inactive_rows`.  Paged pools pass
    through (:func:`spec_restore_cells` owns their rollback)."""
    idx = jnp.clip(advance - 1, 0, None).astype(jnp.int32)
    out = []
    with jax.named_scope("spec_select_state"):
        for st_old, st_new in zip(states_old, states_new):
            if is_paged_cache(st_old) or not st_old:
                out.append(st_new)
                continue

            def sel(o, n):
                ix = idx.reshape((1, -1, 1) + (1,) * (n.ndim - 3))
                picked = jnp.take_along_axis(
                    n, jnp.broadcast_to(ix, n.shape[:2] + (1,)
                                        + n.shape[3:]), axis=2)[:, :, 0]
                act = active.reshape((1, -1) + (1,) * (o.ndim - 2))
                return jnp.where(act, picked.astype(o.dtype), o)

            out.append(jax.tree_util.tree_map(sel, st_old, st_new))
    return out


def snapshot_slot_recurrent(states: list[Any], slot: jax.Array,
                            ) -> list[Any]:
    """Copy slot ``slot``'s recurrent rows out of the shared tree (paged
    pools are skipped — a snapshot is O(d) per layer, not O(pool)).

    Prefix caching stores these at block boundaries during prefill:
    restoring one into a fresh slot reproduces bit-exactly the state a
    from-scratch prefill of the same prefix would reach (the recurrent
    prefill branches are per-token scans whose chunk boundaries cannot
    move numerics, and rows never couple across the batch)."""
    out = []
    for st in states:
        if is_paged_cache(st) or not st:
            out.append({})
        else:
            out.append(jax.tree_util.tree_map(
                lambda l: jax.lax.dynamic_slice_in_dim(l, slot, 1, axis=1),
                st))
    return out


def restore_slot_recurrent(states: list[Any], snap: list[Any],
                           slot: jax.Array) -> list[Any]:
    """Inverse of :func:`snapshot_slot_recurrent`: splice the cached
    recurrent rows into ``slot`` (replaces the fresh-reset a no-hit
    admission would do)."""
    out = []
    for st, sn in zip(states, snap):
        if is_paged_cache(st) or not st or not sn:
            out.append(st)
        else:
            out.append(jax.tree_util.tree_map(
                lambda f, o: jax.lax.dynamic_update_slice_in_dim(
                    f, o.astype(f.dtype), slot, axis=1),
                st, sn))
    return out


def has_kv_cache(cfg: ModelConfig) -> bool:
    """Whether any layer in the repeating period carries a KV cache
    (pure-recurrent stacks — xLSTM — page nothing but still benefit
    from chunked prefill)."""
    p_len = transformer.period(cfg)
    return any(transformer.mixer_kind(cfg, j) == "attn"
               for j in range(p_len))


def has_recurrent_state(cfg: ModelConfig) -> bool:
    """Whether any layer carries per-slot recurrent state (mamba/
    xlstm) that chunked prefill must reset on slot reuse."""
    p_len = transformer.period(cfg)
    return any(transformer.mixer_kind(cfg, j) != "attn"
               for j in range(p_len))


def place_serve_states(states: list[Any], mesh, kv_heads: int
                       ) -> list[Any]:
    """Place a freshly-initialised decode-state tree on a TP serving
    mesh: KV pools/caches shard their KV-head axis over ``model``
    (``dist.sharding.serve_state_specs``), recurrent rows replicate.

    Called once per scheduler reset; from then on the jitted steps'
    donated in-place updates keep the layout (attention pins it with
    ``shard_act`` each step, so per-token writes never drift it).
    """
    from repro.dist import sharding as shd
    specs = shd.serve_state_specs(states, mesh, kv_heads=kv_heads)
    return jax.device_put(states, shd.named_shardings(mesh, specs))


def kv_cache_bytes(states: list[Any]) -> int:
    """Total bytes held by KV storage (contiguous ``k``/``v`` windows or
    paged ``k_pool``/``v_pool`` stores) in a decode-state tree."""
    total = 0
    for st in states:
        if not isinstance(st, dict):
            continue
        for name in ("k", "v", "k_pool", "v_pool"):
            leaf = st.get(name)
            if leaf is not None:
                total += leaf.size * leaf.dtype.itemsize
    return total
