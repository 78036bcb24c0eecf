"""Continuous-batching serve scheduler: slot-based decode over one
shared prepacked parameter set.

PR 2 made a *static* batch decode fast; real serving traffic (the
ROADMAP's north star) is a stream of requests that arrive at different
times, with different prompt lengths, temperatures and stop conditions,
and finish at different times.  PUMA-style PUM accelerators live or die
by the runtime that keeps the (expensively programmed) crossbars busy
across concurrent workloads — weights are packed once at load and every
request decodes against the same programmed arrays.

Design
------
A fixed pool of ``num_slots`` decode slots backs one shared, group-
stacked decode-state tree (batch axis = slots).  The engine runs three
kinds of work:

  * **admit** — a queued request claims a free slot: its prompt is
    prefilled alone (batch 1, exact length — the same jitted prefill the
    oracle uses) and the resulting state is spliced into the shared tree
    at the slot's batch row.  The first token is sampled from the
    prefill logits with the request's own PRNG key.
  * **step** — ONE jitted slot-wise decode advances *all* slots: per-
    slot ``cache_index`` vector (every row writes/attends at its own
    depth), per-slot RNG keys folded by each request's local step count,
    per-slot temperatures, and an active mask.  Finished/empty slots run
    through the same computation (shapes never change, so the step
    compiles exactly once) but their lanes are masked out of bookkeeping.
  * **retire** — a slot whose row sampled its EOS id, or hit its
    ``max_tokens`` budget, frees the slot for the next queued request.

The host loop is plain Python (admission order, arrival times, harvest);
everything per-token is inside the one jitted step.

Step-wise driving (PR 7)
------------------------
``run`` is a convenience loop over four public primitives an external
driver (``serve.frontend.ServeFrontend``) can call directly:

  * :meth:`start_request` — admit ONE request into a free slot (typed
    ``PoolExhausted`` when it cannot be funded right now);
  * :meth:`tick` — advance the engine by one scheduler iteration
    (prefill chunks + at most one decode dispatch), returning per-token
    events for streaming, harvested completions, and dispatch counts;
  * :meth:`cancel` — retire a request mid-flight (mid-prefill or
    mid-decode), freeing its slot and KV blocks; co-batched requests
    are untouched (their lanes were already isolated by the active
    mask / trash-block table masking / recurrent-row freezing);
  * :meth:`drain` — cancel everything in flight, returning partial
    ``Completion``s flagged ``truncated=True`` so teardown never
    silently loses work.

``tick`` accepts a ``fault_hook`` called at each injection point
(before every chunk-prefill dispatch and before the decode dispatch)
that may raise :class:`~repro.serve.errors.FaultInjected`; the hooks
run *before* any host-side state mutation for that dispatch, so a
raised fault always leaves the slot state machine consistent — the
chaos suite (``tests/test_chaos.py``) proves survivors stay
bit-identical and no blocks leak under seeded fault storms.

Paged KV cache + chunked prefill
--------------------------------
With ``kv_block_size > 0`` the attention KV state is no longer a private
``[slots, max_len]`` window per slot but one shared pool of fixed-size
token blocks (``serve.kv_pool``), addressed through per-slot block
tables — DARTH-PUM's array-pool allocation applied to the cache.  A
request owns ``ceil((prompt + max_tokens - 1) / block_size)`` blocks
for exactly its lifetime, so total KV memory follows the *live* token
count instead of ``slots * max_len``.  Admission then also waits for
blocks: a slot may be free while the pool is not.

Prefill stops being a monolithic splice: prompts are streamed through a
batch-1 chunked-prefill step that writes K/V straight into the shared
pool through the slot's block table (recurrent xlstm/ssm rows are
spliced per chunk — they are tiny).  With ``chunked_prefill=True`` the
chunks are ``block_size`` tokens and at most one chunk per slot is fed
per scheduler iteration, interleaved with the decode step — a long
prompt no longer head-of-line-blocks the decode of live slots, and the
chunk step compiles for ONE shape instead of one shape per prompt
length.  Both paths preserve the oracle-equivalence invariant below.

Oracle equivalence
------------------
For *any* interleaved arrival trace, every request's tokens are
bit-identical to running that request alone through
``ServeEngine.generate_loop`` — greedy and sampled, across state
families (dense KV / xlstm / ssm), execution modes (bf16/int8/pum), and
KV layouts (contiguous / paged, chunked or monolithic prefill).
``tests/test_scheduler.py`` property-tests this invariant.  Three pieces
of the stack make it hold:

  * activation quantisation uses per-input-row scales
    (``core.pum_linear._quantize_act``), so a row's numerics never
    depend on what it is co-batched with;
  * per-slot sampling draws each row from its own key
    (``engine.sample_token``'s vector form), reproducing the solo call's
    key schedule exactly;
  * the paged gather is cropped back to the engine window
    (``kv_len``), so attention reduction shapes — and the compiled
    reduction order — match the contiguous cache exactly, and the
    recurrent prefill branches are per-token scans whose chunk
    boundaries cannot move numerics.

MoE configs schedule fine but are excluded from the guarantee: expert
capacity is shared across the batch, so dropping is inherently coupled.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from collections.abc import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.kernels import registry as kreg
from repro.models import lm
from repro.serve import kv_pool
from repro.serve import spec as spec_mod
from repro.serve.engine import (ServeEngine, make_decode_step,
                                make_verify_step, sample_token)
from repro.serve.errors import (InvalidRequest, PoolExhausted,
                                RequestTooLarge, SchedulerStalled)


# ---------------------------------------------------------------------------
# Request / completion records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    """One generation request entering the scheduler's queue.

    ``arrival`` is measured in scheduler decode steps: the request is
    invisible to admission before that step (synthetic arrival traces).
    ``eos_id < 0`` disables EOS termination; ``max_tokens`` counts every
    generated token, including the EOS itself.

    The last three fields are front-end metadata the scheduler itself
    ignores: ``arrival_time`` is the wall-clock arrival in seconds
    (Poisson traces for the async front-end), ``priority`` orders the
    admission queue under the ``priority`` policy (higher first), and
    ``deadline_ms`` is the per-request latency budget the front-end
    enforces (queued past it → expired; decoding past it → cancelled
    with a partial completion).
    """
    prompt: Sequence[int]
    max_tokens: int
    temperature: float = 0.0
    eos_id: int = -1
    seed: int = 0
    arrival: int = 0
    rid: int | None = None
    arrival_time: float | None = None
    priority: int = 0
    deadline_ms: float | None = None


@dataclasses.dataclass
class Completion:
    rid: int
    prompt: list[int]
    tokens: list[int]                  # generated tokens, EOS included
    finish_reason: str                 # "eos" | "length" | a partial
    #                                    reason ("cancelled" / "expired"
    #                                    / "fault" / "truncated")
    admitted_step: int                 # scheduler step of admission
    finished_step: int                 # scheduler step of the last token
    truncated: bool = False            # True = retired before its natural
    #                                    EOS/length finish (cancel, drain,
    #                                    deadline, injected fault)


@dataclasses.dataclass
class TickResult:
    """What one scheduler iteration produced.

    ``events`` are per-token streaming records ``(rid, index, token)``
    — ``index`` is the position in the request's generated-token list,
    so a driver that re-runs a request after a fault can dedupe the
    (bit-identical) regenerated prefix.  ``completions`` are requests
    that retired this tick; ``dispatches`` counts jitted calls (the
    runaway guard's currency); ``decoded`` says whether the slot-wise
    decode step ran.
    """
    events: list[tuple[int, int, int]]
    completions: dict[int, Completion]
    dispatches: int
    decoded: bool


@dataclasses.dataclass
class _PrefillJob:
    """A slot mid-prefill: the prompt streams into the paged pool in
    chunks; the slot joins decode once the last chunk lands.

    Prefix caching starts ``pos`` past the cached prefix (only the
    uncached tail is fed).  ``hashes`` are the prompt's full-block chain
    hashes (computed once at admission, reused at registration);
    ``snaps`` collects recurrent-state snapshots at block boundaries;
    ``cow_col``/``cow_dst`` are the pending copy-on-write (the fully
    cached last prompt block must be re-run for first-token logits, so
    it is copied into a private block before the tail chunk lands)."""
    req: Request
    prompt: list[int]
    pos: int = 0                       # prompt tokens already fed
    hashes: list[str] = dataclasses.field(default_factory=list)
    snaps: dict[int, object] = dataclasses.field(default_factory=dict)
    cow_col: int = -1                  # table column awaiting COW (-1: none)
    cow_dst: int = -1                  # private block the copy lands in


# ---------------------------------------------------------------------------
# The jitted slot-wise decode step
# ---------------------------------------------------------------------------

# Donated argnums for the jitted slot step / chunk-prefill step.  The
# graph auditor's mutation self-test flips this to () to prove the
# donation rule notices undonated decode carries (analysis/mutations.py).
_STEP_DONATE = (1,)


def _mask_block_table(block_table: jax.Array, active: jax.Array):
    """Route every non-decoding row's KV writes to the trash block.

    Rows that are empty, retired, or still mid-prefill must not scribble
    over pool blocks another slot owns (or that a streaming prefill is
    filling); zeroing their table rows sends the masked writes to the
    reserved trash block instead.  Lives *inside* the jitted slot step
    (an exact int32 multiply) so the auditor's masked-scatter rule can
    statically see that scatter addresses depend on the active mask.
    """
    with jax.named_scope("mask_table"):
        return block_table * active.astype(block_table.dtype)[:, None]


# Re-exported under a module-level name so the auditor's mutation
# self-test can knock the shared-block write protection out through
# *this* module (the jitted steps resolve it by global lookup at trace
# time, exactly like `_mask_block_table` above).
_mask_shared_cols = kv_pool._mask_shared_cols


def make_slot_step(cfg: ModelConfig, kv_len: int | None = None):
    """Build the one-dispatch-per-token engine core.

    (params, states, cur_tok [B,1], cache_index [B], keys [B,2],
     active [B] bool, temp [B], eos [B], gen [B], max_toks [B]
     [, block_table [B,W], shared_cols [B]])
      -> (states', tok [B], cache_index', keys', active', gen', done [B])

    Every slot — live, finished, or never filled — flows through the
    same decode so the step compiles once; ``active`` masks slots out of
    the counters and termination logic.  Key schedule per slot: the
    request's chain key is folded with its local step number
    (``gen - 1``), mirroring ``generate_loop``'s ``fold_in(key, i)``.

    ``block_table`` (and ``kv_len`` at build time) select the paged KV
    path: rows address the shared block pool through their table row.
    The step masks the table itself (``_mask_block_table``): rows not
    actively decoding write to the reserved trash block, whatever table
    the host hands in.  ``shared_cols`` counts each row's leading
    prefix-cache-shared table columns: gathers read through the real
    table, but the write path goes through a second masking
    (``_mask_shared_cols``) that trash-routes those columns — shared
    blocks are structurally read-only (all-zero without prefix caching,
    so the signature, and the auditor's proof obligation, never change).
    """
    decode = make_decode_step(cfg, kv_len=kv_len)
    paged = kv_len is not None

    def slot_step(params, states, cur_tok, cache_index, keys, active,
                  temp, eos, gen, max_toks, block_table=None,
                  shared_cols=None):
        step_keys = jax.vmap(jax.random.fold_in)(keys, gen - 1)
        write_table = None
        if paged:
            block_table = _mask_block_table(block_table, active)
            write_table = _mask_shared_cols(block_table, shared_cols)
        logits, new_states = decode(params, states, cur_tok, cache_index,
                                    block_table=block_table,
                                    write_table=write_table)
        if paged:
            # chunked prefill streams prompts in *between* decode steps:
            # a mid-prefill row's recurrent state must not move under it
            # (its KV writes already go to the trash block via the
            # zeroed block-table row)
            states = kv_pool.freeze_inactive_rows(states, new_states,
                                                  active)
        else:
            states = new_states
        tok = sample_token(logits, step_keys, temp)            # [B, 1]
        gen = gen + active.astype(gen.dtype)
        done = active & ((tok[:, 0] == eos) | (gen >= max_toks))
        cache_index = cache_index + active.astype(cache_index.dtype)
        active = active & ~done
        return states, tok[:, 0], cache_index, step_keys, active, gen, done

    return slot_step


def make_spec_step(cfg: ModelConfig, k: int, kv_len: int):
    """Build the draft-and-verify speculative decode step (paged only).

    (params, states, cur_tok [B,1], draft [B,k], cache_index [B],
     keys [B,2], active [B] bool, temp [B], eos [B], gen [B],
     max_toks [B], block_table [B,W], shared_cols [B])
      -> (states', emitted [B,k+1], advance [B], cache_index', keys',
          active', gen', done [B])

    One verify forward scores all k+1 positions (current token + k
    drafts); each row then commits the longest draft prefix that matches
    what solo decode would have sampled, plus one bonus token — so every
    active row advances by ``advance`` ∈ [1, k+1] tokens per dispatch,
    and the emitted tokens are bit-identical to the single-token oracle
    whatever the drafter proposed:

      * the j-th emitted token is sampled from the verify logits at
        position j with the *solo key chain's* j-th key (``fold_in`` by
        the local step number, exactly ``generate_loop``'s schedule), so
        greedy and sampled rows alike emit the oracle's token at every
        accepted position;
      * positions are only accepted while the *draft* matched the
        emitted token, so every accepted position attended exclusively
        to oracle-correct KV;
      * rejected draft positions' KV writes are rolled back cell-wise
        (``kv_pool.spec_save_cells`` / ``spec_restore_cells``): the
        pool's net change is exactly a k=0 replay's;
      * recurrent rows (xlstm/ssm) select the per-position state at
        ``advance - 1`` from the verify scan's collected states
        (``collect_states``) — bit-identical to stepping one token at a
        time, because the scan *is* the per-token recurrence.

    Termination mirrors ``slot_step`` per emitted token: the advance is
    capped at the first EOS (inclusive) and at the remaining
    ``max_tokens`` budget.  The paged-attention Pallas kernel is pinned
    to the XLA composition inside this step only: the kernel's write
    routing clips out-of-range columns into the last owned block,
    while draft probes past the funded window must trash-route
    (``attention.paged_write_cells``).
    """
    verify = make_verify_step(cfg, kv_len=kv_len)
    s = k + 1

    def spec_step(params, states, cur_tok, draft, cache_index, keys,
                  active, temp, eos, gen, max_toks, block_table,
                  shared_cols):
        # the solo oracle's key chain for the next k+1 tokens: token
        # gen-1+j is sampled after fold_in(..., gen-1+j) applied to the
        # request key folded through every earlier step
        chain = []
        kk = keys
        for j in range(s):
            kk = jax.vmap(jax.random.fold_in)(kk, gen - 1 + j)
            chain.append(kk)
        chain = jnp.stack(chain, axis=1)                   # [B, k+1, 2]

        block_table = _mask_block_table(block_table, active)
        write_table = _mask_shared_cols(block_table, shared_cols)
        tokens = jnp.concatenate([cur_tok, draft], axis=1)  # [B, k+1]

        # transactional KV: snapshot the k+1 cells each row will write,
        # run the verify forward, then restore the cells past each row's
        # accepted advance — the pool's net change is a k=0 replay's
        saved = kv_pool.spec_save_cells(states, write_table, cache_index,
                                        s)
        with kreg.use_backend(paged_attention="xla"):
            logits, new_states = verify(params, states, tokens,
                                        cache_index,
                                        block_table=block_table,
                                        write_table=write_table)

        emitted = jnp.stack(
            [sample_token(logits[:, j:j + 1], chain[:, j], temp)[:, 0]
             for j in range(s)], axis=1)                   # [B, k+1]

        # longest matching draft prefix, then the caps
        match = (emitted[:, :k] == draft) if k else \
            jnp.zeros((emitted.shape[0], 0), bool)
        n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                        axis=1)
        m_raw = n_acc + 1                                  # tokens to emit
        valid = jnp.arange(s)[None, :] < m_raw[:, None]
        is_eos = (emitted == eos[:, None]) & valid
        any_eos = jnp.any(is_eos, axis=1)
        first_eos = jnp.argmax(is_eos, axis=1).astype(jnp.int32)
        eos_cap = jnp.where(any_eos, first_eos + 1, s)
        len_cap = jnp.maximum(max_toks - gen, 1)           # >= 1 token
        adv = jnp.where(active,
                        jnp.minimum(jnp.minimum(m_raw, eos_cap), len_cap),
                        0).astype(cache_index.dtype)

        out_states = kv_pool.spec_restore_cells(new_states, saved,
                                                write_table, cache_index,
                                                s, adv)
        # recurrent rows: pick the collected per-position state at the
        # last accepted position; inactive rows keep their PRE-step
        # state (the freeze_inactive_rows contract)
        out_states = kv_pool.spec_select_recurrent(states, out_states,
                                                   adv, active)
        states = out_states
        gen = gen + adv
        eos_hit = any_eos & (adv == first_eos + 1)
        done = active & (eos_hit | (gen >= max_toks))
        # carry the key the solo loop would hold after the last emitted
        # token (inactive rows churn to chain[0], exactly slot_step's
        # step_keys churn — harmless, re-seeded at admission)
        sel = jnp.clip(adv - 1, 0).astype(jnp.int32)[:, None, None]
        keys = jnp.take_along_axis(
            chain, jnp.broadcast_to(sel, (chain.shape[0], 1, 2)),
            axis=1)[:, 0]
        cache_index = cache_index + adv
        active = active & ~done
        return (states, emitted, adv, cache_index, keys, active, gen,
                done)

    return spec_step


# ---------------------------------------------------------------------------
# The scheduler
# ---------------------------------------------------------------------------

class ContinuousBatchingScheduler:
    """Continuous-batching engine over a fixed pool of decode slots.

    Wraps a :class:`ServeEngine` (shared prepacked params, jitted
    prefill) and adds the slot pool + host admission loop.  ``run`` is
    re-entrant: all slots drain before it returns, so one scheduler
    serves many traces (and the jitted step/prefill stay warm).  An
    external driver can instead call ``start_request`` / ``tick`` /
    ``cancel`` / ``drain`` directly (the async front-end does).

    ``kv_block_size > 0`` switches the attention KV state from
    per-slot contiguous windows to the shared paged block pool
    (``serve.kv_pool``); ``num_kv_blocks`` sizes the pool (default:
    the contiguous equivalent, ``num_slots * ceil(max_len /
    block_size)`` — pass less to actually save memory).
    ``chunked_prefill=True`` (paged only) streams prompts in
    ``kv_block_size``-token chunks interleaved with decode steps.

    ``mesh`` (a 1-D ``model`` mesh) turns on tensor-parallel serving:
    prepacked weights and the KV pool shard across devices
    (``dist.sharding.serve_param_specs`` / ``serve_state_specs``) and
    every jitted step runs mesh-aware; completions stay bit-identical
    to the single-device oracle.

    ``kernel_backend`` selects the kernel backend
    (:mod:`repro.kernels.registry`: ``"xla"`` / ``"pallas"`` /
    ``"interpret"``) ambient for every jitted step; ``None`` keeps the
    pre-registry defaults (the XLA composition unless ``use_kernel``).
    Completions are bit-identical across backends.

    ``speculate_k > 0`` (paged only) switches decode dispatches to the
    draft-and-verify speculative step (:func:`make_spec_step`):
    ``drafter`` (``"ngram"`` — prompt-lookahead self-speculation — or
    any object with ``propose(context, k)``, e.g.
    :class:`~repro.serve.spec.ModelDrafter`) proposes k tokens per
    active slot, one verify forward scores all k+1 positions, and each
    slot advances by 1..k+1 tokens.  Output stays bit-identical to the
    single-token oracle for any drafter; ``spec_stats()`` tracks the
    acceptance rate and mean advance.
    """

    def __init__(self, cfg: ModelConfig, params, num_slots: int = 4,
                 max_len: int = 128, prepack: bool | None = None,
                 kv_block_size: int = 0, num_kv_blocks: int = 0,
                 chunked_prefill: bool = False,
                 mesh: jax.sharding.Mesh | None = None,
                 prefix_cache: bool = False,
                 prefix_cache_entries: int = 0,
                 kernel_backend=None,
                 speculate_k: int = 0, drafter="ngram"):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if chunked_prefill and kv_block_size <= 0:
            raise ValueError(
                "chunked_prefill streams prompts through the paged pool; "
                "set kv_block_size > 0 to enable it")
        if prefix_cache and kv_block_size <= 0:
            raise ValueError(
                "prefix_cache shares paged pool blocks between requests; "
                "set kv_block_size > 0 to enable it")
        if speculate_k > 0 and kv_block_size <= 0:
            raise ValueError(
                "speculative decoding rolls rejected draft KV writes "
                "back through the paged pool; set kv_block_size > 0 to "
                "enable it")
        self.engine = ServeEngine(cfg, params, max_len=max_len,
                                  prepack=prepack, mesh=mesh,
                                  kernel_backend=kernel_backend,
                                  speculate_k=speculate_k)
        self.mesh = mesh
        self.cfg = self.engine.cfg
        self.params = self.engine.params
        self.num_slots = num_slots
        self.max_len = max_len
        self.paged = kv_block_size > 0
        self.chunked_prefill = chunked_prefill
        # donate the state tree: the per-row KV-cache updates then happen
        # in place instead of copying the whole cache every token (the
        # host rebinds self.states to the step's return unconditionally)
        if self.paged:
            self.block_size = kv_block_size
            self.table_width = kv_pool.table_width(max_len, kv_block_size)
            self.num_kv_blocks = (num_kv_blocks
                                  or num_slots * self.table_width)
            # pure-recurrent stacks (xLSTM) have no KV to page: the pool
            # machinery idles at zero blocks per request, but chunked
            # prefill still applies to their per-token state scans
            self._has_kv = kv_pool.has_kv_cache(self.cfg)
            self._step = jax.jit(make_slot_step(self.cfg, kv_len=max_len),
                                 donate_argnums=_STEP_DONATE)
            self.speculate_k = self.engine.speculate_k
            if self.speculate_k > 0:
                self._drafter = spec_mod.resolve_drafter(
                    drafter, self.cfg.vocab_size)
                self._spec_step = jax.jit(
                    make_spec_step(self.cfg, self.speculate_k,
                                   kv_len=max_len),
                    donate_argnums=_STEP_DONATE)
            self._chunk_prefill = self._build_chunk_prefill()
            self._has_recurrent = kv_pool.has_recurrent_state(self.cfg)
            cfg_, ml_ = self.cfg, max_len
            self._reset_slot = jax.jit(
                lambda states, slot: kv_pool.reset_slot_recurrent(
                    cfg_, states, slot, ml_),
                donate_argnums=(0,))
            self.prefix_caching = prefix_cache
            self._prefix_entries = (prefix_cache_entries
                                    or self.num_kv_blocks)
            if prefix_cache:
                self._cow_copy = jax.jit(self._cow_copy_impl,
                                         donate_argnums=(0,))
                # snapshots are read back later, so the source tree is
                # NOT donated here (restore donates normally)
                self._snap_slot = jax.jit(kv_pool.snapshot_slot_recurrent)
                self._restore_slot = jax.jit(
                    kv_pool.restore_slot_recurrent, donate_argnums=(0,))
        else:
            self.prefix_caching = False
            self.speculate_k = 0
            self._step = jax.jit(make_slot_step(self.cfg),
                                 donate_argnums=_STEP_DONATE)
            self._insert = jax.jit(self._insert_impl, donate_argnums=(0,))
        # lifetime speculative-decoding counters (all zero at k=0)
        self._spec_steps = 0           # spec dispatches run
        self._spec_rows = 0            # active row-steps inside them
        self._spec_proposed = 0        # draft tokens proposed
        self._spec_accepted = 0        # draft tokens accepted
        self._spec_emitted = 0         # tokens emitted (advance sum)
        self._reset()

    def _reset(self) -> None:
        b = self.num_slots
        if self.paged:
            self.states = lm.init_paged_state(
                self.cfg, b, self.max_len, num_blocks=self.num_kv_blocks,
                block_size=self.block_size)
            self._alloc = kv_pool.BlockAllocator(self.num_kv_blocks)
            self._block_table = np.zeros((b, self.table_width), np.int32)
            self._shared_cols = np.zeros((b,), np.int32)
            self._slot_blocks: list[list[int]] = [[] for _ in range(b)]
            self._prefills: dict[int, _PrefillJob] = {}
            self._prefix: kv_pool.PrefixCache | None = None
            if self.prefix_caching:
                # the hash root folds in model/config identity + block
                # size, so entries can never match across engines whose
                # numerics (or block geometry) differ
                self._prefix = kv_pool.PrefixCache(
                    self._alloc, self.block_size,
                    capacity=self._prefix_entries,
                    root=f"{self.cfg!r}/bs={self.block_size}")
        else:
            self.states = lm.init_state(self.cfg, b, self.max_len)
            self._prefills = {}
            self._prefix = None
        if self.mesh is not None:
            self.states = kv_pool.place_serve_states(self.states, self.mesh)
        # host mirrors of the per-slot lanes (tiny; re-shipped per step)
        self._cur_tok = np.zeros((b, 1), np.int32)
        self._cache_index = np.zeros((b,), np.int32)
        self._keys = np.zeros((b, 2), np.uint32)
        self._active = np.zeros((b,), bool)
        self._temp = np.zeros((b,), np.float32)
        self._eos = np.full((b,), -1, np.int32)
        self._gen = np.zeros((b,), np.int32)
        self._max_toks = np.ones((b,), np.int32)
        self._slot_req: list[Request | None] = [None] * b
        self._slot_toks: list[list[int]] = [[] for _ in range(b)]
        self._slot_admitted = np.zeros((b,), np.int64)
        self._events: list[tuple[int, int, int]] = []

    @staticmethod
    def _cow_copy_impl(states, src, dst):
        """Copy pool block ``src`` into ``dst`` across every paged
        group (whole-block K/V copy: each row of a fully-cached prompt
        block is valid prompt K/V, so copying all ``block_size``
        positions is bit-safe).  The copy-on-write escape for a
        fully-cached prompt: the last prompt position must be re-run
        for first-token logits, and its write lands in the private
        copy, never the shared original."""
        with jax.named_scope("cow_copy"):
            out = []
            for st in states:
                if kv_pool.is_paged_cache(st):
                    st = dict(st)
                    for name in ("k_pool", "v_pool"):
                        pool = st[name]
                        row = jax.lax.dynamic_slice_in_dim(
                            pool, src, 1, axis=1)
                        st[name] = jax.lax.dynamic_update_slice_in_dim(
                            pool, row, dst, axis=1)
                out.append(st)
            return out

    @staticmethod
    def _insert_impl(full_states, one_states, slot):
        """Splice a batch-1 prefill state into batch row ``slot`` of the
        shared tree (leaves are [n_groups, B, ...])."""
        return jax.tree_util.tree_map(
            lambda f, o: jax.lax.dynamic_update_slice_in_dim(
                f, o.astype(f.dtype), slot, axis=1),
            full_states, one_states)

    def _build_chunk_prefill(self):
        """The jitted batch-1 chunk step: run ``tokens`` of one slot's
        prompt against the shared tree — K/V scatter through the slot's
        block-table row into the pool, recurrent rows sliced out /
        spliced back (they are O(B * d), not O(B * max_len * d)).
        Compiles once per distinct chunk length: with chunked prefill
        that is the block size plus ragged tails, not one shape per
        prompt length."""
        cfg, max_len = self.cfg, self.max_len

        def chunk_prefill(params, states, tokens, start, table_row, slot,
                          shared_cols):
            # same read/write split as the decode step: the tail chunk
            # of a prefix-cache hit must *attend* the shared K/V but its
            # scatters must never land in a shared block
            write_row = _mask_shared_cols(table_row, shared_cols)
            one = kv_pool.slot_states_view(cfg, states, slot)
            logits, one, _ = lm.forward(
                params, tokens, cfg, states=one,
                cache_index=jnp.reshape(start, (1,)),
                block_table=table_row, last_only=True, kv_len=max_len,
                write_table=write_row)
            states = kv_pool.slot_states_merge(cfg, states, one, slot)
            return states, logits

        return jax.jit(chunk_prefill, donate_argnums=_STEP_DONATE)

    # -- admission ---------------------------------------------------------

    def _blocks_for(self, req: Request) -> int:
        if not self._has_kv:
            return 0
        return kv_pool.blocks_needed(len(req.prompt), req.max_tokens,
                                     self.block_size)

    def _prefix_peek(self, req: Request) -> tuple[int, list[str], bool]:
        """Non-mutating cache lookup for ``req``: (matched blocks,
        chain hashes, needs-COW).  Recurrent stacks resume only at a
        snapshot-bearing boundary strictly before the last prompt token;
        dense stacks can consume a *fully* cached prompt by
        copy-on-writing its last block (the tail re-runs just position
        ``prompt_len - 1`` for first-token logits)."""
        assert self._prefix is not None
        plen = len(req.prompt)
        hashes = self._prefix.hashes(req.prompt)
        if self._has_recurrent:
            n = self._prefix.match(hashes, need_snapshot=True,
                                   limit=(plen - 1) // self.block_size)
            return n, hashes, False
        n = self._prefix.match(hashes)
        cow = n > 0 and n * self.block_size == plen
        return n, hashes, cow

    def blocks_needed(self, req: Request) -> int:
        """KV blocks admission would *newly allocate* for ``req`` (0 on
        the contiguous layout or for pure-recurrent stacks) — the
        front-end's cost-aware admission reads this against
        ``free_blocks``.  With prefix caching this is the post-cache-hit
        private footprint: total minus shared attachments, plus one for
        the copy-on-write destination when the whole prompt is cached."""
        if not self.paged:
            return 0
        total = self._blocks_for(req)
        if self._prefix is None or total == 0:
            return total
        n, _, cow = self._prefix_peek(req)
        return total - n + (1 if cow else 0)

    def validate_request(self, req: Request) -> None:
        """Typed up-front validation: :class:`InvalidRequest` for
        malformed requests, :class:`RequestTooLarge` for requests that
        can never be served by this engine (window / pool capacity)."""
        if len(req.prompt) < 1:
            raise InvalidRequest(f"request {req.rid}: empty prompt")
        if req.max_tokens < 1:
            raise InvalidRequest(
                f"request {req.rid}: max_tokens must be >= 1, "
                f"got {req.max_tokens}")
        self.engine._check_window(len(req.prompt), req.max_tokens)
        if self.paged:
            need = self._blocks_for(req)
            if need > self.num_kv_blocks:
                raise RequestTooLarge(
                    f"request {req.rid}: prompt_len={len(req.prompt)} + "
                    f"max_tokens={req.max_tokens} needs {need} KV "
                    f"blocks, exceeding the pool capacity of "
                    f"{self.num_kv_blocks} blocks "
                    f"({self.num_kv_blocks * self.block_size} "
                    f"positions); re-create the scheduler with "
                    f"num_kv_blocks >= {need}")

    def _free_slot(self) -> int | None:
        for slot in range(self.num_slots):
            if not self._active[slot] and self._slot_req[slot] is None:
                return slot
        return None

    @property
    def num_free_slots(self) -> int:
        return sum(not self._active[s] and self._slot_req[s] is None
                   for s in range(self.num_slots))

    @property
    def free_blocks(self) -> int:
        """KV blocks admission can spend right now: unallocated blocks
        plus — with prefix caching — cached blocks no live request
        references (evictable on demand).  The whole pool when
        contiguous — admission is then slot-bound only."""
        if not self.paged:
            return 0
        free = self._alloc.free_blocks
        if self._prefix is not None:
            free += self._prefix.evictable_blocks
        return free

    @property
    def total_blocks(self) -> int:
        return self.num_kv_blocks if self.paged else 0

    def can_fund(self, req: Request) -> bool:
        """Whether admission could succeed *right now* (a free slot and,
        when paged, enough free + evictable blocks net of the request's
        cache hit).  Purely advisory — the pool only moves when
        ``start_request`` commits."""
        if self._free_slot() is None:
            return False
        if not self.paged:
            return True
        if self._prefix is None:
            return self._alloc.can_alloc(self._blocks_for(req))
        total = self._blocks_for(req)
        if total == 0:
            return True
        n, hashes, cow = self._prefix_peek(req)
        need = total - n + (1 if cow else 0)
        return need <= self._alloc.free_blocks \
            + self._prefix.evictable_margin(exclude=hashes[:n])

    def in_flight(self) -> list[int]:
        """rids currently holding a slot (decoding or mid-prefill)."""
        return [req.rid for req in self._slot_req if req is not None]

    def start_request(self, req: Request, step: int = 0,
                      ) -> Completion | None:
        """Admit ONE request into a free slot.

        Returns an instant :class:`Completion` when the request finishes
        at prefill already (EOS on the first token / ``max_tokens == 1``
        on the contiguous path), else ``None`` — the request now owns a
        slot and will produce ``tick`` events.  Raises
        :class:`PoolExhausted` when no slot or (paged) no blocks can
        fund it right now, and the validation errors of
        :meth:`validate_request`.
        """
        self.validate_request(req)
        slot = self._free_slot()
        if slot is None:
            raise PoolExhausted(
                f"request {req.rid}: all {self.num_slots} decode slots "
                f"are occupied")
        if self.paged:
            if not self._admit_paged(slot, req, step):
                raise PoolExhausted(
                    f"request {req.rid}: needs {self.blocks_needed(req)} "
                    f"KV blocks, pool has {self.free_blocks} free")
            return None
        return self._admit(slot, req, step)

    def _admit(self, slot: int, req: Request, step: int,
               ) -> Completion | None:
        """Prefill ``req`` into ``slot``.  Returns the instant
        completion when it finished at prefill already (the slot stays
        free), else None (the request occupies the slot)."""
        prompt = list(int(t) for t in req.prompt)
        s = len(prompt)
        states1, logits, _ = self.engine.prefill(
            jnp.asarray(prompt, jnp.int32)[None])
        key = jax.random.PRNGKey(req.seed)
        tok0 = int(sample_token(logits, key, req.temperature)[0, 0])

        if tok0 == req.eos_id or req.max_tokens == 1:
            reason = "eos" if tok0 == req.eos_id else "length"
            return Completion(req.rid, prompt, [tok0], reason, step, step)

        with self.engine.mesh_ctx():
            self.states = self._insert(self.states, states1,
                                       jnp.int32(slot))
        self._cur_tok[slot, 0] = tok0
        self._cache_index[slot] = s
        self._keys[slot] = np.asarray(key, np.uint32)
        self._active[slot] = True
        self._temp[slot] = req.temperature
        self._eos[slot] = req.eos_id if req.eos_id >= 0 else -1
        self._gen[slot] = 1
        self._max_toks[slot] = req.max_tokens
        self._slot_req[slot] = req
        self._slot_toks[slot] = [tok0]
        self._slot_admitted[slot] = step
        self._events.append((req.rid, 0, tok0))
        return None

    def _admit_paged(self, slot: int, req: Request, step: int) -> bool:
        """Claim ``slot`` and the request's KV blocks; prefill happens
        incrementally via ``_feed_prefills``.  Returns False (leaving
        the allocator and prefix index untouched) when the pool cannot
        fund the request yet — the caller keeps it queued FIFO.

        With prefix caching: look up the longest cached prefix, attach
        its blocks read-only (an extra allocator reference each), evict
        idle cache entries if the free list alone cannot fund the
        private tail, and allocate only the post-hit footprint.  A fully
        cached prompt additionally reserves one block as the
        copy-on-write destination (the copy itself is deferred to
        ``_feed_prefills`` so it sits behind the same fault-injection
        point as any other prefill dispatch)."""
        total = self._blocks_for(req)
        plen = len(req.prompt)
        n_match, hashes, cow = 0, [], False
        if self._prefix is not None:
            n_match, hashes, cow = self._prefix_peek(req)
        shared: list[int] = []
        if n_match and self._has_kv:
            private = total - n_match + (1 if cow else 0)
        else:
            private = total
        if self._prefix is not None \
                and self._alloc.free_blocks < private:
            self._prefix.evict_blocks(
                private - self._alloc.free_blocks,
                exclude=hashes[:n_match])
        if n_match and self._has_kv:
            shared = self._prefix.attach(hashes[:n_match])
        ids = self._alloc.alloc(private)
        if ids is None:
            if shared:                 # roll back: admission is atomic
                self._alloc.release(shared)
            return False
        cow_dst = -1
        table_private = ids
        if cow:
            cow_dst, table_private = ids[0], ids[1:]
        row = shared + table_private
        self._slot_blocks[slot] = shared + ids
        self._block_table[slot, :] = 0
        self._block_table[slot, :len(row)] = row
        self._shared_cols[slot] = len(shared)
        # resume point: a fully-cached dense prompt re-runs only its
        # last token (COW gives the write somewhere private to land);
        # otherwise the tail starts at the first uncached block edge
        tail_start = min(n_match * self.block_size, plen - 1) \
            if cow else n_match * self.block_size
        if self._has_recurrent:
            snap = None
            if n_match:
                snap = self._prefix.snapshot_at(hashes[n_match - 1])
            with self.engine.mesh_ctx():
                if snap is not None:
                    # splice the cached recurrent rows in: bit-exactly
                    # the state a from-scratch prefill of the prefix
                    # would reach
                    self.states = self._restore_slot(self.states, snap,
                                                     jnp.int32(slot))
                else:
                    # chunked prefill accumulates prompt state in the
                    # slot's recurrent rows — scrub the retired
                    # occupant's state first
                    self.states = self._reset_slot(self.states,
                                                   jnp.int32(slot))
        if self._prefix is not None and tail_start > 0:
            self._prefix.hits += 1
            self._prefix.tokens_skipped += tail_start
            self._prefix.blocks_shared += len(shared)
        prompt = list(int(t) for t in req.prompt)
        self._prefills[slot] = _PrefillJob(
            req=req, prompt=prompt, pos=tail_start, hashes=hashes,
            cow_col=(n_match - 1) if cow else -1, cow_dst=cow_dst)
        self._slot_req[slot] = req
        self._slot_toks[slot] = []
        self._slot_admitted[slot] = step
        return True

    def _retire_paged_slot(self, slot: int) -> None:
        if self._slot_blocks[slot]:
            # drops one reference per block: privately-owned blocks
            # return to the free list, shared/cached ones stay live
            # under the prefix index's (or another slot's) reference
            self._alloc.release(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
        self._block_table[slot, :] = 0
        self._shared_cols[slot] = 0

    def _register_prefix(self, slot: int, pf: _PrefillJob) -> None:
        """Index every full prompt block of a completed prefill (the
        attached shared prefix dedupes against its existing entries).
        The slot's write protection then widens to cover all cached
        columns — decode writes start strictly past the prompt, so this
        is purely defensive, and it makes cached blocks structurally
        read-only even for the request that registered them."""
        n_full = len(pf.hashes)
        if self._prefix is None or n_full == 0:
            return
        if self._has_kv:
            blocks = [int(self._block_table[slot, i])
                      for i in range(n_full)]
        else:
            blocks = [None] * n_full
        self._prefix.register(
            pf.hashes, blocks,
            pf.snaps if self._has_recurrent else None)
        if self._has_kv:
            # decode writes start strictly past the prompt (columns
            # >= ceil-of-prompt), so masking every full prompt column
            # can never reroute a legitimate write
            self._shared_cols[slot] = max(
                int(self._shared_cols[slot]), n_full)

    def _feed_prefills(self, step: int, out: dict[int, Completion],
                       fault_hook: Callable[[str, int | None], None]
                       | None = None) -> int:
        """Advance every mid-prefill slot by one chunk (``block_size``
        tokens when chunked, the whole prompt otherwise).  A slot whose
        final chunk lands samples its first token and either joins the
        decode batch or completes instantly (EOS at prefill /
        max_tokens=1) and retires.  Returns dispatches performed.

        ``fault_hook`` fires before each chunk dispatch (injection
        point ``"chunk"`` with the victim rid); a raise propagates with
        the slot's job untouched — earlier slots' chunks this tick
        already landed and stay consistent."""
        dispatches = 0
        for slot in sorted(self._prefills):
            pf = self._prefills[slot]
            if fault_hook is not None:
                fault_hook("chunk", pf.req.rid)
            if pf.cow_col >= 0:
                # deferred copy-on-write for a fully-cached prompt: copy
                # the shared last block into the reserved private one,
                # repoint the table column, and drop the shared
                # reference.  Runs *after* the fault hook — a raise
                # leaves the table still pointing at the shared block
                # (which shared_cols still write-protects) and the
                # reserved block in _slot_blocks, so cancel cleans up.
                src = int(self._block_table[slot, pf.cow_col])
                with self.engine.mesh_ctx():
                    self.states = self._cow_copy(
                        self.states, jnp.int32(src),
                        jnp.int32(pf.cow_dst))
                self._block_table[slot, pf.cow_col] = pf.cow_dst
                self._shared_cols[slot] = pf.cow_col
                self._slot_blocks[slot].remove(src)
                self._alloc.release([src])
                pf.cow_col = pf.cow_dst = -1
                dispatches += 1
            chunk = self.block_size if self.chunked_prefill \
                else len(pf.prompt)
            c = min(chunk, len(pf.prompt) - pf.pos)
            toks = jnp.asarray(pf.prompt[pf.pos:pf.pos + c],
                               jnp.int32)[None]
            table_row = jnp.asarray(self._block_table[slot:slot + 1])
            shared_row = jnp.asarray(self._shared_cols[slot:slot + 1])
            with self.engine.mesh_ctx():
                self.states, logits = self._chunk_prefill(
                    self.params, self.states, toks, jnp.int32(pf.pos),
                    table_row, jnp.int32(slot), shared_row)
            pf.pos += c
            dispatches += 1
            if self._prefix is not None and self._has_recurrent \
                    and pf.pos % self.block_size == 0:
                # chunk landed exactly on a block edge: snapshot the
                # slot's recurrent rows so the entry for this prefix is
                # resumable (small copies; the state tree is not donated)
                i = pf.pos // self.block_size - 1
                if i < len(pf.hashes) and pf.hashes[i] not in self._prefix:
                    with self.engine.mesh_ctx():
                        pf.snaps[i] = self._snap_slot(self.states,
                                                      jnp.int32(slot))
            if pf.pos < len(pf.prompt):
                continue

            # prompt fully resident: sample the first token, exactly as
            # the monolithic admission path does
            del self._prefills[slot]
            req = pf.req
            self._register_prefix(slot, pf)
            key = jax.random.PRNGKey(req.seed)
            tok0 = int(sample_token(logits, key, req.temperature)[0, 0])
            if tok0 == req.eos_id or req.max_tokens == 1:
                reason = "eos" if tok0 == req.eos_id else "length"
                out[req.rid] = Completion(
                    req.rid, pf.prompt, [tok0], reason,
                    int(self._slot_admitted[slot]), step)
                self._slot_req[slot] = None
                self._slot_toks[slot] = []
                self._retire_paged_slot(slot)
                continue
            self._cur_tok[slot, 0] = tok0
            self._cache_index[slot] = len(pf.prompt)
            self._keys[slot] = np.asarray(key, np.uint32)
            self._active[slot] = True
            self._temp[slot] = req.temperature
            self._eos[slot] = req.eos_id if req.eos_id >= 0 else -1
            self._gen[slot] = 1
            self._max_toks[slot] = req.max_tokens
            self._slot_toks[slot] = [tok0]
            self._events.append((req.rid, 0, tok0))
        return dispatches

    # -- step-wise driving -------------------------------------------------

    def _decode_spec(self, step: int, out: dict[int, Completion],
                     was_active: np.ndarray) -> None:
        """One draft-and-verify dispatch: draft k tokens per active slot
        on the host, run the jitted spec step, then harvest a *variable*
        number of tokens per slot (``advance`` ∈ [1, k+1]) — each one a
        normal streaming event, bit-identical to the single-token path.
        """
        k = self.speculate_k
        contexts: list[list[int] | None] = [None] * self.num_slots
        for slot in np.nonzero(was_active)[0]:
            req = self._slot_req[slot]
            contexts[slot] = (list(int(t) for t in req.prompt)
                              + self._slot_toks[slot])
        drafts = spec_mod.build_drafts(self._drafter, contexts, k,
                                       self.cfg.vocab_size)
        with self.engine.mesh_ctx():
            (self.states, emitted, adv, cache_index, keys, active, gen,
             done) = self._spec_step(
                self.params, self.states, self._cur_tok,
                jnp.asarray(drafts), self._cache_index, self._keys,
                self._active, self._temp, self._eos, self._gen,
                self._max_toks, jnp.asarray(self._block_table),
                jnp.asarray(self._shared_cols))
        emitted = np.array(emitted)
        adv = np.array(adv)
        self._cache_index = np.array(cache_index)
        self._keys = np.array(keys)
        self._active = np.array(active)
        self._gen = np.array(gen)
        done = np.asarray(done)

        n_rows = int(was_active.sum())
        self._spec_steps += 1
        self._spec_rows += n_rows
        self._spec_proposed += k * n_rows
        for slot in np.nonzero(was_active)[0]:
            req = self._slot_req[slot]
            m = int(adv[slot])
            self._spec_accepted += m - 1
            self._spec_emitted += m
            for j in range(m):
                tok = int(emitted[slot, j])
                self._slot_toks[slot].append(tok)
                self._events.append(
                    (req.rid, len(self._slot_toks[slot]) - 1, tok))
            self._cur_tok[slot, 0] = int(emitted[slot, m - 1])
            if done[slot]:
                # the advance cap makes the last emitted token the
                # decider: EOS-capped rows end exactly on their EOS
                reason = ("eos"
                          if int(emitted[slot, m - 1]) == req.eos_id
                          else "length")
                out[req.rid] = Completion(
                    req.rid, list(int(t) for t in req.prompt),
                    self._slot_toks[slot], reason,
                    int(self._slot_admitted[slot]), step)
                self._slot_req[slot] = None
                self._slot_toks[slot] = []
                self._retire_paged_slot(slot)

    def tick(self, step: int = 0,
             fault_hook: Callable[[str, int | None], None] | None = None,
             ) -> TickResult:
        """One scheduler iteration: feed every mid-prefill slot a chunk,
        then run the slot-wise decode step if any slot is live.

        ``fault_hook(point, rid)`` is called before each jitted dispatch
        (``"chunk"`` per prefill slot, ``"decode"`` once) and may raise
        — by construction no host-side slot state has been mutated for
        that dispatch yet, so the state machine stays consistent and the
        driver can cancel/retry the victim and simply tick again.
        """
        out: dict[int, Completion] = {}
        dispatches = self._feed_prefills(step, out, fault_hook)
        decoded = False
        if self._active.any():
            if fault_hook is not None:
                fault_hook("decode", None)
            was_active = self._active.copy()
            if self.speculate_k > 0:
                self._decode_spec(step, out, was_active)
                events, self._events = self._events, []
                return TickResult(events, out, dispatches + 1, True)
            with self.engine.mesh_ctx():
                (self.states, tok, cache_index, keys, active, gen,
                 done) = self._step(*self._step_args())
            # writable host copies (np.asarray of a jax array is read-only)
            tok = np.array(tok)
            self._cur_tok = tok[:, None].astype(np.int32)
            self._cache_index = np.array(cache_index)
            self._keys = np.array(keys)
            self._active = np.array(active)
            self._gen = np.array(gen)
            done = np.asarray(done)

            for slot in np.nonzero(was_active)[0]:
                req = self._slot_req[slot]
                self._slot_toks[slot].append(int(tok[slot]))
                self._events.append((req.rid,
                                     len(self._slot_toks[slot]) - 1,
                                     int(tok[slot])))
                if done[slot]:
                    reason = ("eos" if int(tok[slot]) == req.eos_id
                              else "length")
                    out[req.rid] = Completion(
                        req.rid, list(int(t) for t in req.prompt),
                        self._slot_toks[slot], reason,
                        int(self._slot_admitted[slot]), step)
                    self._slot_req[slot] = None
                    self._slot_toks[slot] = []
                    if self.paged:
                        self._retire_paged_slot(slot)
            decoded = True
            dispatches += 1
        events, self._events = self._events, []
        return TickResult(events, out, dispatches, decoded)

    def _step_args(self) -> tuple:
        """The decode step's arguments for the current slot lanes."""
        args = (self.params, self.states, self._cur_tok, self._cache_index,
                self._keys, self._active, self._temp, self._eos, self._gen,
                self._max_toks)
        if self.paged:
            # the jitted step masks the table against `active` itself
            # (_mask_block_table), so non-decoding rows' writes land in
            # the trash block no matter what the host passes here;
            # shared_cols additionally trash-routes writes into
            # prefix-cache-shared columns (all zeros when prefix caching
            # is off — same compiled shape either way)
            args += (jnp.asarray(self._block_table),
                     jnp.asarray(self._shared_cols))
        return args

    def precompile(self) -> dict[str, jax.stages.Compiled]:
        """Compile the single-token decode step — and, with chunked
        prefill, the full-block chunk-prefill step — for the live slot
        pool before the first request arrives.  The serve loop's calls
        reuse these executables; ragged prompt tails still compile on
        first use.  Returns them by name (``"decode"``,
        ``"chunk_prefill"``) for inspection (``as_text()``,
        ``memory_analysis()``)."""
        with self.engine.mesh_ctx():
            out = {"decode": self._step.lower(*self._step_args()).compile()}
            if self.chunked_prefill:
                out["chunk_prefill"] = self._chunk_prefill.lower(
                    self.params, self.states,
                    jnp.zeros((1, self.block_size), jnp.int32),
                    jnp.int32(0),
                    jnp.zeros((1, self.table_width), jnp.int32),
                    jnp.int32(0), jnp.zeros((1,), jnp.int32)).compile()
        return out

    def _slot_of(self, rid: int) -> int | None:
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.rid == rid:
                return slot
        return None

    def cancel(self, rid: int, step: int = 0,
               reason: str = "cancelled") -> Completion | None:
        """Retire request ``rid`` mid-flight: deactivate its lane, free
        its slot and KV blocks, and return the partial completion
        (``truncated=True``; tokens generated so far, possibly none for
        a mid-prefill request).  Returns None if ``rid`` is not in
        flight.

        Co-batched requests are untouched — the cancelled row's lane
        was already isolated per step (active-masked bookkeeping,
        trash-routed KV writes via the zeroed table row, frozen
        recurrent rows), and slot reuse re-initialises state exactly as
        a natural retirement does.
        """
        slot = self._slot_of(rid)
        if slot is None:
            return None
        req = self._slot_req[slot]
        tokens = list(self._slot_toks[slot])
        self._active[slot] = False
        self._slot_req[slot] = None
        self._slot_toks[slot] = []
        self._prefills.pop(slot, None)
        if self.paged:
            self._retire_paged_slot(slot)
        return Completion(req.rid, list(int(t) for t in req.prompt),
                          tokens, reason, int(self._slot_admitted[slot]),
                          step, truncated=True)

    def drain(self, step: int = 0) -> dict[int, Completion]:
        """Retire every in-flight request, returning their partial
        ``Completion``s flagged ``truncated=True`` (finish reason
        ``"truncated"``) — teardown never silently loses accepted work.
        The caller is responsible for stopping admission first; after
        ``drain`` all slots and KV blocks are free and the scheduler
        serves the next trace cleanly."""
        out: dict[int, Completion] = {}
        for rid in self.in_flight():
            comp = self.cancel(rid, step, reason="truncated")
            if comp is not None:
                out[rid] = comp
        return out

    # -- the serve loop ----------------------------------------------------

    def run(self, requests: Sequence[Request],
            max_steps: int = 100_000) -> dict[int, Completion]:
        """Serve a trace of requests to completion.

        Requests are admitted FIFO within arrival order as slots free
        up.  Returns ``{rid: Completion}``; rids are assigned by
        position for requests that don't carry one.
        """
        taken = {r.rid for r in requests if r.rid is not None}
        if len(taken) != sum(r.rid is not None for r in requests):
            raise InvalidRequest("duplicate request rids")
        reqs = []
        next_rid = 0
        for r in requests:
            if r.rid is None:      # auto-assign, skipping explicit rids
                while next_rid in taken:
                    next_rid += 1
                r = dataclasses.replace(r, rid=next_rid)
                taken.add(next_rid)
            reqs.append(r)
        # validate the WHOLE trace before admitting anything: a raise
        # mid-run would strand live slots and lose the completed work
        # (`run` is re-entrant; stranded slots would leak into the next
        # trace's results)
        for r in reqs:
            self.validate_request(r)
        pending = deque(sorted(reqs, key=lambda r: r.arrival))
        ready: deque = deque()
        out: dict[int, Completion] = {}
        step = 0               # simulated clock (jumps over idle gaps)
        work_steps = 0         # decode/prefill dispatches performed

        while pending or ready or self._prefills or self._active.any():
            if work_steps > max_steps:
                raise SchedulerStalled(
                    f"scheduler exceeded max_steps={max_steps}")
            while pending and pending[0].arrival <= step:
                ready.append(pending.popleft())
            # FIFO admission: if the pool can't fund the head request
            # yet, nothing behind it jumps the queue
            while ready:
                if self.paged and not self.can_fund(ready[0]):
                    break
                if self._free_slot() is None:
                    break
                comp = self.start_request(ready.popleft(), step)
                if comp is not None:       # finished at prefill already
                    out[comp.rid] = comp

            res = self.tick(step)
            work_steps += res.dispatches
            out.update(res.completions)
            if not res.decoded:
                if self._prefills:
                    # prompts are still streaming in; no decode to run
                    # this iteration, but the clock advances
                    step += 1
                    continue
                # nothing decoding (the admission pass drained `ready`):
                # jump time to the next arrival
                if pending:
                    step = max(step + 1, pending[0].arrival)
                    continue
                break
            step += 1
        return out

    # -- introspection -----------------------------------------------------

    def kv_cache_bytes(self) -> int:
        """Bytes held by KV storage in the live decode-state tree
        (contiguous windows or the shared paged pool)."""
        return kv_pool.kv_cache_bytes(self.states)

    @property
    def prefix_cached_blocks(self) -> int:
        """Pool blocks currently pinned by the prefix index (0 when
        prefix caching is off)."""
        return self._prefix.cached_blocks if self._prefix else 0

    def flush_prefix_cache(self) -> int:
        """Drop every prefix-cache entry not pinned by a live request;
        returns blocks released.  After ``drain()`` + this, the
        allocator must be back to zero live blocks — the leak-freedom
        check the chaos suite pins."""
        return self._prefix.flush() if self._prefix else 0

    def spec_stats(self) -> dict[str, float]:
        """Lifetime speculative-decoding counters (all zero at k=0):
        spec dispatches run, active row-steps inside them, draft tokens
        proposed/accepted, tokens emitted, plus the two derived rates
        the monitor gauges track — ``acceptance_rate`` (accepted /
        proposed drafts) and ``advance_per_step`` (mean tokens emitted
        per active row per dispatch; > 1 means speculation is winning).
        """
        return {"steps": self._spec_steps,
                "rows": self._spec_rows,
                "proposed": self._spec_proposed,
                "accepted": self._spec_accepted,
                "emitted": self._spec_emitted,
                "acceptance_rate": (self._spec_accepted
                                    / max(1, self._spec_proposed)),
                "advance_per_step": (self._spec_emitted
                                     / max(1, self._spec_rows))}

    def prefix_stats(self) -> dict[str, int]:
        """Lifetime prefix-cache counters (all zero when off):
        admissions that skipped prefill work, prompt tokens skipped,
        shared-block attachments, entries and blocks currently held."""
        if self._prefix is None:
            return {"hits": 0, "tokens_skipped": 0, "blocks_shared": 0,
                    "entries": 0, "cached_blocks": 0}
        return {"hits": self._prefix.hits,
                "tokens_skipped": self._prefix.tokens_skipped,
                "blocks_shared": self._prefix.blocks_shared,
                "entries": len(self._prefix),
                "cached_blocks": self._prefix.cached_blocks}


# ---------------------------------------------------------------------------
# Synthetic workloads (arrival traces for benchmarks / the launcher)
# ---------------------------------------------------------------------------

def synthetic_workload(n_requests: int, vocab_size: int, *,
                       max_prompt: int = 8, max_new: int = 16,
                       min_prompt: int = 1, min_new: int = 1,
                       mean_interarrival: float = 0.0,
                       temperature_choices: Sequence[float] = (0.0, 0.7),
                       eos_rate: float = 0.25, seed: int = 0,
                       poisson_rate: float = 0.0,
                       priority_choices: Sequence[int] = (0,),
                       deadline_ms: float | None = None,
                       shared_prefix_len: int = 0,
                       ) -> list[Request]:
    """A seeded trace of requests with varied lengths/arrivals.

    Two arrival modes share this one generator (so the scheduler's step
    traces, the front-end's latency-under-load benches, and the chaos
    suite all draw from the same distribution):

      * ``mean_interarrival`` (legacy, in decode *steps*; 0 = a burst
        at t=0) — exponential gaps truncated to integer step indices,
        for ``ContinuousBatchingScheduler.run``'s simulated clock;
      * ``poisson_rate`` (requests per *second*, overrides the above) —
        a true Poisson arrival process: ``arrival_time`` carries the
        float wall-clock arrival for the async front-end, and
        ``arrival`` its integer-step shadow so the same trace still
        runs through ``run``.

    Prompt lengths are uniform in ``[min_prompt, max_prompt]`` and
    token budgets in ``[min_new, max_new]``.  ``eos_rate`` is the
    fraction of requests given a random EOS id
    (which may or may not ever be sampled — both paths are exercised);
    ``priority_choices``/``deadline_ms`` stamp the front-end metadata
    fields uniformly at random / uniformly on all requests.

    ``shared_prefix_len > 0`` models the multi-turn/system-prompt
    workload prefix caching targets: one fixed token prefix of that
    length is drawn per seed, and every prompt either *is* a slice of
    it (``plen <= shared_prefix_len`` — including full-prompt hits, the
    copy-on-write path) or extends it with a random tail — so traces
    exercise partial, exact, and divergent prefix matches.
    """
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab_size, size=shared_prefix_len).tolist() \
        if shared_prefix_len > 0 else []
    t = 0.0
    reqs = []
    for i in range(n_requests):
        if poisson_rate > 0:
            t += rng.exponential(1.0 / poisson_rate)
        elif mean_interarrival > 0:
            t += rng.exponential(mean_interarrival)
        plen = int(rng.integers(min_prompt, max_prompt + 1))
        eos = int(rng.integers(0, vocab_size)) \
            if rng.random() < eos_rate else -1
        if shared_prefix_len > 0:
            prompt = prefix[:plen] if plen <= shared_prefix_len else \
                prefix + rng.integers(
                    0, vocab_size,
                    size=plen - shared_prefix_len).tolist()
        else:
            prompt = rng.integers(0, vocab_size, size=plen).tolist()
        reqs.append(Request(
            prompt=prompt,
            max_tokens=int(rng.integers(min_new, max_new + 1)),
            temperature=float(rng.choice(list(temperature_choices))),
            eos_id=eos, seed=int(rng.integers(0, 2**31 - 1)),
            arrival=int(t), rid=i,
            arrival_time=float(t) if poisson_rate > 0 else None,
            priority=int(rng.choice(list(priority_choices))),
            deadline_ms=deadline_ms))
    return reqs


def oracle_completion(engine: ServeEngine, req: Request) -> list[int]:
    """The per-request oracle: run ``req`` alone through the per-token
    loop, then truncate at its EOS (inclusive).  The scheduler must
    reproduce this token list exactly for every request in any trace."""
    prompt = jnp.asarray(list(req.prompt), jnp.int32)[None]
    full = engine.generate_loop(prompt, req.max_tokens,
                                temperature=req.temperature, seed=req.seed)
    gen = [int(t) for t in np.asarray(full)[0, prompt.shape[1]:]]
    if req.eos_id >= 0 and req.eos_id in gen:
        gen = gen[:gen.index(req.eos_id) + 1]
    return gen
