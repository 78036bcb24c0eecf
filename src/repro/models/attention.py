"""GQA attention with KV cache, RoPE, optional biases, cross-attention,
and a chunked (online-softmax) path for long prefill.

The attention score/value matmuls are *dynamic* products: per the paper's
§5.2 mapping they never route through the PUM path — only the Q/K/V/O
projections (static weights) do.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.core import ibert
from repro.dist.sharding import shard_act, tp_serving
from repro.kernels import registry as _kreg
from repro.kernels.paged_attention import ops as _paops
from repro.models import layers

Params = dict[str, Any]

NEG_INF = -1e30
CHUNK_Q = 1024          # online-softmax query block
CHUNK_K = 1024          # online-softmax key block

# Module-level alias: the kernel-dispatch mutation self-test knocks this
# out with an XLA shim to prove the auditor notices a decode step
# silently falling back off the Pallas path (analysis/mutations.py).
_paged_attention = _paops.paged_attention

# The kernel keeps the whole [S, T] score tile per row resident; decode
# (S=1) and small chunk-prefill steps qualify, long chunks stay on the
# XLA composition.
_KERNEL_MAX_S = 64


def init_attention(key, cfg: ModelConfig, cross: bool = False) -> Params:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": layers.linear_init(kq, d, cfg.num_heads * hd, cfg.qkv_bias),
        "wk": layers.linear_init(kk, d, cfg.num_kv_heads * hd, cfg.qkv_bias),
        "wv": layers.linear_init(kv, d, cfg.num_kv_heads * hd, cfg.qkv_bias),
        "wo": layers.linear_init(ko, cfg.num_heads * hd, d),
    }


def is_paged_cache(state: Any) -> bool:
    """A paged KV pool (``make_paged_cache``), as opposed to a
    contiguous cache or a recurrent state."""
    return isinstance(state, dict) and "k_pool" in state


def make_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, max_len, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, hd), dtype),
    }


def make_paged_cache(cfg: ModelConfig, num_groups: int, num_blocks: int,
                     block_size: int, dtype=jnp.bfloat16) -> Params:
    """One shared pool of KV blocks instead of per-slot windows.

    ``[num_groups, num_blocks, block_size, kv_heads * head_dim]``: the
    layer-group stack, then *physical* blocks (the reserved trash block
    at id 0 included — ``serve.kv_pool`` allocates usable ids from 1),
    with the KV-head axis folded into the lanes, the layout the
    paged-attention kernel DMAs.  Slots address it through a per-slot
    block table; there is no batch axis — that's the whole point.
    """
    shape = (num_groups, num_blocks, block_size,
             cfg.num_kv_heads * cfg.resolved_head_dim)
    return {"k_pool": jnp.zeros(shape, dtype),
            "v_pool": jnp.zeros(shape, dtype)}


def cache_shape(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> Params:
    hd = cfg.resolved_head_dim
    sds = jax.ShapeDtypeStruct
    return {"k": sds((batch, max_len, cfg.num_kv_heads, hd), dtype),
            "v": sds((batch, max_len, cfg.num_kv_heads, hd), dtype)}


def _softmax(scores: jax.Array, softcap: float) -> jax.Array:
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - jax.lax.stop_gradient(m))
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _plain_attention(q, k, v, mask, softcap, ibert_mode=False):
    """q: [B,S,KV,G,hd]; k/v: [B,T,KV,hd]; mask: [S,T] shared across the
    batch, or [B,S,T] per-row (slot-wise decode at per-slot depths)."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bskgd,btkd->bksgt", q, k,
                        preferred_element_type=jnp.float32) * scale
    m = mask[None, None, :, None, :] if mask.ndim == 2 \
        else mask[:, None, :, None, :]
    scores = jnp.where(m, scores, NEG_INF)
    if ibert_mode:
        probs = ibert.softmax_quantized(scores.astype(jnp.float32), bits=8,
                                        axis=-1)
    else:
        probs = _softmax(scores, softcap)
    out = jnp.einsum("bksgt,btkd->bskgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def _chunked_attention(q, k, v, q_offset, softcap):
    """Online-softmax attention: O(S*T) compute with O(chunk) score memory.

    q: [B,S,KV,G,hd] (queries at absolute positions q_offset + [0, S));
    k/v: [B,T,KV,hd]. Causal. Returns [B,S,KV,G,hd].
    """
    b, s, kvh, g, hd = q.shape
    t = k.shape[1]
    scale = 1.0 / np.sqrt(hd)
    nq = -(-s // CHUNK_Q)
    nk = -(-t // CHUNK_K)
    pad_q = nq * CHUNK_Q - s
    pad_k = nk * CHUNK_K - t
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    qc = q.reshape(b, nq, CHUNK_Q, kvh, g, hd).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(b, nk, CHUNK_K, kvh, hd)
    vc = v.reshape(b, nk, CHUNK_K, kvh, hd)

    q_pos_base = jnp.arange(CHUNK_Q)
    k_pos_base = jnp.arange(CHUNK_K)

    def per_q_chunk(qi, qblk):
        # qblk: [B, CQ, KV, G, hd]
        m0 = jnp.full((b, kvh, g, CHUNK_Q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, CHUNK_Q), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, CHUNK_Q, hd), jnp.float32)

        def body(carry, ki):
            m, l, acc = carry
            kblk = jax.lax.dynamic_index_in_dim(kc, ki, 1, keepdims=False)
            vblk = jax.lax.dynamic_index_in_dim(vc, ki, 1, keepdims=False)
            sc = jnp.einsum("bqkgd,btkd->bkgqt", qblk, kblk,
                            preferred_element_type=jnp.float32) * scale
            if softcap > 0:
                sc = jnp.tanh(sc / softcap) * softcap
            qpos = q_offset + qi * CHUNK_Q + q_pos_base
            kpos = ki * CHUNK_K + k_pos_base
            causal = qpos[:, None] >= kpos[None, :]
            valid = kpos[None, :] < t
            sc = jnp.where((causal & valid)[None, None, None], sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p.astype(vblk.dtype), vblk
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(0, 3, 1, 2, 4)          # [B, CQ, KV, G, hd]

    outs = jax.lax.map(lambda args: per_q_chunk(args[0], args[1]),
                       (jnp.arange(nq), qc))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * CHUNK_Q, kvh, g,
                                                   hd)
    return out[:, :s]


def paged_write_cells(write_table: jax.Array, cache_index: jax.Array,
                      s: int, block_size: int
                      ) -> tuple[jax.Array, jax.Array]:
    """The (physical block, in-block offset) each of a row's next ``s``
    logical positions scatters into.

    ``write_table``: [B, W] physical block ids; ``cache_index``: [B]
    int32 first position.  Positions past the table width — reachable
    only by speculative draft tokens probing beyond a slot's funded
    window — route to the trash block (id 0), exactly like inactive
    rows, instead of wrapping into the slot's own last live block.
    Returns ``(phys, off)``, both [B, S].
    """
    b, w = write_table.shape
    pos = cache_index[:, None] + jnp.arange(s, dtype=cache_index.dtype)
    cols = pos // block_size
    phys = jnp.take_along_axis(write_table, jnp.clip(cols, 0, w - 1),
                               axis=1)
    phys = jnp.where(cols < w, phys, jnp.zeros((), phys.dtype))
    return phys, pos % block_size


def _paged_update_and_gather(cache: Params, layer: jax.Array, k: jax.Array,
                             v: jax.Array, block_table: jax.Array,
                             cache_index: jax.Array, kv_len: int | None,
                             write_table: jax.Array | None = None,
                             ) -> tuple[Params, jax.Array, jax.Array,
                                        jax.Array]:
    """Scatter this step's K/V through the block table into layer
    ``layer`` of the shared stacked pool, then gather each row's logical
    cache view back out of that layer.

    k/v: [B, S, KV, hd] new entries for rows starting at positions
    ``cache_index`` ([B] int32).  The pools are [L, NB, bs, KV * hd]
    (``make_paged_cache``).  ``block_table``: [B, W] physical block ids
    (0 = the trash block: empty/retired rows write there and their
    garbage is never attended).  Returns the updated cache, the gathered
    [B, T, KV, hd] views, and the [B, S] absolute query positions.

    ``write_table`` (default: the block table itself) addresses the
    *scatter* only: prefix caching passes a copy whose shared read-only
    columns are re-routed to the trash block
    (``kv_pool._mask_shared_cols``), so a slot can attend another
    request's cached prefix blocks without ever being able to write
    into them — the gather always uses the real ``block_table``.

    ``kv_len`` crops the gathered view from ``W * block_size`` back to
    the engine's window so the attention reduction shapes — hence the
    compiled reduction order, hence bitwise numerics — match the
    contiguous cache exactly.
    """
    b, s, kvh, hd = k.shape
    bs = cache["k_pool"].shape[2]
    w = block_table.shape[1]
    if write_table is None:
        write_table = block_table
    pos = cache_index[:, None] + jnp.arange(s)[None, :]            # [B, S]
    phys, off = paged_write_cells(write_table, cache_index, s, bs)
    with jax.named_scope("kv_pool_write"):
        k_pool = cache["k_pool"].at[layer, phys, off].set(
            k.reshape(b, s, kvh * hd).astype(cache["k_pool"].dtype))
        v_pool = cache["v_pool"].at[layer, phys, off].set(
            v.reshape(b, s, kvh * hd).astype(cache["v_pool"].dtype))
    # tensor-parallel serving: the pool shards its folded lanes over
    # ``model`` (whole KV heads per shard: KV % tp == 0) and the
    # gathered per-row views their KV-head axis, so both the scatter
    # and the block-table gather stay device-local (each shard owns the
    # whole pool for its heads); no-ops without an active mesh
    k_pool = shard_act(k_pool, None, None, None, "model")
    v_pool = shard_act(v_pool, None, None, None, "model")
    k_all = k_pool[layer, block_table].reshape(b, w * bs, kvh, hd)
    v_all = v_pool[layer, block_table].reshape(b, w * bs, kvh, hd)
    if kv_len is not None and kv_len < w * bs:
        k_all = k_all[:, :kv_len]
        v_all = v_all[:, :kv_len]
    k_all = shard_act(k_all, "data", None, "model", None)
    v_all = shard_act(v_all, "data", None, "model", None)
    return {"k_pool": k_pool, "v_pool": v_pool}, k_all, v_all, pos


def attention(p: Params, x: jax.Array, cfg: ModelConfig, *,
              positions: jax.Array,
              cache: Params | None = None,
              cache_index: jax.Array | None = None,
              cross_kv: tuple[jax.Array, jax.Array] | None = None,
              use_rope: bool = True,
              block_table: jax.Array | None = None,
              kv_len: int | None = None,
              write_table: jax.Array | None = None,
              pool_layer: jax.Array | int | None = None,
              ) -> tuple[jax.Array, Params | None]:
    """x: [B, S, D].  Modes:
      * train/prefill (cache None, cross_kv None): causal self-attention;
        chunked online-softmax when S > 2*CHUNK_Q.
      * decode (cache set): writes K/V at cache_index, attends over cache.
        ``cache_index`` may be a [B] vector — continuous batching, where
        every slot sits at a different cache depth (write, RoPE position
        and causal mask are then all per-row).
      * paged decode (cache holds ``k_pool``/``v_pool`` and
        ``block_table`` is set): same semantics, but rows address one
        shared block pool through their block-table row instead of a
        private contiguous window.  The pools are the whole layer stack
        (``make_paged_cache``) and ``pool_layer`` picks this layer's
        slice, read and written in place.  ``kv_len`` is the engine
        window the gathered view is cropped to (bit-exactness vs the
        contiguous cache).
      * cross attention (cross_kv set): encoder-decoder attention.
    """
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    kvh = cfg.num_kv_heads
    g = cfg.num_heads // kvh
    pum = cfg.pum

    q = layers.linear(p["wq"], x, pum).reshape(b, s, kvh, g, hd)
    if cross_kv is None:
        k = layers.linear(p["wk"], x, pum).reshape(b, s, kvh, hd)
        v = layers.linear(p["wv"], x, pum).reshape(b, s, kvh, hd)
        if use_rope:
            cos, sin = layers.rope_tables(positions, hd, cfg.rope_theta)
            q = apply_rope_gqa(q, cos, sin)
            k = layers.apply_rope(k, cos, sin)
    else:
        k, v = cross_kv

    if cache is not None and "k_pool" in cache and cross_kv is None:
        # paged decode / chunked prefill: per-row (block, offset) scatter
        # and block-table gather over the shared pool
        cache_index = jnp.asarray(cache_index)
        assert cache_index.ndim == 1, \
            "paged attention is slot-wise: cache_index must be [B]"
        assert block_table is not None and pool_layer is not None, \
            "paged attention requires a block_table and a pool_layer"
        # the paged path reduces with plain softmax: beyond this the
        # contiguous oracle switches to online-softmax (_chunked_attention,
        # a different reduction order) and the [B,S,T] score tensor stops
        # being small — stream longer prompts in block-size chunks instead
        assert s <= 2 * CHUNK_Q, \
            f"paged prefill chunk of {s} tokens exceeds {2 * CHUNK_Q}; " \
            f"enable chunked_prefill to stream long prompts"
        backend = _kreg.get_backend("paged_attention")
        if (backend not in (None, _kreg.KernelBackend.XLA)
                and not tp_serving() and not pum.ibert
                and s <= _KERNEL_MAX_S):
            # fused kernel: block-table walk (scatter through the write
            # table, gather through the read table) + plain-softmax
            # attention in one pallas_call, bit-identical to the
            # composition below for scheduler-reachable states
            with jax.named_scope("paged_attn_kernel"):
                kp, vp, out = _paged_attention(
                    q, k, v, cache["k_pool"], cache["v_pool"],
                    block_table,
                    write_table if write_table is not None
                    else block_table,
                    cache_index, pool_layer, kv_len=kv_len,
                    softcap=cfg.attn_logit_softcap, backend=backend)
            cache = {**cache, "k_pool": kp, "v_pool": vp}
        else:
            cache, k_all, v_all, qpos = _paged_update_and_gather(
                cache, pool_layer, k, v, block_table, cache_index, kv_len,
                write_table=write_table)
            kpos = jnp.arange(k_all.shape[1])
            mask = kpos[None, None, :] <= qpos[..., None]          # [B,S,T]
            out = _plain_attention(q, k_all, v_all, mask,
                                   cfg.attn_logit_softcap,
                                   ibert_mode=pum.ibert)
    elif cache is not None and cross_kv is None:
        # decode/prefill-into-cache: write the new K/V at cache_index —
        # a scalar (whole batch at one depth) or a [B] vector (slot-wise
        # decode: each row writes/attends at its own depth)
        cache_index = jnp.asarray(cache_index)
        per_slot = cache_index.ndim == 1
        if per_slot:
            def upd(c, new):
                return jax.vmap(
                    lambda row, n, i: jax.lax.dynamic_update_slice_in_dim(
                        row, n, i, axis=0)
                )(c, new.astype(c.dtype), cache_index)
        else:
            def upd(c, new):
                return jax.lax.dynamic_update_slice_in_dim(
                    c, new.astype(c.dtype), cache_index, axis=1)
        with jax.named_scope("kv_cache_write"):
            k_cache = upd(cache["k"], k)
            v_cache = upd(cache["v"], v)
        if tp_serving():
            # pin the serving cache's steady-state layout (KV heads over
            # model) so per-token updates never drift the sharding; the
            # training/dry-run flows keep decode_state_specs' placement
            k_cache = shard_act(k_cache, "data", None, "model", None)
            v_cache = shard_act(v_cache, "data", None, "model", None)
        cache = {"k": k_cache, "v": v_cache}
        t = k_cache.shape[1]
        if s > 2 * CHUNK_Q:
            # long prefill into a cache: chunked online softmax (prefill
            # is always per-request here, so the offset is a scalar)
            assert not per_slot, \
                "chunked prefill expects a scalar cache_index"
            out = _chunked_attention(q, k_cache, v_cache, cache_index,
                                     cfg.attn_logit_softcap)
        else:
            kpos = jnp.arange(t)
            if per_slot:
                qpos = cache_index[:, None] + jnp.arange(s)[None, :]
                mask = kpos[None, None, :] <= qpos[..., None]   # [B,S,T]
            else:
                mask = (kpos[None, :]
                        <= cache_index + jnp.arange(s)[:, None])
            out = _plain_attention(q, k_cache, v_cache, mask,
                                   cfg.attn_logit_softcap,
                                   ibert_mode=pum.ibert)
    elif cross_kv is not None:
        t = k.shape[1]
        mask = jnp.ones((s, t), bool)
        out = _plain_attention(q, k, v, mask, cfg.attn_logit_softcap,
                               ibert_mode=pum.ibert)
    else:
        if s > 2 * CHUNK_Q:
            out = _chunked_attention(q, k, v, 0, cfg.attn_logit_softcap)
        else:
            mask = jnp.tril(jnp.ones((s, s), bool))
            out = _plain_attention(q, k, v, mask, cfg.attn_logit_softcap,
                                   ibert_mode=pum.ibert)

    out = out.astype(x.dtype).reshape(b, s, cfg.num_heads * hd)
    out = shard_act(out, "data", None, "model")
    return layers.linear(p["wo"], out, pum), cache


def apply_rope_gqa(q: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """q: [B, S, KV, G, hd]."""
    b, s, kvh, g, hd = q.shape
    q2 = q.reshape(b, s, kvh * g, hd)
    q2 = layers.apply_rope(q2, cos, sin)
    return q2.reshape(b, s, kvh, g, hd)
