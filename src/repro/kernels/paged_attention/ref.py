"""Pure-jnp oracle for the paged-attention decode kernel.

This mirrors — op for op, in the same order — the XLA composition the
serving stack runs by default (``models.attention``'s paged branch:
``_paged_update_and_gather`` followed by ``_plain_attention``), so the
kernel's property tests pin bitwise equality against the exact graphs
the scheduler equivalence suites already trust.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def paged_attention_ref(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                        k_pool: jax.Array, v_pool: jax.Array,
                        block_table: jax.Array, write_table: jax.Array,
                        cache_index: jax.Array, *,
                        kv_len: int | None = None, softcap: float = 0.0,
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Scatter + gather + plain-softmax attention over the block pool.

    q: [B, S, KV, G, hd]; k_new/v_new: [B, S, KV, hd];
    k_pool/v_pool: [NB, bs, KV, hd]; block_table/write_table: [B, W]
    int32 (0 = trash block); cache_index: [B] int32.  Returns the
    updated pools and the [B, S, KV, G, hd] attention output (v dtype).
    """
    b, s = k_new.shape[:2]
    bs = k_pool.shape[1]
    w = block_table.shape[1]
    pos = cache_index[:, None] + jnp.arange(s)[None, :]            # [B, S]
    slot_col = jnp.clip(pos // bs, 0, w - 1)
    phys = jnp.take_along_axis(write_table, slot_col, axis=1)      # [B, S]
    off = pos % bs
    k_pool = k_pool.at[phys, off].set(k_new.astype(k_pool.dtype))
    v_pool = v_pool.at[phys, off].set(v_new.astype(v_pool.dtype))
    kvh, hd = k_pool.shape[2:]
    k_all = k_pool[block_table].reshape(b, w * bs, kvh, hd)
    v_all = v_pool[block_table].reshape(b, w * bs, kvh, hd)
    if kv_len is not None and kv_len < w * bs:
        k_all = k_all[:, :kv_len]
        v_all = v_all[:, :kv_len]
    kpos = jnp.arange(k_all.shape[1])
    mask = kpos[None, None, :] <= pos[..., None]                   # [B,S,T]
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bskgd,btkd->bksgt", q, k_all,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None, :, None, :], scores, NEG_INF)
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - jax.lax.stop_gradient(m))
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    out = jnp.einsum("bksgt,btkd->bskgd", probs.astype(v_all.dtype), v_all,
                     preferred_element_type=jnp.float32)
    return k_pool, v_pool, out.astype(v_all.dtype)
