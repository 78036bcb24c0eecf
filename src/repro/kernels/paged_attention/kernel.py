"""Pallas TPU kernel: paged-attention decode over the shared block pool.

One grid program per batch row, walking the row's block table entirely
in-kernel — the DARTH-PUM argument applied to the serving memory system:
instead of materialising a gathered ``[B, T, KV, hd]`` KV view in HBM
every step (the XLA composition's gather) and scattering the new token
through a separate indexed update, the kernel

  * translates the row's ``cache_index`` to (block, offset) coordinates
    and DMAs this step's K/V rows into the pool through the *write*
    table (whose prefix-cache-shared columns are trash-routed — the
    read-only masking happens at the kernel's store address computation,
    never as a separate pool pass);
  * DMAs the row's logical KV view block-by-block through the *read*
    table into a VMEM scratch (trash blocks — id 0 — are gathered like
    any other and their garbage eliminated by the causal position mask,
    exactly as in the oracle);
  * runs the plain-softmax attention for the row, one KV head at a time,
    mirroring ``models.attention._plain_attention`` op for op (f32
    scores, f32 P·V accumulation) so the result is bit-identical to the
    XLA composition.

The pools stay in HBM (``memory_space=ANY``) and enter as
``input_output_aliases``'d outputs: the kernel read-modify-writes them
in place (the gather DMAs start after the row's own write DMAs have
landed — the decode token attends itself).  Only one row's ``T``
positions are ever resident in VMEM, so the pool can be as large as HBM
allows.  The kernel sees each pool as ``[NB, bs, KV * hd]`` — the same
bytes with the head axis folded into the lanes — so every DMA moves
lane-dense rows.  The grid axis is ``arbitrary`` (sequential): rows'
stores target disjoint physical blocks except the trash block, whose
content is never attended.

Guarantee boundary: bit-identity with the oracle holds for every
scheduler-reachable state — an *active* row's causally-visible
positions always map to allocated (non-trash) blocks in both tables, so
its output depends only on real blocks plus its own stores.  Rows whose
visible range is trash-backed (inactive slots, whose outputs the
scheduler discards) may read different garbage than the oracle: the
kernel's row ``b`` gathers before rows ``> b`` store, while the oracle
gathers after *all* stores, so colliding trash-offset writes are
observed at different times.  Trash content is not part of the
contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attention_kernel(idx_ref, table_ref, wtable_ref, q_ref, kn_ref,
                            vn_ref, kp_in_ref, vp_in_ref, kp_ref, vp_ref,
                            o_ref, k_scr, v_scr, sem, *, s_len: int,
                            bs: int, w: int, t: int, kvh: int, hd: int,
                            softcap: float):
    """One batch row.  kp_ref/vp_ref alias the input pools (kp_in_ref /
    vp_in_ref are the pre-aliasing handles, unused: all DMAs go through
    the aliased refs so a row's gather sees its own stores)."""
    del kp_in_ref, vp_in_ref
    b = pl.program_id(0)
    base = idx_ref[b]

    def block_copy(col, to_pool, wait):
        """Start (or wait for) the K and V DMAs of one table column:
        column ``c`` of the row's VMEM view is ``scr[c * bs:(c + 1) *
        bs]``, read through the read table and written back through the
        write table."""
        phys = (wtable_ref if to_pool else table_ref)[b, col]
        rows = pl.ds(pl.multiple_of(col * bs, bs), bs)
        for pool_ref, scr in ((kp_ref, k_scr), (vp_ref, v_scr)):
            src, dst = pool_ref.at[phys], scr.at[rows]
            if to_pool:
                src, dst = dst, src
            cp = pltpu.make_async_copy(src, dst, sem)
            if wait:
                cp.wait()
            else:
                cp.start()

    # -- gather: walk the read table, one DMA per column and pool
    for wait in (False, True):
        jax.lax.fori_loop(
            0, w, lambda c, _, wait=wait: block_copy(c, False, wait), None)

    # -- write: per-token cache_index -> (block, offset).  Each token
    # lands in the VMEM view where the oracle's gather would see it (only
    # if the write table routes it to the block the read table reads:
    # prefix-cache-shared columns are trash-routed, so their readers keep
    # the cached K/V), then every touched column goes back to the pool
    # through the write table — the kernel-side kv_pool_write.
    kn = kn_ref[0]                                      # [S, KV * hd]
    vn = vn_ref[0]
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
    for si in range(s_len):
        pos = base + si
        col = jnp.clip(pos // bs, 0, w - 1)
        hit = (row_ids == pos % bs) & (wtable_ref[b, col] == table_ref[b, col])
        rows = pl.ds(pl.multiple_of(col * bs, bs), bs)
        for new, scr in ((kn, k_scr), (vn, v_scr)):
            scr[rows] = jnp.where(hit, new[si:si + 1], scr[rows])
    first = jnp.clip(base // bs, 0, w - 1)
    last = jnp.clip((base + s_len - 1) // bs, 0, w - 1)
    for wait in (False, True):
        for j in range((s_len - 1) // bs + 2):
            pl.when(first + j <= last)(
                functools.partial(block_copy, first + j, True, wait))

    # -- attention per KV head, mirroring _plain_attention op for op
    # (bit-exactness): f32 scores, scale, causal mask, plain softmax,
    # probs cast to the value dtype, f32 P·V accumulation
    scale = 1.0 / np.sqrt(hd)
    qpos = base + jax.lax.broadcasted_iota(jnp.int32, (s_len, 1, t), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (s_len, 1, t), 2)
    mask = kpos <= qpos                                 # [S, 1, T] causal
    for h in range(kvh):
        lanes = slice(h * hd, (h + 1) * hd)
        k_h = k_scr[:t, lanes]                          # [T, hd]
        v_h = v_scr[:t, lanes]
        q_h = q_ref[0, :, h]                            # [S, G, hd]
        scores = jnp.einsum("sgd,td->sgt", q_h, k_h,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(mask, scores, NEG_INF)
        if softcap > 0:
            scores = jnp.tanh(scores / softcap) * softcap
        m = jnp.max(scores, axis=-1, keepdims=True)
        e = jnp.exp(scores - jax.lax.stop_gradient(m))
        probs = e / jnp.sum(e, axis=-1, keepdims=True)
        out = jnp.einsum("sgt,td->sgd", probs.astype(v_h.dtype), v_h,
                         preferred_element_type=jnp.float32)
        o_ref[0, :, h] = out.astype(o_ref.dtype)


def paged_attention_pallas(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                           k_pool: jax.Array, v_pool: jax.Array,
                           block_table: jax.Array, write_table: jax.Array,
                           cache_index: jax.Array, *,
                           kv_len: int | None = None, softcap: float = 0.0,
                           interpret: bool,
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """q: [B,S,KV,G,hd]; k_new/v_new: [B,S,KV,hd]; pools: [NB,bs,KV,hd];
    tables: [B,W] int32; cache_index: [B] int32.  Returns (k_pool,
    v_pool, out[B,S,KV,G,hd]) with the pools updated in place (aliased).
    ``interpret`` runs the body through the Pallas interpreter (any
    backend) instead of compiling it for the TPU.
    """
    b, s_len, kvh, g, hd = q.shape
    nb, bs = k_pool.shape[:2]
    w = block_table.shape[1]
    t = w * bs if kv_len is None else min(kv_len, w * bs)
    lanes = kvh * hd
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    row = pl.BlockSpec((1, s_len, lanes), lambda i: (i, 0, 0))
    qspec = pl.BlockSpec((1, s_len, kvh, g, hd), lambda i: (i, 0, 0, 0, 0))

    kernel = functools.partial(_paged_attention_kernel, s_len=s_len, bs=bs,
                               w=w, t=t, kvh=kvh, hd=hd, softcap=softcap)
    kp, vp, out = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[smem, smem, smem,          # cache_index, tables
                  qspec, row, row,           # q, k_new, v_new
                  hbm, hbm],                 # k_pool, v_pool
        out_specs=(hbm, hbm, qspec),
        out_shape=(
            jax.ShapeDtypeStruct((nb, bs, lanes), k_pool.dtype),
            jax.ShapeDtypeStruct((nb, bs, lanes), v_pool.dtype),
            jax.ShapeDtypeStruct((b, s_len, kvh, g, hd), v_pool.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((w * bs, lanes), k_pool.dtype),
                        pltpu.VMEM((w * bs, lanes), v_pool.dtype),
                        pltpu.SemaphoreType.DMA],
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(cache_index, block_table, write_table, q,
      k_new.reshape(b, s_len, lanes).astype(k_pool.dtype),
      v_new.reshape(b, s_len, lanes).astype(v_pool.dtype),
      k_pool.reshape(nb, bs, lanes), v_pool.reshape(nb, bs, lanes))
    return kp.reshape(k_pool.shape), vp.reshape(v_pool.shape), out
