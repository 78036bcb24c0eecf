"""Public wrapper for the paged-attention decode kernel, dispatched
through :mod:`repro.kernels.registry` (xla oracle / pallas / interpret).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import registry
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.registry import KernelBackend


@functools.partial(jax.jit, static_argnames=("kv_len", "softcap",
                                             "backend"))
def _paged_attention_impl(q, k_new, v_new, k_pool, v_pool, block_table,
                          write_table, cache_index, layer, *, kv_len,
                          softcap, backend):
    if backend == KernelBackend.XLA:
        return paged_attention_ref(
            q, k_new, v_new, k_pool, v_pool, block_table, write_table,
            cache_index, layer, kv_len=kv_len, softcap=softcap)
    return paged_attention_pallas(
        q, k_new, v_new, k_pool, v_pool, block_table, write_table,
        cache_index, layer, kv_len=kv_len, softcap=softcap,
        interpret=backend == KernelBackend.INTERPRET)


def paged_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                    k_pool: jax.Array, v_pool: jax.Array,
                    block_table: jax.Array, write_table: jax.Array,
                    cache_index: jax.Array, layer: jax.Array | int, *,
                    kv_len: int | None = None, softcap: float = 0.0,
                    backend: KernelBackend | str | None = None,
                    interpret: bool | None = None,
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Paged decode attention: in-kernel block-table walk (scatter this
    step's K/V through the write table, gather through the read table,
    plain-softmax attention), bit-identical to the XLA composition.

    q: [B, S, KV, G, hd]; k_new/v_new: [B, S, KV, hd];
    k_pool/v_pool: the stacked [L, NB, bs, KV * hd] pools;
    block_table/write_table: [B, W] int32; cache_index: [B] int32;
    layer: the pool layer (int32 scalar) this call reads and writes.
    Returns (k_pool, v_pool, out[B, S, KV, G, hd]); the pools are
    donated (aliased) on the kernel backends, and every other layer
    comes back as it went in.
    """
    backend = registry.resolve_backend(backend, kernel="paged_attention",
                                       interpret=interpret)
    return _paged_attention_impl(
        q, k_new, v_new, k_pool, v_pool, block_table, write_table,
        cache_index, layer, kv_len=kv_len, softcap=softcap,
        backend=backend)
