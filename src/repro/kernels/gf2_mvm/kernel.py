"""Pallas TPU kernel: GF(2) matrix-vector multiply (parity matmul).

The AES linear layer (ShiftRows ∘ MixColumns) is linear over GF(2) on the
128-bit state, so one binary 128x128 MVM + parity implements both steps —
exactly the paper's §5.3 insight that only the low bit of each bitline
count is needed ahead of the XOR (early-terminated ADCs in hardware; a
final ``& 1`` here).

Computes  out[M, N] (int8, {0,1}) = (x[M, K] @ a[K, N]) & 1
with x, a in {0,1} int8.  The MXU does the popcount as an int matmul; the
parity mask is fused in the epilogue (never materialising counts in HBM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gf2_mvm_kernel(x_ref, a_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], a_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        # parity epilogue == the paper's 1-bit ADC read-out + XOR combine
        o_ref[...] = (acc_ref[...] & 1).astype(jnp.int8)


def gf2_mvm_pallas(x: jax.Array, a: jax.Array, *, block_m: int = 128,
                   block_n: int = 128, block_k: int = 128,
                   interpret: bool) -> jax.Array:
    """x: [M, K] int8 {0,1}; a: [K, N] int8 {0,1} -> [M, N] int8 {0,1}."""
    m, k = x.shape
    k2, n = a.shape
    assert k == k2
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0
    k_steps = k // block_k
    grid = (m // block_m, n // block_n, k_steps)
    kernel = functools.partial(_gf2_mvm_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gf2_mvm",
    )(x, a)
