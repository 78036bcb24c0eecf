"""Pallas TPU kernel: bit-sliced integer MVM with fused shift-and-add.

This is the TPU-native realisation of the DARTH-PUM ACE + shift-unit
pipeline (paper §4.1).  The analog crossbar's role (many small integer
MACs) maps onto the MXU; the paper's key optimisation — recombining
bit-sliced partial products *during* the data transfer instead of as a
separate write/shift/add phase — maps to fusing the shift-and-add into the
matmul epilogue so per-plane partial products never round-trip to HBM.

Computes  out[M,N] (int32) = sum_s (x[M,K] @ w_planes[s,K,N]) << (M_BITS*s)

with x int8 (quantised activations) and w_planes int8 (differential
bit-planes of the quantised weights, values in [-(2^m-1), 2^m-1]).

Tiling: grid (M/bm, N/bn, K/bk); the K axis is the innermost (arbitrary)
dimension accumulating into a VMEM scratch accumulator; all S planes are
processed per K-step so the recombination happens while the X/W tiles are
resident in VMEM.  MXU-aligned tiles (multiples of 128 on the contracted
and lane dimensions).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bitslice_mvm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_slices: int,
                         bits_per_slice: int, k_steps: int):
    """One (i, j, k) grid step.

    x_ref: [bm, bk] int8      — activation tile
    w_ref: [S, bk, bn] int8   — all weight planes for this (k, j) tile
    o_ref: [bm, bn] int32     — output tile (written at the last k step)
    acc_ref: [bm, bn] int32   — VMEM accumulator scratch
    """
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    acc = acc_ref[...]
    # shift-and-add recombination fused into the contraction epilogue:
    # each plane's partial product is shifted by its bit position and
    # accumulated immediately (never materialised in HBM).
    for s in range(n_slices):
        part = jax.lax.dot_general(
            x, w_ref[s],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc = acc + (part << (s * bits_per_slice))
    acc_ref[...] = acc

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def _bitslice_mvm_scaled_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *,
                                n_slices: int, bits_per_slice: int,
                                k_steps: int):
    """The fused decode tile: the same shift-and-add contraction, with
    the per-row dequant scale applied in the epilogue.

    s_ref: [bm, 1] f32 — one scale per activation row (``xs * w.scale``).
    o_ref: [bm, bn] f32 — ``acc.astype(f32) * s`` written at the last k
    step; the int32 accumulator never leaves VMEM (the paper's
    recombine-during-transfer argument extended one stage further: the
    DCE's dequant multiply also happens before the result ever
    round-trips to HBM).
    """
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    acc = acc_ref[...]
    for s in range(n_slices):
        part = jax.lax.dot_general(
            x, w_ref[s],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc = acc + (part << (s * bits_per_slice))
    acc_ref[...] = acc

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        # dequant epilogue: the same int32->f32 convert + f32 multiply
        # the unfused path performs, so the fused result is bit-identical
        o_ref[...] = acc_ref[...].astype(jnp.float32) * s_ref[...]


def bitslice_mvm_pallas(x: jax.Array, w_planes: jax.Array, *,
                        bits_per_slice: int,
                        block_m: int = 128, block_n: int = 128,
                        block_k: int = 128,
                        interpret: bool) -> jax.Array:
    """x: [M, K] int8; w_planes: [S, K, N] int8 -> [M, N] int32.

    M, K, N must be multiples of the block sizes (ops.py pads).
    ``interpret`` runs the kernel body through the Pallas interpreter
    (any backend) instead of compiling it for the TPU.
    """
    s, k, n = w_planes.shape
    m = x.shape[0]
    assert x.shape[1] == k
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0, (
        (m, k, n, block_m, block_k, block_n))
    # adaptive M grid: ops.py shrinks block_m to the padded row count for
    # small-M (decode) calls, so a [1, K] MVM runs a single 8/32-row tile
    # instead of padding M to 128.
    k_steps = k // block_k
    grid = (m // block_m, n // block_n, k_steps)

    kernel = functools.partial(_bitslice_mvm_kernel, n_slices=s,
                               bits_per_slice=bits_per_slice,
                               k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((s, block_k, block_n), lambda i, j, kk: (0, kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="bitslice_mvm",
    )(x, w_planes)


def bitslice_mvm_scaled_pallas(x: jax.Array, w_planes: jax.Array,
                               row_scale: jax.Array, *,
                               bits_per_slice: int,
                               block_m: int = 128, block_n: int = 128,
                               block_k: int = 128,
                               interpret: bool) -> jax.Array:
    """x: [M, K] int8; w_planes: [S, K, N] int8; row_scale: [M, 1] f32
    -> [M, N] f32 == (recombined int MVM).astype(f32) * row_scale.

    Same tiling contract as :func:`bitslice_mvm_pallas` (ops.py pads).
    """
    s, k, n = w_planes.shape
    m = x.shape[0]
    assert x.shape[1] == k
    assert row_scale.shape == (m, 1), row_scale.shape
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0, (
        (m, k, n, block_m, block_k, block_n))
    k_steps = k // block_k
    grid = (m // block_m, n // block_n, k_steps)

    kernel = functools.partial(_bitslice_mvm_scaled_kernel, n_slices=s,
                               bits_per_slice=bits_per_slice,
                               k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((s, block_k, block_n), lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, kk: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="bitslice_mvm_scaled",
    )(x, w_planes, row_scale)
