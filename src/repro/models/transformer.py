"""Decoder block assembly: per-layer kind selection (attention / Mamba /
mLSTM / sLSTM mixers; dense-MLP / MoE FFNs) and the repeating-period
grouping that lets heterogeneous stacks (jamba's 1:7 attention:Mamba
interleave, xLSTM's sLSTM-every-k) still scan over layers.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.dist.sharding import shard_act
from repro.models import attention, layers, mlp, moe, ssm, xlstm

Params = dict[str, Any]

# Per-module barrier alias: the graph auditor's mutation self-tests
# knock out the block-boundary pin alone through this name.
_barrier = jax.lax.optimization_barrier


def mixer_kind(cfg: ModelConfig, layer_idx: int) -> str:
    if cfg.xlstm_slstm_every > 0:
        return "slstm" if layer_idx % cfg.xlstm_slstm_every == 0 else "mlstm"
    if cfg.attn_period > 0:
        # jamba: one attention layer per `attn_period`, rest Mamba
        return "attn" if layer_idx % cfg.attn_period == (
            cfg.attn_period // 2) else "mamba"
    return "attn"


def ffn_kind(cfg: ModelConfig, layer_idx: int) -> str:
    if cfg.moe.num_experts <= 0:
        return "mlp" if cfg.d_ff > 0 else "none"
    if layer_idx % cfg.moe_layer_period == (cfg.moe_layer_period - 1):
        return "moe"
    return "mlp" if cfg.d_ff > 0 else "none"


def period(cfg: ModelConfig) -> int:
    """Smallest repeating pattern of (mixer, ffn) kinds."""
    p = 1
    if cfg.attn_period > 0:
        p = max(p, cfg.attn_period)
    if cfg.xlstm_slstm_every > 0:
        p = max(p, cfg.xlstm_slstm_every)
    if cfg.moe.num_experts > 0:
        p = max(p, cfg.moe_layer_period)
    while cfg.num_layers % p != 0:       # fall back to unrolled if ragged
        p += 1
        if p > cfg.num_layers:
            return cfg.num_layers
    return p


# ---------------------------------------------------------------------------
# One block
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, layer_idx: int,
               cross: bool = False) -> Params:
    mk = mixer_kind(cfg, layer_idx)
    fk = ffn_kind(cfg, layer_idx)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {"norm1": layers.make_norm(cfg)}
    if mk == "attn":
        p["attn"] = attention.init_attention(k1, cfg)
    elif mk == "mamba":
        p["mamba"] = ssm.init_mamba(k1, cfg)
    elif mk == "mlstm":
        p["mlstm"] = xlstm.init_mlstm(k1, cfg)
    elif mk == "slstm":
        p["slstm"] = xlstm.init_slstm(k1, cfg)
    if fk != "none":
        p["norm2"] = layers.make_norm(cfg)
    if fk == "mlp":
        p["mlp"] = mlp.init_mlp(k2, cfg)
    elif fk == "moe":
        p["moe"] = moe.init_moe(k2, cfg)
    if cross:
        p["norm_x"] = layers.make_norm(cfg)
        p["cross"] = attention.init_attention(k3, cfg, cross=True)
    return p


def block_state_shape(cfg: ModelConfig, layer_idx: int, batch: int,
                      max_len: int, dtype=jnp.bfloat16):
    """ShapeDtypeStruct tree of this block's decode state."""
    mk = mixer_kind(cfg, layer_idx)
    if mk == "attn":
        return attention.cache_shape(cfg, batch, max_len, dtype)
    if mk == "mamba":
        return ssm.ssm_state_shape(cfg, batch)
    if mk == "mlstm":
        return xlstm.mlstm_state_shape(cfg, batch)
    if mk == "slstm":
        return xlstm.slstm_state_shape(cfg, batch)
    return {}


def make_block_state(cfg: ModelConfig, layer_idx: int, batch: int,
                     max_len: int, dtype=jnp.bfloat16):
    mk = mixer_kind(cfg, layer_idx)
    if mk == "attn":
        return attention.make_cache(cfg, batch, max_len, dtype)
    if mk == "mamba":
        return ssm.make_ssm_state(cfg, batch)
    if mk == "mlstm":
        return xlstm.make_mlstm_state(cfg, batch)
    if mk == "slstm":
        return xlstm.make_slstm_state(cfg, batch)
    return {}


def apply_block(p: Params, x: jax.Array, cfg: ModelConfig, layer_idx: int, *,
                positions: jax.Array,
                state: Params | None = None,
                cache_index: jax.Array | None = None,
                encoder_out: jax.Array | None = None,
                block_table: jax.Array | None = None,
                kv_len: int | None = None,
                write_table: jax.Array | None = None,
                collect_states: bool = False,
                pool_layer: jax.Array | int | None = None,
                ) -> tuple[jax.Array, Params | None,
                           dict[str, jax.Array]]:
    """Returns (x, new_state, aux_losses).  ``block_table``/``kv_len``
    select the paged KV path in self-attention (serve.kv_pool), whose
    state is then the whole stacked pool, addressed at ``pool_layer``;
    ``write_table`` re-routes its scatters (prefix-cache shared blocks
    are read-only).  ``collect_states``: recurrent mixers return their
    state after *every* position ([B, S, ...] leaves) instead of only
    the final one — the speculative verify step's variable-advance
    hook (KV caches are unaffected; rollback handles those)."""
    mk = mixer_kind(cfg, layer_idx)
    fk = ffn_kind(cfg, layer_idx)
    aux: dict[str, jax.Array] = {}

    h = layers.norm_apply(p["norm1"], x, cfg)
    if mk == "attn":
        h, state = attention.attention(
            p["attn"], h, cfg, positions=positions, cache=state,
            cache_index=cache_index,
            use_rope=not cfg.is_encoder_decoder,
            block_table=block_table, kv_len=kv_len,
            write_table=write_table, pool_layer=pool_layer)
    elif mk == "mamba":
        h, state = ssm.mamba(p["mamba"], h, cfg, state=state,
                             collect_states=collect_states)
    elif mk == "mlstm":
        h, state = xlstm.mlstm(p["mlstm"], h, cfg, state=state,
                               collect_states=collect_states)
    elif mk == "slstm":
        h, state = xlstm.slstm(p["slstm"], h, cfg, state=state,
                               collect_states=collect_states)
    x = x + h

    if "cross" in p and encoder_out is not None:
        h = layers.norm_apply(p["norm_x"], x, cfg)
        kv_proj_k = layers.linear(p["cross"]["wk"], encoder_out, cfg.pum)
        kv_proj_v = layers.linear(p["cross"]["wv"], encoder_out, cfg.pum)
        b, t, _ = encoder_out.shape
        hd = cfg.resolved_head_dim
        cross_kv = (kv_proj_k.reshape(b, t, cfg.num_kv_heads, hd),
                    kv_proj_v.reshape(b, t, cfg.num_kv_heads, hd))
        h, _ = attention.attention(p["cross"], h, cfg, positions=positions,
                                   cross_kv=cross_kv, use_rope=False)
        x = x + h

    if fk != "none":
        h = layers.norm_apply(p["norm2"], x, cfg)
        if fk == "mlp":
            h = mlp.mlp(p["mlp"], h, cfg)
        else:
            h, aux = moe.moe_ffn(p["moe"], h, cfg)
        x = x + h
    # residual-stream constraint mode (seq/hidden/batch) — hillclimb knob
    from repro.dist import sharding as _shd
    x = shard_act(x, *_shd.residual_spec())
    if cfg.pum.inference:
        # serving: pin the residual's bf16 rounding at the block
        # boundary — XLA keeps bf16 regions in f32 between rounding
        # points, so without this the next block's norm could consume a
        # pre-rounding value whose availability depends on graph
        # partitioning (single device vs tensor-parallel serving)
        with jax.named_scope("block_tail"):
            x = _barrier(x)
    return x, state, aux
