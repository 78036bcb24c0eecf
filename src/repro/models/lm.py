"""The assembled language model: embeddings -> block stack -> head.

Covers all ten assigned architectures through ``ModelConfig``:
  * dense / GQA decoders (glm4, command-r, qwen2.5, minicpm),
  * MoE decoders (olmoe, granite),
  * hybrid attention+Mamba+MoE (jamba),
  * xLSTM (mLSTM/sLSTM stacks),
  * encoder-decoder with a conv-frontend stub (whisper),
  * VLM with a patch-embedding stub frontend (llava-next).

Layer stacking scans over repeating *groups* (period = the heterogeneous
pattern length), so jamba's 32 layers compile as a scan over 4 groups of 8
distinct blocks, and dense models as a scan over L groups of 1.  Decode
states ride through the scan as per-group stacked pytrees; paged KV
pools ride whole in the scan carry, addressed by group index.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.dist.sharding import shard_act, tp_replicate
from repro.models import attention, layers, transformer

Params = dict[str, Any]

# Per-module barrier alias: the graph auditor's mutation self-tests
# knock out the embedding pin alone through this name.
_barrier = jax.lax.optimization_barrier


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Params:
    p_len = transformer.period(cfg)
    n_groups = cfg.num_layers // p_len
    keys = jax.random.split(key, 8)
    vp = layers.padded_vocab(cfg.vocab_size)
    params: Params = {
        "embed": layers.embed_init(keys[0], cfg.vocab_size, cfg.d_model),
        "final_norm": layers.make_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(
            keys[1], (cfg.d_model, vp), jnp.float32) * 0.02)

    def stack_init(fn, key, n):
        ks = jax.random.split(key, n)
        return jax.vmap(fn)(ks)

    blocks = []
    for j in range(p_len):
        fn = functools.partial(transformer.init_block, cfg=cfg, layer_idx=j,
                               cross=cfg.is_encoder_decoder)
        blocks.append(stack_init(lambda k: fn(k), keys[2 + j % 4], n_groups))
    params["blocks"] = blocks

    if cfg.is_encoder_decoder:
        enc_cfg = cfg.replace(attn_period=0, xlstm_slstm_every=0,
                              moe=cfg.moe.__class__())
        enc_blocks = stack_init(
            lambda k: transformer.init_block(k, enc_cfg, 0),
            keys[6], cfg.encoder_layers)
        params["encoder"] = {
            "blocks": enc_blocks,
            "norm": layers.make_norm(cfg),
            "pos_embed": jax.random.normal(
                keys[7], (cfg.encoder_seq, cfg.d_model)) * 0.02,
        }
    if cfg.vision_stub:
        params["vision_proj"] = layers.linear_init(
            keys[5], cfg.d_model, cfg.d_model)
    return params


def params_shape(cfg: ModelConfig) -> Params:
    """ShapeDtypeStruct tree without allocating (for the dry-run)."""
    return jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.random.PRNGKey(0))


def prepack_for_serving(params: Params, cfg: ModelConfig) -> Params:
    """Pack every linear weight once for inference (crossbar programming).

    No-op for bf16.  The embedding table and lm_head stay float (they are
    not PUM-routed); every ``{"w": ...}`` linear — block projections,
    encoder blocks, vision_proj — becomes a ``PackedLinear`` whose forward
    skips per-call quantisation/slicing and the QAT shadow matmul.
    """
    from repro.core import prepack
    return prepack.prepack_params(params, cfg.pum)


def init_served_params(cfg: ModelConfig, key) -> Params:
    """``prepack_for_serving(init_params(cfg, key), cfg)`` as one jitted
    program: the float32 init tree is an intermediate of that program,
    never a live buffer next to its packed copy, so a model whose f32
    weights alone would fill the device still loads (qwen2.5-3b: 12.4 GB
    of f32 against 2.8 GB of int8 planes + a 1.2 GB f32 embedding)."""
    return jax.jit(lambda k: prepack_for_serving(init_params(cfg, k),
                                                 cfg))(key)


# ---------------------------------------------------------------------------
# Decode-state trees
# ---------------------------------------------------------------------------

def init_state(cfg: ModelConfig, batch: int, max_len: int,
               abstract: bool = False) -> list[Any]:
    """Per period-position, group-stacked decode states."""
    p_len = transformer.period(cfg)
    n_groups = cfg.num_layers // p_len
    out = []
    for j in range(p_len):
        if abstract:
            one = transformer.block_state_shape(cfg, j, batch, max_len)
            stacked = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct((n_groups,) + s.shape,
                                               s.dtype), one)
        else:
            one = transformer.make_block_state(cfg, j, batch, max_len)
            stacked = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (n_groups,) + a.shape).copy()
                if a.size else a, one)
        out.append(stacked)
    return out


def init_paged_state(cfg: ModelConfig, batch: int, max_len: int, *,
                     num_blocks: int, block_size: int) -> list[Any]:
    """Decode states with attention KV paged into one shared block pool.

    Attention period-positions get ``[n_groups, num_blocks + 1,
    block_size, kv_heads * head_dim]`` pools (physical block 0 is the
    reserved trash block — ``serve.kv_pool``; the heads folded into the
    lanes, ``attention.make_paged_cache``); recurrent families keep
    their per-slot ``[n_groups, batch, ...]`` rows.  Total KV storage is
    ``(num_blocks + 1) * block_size`` positions per layer group instead
    of ``batch * max_len``.
    """
    p_len = transformer.period(cfg)
    n_groups = cfg.num_layers // p_len
    out = []
    for j in range(p_len):
        if transformer.mixer_kind(cfg, j) == "attn":
            out.append(attention.make_paged_cache(cfg, n_groups,
                                                  num_blocks + 1,
                                                  block_size))
            continue
        one = transformer.make_block_state(cfg, j, batch, max_len)
        out.append(jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (n_groups,) + a.shape).copy()
            if a.size else a, one))
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _run_encoder(params: Params, cfg: ModelConfig,
                 encoder_frames: jax.Array) -> jax.Array:
    """Whisper-style encoder over precomputed frame embeddings (the conv
    frontend is a stub per the assignment: input_specs provides frames)."""
    enc_cfg = cfg.replace(attn_period=0, xlstm_slstm_every=0,
                          moe=cfg.moe.__class__())
    h = encoder_frames + params["encoder"]["pos_embed"][None, :encoder_frames.shape[1]]
    positions = jnp.arange(h.shape[1])

    def body(x, blk):
        # bidirectional self-attention: emulate with full-mask attention
        hh = layers.norm_apply(blk["norm1"], x, enc_cfg)
        b, t, _ = hh.shape
        hd = enc_cfg.resolved_head_dim
        k = layers.linear(blk["attn"]["wk"], hh, enc_cfg.pum).reshape(
            b, t, enc_cfg.num_kv_heads, hd)
        v = layers.linear(blk["attn"]["wv"], hh, enc_cfg.pum).reshape(
            b, t, enc_cfg.num_kv_heads, hd)
        hh, _ = attention.attention(blk["attn"], hh, enc_cfg,
                                    positions=positions, cross_kv=(k, v),
                                    use_rope=False)
        x = x + hh
        from repro.models import mlp as mlp_mod
        hh = layers.norm_apply(blk["norm2"], x, enc_cfg)
        x = x + mlp_mod.mlp(blk["mlp"], hh, enc_cfg)
        return x, None

    h, _ = jax.lax.scan(lambda x, b: body(x, b), h,
                        params["encoder"]["blocks"])
    return layers.norm_apply(params["encoder"]["norm"], h, cfg)


def forward(params: Params, tokens: jax.Array, cfg: ModelConfig, *,
            states: list[Any] | None = None,
            cache_index: jax.Array | None = None,
            image_embeds: jax.Array | None = None,
            encoder_frames: jax.Array | None = None,
            encoder_out: jax.Array | None = None,
            remat: bool = True,
            scan_layers: bool = True,
            last_only: bool = False,
            block_table: jax.Array | None = None,
            kv_len: int | None = None,
            write_table: jax.Array | None = None,
            collect_states: bool = False,
            ) -> tuple[jax.Array, list[Any] | None,
                       dict[str, jax.Array]]:
    """tokens: [B, S] int32 -> (logits, states', aux).

    Modes: train (states None); prefill (states = fresh init_state,
    cache_index=0); decode (states given, cache_index = position).
    ``cache_index`` may be a scalar (whole batch at one position) or a
    vector ``[B]`` (continuous batching: every slot at its own depth).
    The vector form threads through all state families — dense KV caches
    write/mask per row; xlstm and ssm states are per-row recurrences that
    never index the cache, so the position only shapes RoPE.
    Paged KV (states from ``init_paged_state``): pass the per-row
    ``block_table`` [B, W] and the engine window ``kv_len``; attention
    then scatters/gathers through the shared block pool.
    VLM: image_embeds [B, N, D] prepended.  Enc-dec: encoder_frames
    [B, T, D] runs the encoder (or pass precomputed ``encoder_out``).
    ``collect_states``: recurrent leaves of the returned states gain a
    per-position axis — [n_groups, B, S, ...], index j holding the
    state after consuming position j (bit-identical to stepping one
    token at a time).  Paged/contiguous KV leaves are unchanged.  The
    speculative verify step uses this to adopt each row's state at its
    accepted depth.
    """
    b, s = tokens.shape
    with jax.named_scope("embed"):
        h = params["embed"][tokens].astype(jnp.bfloat16 if cfg.dtype ==
                                           "bfloat16" else jnp.float32)
        if cfg.pum.inference:
            # serving: pin the embedding's bf16 rounding (see the block-
            # boundary barrier in transformer.apply_block)
            h = _barrier(h)
    if image_embeds is not None:
        img = layers.linear(params["vision_proj"],
                            image_embeds.astype(h.dtype), cfg.pum)
        h = jnp.concatenate([img, h], axis=1)
        s = h.shape[1]
    h = shard_act(h, "data", None, None)

    if cache_index is not None:
        cache_index = jnp.asarray(cache_index)
        if cache_index.ndim == 1:          # per-slot depths -> [B, S]
            positions = cache_index[:, None] + jnp.arange(s)[None, :]
        else:
            positions = cache_index + jnp.arange(s)
    else:
        positions = jnp.arange(s)

    if cfg.is_encoder_decoder and encoder_out is None \
            and encoder_frames is not None:
        encoder_out = _run_encoder(params, cfg,
                                   encoder_frames.astype(h.dtype))

    p_len = transformer.period(cfg)
    aux_total: dict[str, jax.Array] = {}

    def group_body(x, layer, pools, blk_params, blk_states):
        """One group = one period of distinct blocks.  ``pools[j]`` is
        period position j's whole paged pool stack, read and written at
        group ``layer`` (None where that position's state is a per-group
        slice in ``blk_states``)."""
        pools = list(pools)
        new_states = []
        aux_acc = {}
        for j in range(p_len):
            if pools[j] is not None:
                st = pools[j]
            else:
                st = blk_states[j] if blk_states is not None else None
                if st is not None and not st:      # empty dict = stateless
                    st = None
            with jax.named_scope(f"layer{j}"):
                x, st_new, aux = transformer.apply_block(
                    blk_params[j], x, cfg, j, positions=positions,
                    state=st, cache_index=cache_index,
                    encoder_out=encoder_out, block_table=block_table,
                    kv_len=kv_len, write_table=write_table,
                    collect_states=collect_states, pool_layer=layer)
            if pools[j] is not None:
                pools[j], st_new = st_new, None
            new_states.append(st_new if st_new is not None else {})
            for k, v in aux.items():
                aux_acc[k] = aux_acc.get(k, 0.0) + v
        return x, pools, new_states, aux_acc

    # Paged pools stay whole through the layer loop: they ride in the
    # carry and each group's attention reads and writes its own layer of
    # them in place.  Every other state (recurrent rows, contiguous
    # caches) is sliced per group as scan xs and restacked as ys.
    no_pools = [None] * p_len
    if states is None:
        pools, sliced = no_pools, None
    else:
        pools = [st if attention.is_paged_cache(st) else None
                 for st in states]
        sliced = [{} if attention.is_paged_cache(st) else st
                  for st in states]

    n_groups = cfg.num_layers // p_len
    if scan_layers:
        if states is None:
            def body(x, bp):
                x, _, _, aux = group_body(x, None, no_pools, bp, None)
                return x, aux
            if remat:
                body = jax.checkpoint(body)
            h, aux_stack = jax.lax.scan(body, h, params["blocks"])
            out_states = None
        else:
            def body(carry, group_in):
                x, layer, pools = carry
                x, pools, new_states, aux = group_body(x, layer, pools,
                                                       *group_in)
                return (x, layer + 1, pools), (new_states, aux)
            (h, _, pools), (out_states, aux_stack) = jax.lax.scan(
                body, (h, jnp.int32(0), pools), (params["blocks"], sliced))
        if aux_stack:
            aux_total = {k: jnp.sum(v) for k, v in aux_stack.items()}
    else:
        # unrolled: python loop over groups (accurate cost_analysis in the
        # dry-run: while-loop bodies are otherwise counted once)
        body = group_body
        if remat and states is None:
            body = jax.checkpoint(body)
        collected = []
        for g in range(n_groups):
            bp = jax.tree_util.tree_map(lambda l, g=g: l[g],
                                        params["blocks"])
            st = None
            if states is not None:
                st = jax.tree_util.tree_map(lambda l, g=g: l[g], sliced)
            h, pools, new_st, aux_g = body(h, g, pools, bp, st)
            collected.append(new_st)
            for k, v in aux_g.items():
                aux_total[k] = aux_total.get(k, 0.0) + v
        if states is not None:
            out_states = jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *collected)
        else:
            out_states = None
    if out_states is not None:
        out_states = [pool if pool is not None else st
                      for pool, st in zip(pools, out_states)]

    h = layers.norm_apply(params["final_norm"], h, cfg)
    if last_only:
        h = h[:, -1:]
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jnp.einsum("bsd,dv->bsv", h.astype(jnp.float32),
                        head.astype(jnp.float32))
    logits = shard_act(logits, "data", None, "model")
    # TP serving gathers the vocab-sharded logits: sampling (argmax /
    # categorical) then runs replicated, so tie-breaks and gumbel draws
    # are bit-identical to the single-device oracle
    logits = tp_replicate(logits)
    return logits, out_states, aux_total
