"""Work of the paged-attention kernel (``paged_attention``) in one tick.

What the algorithm needs: each query token attends the live context up
to its own position (causal), in every layer; the live keys and values
are read once per row in bf16, the row's new keys and values written,
its queries read and its outputs written.  Positions past a row's live
context, and rows not in the tick, need nothing.  ``rows`` holds
``(tokens, context after, logits)`` per request row.
"""
from __future__ import annotations

BF16 = 2


def work(rows, model: dict) -> dict:
    d, h = model["hidden_size"], model["num_attention_heads"]
    kv = model["num_key_value_heads"]
    hd = model.get("head_dim") or d // h
    layers = model["num_hidden_layers"]
    ops = bytes_ = 0.0
    for q, ctx, _ in rows:
        attended = q * (ctx - q) + q * (q + 1) // 2
        ops += 4.0 * h * hd * attended            # QK^T and PV
        bytes_ += BF16 * (2 * ctx * kv * hd + 2 * q * h * hd)
    return {"ops": ops * layers, "bytes": bytes_ * layers,
            "ops_peak": "bf16_flops"}
