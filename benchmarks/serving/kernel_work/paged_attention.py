"""Work of the paged-attention kernel (``paged_attention``) in one tick.

What the algorithm needs: each query token attends the live context up
to its own position (causal), in every layer that attends (``layers``,
the reference's declaration); the live keys and values are read once
per row in bf16, the row's new keys and values written, its queries
read and its outputs written.  Positions past a row's live context,
rows not in the tick, and layers that do not attend need nothing.
``rows`` holds ``(tokens, context after, logits)`` per request row.
"""
from __future__ import annotations

BF16 = 2


def work(rows, layers) -> dict:
    ops = bytes_ = 0
    for a in (layer.attention for layer in layers):
        if a is None:
            continue
        for q, ctx, _ in rows:
            attended = q * (ctx - q) + q * (q + 1) // 2
            ops += 4 * a.heads * a.head_dim * attended    # QK^T and PV
            bytes_ += BF16 * (2 * ctx * a.kv_heads * a.head_dim
                              + 2 * q * a.heads * a.head_dim)
    return {"ops": float(ops), "bytes": float(bytes_),
            "ops_peak": "bf16_flops"}
