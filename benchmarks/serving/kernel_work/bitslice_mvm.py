"""Work of the int8 MVM kernel (``bitslice_mvm``) in one scheduler tick.

What the algorithm needs, not what an implementation reads: every token
of the tick (prompt chunks and decode rows alike) goes through each
layer's own linears once (``layers``, the reference's declaration), so
the int8 weights are read once per tick, each token's int8 activations
in and int32 accumulators out.  ``rows`` holds ``(tokens, context
after, logits)`` per request row.
"""
from __future__ import annotations


def work(rows, layers) -> dict:
    tokens = sum(r[0] for r in rows)
    if tokens == 0:
        return {"ops": 0.0, "bytes": 0.0, "ops_peak": "int8_ops"}
    weights = sum(k * n for layer in layers for k, n in layer.linears)
    per_token = sum(k + 4 * n for layer in layers for k, n in layer.linears)
    return {"ops": 2.0 * tokens * weights,
            "bytes": float(weights + tokens * per_token),
            "ops_peak": "int8_ops"}
