"""Work of the int8 MVM kernel (``bitslice_mvm``) in one scheduler tick.

What the algorithm needs, not what an implementation reads: every token
of the tick (prompt chunks and decode rows alike) goes through the seven
linear layers of each block once, so the int8 weights are read once per
tick, each token's int8 activations in and int32 accumulators out.
``rows`` holds ``(tokens, context after, logits)`` per request row.
"""
from __future__ import annotations


def linears(model: dict) -> list[tuple[int, int]]:
    """(K, N) of Q, K, V, O, gate, up and down in one block."""
    d, f = model["hidden_size"], model["intermediate_size"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or d // h
    return [(d, h * hd), (d, kv * hd), (d, kv * hd), (h * hd, d),
            (d, f), (d, f), (f, d)]


def work(rows, model: dict) -> dict:
    tokens = sum(r[0] for r in rows)
    if tokens == 0:
        return {"ops": 0.0, "bytes": 0.0, "ops_peak": "int8_ops"}
    layers = model["num_hidden_layers"]
    shapes = linears(model)
    weights = sum(k * n for k, n in shapes)
    per_token = sum(k + 4 * n for k, n in shapes)
    return {"ops": 2.0 * tokens * weights * layers,
            "bytes": float(layers * (weights + tokens * per_token)),
            "ops_peak": "int8_ops"}
