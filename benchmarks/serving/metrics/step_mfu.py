"""Model operations of the traced ticks over the host time of those
ticks, as a share of the chip's int8 peak, in %: per token, 2
operations per weight of each layer's linears (the reference's
``layers``), causal attention over the live context in each layer that
attends, and the head for each token whose logits are used.  A
recurrent mixer's elementwise state update is no linear layer and is
not counted: only its projections are."""


def model_ops(rows, layers, m) -> float:
    per_token = sum(k * n for layer in layers for k, n in layer.linears)
    attn = sum(4 * a.heads * a.head_dim
               for a in (layer.attention for layer in layers) if a)
    head = m["hidden_size"] * m["padded_vocab_size"]
    ops = 0
    for q, ctx, logits in rows:
        attended = q * (ctx - q) + q * (q + 1) // 2
        ops += 2 * q * per_token + (2 * head if logits else 0) \
            + attn * attended
    return float(ops)


def read(w):
    ticks = w.work_ticks()
    if not ticks or w.peaks is None:
        return None
    seconds = sum(t.t1 - t.t0 for t in ticks)
    ops = sum(model_ops(t.rows, w.layers, w.model) for t in ticks)
    return 100.0 * ops / seconds / w.peaks["int8_ops"]
