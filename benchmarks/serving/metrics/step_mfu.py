"""Model operations of the traced ticks over the host time of those
ticks, as a share of the chip's int8 peak, in %: 2 operations per
weight of every linear layer per token, the tied head for each token
whose logits are used, and attention over the live context."""


def model_ops(rows, m) -> float:
    d, f = m["hidden_size"], m["intermediate_size"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim") or d // h
    per_token = m["num_hidden_layers"] * (
        d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f)
    head = d * m["padded_vocab_size"]
    ops = 0.0
    for q, ctx, logits in rows:
        attended = q * (ctx - q) + q * (q + 1) // 2
        ops += 2.0 * q * per_token + (2.0 * head if logits else 0.0) \
            + 4.0 * h * hd * attended * m["num_hidden_layers"]
    return ops


def read(w):
    ticks = w.work_ticks()
    if not ticks or w.peaks is None:
        return None
    seconds = sum(t.t1 - t.t0 for t in ticks)
    ops = sum(model_ops(t.rows, w.model) for t in ticks)
    return 100.0 * ops / seconds / w.peaks["int8_ops"]
