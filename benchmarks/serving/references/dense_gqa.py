"""Plain reference of a dense decoder with grouped-query attention, served
with int8 linear layers: the Qwen2 / Llama block as the configuration
file states it (pre-norm RMSNorm, Q/K/V with optional bias, rotary
embeddings on the first and second halves of each head, causal softmax
attention, SwiGLU feed-forward, tied embedding head).

Quantisation, as the served mode states it: each linear weight is
symmetric per output channel (absmax over the input axis / qmax, round
half to even, clip to +-qmax), each activation row symmetric per row at
8 bits, the product accumulated in int32 and rescaled by both scales.
``weight_bits=4`` is the control: the same model one precision step
down (int4 weights, int8 activations).

Everything else runs in float32 at ``highest`` matmul precision, over
whole sequences at once (no cache, no paging, no batching of unrelated
rows into one step), one layer at a time so that it fits beside nothing
else.  It imports nothing of the program: weights are regenerated from
the seed by ``benchmarks.serving.weights``.

It declares what it reads of the served tree (``served_leaves``), which
the harness compares with the program's tree leaf by leaf, and each of
its layers (``layers``), from which the work counts are taken.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.serving import weights
from benchmarks.serving.layout import Attention, Layer

# where the served parameter tree keeps each weight (one layer group)
LAYER = {
    "norm1": "['blocks'][0]['norm1']['scale']",
    "wq": "['blocks'][0]['attn']['wq']['w']",
    "bq": "['blocks'][0]['attn']['wq']['b']",
    "wk": "['blocks'][0]['attn']['wk']['w']",
    "bk": "['blocks'][0]['attn']['wk']['b']",
    "wv": "['blocks'][0]['attn']['wv']['w']",
    "bv": "['blocks'][0]['attn']['wv']['b']",
    "wo": "['blocks'][0]['attn']['wo']['w']",
    "norm2": "['blocks'][0]['norm2']['scale']",
    "wg": "['blocks'][0]['mlp']['wg']['w']",
    "wu": "['blocks'][0]['mlp']['wu']['w']",
    "wd": "['blocks'][0]['mlp']['wd']['w']",
}
EMBED = "['embed']"
FINAL_NORM = "['final_norm']['scale']"
Q_BLOCK = 512          # query rows per attention block


def sizes(model: dict) -> dict[str, int]:
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    return {"d": d, "f": model["intermediate_size"], "h": h,
            "kv": model["num_key_value_heads"],
            "hd": model.get("head_dim") or d // h,
            "layers": model["num_hidden_layers"],
            "vocab_rows": model["padded_vocab_size"]}


def leaf_shapes(model: dict) -> dict[str, tuple[int, ...]]:
    """Unstacked shape of every leaf the reference reads."""
    s = sizes(model)
    d, f, qd, kvd = s["d"], s["f"], s["h"] * s["hd"], s["kv"] * s["hd"]
    out = {"norm1": (d,), "wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd),
           "wo": (qd, d), "norm2": (d,), "wg": (d, f), "wu": (d, f),
           "wd": (f, d)}
    if model.get("attention_bias"):
        out.update(bq=(qd,), bk=(kvd,), bv=(kvd,))
    return out


def served_leaves(model: dict) -> dict[str, tuple[int, ...]]:
    """Every served leaf the reference reads, at its stacked shape: one
    layer group, every layer alike."""
    n = model["num_hidden_layers"]
    return {LAYER[name]: (n,) + shape
            for name, shape in leaf_shapes(model).items()}


def layers(model: dict) -> list[Layer]:
    """Every layer: Q, K, V, O, gate, up and down through the int8 MVM,
    and grouped-query attention."""
    s = sizes(model)
    d, f, qd, kvd = s["d"], s["f"], s["h"] * s["hd"], s["kv"] * s["hd"]
    layer = Layer(linears=((d, qd), (d, kvd), (d, kvd), (qd, d),
                           (d, f), (d, f), (f, d)),
                  attention=Attention(s["h"], s["kv"], s["hd"]))
    return [layer] * s["layers"]


def _quant(x, bits: int, axis: int):
    qmax = (1 << (bits - 1)) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-12) / qmax
    return jnp.clip(jnp.round(x / s), -qmax, qmax).astype(jnp.int8), s


def _linear(x, w, weight_bits: int):
    wq, ws = _quant(w, weight_bits, axis=0)
    xq, xs = _quant(x, 8, axis=-1)
    acc = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (xs * ws)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs        # [L, half]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]    # [L, 1, half]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v):
    """q [n,L,H,hd], k/v [n,L,KV,hd]: causal, queries in blocks."""
    n, length, h, hd = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    outs = []
    for q0 in range(0, length, Q_BLOCK):
        qb = q[:, q0:q0 + Q_BLOCK]
        sc = jnp.einsum("nqhd,nthd->nhqt", qb, k) / np.sqrt(hd)
        qpos = q0 + jnp.arange(qb.shape[1])
        causal = jnp.arange(length)[None, :] <= qpos[:, None]
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("nhqt,nthd->nqhd", p, v))
    return jnp.concatenate(outs, axis=1)


@functools.partial(jax.jit, static_argnames=("model_items", "weight_bits"))
def _layer(h, keys, layer, *, model_items, weight_bits):
    model = dict(model_items)
    s = sizes(model)
    shapes = leaf_shapes(model)
    p = {name: weights.layer_leaf(keys[name], weights.kind_of(LAYER[name]),
                                  layer, shape)
         for name, shape in shapes.items()}
    eps = model["rms_norm_eps"]
    n, length, _ = h.shape
    pos = jnp.arange(length)
    x = _rmsnorm(h, p["norm1"], eps)
    q = _linear(x, p["wq"], weight_bits) + p.get("bq", 0.0)
    k = _linear(x, p["wk"], weight_bits) + p.get("bk", 0.0)
    v = _linear(x, p["wv"], weight_bits) + p.get("bv", 0.0)
    q = _rope(q.reshape(n, length, s["h"], s["hd"]), pos, model["rope_theta"])
    k = _rope(k.reshape(n, length, s["kv"], s["hd"]), pos,
              model["rope_theta"])
    v = v.reshape(n, length, s["kv"], s["hd"])
    a = _attention(q, k, v).reshape(n, length, s["h"] * s["hd"])
    h = h + _linear(a, p["wo"], weight_bits)
    x = _rmsnorm(h, p["norm2"], eps)
    m = jax.nn.silu(_linear(x, p["wg"], weight_bits)) \
        * _linear(x, p["wu"], weight_bits)
    return h + _linear(m, p["wd"], weight_bits)


@functools.partial(jax.jit, static_argnames=("model_items",))
def _embed(tokens, key, *, model_items):
    s = sizes(dict(model_items))
    table = weights.leaf(key, "embed", (s["vocab_rows"], s["d"]))
    return table[tokens]


@functools.partial(jax.jit, static_argnames=("model_items",))
def _head(h, norm_key, embed_key, *, model_items):
    model = dict(model_items)
    s = sizes(model)
    g = weights.leaf(norm_key, "scale", (s["d"],))
    table = weights.leaf(embed_key, "embed", (s["vocab_rows"], s["d"]))
    return _rmsnorm(h, g, model["rms_norm_eps"]) @ table.T


def logits(model: dict, keys: dict[str, jax.Array], tokens: np.ndarray,
           rows: np.ndarray, weight_bits: int = 8) -> jax.Array:
    """Reference logits ``[len(rows), vocab_rows]`` at flat positions
    ``rows`` of ``tokens`` [n, L] (row ``i * L + t`` scores the token
    after position ``t`` of sequence ``i``).  ``keys`` are the base keys
    of the served tree's leaves (``weights.tree_keys``)."""
    if model.get("hidden_act", "silu") != "silu" \
            or not model.get("tie_word_embeddings", True):
        raise ValueError("dense_gqa covers SwiGLU decoders with a tied "
                         "embedding head only")
    items = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (int, float, bool))))
    layer_keys = {name: keys[path] for name, path in LAYER.items()
                  if name in leaf_shapes(model)}
    with jax.default_matmul_precision("highest"):
        h = _embed(jnp.asarray(tokens), keys[EMBED], model_items=items)
        for layer in range(sizes(model)["layers"]):
            h = _layer(h, layer_keys, jnp.int32(layer), model_items=items,
                       weight_bits=weight_bits)
        flat = h.reshape(-1, h.shape[-1])[jnp.asarray(rows)]
        return _head(flat, keys[FINAL_NORM], keys[EMBED], model_items=items)
