"""The output check: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample
of finished requests (drawn from the seed, the one with the most served
tokens always in it) is run through the reference over its prompt and
served tokens.  At each served position the reading is the gap by which
the served token's reference logit lies below the reference's best
logit there; the number compared is the widest such gap.  Greedy
decoding that computes what the configuration states puts that gap at
rounding; a lower precision puts it far above.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

PAD_TO = 512           # sequence lengths are padded to a multiple of this


def pick(finished: list, rng: np.random.Generator, n: int) -> list:
    """``n`` finished requests (fewer if fewer finished): the one with
    the most served tokens, then others in the seed's order."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.tokens), -r.rid))
    rest = [r for r in finished if r is not longest]
    order = rng.permutation(len(rest))
    return [longest] + [rest[i] for i in order[:n - 1]]


def batch(sample: list, n: int) -> tuple[np.ndarray, np.ndarray, list]:
    """Token rows ``[n, L]`` (prompt + served tokens fed back, padded,
    the batch filled up with copies of the first row so its shape is
    fixed), the flat rows that score each served token, and the served
    tokens in that order."""
    seqs = [list(r.prompt) + list(r.tokens[:-1]) for r in sample]
    length = -(-max(len(s) for s in seqs) // PAD_TO) * PAD_TO
    toks = np.zeros((n, length), np.int32)
    for i in range(n):
        s = seqs[i % len(seqs)]
        toks[i, :len(s)] = s
    rows, served = [], []
    for i, r in enumerate(sample):
        p = len(r.prompt)
        rows += [i * length + p - 1 + j for j in range(len(r.tokens))]
        served += list(r.tokens)
    return toks, np.asarray(rows, np.int64), served


def widest_gap(logits, tokens) -> float:
    """max over rows of (best logit - logit of ``tokens[row]``)."""
    t = jnp.asarray(np.asarray(tokens, np.int32))[:, None]
    chosen = jnp.take_along_axis(logits, t, axis=1)[:, 0]
    return float(jnp.max(jnp.max(logits, axis=1) - chosen))


def served_gap(reference, model: dict, keys: dict, sample: list, n: int
               ) -> tuple[float, int]:
    """(widest gap of the served tokens, served tokens compared)."""
    toks, rows, served = batch(sample, n)
    logits = reference.logits(model, keys, toks, rows, weight_bits=8)
    return widest_gap(logits, served), len(served)


def control_gap(reference, model: dict, keys: dict, sample: list, n: int,
                weight_bits: int = 4) -> float:
    """The control: at the same positions, the gap of the token that
    the reference one precision step down puts first."""
    toks, rows, _ = batch(sample, n)
    exact = reference.logits(model, keys, toks, rows, weight_bits=8)
    low = reference.logits(model, keys, toks, rows, weight_bits=weight_bits)
    return widest_gap(exact, np.asarray(jnp.argmax(low, axis=1)))
