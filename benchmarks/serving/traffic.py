"""The one traffic generator: reads a mix's parameters from
``traffic/<name>.json`` and yields requests.

Every seed gets the same work on the same schedule.  Lengths and
arrival gaps are fixed quantiles of the mix's distributions, in blocks
of ``block`` requests, each block shuffled (prompt lengths, output
lengths and gaps apart) by the mix's own ``schedule_seed``; ``--seed``
draws the prompt tokens (and the weights, elsewhere).  Runs with
different seeds differ in content, not in how much there is to do or
when: under load the order of long and short prompts alone moved a
cell's median gap between tokens by half (PERF.md).

Mix keys (``source``, ``assumed`` and ``cuts`` say what the mix stands
for, and where its lengths were cut to fit; the generator reads the
rest):

  ``loop``         ``"open"`` (arrivals on a schedule, ``rate_per_s``)
                   or ``"closed"`` (``clients`` callers, each sending its
                   next request when the last one finished);
  ``prompt``, ``output``  length distributions: ``{"dist": "uniform" |
                   "lognormal", "min", "max"[, "median", "sigma"]}``;
  ``block``        requests per shuffled block;
  ``schedule_seed`` the seed of the shuffles;
  ``temperature``  0 for greedy decoding;
  ``slots``, ``max_len``, ``kv_block_size``, ``num_kv_blocks``  the
                   scheduler the mix is served by;
  ``ramp_s``       seconds of the same traffic served before the window
                   opens, so that the window sees the steady state;
  ``warmup_output`` tokens per warm-up request;
  ``check_requests``, ``check_tokens``  requests the output check
                   compares, and the served tokens it compares at least.
"""
from __future__ import annotations

from collections.abc import Iterator
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Item:
    """One request as the generator makes it."""
    rid: int
    prompt: list[int]
    max_tokens: int
    seed: int
    gap_s: float          # open loop: seconds after the previous arrival


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the midpoints of ``n`` equal-probability bins of
    the distribution ``spec``, clipped to its ``[min, max]``."""
    lo, hi = int(spec["min"]), int(spec["max"])
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        x = lo + np.floor(u * (hi - lo + 1))
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def gaps(rate_per_s: float, n: int) -> np.ndarray:
    """Exponential inter-arrival quantiles of a Poisson process."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate_per_s


def stream(mix: dict, seed: int, vocab: int) -> Iterator[Item]:
    """Requests of ``mix`` for ``seed``, without end."""
    n = int(mix["block"])
    prompts = quantiles(mix["prompt"], n)
    outputs = quantiles(mix["output"], n)
    arrivals = (gaps(float(mix["rate_per_s"]), n)
                if mix["loop"] == "open" else np.zeros(n))
    order = np.random.default_rng(int(mix["schedule_seed"]))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x7AFF1C]))
    rid = 0
    while True:
        p, o, g = (order.permutation(prompts), order.permutation(outputs),
                   order.permutation(arrivals))
        for i in range(n):
            yield Item(rid=rid,
                       prompt=rng.integers(0, vocab, int(p[i])).tolist(),
                       max_tokens=int(o[i]),
                       seed=int(rng.integers(0, 2**31 - 1)),
                       gap_s=float(g[i]))
            rid += 1


def warmup(mix: dict, vocab: int) -> list[Item]:
    """One request per residue of the prompt length mod the KV block
    size (so every ragged tail chunk shape compiles in set-up), with
    the same content for every seed."""
    bs = int(mix["kv_block_size"])
    rng = np.random.default_rng(0)
    return [Item(rid=-1 - r, prompt=rng.integers(0, vocab, bs + r).tolist(),
                 max_tokens=int(mix["warmup_output"]), seed=r, gap_s=0.0)
            for r in range(1, bs + 1)]
