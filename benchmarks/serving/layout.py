"""What a reference declares of each layer of its model, for the work
counts (``kernel_work/<kernel>.py``, ``metrics/step_mfu.py``).

A reference's ``layers(config)`` returns one ``Layer`` per model layer,
in order.  Nothing that reads a ``Layer`` knows which model it is.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Attention:
    """A layer's causal softmax attention over its paged KV."""
    heads: int
    kv_heads: int
    head_dim: int


@dataclasses.dataclass(frozen=True)
class Layer:
    """One model layer: the (K, N) of each linear the int8 MVM computes
    for a token, and its attention where it attends (a recurrent mixer
    declares its projections and no attention)."""
    linears: tuple[tuple[int, int], ...]
    attention: Attention | None = None
