"""Median of every gap between consecutive tokens of a request whose
later token came in the window, pooled over all requests."""
from benchmarks.serving.harness import percentile


def read(w):
    return percentile(w.itl_gaps_ms(), 50)
