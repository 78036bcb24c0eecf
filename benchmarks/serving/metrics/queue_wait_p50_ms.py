"""Median, over requests due in the window, of the time from when a
request was due to ``start_request`` (waiting for a slot or for KV
blocks behind the FIFO head)."""
from benchmarks.serving.harness import percentile


def read(w):
    return percentile([(r.started - r.due) * 1e3 for r in w.due()
                       if r.started is not None], 50)
