"""Median, over every request due in the window, of the time from when
it was due to its first token.  A request still without one when
serving stopped counts with the time up to then."""
from benchmarks.serving.harness import percentile


def read(w):
    return percentile([((r.times[0] if r.times else w.closed_at) - r.due)
                       * 1e3 for r in w.due() if not r.failed], 50)
