"""99th percentile of how late the open-loop generator released the
requests due in the window (release on the host clock minus due)."""
from benchmarks.serving.harness import percentile


def read(w):
    return percentile([(r.released - r.due) * 1e3 for r in w.due()
                       if r.released is not None], 99)
