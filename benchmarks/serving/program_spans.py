"""The program's own spans of the traced ticks: what the scheduler
records while the profiler runs (``repro.serve.spans``), on the
``time.perf_counter`` clock the harness stamps its ticks with.  A
program that records no spans gives None, and so do the metrics that
read them."""
from __future__ import annotations

import bisect

BLOCKING = ("decode.wait", "first_token")   # host reads that wait for
#                                             the device


def recorded() -> list | None:
    try:
        from repro.serve import spans
    except ImportError:
        return None
    return spans.recorded()


def by_tick(ticks, records) -> list[list]:
    """For each of ``ticks`` (in time order), the records that began
    inside it, in the order they began."""
    out: list[list] = [[] for _ in ticks]
    starts = [t.t0 for t in ticks]
    for r in sorted(records, key=lambda r: r.t0):
        i = bisect.bisect_right(starts, r.t0) - 1
        if i >= 0 and r.t0 <= ticks[i].t1:
            out[i].append(r)
    return out
