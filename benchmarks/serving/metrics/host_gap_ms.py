"""Mean host time per traced tick in which nothing the scheduler
dispatched was pending on the device, in ms, from the program's spans.
Each stretch runs from the end of a blocking read (``serve.decode.wait``,
``serve.first_token``) to the start of the next ``serve.*.dispatch``,
and counts in the tick of that dispatch; between ticks it holds the
caller's own work.  A tick counts when both ends of each of its
stretches were recorded: not the first traced tick, whose first stretch
began before the profiler started."""
from benchmarks.serving import program_spans


def gap_ms(ticks, records) -> float | None:
    total, counted = 0.0, 0
    idle_from = None          # end of the last read, nothing dispatched since
    known = False             # whether the device's state is known
    for recs in program_spans.by_tick(ticks, records):
        if not recs:          # nothing recorded: the state is lost
            idle_from, known = None, False
            continue
        events = sorted(
            [(r.t1, False) for r in recs
             if r.name in program_spans.BLOCKING]
            + [(r.t0, True) for r in recs if r.name.endswith(".dispatch")])
        whole, gap = known, 0.0
        for t, dispatch in events:
            if dispatch and idle_from is not None:
                gap += t - idle_from
            idle_from = None if dispatch else t
            known = True
        if whole:
            total += gap
            counted += 1
    return 1e3 * total / counted if counted else None


def read(w):
    records = program_spans.recorded()
    return gap_ms(w.traced_ticks(), records) if records else None
