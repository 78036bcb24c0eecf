"""A rate sweep of an open-loop cell in one process: the same scheduler
serves the cell's mix at each of ``--rates`` (requests per second) for
``--seconds``, draining between rates.  Finds the knee, the highest rate
at which the system, and every lower rate, sustains its load: it
completes at least 85% of the offered rate, no request is left
unstarted, and the requests in the system at the window's close exceed
those at its open by at most max(2, a tenth of the window's arrivals).
The cell's traffic file then fixes its rate at 80% of the knee.

    python3 benchmarks/serving/sweep.py --workload <cell> --seed 1 \
        --rates 0.3,0.35,0.4,0.45,0.5,0.6 --seconds 60

Needs the cell's chips, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from benchmarks.serving import harness, traffic
    cell = harness.Cell(ROOT, args.workload)
    srv = cell.build(args.seed)
    knee, sustained_so_far = None, True
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = {**cell.mix, "rate_per_s": rate}
        driver = srv.driver
        driver.mix = mix
        start = time.perf_counter()
        t0 = start + float(mix.get("ramp_s", 0.0))
        recs, ticks, _ = driver.serve(
            traffic.stream(mix, args.seed, cell.config["vocab_size"]),
            start, t0, args.seconds, grace_s=0.0)
        driver.drain()
        w = harness.Window(t0=t0, seconds=args.seconds, setup_s=0.0,
                           closed_at=time.perf_counter(), recs=recs,
                           ticks=ticks, compiles=0, slots=mix["slots"],
                           model=cell.config, peaks=None, kernel_work={},
                           layers=cell.layers)
        due = w.due()
        half = t0 + args.seconds / 2
        late = [r for r in due if r.due >= half]

        def ttft(rs, q):
            return harness.percentile(
                [((r.times[0] if r.times else w.closed_at) - r.due) * 1e3
                 for r in rs], q)

        def in_system(t):
            return sum(r.released is not None and r.released <= t
                       and (r.done is None or r.done > t) for r in recs)

        finished_per_s = sum(r.done is not None and r.done <= w.end
                             for r in recs) / args.seconds
        unstarted = sum(r.started is None for r in due)
        grew = in_system(w.end) - in_system(w.t0)
        sustained_so_far = sustained_so_far and (
            finished_per_s >= 0.85 * rate and unstarted == 0
            and grew <= max(2, 0.1 * rate * args.seconds))
        if sustained_so_far:
            knee = rate
        print(json.dumps({
            "rate_per_s": rate, "sustained": sustained_so_far,
            "due": len(due),
            "unstarted_at_close": unstarted,
            "in_system_at_open": in_system(w.t0),
            "in_system_at_close": in_system(w.end),
            "finished_per_s": finished_per_s,
            "ttft_p50_ms": ttft(due, 50),
            "ttft_p90_ms": ttft(due, 90),
            "ttft_p50_ms_second_half": ttft(late, 50),
            "queue_wait_p90_ms": harness.percentile(
                [((r.started or w.closed_at) - r.due) * 1e3 for r in due],
                90),
            "itl_p50_ms": harness.percentile(w.itl_gaps_ms(), 50),
            "itl_p95_ms": harness.percentile(w.itl_gaps_ms(), 95),
            "output_tok_s": sum(w.t0 <= t <= w.end for r in recs
                                for t in r.times) / args.seconds}),
            flush=True)
    print(json.dumps({"knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
