"""Readings that a cell's output-check limit is set from, in one process:
the program's widest logit gap on each of ``--seeds`` (a window at the
cell's own load, the same sample as a run draws), and on each of
``--control-seeds`` the control's: at the same positions, the gap of the
token that the reference one precision step down (int4 weights) puts
first.

    python3 benchmarks/serving/calibrate.py --workload <cell> \
        --seeds 1,2,3 --control-seeds 1,2,3 --seconds 20 --out <file>

Needs the cell's chips, like ``run.py``.  The benchmark's own runs do
not run it.
"""
from __future__ import annotations

import argparse
import gc
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from benchmarks.serving import harness
    cell = harness.Cell(ROOT, args.workload)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    readings = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        srv = cell.build(seed)
        start = time.perf_counter()
        t0 = start + float(cell.mix.get("ramp_s", 0.0))
        recs, _, _ = srv.driver.serve(cell.requests(seed), start, t0,
                                      args.seconds)
        srv.driver.drain()
        keys = srv.keys
        del srv
        gc.collect()
        finished = [r for r in recs if r.done is not None]
        sample = cell.sample(seed, finished)
        gap, compared = cell.served_gap(keys, sample)
        row = {"seed": seed, "program_gap": gap, "tokens": compared,
               "finished": len(finished)}
        if seed in controls:
            row["control_gap"] = cell.control_gap(keys, sample)
        print(json.dumps(row), flush=True)
        readings.append(row)
    pathlib.Path(args.out).write_text(json.dumps(
        {"workload": args.workload, "seconds": args.seconds,
         "readings": readings}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
