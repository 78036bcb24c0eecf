"""The on-chip serving benchmark: one cell, one seed, one run.

    python3 benchmarks/serving/run.py --workload qwen2.5-3b-int8.chat \
        --seed 7 --seconds 40 --trace 0

Run from the root of a checkout, on a machine whose TPU chips the cell
asks for; without them it exits non-zero and prints no result.  The last
line of standard output is the result: ``correct``, ``attempted``,
``failed``, the cell's metrics (end-to-end with ``--trace 0``, per-layer
with ``--trace 1``), the device, and last the numbers the output check
compared, each beside its limit.  See ``harness.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a workload name from BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True,
                    help="inputs and weights are made from this")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile part of the window and report the "
                         "per-layer metrics")
    args = ap.parse_args(argv)

    from benchmarks.serving import harness
    try:
        result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
