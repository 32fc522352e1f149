"""One benchmark run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line on standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last the numbers the
correctness check compared, each with its limit (also the last lines on
standard error).  Without the chips the cell asks for, the run prints no
result and exits 3.
"""

from __future__ import annotations

import argparse
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import cell  # noqa: E402

T_START = cell.process_start_time()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        out = cell.run(args.workload, args.seed, args.seconds, bool(args.trace), T_START)
    except cell.NoChip as exc:
        print(f"no result: {exc}", file=sys.stderr)
        return 3
    cell.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
