"""Run one cell of the on-chip serving benchmark once.

    python benchmarks/chip/run.py --workload stablelm-3b.chat --seed 7 --seconds 30 --trace 0

Prints the result as one JSON object, the last line of standard output,
and the numbers the check compared, each beside its limit, as the last
lines of standard error. Exits non-zero with no result when JAX finds no
TPU, or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from chipbench.cell import load
    from chipbench.harness import NoChip, run_cell

    cell = load(args.workload)
    try:
        line, notes = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    for note in notes:
        print(note, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
