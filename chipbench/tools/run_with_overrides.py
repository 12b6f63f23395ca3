"""One run of a cell with further overrides of the program's configuration,
for readings that ``PERF.md`` quotes and no cell keeps:

    # what dropout at the source's rate costs the timed step (``correct``
    # comes out false: the reference follows no mask)
    python3 chipbench/tools/run_with_overrides.py --workload fed8.b64 \\
        --seed 7 --seconds 20 --set model.dropout_rate=0.2

    # the second witness of the comparison: the program in float32 on a
    # catalog small enough for a float32 table, the cell's batch and widths
    python3 chipbench/tools/run_with_overrides.py --workload fed8.b64 \\
        --seed 101 --seconds 2 --set model.dtype=float32 --rows 16384

Prints the run's result line; it is not the benchmark's command.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append", default=[], dest="overrides")
    ap.add_argument("--rows", type=int, help="catalog rows, where the cell's do not fit")
    args = ap.parse_args()
    from chipbench import cells
    from chipbench import harness_training_rounds as harness

    cell = cells.load_cell(ROOT, args.workload)
    cell["config"]["overrides"] = [*cell["config"]["overrides"], *args.overrides]
    if args.rows:
        cell["config"]["shapes"]["catalog_rows"] = args.rows
        cell["traffic"]["num_news"] = args.rows
    line = harness.run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
                            T_START, cell=cell)
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
