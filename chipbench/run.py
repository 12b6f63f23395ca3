"""The benchmark's command: one cell, once, in this process.

    python3 chipbench/run.py --workload fed8.b64 --seed 7 --seconds 10 --trace 0

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` with ``--trace 1``); the numbers compared beside their limits
come last in it and as the last lines of standard error. Exits non-zero and
prints no result when JAX's first device is not a TPU, when the chips are
not as many as the cell asks for, or when the program is not in the
checkout. Takes no notice of the environment beyond what JAX reads.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    try:
        import fedrec_tpu  # noqa: F401 - the system under test
    except ImportError as e:
        print(f"chipbench: the program is not in this checkout ({e}); no result",
              file=sys.stderr)
        return 4
    import importlib

    from chipbench import cells

    # one harness module per kind of traffic: ``harness_<kind>.py`` with
    # ``run_cell`` and ``print_result``; a new kind is a new file
    cell = cells.load_cell(ROOT, args.workload)
    harness = importlib.import_module(f"chipbench.harness_{cell['traffic']['kind']}")
    line = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                            bool(args.trace), T_START, cell=cell)
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
