"""Records the trace that ``tests/test_trace_scopes.py`` reduces: one traced
round of two steps of a token-table cell, on the chip, as
``record_fixture.py`` records the other fixture.

    python3 chipbench/tools/record_scopes_fixture.py --workload st21b-ep4.b16 \\
        --out chiprun_out/scopes_fixture

writes ``trace.xplane.pb``, ``host_spans.json`` and ``expected.json`` (device
seconds by scope, as ``trace_scopes.reduce_scopes`` made them) under
``--out``. The committed fixture is that trace, gzipped.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=2029)
    args = ap.parse_args()
    from chipbench import cells, trace_reduce, trace_scopes
    from chipbench import harness_training_rounds_tokens as harness

    cell = cells.load_cell(ROOT, args.workload)
    per_step = cell["config"]["shapes"]["clients"] * cell["config"]["shapes"]["batch_per_client"]
    cell["traffic"] = dict(cell["traffic"], samples_per_round=3 * per_step, traced_rounds=1)
    out = Path(args.out)
    # the second round of the window is the traced one: a window that ends
    # inside it (a round of three steps takes about 3.5 s)
    line = harness.run_cell(ROOT, args.workload, args.seed, 6.0, True, T_START, cell=cell, keep_trace=out)
    harness.print_result(line)
    scopes = trace_scopes.reduce_scopes(out / "trace.xplane.pb", harness.SCOPES)
    raw = trace_reduce.read_trace(out / "trace.xplane.pb")
    red = trace_reduce.reduce_trace(raw, json.loads((out / "host_spans.json").read_text()))
    expected = {
        "recorded": f"{args.workload}, one traced round of three steps, one v5e chip, "
                    "chipbench/tools/record_scopes_fixture.py",
        "scopes": scopes, "ops_seconds": sum(red["ops"].values()), "modules": red["modules"],
    }
    (out / "expected.json").write_text(json.dumps(expected, indent=1))
    print(json.dumps(expected))
    return 0


if __name__ == "__main__":
    sys.exit(main())
