"""Records the trace that ``tests/test_trace_reduce.py`` reduces: a cell's
traced rounds under a traffic file with few steps a round, on the chip.

    python3 chipbench/tools/record_fixture.py --workload fed8.b64 \\
        --traffic rounds4 --out chiprun_out/fixture

writes ``trace.xplane.pb``, ``host_spans.json`` and ``expected.json`` (what
the reduction made of them) under ``--out``, and prints what planes and lines
the trace holds. The committed fixture is that trace, gzipped.
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
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=2025)
    args = ap.parse_args()
    from chipbench import cells, trace_reduce
    from chipbench import harness_training_rounds as harness

    cell = cells.load_cell(ROOT, args.workload)
    cell["traffic"] = json.loads((cells.BENCH_DIR / "traffic" / f"{args.traffic}.json").read_text())
    out = Path(args.out)
    line = harness.run_cell(ROOT, args.workload, args.seed, 3.0, True, T_START,
                            cell=cell, keep_trace=out)
    harness.print_result(line)

    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(out / "trace.xplane.pb"))
    for plane in data.planes:
        print("PLANE", plane.name)
        for ln in plane.lines:
            events = list(ln.events)
            names = sorted({trace_reduce.module_name(e.name) for e in events})
            print("  LINE", ln.name, len(events), names[:12])
    raw = trace_reduce.read_trace(out / "trace.xplane.pb")
    spans = json.loads((out / "host_spans.json").read_text())
    red = trace_reduce.reduce_trace(raw, spans)
    expected = {
        "recorded": f"{args.workload} under traffic {args.traffic}, one v5e chip, chipbench/tools/record_fixture.py",
        "window_s": red["window_s"], "busy_s": red["busy_s"], "modules": red["modules"],
        "top_ops": [n for n, _ in trace_reduce.top(red["ops"], 5)],
        "idle_by_host_activity": red["idle_by_host_activity"], "host_spans": spans,
    }
    (out / "expected.json").write_text(json.dumps(expected, indent=1))
    print(json.dumps({k: expected[k] for k in ("window_s", "busy_s", "modules", "top_ops", "idle_by_host_activity")}))
    print(json.dumps(trace_reduce.top(red["ops"], 25)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
