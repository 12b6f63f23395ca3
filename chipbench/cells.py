"""Finds a cell's files by the names in ``BENCHMARK.json``: the
configuration's file, the traffic mix's file, the limits of the comparison
and the readers of its per-layer metrics. A later PR adds a cell, a
configuration or a metric by adding files and entries; no file here needs
an edit for it."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent


def load_benchmark(root: Path) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, workload: str, bench_dir: Path = BENCH_DIR) -> dict:
    bench = load_benchmark(root)
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        names = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have {names}")
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _read_json(root / config_entry["file"])
    traffic = _read_json(bench_dir / "traffic" / f"{cell['traffic']}.json")
    limits = _read_json(bench_dir / "limits" / f"{workload}.json")

    def reports(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"] if reports(m) and m["moves"] in e2e_names]
    return {
        "name": workload, "chips": int(cell["chips"]), "config_name": cell["config"],
        "traffic_name": cell["traffic"], "config": config, "traffic": traffic,
        "limits": limits["limits"], "end_to_end": end_to_end, "per_layer": per_layer,
        "bench_dir": bench_dir,
    }


def load_reader(bench_dir: Path, metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
