"""``metrics/encoded_news_pct.py``: the reader on a run the harness recorded
from the program (the tiny cell, on the CPU path of the TEST only), on spans
as a window with one full-size step would hold them, and on a program whose
``dispatch`` spans carry no ``rows`` (the parent of PR 30): nothing to read."""

import time

import pytest

from chipbench import cells
from chipbench import harness_training_rounds as harness
from conftest import BENCH, TINY_SHAPES, write_tiny_benchmark

LIMITS = {
    "loss_gap": 2.5e-3, "grad_gap": 3.2e-2, "delta_gap_median": 3.7e-3, "sync_gap": 1e-5,
    "bad_batch_rows": 0, "rounds_failed": 0, "nonfinite_losses": 0, "compiled_in_window": 0,
}


def read(run):
    return cells.load_reader(BENCH, "encoded_news_pct")(run)


def span(name, **args):
    return {"name": name, "start_ns": 0, "end_ns": 1, "args": args}


def test_reader_on_a_recorded_run(tmp_path, monkeypatch):
    kept = {}
    end_to_end = harness._end_to_end
    monkeypatch.setattr(harness, "_end_to_end",
                        lambda run: kept.update(run=run) or end_to_end(run))
    workload = write_tiny_benchmark(tmp_path, LIMITS)
    line = harness.run_cell(tmp_path, workload, 6, 0.5, False, time.perf_counter(),
                            need_tpu=False, bench_dir=tmp_path / "chipbench")
    assert line["correct"] is True
    dispatches = [s for s in kept["run"]["spans"] if s["name"] == "dispatch"]
    slots = TINY_SHAPES["batch_per_client"] * (TINY_SHAPES["candidates"] + TINY_SHAPES["history"])
    assert dispatches and all(s["args"]["slots"] == slots for s in dispatches)
    rows = {s["args"]["rows"] for s in dispatches}
    assert len(rows) == 1                   # one size a run: no step at the full size
    value = read(kept["run"])
    assert value == pytest.approx(100.0 * rows.pop() / slots)
    # never under the traffic's own share, which distinct_news_pct reads
    assert 100.0 * kept["run"]["distinct_news_share"] <= value <= 100.0


def test_reader_counts_a_step_served_at_the_full_size():
    spans = [span("dispatch", kind="step", n=1, rows=2880, slots=3520)] * 31
    spans += [span("dispatch", kind="step", n=1, rows=3520, slots=3520),
              span("h2d", n=1), span("batch_build", epoch=3)]
    assert read({"spans": spans}) == pytest.approx(100.0 * (31 * 2880 + 3520) / (32 * 3520))


@pytest.mark.parametrize("run", [
    {"spans": [span("dispatch", kind="step", n=1), span("h2d", n=1)]},
    {"spans": []}, {},
], ids=["parent-spans", "no-spans", "no-run"])
def test_reader_finds_nothing_in_a_program_without_the_arguments(run):
    assert read(run) is None
