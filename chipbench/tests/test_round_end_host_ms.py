"""``metrics/round_end_host_ms.py``: the mean of planted ``round_end`` spans
in ms, nothing to read in a program without the span (the parent of PR 32)
or in a run that was not traced, and the metric's entry in ``BENCHMARK.json``."""

import json

import pytest

from chipbench import cells, trace_reduce
from conftest import BENCH, ROOT


def read(run):
    return cells.load_reader(BENCH, "round_end_host_ms")(run)


def span(name, start_ms, dur_ms):
    return {"name": name, "start_ns": int(start_ms * 1e6),
            "end_ns": int((start_ms + dur_ms) * 1e6)}


def test_reader_gives_the_mean_of_the_traced_rounds_spans_in_ms():
    spans = [span("fed_round", 0, 1000), span("dispatch", 10, 1), span("round_end", 990, 4),
             span("fed_round", 1001, 1000), span("dispatch", 1010, 1), span("round_end", 1990, 7)]
    assert read({"traced_spans": spans}) == pytest.approx(5.5)
    # the window's other rounds (``spans``) are not read: the metric is of the traced rounds
    assert read({"traced_spans": spans[:3], "spans": spans}) == pytest.approx(4.0)


@pytest.mark.parametrize("run", [
    {"traced_spans": [span("fed_round", 0, 1000), span("dispatch", 10, 1)]},
    {"traced_spans": []}, {"traced_spans": None},
    {"spans": [span("round_end", 990, 4)]}, {},
], ids=["parent-spans", "no-spans", "none", "not-traced", "no-run"])
def test_reader_finds_nothing_without_a_traced_round_end_span(run):
    assert read(run) is None


def test_the_metrics_entry_in_the_benchmark():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["per_layer"][-1] == {
        "name": "round_end_host_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "round loop", "moves": "train_samples_per_s",
    }
    # the idleness under the span keeps reading as ``round_other`` in ``breakdown``
    assert "round_end" not in trace_reduce._SPECIFIC
