"""The five readers PR 38 adds (``round_prologue_host_ms``,
``round_epilogue_host_ms``, ``round_unspanned_host_ms``,
``round_other_unexplained_ms``, ``unscoped_device_pct``) on hand-made runs:
what each reads where the program's spans and the harness's keys are there,
nothing where they are not (the parent's runs), and their entries in
``BENCHMARK.json`` by name."""

import json

import pytest

from chipbench import cells
from conftest import BENCH, ROOT

ALL_FIVE = ["fed8.b64", "central.b512", "st21b-ep4.b16", "xing29b-ep8.b2", "laguna33b-ep8.b1"]
ROUTED = ALL_FIVE[2:]


def read(metric, run):
    return cells.load_reader(BENCH, metric)(run)


def span(name, start_ms, dur_ms):
    return {"name": name, "start_ns": int(start_ms * 1e6),
            "end_ns": int((start_ms + dur_ms) * 1e6)}


def tiled_round(at, prologue=4.0, end=2.0, epilogue=1.0):
    """A round of two steps whose children leave 0.25 ms unspanned."""
    t, out = at + 0.05, []
    for name, dur in (("round_prologue", prologue), ("batch_build", 1.0), ("h2d", 0.5),
                      ("dispatch", 0.7), ("step_keep", 0.1), ("batch_build", 1.0),
                      ("h2d", 0.5), ("dispatch", 0.7), ("step_keep", 0.1),
                      ("device_wait", 50.0), ("round_end", end), ("round_epilogue", epilogue)):
        out.append(span(name, t, dur))
        t += dur + 0.0125
    return [span("fed_round", at, t + 0.05 - at), *out]


PARENT_ROUND = [span("fed_round", 0, 100), span("batch_build", 5, 1), span("h2d", 6, 0.5),
                span("dispatch", 7, 1), span("round_end", 90, 2)]


@pytest.mark.parametrize("metric,expected", [
    ("round_prologue_host_ms", 5.0), ("round_epilogue_host_ms", 1.5),
])
def test_mean_of_the_traced_rounds_spans(metric, expected):
    spans = tiled_round(0) + tiled_round(100, prologue=6.0, epilogue=2.0)
    assert read(metric, {"traced_spans": spans}) == pytest.approx(expected)
    # the window's other rounds (``spans``) are not read
    assert read(metric, {"traced_spans": tiled_round(0), "spans": spans}) == pytest.approx(
        {"round_prologue_host_ms": 4.0, "round_epilogue_host_ms": 1.0}[metric])


def test_unspanned_is_the_round_less_the_union_of_what_lies_inside():
    spans = tiled_round(0) + tiled_round(100)
    assert read("round_unspanned_host_ms", {"traced_spans": spans}) == pytest.approx(0.25)
    # spans that overlap (a nested one, one that runs into the next) count once
    overlapping = spans + [span("eval", 0.06, 2.0), span("hbm", 3.9, 0.5)]
    assert read("round_unspanned_host_ms", {"traced_spans": overlapping}) == pytest.approx(0.25 - 0.0125 / 2)
    # a span that reaches outside its round is not inside it
    assert read("round_unspanned_host_ms", {"traced_spans": spans + [span("capture", 70, 60)]}) \
        == pytest.approx(0.25)


@pytest.mark.parametrize("idle_s,expected", [(0.0170, 1.5), (0.0120, -1.0), (None, -7.0)],
                         ids=["more-idle-than-named", "negative", "no-round-other-gap"])
def test_round_other_unexplained_is_signed(idle_s, expected):
    spans = tiled_round(0) + tiled_round(100)             # 7 ms a round under the three
    gaps = {"dispatch": 0.001} if idle_s is None else {"round_other": idle_s, "h2d": 0.002}
    run = {"traced_spans": spans, "trace": {"idle_by_host_activity": gaps}}
    assert read("round_other_unexplained_ms", run) == pytest.approx(expected)


def test_unscoped_share_of_the_steps_device_time():
    run = {"module_names": {"train_step": "jit_sharded_step"},
           "trace": {"modules": {"jit_sharded_step": {"count": 16.0, "seconds": 4.0},
                                 "jit_other": {"count": 1.0, "seconds": 9.0}},
                     "scopes": {"": 0.6, "moe_route": 1.4, "trunk_attention": 2.0}}}
    assert read("unscoped_device_pct", run) == pytest.approx(15.0)
    run["trace"]["scopes"].pop("")
    assert read("unscoped_device_pct", run) == 0.0


TRACE = {"idle_by_host_activity": {"round_other": 0.017}, "modules": {}, "scopes": None}


@pytest.mark.parametrize("metric", [
    "round_prologue_host_ms", "round_epilogue_host_ms", "round_unspanned_host_ms",
    "round_other_unexplained_ms", "unscoped_device_pct",
])
@pytest.mark.parametrize("run", [
    {"traced_spans": PARENT_ROUND, "trace": TRACE, "module_names": {}},
    {"traced_spans": [], "trace": TRACE, "module_names": {}},
    {"traced_spans": None, "trace": None},
    {"spans": tiled_round(0)}, {},
], ids=["parent-spans", "no-spans", "none", "not-traced", "no-run"])
def test_nothing_to_read_without_the_spans_or_the_keys(metric, run):
    assert read(metric, run) is None


@pytest.mark.parametrize("name,unit,source,layer,workloads", [
    ("round_prologue_host_ms", "ms", "program_span", "round loop", ALL_FIVE),
    ("round_epilogue_host_ms", "ms", "program_span", "round loop", ALL_FIVE),
    ("round_unspanned_host_ms", "ms", "program_span", "round loop", ALL_FIVE),
    ("round_other_unexplained_ms", "ms", "program_span", "round loop", ALL_FIVE),
    ("unscoped_device_pct", "%", "device_trace", "train step", ROUTED),
])
def test_the_entries_in_the_benchmark_by_name(name, unit, source, layer, workloads):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": source,
                     "layer": layer, "moves": "train_samples_per_s", "workloads": workloads}
    assert (BENCH / "metrics" / f"{name}.py").is_file()
    for cell in ALL_FIVE:
        listed = name in [m["name"] for m in cells.load_cell(ROOT, cell)["per_layer"]]
        assert listed == (cell in workloads)
