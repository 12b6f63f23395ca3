"""The yardstick's arithmetic and the names in ``BENCHMARK.json``."""

import json
import re

import pytest

from chipbench import cells, flops, peaks
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


def shapes_of(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())["shapes"]


def test_text_head_by_hand():
    # per slot: fc1 2 x (2*50*768*384), fc2 3 x (2*50*384), pool 2 x (2*50*768),
    # fc 3 x (2*768*400)
    assert flops.text_head_flops_per_slot(shapes_of("mind-small-fed8")) == (
        2 * 29_491_200 + 3 * 38_400 + 2 * 76_800 + 3 * 614_400
    ) == 61_094_400


def test_user_tower_by_hand():
    # per sample: q/k/v 48,000,000; scores+context 4,000,000; pool 8,000,000 +
    # 20,000 + 40,000; score 4,000; all x3
    assert flops.user_tower_flops_per_sample(shapes_of("mind-small-fed8")) == 3 * 60_064_000


@pytest.mark.parametrize("name", ["mind-small-fed8", "mind-small-central"])
def test_step_flops_by_hand(name):
    # 28,160 slots x 61,094,400 + 512 samples x 180,192,000: both cells do the
    # same required work a step, 1.81e12 operations, 9.2 ms at the chip's peak
    s = shapes_of(name)
    assert flops.samples_per_step(s) == 512
    assert flops.train_step_flops(s) == 28_160 * 61_094_400 + 512 * 180_192_000 == 1_812_676_608_000
    peak = peaks.chip_peaks("TPU v5 lite")["bf16_flops_per_s"]
    assert 9.1e-3 < flops.train_step_flops(s) / peak < 9.3e-3


def test_flop_count_reads_shapes_only():
    s = dict(shapes_of("mind-small-fed8"))
    s.pop("query_dim")
    with pytest.raises(KeyError):
        flops.train_step_flops(s)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.chip_peaks("TPU v9")


def test_names_and_units():
    allowed_units = {"samples/s", "ms", "%", "GB", "s", "count"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCHMARK[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert UNIT.match(m["unit"]) and m["unit"] in allowed_units, m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in BENCHMARK["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.1
    for w in BENCHMARK["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and len(w["why"]) <= 200
    assert any(m["name"] == "setup_s" for m in BENCHMARK["end_to_end"])


def test_every_entry_has_its_file():
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    layers = (ROOT / "PERF.md").read_text()
    for m in BENCHMARK["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]
        assert m["moves"] in e2e
        assert m["layer"] in layers
    for w in BENCHMARK["workloads"]:
        cell = cells.load_cell(ROOT, w["name"])
        assert cell["config"]["shapes"]["clients"] * cell["config"]["shapes"]["batch_per_client"] == 512
        assert set(cell["limits"]) >= {"loss_gap", "grad_gap", "delta_gap_median"}
        assert {m["name"] for m in cell["per_layer"]} <= {p.stem for p in (BENCH / "metrics").glob("*.py")}
    fed = cells.load_cell(ROOT, "fed8.b64")
    central = cells.load_cell(ROOT, "central.b512")
    assert "round_sync_device_ms" in {m["name"] for m in fed["per_layer"]}
    assert "round_sync_device_ms" not in {m["name"] for m in central["per_layer"]}
