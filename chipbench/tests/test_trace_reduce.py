"""The reduction from a trace to numbers: on hand-made events, and on the
trace recorded on the chip that is committed under ``fixtures/``."""

import gzip
import json
import shutil

import pytest

from chipbench import trace_reduce as tr
from conftest import BENCH


def test_union_clip_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert tr.gaps_of([(2, 3), (5, 6)], 0, 10) == [(0, 2), (3, 5), (6, 10)]
    assert tr.module_name("jit_sharded_step(123456)") == "jit_sharded_step"


def hand_made():
    # window 1000..11000 ns on the trace clock; perf_counter = trace - 500
    ops = [("fusion.1", 1000.0, 2000.0), ("copy.2", 2500.0, 1500.0),   # overlap 2500..3000
           ("fusion.1", 6000.0, 3000.0), ("fusion.9", 11500.0, 100.0)]  # last one is outside
    modules = [("jit_step(7)", 1000.0, 3000.0), ("jit_step(7)", 6000.0, 3000.0),
               ("jit_sync(9)", 9500.0, 500.0)]
    return {
        "marks": {tr.MARK_BEGIN: (1000.0, 500), tr.MARK_END: (11000.0, 10500)},
        "devices": {0: {"modules": modules, "ops": ops}},
    }


def test_reduce_hand_made_trace():
    spans = [
        {"name": "fed_round", "start_ns": 500, "end_ns": 9500},
        {"name": "batch_build", "start_ns": 3500, "end_ns": 4500},    # trace 4000..5000
        {"name": "dispatch", "start_ns": 4500, "end_ns": 5000},       # trace 5000..5500
    ]
    out = tr.reduce_trace(hand_made(), spans)
    assert out["window_s"] == pytest.approx(10000e-9)
    # busy: 1000..4000 and 6000..9000
    assert out["busy_s"] == pytest.approx(6000e-9)
    assert out["modules"]["jit_step"] == {"count": 2.0, "seconds": pytest.approx(6000e-9)}
    assert out["modules"]["jit_sync"]["count"] == 1.0
    assert out["ops"]["fusion.1"] == pytest.approx(5000e-9) and "fusion.9" not in out["ops"]
    idle = out["idle_by_host_activity"]
    # gaps: 4000..6000 (1000 batch_build, 500 dispatch, 500 elsewhere in the
    # round) and 9000..11000 (1000 inside the round, 1000 after it)
    assert idle["batch_build"] == pytest.approx(1000e-9)
    assert idle["dispatch"] == pytest.approx(500e-9)
    assert idle["round_other"] == pytest.approx(1500e-9)
    assert idle["between_rounds"] == pytest.approx(1000e-9)
    assert sum(idle.values()) == pytest.approx(out["window_s"] - out["busy_s"])
    assert tr.top(out["ops"], 1) == [["fusion.1", pytest.approx(5000e-9)]]


def test_a_trace_without_marks_or_device_is_refused():
    raw = hand_made()
    with pytest.raises(ValueError):
        tr.reduce_trace({"marks": {}, "devices": raw["devices"]})
    with pytest.raises(ValueError):
        tr.reduce_trace({"marks": raw["marks"], "devices": {}})


def test_fixture_trace_reduces_to_fixed_numbers(tmp_path):
    """A few steps of ``fed8.b64`` recorded on a v5e chip (PR 25)."""
    expected = json.loads((BENCH / "fixtures" / "fed8_steps.expected.json").read_text())
    packed = BENCH / "fixtures" / "fed8_steps.xplane.pb.gz"
    plain = tmp_path / "fed8_steps.xplane.pb"
    with gzip.open(packed, "rb") as src, open(plain, "wb") as dst:
        shutil.copyfileobj(src, dst)
    raw = tr.read_trace(plain)
    out = tr.reduce_trace(raw, expected["host_spans"])
    assert out["window_s"] == pytest.approx(expected["window_s"], rel=1e-9)
    assert out["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    for name, m in expected["modules"].items():
        assert out["modules"][name]["count"] == m["count"]
        assert out["modules"][name]["seconds"] == pytest.approx(m["seconds"], rel=1e-9)
    assert [n for n, _ in tr.top(out["ops"], 5)] == expected["top_ops"]
    for name, s in expected["idle_by_host_activity"].items():
        assert out["idle_by_host_activity"][name] == pytest.approx(s, rel=1e-6)
