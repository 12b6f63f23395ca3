"""``trace_scopes.py``: the innermost named scope of an op's path, and the
reduction of recorded traces: the new step's (recorded on a v5e by
``tools/record_scopes_fixture.py``) and, for a program that names none of
the scopes, the accepted cells' fixture."""

import gzip
import json
import shutil

import pytest

from chipbench import trace_reduce, trace_scopes
from chipbench.harness_training_rounds_tokens import SCOPES
from conftest import BENCH

FIXTURES = BENCH / "fixtures"


@pytest.mark.parametrize("path,scope", [
    ("jit(sharded_step)/jit(main)/jvp(TextEncoder)/trunk/layer_1/moe_experts/ragged_dot_general", "moe_experts"),
    ("jit(sharded_step)/transpose(jvp(TextEncoder))/trunk/layer_0/trunk_attention/attn/q_proj/dot_general", "trunk_attention"),
    # a rematerialised forward inside the backward pass keeps its scope
    ("jit(sharded_step)/transpose(jvp(TextEncoder))/trunk/rematted_computation/layer_2/moe_route/sort", "moe_route"),
    # nested: the innermost wins
    ("jit(f)/trunk_attention/moe_route/top_k", "moe_route"),
    ("jit(f)/moe_route/layer_0/trunk_attention/add", "trunk_attention"),
    # a longer name that holds a scope's name is not that scope
    ("jit(f)/not_moe_experts_at_all/dot_general", ""),
    ("jit(sharded_step)/vmap(jvp(NewsRecommender))/user_encoder/pool/reduce_sum", ""),
    ("jit(f)/jvp(text_head)/head/fc/dot_general", "text_head"),
    ("", ""),
])
def test_innermost_scope(path, scope):
    assert trace_scopes.innermost(path, SCOPES) == scope


def test_a_loop_counts_once():
    """A ``while`` spans its body's ops on the same line: its own time is
    what is left of it, and the sum is the time the device was busy."""
    ops = [("jit(f)/moe_route/while", 0.0, 100.0),
           ("jit(f)/moe_route/while/body/moe_experts/dot", 10.0, 30.0),
           ("jit(f)/moe_route/while/body/moe_combine/gather", 40.0, 20.0),
           ("jit(f)/trunk_attention/dot", 100.0, 50.0),      # starts where the loop ends
           ("jit(f)/while", 200.0, 10.0),
           ("jit(f)/while/body/while", 201.0, 8.0),
           ("jit(f)/while/body/while/body/text_head/add", 202.0, 5.0)]
    own = {name: t for name, _, t in trace_scopes.self_times(ops)}
    assert own["jit(f)/moe_route/while"] == 50.0
    assert own["jit(f)/trunk_attention/dot"] == 50.0
    assert own["jit(f)/while"] == 2.0 and own["jit(f)/while/body/while"] == 3.0
    assert sum(own.values()) == 160.0


def _unzip(name, tmp_path):
    out = tmp_path / name.removesuffix(".gz")
    with gzip.open(FIXTURES / name) as src, open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return out


def test_a_program_without_the_scopes_reduces_to_the_unnamed_rest(tmp_path):
    """The accepted cells' step names none of the trunk's scopes: its device
    time is unnamed or its text head's, in all what ``trace_reduce`` sums over its ops
    (two readers of one file agree), and a scope's metric finds nothing."""
    path = _unzip("fed8_steps.xplane.pb.gz", tmp_path)
    scopes = trace_scopes.reduce_scopes(path, SCOPES)
    # (its flax module ``text_head`` bears the name of one of the scopes)
    assert set(scopes) == {"", "text_head"}
    reduced = trace_reduce.reduce_trace(trace_reduce.read_trace(path), [])
    assert sum(scopes.values()) == pytest.approx(sum(reduced["ops"].values()), rel=1e-4)
    run = {"trace": {**reduced, "scopes": scopes}, "module_names": {"train_step": "jit_sharded_step"}}
    assert trace_scopes.scope_ms_per_step(run, ("moe_experts",)) is None
    assert trace_scopes.scope_ms_per_step({"trace": reduced, "module_names": {}}, ("moe_experts",)) is None
    # the flax module names of that program are scopes like any other
    by_module = trace_scopes.reduce_scopes(path, ("user_encoder", "text_head"))
    assert by_module["user_encoder"] > 0 and by_module["text_head"] > 0


def test_the_recorded_trunk_step_reduces_as_recorded(tmp_path):
    path = _unzip("st21b_steps.xplane.pb.gz", tmp_path)
    expected = json.loads((FIXTURES / "st21b_steps.expected.json").read_text())
    scopes = trace_scopes.reduce_scopes(path, SCOPES)
    assert set(SCOPES) <= set(scopes)
    for name, seconds in expected["scopes"].items():
        assert scopes[name] == pytest.approx(seconds, rel=1e-9), name
    # each op's own time: the sum is the time the step's program ran, where
    # ``trace_reduce``'s per-op sums count the chunk loops and their bodies
    busy = sum(m["seconds"] for m in expected["modules"].values())
    assert sum(scopes.values()) == pytest.approx(busy, rel=1e-3)
    assert expected["ops_seconds"] > 1.3 * busy
    # the trunk takes nearly all of the step; what no scope names is the
    # user tower, the loss, the dedup and Adam
    assert scopes[""] < 0.5 * sum(scopes.values())
    run = {"trace": {"scopes": scopes, "modules": expected["modules"]},
           "module_names": {"train_step": "jit_sharded_step"}}
    steps = expected["modules"]["jit_sharded_step"]["count"]
    assert trace_scopes.scope_ms_per_step(run, ("moe_route", "moe_combine")) == pytest.approx(
        (scopes["moe_route"] + scopes["moe_combine"]) / steps * 1e3)
