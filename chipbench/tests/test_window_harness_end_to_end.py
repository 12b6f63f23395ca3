"""The window trunk's harness (``kind: training_rounds_tokens_window``) end
to end at a tiny size, on the CPU path of the TEST only (``need_tpu=False``),
with a throw-away configuration, traffic mix and cell added in a temporary
directory as a PR adds them: the trunk's family, depth and share go in
through the overrides, the published keys of the configuration file say the
same, and the harness refuses a file whose two halves disagree. The head
counts of the two kinds of layer, the head's 128 dimensions, the window of
512 and the dense width stay as published (the overrides cannot shrink
them), so at 24 tokens a text the window does not bind here:
``tests/test_window_trunk.py`` is where it does."""

import json
import shutil
import time

import pytest

from chipbench import harness_training_rounds_tokens_window as harness
from conftest import BENCH, ROOT, TINY_TRAFFIC

TINY = json.loads((BENCH / "configs" / "mind-laguna33b-ep8.json").read_text())
TINY.update({
    "name": "tiny-window", "hidden_size": 32, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "num_hidden_layers": 3, "num_experts": 64, "vocab_size": 500,
    "layer_types": TINY["layer_types"][:3], "mlp_layer_types": TINY["mlp_layer_types"][:3],
    "num_attention_heads_per_layer": TINY["num_attention_heads_per_layer"][:3],
    "held": {"first_expert": 64, "vocab_first": 0},
    "shapes": {"clients": 1, "batch_per_client": 2, "candidates": 5, "history": 6, "title_len": 24,
               "bert_hidden": 32, "attn_hidden": 16, "news_dim": 32, "heads": 4, "head_dim": 8,
               "query_dim": 16, "catalog_rows": 256},
    "overrides": [
        "fed.num_clients=1", "fed.strategy=grad_avg", "data.batch_size=2", "data.dataset=synthetic",
        "data.max_his_len=6", "data.max_title_len=24",
        "model.text_encoder_mode=finetune", "model.text_trunk=window_moe",
        "model.bert_hidden=32", "model.trunk_layers=3", "model.trunk_dense_layers=1",
        "model.trunk_heads=48", "model.trunk_ffn=16",
        "model.trunk_vocab=500", "model.trunk_first_expert=64", "model.trunk_experts_held=64",
        "model.dtype=bfloat16", "model.dropout_rate=0.0", "model.news_dim=32", "model.num_heads=4",
        "model.head_dim=8", "model.query_dim=16",
        "fed.rounds=1000000", "train.eval_every=1000000", "train.save_every=1000000",
        "train.snapshot_dir=", "train.resume=false"],
})
TRAFFIC = dict(TINY_TRAFFIC, kind="training_rounds_tokens_window", samples_per_round=8,
               token_ids={"law": "uniform_over_held_rows", "mask": "full"})
# tiny-size limits, set the way the cell's limits are set (PERF.md): above
# what sound runs of the tiny cell read on seeds 1-6 and 2147483659 (loss up
# to 3.6e-3, first gradient up to 0.050, the held experts' up to 0.032, the
# median leaf's change up to 2.6e-3: with 528 tokens a step, one token that a
# bfloat16 near-tie sends to another expert shows) and below what the five
# faults read on seeds 4-5: whole-head rotary 0.18 / 0.23 and regrouped heads
# 0.35 / 0.44 on the first gradient, a gate of 1 2.2 / 3.0, half a batch 0.41
# / 0.51, the dropped 8th choice 0.056 / 0.097 on the held experts' own
LIMITS = {"loss_gap": 8e-3, "grad_gap": 0.135, "experts_grad_gap": 0.045, "router_bias_grad": 0,
          "delta_gap_median": 5e-3, "bad_batch_rows": 0, "rounds_failed": 0, "nonfinite_losses": 0,
          "compiled_in_window": 0}


def write_cell(root, config=TINY):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bdir = root / "chipbench"
    for sub in ("configs", "traffic", "limits"):
        (bdir / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "metrics", bdir / "metrics", dirs_exist_ok=True)
    (bdir / "configs" / "tiny-window.json").write_text(json.dumps(config))
    (bdir / "traffic" / "tinywindow.json").write_text(json.dumps(TRAFFIC))
    (bdir / "limits" / "tiny.window.json").write_text(json.dumps({"limits": LIMITS}))
    bench["configs"].append({"name": "tiny-window", "source": "test",
                             "file": "chipbench/configs/tiny-window.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.window", "config": "tiny-window", "traffic": "tinywindow",
                               "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if "laguna33b-ep8.b1" in m.get("workloads", []):
            m["workloads"].append("tiny.window")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return "tiny.window"


def run_tiny(root, seed, trace=False, config=TINY):
    workload = write_cell(root, config)
    return harness.run_cell(root, workload, seed, 0.5, trace, time.perf_counter(),
                            need_tpu=False, bench_dir=root / "chipbench")


def test_added_cell_runs_and_is_correct(tmp_path, capsys):
    line = run_tiny(tmp_path, seed=2**31 + 11)          # the driver's seeds are large
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1, line["compared"]
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"           # and so never a device metric
    assert list(line)[-1] == "compared"
    err = capsys.readouterr().err
    # the program's gauges reached the harness: a quarter of the experts is
    # held (64-127 of 256), so most pairs fall on absent ones; a text of 24
    # tokens is one query block, so the core computes the whole square
    said = err.split("routing gauges of the last round: ")[1].splitlines()[0]
    routing = json.loads(said.split("; ")[0].replace("'", '"'))
    assert 0.4 < routing["absent_share"] < 0.95 and routing["load_max_over_mean"] >= 1.0
    share = json.loads(said.split("trunk.attention_scores_computed_share ")[1].replace("'", '"'))
    assert share == {"full": 1.0, "window": 1.0}


def test_same_seed_same_inputs(tmp_path):
    a, b = run_tiny(tmp_path, seed=5), run_tiny(tmp_path, seed=5)
    assert a["compared"]["loss_gap"] == b["compared"]["loss_gap"]


@pytest.mark.parametrize("key,value,named", [
    ("num_key_value_heads", 4, "trunk.kv_heads"),
    ("sliding_window", 256, "trunk.sliding_window"),
    ("mlp_layer_types", ["dense", "dense", "sparse"], "trunk.dense_layers"),
])
def test_a_configuration_whose_halves_disagree_is_refused(tmp_path, key, value, named):
    with pytest.raises(ValueError, match=named):
        run_tiny(tmp_path, seed=1, config=dict(TINY, **{key: value}))


@pytest.mark.parametrize("fault", ["whole_head_rotary", "gate_one", "heads_regrouped", "drop_last_choice",
                                   "half_batch"])
def test_a_fault_in_the_step_is_not_correct(tmp_path, monkeypatch, fault):
    """The reference with a fault planted (one of the equations', or a step
    that sees half its batch) stands in for a program that has it: the
    gaps are symmetric, and the cell's limits must see it. (The window
    ignored is ``tests/test_window_trunk.py``'s: 24 tokens are inside it.)"""
    from chipbench import reference_window_trunk as ref

    sound = ref.follow_steps
    planted = {"keep": slice(0, TINY["shapes"]["batch_per_client"] // 2)} if fault == "half_batch" else {"fault": fault}
    monkeypatch.setattr(ref, "follow_steps", lambda *a, **kw: sound(*a, **planted, **kw))
    line = run_tiny(tmp_path, seed=4)
    over = {k for k, c in line["compared"].items() if not c["value"] <= c["limit"]}
    assert line["correct"] is False and over, line["compared"]
