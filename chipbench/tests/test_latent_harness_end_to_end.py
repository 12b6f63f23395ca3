"""The latent trunk's harness (``kind: training_rounds_tokens_latent``) end
to end at a tiny size, on the CPU path of the TEST only (``need_tpu=False``),
with a throw-away configuration, traffic mix and cell added in a temporary
directory as a PR adds them: the trunk's family, depth and share go in
through the overrides, the published keys of the configuration file say the
same, and the harness refuses a file whose two halves disagree. The ranks,
the head's two parts and the dense width stay as published (the overrides
cannot shrink them)."""

import json
import math
import shutil
import time

import pytest

from chipbench import harness_training_rounds_tokens_latent as harness
from conftest import BENCH, ROOT, TINY_TRAFFIC

TINY = json.loads((BENCH / "configs" / "mind-xing29b-ep8.json").read_text())
TINY.update({
    "name": "tiny-latent", "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 4,
    "moe_intermediate_size": 16, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 16, "vocab_size": 500,
    "held": {"first_expert": 16, "vocab_first": 0},
    "shapes": {"clients": 1, "batch_per_client": 4, "candidates": 5, "history": 6, "title_len": 8,
               "bert_hidden": 32, "attn_hidden": 16, "news_dim": 32, "heads": 4, "head_dim": 8,
               "query_dim": 16, "catalog_rows": 256},
    "overrides": [
        "fed.num_clients=1", "fed.strategy=grad_avg", "data.batch_size=4", "data.dataset=synthetic",
        "data.max_his_len=6", "data.max_title_len=8",
        "model.text_encoder_mode=finetune", "model.text_trunk=latent_moe",
        "model.bert_hidden=32", "model.trunk_layers=3", "model.trunk_dense_layers=1",
        "model.trunk_heads=4", "model.trunk_ffn=16",
        "model.trunk_vocab=500", "model.trunk_first_expert=16", "model.trunk_experts_held=16",
        "model.dtype=bfloat16", "model.dropout_rate=0.0", "model.news_dim=32", "model.num_heads=4",
        "model.head_dim=8", "model.query_dim=16",
        "fed.rounds=1000000", "train.eval_every=1000000", "train.save_every=1000000",
        "train.snapshot_dir=", "train.resume=false"],
})
TRAFFIC = dict(TINY_TRAFFIC, kind="training_rounds_tokens_latent",
               token_ids={"law": "uniform_over_held_rows", "mask": "full"})
# tiny-size limits, set the way the cell's limits are set (PERF.md): above
# what sound runs of the tiny cell read on seeds 1-6 and 2147483659 (loss up to
# 4.7e-3, first gradient up to 0.118, median leaf's change up to 2.7e-3: with
# 160 tokens a step, one token that a bfloat16 near-tie sends to another
# expert shows) and, for the first gradient, below what the four faults read
# on seeds 4-5 (0.149 to 0.72; the dropped choice is the nearest). The held
# experts' own gradients read up to 0.033 in sound runs, 0.149 to 0.173 with
# the dropped choice and 0.16 to 0.36 with half the batch (seeds 4-6)
LIMITS = {"loss_gap": 8e-3, "grad_gap": 0.135, "experts_grad_gap": 0.07, "router_bias_grad": 0,
          "delta_gap_median": 5e-3, "bad_batch_rows": 0, "rounds_failed": 0, "nonfinite_losses": 0, "compiled_in_window": 0}


def write_cell(root, config=TINY):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bdir = root / "chipbench"
    for sub in ("configs", "traffic", "limits"):
        (bdir / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "metrics", bdir / "metrics", dirs_exist_ok=True)
    (bdir / "configs" / "tiny-latent.json").write_text(json.dumps(config))
    (bdir / "traffic" / "tinylatent.json").write_text(json.dumps(TRAFFIC))
    (bdir / "limits" / "tiny.latent.json").write_text(json.dumps({"limits": LIMITS}))
    bench["configs"].append({"name": "tiny-latent", "source": "test",
                             "file": "chipbench/configs/tiny-latent.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.latent", "config": "tiny-latent", "traffic": "tinylatent",
                               "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if "xing29b-ep8.b2" in m.get("workloads", []):
            m["workloads"].append("tiny.latent")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return "tiny.latent"


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
    # held (16-31 of 64), so most pairs fall on absent ones
    said = err.split("routing gauges of the last round: ")[1].splitlines()[0]
    routing = json.loads(said.split("; ")[0].replace("'", '"'))
    assert 0.4 < routing["absent_share"] < 0.95 and routing["load_max_over_mean"] >= 1.0
    assert 0.0 <= float(said.split("trunk.residual_mix_err_max ")[1]) < 2.0


def test_same_seed_same_inputs(tmp_path):
    a, b = run_tiny(tmp_path, seed=5), run_tiny(tmp_path, seed=5)
    assert a["compared"]["loss_gap"] == b["compared"]["loss_gap"]


def test_a_configuration_whose_halves_disagree_is_refused(tmp_path):
    wrong = dict(TINY, first_k_dense_replace=2)          # the overrides hold 1
    with pytest.raises(ValueError, match="trunk.dense_layers"):
        run_tiny(tmp_path, seed=1, config=wrong)


@pytest.mark.parametrize("fault", ["drop_last_choice", "bias_in_weights", "one_sinkhorn_iteration", "plain_rope",
                                   "half_batch"])
def test_a_fault_in_the_step_is_not_correct(tmp_path, monkeypatch, fault):
    """The reference with a fault planted (one of the equations', or a step
    that sees half its batch) stands in for a program that has it: the
    gaps are symmetric, and the cell's limits must see it."""
    from chipbench import reference_latent_trunk as ref

    sound = ref.follow_steps
    planted = {"keep": slice(0, TINY["shapes"]["batch_per_client"] // 2)} if fault == "half_batch" else {"fault": fault}
    monkeypatch.setattr(ref, "follow_steps", lambda *a, **kw: sound(*a, **planted, **kw))
    line = run_tiny(tmp_path, seed=4)
    over = {k for k, c in line["compared"].items() if not c["value"] <= c["limit"]}
    assert line["correct"] is False and over, line["compared"]
    if fault in ("drop_last_choice", "half_batch"):
        assert {"experts_grad_gap", "loss_gap"} & over, line["compared"]


def test_the_routed_numbers_read_the_experts_and_the_bias():
    leaf = lambda g_ref, g_prog: (g_ref, g_prog, 1.0, 1.0)  # noqa: E731
    compared = {"per_leaf": [
        {"news/trunk/layer_1_ffn/ffn/experts/w_down": leaf(2.0, 1.5),
         "news/trunk/layer_2_ffn/ffn/experts/w_up": leaf(1.0, 1.1),
         "news/trunk/layer_1_ffn/ffn/router": leaf(1.0, 9.0),
         "news/trunk/layer_1_ffn/ffn/router_bias": leaf(0.0, 0.0)},
        {"news/trunk/layer_1_ffn/ffn/experts/w_down": leaf(2.0, 2.0),
         "news/trunk/layer_1_ffn/ffn/router_bias": leaf(0.0, 3e-4)}]}
    assert harness.routed_numbers(compared) == {"experts_grad_gap": 0.25, "router_bias_grad": 3e-4}
    not_a_number = {"per_leaf": [{"a/experts/w_up": leaf(1.0, math.nan), "a/experts/w_down": leaf(1.0, 1.0)}]}
    assert math.isnan(harness.routed_numbers(not_a_number)["experts_grad_gap"])   # and so fails its limit
