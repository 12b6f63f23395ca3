"""The token-table harness (``kind: training_rounds_tokens``) end to end at a
tiny size, on the CPU path of the TEST only (``need_tpu=False``), with a
throw-away configuration, traffic mix and cell added in a temporary
directory as a PR adds them: the trunk's family, depth and share go in
through the overrides, the published keys of the configuration file say
the same, and the harness refuses a file whose two halves disagree."""

import json
import shutil
import time

import pytest

from chipbench import harness_training_rounds_tokens as harness
from conftest import BENCH, ROOT, TINY_TRAFFIC

TINY = json.loads((BENCH / "configs" / "mind-smallthinker21b-ep4.json").read_text())
TINY.update({
    "name": "tiny-tokens", "hidden_size": 32, "num_attention_heads": 4, "moe_ffn_hidden_size": 16,
    "num_hidden_layers": 4, "moe_num_primary_experts": 16, "vocab_size": 500,
    "held": {"first_expert": 16, "vocab_first": 0},
    "shapes": {"clients": 1, "batch_per_client": 4, "candidates": 5, "history": 6, "title_len": 8,
               "bert_hidden": 32, "attn_hidden": 16, "news_dim": 32, "heads": 4, "head_dim": 8,
               "query_dim": 16, "catalog_rows": 256},
    "overrides": [
        "fed.num_clients=1", "fed.strategy=grad_avg", "data.batch_size=4", "data.dataset=synthetic",
        "data.max_his_len=6", "data.max_title_len=8",
        "model.text_encoder_mode=finetune", "model.text_trunk=sparse_expert",
        "model.bert_hidden=32", "model.trunk_layers=4", "model.trunk_heads=4", "model.trunk_ffn=16",
        "model.trunk_vocab=500", "model.trunk_first_expert=16", "model.trunk_experts_held=16",
        "model.dtype=bfloat16", "model.dropout_rate=0.0", "model.news_dim=32", "model.num_heads=4",
        "model.head_dim=8", "model.query_dim=16",
        "fed.rounds=1000000", "train.eval_every=1000000", "train.save_every=1000000",
        "train.snapshot_dir=", "train.resume=false"],
})
TRAFFIC = dict(TINY_TRAFFIC, kind="training_rounds_tokens",
               token_ids={"law": "uniform_over_held_rows", "mask": "full"})
# tiny-size limits, set the way the cell's limits are set (PERF.md): above
# what sound runs of the tiny cell read on seeds 1-8 (loss up to 1.8e-3, first
# gradient up to 2.3e-2, median leaf's change up to 1.0e-3) and below what the
# dropped 6th choice reads on seeds 4-6 (first gradient 4.8e-2 to 7.8e-2,
# median leaf's change 1.4e-3 to 2.0e-3)
LIMITS = {"loss_gap": 4e-3, "grad_gap": 3.5e-2, "delta_gap_median": 1.25e-3,
          "bad_batch_rows": 0, "rounds_failed": 0, "nonfinite_losses": 0, "compiled_in_window": 0}


def write_cell(root, config=TINY):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bdir = root / "chipbench"
    for sub in ("configs", "traffic", "limits"):
        (bdir / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "metrics", bdir / "metrics", dirs_exist_ok=True)
    (bdir / "configs" / "tiny-tokens.json").write_text(json.dumps(config))
    (bdir / "traffic" / "tinytokens.json").write_text(json.dumps(TRAFFIC))
    (bdir / "limits" / "tiny.tokens.json").write_text(json.dumps({"limits": LIMITS}))
    bench["configs"].append({"name": "tiny-tokens", "source": "test",
                             "file": "chipbench/configs/tiny-tokens.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.tokens", "config": "tiny-tokens", "traffic": "tinytokens",
                               "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if "st21b-ep4.b16" in m.get("workloads", []):
            m["workloads"].append("tiny.tokens")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return "tiny.tokens"


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
    # the program's routing gauges reached the harness: a quarter of the
    # experts is held (16-31 of 64), so most pairs fall on absent ones
    routing = json.loads(err.split("routing gauges of the last round: ")[1].splitlines()[0].replace("'", '"'))
    assert 0.4 < routing["absent_share"] < 0.95 and routing["load_max_over_mean"] >= 1.0


def test_same_seed_same_inputs(tmp_path):
    a, b = run_tiny(tmp_path, seed=5), run_tiny(tmp_path, seed=5)
    assert a["compared"]["loss_gap"] == b["compared"]["loss_gap"]


def test_a_configuration_whose_halves_disagree_is_refused(tmp_path):
    wrong = dict(TINY, moe_num_primary_experts=8)        # the overrides hold 16
    with pytest.raises(ValueError, match="trunk.experts_held"):
        run_tiny(tmp_path, seed=1, config=wrong)


@pytest.mark.parametrize("fault", ["drop_last_choice", "rotary_everywhere"])
def test_a_fault_in_the_equations_is_not_correct(tmp_path, monkeypatch, fault):
    """The reference with a fault planted stands in for a program that has
    it: the comparison is symmetric, and the cell's limits must see it."""
    from chipbench import reference_moe_trunk as ref

    sound = ref.follow_steps
    monkeypatch.setattr(ref, "follow_steps", lambda *a, **kw: sound(*a, fault=fault, **kw))
    line = run_tiny(tmp_path, seed=4)
    over = {k for k, c in line["compared"].items() if not c["value"] <= c["limit"]}
    assert line["correct"] is False and over, line["compared"]
