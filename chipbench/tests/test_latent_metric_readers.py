"""The eight readers of the latent trunk's per-layer metrics on a made-up
``run``: what each reads, and that each returns nothing, without raising,
from a run that lacks it (a program from before the trunk, an untraced run,
a device that is not a TPU)."""

import json

import pytest

from chipbench import cells, flops_latent_trunk
from conftest import BENCH, ROOT

CONFIG = json.loads((BENCH / "configs" / "mind-xing29b-ep8.json").read_text())
NAMES = ("latent_trunk_step_mfu_pct", "latent_attention_device_ms", "residual_mix_device_ms",
         "latent_experts_device_ms", "latent_experts_roofline_pct", "residual_mix_err_max",
         "latent_expert_load_max_over_mean", "latent_route_device_ms")


def reader(name):
    return cells.load_reader(BENCH, name)


def made_up_run():
    from chipbench import corpus_latent

    return {
        "shapes": CONFIG["shapes"], "trunk": corpus_latent.trunk_of(CONFIG),
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "peaks": {"bf16_flops_per_s": 197e12}, "hbm_bytes_per_s": 819e9,
        "module_names": {"train_step": "jit_sharded_step"}, "mixer": 0.25,
        "routing": {"load_max_over_mean": 1.5, "absent_share": 0.875},
        "trace": {
            "window_s": 8.0, "modules": {"jit_sharded_step": {"count": 16, "seconds": 6.4}},
            "scopes": {"": 0.8, "latent_attention": 1.6, "residual_mix": 1.28, "moe_route": 0.16,
                       "moe_experts": 0.32, "moe_combine": 0.08, "shared_expert": 0.24,
                       "dense_ffn": 0.4, "trunk_embed": 0.01, "text_head": 0.02},
        },
    }


def test_each_reader_reads_its_own():
    run = made_up_run()
    assert reader("latent_attention_device_ms")(run) == pytest.approx(100.0)
    assert reader("residual_mix_device_ms")(run) == pytest.approx(80.0)
    assert reader("latent_experts_device_ms")(run) == pytest.approx(50.0)
    assert reader("residual_mix_err_max")(run) == 0.25
    assert reader("latent_expert_load_max_over_mean")(run) == 150.0
    assert reader("latent_route_device_ms")(run) == pytest.approx(15.0)
    flops_step = flops_latent_trunk.train_step_flops(run["shapes"], run["trunk"])
    assert reader("latent_trunk_step_mfu_pct")(run) == pytest.approx(100 * flops_step * 16 / 8.0 / 197e12)
    assert 0 < reader("latent_trunk_step_mfu_pct")(run) < 100
    # 20 ms a step in the grouped products against 3.70 ms by bytes (3.69 by operations)
    least = flops_latent_trunk.experts_bytes_per_step(run["shapes"], run["trunk"]) / 819e9
    assert reader("latent_experts_roofline_pct")(run) == pytest.approx(100 * least / 0.020)
    assert reader("latent_experts_roofline_pct")(run) == pytest.approx(18.5, abs=0.1)


def test_the_roofline_counts_the_pairs_the_program_counted():
    run = made_up_run()
    even = reader("latent_experts_roofline_pct")(run)
    run["routing"]["absent_share"] = 0.9                  # a fifth fewer pairs on held experts than expected
    rows = 0.1 * 4 * 5500
    assert rows == pytest.approx(0.8 * flops_latent_trunk.held_pairs_per_token(run["trunk"]) * 5500)
    least = flops_latent_trunk.experts_bytes_per_step(run["shapes"], run["trunk"], 0.1) / 819e9
    assert reader("latent_experts_roofline_pct")(run) == pytest.approx(100 * least / 0.020)
    assert 0.8 * even < reader("latent_experts_roofline_pct")(run) < even   # the weights' bytes do not shrink
    run["routing"] = None                                 # no counter, no share
    assert reader("latent_experts_roofline_pct")(run) is None


def test_the_roofline_takes_the_larger_of_operations_and_bytes():
    run = made_up_run()
    run["hbm_bytes_per_s"] = 8190e9                       # ten times the bandwidth: operations bind
    least = flops_latent_trunk.experts_flops_per_step(run["shapes"], run["trunk"]) / 197e12
    assert reader("latent_experts_roofline_pct")(run) == pytest.approx(100 * least / 0.020)


@pytest.mark.parametrize("name", NAMES)
def test_a_run_without_it_reads_nothing_and_does_not_raise(name):
    parent = made_up_run()                                # a program from before the trunk:
    parent["trace"]["scopes"] = {"": 6.0}                 # no such scope, no gauge, no group
    parent.update(trunk=None, mixer=None, hbm_bytes_per_s=None, routing=None)
    if name != "latent_trunk_step_mfu_pct":               # (the step's share needs the trunk group only)
        assert reader(name)(parent) is None
    untraced = {**made_up_run(), "trace": None, "mixer": None, "routing": None}
    assert reader(name)(untraced) is None
    assert reader(name)({"trace": None}) is None


def test_the_benchmark_lists_the_eight_for_the_cell_alone():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = [m for m in bench["per_layer"] if m["name"] in NAMES]
    assert [m["name"] for m in mine] == list(NAMES) == [m["name"] for m in bench["per_layer"][-8:]]
    for m in mine:
        assert m["workloads"] == ["xing29b-ep8.b2"] and m["layer"] == "latent trunk"
        assert m["moves"] == "train_samples_per_s" and (BENCH / "metrics" / f"{m['name']}.py").exists()
    cell = cells.load_cell(ROOT, "xing29b-ep8.b2")
    assert cell["traffic"]["kind"] == "training_rounds_tokens_latent" and cell["chips"] == 1
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(NAMES) <= reported and "moe_experts_roofline_pct" not in reported
    # the eight accepted metrics without a list of cells are read here too
    assert {"compile_cache_misses", "host_build_ms_per_step", "dispatch_ms_per_step", "distinct_news_pct",
            "train_step_device_ms", "device_idle_pct", "hbm_peak_gb", "round_end_host_ms"} <= reported
