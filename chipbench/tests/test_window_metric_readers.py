"""The nine readers of the window trunk's per-layer metrics on a made-up
``run``: what each reads, and that each returns nothing, without raising,
from a run that lacks it (a program from before the trunk, an untraced run,
a device that is not a TPU)."""

import json

import pytest

from chipbench import cells, flops_window_trunk
from conftest import BENCH, ROOT

CONFIG = json.loads((BENCH / "configs" / "mind-laguna33b-ep8.json").read_text())
NAMES = ("window_trunk_step_mfu_pct", "window_attention_device_ms", "attention_core_device_ms",
         "attention_core_roofline_pct", "window_experts_device_ms", "window_experts_roofline_pct",
         "window_route_device_ms", "window_expert_load_max_over_mean", "attention_scores_computed_pct")


def reader(name):
    return cells.load_reader(BENCH, name)


def made_up_run():
    from chipbench import corpus_window

    return {
        "shapes": CONFIG["shapes"], "trunk": corpus_window.trunk_of(CONFIG),
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "peaks": {"bf16_flops_per_s": 197e12}, "hbm_bytes_per_s": 819e9,
        "module_names": {"train_step": "jit_sharded_step"},
        "routing": {"load_max_over_mean": 1.2, "absent_share": 0.875},
        "attention_share": {"full": 0.5625, "window": 0.46875},
        "trace": {
            "window_s": 16.0, "modules": {"jit_sharded_step": {"count": 8, "seconds": 15.9}},
            "scopes": {"": 1.6, "window_attention": 6.4, "attention_core": 3.2, "moe_route": 0.4,
                       "moe_experts": 0.8, "moe_combine": 0.24, "shared_expert": 0.16,
                       "dense_ffn": 2.4, "trunk_embed": 0.08, "text_head": 0.16},
        },
    }


def test_each_reader_reads_its_own():
    run = made_up_run()
    # the attention scope's metric holds the core inside it
    assert reader("window_attention_device_ms")(run) == pytest.approx(1200.0)
    assert reader("attention_core_device_ms")(run) == pytest.approx(400.0)
    assert reader("window_experts_device_ms")(run) == pytest.approx(200.0)
    assert reader("window_route_device_ms")(run) == pytest.approx(80.0)
    assert reader("window_expert_load_max_over_mean")(run) == 120.0
    # 2 full layers of 48 heads, 3 window layers of 64
    assert reader("attention_scores_computed_pct")(run) == pytest.approx(
        100 * (2 * 48 * 0.5625 + 3 * 64 * 0.46875) / (2 * 48 + 3 * 64))
    flops_step = flops_window_trunk.train_step_flops(run["shapes"], run["trunk"])
    assert reader("window_trunk_step_mfu_pct")(run) == pytest.approx(100 * flops_step * 8 / 16.0 / 197e12)
    assert 0 < reader("window_trunk_step_mfu_pct")(run) < 100
    # 400 ms a step in the core against 54 ms by operations (35 by bytes)
    least = flops_window_trunk.core_flops_per_step(run["shapes"], run["trunk"]) / 197e12
    assert reader("attention_core_roofline_pct")(run) == pytest.approx(100 * least / 0.400)
    assert reader("attention_core_roofline_pct")(run) == pytest.approx(13.5, abs=0.1)
    # 100 ms a step in the grouped products against 21.6 ms by operations
    least = flops_window_trunk.experts_flops_per_step(run["shapes"], run["trunk"]) / 197e12
    assert reader("window_experts_roofline_pct")(run) == pytest.approx(100 * least / 0.100)


def test_the_rooflines_take_the_larger_of_operations_and_bytes():
    run = made_up_run()
    run["hbm_bytes_per_s"] = 81.9e9                       # a tenth of the bandwidth: bytes bind
    least = flops_window_trunk.core_bytes_per_step(run["shapes"], run["trunk"]) / 81.9e9
    assert reader("attention_core_roofline_pct")(run) == pytest.approx(100 * least / 0.400)
    run["routing"]["absent_share"] = 0.9                  # a fifth fewer pairs on held experts than expected
    least = flops_window_trunk.experts_bytes_per_step(run["shapes"], run["trunk"], 0.1) / 81.9e9
    assert reader("window_experts_roofline_pct")(run) == pytest.approx(100 * least / 0.100)
    run["routing"] = None                                 # no counter, no share
    assert reader("window_experts_roofline_pct")(run) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_run_without_it_reads_nothing_and_does_not_raise(name):
    parent = made_up_run()                                # a program from before the trunk:
    parent["trace"]["scopes"] = {"": 6.0}                 # no such scope, no gauge, no group
    parent.update(trunk=None, attention_share=None, hbm_bytes_per_s=None, routing=None)
    assert reader(name)(parent) is None
    untraced = {**made_up_run(), "trace": None, "attention_share": None, "routing": None}
    assert reader(name)(untraced) is None
    assert reader(name)({"trace": None}) is None


def test_the_benchmark_lists_the_nine_for_the_cell_alone():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = [m for m in bench["per_layer"] if m["name"] in NAMES]
    assert [m["name"] for m in mine] == list(NAMES)
    for m in mine:
        assert m["workloads"] == ["laguna33b-ep8.b1"] and m["layer"] == "window trunk"
        assert m["moves"] == "train_samples_per_s" and (BENCH / "metrics" / f"{m['name']}.py").exists()
    cell = cells.load_cell(ROOT, "laguna33b-ep8.b1")
    assert cell["traffic"]["kind"] == "training_rounds_tokens_window" and cell["chips"] == 1
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(NAMES) <= reported and "latent_experts_roofline_pct" not in reported
    # the eight accepted metrics without a list of cells are read here too
    assert {"compile_cache_misses", "host_build_ms_per_step", "dispatch_ms_per_step", "distinct_news_pct",
            "train_step_device_ms", "device_idle_pct", "hbm_peak_gb", "round_end_host_ms"} <= reported
    # a share of a roofline or of a peak that the benchmark already had lists its own cells
    for m in bench["per_layer"]:
        if ("roofline" in m["name"] or "mfu" in m["name"]) and m["name"] not in NAMES:
            assert "laguna33b-ep8.b1" not in m["workloads"]
