"""``pair_sum_roofline_pct`` and the bytes behind it
(``chipbench/bytes_pair_sum.py``) on made-up ``run`` dicts: what the reader
reads where the trace holds the kernel, that it returns nothing, without
raising, where the trace does not (a program from before the kernel, an
untraced run), and the bytes at the three routed cells' shapes."""

import json

import pytest

from chipbench import bytes_pair_sum, cells, corpus_latent, corpus_tokens, corpus_window
from conftest import BENCH, ROOT

# the routed cells: configuration, trunk group, the share of pairs on absent
# experts a uniform router gives, tokens a chunk and chunk-layers a step
CELLS = {
    "st21b-ep4.b16": ("mind-smallthinker21b-ep4", corpus_tokens, 0.75, 11_000, 16),
    "xing29b-ep8.b2": ("mind-xing29b-ep8", corpus_latent, 0.875, 5_500, 4),
    "laguna33b-ep8.b1": ("mind-laguna33b-ep8", corpus_window, 0.875, 11_264, 20),
}


def made_up_run(cell, ops, hbm=True):
    config_name, corpus, absent, _, _ = CELLS[cell]
    config = json.loads((BENCH / "configs" / f"{config_name}.json").read_text())
    run = {
        "shapes": config["shapes"], "trunk": corpus.trunk_of(config),
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "module_names": {"train_step": "jit_sharded_step"},
        "routing": {"load_max_over_mean": 1.2, "absent_share": absent},
        "trace": {"window_s": 16.0, "modules": {"jit_sharded_step": {"count": 8, "seconds": 15.9}},
                  "ops": ops},
    }
    if hbm:
        run["hbm_bytes_per_s"] = 819e9
    return run


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_bytes_at_the_cells_shapes(cell):
    """Two sums a chunk-layer, each reading the held pairs' rows and the
    indices and writing a row a token; the combine reads the weights too."""
    config_name, corpus, absent, chunk, chunk_layers = CELLS[cell]
    config = json.loads((BENCH / "configs" / f"{config_name}.json").read_text())
    t = corpus.trunk_of(config)
    k, d = t["experts_per_token"], t["dim"]
    one_call = chunk * (k * (1 - absent) * d * 2 + d * 2 + k * 4)
    want = chunk_layers * (2 * one_call + chunk * k * 4)
    assert bytes_pair_sum.bytes_per_step(config["shapes"], t, 1 - absent) == pytest.approx(want)


def test_the_reader_reads_the_kernels_ops():
    """8 steps in the window; 0.32 s in ``pair_sum`` and 0.08 s in its row
    copy, wherever the names' suffixes fall; other ops of the tokens' shape
    are not the kernel's."""
    ops = {"pair_sum.1 custom-call bf16[11000,2560]": 0.2, "pair_sum.3 custom-call bf16[11000,2560]": 0.12,
           "pair_sum_rows.1 custom-call u32[33280,1,1280]": 0.08,
           "fusion.5 fusion bf16[11000,2560]": 5.0, "pair_summary custom-call f32[8]": 3.0}
    run = made_up_run("st21b-ep4.b16", ops)
    least_s = bytes_pair_sum.bytes_per_step(run["shapes"], run["trunk"], 0.25) / 819e9
    got = cells.load_reader(BENCH, "pair_sum_roofline_pct")(run)
    assert got == pytest.approx(100 * least_s / 0.05)
    # 4.5 GB a step are 5.5 ms at 819 GB/s, against 50 ms
    assert got == pytest.approx(11.0, abs=0.1)
    # the harness that gives no bandwidth: the device's published one
    assert cells.load_reader(BENCH, "pair_sum_roofline_pct")(made_up_run("st21b-ep4.b16", ops, hbm=False)) == got


@pytest.mark.parametrize("missing", ["kernel", "trace", "routing", "module", "platform"])
def test_the_reader_finds_nothing_without_raising(missing):
    run = made_up_run("laguna33b-ep8.b1", {"pair_sum.1 custom-call bf16[11264,2048]": 0.4})
    if missing == "kernel":
        run["trace"]["ops"] = {"fusion.5 fusion bf16[11264,2048]": 1.0, "gather.3 gather bf16[11264,2048]": 1.0}
    elif missing == "trace":
        run["trace"] = None
    elif missing == "routing":
        run["routing"] = None
    elif missing == "module":
        run["module_names"] = {"train_step": "jit_other"}
    else:
        run["device"] = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert cells.load_reader(BENCH, "pair_sum_roofline_pct")(run) is None


def test_the_benchmark_names_the_metric_in_the_routed_cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == "pair_sum_roofline_pct")
    assert entry == {
        "name": "pair_sum_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "sparse-expert trunk", "moves": "train_samples_per_s",
        "workloads": ["st21b-ep4.b16", "xing29b-ep8.b2", "laguna33b-ep8.b1"],
    }
    assert set(entry["workloads"]) == set(CELLS)
