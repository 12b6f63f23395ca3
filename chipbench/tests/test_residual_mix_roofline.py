"""``residual_mix_roofline_pct`` and the bytes behind it
(``chipbench/bytes_residual_mix.py``) on made-up ``run`` dicts: the count at
the cell's shapes against the sum by hand, what the reader reads where the
trace names the scope, and that it returns nothing, without raising, where
it does not (an untraced run, a program without the scope, no trunk)."""

import json

import pytest

from chipbench import bytes_residual_mix, cells, corpus_latent
from conftest import BENCH, ROOT

CONFIG = json.loads((BENCH / "configs" / "mind-xing29b-ep8.json").read_text())
TRUNK = corpus_latent.trunk_of(CONFIG)


def made_up_run(hbm=True):
    run = {
        "shapes": CONFIG["shapes"], "trunk": TRUNK,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "module_names": {"train_step": "jit_sharded_step"},
        "trace": {"window_s": 8.0, "modules": {"jit_sharded_step": {"count": 16, "seconds": 6.4}},
                  "scopes": {"": 0.8, "latent_attention": 1.6, "residual_mix": 1.28, "moe_experts": 0.32}},
    }
    if hbm:
        run["hbm_bytes_per_s"] = 819e9
    return run


def test_bytes_at_the_cells_shapes():
    """5,500 tokens through 5 layers of two sublayers: 62 d a token a
    sublayer at four streams (forward 12 d + 18 d, backward 32 d), and the
    tile and the merge, 10 d each way: 13.0 GB, 15.9 ms at 819 GB/s."""
    d = 3584
    assert (TRUNK["streams"], TRUNK["dim"], TRUNK["layers"]) == (4, d, 5)
    per_sublayer = bytes_residual_mix.sublayer_bytes_per_token(TRUNK)
    assert per_sublayer == {"read_in": 12 * d, "write_back": 18 * d, "backward": 32 * d}
    assert bytes_residual_mix.ends_bytes_per_token(TRUNK) == 40 * d
    want = 5500 * (10 * 62 * d + 40 * d)
    assert bytes_residual_mix.bytes_per_step(CONFIG["shapes"], TRUNK) == pytest.approx(want)
    assert want / 819e9 == pytest.approx(15.9e-3, abs=0.05e-3)


@pytest.mark.parametrize("streams", [1, 2, 4])
def test_bytes_follow_the_streams(streams):
    """(12 n + 14) d a token a sublayer: the state's three reads and writes
    forward and three backward, the float32 x_in and dx_in, out and d_out."""
    trunk = {**TRUNK, "streams": streams}
    assert sum(bytes_residual_mix.sublayer_bytes_per_token(trunk).values()) == (12 * streams + 14) * 3584


def test_the_reader_reads_the_scope():
    """1.28 s of ``residual_mix`` over 16 steps: 80 ms a step against 15.9 ms."""
    run = made_up_run()
    least_s = bytes_residual_mix.bytes_per_step(run["shapes"], run["trunk"]) / 819e9
    got = cells.load_reader(BENCH, "residual_mix_roofline_pct")(run)
    assert got == pytest.approx(100 * least_s / 0.080)
    assert got == pytest.approx(19.86, abs=0.01)
    # the harness that gives no bandwidth: the device's published one
    assert cells.load_reader(BENCH, "residual_mix_roofline_pct")(made_up_run(hbm=False)) == got


@pytest.mark.parametrize("missing", ["scope", "trace", "trunk", "module"])
def test_the_reader_finds_nothing_without_raising(missing):
    run = made_up_run()
    if missing == "scope":
        run["trace"]["scopes"] = {"": 6.4}
    elif missing == "trace":
        run["trace"] = None
    elif missing == "trunk":
        run["trunk"] = None
    else:
        run["module_names"] = {"train_step": "jit_other"}
    assert cells.load_reader(BENCH, "residual_mix_roofline_pct")(run) is None


def test_the_benchmark_names_the_metric_for_the_latent_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == "residual_mix_roofline_pct")
    assert entry == {
        "name": "residual_mix_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "latent trunk", "moves": "train_samples_per_s", "workloads": ["xing29b-ep8.b2"],
    }
    reported = {m["name"] for m in cells.load_cell(ROOT, "xing29b-ep8.b2")["per_layer"]}
    assert "residual_mix_roofline_pct" in reported and "residual_mix_device_ms" in reported
