"""``flops_window_trunk.py`` against a hand count for one token of each kind
of layer at tiny shapes, and at the cell's own shapes against the numbers
``PERF.md`` quotes; ``trunk_of`` against the configuration file."""

import json

import pytest

from chipbench import corpus_window, flops, flops_window_trunk
from conftest import BENCH

TRUNK = {"dim": 8, "layers": 3, "dense_layers": 1, "layer_kinds": ["full", "window", "window"],
         "heads_per_layer": [2, 4, 4], "kv_heads": 2, "head_dim": 4, "sliding_window": 3, "dense_dim": 10,
         "experts": 8, "experts_per_token": 2, "expert_dim": 6, "shared_dim": 6, "experts_held": 4}
SHAPES = {"clients": 1, "batch_per_client": 2, "candidates": 2, "history": 3, "title_len": 5,
          "bert_hidden": 8, "attn_hidden": 4, "news_dim": 3, "heads": 2, "head_dim": 2, "query_dim": 2}


def test_hand_count_for_one_token_of_each_kind_of_layer():
    full = flops_window_trunk.layer_flops_per_token(TRUNK, 0, 5)
    # q 8x(2x4), k and v 8x(2x4) each, o (2x4)x8, the gate 8x2; a multiply-add is 2
    assert full["projections"] == 2 * (64 + 64 + 64 + 64 + 16) == 544
    # causal: 5 tokens read 1+2+3+4+5 = 15 keys, 3 a token; 2 heads; score
    # and context over 4 dimensions each
    assert flops_window_trunk.band_pairs(5, None) == 15
    assert full["core"] == 2 * 3 * (2 * 4 + 2 * 4) == 96
    assert full["dense"] == 3 * 2 * 8 * 10 == 480 and set(full) == {"projections", "core", "dense"}
    window = flops_window_trunk.layer_flops_per_token(TRUNK, 1, 5)
    # 4 heads: q and o 8x16, k and v 8x8, the gate 8x4
    assert window["projections"] == 2 * (128 + 64 + 64 + 128 + 32) == 832
    # window 3: the tokens read 1+2+3+3+3 = 12 keys, 2.4 a token; 4 heads
    assert flops_window_trunk.band_pairs(5, 3) == 12
    assert window["core"] == pytest.approx(4 * 2.4 * 16)
    assert window["router"] == 2 * 8 * 8 == 128
    assert window["shared"] == 3 * 2 * 8 * 6 == 288
    # 2 choices a token, 4 of 8 experts held: 1 pair a token expected
    assert window["experts"] == 1 * 3 * 2 * 8 * 6 == 288
    per_token = (544 + 96 + 480) + 2 * (832 + 153.6 + 128 + 288 + 288)
    assert flops_window_trunk.trunk_flops_per_token(TRUNK, 5) == pytest.approx(per_token)
    tokens = 2 * (2 + 3) * 5
    assert flops_window_trunk.core_flops_per_step(SHAPES, TRUNK) == pytest.approx(3 * (96 + 2 * 153.6) * tokens)
    # six passes over a query-wide and six over a key/value-wide array a layer, bfloat16
    assert flops_window_trunk.core_bytes_per_step(SHAPES, TRUNK) == 6 * 2 * 4 * ((2 + 2) + 2 * (4 + 2)) * tokens
    # the grouped products are the latent trunk's count over this trunk's group
    assert flops_window_trunk.experts_flops_per_step(SHAPES, TRUNK) == 3 * 2 * 288 * tokens
    assert flops_window_trunk.experts_bytes_per_step(SHAPES, TRUNK) == 9 * 2 * 2 * (50 * 14 + 192)
    head = 2 * 5 * 8 * 4 + 2 * 5 * 4 + 2 * 5 * 8 + 2 * 8 * 3
    want = 3 * per_token * tokens + 3 * head * 10 + flops.user_tower_flops_per_sample(SHAPES) * 2
    assert flops_window_trunk.train_step_flops(SHAPES, TRUNK) == pytest.approx(want)
    # a window as long as the text is the causal triangle
    assert flops_window_trunk.band_pairs(5, 5) == flops_window_trunk.band_pairs(5, 9) == 15


def test_the_cells_count_and_trunk():
    config = json.loads((BENCH / "configs" / "mind-laguna33b-ep8.json").read_text())
    t = corpus_window.trunk_of(config)
    assert (t["dim"], t["layers"], t["dense_layers"], t["kv_heads"], t["head_dim"]) == (2048, 5, 1, 8, 128)
    assert t["layer_kinds"] == ["full", "window", "window", "window", "full"]
    assert t["heads_per_layer"] == [48, 64, 64, 64, 48]
    assert (t["experts"], t["experts_held"], t["experts_per_token"], t["expert_dim"]) == (256, 32, 8, 512)
    assert (t["sliding_window"], t["dense_dim"], t["shared_dim"], t["routed_scale"]) == (512, 8192, 512, 2.5)
    assert (t["vocab_held"], t["first_expert"], t["full_rotary_share"]) == (12544, 0, 0.5)
    assert t["rope"]["attention_factor"] == 1.4158883083359672
    s = config["shapes"]
    # ISSUE 35's table, in millions a token forward
    parts = [flops_window_trunk.layer_flops_per_token(t, i, s["title_len"]) for i in range(5)]
    assert [round(p["projections"] / 1e6, 1) for p in parts] == [58.9, 75.8, 75.8, 75.8, 58.9]
    assert all(round(p["core"] / 1e6, 1) == 12.6 for p in parts)
    assert flops_window_trunk.band_pairs(1024, 512) == 393_472 and flops_window_trunk.band_pairs(1024, None) == 524_800
    assert round(parts[0]["dense"] / 1e6, 1) == 100.7
    assert [round(parts[1][k] / 1e6, 1) for k in ("router", "experts", "shared")] == [1.0, 6.3, 6.3]
    assert flops_window_trunk.trunk_flops_per_token(t, 1024) == pytest.approx(563.27e6, rel=1e-4)
    assert flops_window_trunk.train_step_flops(s, t) == pytest.approx(95.88e12, rel=1e-4)
    assert flops_window_trunk.core_flops_per_step(s, t) == pytest.approx(10.638e12, rel=1e-4)
    assert flops_window_trunk.core_bytes_per_step(s, t) == pytest.approx(28.37e9, rel=1e-3)
    # the parts of the issue's shares: attention 72% (projections 61, core 11), dense 18, routed 10
    total = flops_window_trunk.trunk_flops_per_token(t, 1024)
    share = lambda key: sum(p.get(key, 0.0) for p in parts) / total  # noqa: E731
    assert round(100 * share("projections")) == 61 and round(100 * share("core")) == 11
    assert round(100 * share("dense")) == 18


def test_a_configuration_the_reference_does_not_know_is_refused():
    config = json.loads((BENCH / "configs" / "mind-laguna33b-ep8.json").read_text())
    with pytest.raises(ValueError, match="gate"):
        corpus_window.trunk_of(dict(config, gating=False))
    with pytest.raises(ValueError, match="per-layer lists"):
        corpus_window.trunk_of(dict(config, num_hidden_layers=4))
    with pytest.raises(ValueError, match="one head count a kind"):
        corpus_window.trunk_of(dict(config, num_attention_heads_per_layer=[48, 64, 64, 48, 48]))
    with pytest.raises(ValueError, match="leading dense"):
        corpus_window.trunk_of(dict(config, mlp_layer_types=["dense", "sparse", "dense", "sparse", "sparse"]))
