"""``flops_moe_trunk.py`` against a hand count at tiny shapes, and at the
cell's own shapes against the numbers ``PERF.md`` quotes; ``trunk_of``
against the configuration file."""

import json

import pytest

from chipbench import corpus_tokens, flops_moe_trunk, peaks
from conftest import BENCH

TRUNK = {"dim": 8, "layers": 2, "heads": 4, "kv_heads": 2, "head_dim": 4, "experts": 8,
         "experts_per_token": 2, "expert_dim": 6, "experts_held": 4}
SHAPES = {"clients": 1, "batch_per_client": 2, "candidates": 2, "history": 3, "title_len": 5,
          "bert_hidden": 8, "attn_hidden": 4, "news_dim": 3, "heads": 2, "head_dim": 2, "query_dim": 2}


def test_hand_count_at_tiny_shapes():
    parts = flops_moe_trunk.layer_flops_per_token(TRUNK, 5)
    # q 8x16, k 8x8, v 8x8 and o 16x8, a multiply-add is 2
    assert parts["projections"] == 2 * 8 * (16 + 8 + 8) + 2 * 16 * 8 == 768
    # causal: 5 tokens read 1+2+3+4+5 = 15 keys, 3 a token; 4 heads; score and
    # context 2 x 4 each
    assert parts["core"] == 4 * 3 * (2 * 4 + 2 * 4) == 192
    assert parts["router"] == 2 * 8 * 8 == 128
    # 2 choices a token, 4 of 8 experts held: 1 pair a token expected; gate, up, down of 8 x 6
    assert parts["experts"] == 1 * 3 * 2 * 8 * 6 == 288
    tokens = 2 * (2 + 3) * 5
    assert flops_moe_trunk.tokens_per_step(SHAPES) == tokens == 50
    assert flops_moe_trunk.experts_flops_per_step(SHAPES, TRUNK) == 3 * 2 * 288 * 50
    # head per title: fc1 5x8x4, fc2 5x4, pool 5x8, fc 8x3
    head = 2 * 5 * 8 * 4 + 2 * 5 * 4 + 2 * 5 * 8 + 2 * 8 * 3
    assert flops_moe_trunk.head_flops_per_slot(SHAPES) == head == 488
    from chipbench import flops

    want = 3 * 2 * (768 + 192 + 128 + 288) * 50 + 3 * 488 * 10 + flops.user_tower_flops_per_sample(SHAPES) * 2
    assert flops_moe_trunk.train_step_flops(SHAPES, TRUNK) == want


def test_the_cells_count_and_trunk():
    config = json.loads((BENCH / "configs" / "mind-smallthinker21b-ep4.json").read_text())
    trunk = corpus_tokens.trunk_of(config)
    assert (trunk["dim"], trunk["layers"], trunk["heads"], trunk["kv_heads"], trunk["head_dim"]) == (2560, 4, 28, 4, 128)
    assert (trunk["experts"], trunk["experts_per_token"], trunk["expert_dim"]) == (64, 6, 768)
    assert (trunk["first_expert"], trunk["experts_held"], trunk["vocab_held"]) == (0, 16, 37984)
    assert (trunk["global_every"], trunk["sliding_window"], trunk["rope_theta"]) == (4, 4096, 1.5e6)
    s = config["shapes"]
    assert flops_moe_trunk.tokens_per_step(s) == 44_000
    total = flops_moe_trunk.train_step_flops(s, trunk)
    assert total / 44_000 == pytest.approx(744e6, rel=0.01)        # a token, PERF.md section 4
    assert total == pytest.approx(32.7e12, rel=0.01)
    peak = peaks.chip_peaks("TPU v5 lite")["bf16_flops_per_s"]
    assert total / peak == pytest.approx(0.166, rel=0.01)            # seconds a step at peak
    experts = flops_moe_trunk.experts_flops_per_step(s, trunk)
    assert experts / total == pytest.approx(0.285, abs=0.01)


def test_the_head_must_be_as_wide_as_the_trunk():
    with pytest.raises(ValueError, match="widths"):
        flops_moe_trunk.train_step_flops(dict(SHAPES, bert_hidden=16), TRUNK)


def test_a_layout_that_is_not_one_global_layer_a_period_is_refused():
    config = json.loads((BENCH / "configs" / "mind-smallthinker21b-ep4.json").read_text())
    config["sliding_window_layout"] = [0, 1, 0, 1, 1]
    config["rope_layout"] = [0, 1, 0, 1, 1]
    with pytest.raises(ValueError, match="period"):
        corpus_tokens.trunk_of(config)
