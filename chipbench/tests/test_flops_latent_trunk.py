"""``flops_latent_trunk.py`` against a hand count for one token of one layer
at tiny shapes, and at the cell's own shapes against the numbers ``PERF.md``
quotes; ``trunk_of`` against the configuration file."""

import json

import pytest

from chipbench import corpus_latent, flops, flops_latent_trunk, peaks, peaks_memory
from conftest import BENCH

TRUNK = {"dim": 8, "layers": 3, "dense_layers": 1, "heads": 2, "q_rank": 4, "kv_rank": 3, "nope_dim": 4,
         "rope_dim": 2, "v_dim": 4, "dense_dim": 10, "experts": 8, "experts_per_token": 2, "expert_dim": 6,
         "shared_experts": 1, "streams": 2, "experts_held": 4}
SHAPES = {"clients": 1, "batch_per_client": 2, "candidates": 2, "history": 3, "title_len": 5,
          "bert_hidden": 8, "attn_hidden": 4, "news_dim": 3, "heads": 2, "head_dim": 2, "query_dim": 2}


def test_hand_count_for_one_token_of_one_layer():
    routed = flops_latent_trunk.layer_flops_per_token(TRUNK, 5, routed=True)
    # q_a 8x4, q_b 4x(2x6), kv_a 8x(3+2), kv_b 3x(2x8), o (2x4)x8; a multiply-add is 2
    assert routed["projections"] == 2 * (32 + 48 + 40 + 48 + 64) == 464
    # causal: 5 tokens read 1+2+3+4+5 = 15 keys, 3 a token; 2 heads; the
    # score over 4 + 2 dimensions, the context over 4
    assert routed["core"] == 2 * 3 * (2 * 6 + 2 * 4) == 120
    # a mixer: maps (2x8) x (2 + 2 + 4), mixing 2x2x8, reading and writing 2 x (2x8); two a layer
    assert flops_latent_trunk.mixer_flops_per_token(TRUNK) == 2 * 16 * 8 + 2 * 4 * 8 + 2 * 2 * 16 == 384
    assert routed["mixers"] == 768
    assert routed["router"] == 2 * 8 * 8 == 128
    assert routed["shared"] == 3 * 2 * 8 * 6 == 288
    # 2 choices a token, 4 of 8 experts held: 1 pair a token expected
    assert routed["experts"] == 1 * 3 * 2 * 8 * 6 == 288
    dense = flops_latent_trunk.layer_flops_per_token(TRUNK, 5, routed=False)
    assert dense["dense"] == 3 * 2 * 8 * 10 == 480
    assert set(dense) == {"projections", "core", "mixers", "dense"}
    per_token = (464 + 120 + 768 + 480) + 2 * (464 + 120 + 768 + 128 + 288 + 288)
    assert flops_latent_trunk.trunk_flops_per_token(TRUNK, 5) == per_token == 5944
    tokens = 2 * (2 + 3) * 5
    assert flops_latent_trunk.experts_flops_per_step(SHAPES, TRUNK) == 3 * 2 * 288 * tokens
    # 50 rows on held experts; a pass moves rows x (8 + 6) + 4 x 8 x 6 bfloat16 values
    assert flops_latent_trunk.experts_bytes_per_step(SHAPES, TRUNK) == 9 * 2 * 2 * (50 * 14 + 192)
    head = 2 * 5 * 8 * 4 + 2 * 5 * 4 + 2 * 5 * 8 + 2 * 8 * 3
    want = 3 * per_token * tokens + 3 * head * 10 + flops.user_tower_flops_per_sample(SHAPES) * 2
    assert flops_latent_trunk.train_step_flops(SHAPES, TRUNK) == want


def test_the_cells_count_and_trunk():
    config = json.loads((BENCH / "configs" / "mind-xing29b-ep8.json").read_text())
    t = corpus_latent.trunk_of(config)
    assert (t["dim"], t["layers"], t["dense_layers"], t["heads"]) == (3584, 5, 1, 32)
    assert (t["q_rank"], t["kv_rank"], t["nope_dim"], t["rope_dim"], t["v_dim"]) == (768, 512, 128, 64, 128)
    assert (t["experts"], t["experts_per_token"], t["expert_dim"], t["dense_dim"]) == (64, 4, 1024, 9216)
    assert (t["first_expert"], t["experts_held"], t["vocab_held"], t["streams"]) == (0, 8, 16384, 4)
    assert (t["sinkhorn_iters"], t["routed_scale"], t["rope"]["factor"]) == (20, 2.0, 64)
    s = config["shapes"]
    routed = flops_latent_trunk.layer_flops_per_token(t, 50, routed=True)
    total_layer = sum(routed.values())
    # the cell's why: attention 62% of a sparse layer's operations, held experts 12%
    assert (routed["projections"] + routed["core"]) / total_layer == pytest.approx(0.62, abs=0.005)
    assert routed["experts"] / total_layer == pytest.approx(0.12, abs=0.005)
    total = flops_latent_trunk.train_step_flops(s, t)
    assert 3 * flops_latent_trunk.trunk_flops_per_token(t, 50) * 5500 == pytest.approx(10.35e12, rel=1e-3)
    assert total == pytest.approx(10.57e12, rel=1e-3)
    peak = peaks.chip_peaks("TPU v5 lite")["bf16_flops_per_s"]
    assert total / peak == pytest.approx(0.0536, rel=0.01)           # seconds a step at peak
    # the grouped products sit on the roofline's ridge at 344 rows an expert
    by_ops = flops_latent_trunk.experts_flops_per_step(s, t) / peak
    by_bytes = flops_latent_trunk.experts_bytes_per_step(s, t) / peaks_memory.hbm_bytes_per_s("TPU v5 lite")
    assert by_ops == pytest.approx(3.69e-3, rel=0.01) and by_bytes == pytest.approx(3.70e-3, rel=0.01)


def test_the_head_must_be_as_wide_as_the_trunk():
    with pytest.raises(ValueError, match="widths"):
        flops_latent_trunk.train_step_flops(dict(SHAPES, bert_hidden=16), TRUNK)


def test_an_unknown_chip_has_no_bandwidth():
    with pytest.raises(KeyError, match="no published memory bandwidth"):
        peaks_memory.hbm_bytes_per_s("TPU v9")


@pytest.mark.parametrize("key,value", [("scoring_func", "softmax"), ("n_group", 8), ("hidden_act", "relu")])
def test_a_router_or_an_activation_the_reference_does_not_know_is_refused(key, value):
    config = json.loads((BENCH / "configs" / "mind-xing29b-ep8.json").read_text())
    with pytest.raises(ValueError, match="the reference knows"):
        corpus_latent.trunk_of({**config, key: value})


def test_the_configuration_file_holds_the_catalogs_numbers():
    """Every number of the published config stands at the file's top level
    under the same key, but the keys under ``reduced``."""
    config = json.loads((BENCH / "configs" / "mind-xing29b-ep8.json").read_text())
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu",
        "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
        "max_position_embeddings": 262144, "model_type": "xing4_0", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096, "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072,
    }
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"]) - {"dropout_rate"}
    assert {k: published[k] for k in differs} == config["published"]
