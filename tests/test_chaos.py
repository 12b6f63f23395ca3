"""Deterministic fault injection: FaultPlan unit pins + the chaos e2e.

Acceptance (ISSUE 5): under a seeded FaultPlan (30% dropout + one
nan-update client + one ×100 scale-poison client), a trimmed-mean run
completes all rounds, final eval is within tolerance of the fault-free
baseline, and re-running the same plan reproduces the trajectory
bit-identically.
"""

from __future__ import annotations

import numpy as np
import pytest

from fedrec_tpu.config import ChaosConfig, ExperimentConfig
from fedrec_tpu.data import make_synthetic_mind
from fedrec_tpu.fed.chaos import FAULT_CODES, FaultPlan, parse_faults
from fedrec_tpu.obs import MetricsRegistry, Tracer, set_registry, set_tracer


# ------------------------------------------------------------- plan units
def test_parse_faults_dsl():
    specs = parse_faults("nan@2:3,scale@*:5x100,flip@4:2", 8)
    assert specs == [
        ("nan", 2, 3, 1.0), ("scale", None, 5, 100.0), ("flip", 4, 2, 1.0),
    ]


@pytest.mark.parametrize("bad", [
    "nan@2", "warp@1:2", "nan@x:2", "nan@1:99", "scale@1:2x?",
])
def test_parse_faults_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_faults(bad, 8)


def _plan(**over):
    cc = ChaosConfig(enabled=True, **over)
    return FaultPlan(cc, num_clients=8)


def test_fault_plan_is_deterministic_and_idempotent():
    p1 = _plan(seed=3, drop_rate=0.3, straggle_rate=0.1, faults="nan@*:3")
    p2 = _plan(seed=3, drop_rate=0.3, straggle_rate=0.1, faults="nan@*:3")
    for r in range(10):
        a, b = p1.round_faults(r), p2.round_faults(r)
        np.testing.assert_array_equal(a.weight_mask, b.weight_mask)
        np.testing.assert_array_equal(a.codes, b.codes)
        np.testing.assert_array_equal(a.scales, b.scales)
        # idempotent within one plan too (rollback replays re-query)
        c = p1.round_faults(r)
        np.testing.assert_array_equal(a.weight_mask, c.weight_mask)
    # different seed -> different draws somewhere in 10 rounds
    p3 = _plan(seed=4, drop_rate=0.3)
    assert any(
        not np.array_equal(
            p1.round_faults(r).weight_mask, p3.round_faults(r).weight_mask
        )
        for r in range(10)
    )


def test_fault_plan_codes_and_masks():
    p = _plan(seed=0, faults="nan@2:3,scale@*:5x100,flip@1:0")
    r2 = p.round_faults(2)
    assert r2.codes[3] == FAULT_CODES["nan"]
    assert r2.codes[5] == FAULT_CODES["scale"] and r2.scales[5] == 100.0
    assert r2.codes[0] == 0  # flip only at round 1
    assert p.round_faults(1).codes[0] == FAULT_CODES["flip"]
    np.testing.assert_array_equal(
        p.round_faults(0).weight_mask, np.ones(8, np.float32)
    )  # no drop_rate -> nobody dropped
    keys = p.batch_keys(2)
    assert keys["chaos.code"].dtype == np.int32
    assert keys["chaos.scale"].dtype == np.float32


def test_drop_and_straggle_share_one_draw():
    p = _plan(seed=1, drop_rate=0.4, straggle_rate=0.4)
    for r in range(5):
        rf = p.round_faults(r)
        assert not (set(rf.dropped) & set(rf.straggled))
        for c in list(rf.dropped) + list(rf.straggled):
            assert rf.weight_mask[c] == 0.0


# ------------------------------------------------------------ trainer e2e
def _trainer(chaos: bool, rounds: int = 3, method: str = "trimmed_mean"):
    from fedrec_tpu.train.trainer import Trainer

    set_registry(MetricsRegistry())
    set_tracer(Tracer())
    cfg = ExperimentConfig()
    cfg.model.news_dim = 32
    cfg.model.num_heads = 4
    cfg.model.head_dim = 8
    cfg.model.query_dim = 16
    cfg.model.bert_hidden = 48
    cfg.model.text_encoder_mode = "head"
    cfg.data.max_his_len = 10
    cfg.data.max_title_len = 12
    cfg.data.batch_size = 8
    cfg.fed.num_clients = 8
    cfg.fed.strategy = "param_avg"
    cfg.fed.rounds = rounds
    cfg.fed.robust.method = method
    cfg.train.snapshot_dir = ""
    cfg.train.eval_every = 1000
    if chaos:
        # the acceptance plan: 30% dropout + one nan client + one x100
        # scale-poison client; trim_k=2 because TWO clients are byzantine
        cfg.chaos.enabled = True
        cfg.chaos.seed = 7
        cfg.chaos.drop_rate = 0.3
        cfg.chaos.faults = "nan@*:3,scale@*:5x100"
        cfg.fed.robust.trim_k = 2
        # robust aggregation IS the defense here; the sentry keeps
        # reporting, it just must not abort the run
        cfg.obs.health.abort_on_nonfinite = False
    data = make_synthetic_mind(
        num_news=64, num_train=256, num_valid=64,
        title_len=12, his_len_range=(2, 10), seed=0, popular_frac=0.2,
    )
    states = np.random.default_rng(1).standard_normal(
        (64, 12, 48)
    ).astype(np.float32)
    return Trainer(cfg, data, states)


@pytest.mark.slow  # jit-heavy; tier-1 keeps the fast unit proofs
def test_chaos_e2e_trimmed_mean_survives_and_reproduces():
    t = _trainer(chaos=True)
    h = t.run()
    assert len(h) == 3
    losses = [r.train_loss for r in h]
    assert all(np.isfinite(losses)), losses
    ev = t.evaluate()
    assert np.isfinite(ev["auc"])

    # bit-identical reproduction of the same plan
    t2 = _trainer(chaos=True)
    losses2 = [r.train_loss for r in t2.run()]
    assert losses == losses2
    u1, n1 = t._client0_params()
    u2, n2 = t2._client0_params()
    import jax

    for a, b in zip(
        jax.tree_util.tree_leaves((u1, n1)), jax.tree_util.tree_leaves((u2, n2))
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # within tolerance of the fault-free baseline (5-6 honest clients of 8
    # still learn the same popularity signal)
    tb = _trainer(chaos=False)
    tb.run()
    evb = tb.evaluate()
    assert abs(ev["auc"] - evb["auc"]) < 0.15, (ev["auc"], evb["auc"])

    # faults were actually injected and counted
    reg = t.registry
    faults = reg.counter("chaos.faults_total", labels=("kind",))
    assert faults.value(kind="nan") >= 3
    assert faults.value(kind="scale") >= 3
    assert faults.value(kind="drop") >= 1
    robust = reg.counter("fed.robust_rounds_total", labels=("method",))
    assert robust.value(method="trimmed_mean") == 3


def test_chaos_requires_no_seq_parallel():
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.parallel.mesh import fed_mesh
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.train.step import build_fed_train_step

    cfg = ExperimentConfig()
    cfg.model.news_dim = 32
    cfg.model.num_heads = 4
    cfg.model.head_dim = 8
    cfg.model.query_dim = 16
    cfg.model.bert_hidden = 48
    cfg.model.text_encoder_mode = "head"  # joint mode: seq-parallel-legal
    cfg.data.max_his_len = 10
    cfg.data.max_title_len = 12
    cfg.fed.num_clients = 4
    cfg.fed.seq_shards = 2
    cfg.chaos.enabled = True
    mesh = fed_mesh(cfg)
    with pytest.raises(NotImplementedError, match="chaos"):
        build_fed_train_step(
            NewsRecommender(cfg.model), cfg, get_strategy("param_avg"), mesh
        )
