"""In-graph numeric sentry: the jitted step's health aux vector is present
(and finite) on healthy runs, reaches the round's health check stacked
over its steps, flags a forced non-finite update, carries the DP
clip-rate, and vanishes when
``obs.health.sentry`` is off — with trajectories UNCHANGED by the aux."""

from __future__ import annotations

import numpy as np
import pytest

import jax

from fedrec_tpu.fed import get_strategy
from fedrec_tpu.parallel import client_mesh, shard_batch
from fedrec_tpu.train import build_fed_train_step

from test_train import make_setup, small_cfg, _batch_dict

HEALTH_KEYS = {
    "health.grad_norm", "health.update_norm", "health.param_norm",
    "health.nonfinite",
}


def _one_batch(batcher, n):
    return _batch_dict(next(iter(batcher.epoch_batches_sharded(n, 0))))


def test_sentry_vector_present_and_finite_joint():
    cfg = small_cfg()
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    step = build_fed_train_step(model, cfg, get_strategy("grad_avg"), mesh,
                                mode="joint")
    batch = shard_batch(mesh, _one_batch(batcher, 8))
    _, m = step(stacked, batch, token_states)
    assert HEALTH_KEYS <= set(m)
    for k in HEALTH_KEYS:
        assert np.asarray(m[k]).shape == (8,)  # per-client vector
    assert np.asarray(m["health.nonfinite"]).sum() == 0
    assert np.all(np.asarray(m["health.grad_norm"]) > 0)
    assert np.all(np.asarray(m["health.param_norm"]) > 0)


def test_sentry_off_removes_aux():
    cfg = small_cfg()
    cfg.obs.health.sentry = False
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    step = build_fed_train_step(model, cfg, get_strategy("grad_avg"), mesh,
                                mode="joint")
    _, m = step(stacked, shard_batch(mesh, _one_batch(batcher, 8)), token_states)
    assert not (HEALTH_KEYS & set(m))


def test_sentry_does_not_change_the_trajectory():
    """The aux is pure observation: states and losses with sentry on must
    be bit-comparable to sentry off (same seeds, same batches)."""
    results = {}
    for sentry in (True, False):
        cfg = small_cfg(optim__user_lr=3e-3)
        cfg.obs.health.sentry = sentry
        _, batcher, token_states, model, stacked, mesh = make_setup(cfg, seed=0)
        step = build_fed_train_step(model, cfg, get_strategy("grad_avg"),
                                    mesh, mode="joint")
        losses = []
        for i, b in enumerate(batcher.epoch_batches_sharded(8, 0)):
            stacked, m = step(stacked, shard_batch(mesh, _batch_dict(b)),
                              token_states)
            losses.append(np.asarray(m["mean_loss"]))
            if i >= 2:
                break
        results[sentry] = (
            np.stack(losses),
            [np.asarray(x) for x in jax.tree_util.tree_leaves(stacked.user_params)],
        )
    np.testing.assert_allclose(results[True][0], results[False][0],
                               rtol=1e-6, atol=1e-7)
    for a, b in zip(results[True][1], results[False][1]):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_forced_nonfinite_flags_every_client():
    cfg = small_cfg()
    cfg.optim.user_lr = float("inf")  # first Adam update -> inf/nan params
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    step = build_fed_train_step(model, cfg, get_strategy("grad_avg"), mesh,
                                mode="joint")
    _, m = step(stacked, shard_batch(mesh, _one_batch(batcher, 8)), token_states)
    nf = np.asarray(m["health.nonfinite"])
    assert nf.sum() == 8  # every client stepped with the poisoned lr
    assert not np.all(np.isfinite(np.asarray(m["health.update_norm"])))
    # the loss itself was still finite — only the sentry sees the corpse
    assert np.all(np.isfinite(np.asarray(m["loss"])))


def test_round_health_rows_reach_the_check_as_steps_by_clients(tmp_path):
    """A round's per-step health vectors reach ``_check_health`` one row a
    step and the monitor as one round's (steps, clients) stack."""
    from fedrec_tpu.train.trainer import Trainer

    cfg = small_cfg()
    cfg.model.text_encoder_mode = "head"
    cfg.fed.strategy = "param_avg"
    cfg.train.snapshot_dir = str(tmp_path / "snap")
    cfg.train.eval_every = 1000
    data, _, token_states, _, _, _ = make_setup(cfg, num_train=3 * 64, seed=0)
    t = Trainer(cfg, data, np.asarray(token_states))
    rows_seen, stacks_seen = [], []
    check_health, monitor_check = t._check_health, t.health.check

    def spy_rows(round_idx, health_rows=None, round_losses=()):
        rows_seen.append(health_rows)
        return check_health(round_idx, health_rows, round_losses)

    def spy_stack(start_round, arrays, round_losses, **kw):
        stacks_seen.append(arrays)
        return monitor_check(start_round, arrays, round_losses, **kw)

    t._check_health, t.health.check = spy_rows, spy_stack
    t.train_round(0)
    (rows,), (arrays,) = rows_seen, stacks_seen
    assert len(rows) == 3 and all(HEALTH_KEYS <= set(r) for r in rows)
    assert all(np.asarray(r["health.grad_norm"]).shape == (8,) for r in rows)
    for k in HEALTH_KEYS:
        assert arrays[k].shape == (1, 3, 8)  # one round of (steps, clients)
    assert arrays["health.nonfinite"].sum() == 0
    np.testing.assert_array_equal(
        arrays["health.grad_norm"][0, 1], np.asarray(rows[1]["health.grad_norm"])
    )


def test_dpsgd_step_emits_clip_rate():
    cfg = small_cfg()
    cfg.privacy.enabled = True
    cfg.privacy.sigma = 0.5
    cfg.privacy.clip_norm = 1e-6  # clip EVERYTHING -> rate exactly 1.0
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    step = build_fed_train_step(model, cfg, get_strategy("grad_avg"), mesh,
                                mode="joint")
    _, m = step(stacked, shard_batch(mesh, _one_batch(batcher, 8)), token_states)
    assert np.asarray(m["health.clip_rate"]).shape == (8,)
    np.testing.assert_array_equal(np.asarray(m["health.clip_rate"]), 1.0)
    assert np.all(np.asarray(m["health.clip_max_norm"]) > 0)


def test_decoupled_mode_sentry():
    from fedrec_tpu.train import encode_all_news

    cfg = small_cfg()
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    p0 = jax.tree_util.tree_map(lambda x: x[0], stacked.news_params)
    table = encode_all_news(model, p0, token_states)
    step = build_fed_train_step(model, cfg, get_strategy("local"), mesh,
                                mode="decoupled")
    _, m = step(stacked, shard_batch(mesh, _one_batch(batcher, 8)), table)
    assert HEALTH_KEYS <= set(m)
    assert np.asarray(m["health.nonfinite"]).sum() == 0


def test_cohort_mesh_sentry_shapes():
    """k=2 cohorts (8 clients on 4 devices): health vectors still come
    back as (num_clients,) — packing-independent like every metric."""
    cfg = small_cfg()
    mesh = client_mesh(8, max_devices=4)
    _, batcher, token_states, model, stacked, _ = make_setup(cfg)
    step = build_fed_train_step(model, cfg, get_strategy("grad_avg"), mesh,
                                mode="joint")
    _, m = step(stacked, shard_batch(mesh, _one_batch(batcher, 8)), token_states)
    assert np.asarray(m["health.update_norm"]).shape == (8,)
