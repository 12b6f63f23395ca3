"""FSDP at-rest sharding (shard.fsdp) end to end.

Pins the degenerate contract (fsdp=1 builds the exact 1-D mesh and
programs), the 3-round trajectory equality of fsdp>1 against the
replicated baseline, the
at-rest residency actually shrinking, and the sharded-checkpoint
round-trip (save gathers, restore re-commits, resume is bit-identical).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedrec_tpu.parallel import FSDP_AXIS, client_mesh, fed_mesh, shard_batch
from fedrec_tpu.shard.policy import fsdp_state_shardings

from test_train import _batch_dict, make_setup, small_cfg


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _assert_trees_equal(a, b):
    for x, y in zip(_leaves(a), _leaves(b)):
        np.testing.assert_array_equal(x, y)


def test_fed_mesh_grows_fsdp_axis_and_degenerates():
    cfg = small_cfg(fed__num_clients=4)
    cfg.shard.fsdp = 2
    mesh = fed_mesh(cfg)
    assert mesh.axis_names == (cfg.fed.mesh_axis, FSDP_AXIS)
    assert dict(mesh.shape) == {"clients": 4, FSDP_AXIS: 2}
    cfg.shard.fsdp = 1
    assert fed_mesh(cfg).axis_names == (cfg.fed.mesh_axis,)


def test_fsdp_x_seq_shards_fails_fast():
    cfg = small_cfg(fed__num_clients=2, fed__seq_shards=2, data__max_his_len=10)
    cfg.shard.fsdp = 2
    with pytest.raises(ValueError, match="shard.fsdp=2 with fed.seq_shards=2"):
        fed_mesh(cfg)


def test_fsdp_step_and_sync_bitwise_match_replicated_baseline():
    """3 steps + round-end syncs under fsdp=2 == the 1-D 4-device run."""
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.train import build_fed_train_step, build_param_sync

    cfg_f = small_cfg(
        fed__num_clients=4, model__text_encoder_mode="head",
        optim__user_lr=3e-3, optim__news_lr=3e-3,
    )
    cfg_f.shard.fsdp = 2
    cfg_f.shard.fsdp_min_size_mb = 0.0
    mesh_f = fed_mesh(cfg_f)
    data, batcher, token_states, model, st0, _ = make_setup(cfg_f, seed=0)
    shardings = fsdp_state_shardings(st0, mesh_f, cfg_f)
    assert shardings is not None
    placed = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(jnp.asarray(x), s), st0, shardings
    )
    # at-rest residency: the biggest single-device buffer is smaller than
    # the replicated per-device footprint
    rep_bytes = sum(x.nbytes for x in _leaves(st0)) // 4  # per client slot
    local_bytes = max(
        max(s.data.nbytes for s in x.addressable_shards)
        for x in jax.tree_util.tree_leaves(placed.user_params)
    )
    assert local_bytes < rep_bytes

    step_f = build_fed_train_step(
        model, cfg_f, get_strategy("param_avg"), mesh_f, mode="joint",
        state_shardings=shardings,
    )
    sync_f = build_param_sync(
        cfg_f, mesh_f, get_strategy("param_avg"), state_shardings=shardings
    )

    cfg_b = small_cfg(
        fed__num_clients=4, model__text_encoder_mode="head",
        optim__user_lr=3e-3, optim__news_lr=3e-3,
    )
    mesh_b = client_mesh(4, max_devices=4)
    _, _, _, _, st_b, _ = make_setup(cfg_b, seed=0)
    step_b = build_fed_train_step(
        model, cfg_b, get_strategy("param_avg"), mesh_b, mode="joint"
    )
    sync_b = build_param_sync(cfg_b, mesh_b, get_strategy("param_avg"))

    w = jnp.ones((4,), jnp.float32)
    batches = []
    for b in batcher.epoch_batches_sharded(4, 0):
        batches.append(_batch_dict(b))
        if len(batches) >= 3:
            break
    st_f = placed
    for b in batches:
        st_f, mf = step_f(st_f, shard_batch(mesh_f, b), token_states)
        st_f = sync_f(st_f, w)
        st_b, mb = step_b(st_b, shard_batch(mesh_b, b), token_states)
        st_b = sync_b(st_b, w)
        np.testing.assert_array_equal(
            np.asarray(mf["loss"]), np.asarray(mb["loss"])
        )
    _assert_trees_equal(st_f.user_params, st_b.user_params)
    _assert_trees_equal(st_f.news_params, st_b.news_params)
    _assert_trees_equal(st_f.opt_user, st_b.opt_user)
    # the step's output state kept the at-rest fsdp layout (donation-safe)
    out_specs = {
        str(x.sharding.spec)
        for x in jax.tree_util.tree_leaves(st_f.user_params)
    }
    assert any(FSDP_AXIS in s for s in out_specs)


# ----------------------------------------------------- Trainer trajectories
def _tiny_trainer(tmp=None, **over):
    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.data import make_synthetic_mind

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
    cfg.fed.num_clients = 4
    cfg.fed.rounds = 3
    cfg.train.eval_every = 100  # skip eval: trajectory is the claim here
    cfg.train.snapshot_dir = str(tmp) if tmp else ""
    for k, v in over.items():
        section, key = k.split("__")
        setattr(getattr(cfg, section), key, v)
    data = make_synthetic_mind(
        num_news=64, num_train=128, num_valid=16,
        title_len=cfg.data.max_title_len,
        his_len_range=(2, cfg.data.max_his_len), seed=0, popular_frac=0.2,
    )
    rng = np.random.default_rng(0)
    ts = rng.standard_normal(
        (64, cfg.data.max_title_len, cfg.model.bert_hidden)
    ).astype(np.float32)
    return cfg, data, ts


def _run(cfg, data, ts):
    from fedrec_tpu.train.trainer import Trainer

    tr = Trainer(cfg, data, ts)
    hist = tr.run()
    user, table = tr.export_for_serving()
    return (
        [h.train_loss for h in hist],
        [np.asarray(x) for x in jax.tree_util.tree_leaves(user)],
        np.asarray(table),
    )


def test_trainer_fsdp_trajectory_matches_replicated():
    """The acceptance pin: 3-round fsdp=2 trajectory bit-identical to the
    replicated baseline."""
    cfg_b, data, ts = _tiny_trainer()
    base = _run(cfg_b, data, ts)
    cfg_f, _, _ = _tiny_trainer(shard__fsdp=2, shard__fsdp_min_size_mb=0.0)
    fsdp = _run(cfg_f, data, ts)
    assert base[0] == fsdp[0], (base[0], fsdp[0])
    for a, b in zip(base[1], fsdp[1]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(base[2], fsdp[2])


def test_trainer_fsdp_snapshot_resumes_identically(tmp_path):
    """Sharded checkpoint round-trip: save gathers the fsdp leaves,
    restore re-commits them, and the resumed run's remaining rounds are
    bit-identical to the uninterrupted one."""
    over = {"shard__fsdp": 2, "shard__fsdp_min_size_mb": 0.0}
    cfg_full, data, ts = _tiny_trainer(tmp_path / "full", **over)
    cfg_full.train.save_every = 1
    full = _run(cfg_full, data, ts)

    cfg_a, _, _ = _tiny_trainer(tmp_path / "resumed", **over)
    cfg_a.fed.rounds = 2
    cfg_a.train.save_every = 1
    _run(cfg_a, data, ts)
    cfg_b, _, _ = _tiny_trainer(tmp_path / "resumed", **over)
    cfg_b.train.save_every = 1
    from fedrec_tpu.train.trainer import Trainer

    tr = Trainer(cfg_b, data, ts)
    assert tr.start_round == 2
    # the restored at-rest state is genuinely fsdp-sharded again
    specs = {
        str(x.sharding.spec)
        for x in jax.tree_util.tree_leaves(tr.state.user_params)
    }
    assert any(FSDP_AXIS in s for s in specs)
    hist = tr.run()
    user, table = tr.export_for_serving()
    resumed_losses = [h.train_loss for h in hist]
    assert resumed_losses == full[0][2:], (resumed_losses, full[0])
    for a, b in zip(
        full[1], [np.asarray(x) for x in jax.tree_util.tree_leaves(user)]
    ):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(full[2], np.asarray(table))


def test_gather_for_save_passthrough_on_addressable():
    from fedrec_tpu.train.checkpoint import gather_for_save

    tree = {"a": np.arange(4), "b": jnp.arange(3.0)}
    out = gather_for_save(tree)
    np.testing.assert_array_equal(out["a"], tree["a"])
    np.testing.assert_array_equal(np.asarray(out["b"]), np.asarray(tree["b"]))
