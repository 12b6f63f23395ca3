"""End-to-end federated training tests on a fake 8-device CPU mesh.

The JAX-native analogue of the reference's localhost torchrun simulation
(reference README.md:27-34): 8 virtual devices = 8 clients, loss must
decrease, aggregation must match hand-computed math.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fedrec_tpu.config import ExperimentConfig
from fedrec_tpu.data import TrainBatcher, index_samples, make_synthetic_mind
from fedrec_tpu.fed import get_strategy
from fedrec_tpu.models import NewsRecommender
from fedrec_tpu.parallel import client_mesh, shard_batch
from fedrec_tpu.train import (
    build_fed_train_step,
    build_news_update_step,
    build_param_sync,
    build_eval_step,
    encode_all_news,
)
from fedrec_tpu.train.state import init_client_state, replicate_state


def small_cfg(**over) -> ExperimentConfig:
    cfg = ExperimentConfig()
    cfg.model.news_dim = 32
    cfg.model.num_heads = 4
    cfg.model.head_dim = 8
    cfg.model.query_dim = 16
    cfg.model.bert_hidden = 48
    cfg.data.max_his_len = 10
    cfg.data.max_title_len = 12
    cfg.data.batch_size = 8
    cfg.fed.num_clients = 8
    for k, v in over.items():
        section, key = k.split("__")
        setattr(getattr(cfg, section), key, v)
    return cfg


def make_setup(cfg, num_news=64, num_train=256, seed=0):
    rng = np.random.default_rng(seed)
    data = make_synthetic_mind(
        num_news=num_news,
        num_train=num_train,
        num_valid=32,
        title_len=cfg.data.max_title_len,
        his_len_range=(2, cfg.data.max_his_len),
        seed=seed,
        popular_frac=0.2,  # learnable popularity signal
    )
    ix = index_samples(data.train_samples, data.nid2index, cfg.data.max_his_len)
    batcher = TrainBatcher(
        ix, cfg.data.batch_size, cfg.data.npratio, seed=seed
    )
    # synthetic frozen-trunk token states (stand-in for cached DistilBERT)
    token_states = jnp.asarray(
        rng.standard_normal((num_news, cfg.data.max_title_len, cfg.model.bert_hidden)).astype(
            np.float32
        )
    )
    model = NewsRecommender(cfg.model)
    state0 = init_client_state(
        model, cfg, jax.random.PRNGKey(seed), num_news, cfg.data.max_title_len
    )
    stacked = replicate_state(state0, cfg.fed.num_clients, jax.random.PRNGKey(seed + 1))
    mesh = client_mesh(cfg.fed.num_clients)
    return data, batcher, token_states, model, stacked, mesh


def _batch_dict(b):
    return {
        "candidates": b.candidates,
        "history": b.history,
        "labels": b.labels,
    }


def test_joint_training_loss_decreases():
    cfg = small_cfg(optim__user_lr=3e-3, optim__news_lr=3e-3)
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    strategy = get_strategy("grad_avg")
    step = build_fed_train_step(model, cfg, strategy, mesh, mode="joint")
    losses = []
    for epoch in range(4):
        for b in batcher.epoch_batches_sharded(cfg.fed.num_clients, epoch):
            batch = shard_batch(mesh, _batch_dict(b))
            stacked, metrics = step(stacked, batch, token_states)
            losses.append(float(np.mean(np.asarray(metrics["mean_loss"]))))
    assert losses[-1] < losses[0], f"loss did not decrease: {losses[0]} -> {losses[-1]}"


def test_grad_avg_keeps_clients_in_lockstep():
    cfg = small_cfg()
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    step = build_fed_train_step(model, cfg, get_strategy("grad_avg"), mesh, mode="joint")
    for b in batcher.epoch_batches_sharded(cfg.fed.num_clients, 0):
        batch = shard_batch(mesh, _batch_dict(b))
        stacked, _ = step(stacked, batch, token_states)
    # all clients saw identical (averaged) grads from identical init -> equal
    leaves = jax.tree_util.tree_leaves(stacked.user_params)
    for leaf in leaves:
        arr = np.asarray(leaf)
        np.testing.assert_allclose(arr[0], arr[-1], rtol=1e-4, atol=1e-5)


def test_param_avg_round_sync_matches_hand_mean():
    cfg = small_cfg()
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    step = build_fed_train_step(model, cfg, get_strategy("param_avg"), mesh, mode="joint")
    for b in batcher.epoch_batches_sharded(cfg.fed.num_clients, 0):
        batch = shard_batch(mesh, _batch_dict(b))
        stacked, _ = step(stacked, batch, token_states)
    # clients diverge during the round (no grad sync)
    leaf0 = np.asarray(jax.tree_util.tree_leaves(stacked.user_params)[0])
    assert not np.allclose(leaf0[0], leaf0[-1])
    # round-end FedAvg: every client adopts the hand-computed mean
    sync = build_param_sync(cfg, mesh)
    weights = jnp.ones((cfg.fed.num_clients,), jnp.float32)
    expected = {
        i: np.mean(np.asarray(leaf), axis=0)
        for i, leaf in enumerate(jax.tree_util.tree_leaves(stacked.user_params))
    }
    synced = sync(stacked, weights)
    for i, leaf in enumerate(jax.tree_util.tree_leaves(synced.user_params)):
        arr = np.asarray(leaf)
        for c in range(cfg.fed.num_clients):
            np.testing.assert_allclose(arr[c], expected[i], rtol=1e-5, atol=1e-6)


def test_participation_weighted_sync():
    cfg = small_cfg()
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    step = build_fed_train_step(model, cfg, get_strategy("param_avg"), mesh, mode="joint")
    for b in batcher.epoch_batches_sharded(cfg.fed.num_clients, 0):
        stacked, _ = step(stacked, shard_batch(mesh, _batch_dict(b)), token_states)
    sync = build_param_sync(cfg, mesh)
    # only clients 0 and 3 participate this round
    weights = jnp.zeros((cfg.fed.num_clients,), jnp.float32).at[0].set(1.0).at[3].set(1.0)
    expected = {
        i: 0.5 * (np.asarray(leaf)[0] + np.asarray(leaf)[3])
        for i, leaf in enumerate(jax.tree_util.tree_leaves(stacked.user_params))
    }
    synced = sync(stacked, weights)
    for i, leaf in enumerate(jax.tree_util.tree_leaves(synced.user_params)):
        arr = np.asarray(leaf)
        for c in range(cfg.fed.num_clients):  # dropouts also adopt the aggregate
            np.testing.assert_allclose(arr[c], expected[i], rtol=1e-5, atol=1e-6)


def test_decoupled_mode_accumulates_and_updates_news_head():
    cfg = small_cfg(optim__user_lr=3e-3, optim__news_lr=3e-3)
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    step = build_fed_train_step(model, cfg, get_strategy("param_avg"), mesh, mode="decoupled")
    news_update = build_news_update_step(model, cfg, mesh)
    # table from initial head params (client 0's copy; all clients identical)
    p0 = jax.tree_util.tree_map(lambda x: x[0], stacked.news_params)
    table = encode_all_news(model, p0, token_states)
    before_accum = float(jnp.sum(jnp.abs(stacked.news_grad_accum)))
    assert before_accum == 0.0
    losses = []
    for epoch in range(3):
        for b in batcher.epoch_batches_sharded(cfg.fed.num_clients, epoch):
            stacked, metrics = step(stacked, shard_batch(mesh, _batch_dict(b)), table)
            losses.append(float(np.mean(np.asarray(metrics["mean_loss"]))))
        assert float(jnp.sum(jnp.abs(stacked.news_grad_accum))) > 0.0
        old_news = jax.tree_util.tree_leaves(stacked.news_params)[0].copy()
        stacked, new_tables = news_update(stacked, token_states)
        # accumulator reset + head params moved + table refreshed per client
        assert float(jnp.sum(jnp.abs(stacked.news_grad_accum))) == 0.0
        assert not np.allclose(
            np.asarray(old_news), np.asarray(jax.tree_util.tree_leaves(stacked.news_params)[0])
        )
        table = jax.tree_util.tree_map(lambda x: x[0], new_tables)
    assert losses[-1] < losses[0]


def test_eval_step_metrics_shape():
    cfg = small_cfg()
    data, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    p0 = jax.tree_util.tree_map(lambda x: x[0], stacked.news_params)
    u0 = jax.tree_util.tree_map(lambda x: x[0], stacked.user_params)
    table = encode_all_news(model, p0, token_states)
    evaluate = build_eval_step(model, cfg)
    ix = index_samples(data.valid_samples, data.nid2index, cfg.data.max_his_len)
    vb = next(iter(TrainBatcher(ix, 16, cfg.data.npratio, seed=1).epoch_batches()))
    out = evaluate(u0, table, _batch_dict(vb))
    for k in ("auc", "mrr", "ndcg5", "ndcg10", "loss"):
        v = np.asarray(out[k])
        assert v.shape == (16,)  # per-impression, so callers can trim padding
        assert np.all(np.isfinite(v))
    assert np.all((np.asarray(out["auc"]) >= 0) & (np.asarray(out["auc"]) <= 1))


def test_zero_participation_round_keeps_local_params():
    # review finding: an all-dropout round must not NaN the models
    cfg = small_cfg()
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    step = build_fed_train_step(model, cfg, get_strategy("param_avg"), mesh, mode="joint")
    for b in batcher.epoch_batches_sharded(cfg.fed.num_clients, 0):
        stacked, _ = step(stacked, shard_batch(mesh, _batch_dict(b)), token_states)
    sync = build_param_sync(cfg, mesh)
    before = [np.asarray(x).copy() for x in jax.tree_util.tree_leaves(stacked.user_params)]
    synced = sync(stacked, jnp.zeros((cfg.fed.num_clients,), jnp.float32))
    after = jax.tree_util.tree_leaves(synced.user_params)
    for b_leaf, a_leaf in zip(before, after):
        arr = np.asarray(a_leaf)
        assert np.isfinite(arr).all()
        np.testing.assert_allclose(arr, b_leaf, rtol=1e-6)


def test_grad_avg_sync_also_covers_news_head_in_decoupled_mode():
    # review finding: GradAvg must keep the news tower in lockstep too
    cfg = small_cfg()
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    strategy = get_strategy("grad_avg")
    step = build_fed_train_step(model, cfg, strategy, mesh, mode="decoupled")
    news_update = build_news_update_step(model, cfg, mesh, strategy)
    p0 = jax.tree_util.tree_map(lambda x: x[0], stacked.news_params)
    table = encode_all_news(model, p0, token_states)
    for b in batcher.epoch_batches_sharded(cfg.fed.num_clients, 0):
        stacked, _ = step(stacked, shard_batch(mesh, _batch_dict(b)), table)
    stacked, _ = news_update(stacked, token_states)
    for leaf in jax.tree_util.tree_leaves(stacked.news_params):
        arr = np.asarray(leaf)
        np.testing.assert_allclose(arr[0], arr[-1], rtol=1e-4, atol=1e-6)


def test_local_strategy_param_sync_is_identity():
    cfg = small_cfg()
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    step = build_fed_train_step(model, cfg, get_strategy("param_avg"), mesh, mode="joint")
    for b in batcher.epoch_batches_sharded(cfg.fed.num_clients, 0):
        stacked, _ = step(stacked, shard_batch(mesh, _batch_dict(b)), token_states)
    sync = build_param_sync(cfg, mesh, get_strategy("local"))
    synced = sync(stacked, jnp.ones((cfg.fed.num_clients,), jnp.float32))
    for a, b_leaf in zip(
        jax.tree_util.tree_leaves(stacked.user_params),
        jax.tree_util.tree_leaves(synced.user_params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_leaf), rtol=1e-6)


def test_popular_frac_validation():
    with pytest.raises(ValueError, match="popular_frac"):
        make_synthetic_mind(num_news=10, popular_frac=0.95)


def test_encode_all_news_sharded_matches_single():
    """Mesh-sharded corpus encode == single-device encode, including the
    pad-to-divisible path (N=101 not divisible by 8 devices)."""
    import jax.numpy as jnp

    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.parallel import client_mesh
    from fedrec_tpu.train.state import init_client_state
    from fedrec_tpu.train.step import encode_all_news, encode_all_news_sharded

    cfg = ExperimentConfig()
    cfg.model.bert_hidden = 32
    cfg.model.news_dim = 32
    cfg.model.num_heads = 4
    cfg.model.head_dim = 8
    cfg.model.query_dim = 16
    model = NewsRecommender(cfg.model)
    rng = np.random.default_rng(7)
    states = jnp.asarray(rng.standard_normal((101, 6, 32)).astype(np.float32))
    p = init_client_state(model, cfg, jax.random.PRNGKey(0), 101, 6).news_params

    single = encode_all_news(model, p, states)
    # 1-D clients mesh AND a 2-D (clients, seq) mesh: rows shard over the
    # PRODUCT of axes — no device may hold redundant work
    from jax.sharding import Mesh

    meshes = [
        client_mesh(8),
        Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("clients", "seq")),
    ]
    for mesh in meshes:
        sharded = encode_all_news_sharded(model, p, states, mesh)
        assert sharded.shape == single.shape == (101, 32)
        np.testing.assert_allclose(
            np.asarray(sharded), np.asarray(single), rtol=2e-5, atol=2e-6
        )


def _server_opt_trainer(tmp_path, server_opt, lr=1.0, momentum=0.0, rounds=3,
                        snapshot=False):
    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.data import make_synthetic_mind
    from fedrec_tpu.train.trainer import Trainer

    cfg = ExperimentConfig()
    cfg.model.news_dim = 32
    cfg.model.num_heads = 4
    cfg.model.head_dim = 8
    cfg.model.query_dim = 16
    cfg.model.bert_hidden = 48
    cfg.model.text_encoder_mode = "head"
    cfg.data.max_his_len = 8
    cfg.data.max_title_len = 8
    cfg.data.batch_size = 8
    cfg.fed.num_clients = 4
    cfg.fed.strategy = "param_avg"
    cfg.fed.rounds = rounds
    cfg.fed.server_opt = server_opt
    cfg.fed.server_lr = lr
    cfg.fed.server_momentum = momentum
    cfg.train.snapshot_dir = str(tmp_path) if snapshot else ""
    cfg.train.resume = snapshot
    cfg.train.save_every = 1
    data = make_synthetic_mind(
        num_news=64, num_train=96, num_valid=0, title_len=8,
        his_len_range=(2, 8), seed=3,
    )
    states = np.random.default_rng(1).standard_normal(
        (64, 8, 48)
    ).astype(np.float32)
    return Trainer(cfg, data, states), cfg


def _flat_params(trainer):
    import jax

    u, n = trainer._client0_params()
    return np.concatenate(
        [np.ravel(np.asarray(x)) for x in jax.tree_util.tree_leaves((u, n))]
    )


def test_server_opt_sgd_neutral_equals_fedavg(tmp_path):
    """FedOpt with sgd(lr=1, momentum=0) IS plain FedAvg: identical params."""
    t_plain, _ = _server_opt_trainer(tmp_path / "plain", "none")
    t_neutral, _ = _server_opt_trainer(tmp_path / "neutral", "sgd", lr=1.0)
    for r in range(3):
        t_plain.train_round(r)
        t_neutral.train_round(r)
    # g + (m - g) per round is not bitwise m in float32; absolute floor
    # needed for near-zero params (same rationale as the coordinator test)
    np.testing.assert_allclose(
        _flat_params(t_plain), _flat_params(t_neutral), rtol=1e-4, atol=1e-5
    )


def test_server_opt_momentum_math():
    """ServerOptimizer reproduces hand-rolled FedAvgM over two rounds."""
    from fedrec_tpu.fed.strategies import ServerOptimizer

    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.standard_normal(5).astype(np.float32))}
    m1 = {"w": jnp.asarray(rng.standard_normal(5).astype(np.float32))}
    m2 = {"w": jnp.asarray(rng.standard_normal(5).astype(np.float32))}
    lr, beta = 0.5, 0.9

    opt = ServerOptimizer("sgd", lr=lr, momentum=beta)
    g1 = opt.step(g, m1)
    g2 = opt.step(g1, m2)

    # optax sgd-with-momentum: buf = beta*buf + delta; p -= lr*buf
    d1 = np.asarray(g["w"]) - np.asarray(m1["w"])
    buf = d1
    want1 = np.asarray(g["w"]) - lr * buf
    np.testing.assert_allclose(np.asarray(g1["w"]), want1, rtol=1e-6)
    d2 = want1 - np.asarray(m2["w"])
    buf = beta * buf + d2
    want2 = want1 - lr * buf
    np.testing.assert_allclose(np.asarray(g2["w"]), want2, rtol=1e-6)


def test_server_opt_resume_bit_identical(tmp_path):
    """FedAvgM momentum buffers survive resume via the sidecar: interrupted
    + resumed == straight through."""
    t_a, _ = _server_opt_trainer(
        tmp_path / "a", "sgd", lr=0.7, momentum=0.9, rounds=4, snapshot=True
    )
    t_a.run()

    t_b, _ = _server_opt_trainer(
        tmp_path / "b", "sgd", lr=0.7, momentum=0.9, rounds=2, snapshot=True
    )
    t_b.run()
    t_b2, _ = _server_opt_trainer(
        tmp_path / "b", "sgd", lr=0.7, momentum=0.9, rounds=4, snapshot=True
    )
    assert t_b2.start_round == 2
    t_b2.run()
    np.testing.assert_allclose(
        _flat_params(t_a), _flat_params(t_b2), rtol=1e-6, atol=1e-7
    )


def test_gru_tower_federated_training_loss_decreases():
    """The second model family (model.user_tower='gru') drives the SAME
    federated step/mesh machinery end-to-end."""
    cfg = small_cfg(optim__user_lr=3e-3, optim__news_lr=3e-3)
    cfg.model.user_tower = "gru"
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    step = build_fed_train_step(model, cfg, get_strategy("grad_avg"), mesh, mode="joint")
    losses = []
    for epoch in range(3):
        for b in batcher.epoch_batches_sharded(cfg.fed.num_clients, epoch):
            batch = shard_batch(mesh, _batch_dict(b))
            stacked, metrics = step(stacked, batch, token_states)
            losses.append(float(np.mean(np.asarray(metrics["mean_loss"]))))
    assert losses[-1] < losses[0], f"loss did not decrease: {losses[0]} -> {losses[-1]}"
