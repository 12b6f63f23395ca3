"""Sequence/context parallelism: ring + Ulysses attention vs dense reference.

The JAX-native analogue of multi-node testing (SURVEY §4): an 8-virtual-device
CPU mesh via ``--xla_force_host_platform_device_count`` (set in conftest).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from fedrec_tpu.parallel.ring import (
    ring_attention,
    seq_parallel_pool,
    ulysses_attention,
)

SEQ = 4  # devices on the seq axis


def _mesh():
    return Mesh(np.array(jax.devices()[:SEQ]), ("seq",))


def _dense_reference(q, k, v, mask):
    """Stable-softmax dense attention with the framework's mask semantics."""
    dk = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dk))
    s = jnp.where(mask[:, None, None, :] > 0, s, -1e30)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s) * mask[:, None, None, :]
    p = p / (jnp.sum(p, axis=-1, keepdims=True) + 1e-8)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _rand_qkv(b=2, l=16, h=4, d=8, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, l, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, l, h, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, l, h, d)).astype(np.float32))
    mask = np.ones((b, l), np.float32)
    mask[:, -3:] = 0.0  # padding tail, shared across batch for simplicity
    return q, k, v, jnp.asarray(mask)


@pytest.mark.parametrize("impl", [ring_attention, ulysses_attention])
def test_sp_attention_matches_dense(impl):
    q, k, v, mask = _rand_qkv()
    want = _dense_reference(q, k, v, mask)

    fn = shard_map(
        lambda *a: impl(*a, axis_name="seq"),
        mesh=_mesh(),
        in_specs=(
            P(None, "seq", None, None),
            P(None, "seq", None, None),
            P(None, "seq", None, None),
            P(None, "seq"),
        ),
        out_specs=P(None, "seq", None, None),
    )
    got = fn(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("impl", [ring_attention, ulysses_attention])
def test_sp_attention_maskless_matches_dense(impl):
    q, k, v, _ = _rand_qkv(seed=5)
    ones = jnp.ones(q.shape[:2], jnp.float32)
    want = _dense_reference(q, k, v, ones)

    fn = shard_map(
        lambda a, b, c: impl(a, b, c, None, axis_name="seq"),
        mesh=_mesh(),
        in_specs=(
            P(None, "seq", None, None),
            P(None, "seq", None, None),
            P(None, "seq", None, None),
        ),
        out_specs=P(None, "seq", None, None),
    )
    got = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("impl", [ring_attention, ulysses_attention])
def test_sp_attention_grads_match_dense(impl):
    q, k, v, mask = _rand_qkv(seed=1)

    def dense_loss(q, k, v):
        return jnp.sum(_dense_reference(q, k, v, mask) ** 2)

    def sp_loss(q, k, v):
        fn = shard_map(
            lambda *a: impl(*a, axis_name="seq"),
            mesh=_mesh(),
            in_specs=(
                P(None, "seq", None, None),
                P(None, "seq", None, None),
                P(None, "seq", None, None),
                P(None, "seq"),
            ),
            out_specs=P(None, "seq", None, None),
        )
        return jnp.sum(fn(q, k, v, mask) ** 2)

    g_want = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(sp_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_seq_parallel_pool_matches_dense():
    rng = np.random.default_rng(2)
    b, l, d = 3, 16, 8
    x = jnp.asarray(rng.standard_normal((b, l, d)).astype(np.float32))
    logits = jnp.asarray(rng.standard_normal((b, l)).astype(np.float32))
    mask = np.ones((b, l), np.float32)
    mask[:, -5:] = 0.0
    mask = jnp.asarray(mask)

    w = jnp.exp(logits - jnp.max(jnp.where(mask > 0, logits, -1e30), axis=-1, keepdims=True))
    w = w * mask
    want = jnp.einsum("bl,bld->bd", w / (jnp.sum(w, -1, keepdims=True) + 1e-8), x)

    fn = shard_map(
        lambda *a: seq_parallel_pool(*a, axis_name="seq"),
        mesh=_mesh(),
        in_specs=(P(None, "seq", None), P(None, "seq"), P(None, "seq")),
        out_specs=P(),
    )
    got = fn(x, logits, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_user_encoder_seq_parallel_matches_single_chip():
    """Full UserEncoder with history sharded over the seq axis == dense run."""
    from fedrec_tpu.models.encoders import UserEncoder

    b, hist, heads, hd = 2, 16, 4, 8
    dim = heads * hd
    rng = np.random.default_rng(3)
    clicked = jnp.asarray(rng.standard_normal((b, hist, dim)).astype(np.float32))
    mask = np.ones((b, hist), np.float32)
    mask[:, -4:] = 0.0
    mask = jnp.asarray(mask)

    dense_enc = UserEncoder(news_dim=dim, num_heads=heads, head_dim=hd, query_dim=16)
    params = dense_enc.init(jax.random.PRNGKey(0), clicked, mask)
    want = dense_enc.apply(params, clicked, mask)

    for impl in ("ring", "ulysses"):
        sp_enc = UserEncoder(
            news_dim=dim, num_heads=heads, head_dim=hd, query_dim=16,
            seq_axis="seq", seq_impl=impl,
        )
        fn = shard_map(
            lambda p, x, m: sp_enc.apply(p, x, m),
            mesh=_mesh(),
            in_specs=(P(), P(None, "seq", None), P(None, "seq")),
            out_specs=P(),
        )
        got = fn(params, clicked, mask)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-5,
            err_msg=f"seq_impl={impl}",
        )


def test_fed_train_step_seq_parallel_matches_plain():
    """build_fed_train_step on a (2 clients x 4 seq) mesh == the plain
    2-client step: same loss, same updated params (dropout off so the only
    difference is the sharding)."""
    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.parallel import fed_mesh, shard_fed_batch
    from fedrec_tpu.train import build_fed_train_step
    from fedrec_tpu.train.state import init_client_state, replicate_state

    def make_cfg(seq_shards):
        cfg = ExperimentConfig()
        cfg.model.news_dim = 32
        cfg.model.num_heads = 4
        cfg.model.head_dim = 8
        cfg.model.query_dim = 16
        cfg.model.bert_hidden = 48
        cfg.model.dropout_rate = 0.0
        cfg.model.text_encoder_mode = "head"
        cfg.data.max_his_len = 16
        cfg.data.max_title_len = 8
        cfg.data.batch_size = 4
        cfg.fed.num_clients = 2
        cfg.fed.seq_shards = seq_shards
        return cfg

    num_news, n_cli = 32, 2
    rng = np.random.default_rng(11)
    token_states = jnp.asarray(
        rng.standard_normal((num_news, 8, 48)).astype(np.float32)
    )
    raw_batch = {
        "candidates": rng.integers(0, num_news, (n_cli, 4, 5)).astype(np.int32),
        "history": rng.integers(0, num_news, (n_cli, 4, 16)).astype(np.int32),
        "labels": np.zeros((n_cli, 4), np.int32),
    }

    results = {}
    for seq_shards in (1, 4):
        cfg = make_cfg(seq_shards)
        model = NewsRecommender(cfg.model)
        state0 = init_client_state(model, cfg, jax.random.PRNGKey(0), num_news, 8)
        stacked = replicate_state(state0, n_cli, jax.random.PRNGKey(1))
        mesh = fed_mesh(cfg)
        batch = shard_fed_batch(mesh, raw_batch, cfg)
        step = build_fed_train_step(
            model, cfg, get_strategy("grad_avg"), mesh, mode="joint"
        )
        new_state, metrics = step(stacked, batch, token_states)
        results[seq_shards] = (
            np.asarray(metrics["mean_loss"]),
            jax.tree_util.tree_map(np.asarray, new_state.user_params),
            jax.tree_util.tree_map(np.asarray, new_state.news_params),
        )

    loss1, user1, news1 = results[1]
    loss4, user4, news4 = results[4]
    np.testing.assert_allclose(loss4, loss1, atol=1e-5)
    # params pass through Adam's g/(sqrt(v)+eps) at step 1, which amplifies
    # float32 reduction-order noise in near-zero grads — hence the looser tol
    for a, b in zip(jax.tree_util.tree_leaves(user4), jax.tree_util.tree_leaves(user1)):
        np.testing.assert_allclose(a, b, atol=2e-3)
    for a, b in zip(jax.tree_util.tree_leaves(news4), jax.tree_util.tree_leaves(news1)):
        np.testing.assert_allclose(a, b, atol=2e-3)


@pytest.mark.parametrize("dropout", [0.0, 0.2])
def test_fed_train_step_seq_parallel_finetune(dropout):
    """Finetune mode (full trunk in-loop) on a (2 clients x 4 seq) mesh.

    With dropout off the sharded step must match the plain 2-client step
    exactly (loss + updated trunk params). With dropout on, the candidate
    encode is split from the history encode so its row layout — and dropout
    mask — is identical on every shard (the round-1 divergence bug); here we
    assert the step runs and stays finite.
    """
    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.parallel import fed_mesh, shard_fed_batch
    from fedrec_tpu.train import build_fed_train_step
    from fedrec_tpu.train.state import init_client_state, replicate_state

    def make_cfg(seq_shards):
        cfg = ExperimentConfig()
        cfg.model.news_dim = 32
        cfg.model.num_heads = 4
        cfg.model.head_dim = 8
        cfg.model.query_dim = 16
        cfg.model.bert_hidden = 32
        cfg.model.dropout_rate = dropout
        cfg.model.trunk_dropout = dropout
        cfg.model.text_encoder_mode = "finetune"
        cfg.model.trunk_layers = 1
        cfg.model.trunk_heads = 2
        cfg.model.trunk_ffn = 64
        cfg.model.trunk_vocab = 500
        cfg.data.max_his_len = 16
        cfg.data.max_title_len = 8
        cfg.data.batch_size = 4
        cfg.fed.num_clients = 2
        cfg.fed.seq_shards = seq_shards
        return cfg

    num_news, n_cli = 32, 2
    rng = np.random.default_rng(3)
    news_tokens = jnp.asarray(
        rng.integers(1, 500, (num_news, 2, 8)).astype(np.int32)
    )
    raw_batch = {
        "candidates": rng.integers(0, num_news, (n_cli, 4, 5)).astype(np.int32),
        "history": rng.integers(0, num_news, (n_cli, 4, 16)).astype(np.int32),
        "labels": np.zeros((n_cli, 4), np.int32),
    }

    results = {}
    for seq_shards in (1, 4):
        cfg = make_cfg(seq_shards)
        model = NewsRecommender(cfg.model)
        state0 = init_client_state(model, cfg, jax.random.PRNGKey(0), num_news, 8)
        stacked = replicate_state(state0, n_cli, jax.random.PRNGKey(1))
        mesh = fed_mesh(cfg)
        batch = shard_fed_batch(mesh, raw_batch, cfg)
        step = build_fed_train_step(
            model, cfg, get_strategy("grad_avg"), mesh, mode="finetune"
        )
        new_state, metrics = step(stacked, batch, news_tokens)
        results[seq_shards] = (
            np.asarray(metrics["mean_loss"]),
            jax.tree_util.tree_map(np.asarray, new_state.news_params),
        )

    loss1, news1 = results[1]
    loss4, news4 = results[4]
    assert np.all(np.isfinite(loss1)) and np.all(np.isfinite(loss4))
    if dropout == 0.0:
        np.testing.assert_allclose(loss4, loss1, atol=1e-5)
        for a, b in zip(
            jax.tree_util.tree_leaves(news4), jax.tree_util.tree_leaves(news1)
        ):
            np.testing.assert_allclose(a, b, atol=2e-3)


def test_finetune_candidate_encode_replicated_across_shards():
    """The property behind the finetune seq-parallel fix: with trunk dropout
    active and a SHARED key, encoding candidates alone gives bitwise-identical
    vectors on every seq shard, while the old joint dedup (candidates + the
    local history shard) places candidates at shard-dependent row indices and
    de-replicates their dropout masks."""
    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.models.bert import make_text_encoder
    from fedrec_tpu.train.step import _batch_news_vecs_tokens, _encode_tokens_rows

    cfg = ExperimentConfig()
    cfg.model.news_dim = 32
    cfg.model.bert_hidden = 32
    cfg.model.trunk_layers = 1
    cfg.model.trunk_heads = 2
    cfg.model.trunk_ffn = 64
    cfg.model.trunk_vocab = 500
    cfg.model.trunk_dropout = 0.2
    cfg.data.max_title_len = 8
    te = make_text_encoder(cfg.model)

    rng = np.random.default_rng(9)
    tokens = jnp.asarray(rng.integers(1, 500, (32, 2, 8)).astype(np.int32))
    params = te.init(jax.random.PRNGKey(0), jnp.zeros((1, 2, 8), jnp.int32))["params"]
    cand = jnp.asarray(rng.integers(0, 32, (4, 5)).astype(np.int32))
    # two different history shards, as two seq shards would see them
    his_shards = [
        jnp.asarray(rng.integers(0, 32, (4, 8)).astype(np.int32)) for _ in range(2)
    ]
    key = jax.random.PRNGKey(5)

    # new path: candidates encoded alone -> identical on every "shard"
    per_shard = [
        np.asarray(_encode_tokens_rows(te, params, tokens, cand, key))
        for _ in his_shards
    ]
    np.testing.assert_array_equal(per_shard[0], per_shard[1])

    # old path: joint dedup with the local history shard -> masks diverge
    joint = [
        np.asarray(_batch_news_vecs_tokens(te, params, tokens, cand, h, key)[0])
        for h in his_shards
    ]
    assert np.abs(joint[0] - joint[1]).max() > 1e-6


def test_fed_train_step_seq_parallel_rejects_decoupled():
    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.parallel import fed_mesh
    from fedrec_tpu.train import build_fed_train_step

    cfg = ExperimentConfig()
    cfg.fed.num_clients = 2
    cfg.fed.seq_shards = 4
    cfg.data.max_his_len = 48  # divisible by seq_shards
    mesh = fed_mesh(cfg)
    model = NewsRecommender(cfg.model)
    with pytest.raises(NotImplementedError):
        build_fed_train_step(
            model, cfg, get_strategy("grad_avg"), mesh, mode="decoupled"
        )


def test_user_encoder_seq_parallel_grads_match():
    """Param grads through the SP encoder == dense param grads."""
    from fedrec_tpu.models.encoders import UserEncoder

    b, hist, heads, hd = 2, 16, 4, 8
    dim = heads * hd
    rng = np.random.default_rng(4)
    clicked = jnp.asarray(rng.standard_normal((b, hist, dim)).astype(np.float32))
    mask = jnp.ones((b, hist), jnp.float32)

    dense_enc = UserEncoder(news_dim=dim, num_heads=heads, head_dim=hd, query_dim=16)
    params = dense_enc.init(jax.random.PRNGKey(0), clicked, mask)

    def dense_loss(p):
        return jnp.mean(dense_enc.apply(p, clicked, mask) ** 2)

    sp_enc = UserEncoder(
        news_dim=dim, num_heads=heads, head_dim=hd, query_dim=16, seq_axis="seq"
    )

    def sp_loss(p):
        fn = shard_map(
            lambda p, x, m: jnp.mean(sp_enc.apply(p, x, m) ** 2),
            mesh=_mesh(),
            in_specs=(P(), P(None, "seq", None), P(None, "seq")),
            out_specs=P(),
        )
        return fn(p, clicked, mask)

    g_want = jax.grad(dense_loss)(params)
    g_got = jax.grad(sp_loss)(params)
    flat_w, _ = jax.tree_util.tree_flatten(g_want)
    flat_g, _ = jax.tree_util.tree_flatten(g_got)
    for a, b_ in zip(flat_g, flat_w):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4)
