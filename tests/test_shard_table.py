"""Mesh-sharded news catalog (shard/table.py) on the fake 8-device mesh.

The acceptance pins: the owner-bucketed all_to_all gather is BIT-IDENTICAL
to the dense ``full_table[ids]``, per-device rows equal
``total_rows / shards``, and the sharded-table train step matches the
replicated-table step bitwise, round-end sync included — plus the
build-time guards, the serving
store's sharded mode, and the report's Sharding section.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from fedrec_tpu.fed import get_strategy
from fedrec_tpu.parallel import client_mesh, shard_batch
from fedrec_tpu.shard.table import (
    ShardedNewsTable,
    TableSpec,
    a2a_bytes_per_gather,
    owner_bucketed_gather,
)
from fedrec_tpu.train import build_fed_train_step, build_param_sync

from fedrec_tpu.train.step import NEWS_ROWS, host_news_dedup

from test_train import _batch_dict, make_setup, small_cfg


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _assert_trees_equal(a, b):
    for x, y in zip(_leaves(a), _leaves(b)):
        np.testing.assert_array_equal(x, y)


# ------------------------------------------------------------- the gather
def test_create_pads_and_splits_rows_per_device():
    mesh = client_mesh(8)
    full = np.arange(100 * 3, dtype=np.float32).reshape(100, 3)
    tab = ShardedNewsTable.create(full, mesh, "clients")
    assert tab.spec == TableSpec("clients", 8, 13, 100)
    assert tab.spec.padded_rows == 104
    # per-device resident rows == padded / shards, from the REAL shards
    assert {s.data.shape[0] for s in tab.rows.addressable_shards} == {13}
    # padding rows are zeros, real rows bit-equal
    host = np.asarray(tab.rows)
    np.testing.assert_array_equal(host[:100], full)
    assert (host[100:] == 0).all()


@pytest.mark.parametrize("case", ["random", "one_shard", "dupes"])
def test_owner_bucketed_gather_exact(case):
    mesh = client_mesh(8)
    rng = np.random.default_rng(3)
    n, row = 100, (5, 4)
    full = rng.standard_normal((n,) + row).astype(np.float32)
    tab = ShardedNewsTable.create(full, mesh, "clients")
    u = 16
    if case == "random":
        ids = rng.integers(0, n, (8, u)).astype(np.int32)
    elif case == "one_shard":
        # every id owned by shard 0 — the worst-case bucket capacity
        ids = rng.integers(0, tab.spec.rows_per_shard, (8, u)).astype(np.int32)
    else:
        ids = np.zeros((8, u), np.int32)
        ids[:, ::2] = rng.integers(0, n, (8, (u + 1) // 2))

    @partial(
        shard_map, mesh=mesh,
        in_specs=(P("clients"), P("clients")), out_specs=P("clients"),
        check_vma=False,
    )
    def gather(rows, ids_blk):
        return owner_bucketed_gather(rows, ids_blk[0], tab.spec)[None]

    out = jax.jit(gather)(
        tab.rows, jax.device_put(ids, NamedSharding(mesh, P("clients")))
    )
    np.testing.assert_array_equal(np.asarray(out), full[ids])


def test_a2a_bytes_model():
    spec = TableSpec("clients", 8, 13, 100)
    # per device: S*U ids at 4B + S*U rows; whole mesh = x S
    assert a2a_bytes_per_gather(16, (5, 4), np.float32, spec) == (
        8 * (8 * 16 * (4 + 5 * 4 * 4))
    )


# ------------------------------------------------------------ step equality
def test_sharded_step_bitwise_equals_dense():
    cfg = small_cfg(
        model__text_encoder_mode="head", optim__user_lr=3e-3,
        optim__news_lr=3e-3,
    )
    data, batcher, token_states, model, _, mesh = make_setup(cfg, seed=0)
    tab = ShardedNewsTable.create(np.asarray(token_states), mesh, "clients")
    batches = []
    for b in batcher.epoch_batches_sharded(8, 0):
        batches.append(_batch_dict(b))
        if len(batches) >= 2:
            break

    step_d = build_fed_train_step(
        model, cfg, get_strategy("param_avg"), mesh, mode="joint"
    )
    step_s = build_fed_train_step(
        model, cfg, get_strategy("param_avg"), mesh, mode="joint",
        sharded_table=tab.spec,
    )
    st_d = make_setup(cfg, seed=0)[4]
    st_s = make_setup(cfg, seed=0)[4]
    for b in batches:
        st_d, md = step_d(st_d, shard_batch(mesh, b), token_states)
        st_s, ms = step_s(st_s, shard_batch(mesh, b), tab.rows)
        np.testing.assert_array_equal(
            np.asarray(md["loss"]), np.asarray(ms["loss"])
        )
    _assert_trees_equal(st_d.user_params, st_s.user_params)
    _assert_trees_equal(st_d.news_params, st_s.news_params)

    # the round-end weighted sync on top of either table
    sync = build_param_sync(cfg, mesh, get_strategy("param_avg"))
    w = jnp.asarray([1, 1, 0, 1, 1, 1, 0, 1], jnp.float32)
    _assert_trees_equal(
        sync(st_d, w).user_params, sync(st_s, w).user_params
    )


def test_sharded_step_composes_with_chunk_and_host_dedup():
    cfg = small_cfg(model__text_encoder_mode="head", data__gather_chunk=16)
    data, batcher, token_states, model, _, mesh = make_setup(cfg, seed=0)
    tab = ShardedNewsTable.create(np.asarray(token_states), mesh, "clients")
    b = _batch_dict(next(iter(batcher.epoch_batches_sharded(8, 0))))
    step_d = build_fed_train_step(
        model, cfg, get_strategy("param_avg"), mesh, mode="joint"
    )
    step_s = build_fed_train_step(
        model, cfg, get_strategy("param_avg"), mesh, mode="joint",
        sharded_table=tab.spec,
    )
    _, md = step_d(make_setup(cfg, seed=0)[4], shard_batch(mesh, b), token_states)
    _, ms = step_s(make_setup(cfg, seed=0)[4], shard_batch(mesh, b), tab.rows)
    np.testing.assert_array_equal(np.asarray(md["loss"]), np.asarray(ms["loss"]))
    # the exchange takes the ids it is given: fed the host's dedup at 60
    # rows (below the 64 catalog rows the device-side dedup is bounded by,
    # the GLOBAL count, not the local block's 8), both programs still agree
    entries, most = host_news_dedup(b["candidates"], b["history"], 60, 64)
    assert most <= 60 and entries[NEWS_ROWS].shape == (8, 60)
    hb = {**b, **entries}
    _, mdh = step_d(make_setup(cfg, seed=0)[4], shard_batch(mesh, hb), token_states)
    _, msh = step_s(make_setup(cfg, seed=0)[4], shard_batch(mesh, hb), tab.rows)
    np.testing.assert_array_equal(np.asarray(mdh["loss"]), np.asarray(msh["loss"]))
    np.testing.assert_allclose(
        np.asarray(msh["loss"]), np.asarray(ms["loss"]), rtol=1e-6
    )


# ------------------------------------------------------------------ guards
def _spec8():
    return TableSpec("clients", 8, 8, 64)


@pytest.mark.parametrize("over,err", [
    ({"model__text_encoder_mode": "table"}, "text_encoder_mode='head'"),
    ({"model__text_encoder_mode": "head", "model__fuse_hot_path": True},
     "fuse_hot_path with shard.table"),
    ({"model__text_encoder_mode": "head", "fed__seq_shards": 2,
      "data__max_his_len": 10}, "seq_shards>1"),
])
def test_build_time_guards(over, err):
    cfg = small_cfg(**over)
    mode = "decoupled" if cfg.model.text_encoder_mode == "table" else "joint"
    if cfg.fed.seq_shards > 1:
        from fedrec_tpu.parallel import fed_mesh

        mesh = fed_mesh(cfg)
    else:
        mesh = client_mesh(8)
    model_cfg = small_cfg(**over)
    from fedrec_tpu.models import NewsRecommender

    model = NewsRecommender(model_cfg.model)
    with pytest.raises(NotImplementedError, match=err):
        build_fed_train_step(
            model, cfg, get_strategy("param_avg"), mesh, mode=mode,
            sharded_table=_spec8(),
        )


def test_guard_dpsgd_and_cohorts():
    from fedrec_tpu.models import NewsRecommender

    cfg = small_cfg(
        model__text_encoder_mode="head", privacy__enabled=True,
        privacy__mechanism="dpsgd", privacy__sigma=1.0,
    )
    model = NewsRecommender(cfg.model)
    with pytest.raises(NotImplementedError, match="dpsgd"):
        build_fed_train_step(
            model, cfg, get_strategy("param_avg"), client_mesh(8),
            mode="joint", sharded_table=_spec8(),
        )
    # 16 clients on 8 devices: k=2 in-device cohorts
    cfg_k = small_cfg(
        model__text_encoder_mode="head", fed__num_clients=16
    )
    model_k = NewsRecommender(cfg_k.model)
    with pytest.raises(NotImplementedError, match="in-device cohorts"):
        build_fed_train_step(
            model_k, cfg_k, get_strategy("param_avg"), client_mesh(16),
            mode="joint", sharded_table=_spec8(),
        )


def test_trainer_guard_topk_x_fsdp():
    from fedrec_tpu.train.trainer import Trainer
    from fedrec_tpu.data import make_synthetic_mind

    cfg = small_cfg(fed__num_clients=4)
    cfg.model.text_encoder_mode = "head"
    cfg.shard.fsdp = 2
    cfg.fed.dcn_compress = "topk"
    cfg.train.snapshot_dir = ""
    data = make_synthetic_mind(
        num_news=32, num_train=64, num_valid=8,
        title_len=cfg.data.max_title_len, seed=0,
    )
    rng = np.random.default_rng(0)
    ts = rng.standard_normal(
        (32, cfg.data.max_title_len, cfg.model.bert_hidden)
    ).astype(np.float32)
    with pytest.raises(ValueError, match="topk"):
        Trainer(cfg, data, ts)


# ---------------------------------------------------------------- serving
def test_publish_sharded_scores_match_dense():
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.serve import build_recommend_fn
    from fedrec_tpu.serving.store import EmbeddingStore, publish_sharded

    cfg = small_cfg()
    model = NewsRecommender(cfg.model)
    rng = np.random.default_rng(0)
    n = 100  # not divisible by 8: pad rows exist and must never serve
    table = rng.standard_normal((n, cfg.model.news_dim)).astype(np.float32)
    dummy = jnp.zeros((1, cfg.data.max_his_len, cfg.model.news_dim))
    user_params = model.init(
        jax.random.PRNGKey(0), dummy, method=NewsRecommender.encode_user
    )["params"]["user_encoder"]

    store = EmbeddingStore()
    gen = publish_sharded(store, table, user_params, source="test")
    assert gen.source.endswith(":sharded")
    assert gen.num_news >= n and gen.num_news % 8 == 0
    assert not gen.valid_mask[n:].any()

    history = rng.integers(1, n, (4, cfg.data.max_his_len)).astype(np.int32)
    fn_dense = build_recommend_fn(model, top_k=5)
    fn_mask = build_recommend_fn(model, top_k=5, valid_mask=gen.valid_mask)
    ids_d, scores_d = fn_dense(user_params, jnp.asarray(table), history)
    ids_s, scores_s = fn_mask(user_params, gen.news_vecs, history)
    np.testing.assert_array_equal(np.asarray(ids_d), np.asarray(ids_s))
    np.testing.assert_allclose(
        np.asarray(scores_d), np.asarray(scores_s), rtol=1e-6, atol=1e-6
    )


# ----------------------------------------------------------------- report
def test_report_sharding_section():
    from fedrec_tpu.obs.report import build_report, render_text

    snap = {"kind": "registry_snapshot", "ts": 0, "metrics": {
        "shard.fsdp_shards": {"values": [{"value": 2.0}]},
        "shard.state_bytes_per_device": {"values": [{"value": 1048576.0}]},
        "shard.table_rows_per_device": {"values": [{"value": 13.0}]},
        "shard.table_occupancy": {"values": [{"value": 0.96}]},
        "shard.remote_gather_rows": {"values": [{"value": 800.0}]},
        "shard.a2a_bytes_total": {"values": [{"value": 2097152.0}]},
    }}
    report = build_report([], [snap])
    assert report["sharding"]["fsdp_shards"] == 2.0
    assert report["sharding"]["a2a_bytes"] == 2097152.0
    text = render_text(report)
    assert "## Sharding" in text
    assert "catalog rows/device: 13" in text
    assert "fsdp shards: 2" in text

    # replicated run: no sharding section
    empty = build_report([], [{
        "kind": "registry_snapshot", "ts": 0, "metrics": {
            "shard.fsdp_shards": {"values": [{"value": 1.0}]},
        },
    }])
    assert "sharding" not in empty
