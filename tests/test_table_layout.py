"""The token-state table's at-rest layout (PERF.md section 5, PR 27).

A TPU keeps ``(N, 50, 768)`` with the 50-axis major and every program
handed that rewrites the whole table before it can gather; the ``Trainer``
commits the table once to the row-major layout the gather reads and every
program that takes it states that format. On the CPU the default layout is
already row-major, so nothing is relaid here: these tests hold the rule
(who commits, which programs state the format, what bypasses) and that it
changes no bit of a step. The compile for the described chip, where the
layouts differ, is in ``tests/test_chip_compile.py``.
"""

from __future__ import annotations

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from fedrec_tpu.config import ExperimentConfig
from fedrec_tpu.data import make_synthetic_mind
from fedrec_tpu.fed import get_strategy
from fedrec_tpu.models import NewsRecommender
from fedrec_tpu.obs import get_tracer
from fedrec_tpu.parallel.mesh import fed_mesh, shard_fed_batch
from fedrec_tpu.train import build_fed_train_step
from fedrec_tpu.train.state import init_client_state, replicate_state
from fedrec_tpu.train.step import (
    commit_token_table,
    is_token_state_table,
    token_table_format,
)
from fedrec_tpu.train.trainer import Trainer

ROW_MAJOR = (0, 1, 2)
NUM_NEWS = 64


def tiny_cfg(mode: str = "head", **over) -> ExperimentConfig:
    cfg = ExperimentConfig()
    cfg.model.text_encoder_mode = mode
    cfg.model.news_dim = 32
    cfg.model.num_heads = 4
    cfg.model.head_dim = 8
    cfg.model.query_dim = 16
    cfg.model.bert_hidden = 48
    cfg.model.trunk_layers = 1
    cfg.model.trunk_heads = 4
    cfg.model.trunk_ffn = 64
    cfg.model.trunk_vocab = 2048
    cfg.model.dropout_rate = 0.0
    cfg.data.max_his_len = 10
    cfg.data.max_title_len = 12
    cfg.data.batch_size = 8
    cfg.fed.num_clients = 8
    cfg.fed.rounds = 2
    cfg.train.snapshot_dir = ""
    cfg.train.eval_every = 1_000_000
    for k, v in over.items():
        section, key = k.split("__")
        setattr(getattr(cfg, section), key, v)
    return cfg


def tiny_inputs(cfg: ExperimentConfig):
    data = make_synthetic_mind(
        num_news=NUM_NEWS, num_train=256, num_valid=16,
        title_len=cfg.data.max_title_len,
        his_len_range=(2, cfg.data.max_his_len),
        seed=0, popular_frac=0.2, vocab=cfg.model.trunk_vocab,
    )
    states = np.random.default_rng(0).standard_normal(
        (NUM_NEWS, cfg.data.max_title_len, cfg.model.bert_hidden)
    ).astype(np.float32)
    return data, states


def commit_spans(mark: int) -> list[dict]:
    return [ev for ev in get_tracer().events_since(mark)
            if ev.get("name") == "table_commit"]


def layout_of(table) -> tuple:
    return tuple(table.format.layout.major_to_minor)


# ------------------------------------------------------------ the functions
@pytest.mark.parametrize("table,expected", [
    (np.zeros((4, 3, 8), np.float32), True),
    (jnp.zeros((4, 3, 8), jnp.bfloat16), True),
    (np.zeros((4, 8), np.float32), False),          # decoupled: news vectors
    (np.zeros((4, 2, 3), np.int32), False),         # finetune: token ids
])
def test_a_token_state_table_is_told_by_what_it_is(table, expected):
    assert is_token_state_table(table) is expected


def test_commit_sets_the_stated_format_and_says_what_it_found():
    cfg = tiny_cfg()
    mesh = fed_mesh(cfg)
    host = np.ones((16, 5, 8), np.float32)
    table, did = commit_token_table(host, mesh)
    assert did["found"] == "host" and did["relaid"]   # a host array has no layout
    # the fresh compile is a TPU's (its cache mislabels the result): not here
    assert did["compiled_afresh"] is False
    assert table.committed and layout_of(table) == ROW_MAJOR
    assert table.sharding.is_equivalent_to(NamedSharding(mesh, P()), 3)
    assert token_table_format(mesh).layout.major_to_minor == ROW_MAJOR

    on_device = jnp.asarray(host)
    again, did = commit_token_table(on_device, mesh)
    assert did["found"] == str(ROW_MAJOR) and again is not on_device
    assert not on_device.is_deleted()                 # the caller's array stays

    # a table that already rests there costs nothing
    same, did = commit_token_table(table, mesh)
    assert same is table and did["found"] == did["set"] == str(ROW_MAJOR)
    assert not did["relaid"]


@pytest.mark.parametrize("table", [
    np.zeros((4, 8), np.float32),                   # decoupled: news vectors
    np.zeros((4, 2, 3), np.int32),                  # finetune: token ids
])
def test_commit_leaves_the_other_modes_tables_alone(table):
    """Whoever holds a step's table calls ``commit_token_table`` without
    asking the mode (the flight-recorder replay, the benchmarks): a table
    that is no token-state table comes back as it went in."""
    got, did = commit_token_table(table, fed_mesh(tiny_cfg()))
    assert got is table and did is None


def test_commit_keeps_the_row_blocks_of_a_sharded_table():
    cfg = tiny_cfg()
    mesh = fed_mesh(cfg)
    spec = P(cfg.fed.mesh_axis)
    table, _ = commit_token_table(np.ones((16, 5, 8), np.float32), mesh, spec)
    assert layout_of(table) == ROW_MAJOR
    assert table.sharding.is_equivalent_to(NamedSharding(mesh, spec), 3)
    assert {s.data.shape for s in table.addressable_shards} == {(2, 5, 8)}


# ------------------------------------------------------------- the trainer
def test_trainer_commits_the_token_table_once():
    cfg = tiny_cfg("head")
    data, states = tiny_inputs(cfg)
    mine = jnp.asarray(states)
    mark = get_tracer().event_count()
    trainer = Trainer(cfg, data, mine)
    table = trainer.token_states
    assert table.committed and layout_of(table) == ROW_MAJOR
    assert trainer._feature_table() is table
    spans = commit_spans(mark)
    assert len(spans) == 1
    args = spans[0]["args"]
    assert args["found"] == str(ROW_MAJOR) and args["set"] == str(ROW_MAJOR)
    assert args["relaid"] is False and args["bytes"] == states.nbytes
    # the caller's array is neither donated nor deleted
    assert table is not mine and not mine.is_deleted()
    np.testing.assert_array_equal(np.asarray(mine), states)


def test_trainer_commits_a_host_table_and_a_sharded_one():
    cfg = tiny_cfg("head", shard__table=True)
    data, states = tiny_inputs(cfg)
    mark = get_tracer().event_count()
    trainer = Trainer(cfg, data, states)
    table = trainer.token_states
    assert layout_of(table) == ROW_MAJOR
    assert table.sharding.spec == P(cfg.fed.mesh_axis)
    assert len(commit_spans(mark)) == 1
    result = trainer.train_round(0)
    assert np.isfinite(result.train_loss)
    assert trainer.train_step.__wrapped__._cache_size() == 1


@pytest.mark.parametrize("mode", ["table", "finetune"])
def test_other_feature_tables_bypass_the_commit(mode):
    """``decoupled`` trains on the (N, D) news vectors and ``finetune`` on
    the int32 token rows: neither is a token-state table, and
    ``_feature_table()`` hands them over as they are."""
    cfg = tiny_cfg(mode)
    data, states = tiny_inputs(cfg)
    mark = get_tracer().event_count()
    trainer = Trainer(cfg, data, states)
    fed = trainer._feature_table()
    assert not is_token_state_table(fed)
    if mode == "finetune":
        assert fed is trainer.news_tokens and fed.dtype == jnp.int32
        assert trainer.token_states is None
        assert commit_spans(mark) == []
    else:
        assert fed.shape == (NUM_NEWS, cfg.model.news_dim)
        # the cached states behind the vectors are a token-state table: the
        # epoch-end news_update and the corpus encode read them committed
        assert layout_of(trainer.token_states) == ROW_MAJOR
        assert len(commit_spans(mark)) == 1
    at_construction = len(commit_spans(mark))
    result = trainer.train_round(0)
    assert np.isfinite(result.train_loss)
    assert len(commit_spans(mark)) == at_construction  # none per round


def test_the_trainers_copy_dies_with_the_trainer():
    cfg = tiny_cfg("head")
    data, states = tiny_inputs(cfg)
    mine = jnp.asarray(states)
    trainer = Trainer(cfg, data, mine)
    trainer.train_round(0)
    theirs = weakref.ref(trainer.token_states)
    assert theirs() is not None and theirs() is not mine
    del trainer
    gc.collect()
    assert theirs() is None, "something outside the trainer holds its table"
    assert not mine.is_deleted()
    np.testing.assert_array_equal(np.asarray(mine), states)


# ---------------------------------------------------------------- the step
def _joint_step_inputs(cfg):
    model = NewsRecommender(cfg.model)
    mesh = fed_mesh(cfg)
    n, b = cfg.fed.num_clients, cfg.data.batch_size
    state = replicate_state(
        init_client_state(model, cfg, jax.random.PRNGKey(0), NUM_NEWS,
                          cfg.data.max_title_len),
        n, jax.random.PRNGKey(1),
    )
    rng = np.random.default_rng(3)
    batch = {
        "candidates": rng.integers(1, NUM_NEWS, (n, b, 1 + cfg.data.npratio)).astype(np.int32),
        "history": rng.integers(0, NUM_NEWS, (n, b, cfg.data.max_his_len)).astype(np.int32),
        "labels": np.zeros((n, b), np.int32),
    }
    states = rng.standard_normal(
        (NUM_NEWS, cfg.data.max_title_len, cfg.model.bert_hidden)
    ).astype(np.float32)
    return model, mesh, state, batch, states


def test_the_stated_format_changes_no_bit_of_a_joint_step():
    cfg = tiny_cfg("head")
    model, mesh, state, batch, states = _joint_step_inputs(cfg)
    strategy = get_strategy(cfg.fed.strategy)
    step = build_fed_train_step(model, cfg, strategy, mesh, mode="joint")
    plain = jax.jit(step.__wrapped__)      # the same program, no Format stated
    table, _ = commit_token_table(states, mesh)
    fed = shard_fed_batch(mesh, batch, cfg)
    copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
    got_state, got_metrics = step(copy(state), fed, table)
    want_state, want_metrics = plain(copy(state), fed, jnp.asarray(states))
    got = jax.tree_util.tree_leaves((got_state, got_metrics))
    want = jax.tree_util.tree_leaves((want_state, want_metrics))
    assert len(got) == len(want) > 10
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    stated = step.lower(state, fed, table).compile().input_formats[0][2]
    assert tuple(stated.layout.major_to_minor) == ROW_MAJOR


@pytest.mark.parametrize("form", ["train_step", "param_sync"])
def test_each_program_of_a_round_compiles_once_over_two_rounds(form):
    cfg = tiny_cfg("head")
    data, states = tiny_inputs(cfg)
    trainer = Trainer(cfg, data, states)
    program = getattr(trainer, form)
    calls = []
    setattr(trainer, form, lambda *a: calls.append(1) or program(*a))
    trainer.train_round(0)
    trainer.train_round(1)
    assert len(calls) >= 2
    assert program.__wrapped__._cache_size() == 1


# ------------------------------------------------- the relayout's own compile
def test_what_compiled_afresh_compiles_stays_out_of_the_persistent_cache(tmp_path):
    """A program with an output layout of its own must not come back from
    the persistent cache (``compiled_afresh``'s docstring says why): inside
    the block nothing is read from it or written to it, after it the cache
    works as before."""
    from jax.experimental.compilation_cache import compilation_cache

    from fedrec_tpu.utils.compile_cache import compiled_afresh

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    was = {k: getattr(jax.config, k) for k in keys}
    entries = lambda: {p.name for p in tmp_path.iterdir() if "atime" not in p.name}  # noqa: E731
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
        x = jnp.asarray(np.arange(12.0, dtype=np.float32).reshape(3, 4))
        jax.jit(lambda a: a * 2.0 + 1.0)(x).block_until_ready()
        before = entries()
        assert len(before) == 1
        with compiled_afresh():
            assert jax.config.jax_enable_compilation_cache is False
            jax.jit(lambda a: a * 3.0 - 1.0)(x).block_until_ready()
            assert entries() == before
        assert jax.config.jax_enable_compilation_cache is True
        jax.jit(lambda a: a * 5.0 + 7.0)(x).block_until_ready()
        assert len(entries()) == len(before) + 1
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


# ----------------------------------- the single worker's text head, batched
def test_a_batch_of_one_text_head_is_the_same_arithmetic():
    """The single worker (one client on one device) runs un-vmapped, and
    its text head is compiled as a batch of one (for the layouts XLA:TPU
    then picks: PERF.md section 6, PR 27). Values and parameter gradients
    agree with the un-batched form to float32 rounding."""
    from fedrec_tpu.train.step import _encode_gathered

    cfg = tiny_cfg("head")
    model, _, state, _, states = _joint_step_inputs(cfg)
    params = jax.tree_util.tree_map(lambda x: x[0], state.news_params)
    table = jnp.asarray(states)
    ids = jnp.asarray(np.random.default_rng(5).integers(0, NUM_NEWS, 40), jnp.int32)

    def loss(p, batch_of_one):
        vecs = _encode_gathered(model, p, table, ids, batch_of_one=batch_of_one)
        assert vecs.shape == (40, cfg.model.news_dim)
        return jnp.sum(vecs * jnp.cos(jnp.arange(vecs.size).reshape(vecs.shape)))

    plain, g_plain = jax.value_and_grad(loss)(params, False)
    lifted, g_lifted = jax.value_and_grad(loss)(params, True)
    np.testing.assert_allclose(lifted, plain, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_lifted),
                    jax.tree_util.tree_leaves(g_plain)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
