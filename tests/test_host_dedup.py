"""The joint step's dedup, host half and device half (train/step.py:
``host_news_dedup`` / ``_batch_news_vecs``; train/trainer.py:
``_choose_encode_rows``): each distinct news of a client-step is gathered
and encoded once, at a size R the round loop derives from the traffic.

The step fed the host's entries must be the step without them (device-side
``jnp.unique`` at the slot count) to float32 reassociation; a step whose
count exceeds R is served at the full size, exactly; R is chosen once a run
and derived again only after a round that needed the full size.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax

from fedrec_tpu.fed import get_strategy
from fedrec_tpu.obs import MetricsRegistry, Tracer, set_registry, set_tracer
from fedrec_tpu.parallel import client_mesh, shard_batch
from fedrec_tpu.train import build_fed_train_step
from fedrec_tpu.train import step as step_mod
from fedrec_tpu.train.step import (
    NEWS_INVERSE,
    NEWS_ROWS,
    encode_rows_for,
    host_news_dedup,
    most_distinct_news,
)
from fedrec_tpu.train.trainer import Trainer

from test_train import _batch_dict, make_setup, small_cfg

NUM_NEWS = 256      # more than the 120 slots of a client-step: the slot count binds
SLOTS = 8 * (5 + 10)


@pytest.fixture()
def fresh_obs():
    reg, tr = MetricsRegistry(), Tracer()
    old_reg, old_tr = set_registry(reg), set_tracer(tr)
    try:
        yield reg, tr
    finally:
        set_registry(old_reg)
        set_tracer(old_tr)


# ------------------------------------------------------------- host half
@pytest.mark.parametrize("most,full,rows", [
    (2_738, 3_520, 2_880),      # fed8.b64's largest count of a round (PERF.md)
    (13_970, 28_160, 14_400),   # central.b512's
    (3_500, 3_520, 3_520),      # room would pass the full size: held to it
    (40, 48, 48),               # catalog smaller than the smallest size
    (10, 1_000, 64),
])
def test_encode_rows_have_room_and_tile_well(most, full, rows):
    got = encode_rows_for(most, full)
    assert got == rows
    assert most <= got <= full
    assert got == full or (
        got % step_mod.ENCODE_ROW_QUANTUM == step_mod.ENCODE_ROW_RESIDUE
    )


def test_host_entries_hold_each_distinct_id_once():
    rng = np.random.default_rng(3)
    cand = rng.integers(0, 40, (3, 4, 5)).astype(np.int32)
    his = rng.integers(0, 40, (3, 4, 10)).astype(np.int32)
    most = most_distinct_news(cand, his)
    entries, got_most = host_news_dedup(cand, his, most + 2, 1_000)
    rows, inv = entries[NEWS_ROWS], entries[NEWS_INVERSE]
    assert got_most == most and rows.shape == (3, most + 2)
    assert rows.dtype == inv.dtype == np.int32
    for c in range(3):
        ids = np.concatenate([cand[c].reshape(-1), his[c].reshape(-1)])
        n = np.unique(ids).size
        np.testing.assert_array_equal(rows[c, :n], np.unique(ids))
        assert not rows[c, n:].any()                       # padded with id 0
        np.testing.assert_array_equal(rows[c][inv[c]], ids)  # slot order kept


@pytest.mark.parametrize("n_news,full", [(1_000, 60), (48, 48)],
                         ids=["slots-bind", "catalog-binds"])
def test_a_count_above_the_rows_takes_the_full_size(n_news, full):
    rng = np.random.default_rng(4)
    cand = rng.integers(0, 48, (2, 4, 5)).astype(np.int32)
    his = rng.integers(0, 48, (2, 4, 10)).astype(np.int32)
    most = most_distinct_news(cand, his)
    entries, _ = host_news_dedup(cand, his, most - 1, n_news)
    assert entries[NEWS_ROWS].shape == (2, full)
    for c in range(2):
        ids = np.concatenate([cand[c].reshape(-1), his[c].reshape(-1)])
        np.testing.assert_array_equal(
            entries[NEWS_ROWS][c][entries[NEWS_INVERSE][c]], ids
        )


# ----------------------------------------------------------- device half
def _step_case(layout: str):
    """(cfg, mesh): a cohort of 4 on one device, the single worker, one
    client a device over a 4-device clients mesh."""
    clients, devices = {
        "cohort-of-4": (4, 1), "single-worker": (1, 1), "clients-mesh-4": (4, 4),
    }[layout]
    cfg = small_cfg(optim__user_lr=3e-3, optim__news_lr=3e-3)
    cfg.fed.num_clients = clients
    return cfg, client_mesh(clients, max_devices=devices)


def _assert_same_trees(got, want, tol: float) -> None:
    """Leaf by leaf. Left out, as in test_step_levers and the chip
    comparison (PERF.md section 2): the biases that shift every logit of a
    softmax alike (the additive-attention ``att_fc2`` bias, the
    self-attention key bias). Their true gradient is zero, so Adam's
    g/(sqrt(g^2)+eps) turns reassociation noise into a step of the size of
    the learning rate."""
    for (kp, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(got),
        jax.tree_util.tree_leaves_with_path(want),
    ):
        path = jax.tree_util.keystr(kp)
        if path.endswith(("['att_fc2']['bias']", "['w_k']['bias']")):
            continue
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=tol, atol=tol, err_msg=path
        )


def _assert_same_step(got, want):
    (s_got, m_got), (s_want, m_want) = got, want
    np.testing.assert_allclose(
        np.asarray(m_got["loss"]), np.asarray(m_want["loss"]), rtol=1e-6
    )
    # new parameters, and the gradients as Adam took them (its moments)
    for name in ("user_params", "news_params", "opt_user", "opt_news"):
        _assert_same_trees(getattr(s_got, name), getattr(s_want, name), 1e-6)


@pytest.mark.parametrize("rows", ["below-slot-count", "exceeded"])
@pytest.mark.parametrize(
    "layout", ["cohort-of-4", "single-worker", "clients-mesh-4"]
)
def test_step_fed_host_entries_equals_step_without(layout, rows):
    cfg, mesh = _step_case(layout)
    k = cfg.fed.num_clients
    assert mesh.size == (4 if layout == "clients-mesh-4" else 1)
    _, batcher, token_states, model, st0, _ = make_setup(cfg, num_news=NUM_NEWS)
    st0 = jax.tree_util.tree_map(np.asarray, st0)   # the step donates its state
    batch = _batch_dict(next(batcher.epoch_batches_sharded(k, 0)))
    most = most_distinct_news(batch["candidates"], batch["history"])
    if rows == "below-slot-count":
        entries, _ = host_news_dedup(
            batch["candidates"], batch["history"], most + 3, NUM_NEWS
        )
        assert most < entries[NEWS_ROWS].shape[1] < SLOTS
    else:   # some client's count exceeds R: served at the full size
        entries, _ = host_news_dedup(
            batch["candidates"], batch["history"], most - 1, NUM_NEWS
        )
        assert entries[NEWS_ROWS].shape[1] == SLOTS

    step = build_fed_train_step(
        model, cfg, get_strategy("grad_avg"), mesh, mode="joint"
    )
    want = step(st0, shard_batch(mesh, batch), token_states)
    got = step(st0, shard_batch(mesh, {**batch, **entries}), token_states)
    _assert_same_step(got, want)


def test_entries_that_no_longer_describe_the_slots_are_not_used():
    """A caller that re-cuts ``candidates`` / ``history`` under the entries
    (``chipbench/tests``' half-batch fault folds every key along axis 1) is
    served by the device-side dedup, which is exact for any batch;
    ``batch_host_dedup`` is the one rule, for the step and for the round
    loop's ``dispatch`` span. Given outright to ``_batch_news_vecs``, such
    entries are refused when the step is traced."""
    cfg, mesh = _step_case("cohort-of-4")
    _, batcher, token_states, model, st0, _ = make_setup(cfg, num_news=NUM_NEWS)
    st0 = jax.tree_util.tree_map(np.asarray, st0)
    batch = _batch_dict(next(batcher.epoch_batches_sharded(4, 0)))
    entries, most = host_news_dedup(
        batch["candidates"], batch["history"], SLOTS - 8, NUM_NEWS
    )
    whole = {**batch, **entries}
    assert step_mod.batch_host_dedup(whole)[0].shape == (4, SLOTS - 8)
    assert step_mod.batch_host_dedup(batch) is None
    half = batch["labels"].shape[1] // 2
    recut = {
        key: np.concatenate([x[:, :half], x[:, :half]], axis=1)
        for key, x in whole.items()
    }
    assert step_mod.batch_host_dedup(recut) is None

    step = build_fed_train_step(
        model, cfg, get_strategy("grad_avg"), mesh, mode="joint"
    )
    bare = {key: recut[key] for key in batch}
    want = step(st0, shard_batch(mesh, bare), token_states)
    got = step(st0, shard_batch(mesh, recut), token_states)
    _assert_same_step(got, want)

    with pytest.raises(ValueError, match="news slots"):
        step_mod._batch_news_vecs(
            model, st0.news_params, token_states,
            batch["candidates"][0], batch["history"][0],
            host_dedup=(entries[NEWS_ROWS][0], entries[NEWS_INVERSE][0][:-1]),
        )


# -------------------------------------------------------------- round loop
def _trainer(tmp_path, num_news=NUM_NEWS, **over):
    cfg = small_cfg(optim__user_lr=3e-3, optim__news_lr=3e-3, **over)
    cfg.model.text_encoder_mode = "head"
    cfg.fed.strategy = "param_avg"
    cfg.fed.num_clients = 4
    cfg.train.snapshot_dir = str(tmp_path / "snap")
    data, _, token_states, _, _, _ = make_setup(
        cfg, num_news=num_news, num_train=256, seed=0
    )
    return Trainer(cfg, data, np.asarray(token_states))


def _tight_rows(monkeypatch, steps: int) -> None:
    """R with no room, from the first ``steps`` steps alone: a later step of
    the round then exceeds it."""
    monkeypatch.setattr(step_mod, "ENCODE_ROOM", 0.0)
    monkeypatch.setattr(step_mod, "ENCODE_ROW_QUANTUM", 1)
    monkeypatch.setattr(step_mod, "ENCODE_ROW_RESIDUE", 0)
    monkeypatch.setattr(Trainer, "ENCODE_ROWS_STEPS", steps)


def test_round_loop_spans_and_series(tmp_path, fresh_obs):
    reg, tracer = fresh_obs
    t = _trainer(tmp_path)
    assert t._encode_rows is None       # chosen at the first round, not before
    t.train_round(0)
    rows = t._encode_rows
    assert 0 < rows <= SLOTS
    dispatches = [e for e in tracer.events_since(0)
                  if e.get("name") == "dispatch" and e.get("ph") == "X"]
    assert dispatches
    assert all(e["args"]["rows"] == rows and e["args"]["slots"] == SLOTS
               for e in dispatches)
    assert reg.get("train.encode_rows").value() == rows
    assert reg.get("train.encode_full_size_steps_total").value() == 0


def test_rows_never_exceed_a_catalog_smaller_than_the_slots(tmp_path, fresh_obs):
    t = _trainer(tmp_path, num_news=64)
    t.train_round(0)
    assert t._encode_rows <= 64 < SLOTS
    batch = next(iter(t._epoch_batch_iter(0)))
    assert batch[NEWS_ROWS].shape == (4, t._encode_rows)
    assert batch[NEWS_INVERSE].shape == (4, SLOTS)


def test_two_rounds_compile_one_step_program(tmp_path, fresh_obs):
    reg, _ = fresh_obs
    t = _trainer(tmp_path)
    t.train_round(0)
    t.train_round(1)
    assert reg.get("xla.compiles_total").value(fn="train_step") == 1
    assert reg.get("train.encode_full_size_steps_total").value() == 0


def test_round_that_needs_the_full_size_is_exact_and_rederives(
    tmp_path, fresh_obs, monkeypatch
):
    reg, _ = fresh_obs
    want = _trainer(tmp_path / "device")
    want._host_dedup = False            # the step dedups at the slot count
    loss_want = want.train_round(0).train_loss

    _tight_rows(monkeypatch, steps=1)
    t = _trainer(tmp_path / "host")
    counts = [
        most_distinct_news(b.candidates, b.history)
        for b in t._epoch_batches_source(0)
    ]
    assert max(counts) > counts[0], "the fixture must outgrow its first step"
    loss = t.train_round(0).train_loss
    served_full = sum(c > counts[0] for c in counts)
    assert reg.get("train.encode_full_size_steps_total").value() == served_full
    assert t._encode_rows == max(counts)    # derived again from this round's
    assert reg.get("train.encode_rows").value() == max(counts)
    np.testing.assert_allclose(loss, loss_want, rtol=1e-6)
    _assert_same_trees(
        (t.state.user_params, t.state.news_params),
        (want.state.user_params, want.state.news_params),
        1e-5,
    )


def test_prefetch_feeds_identical_batches(tmp_path, fresh_obs):
    inline = _trainer(tmp_path / "p0", data__prefetch_batches=0)
    ahead = _trainer(tmp_path / "p2", data__prefetch_batches=2)
    for t in (inline, ahead):
        t._choose_encode_rows(0)
    assert inline._encode_rows == ahead._encode_rows
    counts_a, counts_b = [], []
    a = list(inline._epoch_batch_iter(0, distinct=counts_a))
    it = ahead._epoch_batch_iter(0, distinct=counts_b)
    try:
        b = list(it)
    finally:
        it.close()
    assert len(a) == len(b) > 0 and counts_a == counts_b
    for x, y in zip(a, b):
        assert set(x) == set(y) >= {NEWS_ROWS, NEWS_INVERSE}
        for key in x:
            np.testing.assert_array_equal(x[key], y[key])
