"""The one round loop, held to a hand-written loop of its own parts.

``Trainer.train_round`` / ``Trainer.run`` feed one batch a dispatch through
``build_fed_train_step`` and end a round with ``build_param_sync``. The
reference here is that loop written out by hand: a Python ``for`` over the
epoch's batches, then the sync with the round's weights. The Trainer must
match it loss for loss and leaf for leaf in every composition the two scan
dispatch forms' tests used to cover (``tests/test_scan.py``, removed with
the forms: the Trainer's trajectory was held in tier-1 only through them;
CHANGES.md, PR 31, lists the removed tests by name).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedrec_tpu.obs import (
    MetricsRegistry,
    Tracer,
    TrainingHealthError,
    set_registry,
    set_tracer,
)
from fedrec_tpu.obs.health import HealthMonitor
from fedrec_tpu.parallel import client_mesh, shard_fed_batch
from fedrec_tpu.train import (
    build_fed_train_step,
    build_news_update_step,
    build_param_sync,
    encode_all_news,
)
from fedrec_tpu.train.trainer import RoundRecovery, Trainer

from test_train import _batch_dict, make_setup, small_cfg


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _trainer(tmp_path, tag="t", num_train=128, mesh=None, tracer=None, **over):
    """A small ``Trainer`` on the shared synthetic fixture. Joint mode and
    ``param_avg`` unless overridden; no evaluation, no snapshots."""
    set_registry(MetricsRegistry())
    set_tracer(tracer or Tracer())
    cfg = small_cfg(
        optim__user_lr=3e-3, optim__news_lr=3e-3,
        model__text_encoder_mode="head", fed__strategy="param_avg",
        fed__rounds=2, train__eval_every=1000,
        train__snapshot_dir=str(tmp_path / tag),
    )
    for k, v in over.items():
        *sections, key = k.split("__")
        node = cfg
        for section in sections:
            node = getattr(node, section)
        setattr(node, key, v)
    data, _, token_states, _, _, _ = make_setup(cfg, num_train=num_train, seed=0)
    return Trainer(cfg, data, np.asarray(token_states), mesh=mesh)


def _reference(t, rounds, weights=None):
    """The hand-written loop over ``t``'s own parts, from ``t``'s state as
    it stands: for each round, for each local epoch, one
    ``build_fed_train_step`` dispatch a batch of the epoch (decoupled mode:
    then the epoch-end ``news_update`` and the table it returns), then
    ``build_param_sync`` with the round's weights. Returns each round's
    per-step ``mean_loss`` rows and the final state. Call before ``t``
    trains: its first step donates the state's buffers."""
    cfg = t.cfg
    step = build_fed_train_step(t.model, cfg, t.strategy, t.mesh, mode=t.mode)
    sync = build_param_sync(cfg, t.mesh, t.strategy)
    news_update = build_news_update_step(t.model, cfg, t.mesh, t.strategy)
    state = jax.tree_util.tree_map(jnp.asarray, t._host_state())
    if t.mode == "decoupled":
        p0 = jax.tree_util.tree_map(lambda x: x[0], state.news_params)
        table = encode_all_news(t.model, p0, t.token_states)
    else:
        table = t.token_states
    losses = []
    for r in range(rounds):
        rows = []
        for e in range(cfg.fed.local_epochs):
            epoch = r * cfg.fed.local_epochs + e
            for b in t.batcher.epoch_batches_sharded(cfg.fed.num_clients, epoch):
                state, m = step(
                    state, shard_fed_batch(t.mesh, _batch_dict(b), cfg), table
                )
                rows.append(np.asarray(m["mean_loss"]))
            if t.mode == "decoupled":
                state, tables = news_update(state, t.token_states)
                table = jax.tree_util.tree_map(lambda x: x[0], tables)
        if t.strategy.sync_params_every_round:
            w = np.ones(cfg.fed.num_clients, np.float32) if weights is None \
                else weights[r]
            state = sync(state, jnp.asarray(w))
            if t.mode == "decoupled":
                p0 = jax.tree_util.tree_map(lambda x: x[0], state.news_params)
                table = encode_all_news(t.model, p0, t.token_states)
        losses.append(np.stack(rows))
    return losses, state


def _assert_state_matches(ref_state, t, rtol=1e-5, atol=1e-6):
    for a, b in zip(_leaves(ref_state.user_params), _leaves(t.state.user_params)):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol)
    for a, b in zip(_leaves(ref_state.news_params), _leaves(t.state.news_params)):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol)


def _assert_round_losses(ref_losses, results):
    """Each round's reported loss is the flat mean of the reference's
    (steps, clients) cells of that round."""
    np.testing.assert_allclose(
        [r.train_loss for r in results],
        [rows.mean() for rows in ref_losses], rtol=1e-5,
    )


def _step_metrics(t, plant=None):
    """Capture every train step's whole ``metrics`` (device arrays) as ``t``
    dispatches it; ``plant(call_index, metrics)`` may replace them first."""
    rows = []
    inner = t.train_step

    def recording(state, batch, table):
        state, metrics = inner(state, batch, table)
        if plant is not None:
            metrics = plant(len(rows), metrics)
        rows.append(metrics)
        return state, metrics

    t.train_step = recording
    return rows


@pytest.mark.parametrize("strategy,max_dev,user_tower", [
    ("param_avg", 8, "attn"),   # one client a device: the per-epoch FedAvg loop
    ("param_avg", 4, "attn"),   # cohorts of two
    ("grad_avg", 8, "attn"),    # per-step gradient averaging, no round-end sync
    ("grad_avg", 4, "attn"),    # the same in cohorts of two
    ("local", 8, "attn"),       # no collective at all
    ("param_avg", 4, "gru"),    # cohorts of two under the GRU user tower
])
def test_train_round_matches_hand_written_loop(tmp_path, strategy, max_dev, user_tower):
    """Keeps ``test_scan.py::test_scan_matches_per_step_loop[grad_avg-8 /
    grad_avg-4 / local-8]``, ``::test_round_scan_matches_host_round_loop
    [param_avg-8 / param_avg-4 / grad_avg-8]`` and the two ``*_gru_cohorts_
    compose`` tests: two rounds of ``Trainer.train_round`` equal the
    hand-written step loop + sync, losses step for step, parameters leaf
    for leaf."""
    over = {} if user_tower == "attn" else {"model__user_tower": user_tower}
    t = _trainer(
        tmp_path, mesh=client_mesh(8, max_devices=max_dev),
        fed__strategy=strategy, **over,
    )
    ref_losses, ref_state = _reference(t, rounds=2)
    rows = _step_metrics(t)
    results = [t.train_round(r) for r in range(2)]
    got = np.stack([np.asarray(m["mean_loss"]) for m in rows])
    np.testing.assert_allclose(
        np.concatenate(ref_losses), got, rtol=1e-5, atol=1e-6
    )
    _assert_round_losses(ref_losses, results)
    _assert_state_matches(ref_state, t)


def test_round_that_drops_clients_by_weight(tmp_path, monkeypatch):
    """Keeps the participation half of ``test_scan.py::
    test_round_scan_matches_host_round_loop``: round 1 drops clients 0-2 by
    weight, and the Trainer's round-end sync equals ``build_param_sync``
    with that round's weights (a dropped client adopts the others' mean)."""
    weights = np.ones((3, 8), np.float32)
    weights[1, :3] = 0.0
    t = _trainer(tmp_path, fed__rounds=3)
    monkeypatch.setattr(t, "_round_weights", lambda r: weights[r].copy())
    ref_losses, ref_state = _reference(t, rounds=3, weights=weights)
    results = [t.train_round(r) for r in range(3)]
    _assert_round_losses(ref_losses, results)
    _assert_state_matches(ref_state, t)
    # and the weights mattered: the all-ones sync ends elsewhere
    t2 = _trainer(tmp_path, tag="ones", fed__rounds=3)
    _, ones_state = _reference(t2, rounds=3)
    assert any(
        not np.allclose(a, b, rtol=1e-5, atol=1e-6)
        for a, b in zip(_leaves(ones_state.user_params),
                        _leaves(ref_state.user_params))
    )


def test_participation_mask_reaches_the_sync(tmp_path):
    """The unpatched weights path of the case above: under
    ``fed.participation=0.5`` the Trainer's round equals the hand-written
    loop synced with ``participation_mask`` drawn from the round's key."""
    from fedrec_tpu.fed.strategies import participation_mask

    t = _trainer(tmp_path, fed__participation=0.5)
    weights = np.stack([
        np.asarray(participation_mask(
            jax.random.PRNGKey(hash((t.cfg.train.seed, r)) & 0x7FFFFFFF), 8, 0.5
        ), np.float32)
        for r in range(2)
    ])
    assert 0 < weights.sum() < weights.size
    ref_losses, ref_state = _reference(t, rounds=2, weights=weights)
    results = [t.train_round(r) for r in range(2)]
    _assert_round_losses(ref_losses, results)
    _assert_state_matches(ref_state, t)


def test_decoupled_round_with_epoch_end_news_update(tmp_path):
    """Keeps ``test_scan.py::test_scan_decoupled_accumulates_like_loop`` and
    the decoupled half of its Trainer test of the modes rounds-in-jit
    refused (the mode that only this loop ever ran): the decoupled step
    accumulates news gradients over the epoch, the epoch-end
    ``news_update`` replays them through the head, and the round matches
    the hand-written loop."""
    # SGD: Adam would turn the ulp between the Trainer's sharded first
    # table encode and the reference's eager one into 1e-3 on the attention
    # pooling's scalar biases, whose true gradient is zero
    t = _trainer(
        tmp_path, model__text_encoder_mode="table", fed__strategy="local",
        optim__optimizer="sgd", optim__user_lr=0.1, optim__news_lr=0.1,
    )
    assert t.mode == "decoupled"
    ref_losses, ref_state = _reference(t, rounds=2)
    before = _leaves(t._host_state().news_params)
    results = [t.train_round(r) for r in range(2)]
    _assert_round_losses(ref_losses, results)
    _assert_state_matches(ref_state, t)
    np.testing.assert_array_equal(np.asarray(t.state.news_grad_accum), 0.0)
    # the news head did train, and only news_update trains it in this mode
    assert any(
        not np.array_equal(a, b)
        for a, b in zip(before, _leaves(t.state.news_params))
    )


def test_seq_parallel_round(tmp_path):
    """Keeps ``test_scan.py::test_scan_seq_parallel``: on the (clients, seq)
    mesh, where the step dedups on the device, the Trainer's round equals
    the hand-written loop."""
    t = _trainer(
        tmp_path, fed__num_clients=4, fed__seq_shards=2,
        fed__strategy="grad_avg",
    )
    assert not t._host_dedup
    ref_losses, ref_state = _reference(t, rounds=1)
    rows = _step_metrics(t)
    t.train_round(0)
    np.testing.assert_allclose(
        ref_losses[0], np.stack([np.asarray(m["mean_loss"]) for m in rows]),
        rtol=1e-6, atol=1e-7,
    )
    _assert_state_matches(ref_state, t)


def test_round_loss_is_flat_mean_over_step_client_cells(tmp_path):
    """Keeps the fixture of ``test_scan.py``'s Trainer test of the epoch
    scan against per-batch dispatch (6.5 batches a client set: the last
    step is short and padded by wrap-around): the round's loss is the flat
    mean over every (step, client) cell, the short step's cells counting like any other's."""
    t = _trainer(tmp_path, num_train=6 * 64 + 32, data__drop_remainder=False)
    ref_losses, _ = _reference(t, rounds=2)
    assert ref_losses[0].shape == (7, 8)
    results = [t.train_round(r) for r in range(2)]
    for r, res in enumerate(results):
        np.testing.assert_allclose(
            res.train_loss, ref_losses[r].reshape(-1).mean(), rtol=1e-6
        )


def test_run_same_with_and_without_prefetch(tmp_path):
    """Keeps ``test_scan.py``'s two Trainer tests of rounds-in-jit against
    the round loop (trajectory, and where a chunk had to end): ``run()``
    with the
    producer thread (``data.prefetch_batches=2``) equals ``run()`` without,
    both equal the hand-written loop, evaluation lands on the
    ``eval_every=2`` rounds and ``save_every=2`` leaves the same snapshot
    directory listing, the mid-run snapshot included."""
    def make(prefetch, tag):
        return _trainer(
            tmp_path, tag=tag, fed__rounds=4, data__prefetch_batches=prefetch,
            train__save_every=2, train__eval_every=2,
        )

    t0 = make(0, "inline")
    ref_losses, ref_state = _reference(t0, rounds=4)
    inline = t0.run()
    t2 = make(2, "prefetch")
    ahead = t2.run()
    assert [h.round_idx for h in inline] == [h.round_idx for h in ahead] == [0, 1, 2, 3]
    _assert_round_losses(ref_losses, inline)
    np.testing.assert_array_equal(
        [h.train_loss for h in inline], [h.train_loss for h in ahead]
    )
    _assert_state_matches(ref_state, t0)
    for a, b in zip(_leaves(t0.state), _leaves(t2.state)):
        np.testing.assert_array_equal(a, b)
    # eval cadence: metrics on exactly rounds 1 and 3, the same values
    assert [bool(h.val_metrics) for h in inline] == [False, True, False, True]
    for a, b in zip(inline, ahead):
        assert a.val_metrics == b.val_metrics
    # checkpoint cadence: the same listing, the round-1 snapshot in it
    listing = sorted(p.name for p in (tmp_path / "inline").iterdir())
    assert "1" in listing and "3" in listing
    assert listing == sorted(p.name for p in (tmp_path / "prefetch").iterdir())


# ------------------------------------------------------------ the round's end
def _per_array_round_end(rows):
    """The round's end as the round loop read it before PR 32, written out:
    one ``np.asarray`` a device array, stacked over the steps. Returns the
    round's loss and the monitor's ``(1, steps, clients)`` health arrays."""
    mean_cells = np.stack([np.asarray(m["mean_loss"]) for m in rows]).reshape(-1)
    loss_cells = np.stack([np.asarray(m["loss"]) for m in rows]).reshape(-1)
    if np.isfinite(mean_cells).all():
        loss = float(mean_cells.mean())
    else:
        finite = loss_cells[np.isfinite(loss_cells)]
        loss = float(finite.mean()) if finite.size else float("nan")
    health = [{k: v for k, v in m.items() if k.startswith("health.")} for m in rows]
    arrays = {
        k: np.stack([np.asarray(r[k]) for r in health])[None] for k in health[0]
    }
    return loss, arrays


def _health_instruments(registry):
    return {
        name: m["values"]
        for name, m in registry.snapshot()["metrics"].items()
        if name.startswith("health.")
    }


def _round_end_spans(t):
    return [e for e in t.tracer.events() if e.get("name") == "round_end"]


@pytest.mark.parametrize("strategy,max_dev,user_tower", [
    ("param_avg", 8, "attn"), ("param_avg", 4, "attn"), ("grad_avg", 8, "attn"),
    ("grad_avg", 4, "attn"), ("local", 8, "attn"), ("param_avg", 4, "gru"),
])
def test_round_end_in_one_read_equals_the_per_array_reads(
    tmp_path, strategy, max_dev, user_tower
):
    """Two rounds: ``RoundResult.train_loss`` and every ``health.*``
    instrument equal, bit for bit, what one ``np.asarray`` a device array
    gives on the same step outputs, digested by a monitor of its own."""
    over = {} if user_tower == "attn" else {"model__user_tower": user_tower}
    t = _trainer(
        tmp_path, mesh=client_mesh(8, max_devices=max_dev),
        fed__strategy=strategy, **over,
    )
    rows = _step_metrics(t)
    ref = HealthMonitor(t.cfg.obs.health, MetricsRegistry())
    for r in range(2):
        del rows[:]
        result = t.train_round(r)
        loss, arrays = _per_array_round_end(rows)
        assert result.train_loss == loss            # the same float
        assert ref.check(r, arrays, [loss]) is None
        got, want = _health_instruments(t.registry), _health_instruments(ref.registry)
        # the histograms hold every (step, client) cell, the gauge the last
        assert want["health.grad_norm"][0]["count"] == (r + 1) * len(rows) * 8
        assert want["health.param_norm"][0]["value"] > 0
        assert got == want


def _spy_host_rows(t, monkeypatch):
    """Record the leaf types ``_check_health`` and ``_publish_routing`` are
    handed: what they read must already be on the host."""
    seen = {"health": [], "routing": []}
    check, publish = t._check_health, t._publish_routing

    def check_health(round_idx, health_rows=None, round_losses=()):
        seen["health"].append([type(v) for row in health_rows for v in row.values()])
        return check(round_idx, health_rows=health_rows, round_losses=round_losses)

    def publish_routing(rows):
        seen["routing"].append([type(v) for row in rows for v in row.values()])
        return publish(rows)

    monkeypatch.setattr(t, "_check_health", check_health)
    monkeypatch.setattr(t, "_publish_routing", publish_routing)
    return seen


def _sparse_trunk_trainer(tmp_path, tracer=None):
    from test_sparse_trunk import trunk_cfg, trunk_data

    set_registry(MetricsRegistry())
    set_tracer(tracer or Tracer())
    cfg = trunk_cfg(1)
    return Trainer(cfg, trunk_data(cfg), None, mesh=client_mesh(1, max_devices=1))


@pytest.mark.parametrize("make,kept_keys", [
    (lambda tmp: _trainer(tmp), 6),                   # two losses + four health.*
    (lambda tmp: _trainer(tmp, fed__strategy="grad_avg",
                          mesh=client_mesh(8, max_devices=4)), 6),
    (_sparse_trunk_trainer, 9),                       # and the three moe.*
], ids=["param_avg", "grad_avg-cohorts", "sparse-expert-trunk"])
def test_every_round_ends_in_one_read_of_host_arrays(tmp_path, monkeypatch, make, kept_keys):
    """Every round emits exactly one ``round_end`` span, inside its
    ``fed_round``, with ``reads == 1`` and ``arrays == steps x kept keys``;
    the health digest and the routing counters are handed ``numpy.ndarray``
    leaves only (no read of a device array is left to them); the histogram
    ``train.round_span_seconds{span="round_end"}`` observes the span's
    interval once a round."""
    t = make(tmp_path)
    seen = _spy_host_rows(t, monkeypatch)
    steps = _step_metrics(t)
    rounds = 2
    for r in range(rounds):
        t.train_round(r)
    per_round = len(steps) // rounds
    spans = _round_end_spans(t)
    assert [e["args"] for e in spans] == [
        {"round": r, "arrays": per_round * kept_keys, "reads": 1}
        for r in range(rounds)
    ]
    fed_rounds = [e for e in t.tracer.events() if e.get("name") == "fed_round"]
    for end, whole in zip(spans, fed_rounds):
        assert whole["ts"] <= end["ts"]
        assert end["ts"] + end["dur"] <= whole["ts"] + whole["dur"]
    assert len(seen["health"]) == rounds
    for kinds in seen["health"] + seen["routing"]:
        assert kinds and set(kinds) == {np.ndarray}
    assert len(seen["routing"]) == (rounds if kept_keys == 9 else 0)
    hist = t.registry.snapshot()["metrics"]["train.round_span_seconds"]["values"]
    (cell,) = [c for c in hist if c["labels"] == {"span": "round_end"}]
    assert cell["count"] == rounds
    assert cell["sum"] == pytest.approx(sum(e["dur"] for e in spans) / 1e6)


def _nonfinite(metrics, client):
    """``metrics`` with a non-finite loss for ``client``: the cell, the
    in-graph mean it poisons, and the sentry's flag."""
    return {
        **metrics,
        "loss": metrics["loss"].at[client].set(jnp.nan),
        "mean_loss": jnp.full_like(metrics["mean_loss"], jnp.nan),
        "health.nonfinite": metrics["health.nonfinite"].at[client].set(1),
    }


@pytest.mark.parametrize("recover,raised", [
    (False, TrainingHealthError), (True, RoundRecovery),
], ids=["abort", "recover"])
def test_planted_nonfinite_loss_raises_in_its_own_round(tmp_path, recover, raised):
    """A non-finite loss planted at step 2 of round 1 is digested in round
    1: ``train_round(1)`` itself raises (``TrainingHealthError``, or
    ``RoundRecovery`` under ``fed.robust.recover``), naming the step and
    the client, not a round later; round 0 ends clean."""
    t = _trainer(tmp_path, num_train=256, fed__rounds=3, fed__robust__recover=recover)
    at = {}
    steps = _step_metrics(
        t, lambda i, m: _nonfinite(m, client=3) if i == at.get("call") else m
    )
    t.train_round(0)
    per_round = len(steps)
    assert per_round > 3                            # step 2 is not the round's last
    at["call"] = per_round + 2
    with pytest.raises(raised) as err:
        t.train_round(1)
    assert len(steps) == 2 * per_round              # the whole round ran first
    if recover:
        trigger = err.value.trigger
        assert (trigger["kind"], trigger["round"], trigger["step"], trigger["client"]) \
            == ("nonfinite", 1, 2, 3)
    else:
        assert "[nonfinite] at round 1 step 2 client 3" in str(err.value)
    assert t.registry.counter("health.nonfinite_steps_total").value() == 1
    # the raising round closed its span with the error's name on it
    assert _round_end_spans(t)[-1]["args"]["error"] == raised.__name__


def test_step_metrics_are_what_the_round_end_reads(tmp_path):
    """The step's program is not this loop's to change: under the paper's
    options (8 clients, ``param_avg``, joint mode, bfloat16, sentry on) the
    lowered step returns the six arrays the round's end gathers, one float32
    (clients,) vector each and the int32 sentinel, and nothing else."""
    t = _trainer(tmp_path, model__dtype="bfloat16")
    rows = _step_metrics(t)
    t.train_round(0)
    avals = {k: (v.shape, str(v.dtype)) for k, v in rows[0].items()}
    vec = ((8,), "float32")
    assert avals == {
        "loss": vec, "mean_loss": vec, "health.grad_norm": vec,
        "health.update_norm": vec, "health.param_norm": vec,
        "health.nonfinite": ((8,), "int32"),
    }
