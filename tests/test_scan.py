"""Epoch-in-jit: lax.scan over train steps == the per-step dispatch loop.

The scan wraps the SAME ``_build_local_step`` closure as the per-batch
step, so the trajectories must match step for step — this is the guard
that keeps the two programs from diverging. Dispatch-amortization itself
is a chip property (benched as ``scan_samples_per_sec``); here we pin
semantics on the 8-device CPU mesh.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from fedrec_tpu.fed import get_strategy
from fedrec_tpu.parallel import client_mesh, shard_batch
from fedrec_tpu.train import (
    build_fed_train_scan,
    build_fed_train_step,
    encode_all_news,
    shard_scan_batches,
    stack_batches,
)

from test_train import make_setup, small_cfg, _batch_dict


def _collect_batches(batcher, n_clients, n_steps):
    out = []
    for b in batcher.epoch_batches_sharded(n_clients, 0):
        out.append(_batch_dict(b))
        if len(out) >= n_steps:
            break
    return out


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("strategy,max_dev", [
    ("grad_avg", 8),   # k=1
    ("grad_avg", 4),   # k=2 cohorts
    ("local", 8),
])
def test_scan_matches_per_step_loop(strategy, max_dev):
    cfg = small_cfg(optim__user_lr=3e-3, optim__news_lr=3e-3)
    mesh = client_mesh(8, max_devices=max_dev)
    data, batcher, token_states, model, stacked0, _ = make_setup(cfg, seed=0)
    batches = _collect_batches(batcher, 8, 4)

    step = build_fed_train_step(model, cfg, get_strategy(strategy), mesh, mode="joint")
    st_loop = stacked0
    loop_losses = []
    for b in batches:
        st_loop, m = step(st_loop, shard_batch(mesh, b), token_states)
        loop_losses.append(np.asarray(m["mean_loss"]))

    # fresh identical initial state for the scan (the loop donated its own)
    _, _, _, _, stacked0b, _ = make_setup(cfg, seed=0)
    scan = build_fed_train_scan(model, cfg, get_strategy(strategy), mesh, mode="joint")
    st_scan, ms = scan(
        stacked0b, shard_scan_batches(mesh, stack_batches(batches), cfg), token_states
    )
    scan_losses = np.asarray(ms["mean_loss"])

    np.testing.assert_allclose(
        np.stack(loop_losses), scan_losses, rtol=1e-6, atol=1e-7
    )
    for a, b in zip(_leaves(st_loop.user_params), _leaves(st_scan.user_params)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    for a, b in zip(_leaves(st_loop.news_params), _leaves(st_scan.news_params)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_scan_decoupled_accumulates_like_loop():
    cfg = small_cfg(optim__user_lr=3e-3, optim__news_lr=3e-3)
    mesh = client_mesh(8)
    data, batcher, token_states, model, stacked0, _ = make_setup(cfg, seed=0)
    p0 = jax.tree_util.tree_map(lambda x: x[0], stacked0.news_params)
    table = encode_all_news(model, p0, token_states)
    batches = _collect_batches(batcher, 8, 3)

    step = build_fed_train_step(model, cfg, get_strategy("local"), mesh, mode="decoupled")
    st_loop = stacked0
    for b in batches:
        st_loop, _ = step(st_loop, shard_batch(mesh, b), table)

    _, _, _, _, stacked0b, _ = make_setup(cfg, seed=0)
    scan = build_fed_train_scan(model, cfg, get_strategy("local"), mesh, mode="decoupled")
    st_scan, _ = scan(
        stacked0b, shard_scan_batches(mesh, stack_batches(batches), cfg), table
    )
    np.testing.assert_allclose(
        np.asarray(st_loop.news_grad_accum),
        np.asarray(st_scan.news_grad_accum),
        rtol=1e-5, atol=1e-7,
    )


def test_scan_seq_parallel():
    """Scan composes with the (clients, seq) 2-D mesh and ring attention."""
    from fedrec_tpu.parallel import fed_mesh, shard_fed_batch

    cfg = small_cfg(
        fed__num_clients=4, fed__seq_shards=2, optim__user_lr=3e-3,
        optim__news_lr=3e-3, data__max_his_len=10,
    )
    mesh = fed_mesh(cfg)
    data, batcher, token_states, model, stacked0, _ = make_setup(cfg, seed=0)
    batches = _collect_batches(batcher, 4, 2)

    step = build_fed_train_step(model, cfg, get_strategy("grad_avg"), mesh, mode="joint")
    st_loop = stacked0
    loop_losses = []
    for b in batches:
        st_loop, m = step(st_loop, shard_fed_batch(mesh, b, cfg), token_states)
        loop_losses.append(np.asarray(m["mean_loss"]))

    _, _, _, _, stacked0b, _ = make_setup(cfg, seed=0)
    scan = build_fed_train_scan(model, cfg, get_strategy("grad_avg"), mesh, mode="joint")
    st_scan, ms = scan(
        stacked0b, shard_scan_batches(mesh, stack_batches(batches), cfg), token_states
    )
    np.testing.assert_allclose(
        np.stack(loop_losses), np.asarray(ms["mean_loss"]), rtol=1e-6, atol=1e-7
    )


def _trainer_fixture(cfg, num_train):
    """data + token_states via the shared make_setup fixture (constants live
    in ONE place, tests/test_train.py)."""
    data, _, token_states, _, _, _ = make_setup(cfg, num_train=num_train, seed=0)
    return data, np.asarray(token_states)


def test_trainer_scan_steps_matches_per_batch(tmp_path):
    """Trainer with train.scan_steps=4 produces the same round losses as
    per-batch dispatch (incl. a non-multiple epoch tail on the per-step
    fallback)."""
    from fedrec_tpu.train.trainer import Trainer

    def run(scan_steps, snap):
        cfg = small_cfg(optim__user_lr=3e-3)
        cfg.fed.strategy = "param_avg"
        cfg.fed.rounds = 2
        cfg.train.scan_steps = scan_steps
        cfg.train.snapshot_dir = str(snap)
        cfg.train.eval_every = 1000
        data, token_states = _trainer_fixture(
            cfg, num_train=6 * 64 + 32  # 6.5 groups -> real tail
        )
        t = Trainer(cfg, data, token_states)
        return [h.train_loss for h in t.run()]

    l1 = run(1, tmp_path / "a")
    l4 = run(4, tmp_path / "b")
    np.testing.assert_allclose(l1, l4, rtol=1e-6)


def test_scan_cohorts_gru_compose():
    """Every axis of the round-3 feature matrix in one program: the GRU
    user tower, k=2 cohorts, and an epoch-in-jit scan chain — matching the
    per-step loop trajectory exactly."""
    cfg = small_cfg(optim__user_lr=3e-3, optim__news_lr=3e-3)
    cfg.model.user_tower = "gru"
    mesh = client_mesh(8, max_devices=4)
    data, batcher, token_states, model, stacked0, _ = make_setup(cfg, seed=0)
    batches = _collect_batches(batcher, 8, 3)

    step = build_fed_train_step(model, cfg, get_strategy("grad_avg"), mesh, mode="joint")
    st = stacked0
    loop_losses = []
    for b in batches:
        st, m = step(st, shard_batch(mesh, b), token_states)
        loop_losses.append(np.asarray(m["mean_loss"]))

    _, _, _, _, stacked0b, _ = make_setup(cfg, seed=0)
    scan = build_fed_train_scan(model, cfg, get_strategy("grad_avg"), mesh, mode="joint")
    _, ms = scan(
        stacked0b, shard_scan_batches(mesh, stack_batches(batches), cfg), token_states
    )
    np.testing.assert_allclose(
        np.stack(loop_losses), np.asarray(ms["mean_loss"]), rtol=1e-6, atol=1e-7
    )


def _make_rounds(batcher, R, S):
    """R per-round lists of S batches, tiling the (small) epoch if short."""
    avail = _collect_batches(batcher, 8, R * S)
    flat = (avail * ((R * S) // len(avail) + 1))[: R * S]
    return [flat[r * S:(r + 1) * S] for r in range(R)]


@pytest.mark.parametrize("strategy,max_dev", [
    ("param_avg", 8),  # k=1: the reference's per-epoch FedAvg round loop
    ("param_avg", 4),  # k=2 cohorts
    ("grad_avg", 8),   # sync is a no-op -> plain multi-epoch-in-jit
])
def test_round_scan_matches_host_round_loop(strategy, max_dev):
    """Rounds-in-jit == the host-driven (epoch scan + param_sync) loop,
    including client-subset participation weights at each round end."""
    from fedrec_tpu.train import (
        build_fed_round_scan,
        build_param_sync,
        shard_round_batches,
        stack_rounds,
    )

    cfg = small_cfg(optim__user_lr=3e-3, optim__news_lr=3e-3)
    mesh = client_mesh(8, max_devices=max_dev)
    data, batcher, token_states, model, stacked0, _ = make_setup(cfg, seed=0)
    R, S = 3, 2
    rounds = _make_rounds(batcher, R, S)
    # round 1 drops clients 0-2; others are full-participation
    weights = np.ones((R, 8), np.float32)
    weights[1, :3] = 0.0

    strat = get_strategy(strategy)
    step = build_fed_train_step(model, cfg, strat, mesh, mode="joint")
    sync = build_param_sync(cfg, mesh, strat)
    st_loop = stacked0
    loop_losses = []
    for r in range(R):
        for b in rounds[r]:
            st_loop, m = step(st_loop, shard_batch(mesh, b), token_states)
            loop_losses.append(np.asarray(m["mean_loss"]))
        st_loop = sync(st_loop, jax.numpy.asarray(weights[r]))

    _, _, _, _, stacked0b, _ = make_setup(cfg, seed=0)
    round_scan = build_fed_round_scan(model, cfg, strat, mesh, mode="joint")
    st_rs, ms = round_scan(
        stacked0b,
        shard_round_batches(mesh, stack_rounds(rounds), cfg),
        token_states,
        jax.numpy.asarray(weights),
    )
    # metrics come back (R, S, clients...) == the flat loop order
    rs_losses = np.asarray(ms["mean_loss"]).reshape(R * S, *np.asarray(
        loop_losses[0]).shape)

    np.testing.assert_allclose(
        np.stack(loop_losses), rs_losses, rtol=1e-6, atol=1e-7
    )
    for a, b in zip(_leaves(st_loop.user_params), _leaves(st_rs.user_params)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    for a, b in zip(_leaves(st_loop.news_params), _leaves(st_rs.news_params)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_trainer_rounds_per_scan_matches_host_loop(tmp_path):
    """The PRODUCTION rounds-in-jit path: Trainer with train.rounds_per_scan=4
    reproduces the host-driven round loop exactly — per-round losses, eval
    metrics at the eval_every cadence, and the snapshot directory contents
    (save_every=2 forces a MID-RUN snapshot boundary, so chunks must break
    there: rounds run as two compiled chunks of 2). Prefetch is enabled on
    the scan run so the overlapped input pipeline is covered by the same
    pin."""
    from fedrec_tpu.train.trainer import Trainer

    def run(rounds_per_scan, prefetch, snap):
        cfg = small_cfg(optim__user_lr=3e-3)
        cfg.model.text_encoder_mode = "head"  # joint mode
        cfg.fed.strategy = "param_avg"
        cfg.fed.rounds = 4
        cfg.train.rounds_per_scan = rounds_per_scan
        cfg.data.prefetch_batches = prefetch
        cfg.train.snapshot_dir = str(snap)
        cfg.train.save_every = 2
        cfg.train.eval_every = 2
        data, token_states = _trainer_fixture(cfg, num_train=128)
        t = Trainer(cfg, data, token_states)
        if rounds_per_scan > 1:
            # cadence boundaries after rounds 1 and 3 split the 4 rounds
            # into two compiled chunks
            assert t._round_chunk(0) == 2 and t._round_chunk(2) == 2
        return t.run()

    host = run(1, 0, tmp_path / "host")
    scan = run(4, 2, tmp_path / "scan")
    assert [h.round_idx for h in host] == [h.round_idx for h in scan]
    np.testing.assert_allclose(
        [h.train_loss for h in host], [h.train_loss for h in scan], rtol=1e-6
    )
    # eval cadence: metrics appear on exactly the same rounds, same values
    assert [bool(h.val_metrics) for h in host] == [bool(h.val_metrics) for h in scan]
    assert any(h.val_metrics for h in host)
    for a, b in zip(host, scan):
        for k in a.val_metrics:
            np.testing.assert_allclose(
                a.val_metrics[k], b.val_metrics[k], rtol=1e-5, atol=1e-6
            )
    # checkpoint cadence: identical snapshot directory layout, incl. the
    # mid-run round-1 snapshot a chunk running past the boundary would skip
    host_files = sorted(p.name for p in (tmp_path / "host").iterdir())
    assert "1" in host_files
    assert host_files == sorted(p.name for p in (tmp_path / "scan").iterdir())


def test_trainer_round_chunk_boundary_math(tmp_path):
    """_round_chunk never crosses an eval/save boundary or the end of
    training, and never exceeds train.rounds_per_scan (pure host logic — no
    compiled programs run)."""
    from fedrec_tpu.train.trainer import Trainer

    cfg = small_cfg()
    cfg.model.text_encoder_mode = "head"
    cfg.fed.strategy = "param_avg"
    cfg.fed.rounds = 10
    cfg.train.rounds_per_scan = 8
    cfg.train.snapshot_dir = str(tmp_path / "snap")
    cfg.train.save_every = 5
    cfg.train.eval_every = 3
    data, token_states = _trainer_fixture(cfg, num_train=128)
    t = Trainer(cfg, data, token_states)
    # eval after rounds 2, 5, 8; save after rounds 4, 9; end at 9
    assert t._round_chunk(0) == 3   # stop after round 2 (eval)
    assert t._round_chunk(3) == 2   # stop after round 4 (save)
    assert t._round_chunk(5) == 1   # round 5 is itself an eval boundary
    assert t._round_chunk(6) == 3   # stop after round 8 (eval)
    assert t._round_chunk(9) == 1   # final round
    # no eval set -> only save/end boundaries bite
    t.valid_ix = None
    assert t._round_chunk(0) == 5


def test_trainer_rounds_per_scan_rejects_unsupported_modes(tmp_path):
    """Fail-fast validation: decoupled mode (host-driven epoch-end
    news_update) and FedOpt (host-side server optimizer) cannot run
    rounds-in-jit."""
    from fedrec_tpu.train.trainer import Trainer

    cfg = small_cfg()
    cfg.model.text_encoder_mode = "table"  # decoupled
    cfg.train.rounds_per_scan = 2
    cfg.train.snapshot_dir = str(tmp_path / "a")
    data, token_states = _trainer_fixture(cfg, num_train=128)
    with pytest.raises(ValueError, match="rounds_per_scan"):
        Trainer(cfg, data, token_states)

    cfg2 = small_cfg()
    cfg2.model.text_encoder_mode = "head"
    cfg2.fed.strategy = "param_avg"
    cfg2.fed.server_opt = "adam"
    cfg2.train.rounds_per_scan = 2
    cfg2.train.snapshot_dir = str(tmp_path / "b")
    with pytest.raises(ValueError, match="server_opt"):
        Trainer(cfg2, data, token_states)


def test_round_scan_gru_cohorts_compose():
    """Rounds-in-jit composed with the GRU user tower AND k=2 cohorts.

    The host side here is the SCAN-form loop (one epoch scan per round +
    weighted param_sync) — the same inner math, so the compare is tight
    (observed bit-exact; asserted at 1e-6/1e-7 to stay robust to
    compiler-version reassociation across the fused sync boundary).
    Comparing against the per-STEP loop instead shows a ~1e-4 drift for
    this combo — XLA compiles the vmap'd GRU recurrence differently inside
    a scan than standalone, and early Adam steps amplify the reassociation
    noise; that per-step-vs-scan tolerance is test_scan_cohorts_gru_compose's
    concern, not the round dimension's."""
    from fedrec_tpu.train import (
        build_fed_round_scan,
        build_param_sync,
        shard_round_batches,
        stack_rounds,
    )

    cfg = small_cfg(optim__user_lr=3e-3, optim__news_lr=3e-3)
    cfg.model.user_tower = "gru"
    mesh = client_mesh(8, max_devices=4)  # k=2 cohorts
    data, batcher, token_states, model, stacked0, _ = make_setup(cfg, seed=0)
    R, S = 2, 2
    rounds = _make_rounds(batcher, R, S)
    weights = np.ones((R, 8), np.float32)
    # drop the ENTIRE second cohort {4..7} in round 0: the cross-cohort
    # weighted sync must handle a whole cohort contributing zero weight
    weights[0, 4:] = 0.0

    strat = get_strategy("param_avg")
    epoch_scan = build_fed_train_scan(model, cfg, strat, mesh, mode="joint")
    sync = build_param_sync(cfg, mesh, strat)
    st_loop = stacked0
    for r in range(R):
        st_loop, _ = epoch_scan(
            st_loop, shard_scan_batches(mesh, stack_batches(rounds[r]), cfg),
            token_states,
        )
        st_loop = sync(st_loop, jax.numpy.asarray(weights[r]))

    _, _, _, _, stacked0b, _ = make_setup(cfg, seed=0)
    round_scan = build_fed_round_scan(model, cfg, strat, mesh, mode="joint")
    st_rs, _ = round_scan(
        stacked0b,
        shard_round_batches(mesh, stack_rounds(rounds), cfg),
        token_states,
        jax.numpy.asarray(weights),
    )
    for a, b in zip(_leaves(st_loop.user_params), _leaves(st_rs.user_params)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    for a, b in zip(_leaves(st_loop.news_params), _leaves(st_rs.news_params)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
