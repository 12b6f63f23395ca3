"""Byzantine-robust aggregation: neutralization proofs + participation edges.

The acceptance bar (ISSUE 5): with coordinate-wise trimmed mean (or
median), the aggregate with one ×1000-poisoned client equals the
honest-cohort aggregate on hand-computable fixtures; with
``fed.robust.method=mean`` and no faults the behavior is bit-identical to
pre-robust ``weighted_param_avg``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from fedrec_tpu.fed import (
    get_strategy,
    participation_mask,
    robust_aggregate,
    robust_reduce_tree_np,
    weighted_param_avg,
)
from fedrec_tpu.parallel import client_mesh, shard_batch
from fedrec_tpu.train.step import (
    LOCAL_AXIS,
    build_fed_train_step,
    build_param_sync,
)

from test_train import make_setup, small_cfg, _batch_dict

AXIS = "clients"


def _run_agg(vals, weights, method, max_devices=8, **kw):
    """Drive robust_aggregate through shard_map over an (8, ...) stack —
    the same cohort-axes harness the real sync uses (k>1 packs clients
    per device and vmaps under LOCAL_AXIS)."""
    n = vals.shape[0]
    mesh = client_mesh(n, max_devices=max_devices)
    k = n // int(mesh.shape[AXIS])
    sync_axes = AXIS if k == 1 else (LOCAL_AXIS, AXIS)

    def local(v, w):
        return robust_aggregate(v, w, sync_axes, method=method, **kw)

    @partial(
        shard_map, mesh=mesh, in_specs=(P(AXIS), P(AXIS)), out_specs=P(AXIS),
        check_vma=False,
    )
    def run(stacked, w):
        if k == 1:
            return local(stacked[0], w[0])[None]
        return jax.vmap(local, axis_name=LOCAL_AXIS)(stacked, w)

    return np.asarray(
        run(shard_batch(mesh, jnp.asarray(vals)), shard_batch(mesh, jnp.asarray(weights)))
    )


def test_trimmed_mean_neutralizes_x1000_poison():
    """Hand-computable fixture: honest clients share per-coordinate values,
    one client is ×1000-poisoned — the trimmed aggregate EQUALS the honest
    aggregate exactly (the poison consumes a trim slot)."""
    rng = np.random.default_rng(0)
    honest = rng.standard_normal((3,)).astype(np.float32)
    vals = np.tile(honest, (8, 1))          # every client identical
    vals[5] = honest * 1000.0               # the poisoned client
    w = np.ones((8,), np.float32)
    out = _run_agg(vals, w, "trimmed_mean", trim_k=1)
    for c in range(8):                      # every client adopts the aggregate
        np.testing.assert_allclose(out[c], honest, rtol=1e-6)


def test_trimmed_mean_hand_computed_distinct_values():
    vals = np.arange(8 * 2, dtype=np.float32).reshape(8, 2)
    vals[0] = [-1e6, 1e6]  # extreme both ways
    w = np.ones((8,), np.float32)
    out = _run_agg(vals, w, "trimmed_mean", trim_k=1)
    # per coordinate: sort, drop min+max, mean the middle 6
    expect = np.stack([
        np.sort(vals[:, j])[1:-1].mean() for j in range(2)
    ])
    np.testing.assert_allclose(out[0], expect, rtol=1e-6)


def test_median_neutralizes_poison_and_matches_numpy():
    rng = np.random.default_rng(1)
    vals = rng.standard_normal((8, 5)).astype(np.float32)
    vals[2] *= 1000.0
    w = np.ones((8,), np.float32)
    out = _run_agg(vals, w, "median")
    expect = np.median(vals.astype(np.float64), axis=0)
    np.testing.assert_allclose(out[0], expect, rtol=1e-5, atol=1e-6)


def test_clip_bounds_single_client_influence():
    """Norm-clipped mean: one ×1000 client moves the aggregate by at most
    clip_norm / n — the clipped contribution's worst case."""
    honest = np.full((4,), 2.0, np.float32)
    vals = np.tile(honest, (8, 1))
    vals[6] = honest * 1000.0
    w = np.ones((8,), np.float32)
    clip = 0.5
    out = _run_agg(vals, w, "clip", clip_norm=clip)
    # center (median) == honest value; honest deviations are 0, the poisoned
    # deviation clips to norm 0.5, diluted by the 8-client mean
    shift = np.linalg.norm(out[0] - honest)
    assert shift <= clip / 8 + 1e-5
    # and the aggregate is far closer to honest than the poisoned mean is
    assert shift < 1.0


def test_clip_zeroes_nonfinite_contribution():
    honest = np.linspace(1.0, 2.0, 4).astype(np.float32)
    vals = np.tile(honest, (8, 1))
    vals[3] = np.nan
    w = np.ones((8,), np.float32)
    out = _run_agg(vals, w, "clip", clip_norm=1.0)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[0], honest, rtol=1e-5)


def test_trimmed_mean_excludes_nonfinite_and_nonparticipants():
    vals = np.tile(np.arange(3, dtype=np.float32), (8, 1))
    vals[1] = np.nan              # participant gone non-finite: excluded
    vals[4] = 1e9                 # non-participant poison: weight 0
    w = np.ones((8,), np.float32)
    w[4] = 0.0
    out = _run_agg(vals, w, "trimmed_mean", trim_k=1)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[0], np.arange(3, dtype=np.float32), rtol=1e-6)


@pytest.mark.slow  # jit-heavy; tier-1 keeps the fast unit proofs
def test_zero_participation_keeps_local_params_all_methods():
    rng = np.random.default_rng(2)
    vals = rng.standard_normal((8, 3)).astype(np.float32)
    w = np.zeros((8,), np.float32)
    for method in ("mean", "clip", "trimmed_mean", "median"):
        out = _run_agg(vals, w, method)
        np.testing.assert_allclose(out, vals, rtol=1e-6, err_msg=method)


@pytest.mark.slow  # jit-heavy; tier-1 keeps the fast unit proofs
def test_cohort_packing_independence():
    """8 clients on 8 devices (k=1) == on 4 devices (k=2): the robust
    aggregate must be independent of the client->chip packing, like every
    other cross-client collective."""
    rng = np.random.default_rng(3)
    vals = rng.standard_normal((8, 6)).astype(np.float32)
    vals[0] *= 500.0
    w = np.ones((8,), np.float32)
    for method in ("trimmed_mean", "median", "clip"):
        a = _run_agg(vals, w, method, max_devices=8)
        b = _run_agg(vals, w, method, max_devices=4)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7, err_msg=method)


def test_unknown_method_fails_fast():
    with pytest.raises(ValueError, match="unknown fed.robust.method"):
        _run_agg(np.ones((8, 2), np.float32), np.ones((8,), np.float32), "krum")


# --------------------------------------------------- through the real sync
def _diverged_state(cfg):
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    step = build_fed_train_step(
        model, cfg, get_strategy("local"), mesh, mode="joint"
    )
    for b in batcher.epoch_batches_sharded(cfg.fed.num_clients, 0):
        stacked, _ = step(stacked, shard_batch(mesh, _batch_dict(b)), token_states)
    return stacked, mesh


@pytest.mark.slow  # jit-heavy; tier-1 keeps the fast unit proofs
def test_param_sync_trimmed_mean_neutralizes_poisoned_client():
    cfg = small_cfg()
    stacked, mesh = _diverged_state(cfg)

    def poison(tree):
        def one(x):
            x = np.array(x)
            x[3] = x[3] * 1000.0
            return jnp.asarray(x)

        return jax.tree_util.tree_map(one, tree)

    stacked = stacked.replace(user_params=poison(stacked.user_params))
    cfg.fed.robust.method = "trimmed_mean"
    sync = build_param_sync(cfg, mesh)
    out = sync(stacked, jnp.ones((8,), jnp.float32))
    for pre, post in zip(
        jax.tree_util.tree_leaves(stacked.user_params),
        jax.tree_util.tree_leaves(out.user_params),
    ):
        pre = np.asarray(pre, np.float64)
        # hand-computed per-coordinate trimmed mean over the 8 clients
        srt = np.sort(pre, axis=0)
        expect = srt[1:-1].mean(axis=0)
        arr = np.asarray(post)
        for c in range(8):
            np.testing.assert_allclose(arr[c], expect, rtol=1e-4, atol=1e-6)
        # the poison did NOT move the aggregate toward client 3
        assert np.isfinite(arr).all()


@pytest.mark.slow  # jit-heavy; tier-1 keeps the fast unit proofs
def test_param_sync_mean_is_bitwise_weighted_param_avg():
    """method='mean' routes through the pre-robust weighted_param_avg —
    the same compiled computation, bit-identical outputs."""
    cfg = small_cfg()
    stacked, mesh = _diverged_state(cfg)
    w = jnp.asarray(np.array([1, 0, 1, 1, 2, 1, 1, 1], np.float32))
    assert cfg.fed.robust.method == "mean"  # the default
    out = build_param_sync(cfg, mesh)(stacked, w)

    # reference: weighted_param_avg via the same shard_map harness
    @partial(
        shard_map, mesh=mesh, in_specs=(P(AXIS), P(AXIS)), out_specs=P(AXIS),
        check_vma=False,
    )
    def ref(stack, wv):
        local = weighted_param_avg(
            jax.tree_util.tree_map(lambda x: x[0], stack), wv[0], AXIS
        )
        return jax.tree_util.tree_map(lambda x: x[None], local)

    refd = ref(stacked.user_params, shard_batch(mesh, np.asarray(w)))
    for a, b in zip(
        jax.tree_util.tree_leaves(refd),
        jax.tree_util.tree_leaves(out.user_params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_weighted_param_avg_masks_nan_zero_weight_client():
    """The quarantine contract: a weight-0 client whose params are NaN
    contributes NOTHING (NaN * 0 would be NaN) — pinned at the collective
    level."""
    vals = np.tile(np.linspace(1, 2, 4, dtype=np.float32), (8, 1))
    vals[2] = np.nan
    w = np.ones((8,), np.float32)
    w[2] = 0.0
    out = _run_agg(vals, w, "mean")
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[0], np.linspace(1, 2, 4), rtol=1e-6)


# ------------------------------------------------------------ numpy variant
@pytest.mark.slow  # jit-heavy; tier-1 keeps the fast unit proofs
def test_robust_reduce_tree_np_matches_in_graph():
    """The coordinator's numpy reduction and the in-graph aggregator must
    agree leaf-for-leaf — including clip, whose deviation norm is GLOBAL
    over the whole tree (so the tree goes through in one call)."""
    rng = np.random.default_rng(4)
    tree = {
        "a": rng.standard_normal((8, 3)).astype(np.float32),
        "b": rng.standard_normal((8, 2, 2)).astype(np.float32),
    }
    tree["a"][5] *= 1000.0
    tree["b"][5] *= 1000.0
    w = np.ones((8,), np.float64)
    mesh = client_mesh(8)

    for method in ("trimmed_mean", "median", "clip"):
        np_out = robust_reduce_tree_np(tree, w, method, trim_k=1, clip_norm=0.5)

        @partial(
            shard_map, mesh=mesh, in_specs=(P(AXIS), P(AXIS)),
            out_specs=P(AXIS), check_vma=False,
        )
        def run(stack, wv):
            local = jax.tree_util.tree_map(lambda x: x[0], stack)
            out = robust_aggregate(
                local, wv[0], AXIS, method=method, trim_k=1, clip_norm=0.5
            )
            return jax.tree_util.tree_map(lambda x: x[None], out)

        jx_out = run(
            shard_batch(mesh, jax.tree_util.tree_map(jnp.asarray, tree)),
            shard_batch(mesh, w.astype(np.float32)),
        )
        for k in tree:
            np.testing.assert_allclose(
                np.asarray(np_out[k]), np.asarray(jx_out[k])[0],
                rtol=1e-4, atol=1e-6, err_msg=f"{method}/{k}",
            )


def test_robust_reduce_np_zero_finite_coordinate_keeps_fallback():
    """A coordinate where EVERY contribution is non-finite keeps the
    caller's local value (the in-graph ``m > 0`` guard), not a silent
    0.0 — and finite coordinates are unaffected by the fallback."""
    from fedrec_tpu.fed import robust_reduce_np

    vals = np.tile(np.array([2.0, 5.0]), (4, 1))
    vals[:, 1] = np.nan                      # all-poisoned coordinate
    w = np.ones((4,), np.float64)
    local = np.array([7.0, 9.0])
    for method in ("trimmed_mean", "median"):
        out = robust_reduce_np(vals, w, method, trim_k=1, fallback=local)
        np.testing.assert_allclose(out, [2.0, 9.0], err_msg=method)
        # no fallback: documented 0.0
        out0 = robust_reduce_np(vals, w, method, trim_k=1)
        np.testing.assert_allclose(out0, [2.0, 0.0], err_msg=method)


# ------------------------------------------- participation-mask edge pins
def test_participation_mask_fraction_rounds_to_at_least_one():
    rng = jax.random.PRNGKey(0)
    m = np.asarray(participation_mask(rng, 8, 0.01))
    assert m.sum() == 1.0  # k >= 1 even when fraction*n rounds to 0
    m = np.asarray(participation_mask(rng, 8, 0.5))
    assert m.sum() == 4.0
    assert set(np.unique(m)) <= {0.0, 1.0}


def test_participation_mask_full_fraction_is_all_ones():
    m = np.asarray(participation_mask(jax.random.PRNGKey(1), 8, 1.0))
    np.testing.assert_array_equal(m, np.ones(8, np.float32))


def test_participation_mask_deterministic_under_fixed_rng():
    a = np.asarray(participation_mask(jax.random.PRNGKey(7), 16, 0.25))
    b = np.asarray(participation_mask(jax.random.PRNGKey(7), 16, 0.25))
    np.testing.assert_array_equal(a, b)
    c = np.asarray(participation_mask(jax.random.PRNGKey(8), 16, 0.25))
    assert a.sum() == c.sum() == 4.0  # same k either way
