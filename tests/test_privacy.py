"""Privacy tests: RDP accountant math, per-example clipping, noise statistics,
and DP federated training end-to-end.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fedrec_tpu.config import PrivacyConfig
from fedrec_tpu.privacy import (
    calibrate_sigma,
    clip_by_global_norm_per_example,
    compute_epsilon,
    compute_rdp_subsampled_gaussian,
    make_noise_fn,
    per_example_clipped_grads,
)


# ------------------------------------------------------------- accountant
def test_rdp_full_batch_closed_form():
    # q = 1: RDP(alpha) = alpha / (2 sigma^2) exactly
    sigma, steps = 2.0, 10
    rdp = compute_rdp_subsampled_gaussian(1.0, sigma, steps, orders=(2, 4, 8))
    expected = np.array([2, 4, 8]) / (2 * sigma**2) * steps
    np.testing.assert_allclose(rdp, expected, rtol=1e-12)


def test_rdp_subsampling_amplifies_privacy():
    # smaller q must give (weakly) smaller RDP at every order
    full = compute_rdp_subsampled_gaussian(1.0, 1.0, 100)
    sub = compute_rdp_subsampled_gaussian(0.01, 1.0, 100)
    assert (sub <= full + 1e-12).all()
    assert sub[0] < full[0] * 0.1  # dramatic amplification at q=0.01


def test_epsilon_monotonic_in_sigma_and_steps():
    eps = [compute_epsilon(0.1, s, 100, 1e-5) for s in (0.5, 1.0, 2.0, 4.0)]
    assert eps == sorted(eps, reverse=True)  # more noise, less epsilon
    eps_t = [compute_epsilon(0.1, 1.0, t, 1e-5) for t in (10, 100, 1000)]
    assert eps_t == sorted(eps_t)  # more steps, more epsilon


def test_calibrate_sigma_roundtrip():
    # the reference setting: eps=10, delta=1e-5, 50 epochs (client.py:220-224)
    q, steps, delta, target = 0.05, 50 * 20, 1e-5, 10.0
    sigma = calibrate_sigma(target, delta, q, steps)
    achieved = compute_epsilon(q, sigma, steps, delta)
    assert achieved <= target + 1e-3
    # sigma is tight: 5% less noise must violate the target
    assert compute_epsilon(q, sigma * 0.95, steps, delta) > target


def test_accountant_rejects_bad_inputs():
    with pytest.raises(ValueError):
        compute_rdp_subsampled_gaussian(0.5, -1.0, 10)
    with pytest.raises(ValueError):
        compute_rdp_subsampled_gaussian(1.5, 1.0, 10)
    with pytest.raises(ValueError):
        compute_epsilon(0.5, 1.0, 10, delta=2.0)
    with pytest.raises(ValueError):
        calibrate_sigma(-1.0, 1e-5, 0.1, 10)


def test_sampling_profile_exact_q():
    """ISSUE 6 satellite pin: when client sampling is on, the accountant's
    subsampling fraction is the PRODUCT of the per-round cohort fraction
    (slots / population) and the per-shard batch fraction — hand-exact —
    and every accountant entry point (calibration, spend schedule) shares
    that one definition."""
    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.privacy import round_epsilon_schedule, sampling_profile

    cfg = ExperimentConfig()
    cfg.data.batch_size = 8
    cfg.fed.num_clients = 4
    n_train = 4096

    # fixed world: q is the legacy batch-level constant
    q, steps = sampling_profile(cfg, n_train)
    assert q == 8 / (4096 // 4)            # B / per_client = 1/128
    assert steps == (4096 // 4) // 8       # 128 steps/epoch

    # sampled world: 64 logical clients on 4 slots
    cfg.fed.population.num_clients = 64
    q_s, steps_s = sampling_profile(cfg, n_train)
    shard = 4096 // 64                     # 64 rows/client
    assert q_s == (4 / 64) * (8 / shard)   # q_client * q_batch = 1/128
    assert steps_s == shard // 8           # 8 steps per SELECTED epoch

    # amplification is real: accounting the sampled run at the batch-level
    # constant alone (same q here by construction, but 16x the steps, the
    # fixed-world cadence) overstates the spend
    cfg.privacy.sigma = 1.2
    sched = round_epsilon_schedule(cfg, n_train)
    eps_sampled = sched(10)
    from fedrec_tpu.privacy.accountant import compute_epsilon

    eps_fixed_cadence = compute_epsilon(
        q_s, 1.2, steps * cfg.fed.local_epochs * 10, cfg.privacy.delta
    )
    assert eps_sampled < eps_fixed_cadence

    # degenerate population (== slots) keeps the legacy profile exactly
    cfg.fed.population.num_clients = 4
    assert sampling_profile(cfg, n_train) == (q, steps)

    # amplification assumes a UNIFORM draw: biased samplers are rejected
    # (their per-client selection probability can approach 1, so
    # q = slots/population would understate epsilon)
    cfg.fed.population.num_clients = 64
    cfg.fed.population.sampler = "weighted"
    with pytest.raises(ValueError, match="UNIFORM"):
        sampling_profile(cfg, n_train)


# ---------------------------------------------------------------- clipping
def test_per_example_clip_bounds_global_norm():
    rng = np.random.default_rng(0)
    grads = {
        "a": jnp.asarray(rng.standard_normal((8, 4, 3)).astype(np.float32) * 10),
        "b": jnp.asarray(rng.standard_normal((8, 5)).astype(np.float32) * 10),
    }
    clipped = clip_by_global_norm_per_example(grads, clip_norm=1.0)
    norms = np.sqrt(
        np.sum(np.asarray(clipped["a"]) ** 2, axis=(1, 2))
        + np.sum(np.asarray(clipped["b"]) ** 2, axis=1)
    )
    assert (norms <= 1.0 + 1e-5).all()
    # small grads pass through unscaled
    small = {"a": jnp.full((2, 3), 0.01)}
    out = clip_by_global_norm_per_example(small, clip_norm=1.0)
    np.testing.assert_allclose(np.asarray(out["a"]), 0.01, rtol=1e-6)


def test_per_example_clipped_grads_matches_manual():
    # quadratic loss -> grad = 2 w * x^2 per example; verify clip + mean
    def loss(w, x):
        return jnp.sum((w * x) ** 2)

    w = jnp.asarray([1.0, 2.0])
    xs = jnp.asarray([[1.0, 0.0], [10.0, 0.0], [0.0, 1.0]])
    mean_loss, g = per_example_clipped_grads(loss, w, (xs,), clip_norm=2.0)
    per_ex = np.stack([2 * np.asarray(w) * np.asarray(x) ** 2 for x in xs])
    norms = np.linalg.norm(per_ex, axis=1)
    scaled = per_ex * np.minimum(1.0, 2.0 / norms)[:, None]
    np.testing.assert_allclose(np.asarray(g), scaled.mean(axis=0), rtol=1e-5)


# ------------------------------------------------------------------- noise
def test_dpsgd_noise_statistics():
    cfg = PrivacyConfig(enabled=True, sigma=2.0, clip_norm=3.0, mechanism="dpsgd")
    noise_fn = make_noise_fn(cfg, batch_size=4)
    zero = (jnp.zeros((2000,)), jnp.zeros((2000,)))
    noised = noise_fn(zero, jax.random.PRNGKey(0))
    std = cfg.sigma * cfg.clip_norm / 4
    for part in noised:
        arr = np.asarray(part)
        assert abs(arr.std() - std) < 0.1 * std
        assert abs(arr.mean()) < 3 * std / math.sqrt(arr.size)


def test_ldp_news_noise_targets_only_news_grads():
    cfg = PrivacyConfig(enabled=True, sigma=1.0, mechanism="ldp_news")
    noise_fn = make_noise_fn(cfg, batch_size=4)
    user_g = jnp.zeros((100,))
    news_g = jnp.zeros((100,))
    out_user, out_news = noise_fn((user_g, news_g), jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(out_user), 0.0)  # parity: untouched
    assert np.asarray(out_news).std() > 0.5


def test_noise_fn_disabled_and_invalid():
    assert make_noise_fn(PrivacyConfig(enabled=False), 4) is None
    with pytest.raises(ValueError, match="sigma"):
        make_noise_fn(PrivacyConfig(enabled=True, sigma=0.0), 4)
    with pytest.raises(ValueError, match="mechanism"):
        make_noise_fn(
            PrivacyConfig(enabled=True, sigma=1.0, mechanism="bogus"), 4
        )


# ----------------------------------------------------- end-to-end DP train
def test_dpsgd_federated_training_runs_and_learns():
    from tests.test_train import _batch_dict, make_setup, small_cfg
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.parallel import shard_batch
    from fedrec_tpu.train import build_fed_train_step

    cfg = small_cfg(optim__user_lr=3e-3, optim__news_lr=3e-3)
    cfg.privacy.enabled = True
    cfg.privacy.mechanism = "dpsgd"
    cfg.privacy.clip_norm = 2.0
    cfg.privacy.sigma = 0.05  # mild noise so learning is still visible
    cfg.data.batch_size = 8
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    step = build_fed_train_step(model, cfg, get_strategy("grad_avg"), mesh, mode="joint")
    losses = []
    for epoch in range(4):
        for b in batcher.epoch_batches_sharded(cfg.fed.num_clients, epoch):
            stacked, m = step(stacked, shard_batch(mesh, _batch_dict(b)), token_states)
            losses.append(float(np.mean(np.asarray(m["mean_loss"]))))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_dpsgd_sigma_to_zero_matches_non_dp():
    """σ→0 with an inactive clip ⇒ the DP-SGD estimator IS the non-private
    gradient (VERDICT r3 #4): one federated step under each must produce
    the same parameters. Dropout is disabled because the DP path draws
    per-example dropout keys while the dense path draws one batch key —
    with it off, the only difference left is the estimator itself. The
    noise term contributes std = sigma*C/B ≈ 1e-12*1e3/8 ≈ 1e-10, below
    float32 resolution of the updates."""
    import copy

    from tests.test_train import _batch_dict, make_setup, small_cfg
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.parallel import shard_batch
    from fedrec_tpu.train import build_fed_train_step

    cfg = small_cfg(model__dropout_rate=0.0)
    cfg.data.batch_size = 8
    # SGD, not Adam: the two paths sum news-head grad contributions in
    # different orders (dedup-encode vs per-example), so near-zero grad
    # elements carry float32 reassociation noise; Adam's first-step
    # update ~ lr*g/|g| turns that noise into +-lr sign flips. Under SGD
    # the param delta is linear in the grad and the comparison is exact
    # to float tolerance.
    cfg.optim.optimizer = "sgd"
    _, batcher, token_states, model, stacked0, mesh = make_setup(cfg)

    cfg_dp = copy.deepcopy(cfg)
    cfg_dp.privacy.enabled = True
    cfg_dp.privacy.mechanism = "dpsgd"
    cfg_dp.privacy.clip_norm = 1e3   # far above any per-example norm
    cfg_dp.privacy.sigma = 1e-12

    step = build_fed_train_step(model, cfg, get_strategy("grad_avg"), mesh, mode="joint")
    step_dp = build_fed_train_step(
        model, cfg_dp, get_strategy("grad_avg"), mesh, mode="joint"
    )
    b = next(iter(batcher.epoch_batches_sharded(cfg.fed.num_clients, 0)))
    batch = shard_batch(mesh, _batch_dict(b))
    out, m = step(stacked0, batch, token_states)
    out_dp, m_dp = step_dp(stacked0, batch, token_states)
    np.testing.assert_allclose(
        float(np.mean(np.asarray(m["mean_loss"]))),
        float(np.mean(np.asarray(m_dp["mean_loss"]))),
        rtol=1e-5,
    )
    for a, bp in zip(
        jax.tree_util.tree_leaves(out.user_params),
        jax.tree_util.tree_leaves(out_dp.user_params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bp), rtol=2e-4, atol=1e-6)
    for a, bp in zip(
        jax.tree_util.tree_leaves(out.news_params),
        jax.tree_util.tree_leaves(out_dp.news_params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bp), rtol=2e-4, atol=1e-6)


def test_dpsgd_user_scope_freezes_head_and_matches_user_update():
    """privacy.dp_scope='user' (VERDICT r4 #3): the text head must be
    BIT-identical after a DP step — its grads are never computed, so no
    clip contribution and no noise even at huge sigma — while at σ→0 with
    an inactive clip the user-tower update equals the non-private step's
    (the user grad is evaluated at the same (user, news) point, so the
    frozen head changes nothing about it)."""
    import copy

    from tests.test_train import _batch_dict, make_setup, small_cfg
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.parallel import shard_batch
    from fedrec_tpu.train import build_fed_train_step

    cfg = small_cfg(model__dropout_rate=0.0)
    cfg.data.batch_size = 8
    cfg.optim.optimizer = "sgd"  # see test_dpsgd_sigma_to_zero_matches_non_dp
    _, batcher, token_states, model, stacked0, mesh = make_setup(cfg)

    cfg_dp = copy.deepcopy(cfg)
    cfg_dp.privacy.enabled = True
    cfg_dp.privacy.mechanism = "dpsgd"
    cfg_dp.privacy.dp_scope = "user"
    cfg_dp.privacy.clip_norm = 1e3
    cfg_dp.privacy.sigma = 1e-12

    step = build_fed_train_step(model, cfg, get_strategy("grad_avg"), mesh, mode="joint")
    step_dp = build_fed_train_step(
        model, cfg_dp, get_strategy("grad_avg"), mesh, mode="joint"
    )
    b = next(iter(batcher.epoch_batches_sharded(cfg.fed.num_clients, 0)))
    batch = shard_batch(mesh, _batch_dict(b))
    out, _ = step(stacked0, batch, token_states)
    out_dp, _ = step_dp(stacked0, batch, token_states)
    # head frozen bit-for-bit
    for a, bp in zip(
        jax.tree_util.tree_leaves(stacked0.news_params),
        jax.tree_util.tree_leaves(out_dp.news_params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bp))
    # user tower: σ→0 limit equals the non-private update
    for a, bp in zip(
        jax.tree_util.tree_leaves(out.user_params),
        jax.tree_util.tree_leaves(out_dp.user_params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bp), rtol=2e-4, atol=1e-6)

    # large sigma: the head STILL does not move (noise never touches it),
    # while the user tower does
    cfg_noisy = copy.deepcopy(cfg_dp)
    cfg_noisy.privacy.sigma = 5.0
    step_noisy = build_fed_train_step(
        model, cfg_noisy, get_strategy("grad_avg"), mesh, mode="joint"
    )
    out_noisy, _ = step_noisy(stacked0, batch, token_states)
    for a, bp in zip(
        jax.tree_util.tree_leaves(stacked0.news_params),
        jax.tree_util.tree_leaves(out_noisy.news_params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bp))
    moved = any(
        not np.allclose(np.asarray(a), np.asarray(bp))
        for a, bp in zip(
            jax.tree_util.tree_leaves(stacked0.user_params),
            jax.tree_util.tree_leaves(out_noisy.user_params),
        )
    )
    assert moved, "user tower must train under dp_scope='user'"


def test_dp_scope_validation():
    """dp_scope='user' with ldp_news is contradictory and must fail fast;
    unknown scopes are rejected."""
    import copy

    from tests.test_train import make_setup, small_cfg
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.train import build_fed_train_step

    cfg = small_cfg()
    _, _, _, model, _, mesh = make_setup(cfg)
    bad = copy.deepcopy(cfg)
    bad.privacy.enabled = True
    bad.privacy.sigma = 1.0
    bad.privacy.mechanism = "ldp_news"
    bad.privacy.dp_scope = "user"
    with pytest.raises(ValueError, match="dp_scope"):
        build_fed_train_step(model, bad, get_strategy("grad_avg"), mesh, mode="joint")
    bad2 = copy.deepcopy(cfg)
    bad2.privacy.enabled = True
    bad2.privacy.sigma = 1.0
    bad2.privacy.dp_scope = "everything"
    with pytest.raises(ValueError, match="dp_scope"):
        build_fed_train_step(model, bad2, get_strategy("grad_avg"), mesh, mode="joint")


def test_ldp_news_noise_in_decoupled_mode():
    from tests.test_train import _batch_dict, make_setup, small_cfg
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.parallel import shard_batch
    from fedrec_tpu.train import build_fed_train_step, encode_all_news

    cfg = small_cfg()
    cfg.privacy.enabled = True
    cfg.privacy.mechanism = "ldp_news"
    cfg.privacy.sigma = 0.1
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    p0 = jax.tree_util.tree_map(lambda x: x[0], stacked.news_params)
    table = encode_all_news(model, p0, token_states)
    step = build_fed_train_step(
        model, cfg, get_strategy("param_avg"), mesh, mode="decoupled"
    )
    b = next(iter(batcher.epoch_batches_sharded(cfg.fed.num_clients, 0)))
    stacked, m = step(stacked, shard_batch(mesh, _batch_dict(b)), table)
    assert np.isfinite(float(np.mean(np.asarray(m["mean_loss"]))))
    # noised embedding grads landed in the accumulator
    assert float(jnp.sum(jnp.abs(stacked.news_grad_accum))) > 0.0


def test_dpsgd_rejected_in_decoupled_mode():
    # review finding: unclipped grads + DP-SGD sigma would be a fake guarantee
    from tests.test_train import make_setup, small_cfg
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.train import build_fed_train_step

    cfg = small_cfg()
    cfg.privacy.enabled = True
    cfg.privacy.mechanism = "dpsgd"
    cfg.privacy.sigma = 1.0
    _, _, _, model, _, mesh = make_setup(cfg)
    with pytest.raises(ValueError, match="joint"):
        build_fed_train_step(model, cfg, get_strategy("param_avg"), mesh, mode="decoupled")


def test_dpsgd_user_scope_under_cohorts():
    """The round-5 combination nobody pinned: per-example DP-SGD with
    dp_scope='user' must produce IDENTICAL results packed as in-device
    cohorts (8 clients on 4 devices, k=2) vs one-client-per-device. Both
    programs share _build_local_step, so divergence = a wiring bug in the
    cohort vmap, not the mechanism."""
    from itertools import islice

    from tests.test_train import _batch_dict, make_setup, small_cfg
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.parallel import client_mesh, shard_batch
    from fedrec_tpu.train import build_fed_train_step

    cfg = small_cfg(model__dropout_rate=0.0)
    cfg.data.batch_size = 8
    cfg.optim.optimizer = "sgd"
    cfg.privacy.enabled = True
    cfg.privacy.mechanism = "dpsgd"
    cfg.privacy.dp_scope = "user"
    cfg.privacy.clip_norm = 0.5   # active clipping: exercises the bound
    cfg.privacy.sigma = 1e-12     # deterministic comparison across packings
    _, batcher, token_states, model, stacked0, _ = make_setup(cfg, seed=0)
    batches = [
        _batch_dict(b) for b in islice(batcher.epoch_batches_sharded(8, 0), 3)
    ]

    results = {}
    for tag, max_dev in (("flat", 8), ("cohort", 4)):
        mesh = client_mesh(8, max_devices=max_dev)
        step = build_fed_train_step(
            model, cfg, get_strategy("grad_avg"), mesh, mode="joint"
        )
        _, _, _, _, st, _ = make_setup(cfg, seed=0)
        for b in batches:
            st, _m = step(st, shard_batch(mesh, b), token_states)
        results[tag] = jax.tree_util.tree_map(np.asarray, st.user_params)
        # head frozen in every packing
        for a, bp in zip(
            jax.tree_util.tree_leaves(stacked0.news_params),
            jax.tree_util.tree_leaves(st.news_params),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(bp))

    for a, bp in zip(
        jax.tree_util.tree_leaves(results["flat"]),
        jax.tree_util.tree_leaves(results["cohort"]),
    ):
        np.testing.assert_allclose(a, bp, rtol=2e-4, atol=1e-6)
