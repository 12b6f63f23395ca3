"""Hot-path levers added for the MFU-cliff work (train/step.py):

  * ``data.gather_chunk`` — tiled, rematerialized token-state gather+encode
    (exact same math, bounded HBM footprint);
  * ``donate_batch`` — builder option the Trainer uses to let XLA reclaim
    batch buffers.
"""

from __future__ import annotations

import numpy as np

import jax

from fedrec_tpu.fed import get_strategy
from fedrec_tpu.parallel import client_mesh, shard_batch
from fedrec_tpu.train import build_fed_train_step

from test_train import make_setup, small_cfg, _batch_dict


def test_tiled_gather_matches_untiled():
    """data.gather_chunk tiles the unique gather+encode in rematerialized
    lax.map chunks — the trajectory must match the untiled step exactly
    (row-wise encode; tiling is a memory layout choice, not math)."""
    cfg = small_cfg(optim__user_lr=3e-3, optim__news_lr=3e-3)
    mesh = client_mesh(8)
    data, batcher, token_states, model, st0, _ = make_setup(cfg, seed=0)
    b = next(batcher.epoch_batches_sharded(8, 0))
    batch = _batch_dict(b)

    step = build_fed_train_step(
        model, cfg, get_strategy("grad_avg"), mesh, mode="joint"
    )
    st1, m1 = step(st0, shard_batch(mesh, batch), token_states)

    cfg_t = small_cfg(optim__user_lr=3e-3, optim__news_lr=3e-3)
    cfg_t.data.gather_chunk = 16  # B*(C+H) = 120 slots -> 8 tiles
    _, _, _, _, st0b, _ = make_setup(cfg_t, seed=0)
    step_t = build_fed_train_step(
        model, cfg_t, get_strategy("grad_avg"), mesh, mode="joint"
    )
    st2, m2 = step_t(st0b, shard_batch(mesh, batch), token_states)

    np.testing.assert_allclose(
        np.asarray(m1["mean_loss"]), np.asarray(m2["mean_loss"]),
        rtol=1e-6, atol=1e-7,
    )
    # the parameters after the step agree to f32 reassociation through
    # Adam's first step. Left out, as in the chip comparison (PERF.md
    # section 2): the additive-attention normalization biases
    # (*/att_fc2/bias). A shift of every logit leaves the softmax alone, so
    # their true gradient is zero and g/(sqrt(g^2)+eps) turns reassociation
    # noise into a step of the size of the learning rate.
    for (kp, a), (_, c) in zip(
        jax.tree_util.tree_leaves_with_path((st1.user_params, st1.news_params)),
        jax.tree_util.tree_leaves_with_path((st2.user_params, st2.news_params)),
    ):
        path = jax.tree_util.keystr(kp)
        if path.endswith("['att_fc2']['bias']"):
            continue
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(c), rtol=1e-5, atol=1e-4, err_msg=path
        )


def test_donate_batch_step_runs_with_fresh_buffers():
    """donate_batch=True (the Trainer's configuration) must keep the step
    correct when every dispatch receives freshly device-put batches."""
    cfg = small_cfg(optim__user_lr=3e-3, optim__news_lr=3e-3)
    mesh = client_mesh(8)
    data, batcher, token_states, model, st0, _ = make_setup(cfg, seed=0)
    step_d = build_fed_train_step(
        model, cfg, get_strategy("grad_avg"), mesh, mode="joint",
        donate_batch=True,
    )
    losses = []
    for i, b in enumerate(batcher.epoch_batches_sharded(8, 0)):
        st0, m = step_d(st0, shard_batch(mesh, _batch_dict(b)), token_states)
        losses.append(float(np.mean(np.asarray(m["mean_loss"]))))
        if i >= 2:
            break
    assert all(np.isfinite(l) for l in losses)
