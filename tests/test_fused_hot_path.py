"""Fused hot-path kernels (ISSUE 8): golden parity, mask edges, trajectory
pins, the traced VMEM model, and the evidence-driven attn_impl resolver.

Runs in Pallas interpret mode on CPU — the same kernel code that compiles
on TPU. Numerics contract under test (``ops/fused_hot_path`` docstring):
f32 matches the dense module chain to float roundoff; bf16 is tolerance-
banded (the kernels keep f32 through normalizations where the module
requantizes); parameters whose gradient is MATHEMATICALLY zero (the key-
projection bias — softmax-shift-invariant — and the pool fc2 bias) carry
only O(1e-8) epsilon noise on either path, which Adam amplifies to
noise-level values; trajectory tolerances cover that documented ledger
entry.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import jax.tree_util as jtu

from fedrec_tpu.ops import (
    fused_gather_encode,
    fused_history_score,
    fused_user_vector,
)

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))
from test_train import make_setup, small_cfg, _batch_dict  # noqa: E402

from fedrec_tpu.fed import get_strategy  # noqa: E402
from fedrec_tpu.parallel import client_mesh, shard_batch  # noqa: E402
from fedrec_tpu.train import build_fed_train_step  # noqa: E402


# --------------------------------------------------------------- goldens
def _make_text_head_params(rng, dh, ah, d):
    return {
        "pool": {
            "att_fc1": {
                "kernel": jnp.asarray(rng.standard_normal((dh, ah)) * 0.1,
                                      jnp.float32),
                "bias": jnp.asarray(rng.standard_normal(ah) * 0.1,
                                    jnp.float32),
            },
            "att_fc2": {
                "kernel": jnp.asarray(rng.standard_normal((ah, 1)) * 0.1,
                                      jnp.float32),
                "bias": jnp.zeros((1,), jnp.float32),
            },
        },
        "fc": {
            "kernel": jnp.asarray(rng.standard_normal((dh, d)) * 0.1,
                                  jnp.float32),
            "bias": jnp.asarray(rng.standard_normal(d) * 0.1, jnp.float32),
        },
    }


def _dense_text_head(table, uniq, p):
    """The module chain's math (TextHead: additive pool + projection,
    stable softmax, the module's +1e-8 denominator, no token mask)."""
    x = table[uniq].astype(jnp.float32)
    p1 = p["pool"]["att_fc1"]
    e = jnp.tanh(jnp.einsum("utd,dh->uth", x, p1["kernel"]) + p1["bias"])
    lg = jnp.einsum("uth,h->ut", e, p["pool"]["att_fc2"]["kernel"][:, 0])
    lg = lg + p["pool"]["att_fc2"]["bias"][0]
    lg = lg - jnp.max(lg, axis=-1, keepdims=True)
    w = jnp.exp(lg)
    a = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-8)
    pooled = jnp.einsum("ut,utd->ud", a, x)
    return pooled @ p["fc"]["kernel"] + p["fc"]["bias"]


def _make_user_params(rng, d, q):
    ap = {
        k: {
            "kernel": jnp.asarray(rng.standard_normal((d, d)) * 0.1,
                                  jnp.float32),
            "bias": jnp.asarray(rng.standard_normal(d) * 0.05, jnp.float32),
        }
        for k in ("w_q", "w_k", "w_v")
    }
    pp = {
        "att_fc1": {
            "kernel": jnp.asarray(rng.standard_normal((d, q)) * 0.1,
                                  jnp.float32),
            "bias": jnp.asarray(rng.standard_normal(q) * 0.05, jnp.float32),
        },
        "att_fc2": {
            "kernel": jnp.asarray(rng.standard_normal((q, 1)) * 0.1,
                                  jnp.float32),
            "bias": jnp.zeros((1,), jnp.float32),
        },
    }
    return ap, pp


def _dense_hist_score(x, cand, mask, ap, pp, nh):
    """The UserEncoder+scorer module math on raw params (stable softmax,
    mask-after-exp, +1e-8 denominators)."""
    b, h, d = x.shape
    dh = d // nh
    x32 = x.astype(jnp.float32)

    def mn(logits, m, axis):
        logits = logits - jnp.max(logits, axis=axis, keepdims=True)
        w = jnp.exp(logits)
        if m is not None:
            w = w * m
        return w / (jnp.sum(w, axis=axis, keepdims=True) + 1e-8)

    q = (x32 @ ap["w_q"]["kernel"] + ap["w_q"]["bias"]).reshape(b, h, nh, dh)
    k = (x32 @ ap["w_k"]["kernel"] + ap["w_k"]["bias"]).reshape(b, h, nh, dh)
    v = (x32 @ ap["w_v"]["kernel"] + ap["w_v"]["bias"]).reshape(b, h, nh, dh)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(dh, jnp.float32)
    )
    m4 = None if mask is None else mask[:, None, None, :]
    a = mn(s, m4, -1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(b, h, d)
    e = jnp.tanh(ctx @ pp["att_fc1"]["kernel"] + pp["att_fc1"]["bias"])
    lg = (e @ pp["att_fc2"]["kernel"])[..., 0] + pp["att_fc2"]["bias"][0]
    al = mn(lg, mask, -1)
    user = jnp.einsum("bh,bhd->bd", al, ctx)
    return jnp.einsum("bcd,bd->bc", cand.astype(jnp.float32), user), user


# ------------------------------------------------- kernel 1: gather+encode
@pytest.mark.parametrize("n,t,dh,ah,d,u", [(32, 12, 48, 24, 40, 16),
                                           (10, 7, 36, 18, 24, 5)])
def test_gather_encode_matches_dense(rng, n, t, dh, ah, d, u):
    table = jnp.asarray(rng.standard_normal((n, t, dh)), jnp.float32)
    uniq = jnp.asarray(rng.integers(0, n, (u,)), jnp.int32)
    p = _make_text_head_params(rng, dh, ah, d)
    got = jax.jit(lambda tb, uq: fused_gather_encode(tb, uq, p))(table, uniq)
    want = _dense_text_head(table, uniq, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_gather_encode_grads_match_dense(rng):
    n, t, dh, ah, d, u = 24, 10, 32, 16, 20, 12
    table = jnp.asarray(rng.standard_normal((n, t, dh)), jnp.float32)
    uniq = jnp.asarray(rng.integers(0, n, (u,)), jnp.int32)
    p = _make_text_head_params(rng, dh, ah, d)

    gf = jax.grad(
        lambda p: jnp.sum(
            fused_gather_encode(jax.lax.stop_gradient(table), uniq, p) ** 2
        )
    )(p)
    gd = jax.grad(lambda p: jnp.sum(_dense_text_head(table, uniq, p) ** 2))(p)
    for (kp, a), (_, b) in zip(
        jtu.tree_leaves_with_path(gf), jtu.tree_leaves_with_path(gd)
    ):
        if "att_fc2']['bias" in jtu.keystr(kp):
            # fc2 bias: softmax-invariant shift — the kernel's grad is
            # exactly zero, the dense path's is O(1e-8) epsilon noise
            np.testing.assert_allclose(np.asarray(a), 0.0)
            assert float(jnp.max(jnp.abs(b))) < 1e-5
            continue
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, err_msg=jtu.keystr(kp)
        )


def test_gather_encode_bf16_banded(rng):
    n, t, dh, ah, d, u = 24, 10, 128, 64, 32, 12
    table32 = rng.standard_normal((n, t, dh)).astype(np.float32)
    uniq = jnp.asarray(rng.integers(0, n, (u,)), jnp.int32)
    p = _make_text_head_params(rng, dh, ah, d)
    got = fused_gather_encode(jnp.asarray(table32, jnp.bfloat16), uniq, p)
    assert got.dtype == jnp.bfloat16
    want = _dense_text_head(jnp.asarray(table32), uniq, p)
    # bf16 operand band: ~2-3 decimal digits on O(1) activations
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=0.15, rtol=0.05
    )


# --------------------------------------------- kernel 2: attention + score
@pytest.mark.parametrize("b,h,d,nh,c,q", [(5, 10, 32, 4, 3, 16),
                                          (3, 50, 40, 2, 5, 8)])
def test_hist_score_matches_dense(rng, b, h, d, nh, c, q):
    x = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    cand = jnp.asarray(rng.standard_normal((b, c, d)), jnp.float32)
    mask = jnp.asarray((rng.random((b, h)) > 0.3).astype(np.float32))
    ap, pp = _make_user_params(rng, d, q)
    sf, uf = jax.jit(
        lambda x, cd, m: fused_history_score(x, cd, m, ap, pp, nh)
    )(x, cand, mask)
    sd, ud = _dense_hist_score(x, cand, mask, ap, pp, nh)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sd), atol=2e-6)
    np.testing.assert_allclose(np.asarray(uf), np.asarray(ud), atol=2e-6)


def test_hist_score_fully_masked_row_pools_to_zero(rng):
    """attention.py epsilon semantics: a fully-masked history row must
    yield ~0 (weights 0 / (0 + 1e-8)), NOT a uniform attention."""
    b, h, d, nh, c, q = 4, 12, 32, 4, 3, 16
    x = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    cand = jnp.asarray(rng.standard_normal((b, c, d)), jnp.float32)
    mask = jnp.ones((b, h), jnp.float32).at[1, :].set(0.0)
    ap, pp = _make_user_params(rng, d, q)
    sf, uf = fused_history_score(x, cand, mask, ap, pp, nh)
    sd, ud = _dense_hist_score(x, cand, mask, ap, pp, nh)
    assert float(jnp.max(jnp.abs(uf[1]))) < 1e-6
    assert float(jnp.max(jnp.abs(sf[1]))) < 1e-5
    np.testing.assert_allclose(np.asarray(uf), np.asarray(ud), atol=2e-6)
    # and masked-out keys contribute nothing: perturbing them is a no-op
    x2 = x.at[1].add(100.0)
    sf2, uf2 = fused_history_score(x2, cand, mask, ap, pp, nh)
    np.testing.assert_allclose(np.asarray(uf2[1]), np.asarray(uf[1]))


def test_hist_score_grads_match_dense(rng):
    b, h, d, nh, c, q = 4, 9, 24, 3, 3, 12
    x = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    cand = jnp.asarray(rng.standard_normal((b, c, d)), jnp.float32)
    mask = jnp.asarray((rng.random((b, h)) > 0.2).astype(np.float32))
    mask = mask.at[:, 0].set(1.0)
    ap, pp = _make_user_params(rng, d, q)

    def lf(x, cand, ap, pp):
        s, u = fused_history_score(x, cand, mask, ap, pp, nh)
        return jnp.sum(s**2) + jnp.sum(u**2)

    def ld(x, cand, ap, pp):
        s, u = _dense_hist_score(x, cand, mask, ap, pp, nh)
        return jnp.sum(s**2) + jnp.sum(u**2)

    gf = jax.jit(jax.grad(lf, argnums=(0, 1, 2, 3)))(x, cand, ap, pp)
    gd = jax.grad(ld, argnums=(0, 1, 2, 3))(x, cand, ap, pp)
    for (kp, a), (_, b_) in zip(
        jtu.tree_leaves_with_path(gf), jtu.tree_leaves_with_path(gd)
    ):
        path = jtu.keystr(kp)
        if "att_fc2']['bias" in path:
            np.testing.assert_allclose(np.asarray(a), 0.0)
            continue
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=5e-5, err_msg=path
        )


def test_hist_score_bf16_banded(rng):
    b, h, d, nh, c, q = 4, 20, 40, 4, 5, 16
    x32 = rng.standard_normal((b, h, d)).astype(np.float32)
    cand32 = rng.standard_normal((b, c, d)).astype(np.float32)
    mask = jnp.asarray((rng.random((b, h)) > 0.2).astype(np.float32))
    ap, pp = _make_user_params(rng, d, q)
    sf, uf = fused_history_score(
        jnp.asarray(x32, jnp.bfloat16), jnp.asarray(cand32, jnp.bfloat16),
        mask, ap, pp, nh,
    )
    assert sf.dtype == jnp.bfloat16 and uf.dtype == jnp.bfloat16
    sd, ud = _dense_hist_score(
        jnp.asarray(x32), jnp.asarray(cand32), mask, ap, pp, nh
    )
    np.testing.assert_allclose(
        np.asarray(sf, np.float32), np.asarray(sd), atol=0.15, rtol=0.05
    )
    np.testing.assert_allclose(
        np.asarray(uf, np.float32), np.asarray(ud), atol=0.1, rtol=0.05
    )


def test_fused_user_vector_matches_encode_user(rng):
    """The serving entry (no candidates) returns the same user vector the
    module's encode_user produces — serve.py reuses kernel (2) through it."""
    from fedrec_tpu.config import ModelConfig
    from fedrec_tpu.models import NewsRecommender

    cfg_d = ModelConfig(news_dim=32, num_heads=4, head_dim=8, query_dim=16,
                        bert_hidden=48)
    cfg_f = ModelConfig(news_dim=32, num_heads=4, head_dim=8, query_dim=16,
                        bert_hidden=48, fuse_hot_path=True)
    his = jnp.asarray(rng.standard_normal((6, 10, 32)), jnp.float32)
    md, mf = NewsRecommender(cfg_d), NewsRecommender(cfg_f)
    toks = jnp.asarray(rng.standard_normal((4, 5, 48)), jnp.float32)
    cand = jnp.asarray(rng.standard_normal((6, 3, 32)), jnp.float32)
    vd = md.init(jax.random.PRNGKey(0), toks, cand, his,
                 method=NewsRecommender.init_both_towers)
    uv_d = md.apply(vd, his, method=NewsRecommender.encode_user)
    uv_f = mf.apply(vd, his, method=NewsRecommender.encode_user)
    np.testing.assert_allclose(
        np.asarray(uv_f), np.asarray(uv_d), atol=3e-6
    )


def test_serve_recommend_parity_fused(rng):
    """serve.py's full-catalog scorer rides the fused user-vector kernel
    when the model fuses — identical top-k to the dense model on the same
    params (the serving reuse contract of DESIGN §5h)."""
    from fedrec_tpu.config import ModelConfig
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.serve import build_recommend_fn

    kw = dict(news_dim=32, num_heads=4, head_dim=8, query_dim=16,
              bert_hidden=48)
    md = NewsRecommender(ModelConfig(**kw))
    mf = NewsRecommender(ModelConfig(fuse_hot_path=True, **kw))
    toks = jnp.asarray(rng.standard_normal((4, 5, 48)), jnp.float32)
    cand = jnp.asarray(rng.standard_normal((2, 3, 32)), jnp.float32)
    his_init = jnp.asarray(rng.standard_normal((2, 6, 32)), jnp.float32)
    v = md.init(jax.random.PRNGKey(0), toks, cand, his_init,
                method=NewsRecommender.init_both_towers)
    news_vecs = jnp.asarray(rng.standard_normal((40, 32)), jnp.float32)
    history = jnp.asarray(rng.integers(1, 40, (3, 6)), jnp.int32)
    rec_d = build_recommend_fn(md, top_k=5)
    rec_f = build_recommend_fn(mf, top_k=5)
    ids_d, sc_d = rec_d(v["params"]["user_encoder"], news_vecs, history)
    ids_f, sc_f = rec_f(v["params"]["user_encoder"], news_vecs, history)
    np.testing.assert_array_equal(np.asarray(ids_f), np.asarray(ids_d))
    np.testing.assert_allclose(np.asarray(sc_f), np.asarray(sc_d), atol=1e-4)


def test_recommender_fused_scores_and_param_tree(rng):
    """NewsRecommender with fuse_hot_path: identical parameter tree
    (checkpoint compatibility) and scoring parity against the dense model
    applying the SAME params."""
    from fedrec_tpu.config import ModelConfig
    from fedrec_tpu.models import NewsRecommender

    kw = dict(news_dim=32, num_heads=4, head_dim=8, query_dim=16,
              bert_hidden=48)
    md = NewsRecommender(ModelConfig(**kw))
    mf = NewsRecommender(ModelConfig(fuse_hot_path=True, **kw))
    toks = jnp.asarray(rng.standard_normal((4, 5, 48)), jnp.float32)
    cand = jnp.asarray(rng.standard_normal((6, 3, 32)), jnp.float32)
    his = jnp.asarray(rng.standard_normal((6, 10, 32)), jnp.float32)
    vd = md.init(jax.random.PRNGKey(0), toks, cand, his,
                 method=NewsRecommender.init_both_towers)
    vf = mf.init(jax.random.PRNGKey(0), toks, cand, his,
                 method=NewsRecommender.init_both_towers)
    assert jtu.tree_structure(vd) == jtu.tree_structure(vf)
    for a, b in zip(jtu.tree_leaves(vd), jtu.tree_leaves(vf)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    sd = md.apply(vd, cand, his)
    sf = mf.apply(vd, cand, his)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sd), atol=3e-6)


def test_fuse_invalid_combos_fail_fast():
    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.models import NewsRecommender

    cfg = ExperimentConfig()
    cfg.model.fuse_hot_path = True
    cfg.model.user_tower = "gru"
    with pytest.raises(ValueError, match="fuse_hot_path"):
        NewsRecommender(cfg.model).setup_called = None  # force setup
        NewsRecommender(cfg.model).init(
            jax.random.PRNGKey(0), jnp.zeros((2, 3, 400))
        )

    cfg2 = small_cfg(model__fuse_hot_path=True)
    cfg2.privacy.enabled = True
    cfg2.privacy.mechanism = "dpsgd"
    cfg2.privacy.sigma = 1.0
    mesh = client_mesh(8)
    from fedrec_tpu.models import NewsRecommender as NR

    with pytest.raises(NotImplementedError, match="fuse_hot_path"):
        build_fed_train_step(
            NR(cfg2.model), cfg2, get_strategy("grad_avg"), mesh,
            mode="joint",
        )


# ----------------------------------------------------- trajectory pinning
# Leaves whose gradient is MATHEMATICALLY zero (ops/fused_hot_path ledger):
# the key-projection bias shifts every score in a softmax row uniformly,
# and the pool fc2 bias is a softmax-invariant constant shift. On any path
# their "gradient" is pure float-cancellation noise, which Adam amplifies
# to noise-scale values — so they are pinned at a noise bound instead of
# the tight tolerance (the fused kernels' noise differs from XLA's).
_ZERO_GRAD_LEAVES = ("w_k']['bias", "att_fc2']['bias")


def _assert_trees_match(tree_a, tree_b, rtol, atol, noise_bound=1e-3):
    for (kp, a), (_, b) in zip(
        jtu.tree_leaves_with_path(tree_a), jtu.tree_leaves_with_path(tree_b)
    ):
        path = jtu.keystr(kp)
        if any(z in path for z in _ZERO_GRAD_LEAVES):
            assert float(np.max(np.abs(np.asarray(a) - np.asarray(b)))) < \
                noise_bound, path
            continue
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=rtol, atol=atol, err_msg=path
        )


def _fused_dense_setups(**over):
    cfg_d = small_cfg(optim__user_lr=3e-3, optim__news_lr=3e-3, **over)
    cfg_f = small_cfg(
        optim__user_lr=3e-3, optim__news_lr=3e-3,
        model__fuse_hot_path=True, **over,
    )
    sd = make_setup(cfg_d, seed=0)
    sf = make_setup(cfg_f, seed=0)
    return cfg_d, cfg_f, sd, sf


def test_fused_step_trajectory_matches_dense():
    """3 federated train steps, fused vs dense: losses to float roundoff;
    params tight except the documented zero-gradient noise leaves (key
    bias / fc2 bias), covered by the absolute tolerance."""
    cfg_d, cfg_f, (_, batcher, toks, md, st_d, mesh), (_, _, _, mf, st_f, _) \
        = _fused_dense_setups()
    step_d = build_fed_train_step(md, cfg_d, get_strategy("grad_avg"), mesh,
                                  mode="joint")
    step_f = build_fed_train_step(mf, cfg_f, get_strategy("grad_avg"), mesh,
                                  mode="joint")
    n = 0
    for b in batcher.epoch_batches_sharded(8, 0):
        sb = shard_batch(mesh, _batch_dict(b))
        st_d, m_d = step_d(st_d, sb, toks)
        st_f, m_f = step_f(st_f, sb, toks)
        np.testing.assert_allclose(
            np.asarray(m_d["loss"]), np.asarray(m_f["loss"]),
            rtol=1e-5, atol=1e-6,
        )
        n += 1
        if n >= 3:
            break
    _assert_trees_match(st_d.user_params, st_f.user_params, 2e-4, 1e-4)
    _assert_trees_match(st_d.news_params, st_f.news_params, 2e-4, 1e-4)


# ------------------------------------------------------------- VMEM model
def test_fused_gather_vmem_model_fits_and_is_independent_of_unique():
    """The acceptance pin: the fused gather kernel's traced VMEM working
    set reports fits=True at bf16 flagship shapes — a BlockSpec or
    block-size regression fails HERE, on CPU, without hardware."""
    from fedrec_tpu.ops.attention_kernels import VMEM_BYTES
    from fedrec_tpu.ops.fused_hot_path import fused_gather_encode_vmem_working_set

    gather = fused_gather_encode_vmem_working_set(
        unique=4096, title=50, bert_hidden=768, news_dim=400,
        dtype=jnp.bfloat16,
    )
    assert gather["fits"], (
        f"fused gather kernel working set {gather['worst']/1e6:.1f} MB "
        f"exceeds the {VMEM_BYTES/1e6:.0f} MB budget"
    )
    # the layout's whole point: ONE table row per program, so the working
    # set is independent of how many unique ids the step gathers
    g2 = fused_gather_encode_vmem_working_set(
        unique=256, title=50, bert_hidden=768, news_dim=400,
        dtype=jnp.bfloat16,
    )
    assert g2["worst"] == gather["worst"]


@pytest.mark.xfail(
    strict=True,
    reason="the kernel file's own VMEM model puts the fused score backward "
    "at 18.9 MB against the 16 MiB (16.8 MB) budget at B=1024: "
    "model.fuse_hot_path cannot train on the chip (ROADMAP.md C9); D3 "
    "decides whether the backward is rewritten or the kernel deleted, and "
    "either way takes this mark away",
)
def test_fused_score_vmem_model_fits_at_flagship_scale():
    """The same pin for the fused score kernel at B=1024 / bf16."""
    from fedrec_tpu.ops.attention_kernels import VMEM_BYTES
    from fedrec_tpu.ops.fused_hot_path import fused_score_vmem_working_set

    score = fused_score_vmem_working_set(
        batch=1024, his=50, news_dim=400, cands=5, num_heads=20,
        query_dim=200, dtype=jnp.bfloat16,
    )
    assert score["fits"], (
        f"fused score kernel working set {score['worst']/1e6:.1f} MB "
        f"exceeds the {VMEM_BYTES/1e6:.0f} MB budget"
    )


# ------------------------------------------ evidence-driven attn_impl=auto
def _write_evidence(tmp_path, rows, jax_version=None):
    import json
    from importlib import metadata

    p = tmp_path / "pallas_bench.json"
    p.write_text(json.dumps({
        "platform": "tpu",
        "rows": rows,
        "provenance": {
            "runtime_versions": {
                "jax": jax_version or metadata.version("jax")
            }
        },
    }))
    return p


def test_autotune_picks_measured_winner(tmp_path):
    from fedrec_tpu.ops.autotune import measured_attn_impl

    p = _write_evidence(tmp_path, [
        {"op": "attention fwd+bwd", "H": 50,
         "xla_ms": 0.12, "pallas_ms": 2.9, "chunked_ms": 0.22},
        {"op": "attention fwd+bwd", "H": 2048,
         "xla_ms": None, "pallas_ms": 255.0, "chunked_ms": 299.0},
    ])
    assert measured_attn_impl(50, jnp.float32, path=p, backend="tpu") == "dense"
    # nearest regime: H=2048 row, where pallas is the measured winner
    assert measured_attn_impl(2048, jnp.float32, path=p, backend="tpu") == "pallas"
    assert measured_attn_impl(4096, jnp.float32, path=p, backend="tpu") == "pallas"
    # a DENSE win never extrapolates UPWARD in H: the score tensor is
    # O(L^2), so feasibility at the row's H says nothing at ~2x H —
    # evidence applies at its own H and below only
    assert measured_attn_impl(90, jnp.float32, path=p, backend="tpu") is None
    assert measured_attn_impl(30, jnp.float32, path=p, backend="tpu") == "dense"
    # 50 vs 1024: no row within 2x -> no evidence
    assert measured_attn_impl(400, jnp.float32, path=p, backend="tpu") is None
    # dtype regime: rows are untagged (float32); bf16 has no evidence
    assert measured_attn_impl(50, jnp.bfloat16, path=p, backend="tpu") is None
    # off-TPU the evidence never applies (tier-1 determinism)
    assert measured_attn_impl(50, jnp.float32, path=p, backend="cpu") is None


def test_autotune_rejects_unclean_provenance(tmp_path):
    from fedrec_tpu.ops.autotune import measured_attn_impl

    rows = [{"op": "attention fwd+bwd", "H": 50,
             "xla_ms": 0.12, "pallas_ms": 0.05, "chunked_ms": None}]
    stale = _write_evidence(tmp_path, rows, jax_version="0.0.1")
    assert measured_attn_impl(50, jnp.float32, path=stale, backend="tpu") is None
    # partial artifacts (mid-wedge stamps) are not evidence either
    import json

    clean = _write_evidence(tmp_path, rows)
    payload = json.loads(clean.read_text())
    clean.write_text(json.dumps({"partial": True, **payload}))
    assert measured_attn_impl(50, jnp.float32, path=clean, backend="tpu") is None


def test_mha_auto_uses_evidence(tmp_path, rng, monkeypatch):
    """attn_impl='auto' routes through the measured winner when evidence
    applies: pin by making pallas the (fake) winner at H=50 and checking
    the module output matches the forced-pallas path bit-for-bit."""
    from fedrec_tpu.models.attention import MultiHeadAttention
    from fedrec_tpu.ops import autotune

    p = _write_evidence(tmp_path, [
        {"op": "attention fwd+bwd", "H": 48,
         "xla_ms": 5.0, "pallas_ms": 0.1, "chunked_ms": None},
    ])
    autotune._resolve.cache_clear()
    orig = autotune.measured_attn_impl
    monkeypatch.setattr(
        autotune,
        "measured_attn_impl",
        lambda seq_len, dtype, **kw: orig(
            seq_len, dtype, path=p, backend="tpu"
        ),
    )
    x = jnp.asarray(rng.standard_normal((2, 48, 32)), jnp.float32)
    auto = MultiHeadAttention(num_heads=4, head_dim=8, attn_impl="auto")
    forced = MultiHeadAttention(num_heads=4, head_dim=8, attn_impl="pallas")
    params = forced.init(jax.random.PRNGKey(0), x, x, x)
    out_auto = auto.apply(params, x, x, x)
    out_forced = forced.apply(params, x, x, x)
    np.testing.assert_array_equal(np.asarray(out_auto), np.asarray(out_forced))


# ----------------------------------------------------------- shared timer
def test_chain_timer_policies():
    from fedrec_tpu.utils.chain_timer import differenced_chain_seconds

    # well-behaved chain: returns per-op once the delta clears the target
    calls = []

    def chain(k):
        calls.append(k)
        return 0.01 + k * 0.02  # 20ms/op + fixed 10ms RTT

    assert abs(differenced_chain_seconds(chain, 10) - 0.02) < 1e-12

    # a fast op grows the chain to the cap; the strict policy (bench.py)
    # refuses a sub-target delta there — a 0.1 ms op cannot clear the
    # 0.3 s floor at 2000 iters, and accepting it would be the clamp the
    # protocol replaced...
    def fast_chain(k):
        return 0.05 + k * 1e-4

    with pytest.raises(RuntimeError, match="jitter floor"):
        differenced_chain_seconds(fast_chain, 10)
    # ...while the cap-accepting policy (pallas_bench op chains) takes it
    per = differenced_chain_seconds(
        fast_chain, 10, attempts=6, accept_positive_at_cap=True
    )
    assert abs(per - 1e-4) < 1e-9

    # strict policy raises when the floor is never cleared
    def jitter(k):
        return 0.05  # delta == 0 forever

    with pytest.raises(RuntimeError, match="jitter floor"):
        differenced_chain_seconds(jitter, 10, attempts=3)

    # ...but the accept-at-cap policy returns the last POSITIVE reading on
    # attempt exhaustion even below the cap (the old pallas_bench
    # semantics: raise only on a non-positive delta) — a jittery window
    # banks its best reading instead of nulling the evidence row
    calls = {"n": 0}

    def sub_target(k):  # delta stuck at 0.15 < target on every attempt
        calls["n"] += 1
        return 0.1 if calls["n"] % 2 == 1 else 0.25

    per = differenced_chain_seconds(
        sub_target, 10, attempts=2, accept_positive_at_cap=True
    )
    assert per > 0
    with pytest.raises(RuntimeError, match="jitter floor"):
        differenced_chain_seconds(sub_target, 10, attempts=2)
    with pytest.raises(RuntimeError, match="jitter floor"):
        differenced_chain_seconds(
            jitter, 10, attempts=2, accept_positive_at_cap=True
        )

    # ...and the cap-accepting policy (pallas_bench) returns a positive
    # sub-target delta at the iteration cap instead of raising
    def capped(k):
        return 0.01 + k * 1e-5

    per = differenced_chain_seconds(
        capped, 1999, attempts=6, accept_positive_at_cap=True
    )
    assert abs(per - 1e-5) < 1e-9
