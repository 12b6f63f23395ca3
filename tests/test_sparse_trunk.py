"""The sparse-expert decoder trunk (``models/sparse_trunk.py``) against the
benchmark's plain reference (``chipbench/reference_moe_trunk.py``: float32
``jax.numpy``, dense loop over experts; no second copy lives here), on seeded
random weights at tiny widths, and through the ``Trainer``."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import reference_moe_trunk as ref  # noqa: E402
from fedrec_tpu.models import sparse_trunk  # noqa: E402
from fedrec_tpu.models.bert import TextEncoder  # noqa: E402
from fedrec_tpu.models.sparse_trunk import SparseTrunkConfig  # noqa: E402

# one period (global, sliding, sliding, sliding) plus a second global layer;
# a window shorter than the titles, so mask, rotary and no-position all bind
TINY = dict(vocab_size=400, dim=32, n_layers=5, n_heads=4, n_kv_heads=2, head_dim=8,
            n_experts=8, experts_per_token=3, expert_dim=16, sliding_window=3)
TITLES, LENGTH = 6, 8


def trunk_dict(cfg: SparseTrunkConfig) -> dict:
    """The reference's trunk group from the program's configuration."""
    return {
        "dim": cfg.dim, "layers": cfg.n_layers, "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim, "experts": cfg.n_experts,
        "experts_per_token": cfg.experts_per_token, "expert_dim": cfg.expert_dim,
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "sliding_window": cfg.sliding_window, "global_every": cfg.global_every,
        "first_expert": cfg.first_expert, "experts_held": cfg.experts_held,
        "vocab_first": cfg.vocab_first, "vocab_held": cfg.vocab_held,
    }


def tokens(seed=0, vocab=300, pad=False):
    rng = np.random.default_rng(seed)
    mask = np.ones((TITLES, LENGTH), int)
    if pad:
        mask[1, 5:] = mask[4, 3:] = 0                  # tail-padded titles
    return jnp.asarray(np.stack([rng.integers(0, vocab, (TITLES, LENGTH)), mask], 1), jnp.int32)


def encoder_and_params(cfg, dtype="float32", seed=0, remat=True):
    te = TextEncoder(trunk_cfg=cfg, news_dim=16, dtype=jnp.dtype(dtype), remat=remat)
    params = te.init(jax.random.PRNGKey(seed), tokens())["params"]
    # norm scales off 1, so that each carries a gradient of its own
    params = jax.tree_util.tree_map(
        lambda x: x + 0.1 * jax.random.normal(jax.random.PRNGKey(x.size), x.shape), params)
    return te, params


def loss_of(fn):
    return lambda p, toks: jnp.sum(fn(p, toks).astype(jnp.float32) ** 2)


def grad_of(fn):
    """One compiled program (run op by op, each op of five layers and their
    backward compiles on its own)."""
    return jax.jit(jax.grad(loss_of(fn)))


def rel_gaps(a, b):
    """Per leaf ||a - b|| / ||b||, leaves whose reference is noise left out."""
    scale = max(float(jnp.linalg.norm(x)) for x in jax.tree_util.tree_leaves(b))
    gaps = jax.tree_util.tree_map(
        lambda x, y: float(jnp.linalg.norm(x - y) / jnp.linalg.norm(y))
        if float(jnp.linalg.norm(y)) > 1e-6 * scale else 0.0, a, b)
    return jax.tree_util.tree_leaves(gaps)


@pytest.mark.parametrize("pad", [False, True])
def test_forward_loss_and_gradients_match_the_reference_in_float32(pad):
    cfg = SparseTrunkConfig(**TINY, first_expert=2, experts_held=4, vocab_held=300)
    te, params = encoder_and_params(cfg)
    toks = tokens(pad=pad)
    t = trunk_dict(cfg)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: ref.encode_news(p, x, t))(params, toks)
        got = jax.jit(lambda p, x: te.apply({"params": p}, x))(params, toks)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        g_want = grad_of(lambda p, x: ref.encode_news(p, x, t))(params, toks)
        g_got = grad_of(lambda p, x: te.apply({"params": p}, x))(params, toks)
    # float32 both sides, other orders of summation: 1e-4 of a leaf's norm
    assert max(rel_gaps(g_got, g_want)) < 1e-4


def test_bfloat16_stays_near_the_reference():
    """bfloat16 compute rounds every activation to 8 bits of mantissa
    (2^-8 = 0.4%) over five layers, and a near-tie in a router moves a token
    to another expert; readings on seeds 0-5 at these widths: vectors within
    3.5% of the largest, gradients' leaves within 21% (a router's, the
    smallest leaf), 2-6% for the others. A float32 program reads 1e-6, a
    wrong mask or a dropped expert 20-100% of the vectors (next test)."""
    cfg = SparseTrunkConfig(**TINY, first_expert=2, experts_held=4, vocab_held=300)
    te, params = encoder_and_params(cfg, "bfloat16")
    toks, t = tokens(), trunk_dict(cfg)
    want = ref.encode_news(params, toks, t)
    got = te.apply({"params": params}, toks).astype(jnp.float32)
    assert got.dtype == jnp.float32 and float(jnp.max(jnp.abs(got - want))) < 0.06 * float(jnp.max(jnp.abs(want)))
    g_want = grad_of(lambda p, x: ref.encode_news(p, x, t))(params, toks)
    g_got = grad_of(lambda p, x: te.apply({"params": p}, x))(params, toks)
    assert all(x.dtype == jnp.float32 for x in jax.tree_util.tree_leaves(g_got))
    gaps = rel_gaps(g_got, g_want)
    assert max(gaps) < 0.4 and float(np.median(gaps)) < 0.08


@pytest.mark.parametrize("fault", ["drop_last_choice", "rotary_everywhere", "ignore_window"])
def test_mask_rotary_and_choice_faults_are_seen(fault):
    """A window smaller than the titles and two global layers in one model:
    each planted fault moves the vectors by far more than rounding does."""
    cfg = SparseTrunkConfig(**TINY, first_expert=2, experts_held=4, vocab_held=300)
    te, params = encoder_and_params(cfg)
    toks, t = tokens(), trunk_dict(cfg)
    got = te.apply({"params": params}, toks)
    faulty = ref.encode_news(params, toks, t, fault=fault)
    assert float(jnp.max(jnp.abs(got - faulty))) > 0.1 * float(jnp.max(jnp.abs(got)))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """One layer whole against its four shares (2 of 8 experts each).
    Attention, router and norms are computed alike by every share and count
    once; what the shares' experts add sums to what the uncut layer's add.
    The uncut layer is the REFERENCE's, the shares are the program's."""
    one_layer = {**TINY, "n_layers": 1}
    whole = SparseTrunkConfig(**one_layer, experts_held=8, vocab_held=300)
    _, params = encoder_and_params(whole, remat=False)
    layer_p = params["trunk"]["layer_0"]
    toks = tokens()
    x = params["trunk"]["embedding"][toks[:, 0]]
    mask = toks[:, 1]

    def share_output(first, held):
        cfg = SparseTrunkConfig(**one_layer, first_expert=first, experts_held=held, vocab_held=300)
        p = {**layer_p, "experts": jax.tree_util.tree_map(
            lambda w: w[first: first + held], layer_p["experts"])}
        return sparse_trunk._DecoderLayer(cfg, cfg.is_global(0)).apply({"params": p}, x, mask)

    with jax.default_matmul_precision("highest"):
        uncut = ref.decoder_layer(layer_p, x, mask, trunk_dict(whole), 0, lambda v: v, None)
        # x + attention, what every share computes alike: a share whose
        # experts' weights are nought adds nothing to it
        idle = {**layer_p, "experts": jax.tree_util.tree_map(jnp.zeros_like, layer_p["experts"])}
        alike, *_ = sparse_trunk._DecoderLayer(whole, True).apply({"params": idle}, x, mask)
        total, pairs = alike, 0
        for rank in range(4):
            out, counts, _ = share_output(2 * rank, 2)
            total = total + (out - alike)
            pairs += int(jnp.sum(counts))
    np.testing.assert_allclose(total, uncut, rtol=2e-5, atol=2e-5)
    # every (token, choice) pair fell on exactly one share's experts
    assert pairs == TITLES * LENGTH * whole.experts_per_token


def dominated(params, cfg, columns):
    """Feature 0 dominates every token's embedding, so after the norm it is
    the same large positive number for all; the first layer's router reads
    only that feature and ranks the experts alike for every token,
    ``columns`` first."""
    emb = np.asarray(params["trunk"]["embedding"]).copy()
    emb[:, 0] = 50.0
    router = np.zeros((cfg.dim, cfg.n_experts), np.float32)
    router[0, columns] = np.arange(len(columns), 0, -1, dtype=np.float32)
    layer0 = {**params["trunk"]["layer_0"], "router": jnp.asarray(router),
              "attn_norm": {"scale": jnp.ones((cfg.dim,))}}
    return {**params, "trunk": {**params["trunk"], "embedding": jnp.asarray(emb), "layer_0": layer0}}


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    """Every token's top choices are the same held experts (a router with one
    dominant column each): all T x k pairs land on them, none is dropped."""
    cfg = SparseTrunkConfig(**{**TINY, "n_layers": 1}, first_expert=0, experts_held=4, vocab_held=300)
    te, params = encoder_and_params(cfg)
    toks, t = tokens(), trunk_dict(cfg)
    params = dominated(params, cfg, [0, 1, 2])
    _, sown = te.apply({"params": params}, toks, mutable=["routing"])
    counts = np.asarray(sown["routing"]["expert_tokens"][0])
    assert counts.tolist() == [[TITLES * LENGTH] * 3 + [0]]
    assert float(sown["routing"]["absent_share"][0]) == 0.0
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(te.apply({"params": params}, toks),
                                   ref.encode_news(params, toks, t), rtol=2e-5, atol=2e-5)


def test_chunked_expert_layer_equals_the_unchunked(monkeypatch):
    cfg = SparseTrunkConfig(**TINY, first_expert=2, experts_held=4, vocab_held=300)
    te, params = encoder_and_params(cfg)
    toks = tokens()
    f = loss_of(lambda p, x: te.apply({"params": p}, x))
    whole, g_whole = jax.value_and_grad(f)(params, toks)
    monkeypatch.setattr(sparse_trunk, "MAX_CHUNK_TOKENS", 16)      # 48 tokens: 3 chunks
    assert sparse_trunk._chunks(TITLES * LENGTH) == 3
    chunked, g_chunked = jax.value_and_grad(f)(params, toks)
    np.testing.assert_allclose(chunked, whole, rtol=1e-6)
    assert max(rel_gaps(g_chunked, g_whole)) < 1e-5


# ------------------------------------------- the sorted buffer's two sizes
QUARTER = dict(first_expert=2, experts_held=2, vocab_held=300)     # 2 of 8 held


def small_tiles(monkeypatch):
    """Tiles small enough for the buffer to have two sizes at test widths:
    48 tokens x 3 choices = 144 pairs, full 144 rows, small 72 (twice the
    even share of a quarter of the experts)."""
    monkeypatch.setattr(sparse_trunk, "ROW_TILE", 8)


def test_buffer_rows_follow_the_share_held():
    # the two routed cells' chunks: 11,000 x 6 at 16 of 64, 5,500 x 4 at 8 of 64
    assert sparse_trunk.buffer_rows(66_000, 16, 64) == (33_280, 66_048)
    assert sparse_trunk.buffer_rows(22_000, 8, 64) == (5_632, 22_016)
    # half the experts or more held: one size
    assert sparse_trunk.buffer_rows(66_000, 32, 64) == (66_048, 66_048)
    assert sparse_trunk.buffer_rows(66_000, 64, 64) == (66_048, 66_048)
    assert sparse_trunk.buffer_rows(144, 4, 8) == (512, 512)


@pytest.mark.parametrize("chunk_tokens", [None, 16], ids=["unchunked", "chunked"])
def test_the_small_buffer_equals_the_full_one(monkeypatch, chunk_tokens):
    """2 of 8 experts held: loss and gradients through the small buffer (72
    rows, a conditional) equal those with the buffer forced to its full size
    (144 rows, today's program), whole and in 3 chunks."""
    small_tiles(monkeypatch)
    if chunk_tokens:
        monkeypatch.setattr(sparse_trunk, "MAX_CHUNK_TOKENS", chunk_tokens)
    cfg = SparseTrunkConfig(**TINY, **QUARTER)
    te, params = encoder_and_params(cfg)
    toks = tokens()
    pairs = (chunk_tokens or TITLES * LENGTH) * 3
    assert sparse_trunk.buffer_rows(pairs, 2, 8) == (pairs // 2, pairs)

    def f(p, x):
        vecs, sown = te.apply({"params": p}, x, mutable=["routing"])
        return jnp.sum(vecs ** 2), sown["routing"]

    # a function of its own for each trace: one traced before the constant
    # changed would be found again
    (small, routing), g_small = jax.value_and_grad(f, has_aux=True)(params, toks)
    assert "cond" in str(jax.make_jaxpr(lambda p, x: f(p, x))(params, toks))
    # random routers at 5 layers: some chunk may overflow, most do not
    assert int(routing["full_size_chunks"][0]) < 5 * (3 if chunk_tokens else 1)
    monkeypatch.setattr(sparse_trunk, "EVEN_SHARE_ROOM", 4)       # room for every pair: one size
    assert "cond" not in str(jax.make_jaxpr(lambda p, x: f(p, x))(params, toks))
    (full, routing_full), g_full = jax.value_and_grad(f, has_aux=True)(params, toks)
    assert int(routing_full["full_size_chunks"][0]) == 0
    np.testing.assert_array_equal(routing["expert_tokens"][0], routing_full["expert_tokens"][0])
    np.testing.assert_allclose(small, full, rtol=1e-6)
    assert max(rel_gaps(g_small, g_full)) < 1e-5


@pytest.mark.parametrize("chunk_tokens", [None, 16], ids=["unchunked", "chunked"])
def test_a_chunk_that_overflows_the_small_buffer_runs_at_the_full_size(monkeypatch, chunk_tokens):
    """Every token's top two choices are the two held experts: 96 of the 144
    pairs land here, more than the small buffer's 72 rows. Output and
    gradients still equal the float32 reference, every pair is counted and
    ``full_size_chunks`` counts the chunks."""
    small_tiles(monkeypatch)
    if chunk_tokens:
        monkeypatch.setattr(sparse_trunk, "MAX_CHUNK_TOKENS", chunk_tokens)
    cfg = SparseTrunkConfig(**{**TINY, "n_layers": 1}, **QUARTER)
    te, params = encoder_and_params(cfg)
    params = dominated(params, cfg, [2, 3, 5])
    toks, t = tokens(), trunk_dict(cfg)
    with jax.default_matmul_precision("highest"):
        got, sown = jax.jit(lambda p, x: te.apply({"params": p}, x, mutable=["routing"]))(params, toks)
        np.testing.assert_allclose(got, ref.encode_news(params, toks, t), rtol=2e-5, atol=2e-5)
        g_want = grad_of(lambda p, x: ref.encode_news(p, x, t))(params, toks)
        g_got = grad_of(lambda p, x: te.apply({"params": p}, x))(params, toks)
    # one dominant feature leaves attention's q and k a gradient of near
    # cancellation: 1.2e-4 at either size of the buffer, the other leaves 3e-5
    assert max(rel_gaps(g_got, g_want)) < 5e-4
    assert np.asarray(sown["routing"]["expert_tokens"][0]).tolist() == [[TITLES * LENGTH] * 2]
    assert int(sown["routing"]["full_size_chunks"][0]) == (3 if chunk_tokens else 1)


def test_an_even_router_stays_in_the_small_buffer(monkeypatch):
    """Every token's choices are experts 0, 2, 4: one pair in three on a held
    expert (2), 48 rows of the small buffer's 72."""
    small_tiles(monkeypatch)
    cfg = SparseTrunkConfig(**{**TINY, "n_layers": 1}, **QUARTER)
    te, params = encoder_and_params(cfg)
    params = dominated(params, cfg, [0, 2, 4])
    toks, t = tokens(), trunk_dict(cfg)
    with jax.default_matmul_precision("highest"):
        got, sown = te.apply({"params": params}, toks, mutable=["routing"])
        np.testing.assert_allclose(got, ref.encode_news(params, toks, t), rtol=2e-5, atol=2e-5)
    assert np.asarray(sown["routing"]["expert_tokens"][0]).tolist() == [[TITLES * LENGTH, 0]]
    assert int(sown["routing"]["full_size_chunks"][0]) == 0


@pytest.mark.parametrize("held", [4, 8])
def test_half_the_experts_or_more_lower_without_a_conditional(monkeypatch, held):
    small_tiles(monkeypatch)
    cfg = SparseTrunkConfig(**TINY, experts_held=held, vocab_held=300)
    te, params = encoder_and_params(cfg)
    text = grad_of(lambda p, x: te.apply({"params": p}, x)).lower(params, tokens()).as_text()
    assert "stablehlo.case" not in text and "stablehlo.if" not in text


def test_a_cohort_under_vmap_runs_both_sizes_and_stays_exact(monkeypatch):
    """Two clients under ``vmap``, one whose router overflows the small
    buffer and one whose does not: the conditional is a select, each
    client's output and gradient are what it gets alone."""
    small_tiles(monkeypatch)
    cfg = SparseTrunkConfig(**{**TINY, "n_layers": 1}, **QUARTER)
    te, params = encoder_and_params(cfg)
    clients = [dominated(params, cfg, [2, 3, 5]), dominated(params, cfg, [0, 2, 4])]
    stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *clients)
    toks = tokens()

    def f(p):
        vecs, sown = te.apply({"params": p}, toks, mutable=["routing"])
        return jnp.sum(vecs ** 2), sown["routing"]["full_size_chunks"][0]

    (loss, full), grads = jax.vmap(jax.value_and_grad(f, has_aux=True))(stacked)
    assert full.tolist() == [1, 0]
    for i, alone in enumerate(clients):
        (want, _), g_want = jax.value_and_grad(f, has_aux=True)(alone)
        np.testing.assert_allclose(loss[i], want, rtol=1e-6)
        # batched products sum in another order: 3e-5 on the leaves of near
        # cancellation
        assert max(rel_gaps(jax.tree_util.tree_map(lambda x: x[i], grads), g_want)) < 2e-4


# ------------------------------ the combine and the gathers, choice by choice
def pair_order_case(k, tokens_=13, d=8, held=3, experts=8, cut=16, seed=0):
    """One chunk's integers as ``held_experts_output`` builds them and random
    float32 rows: 13 tokens (not a multiple of 16), a buffer of ``cut`` rows,
    short of the pairs, so that some ``back`` point past it; ``order`` keeps
    padding entries (pair numbers past T*k) when uncut."""
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.permutation(experts)[:k] for _ in range(tokens_)])
    full = -(-tokens_ * k // 8) * 8 + 8                 # a tile of padding at least
    group = np.where(idx.reshape(-1) < held, idx.reshape(-1), held)
    group = np.pad(group, (0, full - tokens_ * k), constant_values=held)
    order = np.argsort(group, kind="stable")
    back = np.argsort(order)[: tokens_ * k]
    rows = cut or full
    assert (back >= rows).any() == bool(cut) and (order[:rows] >= tokens_ * k).any() != bool(cut)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    p = jax.nn.softmax(f32(tokens_, k), axis=-1)
    return (jnp.asarray(order[:rows], jnp.int32), jnp.asarray(back, jnp.int32),
            f32(rows, d), p, f32(tokens_, d))


def in_pair_order(down, p, u, order, back, k):
    """The same layer with its rows laid out in pair order, written plainly:
    gather every pair's row, (T, k, d), a weighted sum over k; the way into
    expert order as the plain gather whose transpose JAX derives."""
    t, rows = p.shape[0], down.shape[0]
    weight = jnp.where((back < rows).reshape(t, k), p, 0)
    y = jnp.einsum("tkd,tk->td", down[jnp.minimum(back, rows - 1)].reshape(t, k, -1), weight)
    token = jnp.minimum(order // k, t - 1)
    xs = jnp.where((order < t * k)[:, None], u[token], 0)
    return y, xs


@pytest.mark.parametrize("cut", [16, 0], ids=["buffer-cut-short", "padding-in-order"])
@pytest.mark.parametrize("k", [4, 6, 8])
def test_choice_by_choice_equals_the_pair_order_formulation(k, cut):
    """``weighted_sum`` and ``to_expert_order`` with their hand-written
    derivatives against the pair-order formulation and ``jax.grad``, in
    float32: forward, and the gradients with respect to ``down``, ``p`` and
    ``u``. A pair past a buffer cut short adds nothing and takes no gradient;
    a padding entry of ``order`` takes none."""
    order, back, down, p, u = pair_order_case(k, cut=cut, seed=k)
    t = p.shape[0]
    choice_major = back.reshape(t, k).T
    mix = jnp.asarray(np.random.default_rng(1).normal(size=(t, 1)), jnp.float32)
    # padding rows of the buffer are zeroed by the caller (``here``): the
    # plain gather states that itself, so the program's side is held to it
    real = (order < t * k)[:, None]

    def got(down, p, u):
        y = sparse_trunk.weighted_sum(down, p, order, choice_major)
        xs = jnp.where(real, sparse_trunk.to_expert_order(u, order, choice_major), 0)
        return y, xs

    def want(down, p, u):
        return in_pair_order(down, p, u, order, back, k)

    def loss(f):
        def scalar(down, p, u):
            y, xs = f(down, p, u)
            return jnp.sum(mix * y ** 2) + jnp.sum(jnp.cos(xs) * jnp.arange(xs.shape[0])[:, None])
        return scalar

    for a, b in zip(got(down, p, u), want(down, p, u)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    g_got = jax.grad(loss(got), argnums=(0, 1, 2))(down, p, u)
    g_want = jax.grad(loss(want), argnums=(0, 1, 2))(down, p, u)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    rows = down.shape[0]
    past = np.asarray(back.reshape(t, k) >= rows)
    if cut:
        # pairs past the buffer take no weight gradient
        assert past.any() and not np.asarray(g_got[1])[past].any()
    else:
        assert not np.asarray(g_got[0])[np.asarray(order) >= t * k].any()


def test_an_id_outside_the_held_vocabulary_embeds_to_zero():
    cfg = SparseTrunkConfig(**{**TINY, "n_layers": 1}, experts_held=8, vocab_first=100, vocab_held=100)
    trunk = sparse_trunk.SparseExpertTrunk(cfg)
    ids = jnp.asarray([[99, 100, 150, 199, 200, 399, 100, 100]], jnp.int32)
    mask = jnp.ones_like(ids)
    params = trunk.init(jax.random.PRNGKey(0), ids, mask)["params"]
    assert params["embedding"].shape == (100, cfg.dim)
    moved = {**params, "embedding": params["embedding"] + 1.0}
    a, _ = trunk.apply({"params": params}, ids, mask)
    b, _ = trunk.apply({"params": moved}, ids, mask)
    # causal: position 0 (id 99, not held) sees only itself and stays put
    np.testing.assert_array_equal(a[0, 0], b[0, 0])
    assert float(jnp.max(jnp.abs(a[0, 1] - b[0, 1]))) > 0


def test_config_refuses_a_share_that_is_not_the_models():
    with pytest.raises(ValueError, match="experts"):
        SparseTrunkConfig(first_expert=60, experts_held=8)
    with pytest.raises(ValueError, match="vocabulary"):
        SparseTrunkConfig(vocab_first=151000, vocab_held=1000)
    with pytest.raises(ValueError, match="group"):
        SparseTrunkConfig(n_heads=28, n_kv_heads=5)


# ------------------------------------------------------- through the Trainer
def trunk_cfg(clients: int):
    """The normal path at test widths: the family, depth and share through
    ``ExperimentConfig``; key/value heads, head size, the 64 experts and 6 a
    token stay as published."""
    from fedrec_tpu.config import ExperimentConfig

    return ExperimentConfig().apply_overrides([
        "model.text_encoder_mode=finetune", "model.text_trunk=sparse_expert",
        "model.bert_hidden=32", "model.trunk_layers=4", "model.trunk_heads=4",
        "model.trunk_ffn=16", "model.trunk_vocab=2000",
        "model.trunk_first_expert=16", "model.trunk_experts_held=16",
        "model.news_dim=32", "model.num_heads=4", "model.head_dim=8", "model.query_dim=16",
        "data.max_his_len=10", "data.max_title_len=12", "data.batch_size=8",
        f"fed.num_clients={clients}", "fed.strategy=" + ("grad_avg" if clients == 1 else "param_avg"),
        "fed.rounds=1", "train.snapshot_dir=", "train.eval_every=1000",
    ])


def trunk_data(cfg):
    from fedrec_tpu.data import make_synthetic_mind

    return make_synthetic_mind(
        num_news=48, num_train=32, num_valid=8, title_len=cfg.data.max_title_len,
        vocab=2000, his_len_range=(2, cfg.data.max_his_len), seed=0)


@pytest.mark.parametrize("clients,devices", [(1, 1), (2, 2), (2, 1)],
                         ids=["one-client", "one-client-a-device", "in-device-cohort"])
def test_trainer_round_with_the_trunk(clients, devices):
    """One round through ``Trainer``: finite loss, routing counters in the
    registry, and for a ``param_avg`` cohort every client equal to the mean
    of what the clients held before the sync (one client a device, and two
    on one device under the cohort's ``vmap``)."""
    from jax.sharding import Mesh

    from fedrec_tpu.obs.registry import MetricsRegistry, get_registry, set_registry
    from fedrec_tpu.train.trainer import Trainer

    old = get_registry()
    set_registry(MetricsRegistry())
    try:
        cfg = trunk_cfg(clients)
        mesh = Mesh(np.array(jax.devices()[:devices]), (cfg.fed.mesh_axis,))
        trainer = Trainer(cfg, trunk_data(cfg), None, mesh=mesh)
        seen = {}
        sync = trainer.param_sync

        def recording_sync(state, *rest):
            seen["before"] = jax.tree_util.tree_map(np.asarray, state.news_params)
            return sync(state, *rest)

        trainer.param_sync = recording_sync
        result = trainer.train_round(0)
        assert np.isfinite(result.train_loss)
        snap = trainer.registry.snapshot()["metrics"]
        absent = snap["moe.absent_share"]["values"][0]["value"]
        assert 0.4 < absent < 0.95                        # 16 of 64 experts held
        # 3,456 pairs a layer, a quarter of the experts held: the routers'
        # share stays within the small buffer's 2,048 rows of the full 3,584
        assert snap["moe.full_size_chunks_total"]["values"][0]["value"] == 0
        cells = snap["moe.expert_tokens_total"]["values"]
        assert len(cells) == 4 * 16 and {c["labels"]["expert"] for c in cells} == {str(e) for e in range(16, 32)}
        steps = 32 // (8 * clients)
        # the dedup encodes min(slots, catalog) = 48 titles of 12 tokens a
        # client-step; 6 choices a token, 4 layers
        pairs = steps * clients * 48 * 12 * 6 * 4
        routed = sum(c["value"] for c in cells)
        assert routed == pytest.approx(pairs * (1 - absent), rel=1e-3)
        assert snap["moe.expert_load_max_over_mean"]["values"][0]["value"] >= 1.0
        if clients > 1:
            after = jax.tree_util.tree_map(np.asarray, trainer.state.news_params)
            for b, a in zip(jax.tree_util.tree_leaves(seen["before"]), jax.tree_util.tree_leaves(after)):
                np.testing.assert_array_equal(a[0], a[1])
                np.testing.assert_allclose(a[0], b.mean(axis=0), rtol=1e-6, atol=1e-7)
            moved = [float(np.abs(b[0] - b[1]).max()) for b in jax.tree_util.tree_leaves(seen["before"])]
            assert max(moved) > 0                          # the clients had diverged
    finally:
        set_registry(old)


def test_make_text_encoder_chooses_the_trunk():
    from fedrec_tpu.models.bert import DistilBertConfig, make_text_encoder

    cfg = trunk_cfg(1)
    chosen = make_text_encoder(cfg.model).trunk_cfg
    assert isinstance(chosen, SparseTrunkConfig)
    assert (chosen.dim, chosen.n_layers, chosen.n_heads, chosen.expert_dim) == (32, 4, 4, 16)
    assert (chosen.first_expert, chosen.experts_held, chosen.vocab_held) == (16, 16, 2000)
    # what the tests do not shrink is as published
    assert (chosen.n_kv_heads, chosen.head_dim, chosen.n_experts, chosen.experts_per_token) == (4, 128, 64, 6)
    cfg.model.text_trunk = "distilbert"
    assert isinstance(make_text_encoder(cfg.model).trunk_cfg, DistilBertConfig)
    cfg.model.text_trunk = "gru"
    with pytest.raises(ValueError, match="text_trunk"):
        make_text_encoder(cfg.model)
