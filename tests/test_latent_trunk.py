"""The latent-attention decoder trunk with a multi-stream residual
(``models/latent_trunk.py``) against the benchmark's plain reference
(``chipbench/reference_latent_trunk.py``: float32 ``jax.numpy``, dense loop
over experts, Sinkhorn as a Python loop; no second copy lives here), on
seeded random weights at tiny widths, and through the ``Trainer``."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import reference_latent_trunk as ref  # noqa: E402
from fedrec_tpu.models import latent_trunk, sparse_trunk  # noqa: E402
from fedrec_tpu.models.bert import TextEncoder  # noqa: E402
from fedrec_tpu.models.latent_trunk import LatentTrunkConfig  # noqa: E402
from fedrec_tpu.ops import residual_mix  # noqa: E402
from test_sparse_trunk import conditionals  # noqa: E402

# one leading dense layer and two routed ones; a context of 16 positions
# "trained on", so that YaRN's ramp falls inside the four frequencies
TINY = dict(vocab_size=400, dim=64, n_layers=3, n_dense_layers=1, n_heads=4, q_rank=24,
            kv_rank=16, nope_dim=16, rope_dim=8, v_dim=16, dense_dim=96, n_experts=8,
            experts_per_token=2, expert_dim=32, rope_original_max=16)
TITLES, LENGTH = 6, 8


def trunk_dict(c: LatentTrunkConfig) -> dict:
    """The reference's trunk group from the program's configuration."""
    return {
        "dim": c.dim, "layers": c.n_layers, "dense_layers": c.n_dense_layers, "heads": c.n_heads,
        "q_rank": c.q_rank, "kv_rank": c.kv_rank, "nope_dim": c.nope_dim, "rope_dim": c.rope_dim,
        "v_dim": c.v_dim, "dense_dim": c.dense_dim, "experts": c.n_experts,
        "experts_per_token": c.experts_per_token, "expert_dim": c.expert_dim,
        "shared_experts": c.n_shared_experts, "routed_scale": c.routed_scale,
        "streams": c.n_streams, "sinkhorn_iters": c.sinkhorn_iters, "hc_eps": c.hc_eps,
        "res_clamp_min": c.res_clamp[0], "res_clamp_max": c.res_clamp[1],
        "rms_norm_eps": c.rms_norm_eps, "rope_theta": c.rope_theta,
        "rope": {"factor": c.rope_factor, "original_max_position_embeddings": c.rope_original_max,
                 "beta_fast": c.rope_beta_fast, "beta_slow": c.rope_beta_slow,
                 "mscale": c.rope_mscale, "mscale_all_dim": c.rope_mscale_all_dim},
        "first_expert": c.first_expert, "experts_held": c.experts_held,
        "vocab_first": c.vocab_first, "vocab_held": c.vocab_held,
    }


def tokens(seed=0, vocab=300, pad=False):
    rng = np.random.default_rng(seed)
    mask = np.ones((TITLES, LENGTH), int)
    if pad:
        mask[1, 5:] = mask[4, 3:] = 0                  # tail-padded titles
    return jnp.asarray(np.stack([rng.integers(0, vocab, (TITLES, LENGTH)), mask], 1), jnp.int32)


def seeded(params, seed=0):
    """Every leaf moved off its initial value by its own normal(0, 0.1): norm
    scales off 1, mixer maps that differ from token to token, a selection
    bias that changes choices; the mixers' residual bias normal(0, 1), so
    that twenty Sinkhorn iterations have something to do and get it done."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    root = jax.random.PRNGKey(seed)
    out = []
    for i, (path, x) in enumerate(leaves):
        noise = jax.random.normal(jax.random.fold_in(root, i), x.shape)
        name = jax.tree_util.keystr(path)
        if "bias_res" in name:
            out.append(noise)
        elif "alpha" in name:
            out.append(1.0 + 0.3 * noise)
        else:
            out.append(x + 0.1 * noise)
    return jax.tree_util.tree_unflatten(treedef, out)


def encoder_and_params(cfg, dtype="float32", seed=0, remat=True):
    te = TextEncoder(trunk_cfg=cfg, news_dim=16, dtype=jnp.dtype(dtype), remat=remat)
    return te, seeded(te.init(jax.random.PRNGKey(seed), tokens())["params"], seed)


def vecs_and_grad_of(fn):
    """One compiled program for the vectors and the loss's gradient (twenty
    unrolled Sinkhorn iterations a sublayer make a compile the slow part)."""
    def loss(p, toks):
        vecs = fn(p, toks)
        return jnp.sum(vecs.astype(jnp.float32) ** 2), vecs

    return jax.jit(lambda p, toks: jax.grad(loss, has_aux=True)(p, toks)[::-1])


def rel_gaps(a, b):
    """Per leaf ||a - b|| / ||b||, leaves whose reference is under a
    thousandth of the largest leaf's left out as noise: the first
    sublayer's ``pre`` and ``res`` maps among them (every stream holds the
    embedding there, so mixing changes nothing and the norm after ``pre``
    takes its scale away) and the last sublayer's ``res`` (the streams are
    summed after it, and a doubly stochastic matrix keeps the sum)."""
    scale = max(float(jnp.linalg.norm(x)) for x in jax.tree_util.tree_leaves(b))
    gaps = jax.tree_util.tree_map(
        lambda x, y: float(jnp.linalg.norm(x - y) / jnp.linalg.norm(y))
        if float(jnp.linalg.norm(y)) > 1e-3 * scale else 0.0, a, b)
    return jax.tree_util.tree_leaves(gaps)


def held(streams=4, **kw):
    return LatentTrunkConfig(**{**TINY, "n_streams": streams, "first_expert": 2,
                                "experts_held": 4, "vocab_held": 300, **kw})


@pytest.mark.parametrize("streams", [2, 4])
@pytest.mark.parametrize("pad", [False, True])
def test_forward_loss_and_gradients_match_the_reference_in_float32(pad, streams):
    cfg = held(streams)
    te, params = encoder_and_params(cfg)
    toks, t = tokens(pad=pad), trunk_dict(cfg)
    with jax.default_matmul_precision("highest"):
        want, g_want = vecs_and_grad_of(lambda p, x: ref.encode_news(p, x, t))(params, toks)
        got, g_got = vecs_and_grad_of(lambda p, x: te.apply({"params": p}, x))(params, toks)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # float32 both sides, other orders of summation: 2e-4 of a leaf's norm
    assert max(rel_gaps(g_got, g_want)) < 2e-4
    bias = lambda g: g["trunk"]["layer_1_ffn"]["ffn"]["router_bias"]  # noqa: E731
    assert not np.any(bias(g_got)) and not np.any(bias(g_want))   # the choice is not differentiated


def test_bfloat16_stays_near_the_reference(monkeypatch):
    """bfloat16 compute rounds every activation and the four-stream state to
    8 bits of mantissa at every sublayer, and with 48 tokens a near-tie in a
    router that moves one token to another expert moves its title's vector:
    on seeds 0-3 the vectors' worst element reads 5-12% of the largest, the
    median element 0.4-0.7%. A float32 program reads 1e-6; the chip's cell
    holds bfloat16 to its limits at 5,500 tokens, where one token is
    nothing. A moved token moves every gradient behind it (seed 0: token 45
    of the first routed layer takes expert 2 for 3, 0.0043 under the
    reference's own second choice; the median leaf then reads 31%), so the
    gradients are held to the reference routed as the program routed, and
    only where each choice of the program lies within 1e-2 of the
    reference's own: on seeds 0-3 the worst leaf then reads 9-21%, the
    median 4.4-5.4%. (On seeds 0, 1 and 3 the program's lowest choice lies
    at most 0.006 under the reference's; on seed 2 one token of the second
    routed layer lies 0.042 under, where bfloat16 has moved that layer's
    scores by up to 0.041.)"""
    cfg = held()
    te, params = encoder_and_params(cfg, "bfloat16")
    toks, t = tokens(), trunk_dict(cfg)
    # the program's choices, routed layer by routed layer (the rematerialised
    # forward repeats them)
    chosen, route = {}, latent_trunk.route_sigmoid

    def recorded(u, *rest):
        idx, weights = route(u, *rest)
        layer = len(chosen)
        chosen[layer] = None
        jax.debug.callback(lambda i: chosen.__setitem__(layer, np.asarray(i)), idx)
        return idx, weights

    monkeypatch.setattr(latent_trunk, "route_sigmoid", recorded)
    got, g_got = vecs_and_grad_of(lambda p, x: te.apply({"params": p}, x))(params, toks)
    assert len(chosen) == cfg.n_routed_layers
    own_routing = ref.routing

    def reference(p, x, follow, program_chose):
        """The reference choosing its own experts, or (``follow``) the
        program's, one compiled program for both; with the vectors, how far
        under the reference's own k-th biased score each routed layer's
        lowest program choice lies (0 where the two choose alike)."""
        layers, below = iter(program_chose), []

        def routing(p, u, t, fault=None):
            idx = jnp.where(follow, next(layers), own_routing(p, u, t)[0])
            scores = jax.nn.sigmoid(jnp.einsum("td,de->te", u, p["router"], precision=ref.HI))
            biased = scores + p["router_bias"]
            kth = jax.lax.top_k(biased, t["experts_per_token"])[0][:, -1]
            below.append(jnp.max(kth - jnp.min(jnp.take_along_axis(biased, idx, axis=-1), axis=-1)))
            picked = jnp.take_along_axis(scores, idx, axis=-1)
            return idx, t["routed_scale"] * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)

        monkeypatch.setattr(ref, "routing", routing)
        return ref.encode_news(p, x, t), jnp.stack(below)

    program_chose = [chosen[k] for k in sorted(chosen)]
    both = jax.jit(lambda p, x, follow, chose: jax.grad(
        lambda p: (lambda v, b: (jnp.sum(v ** 2), (v, b)))(*reference(p, x, follow, chose)),
        has_aux=True)(p)[::-1])
    (want, _), _ = both(params, toks, False, program_chose)
    (_, below), g_want = both(params, toks, True, program_chose)
    # the reference follows the program only where the program's choice is
    # a near-tie in the reference's own scores
    assert float(jnp.max(below)) < 1e-2
    off = jnp.abs(got.astype(jnp.float32) - want)
    largest = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(off)) < 0.25 * largest and float(jnp.median(off)) < 0.02 * largest
    assert float(jnp.linalg.norm(off)) < 0.15 * float(jnp.linalg.norm(want))
    assert all(x.dtype == jnp.float32 for x in jax.tree_util.tree_leaves(g_got))
    gaps = rel_gaps(g_got, g_want)
    assert max(gaps) < 0.35 and float(np.median(gaps)) < 0.1


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS if f])
def test_each_planted_fault_is_seen(fault):
    cfg = held()
    te, params = encoder_and_params(cfg)
    toks, t = tokens(), trunk_dict(cfg)
    got = jax.jit(lambda p, x: te.apply({"params": p}, x))(params, toks)
    faulty = jax.jit(lambda p, x: ref.encode_news(p, x, t, fault=fault))(params, toks)
    assert float(jnp.max(jnp.abs(got - faulty))) > 0.02 * float(jnp.max(jnp.abs(got)))


def test_one_expert_of_eight_runs_through_the_small_buffer(monkeypatch):
    """1 of 8 experts held, tiles small enough for the sorted buffer to have
    two sizes at test widths (96 pairs a layer: full 96 rows, small 24): the
    trunk agrees with its reference and returns how many of its two routed
    layers ran at the full size."""
    monkeypatch.setattr(sparse_trunk, "ROW_TILE", 8)
    assert sparse_trunk.buffer_rows(TITLES * LENGTH * 2, 1, 8) == (24, 96)
    cfg = held(first_expert=3, experts_held=1)
    te, params = encoder_and_params(cfg)
    toks, t = tokens(), trunk_dict(cfg)
    apply = lambda p, x: te.apply({"params": p}, x, mutable=["routing"])  # noqa: E731
    assert conditionals(jax.make_jaxpr(apply)(params, toks))
    with jax.default_matmul_precision("highest"):
        want, g_want = vecs_and_grad_of(lambda p, x: ref.encode_news(p, x, t))(params, toks)
        got, g_got = vecs_and_grad_of(lambda p, x: te.apply({"params": p}, x))(params, toks)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert max(rel_gaps(g_got, g_want)) < 2e-4
    routing = apply(params, toks)[1]["routing"]
    on_held = np.asarray(routing["expert_tokens"][0]).sum(axis=-1)       # (routed layers,)
    assert int(routing["full_size_chunks"][0]) == int(np.sum(on_held > 24))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One routed feed-forward whole against its eight shares (1 of 8
    experts each). The shared expert is computed alike by every share and
    counts once; what the shares' experts add sums to what the uncut
    layer's add. The uncut layer is the REFERENCE's, the shares are the
    program's."""
    whole = LatentTrunkConfig(**TINY, experts_held=8, vocab_held=300)
    u = jax.random.normal(jax.random.PRNGKey(1), (TITLES, LENGTH, whole.dim))
    ffn = latent_trunk._RoutedFFN(whole)
    p = seeded(ffn.init(jax.random.PRNGKey(0), u)["params"])
    with jax.default_matmul_precision("highest"):
        uncut = ref.routed_ffn(p, u, trunk_dict(whole), lambda v: v, None)
        shared = ref.gated_ffn(p["shared_expert"], u, lambda v: v)
        total, pairs = shared, 0
        for rank in range(8):
            cfg = LatentTrunkConfig(**TINY, first_expert=rank, experts_held=1, vocab_held=300)
            share = {**p, "experts": jax.tree_util.tree_map(lambda w: w[rank: rank + 1], p["experts"])}
            out, counts, _ = latent_trunk._RoutedFFN(cfg).apply({"params": share}, u)
            total = total + (out - shared)
            pairs += int(jnp.sum(counts))
    np.testing.assert_allclose(total, uncut, rtol=2e-5, atol=2e-5)
    assert pairs == TITLES * LENGTH * whole.experts_per_token


@pytest.mark.parametrize("streams", [2, 4])
def test_the_mixing_matrix_is_doubly_stochastic_after_twenty_iterations_not_after_one(streams):
    def sums_off(iters):
        cfg = held(streams, sinkhorn_iters=iters)
        mixer = latent_trunk._Mixer(cfg)
        x = jax.random.normal(jax.random.PRNGKey(2), (40, streams * cfg.dim))
        # pre-Sinkhorn entries exp(normal(0, 0.4)): Sinkhorn contracts by
        # tanh^2 of a quarter of the largest log cross-ratio an iteration,
        # so twenty reach 1e-5 only where that stays under about 4
        p = seeded(mixer.init(jax.random.PRNGKey(0), x)["params"])
        p = {**p, "alpha": jnp.asarray([1.0, 1.0, 0.3]), "bias_res": 0.3 * p["bias_res"],
             "proj_res": mixer.init(jax.random.PRNGKey(0), x)["params"]["proj_res"]}
        _, maps, _, err = mixer.apply({"params": p}, x)
        pre, post, m, _ = residual_mix.unpacked_maps(maps, streams)
        assert m.shape == (40, streams, streams) and pre.shape == post.shape == (40, streams)
        assert float(jnp.min(m)) > 0 and 0 < float(jnp.min(pre)) and float(jnp.max(post)) < 2
        cols, rows = jnp.sum(m, axis=1), jnp.sum(m, axis=2)
        off = float(jnp.maximum(jnp.max(jnp.abs(cols - 1)), jnp.max(jnp.abs(rows - 1))))
        assert float(err) == pytest.approx(off)
        want = ref.mixer_maps(p, x.reshape(40, streams, cfg.dim), trunk_dict(cfg))[2]
        np.testing.assert_allclose(m, want, rtol=1e-5, atol=1e-6)
        return off

    assert sums_off(20) < 1e-5
    assert sums_off(1) > 1e-3


def routed_layer_with_one_dominant_feature(cfg, bias=None):
    """A routed feed-forward whose every input has the same large feature 0
    and whose router reads only it: every token scores the experts alike,
    0 over 1 over 2 over the rest."""
    u = jax.random.normal(jax.random.PRNGKey(3), (TITLES, LENGTH, cfg.dim)).at[..., 0].set(3.0)
    ffn = latent_trunk._RoutedFFN(cfg)
    p = seeded(ffn.init(jax.random.PRNGKey(0), u)["params"])
    router = np.zeros((cfg.dim, cfg.n_experts), np.float32)
    router[0, :3] = [0.6, 0.4, 0.2]
    p = {**p, "router": jnp.asarray(router),
         "router_bias": jnp.zeros((cfg.n_experts,)) if bias is None else jnp.asarray(bias)}
    return ffn, p, u


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    cfg = LatentTrunkConfig(**TINY, first_expert=0, experts_held=4, vocab_held=300)
    ffn, p, u = routed_layer_with_one_dominant_feature(cfg)
    out, counts, _ = ffn.apply({"params": p}, u)
    assert counts.tolist() == [TITLES * LENGTH] * 2 + [0, 0]
    with jax.default_matmul_precision("highest"):
        want = ref.routed_ffn(p, u, trunk_dict(cfg), lambda v: v, None)
        np.testing.assert_allclose(ffn.apply({"params": p}, u)[0], want, rtol=2e-5, atol=2e-5)


def test_the_selection_bias_moves_the_choice_and_not_the_weights():
    cfg = LatentTrunkConfig(**TINY, first_expert=0, experts_held=8, vocab_held=300)
    _, p, u = routed_layer_with_one_dominant_feature(cfg)
    flat = u.reshape(-1, cfg.dim)
    route = lambda b: latent_trunk.route_sigmoid(  # noqa: E731
        flat, p["router"], jnp.asarray(b, jnp.float32), cfg.experts_per_token, cfg.routed_scale)
    idx0, w0 = route(np.zeros(8))
    assert set(np.asarray(idx0).ravel()) == {0, 1}
    # a bias on expert 5 larger than any score difference: it takes expert
    # 1's place, and its weight is made of its SCORE (sigmoid(0) = 0.5)
    idx1, w1 = route(np.eye(8)[5] * 0.4)
    assert set(np.asarray(idx1).ravel()) == {0, 5}
    s0 = float(jax.nn.sigmoid(0.6 * 3.0))
    np.testing.assert_allclose(np.sort(np.asarray(w1), axis=-1)[0],
                               [2 * 0.5 / (s0 + 0.5), 2 * s0 / (s0 + 0.5)], rtol=1e-5)
    np.testing.assert_allclose(np.sum(w0, -1), 2.0, rtol=1e-5)
    np.testing.assert_allclose(np.sum(w1, -1), 2.0, rtol=1e-5)
    # a bias that changes no choice changes nothing at all
    idx2, w2 = route(np.eye(8)[5] * 0.01)
    np.testing.assert_array_equal(idx2, idx0)
    np.testing.assert_array_equal(w2, w0)


def test_an_id_outside_the_held_vocabulary_embeds_to_zero():
    cfg = LatentTrunkConfig(**{**TINY, "n_layers": 1, "n_dense_layers": 0}, experts_held=8,
                            vocab_first=100, vocab_held=100)
    trunk = latent_trunk.LatentMoETrunk(cfg)
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


def test_yarn_leaves_fast_frequencies_and_divides_slow_ones():
    """At the published sizes: frequencies 0-10 of the 32 are the plain
    rotary's, 23-31 are divided by 64, the ramp blends the ones between;
    m = 0.1 ln 64 + 1; and the reference computes the same."""
    c = LatentTrunkConfig()
    got = np.asarray(latent_trunk.yarn_inv_freq(
        c.rope_dim, c.rope_theta, c.rope_factor, c.rope_original_max, c.rope_beta_fast, c.rope_beta_slow))
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-6)
    assert np.all(got[11:23] < plain[11:23]) and np.all(got[11:23] > plain[11:23] / 64)
    assert latent_trunk.yarn_mscale(c) == (pytest.approx(1.41589, rel=1e-5), pytest.approx(1.0))
    np.testing.assert_allclose(ref.yarn_frequencies(trunk_dict(c), 64), got, rtol=1e-6)


def test_config_refuses_a_share_that_is_not_the_models():
    with pytest.raises(ValueError, match="experts"):
        LatentTrunkConfig(first_expert=60, experts_held=8)
    with pytest.raises(ValueError, match="vocabulary"):
        LatentTrunkConfig(vocab_first=131000, vocab_held=1000)
    with pytest.raises(ValueError, match="dense"):
        LatentTrunkConfig(n_layers=2, n_dense_layers=3)


# ------------------------------------------------------- through the Trainer
def trunk_cfg(clients: int):
    """The normal path at test widths: the family, depth and share through
    ``ExperimentConfig``; the ranks, the head's two parts, the dense width,
    the 64 experts and 4 a token stay as published."""
    from fedrec_tpu.config import ExperimentConfig

    return ExperimentConfig().apply_overrides([
        "model.text_encoder_mode=finetune", "model.text_trunk=latent_moe",
        "model.bert_hidden=32", "model.trunk_layers=3", "model.trunk_dense_layers=1",
        "model.trunk_heads=4", "model.trunk_ffn=16", "model.trunk_vocab=2000",
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
    """One round through ``Trainer``: finite loss, the routing counters and
    the mixer's gauge in the registry (registered by what the trunk
    returned), the selection bias where it was, and for a ``param_avg``
    cohort every client equal to the mean of what the clients held before
    the sync."""
    from jax.sharding import Mesh

    from fedrec_tpu.obs.registry import MetricsRegistry, get_registry, set_registry
    from fedrec_tpu.train.trainer import Trainer

    old = get_registry()
    set_registry(MetricsRegistry())
    try:
        cfg = trunk_cfg(clients)
        mesh = Mesh(np.array(jax.devices()[:devices]), (cfg.fed.mesh_axis,))
        trainer = Trainer(cfg, trunk_data(cfg), None, mesh=mesh)
        assert "moe.absent_share" not in trainer.registry.snapshot()["metrics"]
        bias_of = lambda params: np.asarray(  # noqa: E731
            params["trunk"]["layer_1_ffn"]["ffn"]["router_bias"])
        bias = np.broadcast_to(0.05 * np.arange(64, dtype=np.float32), bias_of(trainer.state.news_params).shape)
        news = jax.tree_util.tree_map(np.asarray, trainer.state.news_params)
        news["trunk"]["layer_1_ffn"]["ffn"]["router_bias"] = bias
        trainer.state = trainer.state.replace(news_params=jax.tree_util.tree_map(
            lambda new, old: jax.device_put(new, old.sharding), news, trainer.state.news_params))
        seen = {}
        sync = trainer.param_sync

        def recording_sync(state, *rest):
            seen["before"] = jax.tree_util.tree_map(np.asarray, state.news_params)
            return sync(state, *rest)

        trainer.param_sync = recording_sync
        result = trainer.train_round(0)
        assert np.isfinite(result.train_loss)
        np.testing.assert_array_equal(bias_of(trainer.state.news_params), bias)
        snap = trainer.registry.snapshot()["metrics"]
        absent = snap["moe.absent_share"]["values"][0]["value"]
        assert 0.4 < absent < 0.95                        # 16 of 64 experts held
        # 2,304 pairs a layer, a quarter of the experts held: within the
        # small buffer's 1,536 rows of the full 2,560
        assert snap["moe.full_size_chunks_total"]["values"][0]["value"] == 0
        cells = snap["moe.expert_tokens_total"]["values"]
        assert len(cells) == 2 * 16 and {c["labels"]["expert"] for c in cells} == {str(e) for e in range(16, 32)}
        steps = 32 // (8 * clients)
        # the dedup encodes min(slots, catalog) = 48 titles of 12 tokens a
        # client-step; 4 choices a token, 2 routed layers
        pairs = steps * clients * 48 * 12 * 4 * 2
        routed = sum(c["value"] for c in cells)
        assert routed == pytest.approx(pairs * (1 - absent), rel=1e-3)
        assert snap["moe.expert_load_max_over_mean"]["values"][0]["value"] >= 1.0
        assert 0 <= snap["trunk.residual_mix_err_max"]["values"][0]["value"] < 1e-3
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
    from fedrec_tpu.models.bert import make_text_encoder, trunk_families

    cfg = trunk_cfg(1)
    chosen = make_text_encoder(cfg.model).trunk_cfg
    assert isinstance(chosen, LatentTrunkConfig)
    assert (chosen.dim, chosen.n_layers, chosen.n_dense_layers, chosen.n_heads, chosen.expert_dim) == (32, 3, 1, 4, 16)
    assert (chosen.first_expert, chosen.experts_held, chosen.vocab_held) == (16, 16, 2000)
    # what the tests do not shrink is as published
    assert (chosen.q_rank, chosen.kv_rank, chosen.nope_dim, chosen.rope_dim, chosen.v_dim) == (768, 512, 128, 64, 128)
    assert (chosen.dense_dim, chosen.n_experts, chosen.experts_per_token, chosen.n_streams) == (9216, 64, 4, 4)
    from fedrec_tpu.config import ModelConfig

    # the one new field's default is the published count
    assert ModelConfig().trunk_dense_layers == LatentTrunkConfig().n_dense_layers == 2
    cfg.model.text_trunk = "gru"
    with pytest.raises(ValueError, match=r"text_trunk 'gru' \(" + r"\|".join(trunk_families()) + r"\)"):
        make_text_encoder(cfg.model)
