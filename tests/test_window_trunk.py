"""The gated grouped-query trunk with window and full layers
(``models/window_trunk.py``) against the benchmark's plain reference
(``chipbench/reference_window_trunk.py``: float32 ``jax.numpy``, a dense
``L x L`` masked softmax a text, keys and values repeated out a head, a dense
loop over experts; no second copy lives here), on seeded random weights at
tiny widths where the window binds, and through the ``Trainer``."""

import functools
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import corpus_window  # noqa: E402
from chipbench import reference_window_trunk as ref  # noqa: E402
from fedrec_tpu.models import latent_trunk, sparse_trunk, window_trunk  # noqa: E402
from fedrec_tpu.models.bert import TextEncoder  # noqa: E402
from fedrec_tpu.models.window_trunk import WindowTrunkConfig  # noqa: E402
from fedrec_tpu.ops.chunked_attention import chunked_attention, scores_computed_share  # noqa: E402

# one leading dense layer and two routed ones (full, window, window); 6 and
# 8 query heads over 2 key/value heads; texts of 22 tokens, nearly three
# windows of 8 and no multiple of the core's blocks of 8; a context of 16
# positions "trained on", so that YaRN's ramp falls inside the four
# frequencies of a full layer's rotary half
TINY = dict(vocab_size=400, dim=32, n_layers=3, n_dense_layers=1, full_heads=6, window_heads=8,
            n_kv_heads=2, head_dim=16, sliding_window=8, dense_dim=64, n_experts=16,
            experts_per_token=2, expert_dim=16, rope_original_max=16)
TEXTS, LENGTH = 6, 22
SHIPPED_CHUNKS = (window_trunk.TEXT_CHUNK_TOKENS, window_trunk.ROUTED_CHUNK_TOKENS)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Core blocks of 8 queries and runs of 8 keys (a window layer's band is
    two runs, a full layer's up to three: the softmax is online over them);
    three texts a chunk of attention and the dense feed-forward, two of the
    routed one."""
    monkeypatch.setattr(window_trunk, "CORE_BLOCK_Q", 8)
    monkeypatch.setattr(window_trunk, "CORE_BLOCK_K", 8)
    monkeypatch.setattr(window_trunk, "TEXT_CHUNK_TOKENS", 3 * LENGTH)
    monkeypatch.setattr(window_trunk, "ROUTED_CHUNK_TOKENS", 2 * LENGTH)


def trunk_dict(c: WindowTrunkConfig) -> dict:
    """The reference's trunk group from the program's configuration."""
    layers = range(c.n_layers)
    return {
        "dim": c.dim, "layers": c.n_layers, "dense_layers": c.n_dense_layers,
        "layer_kinds": [c.kind(i) for i in layers], "heads_per_layer": [c.heads(c.kind(i)) for i in layers],
        "kv_heads": c.n_kv_heads, "head_dim": c.head_dim, "sliding_window": c.sliding_window,
        "dense_dim": c.dense_dim, "experts": c.n_experts, "experts_per_token": c.experts_per_token,
        "expert_dim": c.expert_dim, "shared_dim": c.n_shared_experts * c.expert_dim,
        "routed_scale": c.routed_scale, "rms_norm_eps": c.rms_norm_eps,
        "window_rope_theta": c.window_rope_theta, "full_rope_theta": c.full_rope_theta,
        "full_rotary_share": c.full_rotary_share,
        "rope": {"factor": c.rope_factor, "original_max_position_embeddings": c.rope_original_max,
                 "beta_fast": c.rope_beta_fast, "beta_slow": c.rope_beta_slow,
                 "attention_factor": c.rope_attention_factor},
        "first_expert": c.first_expert, "experts_held": c.experts_held,
        "vocab_first": c.vocab_first, "vocab_held": c.vocab_held,
    }


def tokens(seed=0, vocab=300, pad=False):
    rng = np.random.default_rng(seed)
    mask = np.ones((TEXTS, LENGTH), int)
    if pad:
        mask[1, 15:] = mask[4, 3:] = 0                 # tail-padded texts
    return jnp.asarray(np.stack([rng.integers(0, vocab, (TEXTS, LENGTH)), mask], 1), jnp.int32)


def seeded(params, seed=0):
    """Every leaf moved off its initial value by its own normal(0, 0.1)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    root = jax.random.PRNGKey(seed)
    return jax.tree_util.tree_unflatten(treedef, [
        x + 0.1 * jax.random.normal(jax.random.fold_in(root, i), x.shape) for i, x in enumerate(leaves)])


def encoder_and_params(cfg, dtype="float32", seed=0, remat=True):
    te = TextEncoder(trunk_cfg=cfg, news_dim=16, dtype=jnp.dtype(dtype), remat=remat)
    return te, seeded(te.init(jax.random.PRNGKey(seed), tokens())["params"], seed)


def vecs_and_grad_of(fn):
    def loss(p, toks):
        vecs = fn(p, toks)
        return jnp.sum(vecs.astype(jnp.float32) ** 2), vecs

    return jax.jit(lambda p, toks: jax.grad(loss, has_aux=True)(p, toks)[::-1])


def rel_gaps(a, b):
    """Per leaf ||a - b|| / ||b||, leaves whose reference is under a
    thousandth of the largest leaf's left out as noise."""
    scale = max(float(jnp.linalg.norm(x)) for x in jax.tree_util.tree_leaves(b))
    gaps = jax.tree_util.tree_map(
        lambda x, y: float(jnp.linalg.norm(x - y) / jnp.linalg.norm(y))
        if float(jnp.linalg.norm(y)) > 1e-3 * scale else 0.0, a, b)
    return jax.tree_util.tree_leaves(gaps)


def held(**kw):
    return WindowTrunkConfig(**{**TINY, "first_expert": 4, "experts_held": 8, "vocab_held": 300, **kw})


# layer 0 is full under every layout: full, window, window (the published
# period's start), with and without padded tails; and all three full
@pytest.mark.parametrize("full_every,pad", [(4, False), (4, True), (1, True)],
                         ids=["full-then-window", "full-then-window-padded", "full-layers-padded"])
def test_forward_loss_and_gradients_match_the_reference_in_float32(pad, full_every):
    cfg = held(full_every=full_every)
    te, params = encoder_and_params(cfg)
    toks, t = tokens(pad=pad), trunk_dict(cfg)
    with jax.default_matmul_precision("highest"):
        want, g_want = vecs_and_grad_of(lambda p, x: ref.encode_news(p, x, t))(params, toks)
        got, g_got = vecs_and_grad_of(lambda p, x: te.apply({"params": p}, x))(params, toks)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # float32 both sides, other orders of summation: 2e-4 of a leaf's norm
    assert max(rel_gaps(g_got, g_want)) < 2e-4
    bias = lambda g: g["trunk"]["layer_1_ffn"]["chunk"]["ffn"]["router_bias"]  # noqa: E731
    assert not np.any(bias(g_got)) and not np.any(bias(g_want))   # the choice is not differentiated


@functools.lru_cache(maxsize=1)
def sound_vectors():
    """The program's vectors on the seeded weights, computed once for the
    faults (at the fixture's blocks: the first caller's are every caller's)."""
    cfg = held()
    te, params = encoder_and_params(cfg)
    return cfg, params, jax.jit(lambda p, x: te.apply({"params": p}, x))(params, tokens())


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS if f])
def test_each_planted_fault_is_seen(fault):
    """The tolerance tells: a gate of 1, the window dropped, the whole head
    rotated in a full layer, a full layer's heads grouped as a window
    layer's or a token's last choice left out each moves the vectors by far
    more than the 2e-5 the sound trunk is held to."""
    cfg, params, got = sound_vectors()
    toks, t = tokens(), trunk_dict(cfg)
    faulty = jax.jit(lambda p, x: ref.encode_news(p, x, t, fault=fault))(params, toks)
    assert float(jnp.max(jnp.abs(got - faulty))) > 0.02 * float(jnp.max(jnp.abs(got)))
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(got, faulty, rtol=2e-5, atol=2e-5)


def test_bfloat16_stays_near_the_reference():
    """bfloat16 compute rounds every activation to 8 bits of mantissa, and
    with 132 tokens a near-tie in a router that moves one token to another
    expert moves its text's vector; a float32 program reads 1e-6. The chip's
    cell holds bfloat16 to its limits at 56,320 tokens."""
    cfg = held()
    te, params = encoder_and_params(cfg, "bfloat16")
    toks, t = tokens(), trunk_dict(cfg)
    want, g_want = vecs_and_grad_of(lambda p, x: ref.encode_news(p, x, t))(params, toks)
    got, g_got = vecs_and_grad_of(lambda p, x: te.apply({"params": p}, x))(params, toks)
    off = jnp.abs(got.astype(jnp.float32) - want)
    assert float(jnp.linalg.norm(off)) < 0.25 * float(jnp.linalg.norm(want))
    assert all(x.dtype == jnp.float32 for x in jax.tree_util.tree_leaves(g_got))
    assert float(np.median(rel_gaps(g_got, g_want))) < 0.4


# -------------------------------------------------------------- the core
def dense_attention(q, k, v, mask, window):
    """Masked softmax over the whole ``L x L`` square, keys and values
    repeated out a head."""
    L, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bshd->bhqs", q, k) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    allowed = j <= i
    if window is not None:
        allowed &= (i - j) < window
    allowed = allowed[None, None] & (mask[:, None, None, :] > 0)
    probs = jax.nn.softmax(jnp.where(allowed, s, -1e30), axis=-1)
    probs = jnp.where(jnp.any(allowed, axis=-1, keepdims=True), probs, 0.0)   # no allowed key: nothing read
    return jnp.einsum("bhqs,bshd->bqhd", probs, v)


@pytest.mark.parametrize("window", [None, 8], ids=["full", "window"])
@pytest.mark.parametrize("block_q,block_k", [(8, 8), (8, 64), (16, 8)],
                         ids=["online-over-runs", "one-run", "wide-query-block"])
def test_the_blocked_core_equals_a_dense_masked_softmax(window, block_q, block_k):
    """Grouped heads (6 over 2), padded keys, a length that is no multiple
    of a block: values and all three gradients."""
    rng = np.random.default_rng(0)
    B, L, H, KV, D = 3, 22, 6, 2, 8
    q, k, v = (jnp.asarray(rng.standard_normal((B, L, h, D)), jnp.float32) for h in (H, KV, KV))
    mask = np.ones((B, L))
    mask[1, 15:] = mask[2, 3:] = 0
    mask = jnp.asarray(mask)
    core = lambda q, k, v: chunked_attention(  # noqa: E731
        q, k, v, mask, block_q, block_k, causal=True, window=window)
    dense = lambda q, k, v: dense_attention(q, k, v, mask, window)  # noqa: E731
    np.testing.assert_allclose(core(q, k, v), dense(q, k, v), atol=2e-5)
    g_core = jax.grad(lambda *a: (core(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    g_dense = jax.grad(lambda *a: (dense(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    for got, want in zip(g_core, g_dense):
        np.testing.assert_allclose(got, want, atol=3e-5)


def test_the_core_holds_no_square_and_skips_the_blocks_outside_the_band():
    """At the cell's sizes (1,024 tokens, window 512, query blocks of 128) a
    full layer computes the 36 blocks up to the diagonal of 64 and a window
    layer the 30 inside its band, at most 512 / 128 + 1 a query block; the
    jaxpr of value and gradient at a smaller size holds no L x L array."""
    assert scores_computed_share(1024, 128, True, None) == 36 / 64
    assert scores_computed_share(1024, 128, True, 512) == 30 / 64
    assert scores_computed_share(1024, 1024, True, 512) == 1.0
    L, H, KV, D = 64, 4, 2, 8
    q, k, v = (jnp.zeros((1, L, h, D)) for h in (H, KV, KV))
    f = lambda q, k, v: (chunked_attention(q, k, v, None, 8, 16, causal=True, window=16) ** 2).sum()  # noqa: E731
    jaxpr = str(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, k, v)).replace(" ", "")
    shapes = {tuple(map(int, dims.split(","))) for dims in re.findall(r"f32\[([\d,]+)\]", jaxpr)}
    assert not any(s[-2:] == (L, L) for s in shapes)
    # the largest scores alive: one query block of 8 against a run of 16 keys, a head
    assert max(s[-2] * s[-1] for s in shapes if len(s) == 5 and s[-3] != D) == 8 * 16
    with pytest.raises(ValueError, match="causal"):
        chunked_attention(q, k, v, None, 8, 16, window=16)


# ------------------------------------------------------------ rotary, gate
def test_the_two_rotary_laws():
    """Window layers: ``sparse_trunk.rotary`` over the whole head. Full
    layers: dimensions 64-127 untouched, the first 64 rotated by YaRN's
    frequencies for a width of 64 and scaled by the attention factor (so
    position 0, where the angle is 0, is the factor times the input)."""
    c = WindowTrunkConfig()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, c.head_dim))
    np.testing.assert_array_equal(window_trunk.rotate(x, c, "window"), sparse_trunk.rotary(x, 10000.0))
    full = window_trunk.rotate(x, c, "full")
    np.testing.assert_array_equal(full[..., 64:], x[..., 64:])
    np.testing.assert_allclose(full[:, 0, :, :64], c.rope_attention_factor * x[:, 0, :, :64], rtol=1e-6)
    assert c.rope_attention_factor == pytest.approx(0.1 * np.log(64.0) + 1.0)
    # rotation keeps a pair's length: every rotated pair grew by the factor
    pair = lambda a: np.hypot(a[..., :32], a[..., 32:64])  # noqa: E731
    np.testing.assert_allclose(pair(full), c.rope_attention_factor * pair(x), rtol=1e-5)
    t = trunk_dict(c)
    np.testing.assert_allclose(ref.rotate(x, t, "full"), full, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        latent_trunk.yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0),
        ref.yarn_frequencies({"rope": t["rope"], "rope_theta": 500000.0}, 64), rtol=1e-6)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One routed feed-forward whole against its eight shares (2 of 16
    experts each). The shared expert is computed alike by every share and
    counts once; what the shares' experts add sums to what the uncut
    layer's add. The uncut layer is the REFERENCE's, the shares are the
    program's."""
    whole = WindowTrunkConfig(**TINY, experts_held=16, vocab_held=300)
    u = jax.random.normal(jax.random.PRNGKey(1), (TEXTS, LENGTH, whole.dim))
    ffn = latent_trunk._RoutedFFN(whole)
    p = seeded(ffn.init(jax.random.PRNGKey(0), u)["params"])
    with jax.default_matmul_precision("highest"):
        uncut = ref.routed_ffn(p, u, trunk_dict(whole), lambda v: v, None)
        shared = ref.gated_ffn(p["shared_expert"], u, lambda v: v)
        total, pairs = shared, 0
        for rank in range(8):
            cfg = WindowTrunkConfig(**TINY, first_expert=2 * rank, experts_held=2, vocab_held=300)
            share = {**p, "experts": jax.tree_util.tree_map(lambda w: w[2 * rank: 2 * rank + 2], p["experts"])}
            out, counts, _ = latent_trunk._RoutedFFN(cfg).apply({"params": share}, u)
            total = total + (out - shared)
            pairs += int(jnp.sum(counts))
    np.testing.assert_allclose(total, uncut, rtol=2e-5, atol=2e-5)
    assert pairs == TEXTS * LENGTH * whole.experts_per_token


def test_chunks_of_whole_texts_change_nothing(monkeypatch):
    cfg = held()
    te, params = encoder_and_params(cfg)
    toks = tokens(pad=True)
    f = lambda p: jnp.sum(te.apply({"params": p}, toks) ** 2)  # noqa: E731
    assert window_trunk._text_chunks(TEXTS, LENGTH, window_trunk.TEXT_CHUNK_TOKENS) == 2
    chunked, g_chunked = jax.jit(jax.value_and_grad(f))(params)
    monkeypatch.setattr(window_trunk, "TEXT_CHUNK_TOKENS", TEXTS * LENGTH)
    monkeypatch.setattr(window_trunk, "ROUTED_CHUNK_TOKENS", TEXTS * LENGTH)
    assert window_trunk._text_chunks(TEXTS, LENGTH, window_trunk.TEXT_CHUNK_TOKENS) == 1
    whole, g_whole = jax.jit(jax.value_and_grad(f))(params)
    np.testing.assert_allclose(chunked, whole, rtol=1e-6)
    assert max(rel_gaps(g_chunked, g_whole)) < 1e-5
    # the cell's 55 texts of 1,024 tokens: attention and the dense
    # feed-forward one text a chunk, the routed feed-forward 11
    assert window_trunk._text_chunks(55, 1024, SHIPPED_CHUNKS[0]) == 55
    assert window_trunk._text_chunks(55, 1024, SHIPPED_CHUNKS[1]) == 5
    assert window_trunk._text_chunks(55, 2048, SHIPPED_CHUNKS[0]) == 55   # a text longer than a chunk goes alone


def test_config_refuses_heads_that_do_not_group_and_shares_that_are_not_the_models():
    with pytest.raises(ValueError, match="full layer"):
        WindowTrunkConfig(full_heads=44)
    with pytest.raises(ValueError, match="window layer"):
        WindowTrunkConfig(window_heads=60)
    with pytest.raises(ValueError, match="experts"):
        WindowTrunkConfig(first_expert=250, experts_held=32)
    with pytest.raises(ValueError, match="vocabulary"):
        WindowTrunkConfig(vocab_first=100000, vocab_held=1000)
    with pytest.raises(ValueError, match="dense"):
        WindowTrunkConfig(n_layers=2, n_dense_layers=3)


# ------------------------------------------ the configuration file's shapes
@pytest.mark.time_limit(300)
def test_the_configuration_files_overrides_build_the_stated_shapes():
    """``chipbench/configs/mind-laguna33b-ep8.json``: the overrides choose
    the family through ``trunk_families`` / ``make_text_encoder``, the trunk
    they build is the one the file's published keys state (the harness's
    ``build_config`` refuses a file whose halves differ), and the tower's
    tree has the parameters the file's ``bytes`` counts."""
    from chipbench import harness_training_rounds_tokens_window as harness
    from fedrec_tpu.models.bert import make_text_encoder, trunk_families

    config = json.loads((ROOT / "chipbench" / "configs" / "mind-laguna33b-ep8.json").read_text())
    trunk = corpus_window.trunk_of(config)
    cfg = harness.build_config(config, trunk, seed=3)
    assert cfg.data.max_title_len == 1024 and cfg.model.text_trunk == "window_moe"
    assert trunk_families()["window_moe"][1] is WindowTrunkConfig
    te = make_text_encoder(cfg.model)
    c = te.trunk_cfg
    assert isinstance(c, WindowTrunkConfig)
    assert (c.dim, c.n_layers, c.n_dense_layers, c.full_heads, c.window_heads) == (2048, 5, 1, 48, 64)
    assert [c.kind(i) for i in range(5)] == ["full", "window", "window", "window", "full"]
    assert (c.n_kv_heads, c.head_dim, c.sliding_window, c.dense_dim) == (8, 128, 512, 8192)
    assert (c.n_experts, c.experts_per_token, c.expert_dim, c.routed_scale) == (256, 8, 512, 2.5)
    assert (c.first_expert, c.experts_held, c.vocab_held, c.full_rotary_dim) == (0, 32, 12544, 64)
    toks = jax.ShapeDtypeStruct((1, 2, 128), jnp.int32)      # the tree does not depend on the length
    shapes = jax.eval_shape(lambda t: te.init(jax.random.PRNGKey(0), t)["params"], toks)
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))  # noqa: E731
    attn = lambda i: count(shapes["trunk"][f"layer_{i}_attn"]) - 2048      # without the sublayer's norm  # noqa: E731
    assert (attn(0), attn(1)) == (29_458_688, 37_880_064)
    assert count(shapes["trunk"]["layer_0_ffn"]) - 2048 == 50_331_648
    assert count(shapes["trunk"]["layer_1_ffn"]["chunk"]["ffn"]["experts"]) == 100_663_296
    assert count(shapes) + 561_601 == 669_416_530 and "669,416,530" in config["bytes"]
    # the first weights the benchmark draws are this tree, leaf for leaf
    specs = corpus_window.leaf_specs(trunk)
    drawn = jax.tree_util.tree_map(lambda s: s[0], specs, is_leaf=lambda x: isinstance(x, tuple))
    assert drawn == jax.tree_util.tree_map(lambda x: x.shape, dict(shapes["trunk"]))
    with pytest.raises(ValueError, match="trunk.heads_per_layer"):
        harness.build_config(dict(config, num_attention_heads_per_layer=[48, 64, 64, 48, 48]),
                             corpus_window.trunk_of(dict(config, num_attention_heads_per_layer=[48, 64, 64, 48, 48],
                                                         layer_types=["full_attention"] + ["sliding_attention"] * 2
                                                         + ["full_attention"] * 2)), seed=3)


# ------------------------------------------------------- through the Trainer
def trunk_cfg(clients: int):
    """The normal path at test widths: the family, depth and share through
    ``ExperimentConfig``; the head counts' ratio to 8 key/value heads, the
    head's 128 dimensions, the window, the dense width, the 256 experts and
    8 a token stay as published."""
    from fedrec_tpu.config import ExperimentConfig

    return ExperimentConfig().apply_overrides([
        "model.text_encoder_mode=finetune", "model.text_trunk=window_moe",
        "model.bert_hidden=32", "model.trunk_layers=3", "model.trunk_dense_layers=1",
        "model.trunk_heads=16", "model.trunk_ffn=16", "model.trunk_vocab=2000",
        "model.trunk_first_expert=64", "model.trunk_experts_held=64",
        "model.news_dim=32", "model.num_heads=4", "model.head_dim=8", "model.query_dim=16",
        "data.max_his_len=10", "data.max_title_len=12", "data.batch_size=8",
        f"fed.num_clients={clients}", "fed.strategy=" + ("grad_avg" if clients == 1 else "param_avg"),
        "fed.rounds=2", "train.snapshot_dir=", "train.eval_every=1000",
    ])


def trunk_data(cfg):
    from fedrec_tpu.data import make_synthetic_mind

    return make_synthetic_mind(
        num_news=48, num_train=32, num_valid=8, title_len=cfg.data.max_title_len,
        vocab=2000, his_len_range=(2, cfg.data.max_his_len), seed=0)


@pytest.mark.parametrize("clients,devices", [(1, 1), (2, 1)], ids=["one-client", "in-device-cohort"])
def test_trainer_rounds_with_the_trunk(clients, devices):
    """Two rounds through ``Trainer.train_round``: finite losses, the routing
    counters and the core's gauge in the registry (registered by what the
    trunk returned), the selection bias where it was."""
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
            params["trunk"]["layer_1_ffn"]["chunk"]["ffn"]["router_bias"])
        bias = bias_of(trainer.state.news_params).copy()
        losses = [trainer.train_round(r).train_loss for r in range(2)]
        assert np.all(np.isfinite(losses))
        np.testing.assert_array_equal(bias_of(trainer.state.news_params), bias)
        snap = trainer.registry.snapshot()["metrics"]
        absent = snap["moe.absent_share"]["values"][0]["value"]
        assert 0.4 < absent < 0.95                        # 64 of 256 experts held
        cells = snap["moe.expert_tokens_total"]["values"]
        assert len(cells) == 2 * 64 and {c["labels"]["expert"] for c in cells} == {str(e) for e in range(64, 128)}
        assert snap["moe.full_size_chunks_total"]["values"][0]["value"] == 0
        assert snap["moe.expert_load_max_over_mean"]["values"][0]["value"] >= 1.0
        share = {c["labels"]["kind"]: c["value"] for c in snap["trunk.attention_scores_computed_share"]["values"]}
        # 12 tokens in the fixture's query blocks of 8, inside the window:
        # both kinds compute 8 x 8 and 4 x 12 of the 12 x 12 square
        assert share == {kind: pytest.approx(112 / 144) for kind in window_trunk.KINDS}
    finally:
        set_registry(old)


def test_make_text_encoder_chooses_the_trunk_and_the_gauge_prices_it():
    from fedrec_tpu.models.bert import make_text_encoder
    from fedrec_tpu.obs.perf import flops_per_train_step

    cfg = trunk_cfg(1)
    chosen = make_text_encoder(cfg.model).trunk_cfg
    assert isinstance(chosen, WindowTrunkConfig)
    assert (chosen.dim, chosen.n_layers, chosen.n_dense_layers, chosen.full_heads, chosen.expert_dim) == (32, 3, 1, 16, 16)
    assert (chosen.first_expert, chosen.experts_held, chosen.vocab_held) == (64, 64, 2000)
    # what the tests do not shrink is as published
    assert (chosen.window_heads, chosen.n_kv_heads, chosen.head_dim, chosen.sliding_window) == (64, 8, 128, 512)
    assert (chosen.dense_dim, chosen.n_experts, chosen.experts_per_token) == (8192, 256, 8)
    # the operator's gauge counts the trunk's required operations a token
    # (the band's pairs, the expected pairs on held experts), x3, over the
    # texts the step encodes: the same count as the benchmark's yardstick
    from chipbench import flops_window_trunk

    head_only = cfg.model.text_encoder_mode
    cfg.model.text_encoder_mode = "head"
    without = flops_per_train_step(cfg, 8, 48)
    cfg.model.text_encoder_mode = head_only
    texts, L = 48, cfg.data.max_title_len
    want = 3.0 * flops_window_trunk.trunk_flops_per_token(trunk_dict(chosen), L) * texts * L
    assert flops_per_train_step(cfg, 8, 48) - without == pytest.approx(want, rel=1e-9)
    # (the other trunks' steps are still counted at their head alone)
    cfg.model.text_trunk = "latent_moe"
    assert flops_per_train_step(cfg, 8, 48) == without
