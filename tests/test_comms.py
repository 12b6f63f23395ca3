"""Update-compression codec subsystem (``fedrec_tpu.comms``, ISSUE 7).

Pins the codec contracts end to end:

* encode/decode round-trip error bounds per codec and input dtype, with
  payload sizes measured from the REAL wire buffers;
* the numpy wire codec and the in-graph jnp twin implement the same
  arithmetic (same scales, same rounding, same top-k tie-break);
* ``fed.dcn_compress='none'`` is bit-identical to the pre-codec round-end
  sync, and the coordinator's numpy
  aggregate path reconstructs exactly;
* error feedback converges on a hand-checkable quadratic where plain
  sign-SGD/top-k stall;
* decode-before-reduce: trimmed mean neutralizes a x1000-poisoned client
  THROUGH the int8 path (numpy stacks and the in-graph param sync);
* the per-client residual rides the population sidecar store
  (LRU/disk-spill round-trip, quarantine-heal reset) and the coordinator's
  per-process residual serializes/restores.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fedrec_tpu.comms import (
    CODECS,
    CodecState,
    codec_state_bytes,
    codec_uses_feedback,
    decode_gathered,
    decode_leaf,
    decode_tree,
    encode_leaf,
    encode_tree,
    jax_encode_decode,
    load_codec_state,
    payload_nbytes,
    topk_count,
    tree_dense_nbytes,
    validate_codec,
)

from test_train import make_setup, small_cfg, _batch_dict


def _rng_tensor(shape, dtype, seed=0):
    x = np.random.default_rng(seed).standard_normal(shape)
    return (x * 3.0).astype(dtype)


# ================================================== round-trip error bounds
@pytest.mark.parametrize("dtype", [np.float32, np.float16, np.float64])
def test_int8_roundtrip_error_bound_per_dtype(dtype):
    """Symmetric per-tensor int8: worst-case element error is scale/2 =
    max|x|/254 (half a quantization level), for every input dtype (the
    wire always carries f32 arithmetic)."""
    x = _rng_tensor((33, 7), dtype)
    p = encode_leaf(x, "int8")
    y = decode_leaf(p, "int8", x.shape)
    xf = np.asarray(x, np.float32)
    bound = np.max(np.abs(xf)) / 254.0 + 1e-6
    assert np.max(np.abs(xf - y)) <= bound
    # real wire buffers: 1 byte/element + one f32 scale
    assert p["q"].dtype == np.int8
    assert payload_nbytes(p) == x.size + 4


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_sign1bit_roundtrip_is_scaled_sign(dtype):
    """1-bit: decode is exactly sign(x) * mean|x| — and the payload is a
    REAL bit-packed buffer (ceil(n/8) bytes + one f32 scale), ~32x down
    from dense f32."""
    x = _rng_tensor((40, 10), dtype, seed=1)
    p = encode_leaf(x, "sign1bit")
    y = decode_leaf(p, "sign1bit", x.shape)
    xf = np.asarray(x, np.float32)
    scale = np.mean(np.abs(xf))
    np.testing.assert_allclose(y, np.where(xf >= 0, scale, -scale), rtol=1e-6)
    assert payload_nbytes(p) == -(-x.size // 8) + 4
    # ~32x asymptotically; the per-tensor f32 scale costs a few bits on a
    # small tensor
    assert 4 * x.size / payload_nbytes(p) > 25


def test_topk_keeps_largest_and_bounds_dropped_mass():
    x = _rng_tensor((25, 8), np.float32, seed=2)
    p = encode_leaf(x, "topk", topk_ratio=0.1)
    k = topk_count(x.size, 0.1)
    assert p["idx"].shape == (k,) and p["val"].shape == (k,)
    y = decode_leaf(p, "topk", x.shape)
    flat = x.reshape(-1)
    kept = np.sort(np.argsort(-np.abs(flat), kind="stable")[:k])
    np.testing.assert_array_equal(np.flatnonzero(y.reshape(-1)), kept)
    np.testing.assert_allclose(y.reshape(-1)[kept], flat[kept], rtol=0)
    # error = the dropped mass: every surviving coordinate is exact, and
    # no dropped |coordinate| exceeds the smallest kept one
    dropped = np.setdiff1d(np.arange(flat.size), kept)
    assert np.max(np.abs(flat[dropped])) <= np.min(np.abs(flat[kept])) + 1e-7
    # real wire buffers: k * (4-byte idx + 4-byte val)
    assert payload_nbytes(p) == 8 * k


def test_none_codec_is_exact_and_zero_tensors_survive():
    x = _rng_tensor((9, 3), np.float32, seed=3)
    np.testing.assert_array_equal(
        decode_leaf(encode_leaf(x, "none"), "none", x.shape), x
    )
    z = np.zeros((5, 2), np.float32)
    for codec in CODECS:
        y = decode_leaf(encode_leaf(z, codec), codec, z.shape)
        np.testing.assert_array_equal(y, z)  # all-zero never NaNs


def test_validate_codec_and_topk_count_fail_fast():
    with pytest.raises(ValueError, match="unknown fed.dcn_compress"):
        validate_codec("gzip")
    with pytest.raises(ValueError, match="dcn_topk_ratio"):
        topk_count(100, 0.0)
    assert topk_count(100, 1.0) == 100
    assert topk_count(3, 1e-9) == 1  # floor of one coordinate
    assert codec_uses_feedback("sign1bit") and codec_uses_feedback("topk")
    assert not codec_uses_feedback("int8")
    assert not codec_uses_feedback("sign1bit", error_feedback=False)


# ================================================= numpy vs in-graph twin
@pytest.mark.parametrize("codec", ["none", "int8", "sign1bit", "topk"])
def test_jax_twin_matches_wire_codec(codec):
    """The in-graph encode->decode must reconstruct the SAME tensor the
    wire codec would — same scales, same rounding, same tie-break."""
    x = _rng_tensor((31, 5), np.float32, seed=4)
    wire = decode_leaf(encode_leaf(x, codec, 0.07), codec, x.shape)
    graph = np.asarray(jax.jit(
        lambda v: jax_encode_decode(v, codec, 0.07)
    )(x))
    np.testing.assert_allclose(graph, wire, rtol=0, atol=1e-6)


def test_jax_twin_topk_tie_break_matches():
    """Ties in |x| keep the LOWEST flat index in both variants (stable
    argsort vs lax.top_k)."""
    x = np.array([1.0, -2.0, 2.0, 0.5, -2.0, 2.0], np.float32)
    # k=3, four tied |2.0| coordinates at flat indices 1,2,4,5: both
    # variants must keep the three LOWEST (1,2,4) and drop 5
    wire = decode_leaf(encode_leaf(x, "topk", 0.5), "topk", x.shape)
    graph = np.asarray(jax_encode_decode(x, "topk", 0.5))
    np.testing.assert_array_equal(wire, graph)
    np.testing.assert_array_equal(np.flatnonzero(wire), [1, 2, 4])


# ========================================================= tree-level wire
def test_encode_tree_roundtrip_and_measured_bytes():
    tree = {
        "a": _rng_tensor((16, 4), np.float32, seed=5),
        "b": {"c": _rng_tensor((64,), np.float32, seed=6)},
    }
    dense = tree_dense_nbytes(tree)
    assert dense == 4 * (16 * 4 + 64)
    for codec, min_red in (("int8", 3.5), ("sign1bit", 15.0)):
        enc = encode_tree(tree, codec)
        assert dense / enc.nbytes() >= min_red  # measured, real buffers
        dec = decode_tree(enc)
        assert set(dec) == {"a", "b"}
        assert dec["a"].shape == (16, 4) and dec["b"]["c"].shape == (64,)


def test_decode_gathered_densifies_per_contribution():
    """decode_gathered: payload arrays with a leading (P,) process dim come
    back as dense (P, *shape) stacks — each contribution decoded
    independently (THE decode-before-reduce step)."""
    contribs = [
        {"w": _rng_tensor((6, 2), np.float32, seed=10 + p)} for p in range(4)
    ]
    encs = [encode_tree(c, "int8") for c in contribs]
    gathered = [
        {
            k: np.stack([np.asarray(e.payloads[i][k]) for e in encs])
            for k in encs[0].payloads[i]
        }
        for i in range(len(encs[0].payloads))
    ]
    stacks = decode_gathered(gathered, encs[0])
    assert stacks["w"].shape == (4, 6, 2)
    for p in range(4):
        np.testing.assert_allclose(
            stacks["w"][p], decode_tree(encs[p])["w"], rtol=0, atol=1e-7
        )


# ============================================ decode-before-reduce (robust)
def test_trimmed_mean_neutralizes_x1000_poison_through_int8():
    """Robust x compress: 8 contributions through the int8 wire codec, one
    poisoned x1000 — the coordinate-wise trimmed mean over the DECODED
    stacks matches the hand-computed trim of the clean values, poison
    gone. (Pre-PR this combination was a hard fail-fast.)"""
    from fedrec_tpu.fed.robust import robust_reduce_tree_np

    rng = np.random.default_rng(7)
    base_vals = [rng.standard_normal((12,)).astype(np.float32) for _ in range(8)]
    vals = [v.copy() for v in base_vals]
    vals[3] = vals[3] * 1000.0
    encs = [encode_tree({"p": v}, "int8") for v in vals]
    decoded = np.stack([decode_tree(e)["p"] for e in encs])
    stacks = {"p": decoded}
    out = robust_reduce_tree_np(
        stacks, np.ones((8,), np.float32), "trimmed_mean", trim_k=1,
        fallback_tree={"p": decoded[0]},
    )["p"]
    # hand check: per-coordinate sort of the DECODED contributions, drop
    # top/bottom 1, mean the rest — the poisoned row lands in the trimmed
    # tail at every coordinate it inflated
    srt = np.sort(decoded, axis=0)
    np.testing.assert_allclose(out, srt[1:-1].mean(axis=0), rtol=1e-5)
    # poison NEUTRALIZED: a x1000 row surviving any coordinate's trim
    # would move the mean by ~10^2; the aggregate stays inside the clean
    # contributions' O(1) range (the trim consumes one tail slot per
    # coordinate, so it differs from the 8-clean-row trim by at most one
    # substituted order statistic — bounded by the clean value spread)
    clean = np.stack(base_vals)
    assert np.max(np.abs(out)) <= np.max(np.abs(clean)) + 0.01
    trim_clean = np.sort(clean, axis=0)[1:-1].mean(axis=0)
    assert np.max(np.abs(out - trim_clean)) < 0.5


@pytest.mark.slow  # jit-heavy; tier-1 keeps the numpy proofs
def test_param_sync_trimmed_mean_neutralizes_poison_through_int8_in_graph():
    """The in-graph twin of the test above: fed.dcn_compress=int8 +
    fed.robust.method=trimmed_mean in the compiled round-end sync — the
    poisoned client's update cannot move the aggregate."""
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.parallel import shard_batch
    from fedrec_tpu.train import build_fed_train_step, build_param_sync

    cfg = small_cfg()
    cfg.fed.dcn_compress = "int8"
    cfg.fed.robust.method = "trimmed_mean"
    cfg.fed.robust.trim_k = 1
    _, batcher, token_states, model, stacked, mesh = make_setup(cfg)
    entry = jax.tree_util.tree_map(
        jnp.copy, (stacked.user_params, stacked.news_params)
    )
    step = build_fed_train_step(
        model, cfg, get_strategy("local"), mesh, mode="joint"
    )
    for b in batcher.epoch_batches_sharded(cfg.fed.num_clients, 0):
        stacked, _ = step(stacked, shard_batch(mesh, _batch_dict(b)), token_states)

    def poison(tree):
        def one(x):
            x = np.array(x)
            x[3] = x[3] * 1000.0
            return jnp.asarray(x)

        return jax.tree_util.tree_map(one, tree)

    stacked = stacked.replace(user_params=poison(stacked.user_params))
    sync = build_param_sync(cfg, mesh)
    out = sync(stacked, jnp.ones((8,), jnp.float32), *entry)
    for e, post in zip(
        jax.tree_util.tree_leaves(entry[0]),
        jax.tree_util.tree_leaves(out.user_params),
    ):
        arr = np.asarray(post)
        assert np.isfinite(arr).all()
        # x1000 deltas would move the mean by ~hundreds of units; the
        # trimmed aggregate stays within the clean clients' update range
        assert np.max(np.abs(arr - np.asarray(e))) < 1.0


# ================================================== error-feedback (EF)
def _ef_descent(codec: str, error_feedback: bool, steps: int = 300, lr=0.05):
    """Hand-checkable quadratic with a DOMINATING third coordinate:

        f(x) = 0.5*x1^2 + 0.5*x2^2 + 0.5*0.02*(x3 - 100)^2

    so g3 ~ -2 stays the largest-|.| gradient for the whole run while the
    two unit-curvature coordinates shrink. Gradient descent where each
    step's gradient goes through encode->decode (topk_ratio=1/3 => k=1),
    optionally with error feedback. Returns the trajectory of x."""
    h = np.array([1.0, 1.0, 0.02], np.float32)
    c = np.array([0.0, 0.0, 100.0], np.float32)
    x = np.array([1.0, -1.0, 0.0], np.float32)
    r = np.zeros_like(x)
    traj = [x.copy()]
    for _ in range(steps):
        g = h * (x - c)
        acc = g + r if error_feedback else g
        dec = decode_leaf(encode_leaf(acc, codec, 1 / 3), codec, acc.shape)
        if error_feedback:
            r = acc - dec
        x = x - lr * dec
        traj.append(x.copy())
    return np.stack(traj)


def test_topk_error_feedback_converges_where_plain_stalls():
    """THE stall pin (ISSUE 7): top-k with k=1 on the quadratic above —
    without EF the dominating third gradient (|g3| ~ 2 > |g1|,|g2| <= 1)
    wins the single slot EVERY step, so coordinates 1 and 2 are never
    transmitted and sit at EXACTLY their initial values forever (plain
    top-k SGD stalls); the residual banks their gradients until they win
    the slot, and both converge."""
    plain = _ef_descent("topk", error_feedback=False)
    ef = _ef_descent("topk", error_feedback=True)
    # plain: bit-exact stall — nothing was ever sent for coords 1, 2
    np.testing.assert_array_equal(plain[-1, :2], [1.0, -1.0])
    # EF: both coordinates converge toward 0 (measured ~0.05 at lr=0.05)
    assert np.abs(ef[-1, :2]).max() < 0.1
    # ... while the dominating coordinate descends in both runs
    assert plain[-1, 2] > 10 and ef[-1, 2] > 10


def test_sign1bit_error_feedback_cancels_the_sign_bias():
    """EF's core theorem, hand-exact: with a CONSTANT anisotropic gradient
    g* = [4, 1], plain sign1bit transmits sign(g*)*mean|g*| = [2.5, 2.5]
    every step — a bias that grows linearly (1.5 per step on each
    coordinate) — while with EF the cumulative transmitted update
    telescopes to T*g* + (r_0 - r_T), within ONE bounded residual of the
    truth at any horizon."""
    g_star = np.array([4.0, 1.0], np.float32)
    T = 100
    cum_plain = np.zeros(2, np.float32)
    cum_ef = np.zeros(2, np.float32)
    r = np.zeros(2, np.float32)
    for _ in range(T):
        cum_plain += decode_leaf(
            encode_leaf(g_star, "sign1bit"), "sign1bit", g_star.shape
        )
        acc = g_star + r
        dec = decode_leaf(encode_leaf(acc, "sign1bit"), "sign1bit", acc.shape)
        r = acc - dec
        cum_ef += dec
    np.testing.assert_allclose(cum_plain, [2.5 * T, 2.5 * T], rtol=1e-6)
    # plain bias: |2.5 - 4| = 1.5/step and |2.5 - 1| = 1.5/step
    np.testing.assert_allclose(
        np.abs(cum_plain - T * g_star), [1.5 * T, 1.5 * T], rtol=1e-5
    )
    # EF: cumulative error == |r_T| (telescoping), bounded — never grows
    np.testing.assert_allclose(cum_ef, T * g_star - r, rtol=1e-4)
    assert np.abs(cum_ef - T * g_star).max() <= np.abs(r).max() + 1e-3
    assert np.abs(r).max() < 2 * np.abs(g_star).max()  # residual bounded


# ==================================== 'none' bit-identity + trainer plumbing
def _codec_trainer(codec: str, rounds=2, **kw):
    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.data import make_synthetic_mind
    from fedrec_tpu.obs import MetricsRegistry, Tracer, set_registry, set_tracer
    from fedrec_tpu.train.trainer import Trainer

    set_registry(MetricsRegistry())
    set_tracer(Tracer())
    cfg = ExperimentConfig()
    cfg.model.news_dim = 32
    cfg.model.num_heads = 4
    cfg.model.head_dim = 8
    cfg.model.query_dim = 16
    cfg.model.bert_hidden = 48
    cfg.model.text_encoder_mode = "head"
    cfg.data.max_his_len = 10
    cfg.data.max_title_len = 12
    cfg.data.batch_size = 8
    cfg.fed.num_clients = 4
    cfg.fed.strategy = "param_avg"
    cfg.fed.rounds = rounds
    cfg.fed.dcn_compress = codec
    cfg.train.snapshot_dir = ""
    cfg.train.eval_every = 1000
    for key, v in kw.items():
        obj = cfg
        parts = key.split(".")
        for p in parts[:-1]:
            obj = getattr(obj, p)
        setattr(obj, parts[-1], v)
    data = make_synthetic_mind(
        num_news=64, num_train=128, num_valid=32,
        title_len=12, his_len_range=(2, 10), seed=0, popular_frac=0.2,
    )
    states = np.random.default_rng(1).standard_normal(
        (64, 12, 48)
    ).astype(np.float32)
    return Trainer(cfg, data, states)


def _params_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves((a.user_params, a.news_params))
    lb = jax.tree_util.tree_leaves((b.user_params, b.news_params))
    return all(
        (np.asarray(x) == np.asarray(y)).all() for x, y in zip(la, lb)
    )


def test_none_codec_bit_identical_host_driven():
    """fed.dcn_compress='none' must keep the PRE-codec sync program: the
    default-config trajectory and the explicit-none trajectory are
    bit-identical, and the codec sync body (extra entry args) is not
    even built."""
    from fedrec_tpu.train import compressed_sync_active
    from fedrec_tpu.fed import get_strategy

    t0 = _codec_trainer("none")
    assert not compressed_sync_active(t0.cfg, get_strategy("param_avg"))
    h0 = t0.run()
    t1 = _codec_trainer("none")
    h1 = t1.run()
    assert [r.train_loss for r in h0] == [r.train_loss for r in h1]
    assert _params_equal(t0.state, t1.state)
    # no codec => no byte accounting on the simulated uplink
    assert t0.registry.counter(
        "fed.dcn_bytes_up_total", labels=("path",)
    ).value(path="cohort") == 0.0


def test_sign1bit_trainer_banks_bytes_and_residual(tmp_path):
    """A compressed run: byte counters carry the measured encoded sizes,
    the compression-ratio gauge shows ~32x, the report renders a
    Communication section, and the per-client EF residual is nonzero
    after a round (the codec actually dropped mass into it)."""
    t = _codec_trainer("sign1bit")
    t.run()
    reg = t.registry
    up = reg.counter("fed.dcn_bytes_up_total", labels=("path",)).value(
        path="cohort"
    )
    down = reg.counter("fed.dcn_bytes_down_total", labels=("path",)).value(
        path="cohort"
    )
    # 2 rounds x 4 reporting clients x the encoded payload
    assert up == 2 * 4 * t._codec_bytes_per_client
    assert down == 2 * 4 * t._dense_bytes_per_client
    assert reg.gauge("fed.dcn_compression_ratio").value() > 20
    res = jax.tree_util.tree_leaves(t.state.ef_residual)
    assert any(np.abs(np.asarray(x)).max() > 0 for x in res)

    from fedrec_tpu.obs.report import build_report, render_text

    snap = {"kind": "registry_snapshot", "ts": 0, "metrics": reg.snapshot()["metrics"]}
    rep = build_report([], [snap])
    comm = rep["communication"]
    assert comm["bytes_up"]["cohort"] == up
    assert comm["compression_ratio"] > 20
    assert "## Communication" in render_text(rep)


def test_codec_config_fails_fast():
    with pytest.raises(ValueError, match="unknown fed.dcn_compress"):
        _codec_trainer("gzip")
    with pytest.raises(ValueError, match="never ships a round update"):
        _codec_trainer("int8", **{"fed.strategy": "grad_avg"})


def test_sign1bit_weight_zero_client_keeps_residual():
    """A non-reporting (weight-0) client transmitted nothing: its residual
    must carry over unchanged while reporting clients bank fresh drop
    mass."""
    t = _codec_trainer("sign1bit", rounds=1, **{"fed.participation": 0.75})
    t.run()
    # participation mask is round-keyed and deterministic; find the
    # weight-0 client of round 0 from the ledger-free mask the trainer used
    w = t._round_weights(0).reshape(-1)
    assert (w == 0).sum() == 1
    idx0 = int(np.flatnonzero(w == 0)[0])
    res = jax.tree_util.tree_map(np.asarray, t.state.ef_residual)
    zeros = [np.abs(x[idx0]).max() for x in jax.tree_util.tree_leaves(res)]
    others = [
        np.abs(x[i]).max()
        for x in jax.tree_util.tree_leaves(res)
        for i in range(4)
        if i != idx0
    ]
    assert max(zeros) == 0.0  # fresh residual, never touched
    assert max(others) > 0.0


# =============================================== residual sidecar + persist
def test_ef_residual_rides_population_sidecar_spill(tmp_path):
    """The residual is a SIDECAR_FIELDS member: it LRU/disk-spills with
    the optimizer moments and round-trips exactly."""
    from fedrec_tpu.fed.population import SIDECAR_FIELDS, ClientPopulation

    assert "ef_residual" in SIDECAR_FIELDS
    pop = ClientPopulation(
        8, num_rows=64, resident_cap=2, spill_dir=tmp_path / "spill"
    )
    mk = lambda c: {
        "step": np.int32(c),
        "ef_residual": {
            "u": np.full((4,), float(c), np.float32),
            "n": np.full((2, 2), -float(c), np.float32),
        },
    }
    for c in range(5):
        pop.put_sidecar(c, mk(c))
    assert pop.spill_count == 3
    for c in range(5):
        sc = pop.get_sidecar(c)
        np.testing.assert_array_equal(sc["ef_residual"]["u"], mk(c)["ef_residual"]["u"])
        np.testing.assert_array_equal(sc["ef_residual"]["n"], mk(c)["ef_residual"]["n"])
    pop.reset_sidecar(1)  # quarantine heal forgets the residual too
    assert pop.get_sidecar(1) is None


def test_population_sidecar_template_includes_zero_residual():
    """A fresh (or healed) logical client starts from the all-zero
    template residual — the same contract as the optimizer moments."""
    t = _codec_trainer(
        "sign1bit", rounds=1, **{"fed.population.num_clients": 8}
    )
    tpl = t._pop_template
    assert "ef_residual" in tpl
    for leaf in jax.tree_util.tree_leaves(tpl["ef_residual"]):
        assert (np.asarray(leaf) == 0).all()


def test_codec_state_serialize_roundtrip():
    """The coordinator's per-process residual: bytes -> CodecState -> the
    identical pytree; a zero-leaf blob restores residual=None; a
    structure mismatch fails with an operator-grade message."""
    template = {
        "u": np.zeros((3, 2), np.float32),
        "n": np.zeros((5,), np.float32),
    }
    res = {
        "u": _rng_tensor((3, 2), np.float32, seed=8),
        "n": _rng_tensor((5,), np.float32, seed=9),
    }
    blob = codec_state_bytes(CodecState(residual=res), round_idx=7)
    restored, rnd = load_codec_state(blob, template)
    assert rnd == 7
    np.testing.assert_array_equal(restored.residual["u"], res["u"])
    np.testing.assert_array_equal(restored.residual["n"], res["n"])
    assert restored.residual_nbytes() == res["u"].nbytes + res["n"].nbytes

    empty_blob = codec_state_bytes(CodecState(), round_idx=3)
    empty, rnd3 = load_codec_state(empty_blob, template)
    assert empty.residual is None and rnd3 == 3

    with pytest.raises(ValueError, match="config changed"):
        load_codec_state(blob, {"only": np.zeros((1,), np.float32)})


def test_ef_residual_survives_state_serialization():
    """ClientState.ef_residual is an ordinary state leaf: flax msgpack
    serialization (the snapshot/coordinator format) round-trips it."""
    from flax import serialization

    t = _codec_trainer("topk", rounds=1)
    t.run()
    blob = serialization.to_bytes(t.state)
    t2 = _codec_trainer("topk", rounds=0)
    restored = serialization.from_bytes(t2.state, blob)
    for a, b in zip(
        jax.tree_util.tree_leaves(t.state.ef_residual),
        jax.tree_util.tree_leaves(restored.ef_residual),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ======================================== coordinator numpy aggregate path
def test_aggregate_from_hosts_none_is_exact_single_process():
    """P=1 world: the 'none' path returns the params bit-exactly (the
    pre-PR weighted-mean contract), every codec path returns them within
    its reconstruction bound, and the EF codecs bank their drop into the
    process residual."""
    from fedrec_tpu.parallel.multihost import aggregate_from_hosts

    params = {
        "u": _rng_tensor((8, 3), np.float32, seed=11),
        "n": _rng_tensor((6,), np.float32, seed=12),
    }
    base = jax.tree_util.tree_map(lambda x: x * 0.9, params)

    out = aggregate_from_hosts(params, weight=2.0, compress="none")
    for a, b in zip(
        jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    out8 = aggregate_from_hosts(
        params, weight=1.0, compress="int8", base=base
    )
    for a, b, bb in zip(
        jax.tree_util.tree_leaves(out8),
        jax.tree_util.tree_leaves(params),
        jax.tree_util.tree_leaves(base),
    ):
        delta = np.asarray(b) - np.asarray(bb)
        bound = np.max(np.abs(delta)) / 254.0 + 1e-6
        assert np.max(np.abs(np.asarray(a) - np.asarray(b))) <= bound

    st = CodecState()
    out1 = aggregate_from_hosts(
        params, weight=1.0, compress="sign1bit", base=base, codec_state=st
    )
    assert st.residual is not None  # the dropped mass was banked
    # residual == acc - decode(encode(acc)) with acc = params - base
    acc = jax.tree_util.tree_map(
        lambda p, b: np.asarray(p) - np.asarray(b), params, base
    )
    enc = encode_tree(acc, "sign1bit")
    expect = jax.tree_util.tree_map(
        lambda a, d: a - d, acc, decode_tree(enc)
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(st.residual),
        jax.tree_util.tree_leaves(expect),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # and the P=1 aggregate adopted base + own decoded contribution
    for o, b, d in zip(
        jax.tree_util.tree_leaves(out1),
        jax.tree_util.tree_leaves(base),
        jax.tree_util.tree_leaves(decode_tree(enc)),
    ):
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(b) + np.asarray(d), atol=1e-5
        )


def test_aggregate_from_hosts_robust_composes_with_codec():
    """Pre-PR this raised; now trimmed_mean + int8 runs (P=1: decode own
    contribution, trim degenerates to it) — the fail-fast survives only
    for non-decodable codecs (the linear sketches, pinned in
    test_sketch_codecs.py::test_aggregate_from_hosts_robust_rejects_sketch)."""
    from fedrec_tpu.config import RobustConfig
    from fedrec_tpu.parallel.multihost import aggregate_from_hosts

    robust = RobustConfig()
    robust.method = "trimmed_mean"
    robust.trim_k = 1
    params = {"u": _rng_tensor((4,), np.float32, seed=13)}
    out = aggregate_from_hosts(
        params, weight=1.0, compress="int8", robust=robust,
        base=jax.tree_util.tree_map(np.zeros_like, params),
    )
    bound = np.max(np.abs(params["u"])) / 254.0 + 1e-6
    assert np.max(np.abs(np.asarray(out["u"]) - params["u"])) <= bound
