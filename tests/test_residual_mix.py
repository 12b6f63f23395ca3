"""The latent trunk's residual mixer as Pallas kernels
(``fedrec_tpu/ops/residual_mix.py``, interpreted here) against the plain
``jax.numpy`` formulas it replaced in ``models/latent_trunk.py``: the maps
(``pre``, ``post``, ``M``), ``x_in``, the next state and the distance from
doubly stochastic, and the derivative of every input and parameter, at 45
tokens (the last block partial) and two or four streams."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from fedrec_tpu.ops import residual_mix

HI = lax.Precision.HIGHEST
TOKENS, DIM = 45, 64
SPEC = dict(norm_eps=1e-6, iters=20, hc_eps=1e-6, clamp_lo=-30.0, clamp_hi=30.0)


def mixer_maps(x, p, spec):
    """The jnp mixer: (T, n*d) -> pre (n, T), post (n, T), M (n, n, T) and
    the largest |sum - 1| of a row or column of M; float32 throughout."""
    n = spec.streams
    x32 = x.astype(jnp.float32)
    z = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + spec.norm_eps)
    z = z * p["norm"]["scale"]
    proj = jnp.concatenate([p["proj_pre"], p["proj_post"], p["proj_res"]], axis=-1)
    raw = jnp.einsum("tc,cm->mt", z, proj, precision=HI)
    alpha = p["alpha"]
    pre = jax.nn.sigmoid(alpha[0] * raw[:n] + p["bias_pre"][:, None])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * raw[n:2 * n] + p["bias_post"][:, None])
    res = alpha[2] * raw[2 * n:].reshape(n, n, -1) + p["bias_res"][:, :, None]
    m = jnp.exp(jnp.clip(res, spec.clamp_lo, spec.clamp_hi))

    def iteration(_, m):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + spec.hc_eps)
        return m / (jnp.sum(m, axis=1, keepdims=True) + spec.hc_eps)

    m = lax.fori_loop(0, spec.iters, iteration, m)
    off = lax.stop_gradient(m)
    err = jnp.maximum(jnp.max(jnp.abs(jnp.sum(off, axis=0) - 1.0)),
                      jnp.max(jnp.abs(jnp.sum(off, axis=1) - 1.0)))
    return pre, post, m, err


def plain(x, out, p, spec):
    """(pre (T, n), post (T, n), M (T, n, n), x_in, x_next, err) by the jnp
    formulas: x_in = sum_j pre_j x_j in float32; x_next_i = sum_j M_ij x_j +
    post_i out, summed in float32 and rounded once to the state's dtype."""
    n = spec.streams
    d = x.shape[1] // n
    pre, post, m, err = mixer_maps(x, p, spec)
    streams = [x[:, j * d:(j + 1) * d].astype(jnp.float32) for j in range(n)]
    x_in = sum(pre[j][:, None] * streams[j] for j in range(n))
    out32 = out.astype(jnp.float32)
    x_next = jnp.concatenate([
        sum(m[i, j][:, None] * streams[j] for j in range(n)) + post[i][:, None] * out32
        for i in range(n)], axis=-1).astype(x.dtype)
    return pre.T, post.T, jnp.moveaxis(m, -1, 0), x_in, x_next, err


def kernels(x, out, p, spec):
    x_in, maps, x = residual_mix.read_in(x, p, spec)
    x_next = residual_mix.write_back(x, out, maps)
    pre, post, m, err = residual_mix.unpacked_maps(maps, spec.streams)
    return pre, post, m, x_in, x_next, jnp.max(err)


def mixer_params(n: int, width: int) -> dict:
    """Maps that differ from token to token and a residual bias that leaves
    Sinkhorn something to do (exp of normal(0, 0.5) entries)."""
    rng = np.random.default_rng(2)
    normal = lambda shape, scale: jnp.asarray(  # noqa: E731
        scale * rng.standard_normal(shape, np.float32))
    return {"norm": {"scale": 1.0 + normal((width,), 0.1)},
            "proj_pre": normal((width, n), width ** -0.5),
            "proj_post": normal((width, n), width ** -0.5),
            "proj_res": normal((width, n * n), width ** -0.5),
            "alpha": 1.0 + normal((3,), 0.3), "bias_pre": normal((n,), 0.1),
            "bias_post": normal((n,), 0.1), "bias_res": normal((n, n), 0.5)}


def forward_and_vjp(fn, spec, x, out, p, cts):
    """Both sides in one program: the six outputs and the derivative of
    sum(output * cotangent) over the first five in every input and
    parameter."""
    def loss(x, out, p):
        outs = fn(x, out, p, spec)
        return sum(jnp.sum(o.astype(jnp.float32) * c) for o, c in zip(outs[:5], cts)), outs

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))(x, out, p)


# the float32 kernels are the bfloat16 ones with the state's projection in
# three parts, so one stream count covers them (an interpreted float32 case
# is the slowest of the file)
@pytest.mark.parametrize("streams,dtype", [(2, "bfloat16"), (4, "bfloat16"), (4, "float32")],
                         ids=["2-bfloat16", "4-bfloat16", "4-float32"])
def test_the_kernels_equal_the_plain_mixer(streams, dtype):
    spec = residual_mix.MixSpec(streams, **SPEC)
    width = streams * DIM
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((TOKENS, width), np.float32), dtype)
    out = jnp.asarray(rng.standard_normal((TOKENS, DIM), np.float32), dtype)
    p = mixer_params(streams, width)
    shapes = [(TOKENS, streams), (TOKENS, streams), (TOKENS, streams, streams), (TOKENS, DIM),
              (TOKENS, width)]
    cts = [jnp.asarray(rng.standard_normal(s, np.float32)) for s in shapes]
    f32 = lambda tree: jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32), tree)  # noqa: E731
    g_want, want = f32(forward_and_vjp(plain, spec, x, out, p, cts))
    g_got, got = f32(forward_and_vjp(kernels, spec, x, out, p, cts))
    assert [o.shape for o in got[:5]] == shapes
    # Sinkhorn did its work: each M is doubly stochastic to well under 1e-3
    assert 0 < float(got[5]) < 1e-3
    names = ("pre", "post", "M", "x_in", "x_next", "err")
    # the maps, x_in and the distance are float32 and read the same rows on
    # both sides; so does every parameter's gradient
    for name, a, b in zip(names, got, want):
        if name != "x_next" or dtype == "float32":
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5, err_msg=name)
    g_x, g_out, g_p = g_got
    for (path, b), a in zip(jax.tree_util.tree_flatten_with_path(g_want[2])[0],
                            jax.tree_util.tree_leaves(g_p)):
        assert np.linalg.norm(a - b) / np.linalg.norm(b) < 2e-4, jax.tree_util.keystr(path)
    if dtype == "float32":
        # as the trunk's float32 test holds the trunk to its reference
        for name, a, b in (("x", g_x, g_want[0]), ("out", g_out, g_want[1])):
            assert np.linalg.norm(a - b) / np.linalg.norm(b) < 2e-4, name
        return
    # a bfloat16 state: x_next and the sublayer output's cotangent are sums
    # in float32 rounded once on both sides (they read equal here), held to
    # one bfloat16 step of each element; the state's cotangent is rounded
    # once by the kernels and at every use by the formulas (0.3% apart here)
    for a, b in ((got[4], want[4]), (g_out, g_want[1])):
        np.testing.assert_allclose(a, b, rtol=2.0 ** -7, atol=1e-6)
    assert np.linalg.norm(g_x - g_want[0]) / np.linalg.norm(g_want[0]) < 1e-2


def test_the_maps_lanes_hold_at_most_five_streams():
    x = np.zeros((8, 6 * 4), np.float32)
    with pytest.raises(ValueError, match="streams"):
        residual_mix.read_in(x, {}, residual_mix.MixSpec(6, **SPEC))
