"""``ops/pair_sum.py`` (interpret mode, the code path the chip compiles)
against a plain per-pair loop, and the expert layer that calls it against
the k-gather formulas it replaced (``to_expert_order`` / ``weighted_sum`` as
they stood before the kernel, copied here), forward and backward, in
float32."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedrec_tpu.models import sparse_trunk

pair_sum_module = importlib.import_module("fedrec_tpu.ops.pair_sum")
pair_sum = pair_sum_module.pair_sum


@pytest.fixture()
def small_blocks(monkeypatch):
    """Token blocks of 16: the tests' 37 tokens are three blocks, the last
    one part full."""
    monkeypatch.setattr(pair_sum_module, "MAX_BLOCK", 16)
    pair_sum_module._pair_sum.clear_cache()
    yield
    pair_sum_module._pair_sum.clear_cache()


def plain(src, back, w, held):
    """y[t] = sum over c with back[c, t] < held of w[t, c] * src[back[c, t]],
    pair by pair: the weight rounded to the rows' dtype, each product and
    the sum in float32, in choice order, rounded once."""
    src32 = np.asarray(src.astype(jnp.float32))
    k, tokens = back.shape
    weight = np.ones((tokens, k), np.float32) if w is None else np.asarray(
        w.astype(src.dtype).astype(jnp.float32))
    out = np.zeros((tokens, src.shape[1]), np.float32)
    for t in range(tokens):
        for c in range(k):
            row = int(back[c, t])
            if row < held:
                out[t] += np.float32(weight[t, c]) * src32[row]
    return np.asarray(jnp.asarray(out).astype(src.dtype).astype(jnp.float32))


HELD = {"none": lambda rows: 0, "some": lambda rows: rows // 3, "all": lambda rows: rows}


@pytest.mark.parametrize("held", sorted(HELD))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("width", [128, 256])
@pytest.mark.parametrize("k", [4, 6, 8])
def test_pair_sum_equals_the_plain_per_pair_sum(small_blocks, k, width, dtype, held):
    """37 tokens over blocks of 16; pairs past the buffer (clipped ones)
    beside pairs past ``held``; weighted and unweighted."""
    rng = np.random.default_rng(k * width)
    rows, tokens = 96, 37
    src = jnp.asarray(rng.normal(size=(rows, width)), dtype)
    back = jnp.asarray(rng.integers(0, rows + 40, size=(k, tokens)), jnp.int32)
    assert (np.asarray(back) >= rows).any()
    w = jax.nn.softmax(jnp.asarray(rng.normal(size=(tokens, k)), jnp.float32), axis=-1)
    h = HELD[held](rows)
    tol = dict(rtol=1e-6, atol=1e-6) if dtype == "float32" else dict(rtol=2 ** -8, atol=1e-6)
    for weights in (w, None):
        got = jax.jit(pair_sum)(src, back, weights, jnp.int32(h))
        assert got.shape == (tokens, width) and got.dtype == src.dtype
        want = plain(src, back, weights, h)
        np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), want, **tol)
        if held == "none":
            assert not np.asarray(got.astype(jnp.float32)).any()


@pytest.mark.parametrize("dtype, width", [("float32", 160), ("bfloat16", 320)])
def test_pair_sum_sums_a_row_in_parts(small_blocks, monkeypatch, dtype, width):
    """A row of more words than LANES, and not a multiple of it (160 words
    in chunks of 64: 64, 64, 32), as the cells' rows are (1,280 / 1,792
    words in chunks of 1,024)."""
    monkeypatch.setattr(pair_sum_module, "LANES", 64)
    pair_sum_module._pair_sum.clear_cache()
    rng = np.random.default_rng(width)
    rows, tokens, k = 96, 37, 6
    src = jnp.asarray(rng.normal(size=(rows, width)), dtype)
    back = jnp.asarray(rng.integers(0, rows + 40, size=(k, tokens)), jnp.int32)
    w = jax.nn.softmax(jnp.asarray(rng.normal(size=(tokens, k)), jnp.float32), axis=-1)
    tol = dict(rtol=1e-6, atol=1e-6) if dtype == "float32" else dict(rtol=2 ** -8, atol=1e-6)
    for weights in (w, None):
        got = jax.jit(pair_sum)(src, back, weights, jnp.int32(rows // 2))
        np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                                   plain(src, back, weights, rows // 2), **tol)


def test_token_block_fits_the_slots_and_the_tokens():
    # the three routed cells' chunks: k slots of a block's rows in 4 MiB
    # (two blocks' slots are in VMEM at once)
    assert pair_sum_module.token_block(11_000, 6, 2560, 2) == 128
    assert pair_sum_module.token_block(5_500, 4, 3584, 2) == 128
    assert pair_sum_module.token_block(11_264, 8, 2048, 2) == 128
    assert pair_sum_module.token_block(37, 8, 128, 4) == 48


# ----------------------------- the expert layer against the k-gather formulas
@jax.custom_vjp
def k_gather_to_expert_order(u, order, back):
    return u[jnp.minimum(order // back.shape[0], u.shape[0] - 1)]


def _k_gather_to_expert_order_bwd(back, ct):
    rows = ct.shape[0]
    total = 0.0
    for choice in back:
        picked = ct[jnp.minimum(choice, rows - 1)].astype(jnp.float32)
        total = total + jnp.where((choice < rows)[:, None], picked, 0)
    return total.astype(ct.dtype), None, None


k_gather_to_expert_order.defvjp(
    lambda u, order, back: (k_gather_to_expert_order(u, order, back), back),
    _k_gather_to_expert_order_bwd,
)


@jax.custom_vjp
def k_gather_weighted_sum(down, p, order, back):
    return _k_gather_weighted_sum_fwd(down, p, order, back)[0]


def _k_gather_weighted_sum_fwd(down, p, order, back):
    rows = down.shape[0]
    total = 0.0
    for c, choice in enumerate(back):
        weight = jnp.where(choice < rows, p[:, c], 0).astype(down.dtype)
        picked = down[jnp.minimum(choice, rows - 1)].astype(jnp.float32)
        total = total + picked * weight.astype(jnp.float32)[:, None]
    return total.astype(down.dtype), (down, p, order, back)


def _k_gather_weighted_sum_bwd(res, ct):
    down, p, order, back = res
    rows, (t, k) = order.shape[0], p.shape
    pair = jnp.minimum(order, t * k - 1)
    weight = jnp.where(order < t * k, p.reshape(-1)[pair], 0).astype(ct.dtype)
    of_token = ct[pair // k].astype(jnp.float32)
    d_down = of_token * weight.astype(jnp.float32)[:, None]
    along = jnp.sum(of_token * down.astype(jnp.float32), axis=-1)
    d_p = jnp.where(back < rows, along[jnp.minimum(back, rows - 1)], 0).T
    return d_down.astype(ct.dtype), d_p.astype(p.dtype), None, None


k_gather_weighted_sum.defvjp(_k_gather_weighted_sum_fwd, _k_gather_weighted_sum_bwd)

TOKENS, K, EXPERTS, HELD_EXPERTS, FIRST, D, F = 40, 4, 8, 2, 2, 32, 16


def routing(seed, on_held):
    """Every token's K distinct experts: ``on_held`` of them the two held
    ones (2 and 3), the rest among the six absent."""
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(TOKENS):
        absent = rng.permutation([0, 1, 4, 5, 6, 7])[: K - on_held]
        rows.append(rng.permutation(np.concatenate([[2, 3][:on_held], absent])))
    return jnp.asarray(np.stack(rows), jnp.int32)


def layer_inputs(seed, on_held):
    rng = np.random.default_rng(seed)

    def f32(*shape):
        scale = np.sqrt(shape[-2]) if len(shape) > 2 else 1.0
        return jnp.asarray(rng.normal(size=shape) / scale, jnp.float32)

    p = jax.nn.softmax(f32(TOKENS, K), axis=-1)
    weights = (f32(HELD_EXPERTS, D, F), f32(HELD_EXPERTS, D, F), f32(HELD_EXPERTS, F, D))
    return f32(TOKENS, D), routing(seed, on_held), p, weights


def value_and_grads(u, idx, p, weights):
    mix = jnp.cos(jnp.arange(TOKENS * D, dtype=jnp.float32)).reshape(TOKENS, D)

    def loss(u, p, w_gate, w_up, w_down):
        y, _, full = sparse_trunk.held_experts_output(
            u, idx, p, w_gate, w_up, w_down, FIRST, EXPERTS, jax.nn.silu)
        return jnp.sum(mix * y ** 2), (y, full)

    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(u, p, *weights)


def k_gather_formulas(monkeypatch):
    monkeypatch.setattr(sparse_trunk, "to_expert_order",
                        lambda u, order, back, held: k_gather_to_expert_order(u, order, back))
    monkeypatch.setattr(sparse_trunk, "weighted_sum",
                        lambda down, p, order, back, held: k_gather_weighted_sum(down, p, order, back))


@pytest.mark.parametrize("case", ["small-buffer", "full-size-after-overflow", "cohort-of-two"])
def test_held_experts_equal_the_k_gather_formulas(monkeypatch, case):
    """2 of 8 experts held, 40 tokens of 4 choices: 160 pairs, tiles of 8
    rows, a small buffer of 80 (40 with ``EVEN_SHARE_ROOM`` 1). Output and
    the gradients with respect to ``u``, ``p`` and the three stacks within
    1e-6 (relative) of the formulas the kernel replaced: in the small
    buffer, at the full size after an overflow (``_either_size``), and
    under a two-client cohort's ``vmap``, one of them overflowing."""
    monkeypatch.setattr(sparse_trunk, "ROW_TILE", 8)
    if case == "small-buffer":
        run = lambda: value_and_grads(*layer_inputs(0, on_held=1))  # noqa: E731
        overflowed = [0]
    else:
        monkeypatch.setattr(sparse_trunk, "EVEN_SHARE_ROOM", 1)
        assert sparse_trunk.buffer_rows(TOKENS * K, HELD_EXPERTS, EXPERTS) == (40, 160)
        if case == "full-size-after-overflow":
            run = lambda: value_and_grads(*layer_inputs(1, on_held=2))  # noqa: E731
            overflowed = [1]
        else:
            clients = [layer_inputs(2, on_held=2), layer_inputs(3, on_held=0)]
            stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *clients)
            run = lambda: jax.vmap(value_and_grads)(*stacked)  # noqa: E731
            overflowed = [1, 0]
    (loss, (y, full)), grads = run()
    assert np.asarray(full).reshape(-1).tolist() == overflowed
    k_gather_formulas(monkeypatch)
    (want_loss, (want_y, _)), want_grads = run()
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for got, want in zip((y, *grads), (want_y, *want_grads)):
        got, want = np.asarray(got), np.asarray(want)
        assert np.abs(want).max() > 0
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * np.abs(want).max())
