"""The latent trunk's residual mixer as Pallas TPU kernels with a custom VJP:
``read_in`` and ``write_back``.

A token carries ``n`` streams side by side, a state row of ``n*d``. Around
every sublayer ``F`` a mixer reads the streams into ``F`` and writes its
output back (``models/latent_trunk.py`` has the equations)::

    z      = RMSNorm(x; g)                 the n*d row, float32
    raw    = z P                           P = [P_pre | P_post | P_res]
    pre    = sigmoid(a_pre raw_pre + b_pre);  post = 2 sigmoid(a_post raw_post + b_post)
    M      = Sinkhorn^iters(exp(clip(a_res raw_res + b_res)))   (n x n, row by row)
    x_in   = sum_j pre_j x_j                                    (float32)
    x_next = sum_j M_ij x_j + post_i out                        (float32, rounded once)

Done as XLA ops every one of these is a pass over the ``(T, n*d)`` state.
Here a pass reads a block of tokens' whole rows once:

* ``read_in`` (kernel ``residual_mix_in``): the norm, the projection, the
  maps, Sinkhorn and ``x_in`` in one pass over ``x``; it writes ``x_in`` and
  a ``(T, 128)`` float32 row of maps a token (``maps``: the layout below).
* ``write_back`` (kernel ``residual_mix_out``): ``x_next`` in one pass.
* their derivatives: ``residual_mix_out_bwd`` reads ``dx_next``, ``x`` and
  ``out`` once and writes the streams' cotangent through ``M``, ``d_out``
  and the maps' cotangents; ``residual_mix_in_bwd`` reads that, ``x`` and
  ``dx_in`` once and writes ``dx`` whole (the mixing, ``pre`` and the path
  through the norm and the projection summed in float32, rounded once),
  the maps' derivative back through Sinkhorn's iterations (recomputed in
  VMEM from the kept ``raw``) and the mixer's gradients, accumulated over
  the token blocks.

``read_in`` returns ``x`` itself beside ``x_in`` and the maps, and
``write_back`` takes that: the streams' cotangent through ``M`` then reaches
``residual_mix_in_bwd`` as the cotangent of that output and is summed into
``dx`` there, in the same pass, instead of by an XLA add of two ``(T, n*d)``
cotangents.

The projection runs on the MXU as exactly as the ``HIGHEST`` product it
replaces: ``z P = r (x (g * P))`` with ``r`` the norm's reciprocal root, the
float32 ``g * P`` split into three bfloat16 parts and ``x`` into one (a
bfloat16 state is exact in it) or three, every product of parts whose ranks
sum to at most 2 accumulated in float32, as XLA's six-pass ``HIGHEST`` does
(two parts side by side in one 128-wide pass). The backward's two products
(``d_raw (g * P)^T`` and the token sum ``x^T (r d_raw)``) are split alike.

Maps are tokens along lanes inside the kernels, one row of 8 sublanes a
group (the n x n arithmetic runs over whole lanes): row ``PRE + j`` is
``pre_j``, ``POST + i`` is ``post_i``, ``RES + 8 i + j`` is ``M_ij``, rows
past ``n`` in a group are zero; ``ERR`` holds the token's largest distance
of a row or column sum of ``M`` from 1; rows ``RAW + k`` hold ``raw`` of
row ``k`` (the projection before its scale and bias), which the backward
reads to recompute the rest. ``maps`` in HBM is that ``(128, T)`` block
transposed, a token's 128 numbers in a row; only the lanes of ``pre``,
``post`` and ``M`` take a cotangent (``ERR`` and ``raw`` are read, not
differentiated).

The last token block may be partial: its rows past ``T`` are never written,
and the backward masks them out of the gradients it accumulates. Off the
TPU the kernels run interpreted (``attention_kernels._interpret``, the
repo's one switch); under a cohort's ``vmap`` they run client by client.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedrec_tpu.ops import attention_kernels

PRE, POST, RES, ERR, RAW = 0, 8, 16, 63, 64
MAP_ROWS = 64          # rows of the projection: the three maps' groups
LANES = 128            # a token's row of ``maps``
GROUP = 8              # sublanes a map group takes
MAX_STREAMS = (ERR - RES) // GROUP
# tokens a grid step takes forward and backward (a whole number of lanes:
# the maps are transposed inside), and the VMEM a kernel may use (a v5e
# core has 128 MiB; the backward's float32 temporaries take the most)
BLOCK_FWD = 256
BLOCK_BWD = 128
VMEM_LIMIT = 96 * 1024 * 1024
SCOPE = "residual_mix"

_F32 = jnp.float32
_BF16 = jnp.bfloat16
_HIGH_HALF = np.uint32(0xFFFF0000)     # sign, exponent and 7 bits of a float32


class MixSpec(NamedTuple):
    """The mixer's fixed numbers: streams, the norm's eps, Sinkhorn's
    iterations and eps, the clamp before ``exp``."""

    streams: int
    norm_eps: float
    iters: int
    hc_eps: float
    clamp_lo: float
    clamp_hi: float


# ------------------------------------------------------------------ helpers
def _parts(x: jnp.ndarray, count: int = 3) -> list[jnp.ndarray]:
    """``count`` float32 parts of ``x``, each exact in bfloat16, whose sum is
    ``x`` (to 24 bits): hi, mid, lo. A part is its remainder with the low 16
    bits cleared, not a round trip through bfloat16, which a compiler that
    allows excess precision folds away (the parts are then ``x``, 0, 0)."""
    out = []
    x = x.astype(_F32)
    for _ in range(count):
        part = lax.bitcast_convert_type(lax.bitcast_convert_type(x, jnp.uint32) & _HIGH_HALF, _F32)
        out.append(part)
        x = x - part
    return out


def _stream_parts(x: jnp.ndarray) -> list[jnp.ndarray]:
    """The bfloat16 parts of a slice of the state: itself where it is
    bfloat16, hi, mid and lo where it is float32."""
    return [x] if x.dtype == _BF16 else [p.astype(_BF16) for p in _parts(x)]


def _dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.dot(a, b, preferred_element_type=_F32)


def _stacked(parts: list[jnp.ndarray], transpose: bool = False) -> jnp.ndarray:
    """float32 parts stacked along sublanes, transposed if asked, in bfloat16."""
    out = jnp.concatenate(parts, axis=0)
    return (jnp.transpose(out) if transpose else out).astype(_BF16)


def _row_of(col: jnp.ndarray) -> jnp.ndarray:
    """(bm, 1) -> (1, bm): one number a token from sublanes to lanes."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], LANES)))[0:1]


def _lane_put(cols: dict[int, jnp.ndarray], rows: int) -> jnp.ndarray:
    """(rows, LANES) float32 holding column ``k`` = ``cols[k]`` (each (rows, 1)),
    zeros elsewhere."""
    lane = lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    out = jnp.zeros((rows, LANES), _F32)
    for k, v in cols.items():
        out = jnp.where(lane == k, v, out)
    return out


def _sinkhorn_step(m: list, eps: float) -> list:
    """One iteration over the rows ``m[i]`` (GROUP, bm): the columns, then
    the rows."""
    col = functools.reduce(jnp.add, m) + eps
    m = [mi / col for mi in m]
    return [mi / (jnp.sum(mi, axis=0, keepdims=True) + eps) for mi in m]


def _maps_from_raw(raw: jnp.ndarray, coef: jnp.ndarray, spec: MixSpec):
    """raw (MAP_ROWS, bm) -> the affine maps ``h``, ``pre``, ``post`` and
    ``M``'s rows before Sinkhorn (rows past ``n`` of a group zero)."""
    n = spec.streams
    h = raw * coef[:, 0:1] + coef[:, 1:2]
    pre = jax.nn.sigmoid(h[PRE:PRE + GROUP])
    post = 2.0 * jax.nn.sigmoid(h[POST:POST + GROUP])
    real = lax.broadcasted_iota(jnp.int32, (GROUP, 1), 0) < n
    m0 = [jnp.where(real, jnp.exp(jnp.clip(h[RES + GROUP * i:RES + GROUP * (i + 1)],
                                            spec.clamp_lo, spec.clamp_hi)), 0.0)
          for i in range(n)]
    return h, pre, post, m0, real


def _valid(step, block: int, tokens: int, shape, axis: int) -> jnp.ndarray:
    return step * block + lax.broadcasted_iota(jnp.int32, shape, axis) < tokens


# ----------------------------------------------------------------- kernels
def _in_kernel(x_ref, g_ref, coef_ref, xin_ref, maps_ref, *, spec: MixSpec, d: int):
    n, bm = spec.streams, x_ref.shape[0]
    width = n * d
    ss = None
    for j in range(n):
        xj = x_ref[:, j * d:(j + 1) * d].astype(_F32)
        s = jnp.sum(xj * xj, axis=1, keepdims=True)
        ss = s if ss is None else ss + s
    r = lax.rsqrt(ss / width + spec.norm_eps)                                # (bm, 1)
    # x (g * P) with (g * P) as [hi | mid] and [lo | 0] columns: every part
    # of x meets hi and mid, its leading part lo too
    xs = _stream_parts(x_ref[...])
    both = functools.reduce(jnp.add, [_dot(p, g_ref[0]) for p in xs]) + _dot(xs[0], g_ref[1])
    both = jnp.transpose(both)                                              # (LANES, bm)
    raw = (both[:MAP_ROWS] + both[MAP_ROWS:]) * _row_of(r)                  # (MAP_ROWS, bm)
    _, pre, post, m0, real = _maps_from_raw(raw, coef_ref[...], spec)
    m = lax.fori_loop(0, spec.iters, lambda _, m: _sinkhorn_step(m, spec.hc_eps), m0)
    col = functools.reduce(jnp.add, m)
    err = jnp.max(jnp.where(real, jnp.abs(col - 1.0), 0.0), axis=0, keepdims=True)
    for mi in m:
        err = jnp.maximum(err, jnp.abs(jnp.sum(mi, axis=0, keepdims=True) - 1.0))
    tail = jnp.where(lax.broadcasted_iota(jnp.int32, (GROUP, 1), 0) == ERR % GROUP, err, 0.0)
    pad = ERR - ERR % GROUP - RES - GROUP * n
    rows = [pre, post, *m] + ([jnp.zeros((pad, bm), _F32)] if pad else []) + [tail, raw]
    maps = jnp.transpose(jnp.concatenate(rows, axis=0))                     # (bm, LANES)
    maps_ref[...] = maps
    acc = None
    for j in range(n):
        y = maps[:, PRE + j:PRE + j + 1] * x_ref[:, j * d:(j + 1) * d].astype(_F32)
        acc = y if acc is None else acc + y
    xin_ref[...] = acc


def _out_kernel(x_ref, out_ref, maps_ref, y_ref, *, n: int, d: int):
    maps = maps_ref[...]
    o = out_ref[...].astype(_F32)
    for i in range(n):
        acc = None
        for j in range(n):
            c = RES + GROUP * i + j
            y = maps[:, c:c + 1] * x_ref[:, j * d:(j + 1) * d].astype(_F32)
            acc = y if acc is None else acc + y
        y_ref[:, i * d:(i + 1) * d] = (acc + maps[:, POST + i:POST + i + 1] * o).astype(y_ref.dtype)


def _out_bwd_kernel(dy_ref, x_ref, out_ref, maps_ref, dx_ref, dout_ref, dmaps_ref, *, n: int, d: int):
    maps = maps_ref[...]
    o = out_ref[...].astype(_F32)
    dy = [dy_ref[:, i * d:(i + 1) * d].astype(_F32) for i in range(n)]
    cols = {}
    dout = None
    for i in range(n):
        cols[POST + i] = jnp.sum(dy[i] * o, axis=1, keepdims=True)
        y = maps[:, POST + i:POST + i + 1] * dy[i]
        dout = y if dout is None else dout + y
    dout_ref[...] = dout.astype(dout_ref.dtype)
    for j in range(n):
        xj = x_ref[:, j * d:(j + 1) * d].astype(_F32)
        acc = None
        for i in range(n):
            c = RES + GROUP * i + j
            cols[c] = jnp.sum(dy[i] * xj, axis=1, keepdims=True)
            y = maps[:, c:c + 1] * dy[i]
            acc = y if acc is None else acc + y
        dx_ref[:, j * d:(j + 1) * d] = acc.astype(dx_ref.dtype)
    dmaps_ref[...] = _lane_put(cols, maps.shape[0])


def _in_bwd_kernel(dxp_ref, x_ref, dxin_ref, maps_ref, dmaps_ref, gs_ref, coef_ref,
                   dx_ref, dgt_ref, dcoef_ref, gdz_ref, iters_ref, *, spec: MixSpec, d: int,
                   tokens: int):
    n, bm = spec.streams, x_ref.shape[0]
    width = n * d
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        dgt_ref[...] = jnp.zeros_like(dgt_ref)
        dcoef_ref[...] = jnp.zeros_like(dcoef_ref)

    # rows past the tokens (a partial last block) hold whatever the buffer
    # held: zero them before anything sums over tokens
    rows_ok = _valid(step, bm, tokens, (bm, 1), 0)
    lanes_ok = _valid(step, bm, tokens, (1, bm), 1)
    x = jnp.where(rows_ok, x_ref[...], 0)
    xs = [x[:, j * d:(j + 1) * d].astype(_F32) for j in range(n)]
    dxin = jnp.where(rows_ok, dxin_ref[...], 0.0)
    ss = functools.reduce(jnp.add, [jnp.sum(xj * xj, axis=1, keepdims=True) for xj in xs])
    r = lax.rsqrt(ss / width + spec.norm_eps)                                # (bm, 1)
    maps = maps_ref[...]
    # the cotangents of pre (from x_in) beside those of post and M, tokens
    # along lanes
    cols = {PRE + j: jnp.sum(dxin * xs[j], axis=1, keepdims=True) for j in range(n)}
    dt = jnp.transpose(jnp.where(rows_ok, dmaps_ref[...], 0.0) + _lane_put(cols, bm))
    mt = jnp.where(lanes_ok, jnp.transpose(maps), 0.0)
    raw = mt[RAW:RAW + MAP_ROWS]
    coef = coef_ref[...]
    h, pre, post, m0, real = _maps_from_raw(raw, coef, spec)
    dh_pre = dt[PRE:PRE + GROUP] * pre * (1.0 - pre)
    dh_post = dt[POST:POST + GROUP] * post * (1.0 - 0.5 * post)

    # Sinkhorn's iterations again, each one's input kept in VMEM, then their
    # derivative in reverse order
    def forward(it, m):
        for i in range(n):
            iters_ref[it, i] = m[i]
        return _sinkhorn_step(m, spec.hc_eps)

    lax.fori_loop(0, spec.iters, forward, m0)

    def backward(k, g):
        m = [iters_ref[spec.iters - 1 - k, i] for i in range(n)]
        col = functools.reduce(jnp.add, m) + spec.hc_eps
        a = [mi / col for mi in m]
        row = [jnp.sum(ai, axis=0, keepdims=True) + spec.hc_eps for ai in a]
        b = [ai / ri for ai, ri in zip(a, row)]
        ga = [(gi - jnp.sum(gi * bi, axis=0, keepdims=True)) / ri for gi, bi, ri in zip(g, b, row)]
        inner = functools.reduce(jnp.add, [gai * ai for gai, ai in zip(ga, a)])
        return [jnp.where(real, (gai - inner) / col, 0.0) for gai in ga]

    g = lax.fori_loop(0, spec.iters, backward,
                      [dt[RES + GROUP * i:RES + GROUP * (i + 1)] for i in range(n)])
    inside = [(hi >= spec.clamp_lo) & (hi <= spec.clamp_hi)
              for hi in (h[RES + GROUP * i:RES + GROUP * (i + 1)] for i in range(n))]
    dh_res = [jnp.where(real & ok, gi * mi, 0.0) for gi, mi, ok in zip(g, m0, inside)]
    pad = MAP_ROWS - RES - GROUP * n
    dh = jnp.concatenate([dh_pre, dh_post, *dh_res] + ([jnp.zeros((pad, bm), _F32)] if pad else []),
                         axis=0)
    dh = jnp.where(lanes_ok, dh, 0.0)                                       # (MAP_ROWS, bm)
    lane = lax.broadcasted_iota(jnp.int32, (MAP_ROWS, LANES), 1)
    dcoef_ref[...] += jnp.where(lane == 0, jnp.sum(dh * raw, axis=1, keepdims=True),
                                jnp.where(lane == 1, jnp.sum(dh, axis=1, keepdims=True), 0.0))
    d_raw = dh * coef[:, 0:1]
    # the projection's gradient as (g * P)^T's: the sum over tokens of
    # r d_raw x, d_raw's three parts stacked against each part of x
    y3 = _stacked(_parts(d_raw * _row_of(r)))                               # (3 MAP_ROWS, bm)
    for j in range(n):
        for p in _stream_parts(x[:, j * d:(j + 1) * d]):
            y = _dot(y3, p)
            dgt_ref[:, j * d:(j + 1) * d] += (y[:MAP_ROWS] + y[MAP_ROWS:2 * MAP_ROWS]
                                              + y[2 * MAP_ROWS:])
    # and the cotangent of r x, d_raw (g * P)^T, in three passes of two parts
    # each against the rows [hi; hi], [mid; hi], [lo; mid]
    hi, mid, lo = _parts(d_raw)
    l1, l2 = _stacked([hi, mid], transpose=True), _stacked([hi, lo], transpose=True)
    s = None
    for j in range(n):
        span = slice(j * d, (j + 1) * d)
        gdz = _dot(l1, gs_ref[0, :, span]) + _dot(l2, gs_ref[1, :, span]) + _dot(l1, gs_ref[2, :, span])
        gdz_ref[:, j * d:(j + 1) * d] = gdz
        t = jnp.sum(xs[j] * gdz, axis=1, keepdims=True)
        s = t if s is None else s + t
    shrink = r * r * r * s / width
    for j in range(n):
        dx = (dxp_ref[:, j * d:(j + 1) * d].astype(_F32) + maps[:, PRE + j:PRE + j + 1] * dxin
              + r * gdz_ref[:, j * d:(j + 1) * d] - xs[j] * shrink)
        dx_ref[:, j * d:(j + 1) * d] = dx.astype(dx_ref.dtype)


# -------------------------------------------------------------- pallas calls
def _block(tokens: int, most: int) -> int:
    return min(most, -(-tokens // LANES) * LANES)


def _rows(bm: int, width: int):
    return pl.BlockSpec((bm, width), lambda i: (i, 0))


def _whole(shape):
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def _pallas(kernel, name: str, args, out_shape, in_specs, out_specs, bm: int,
            semantics: str = "parallel", scratch=()):
    """One grid step a block of ``bm`` tokens; the kernel's ops under the
    scope ``residual_mix`` whatever the caller's (the name stack of a custom
    VJP's rules does not reach their ops under differentiation)."""
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            kernel, out_shape=out_shape, grid=(pl.cdiv(args[0].shape[0], bm),),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=list(scratch),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(semantics,), vmem_limit_bytes=VMEM_LIMIT),
            interpret=attention_kernels._interpret(), name=name,
        )(*args)


@functools.partial(jax.jit, static_argnames="spec")
def _in_call(x, g, coef, spec: MixSpec):
    tokens, width = x.shape
    d, bm = width // spec.streams, _block(tokens, BLOCK_FWD)
    return _pallas(
        functools.partial(_in_kernel, spec=spec, d=d), "residual_mix_in", (x, g, coef),
        (jax.ShapeDtypeStruct((tokens, d), _F32), jax.ShapeDtypeStruct((tokens, LANES), _F32)),
        [_rows(bm, width), _whole(g.shape), _whole(coef.shape)],
        (_rows(bm, d), _rows(bm, LANES)), bm)


@jax.jit
def _out_call(x, out, maps):
    (tokens, width), d = x.shape, out.shape[1]
    bm = _block(tokens, BLOCK_FWD)
    return _pallas(
        functools.partial(_out_kernel, n=width // d, d=d), "residual_mix_out", (x, out, maps),
        jax.ShapeDtypeStruct(x.shape, x.dtype),
        [_rows(bm, width), _rows(bm, d), _rows(bm, LANES)], _rows(bm, width), bm)


@jax.jit
def _out_bwd_call(dy, x, out, maps):
    (tokens, width), d = x.shape, out.shape[1]
    bm = _block(tokens, BLOCK_BWD)
    return _pallas(
        functools.partial(_out_bwd_kernel, n=width // d, d=d), "residual_mix_out_bwd",
        (dy, x, out, maps),
        (jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(out.shape, out.dtype),
         jax.ShapeDtypeStruct(maps.shape, _F32)),
        [_rows(bm, width), _rows(bm, width), _rows(bm, d), _rows(bm, LANES)],
        (_rows(bm, width), _rows(bm, d), _rows(bm, LANES)), bm)


@functools.partial(jax.jit, static_argnames="spec")
def _in_bwd_call(dxp, x, dxin, maps, dmaps, gs, coef, spec: MixSpec):
    tokens, width = x.shape
    d, bm = width // spec.streams, _block(tokens, BLOCK_BWD)
    return _pallas(
        functools.partial(_in_bwd_kernel, spec=spec, d=d, tokens=tokens), "residual_mix_in_bwd",
        (dxp, x, dxin, maps, dmaps, gs, coef),
        (jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct((MAP_ROWS, width), _F32),
         jax.ShapeDtypeStruct((MAP_ROWS, LANES), _F32)),
        [_rows(bm, width), _rows(bm, width), _rows(bm, d), _rows(bm, LANES), _rows(bm, LANES),
         _whole(gs.shape), _whole(coef.shape)],
        (_rows(bm, width), _whole((MAP_ROWS, width)), _whole((MAP_ROWS, LANES))), bm,
        semantics="arbitrary",
        scratch=(pltpu.VMEM((bm, width), _F32),
                 pltpu.VMEM((spec.iters, spec.streams, GROUP, bm), _F32)))


@functools.lru_cache(maxsize=None)
def _sequential(call, spec: MixSpec | None = None):
    """``call`` with ``spec`` bound, run client by client under ``vmap``."""
    return jax.custom_batching.sequential_vmap(
        call if spec is None else functools.partial(call, spec=spec))


# ---------------------------------------------- the parameters, packed
def _map_rows(n: int) -> list[tuple[int, int]]:
    """(row of the maps, column of ``[P_pre | P_post | P_res]``) pairs."""
    return ([(PRE + j, j) for j in range(n)] + [(POST + i, n + i) for i in range(n)]
            + [(RES + GROUP * i + j, 2 * n + n * i + j) for i in range(n) for j in range(n)])


def _packed(params: dict, spec: MixSpec):
    """``(g * P)^T`` in the maps' rows, float32 (MAP_ROWS, n*d), and each
    row's scale and bias in lanes 0 and 1 of a (MAP_ROWS, LANES) array."""
    n = spec.streams
    proj = jnp.concatenate([params["proj_pre"], params["proj_post"], params["proj_res"]], axis=1)
    rows, cols = map(list, zip(*_map_rows(n)))
    pt = jnp.zeros((MAP_ROWS, proj.shape[0]), _F32).at[jnp.asarray(rows)].set(proj.T[jnp.asarray(cols)])
    alpha = params["alpha"]
    bias = jnp.concatenate([params["bias_pre"], params["bias_post"], params["bias_res"].reshape(-1)])
    scale = jnp.zeros((MAP_ROWS,), _F32).at[jnp.asarray(rows)].set(
        alpha[jnp.asarray([0] * n + [1] * n + [2] * (n * n))])
    shift = jnp.zeros((MAP_ROWS,), _F32).at[jnp.asarray(rows)].set(bias)
    coef = jnp.zeros((MAP_ROWS, LANES), _F32).at[:, 0].set(scale).at[:, 1].set(shift)
    return pt, pt * params["norm"]["scale"][None, :], coef


def _unpacked(pt, dgt, dcoef, params: dict, spec: MixSpec) -> dict:
    """The gradients of the mixer's parameters from those of ``(g * P)^T``
    and of each row's scale and bias."""
    n = spec.streams
    rows, cols = map(list, zip(*_map_rows(n)))
    rows = jnp.asarray(rows)
    g = params["norm"]["scale"]
    dproj = (dgt * g[None, :])[rows].T                                       # (n*d, 2n + n*n)
    d_alpha = dcoef[rows, 0]
    d_bias = dcoef[rows, 1]
    return {
        "norm": {"scale": jnp.sum(dgt * pt, axis=0)},
        "proj_pre": dproj[:, :n], "proj_post": dproj[:, n:2 * n], "proj_res": dproj[:, 2 * n:],
        "alpha": jnp.stack([jnp.sum(d_alpha[:n]), jnp.sum(d_alpha[n:2 * n]), jnp.sum(d_alpha[2 * n:])]),
        "bias_pre": d_bias[:n], "bias_post": d_bias[n:2 * n],
        "bias_res": d_bias[2 * n:].reshape(n, n),
    }


# --------------------------------------------------------- the two calls
def _check(width: int, spec: MixSpec):
    if not 0 < spec.streams <= MAX_STREAMS or width % spec.streams:  # fedrec-lint: disable=TS105
        raise ValueError(f"{spec.streams} streams over a row of {width}: the maps hold "
                         f"1 to {MAX_STREAMS} streams of equal width")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def read_in(x: jnp.ndarray, params: dict, spec: MixSpec):
    """x (T, n*d) the state, ``params`` the mixer's tree (``norm/scale``,
    ``proj_pre``, ``proj_post``, ``proj_res``, ``alpha``, ``bias_pre``,
    ``bias_post``, ``bias_res``) -> (x_in (T, d) float32, maps (T, 128)
    float32, x): the sublayer's input, the per-token maps (``pre``,
    ``post``, ``M``, the distance from doubly stochastic, ``raw``: the
    module's docstring has the lanes), and the state for ``write_back``."""
    return _read_in_fwd(x, params, spec)[0]


def _read_in_fwd(x, params, spec):
    _check(x.shape[-1], spec)
    _, gt, coef = _packed(params, spec)
    hi, mid, lo = _parts(gt)
    g = jnp.stack([_stacked([hi, mid]).T, _stacked([lo, jnp.zeros_like(lo)]).T])
    x_in, maps = _sequential(_in_call, spec)(x, g, coef)
    return (x_in, maps, x), (x, params, maps)


def _read_in_bwd(spec, res, cts):
    x, params, maps = res
    dx_in, dmaps, dx_pass = cts
    pt, gt, coef = _packed(params, spec)
    hi, mid, lo = _parts(gt)
    dx, dgt, dcoef = _sequential(_in_bwd_call, spec)(
        dx_pass.astype(x.dtype), x, dx_in.astype(_F32), maps, dmaps.astype(_F32),
        jnp.stack([_stacked(p) for p in ([hi, hi], [mid, hi], [lo, mid])]), coef)
    return dx, _unpacked(pt, dgt, dcoef, params, spec)


read_in.defvjp(_read_in_fwd, _read_in_bwd)


@jax.custom_vjp
def write_back(x: jnp.ndarray, out: jnp.ndarray, maps: jnp.ndarray) -> jnp.ndarray:
    """x (T, n*d) as ``read_in`` returned it, the sublayer's output (T, d)
    and the maps -> the next state, ``x_next_i = sum_j M_ij x_j + post_i
    out`` in float32, rounded once to ``x``'s dtype."""
    return _sequential(_out_call)(x, out, maps)


def _write_back_fwd(x, out, maps):
    return write_back(x, out, maps), (x, out, maps)


def _write_back_bwd(res, dy):
    x, out, maps = res
    return _sequential(_out_bwd_call)(dy.astype(x.dtype), x, out, maps)


write_back.defvjp(_write_back_fwd, _write_back_bwd)


def unpacked_maps(maps: jnp.ndarray, n: int):
    """maps (T, 128) -> pre (T, n), post (T, n), M (T, n, n) and each token's
    distance of ``M`` from doubly stochastic (T,)."""
    res = jnp.stack([maps[:, RES + GROUP * i:RES + GROUP * i + n] for i in range(n)], axis=1)
    return maps[:, PRE:PRE + n], maps[:, POST:POST + n], res, lax.stop_gradient(maps[:, ERR])
