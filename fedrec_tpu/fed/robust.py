"""Byzantine-robust aggregation: the *reaction* half of training robustness.

PR 4's health sentry made a poisoned or diverging client *visible*
(per-client grad/update norms, outlier flags) — but the aggregator still
blended its update into everyone's parameters: ``weighted_param_avg`` is a
weighted mean, and a single ×1000-scaled contribution moves the mean by
×1000/n. This module supplies aggregators with bounded (or zero)
sensitivity to any one client, selectable via ``fed.robust.method``:

* ``mean``         — the existing participation-weighted FedAvg
  (``fedrec_tpu.fed.strategies.weighted_param_avg``); kept as the default
  and bit-identical to pre-robust behavior.
* ``clip``         — norm-clipped mean: each client's deviation from the
  coordinate-wise cohort *median* (a robust center available in-graph,
  unlike the round-start global) is clipped to ``clip_norm`` in global L2
  over the whole aggregated tree, then weighted-mean'd around the center.
  One client moves the aggregate by at most ``w_c * clip_norm / Σw`` —
  and a non-finite contribution clips to exactly zero.
* ``trimmed_mean`` — coordinate-wise: among *finite participant* values,
  drop the ``trim_k`` largest and smallest, mean the rest (unweighted
  over the kept participants, the standard definition — ``trim_k`` is
  clamped per-coordinate so at least one value is always kept).
* ``median``       — coordinate-wise median over finite participants.

All four run INSIDE the jitted round-end sync (``shard_map`` over the
cohort axes), so they compose with everything already in the program: DP
noise is applied per client *before* the sync and FedOpt steps the
post-aggregation global (``train.step._make_local_sync``).

Cost note: the robust methods materialize the full cohort per device via
``lax.all_gather`` — n_clients × params transient memory. Fine for the
cohort sizes federation simulates per chip (8–64 clients); the
coordinator's cross-host gather uses the numpy variant below on arrays
``process_allgather`` already materializes.

Non-participants (weight 0) are excluded from every method — which also
makes quarantine effective: a quarantined client whose parameters are NaN
contributes nothing, not NaN, to any aggregate (including ``mean``, whose
``weighted_param_avg`` masks zero-weight contributions for this reason).
A round with NO participants keeps local parameters, same contract as
``weighted_param_avg``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

ROBUST_METHODS = ("mean", "clip", "trimmed_mean", "median")


def validate_robust_method(method: str) -> str:
    if method not in ROBUST_METHODS:
        raise ValueError(
            f"unknown fed.robust.method {method!r}; expected one of "
            f"{ROBUST_METHODS}"
        )
    return method


# --------------------------------------------------------------- in-graph
# fedrec-lint: traced-scope — compiled into the shard_map round-end sync
def _gather_cohort(x: jnp.ndarray, axis: Any) -> jnp.ndarray:
    """All clients' values as a leading (n, ...) dim, regardless of the
    client->chip packing. Cohort deployments sync over a (LOCAL_AXIS,
    mesh_axis) tuple — ``all_gather`` does not take the joint tuple under
    vmap, so gather one axis at a time and flatten; values and weights go
    through the SAME function, so their per-client pairing is consistent
    (the aggregators treat clients symmetrically, so the flattened order
    itself does not matter)."""
    if isinstance(axis, (tuple, list)):
        out = x
        for ax in axis:
            out = lax.all_gather(out, axis_name=ax, axis=0)
        return out.reshape((-1,) + tuple(x.shape))
    return lax.all_gather(x, axis_name=axis, axis=0)


# fedrec-lint: traced-scope — compiled into the shard_map round-end sync
def _sorted_participants(gathered: jnp.ndarray, wmask: jnp.ndarray):
    """Sort a gathered (n, ...) leaf so finite participant values come
    first, ascending; everything else (dropouts, quarantined clients,
    NaN/inf cells) is replaced by +inf and lands at the end. Returns
    ``(sorted_vals, m)`` where ``m`` is the per-coordinate count of finite
    participant values."""
    shape = (-1,) + (1,) * (gathered.ndim - 1)
    w = wmask.reshape(shape)
    finite = jnp.isfinite(gathered) & (w > 0)
    vals = jnp.where(finite, gathered, jnp.inf)
    return jnp.sort(vals, axis=0), jnp.sum(finite.astype(jnp.int32), axis=0)


# fedrec-lint: traced-scope — compiled into the shard_map round-end sync
def _trimmed_mean_leaf(gathered, wmask, trim_k: int):
    srt, m = _sorted_participants(gathered, wmask)
    pos = jnp.arange(srt.shape[0]).reshape((-1,) + (1,) * (srt.ndim - 1))
    # clamp so >= 1 value is always kept, even per-coordinate
    k = jnp.minimum(trim_k, (m - 1) // 2)
    keep = (pos >= k) & (pos < m - k)
    denom = jnp.maximum(m - 2 * k, 1).astype(srt.dtype)
    mean = jnp.sum(jnp.where(keep, srt, 0.0), axis=0) / denom
    return mean, m


# fedrec-lint: traced-scope — compiled into the shard_map round-end sync
def _median_leaf(gathered, wmask):
    srt, m = _sorted_participants(gathered, wmask)
    pos = jnp.arange(srt.shape[0]).reshape((-1,) + (1,) * (srt.ndim - 1))
    lo, hi = (m - 1) // 2, m // 2  # equal when m is odd
    safe = jnp.where(jnp.isfinite(srt), srt, 0.0)  # m==0: all-inf column
    lo_v = jnp.sum(jnp.where(pos == lo, safe, 0.0), axis=0)
    hi_v = jnp.sum(jnp.where(pos == hi, safe, 0.0), axis=0)
    return 0.5 * (lo_v + hi_v), m


# fedrec-lint: traced-scope — compiled into the shard_map round-end sync
def robust_aggregate(
    trees: Any,
    weight: jnp.ndarray,
    axis: Any,
    method: str,
    trim_k: int = 1,
    clip_norm: float = 10.0,
) -> Any:
    """Robust round-end aggregation inside ``shard_map``.

    ``trees`` is any pytree of per-client parameter leaves (pass BOTH
    towers as one tuple so the ``clip`` method's global norm spans the
    whole client update); ``weight`` is this client's scalar round weight
    (0 = dropped out / quarantined). Every client — including
    non-participants — adopts the aggregate, mirroring
    :func:`fedrec_tpu.fed.strategies.weighted_param_avg`; a round where no
    client reports keeps local parameters.
    """
    validate_robust_method(method)
    if method == "mean":
        from fedrec_tpu.fed.strategies import weighted_param_avg

        return weighted_param_avg(trees, weight, axis)

    gw = _gather_cohort(weight, axis)  # (n,)
    wmask = (gw > 0).astype(jnp.float32)
    gathered = jax.tree_util.tree_map(lambda p: _gather_cohort(p, axis), trees)
    any_participant = jnp.sum(wmask) > 0

    if method in ("trimmed_mean", "median"):

        def agg_leaf(local, g):
            if method == "trimmed_mean":
                agg, m = _trimmed_mean_leaf(g, wmask, trim_k)
            else:
                agg, m = _median_leaf(g, wmask)
            # per-coordinate m==0 (every contribution non-finite) and the
            # zero-participation round both keep the local value
            return jnp.where(any_participant & (m > 0), agg.astype(local.dtype),
                             local)

        return jax.tree_util.tree_map(agg_leaf, trees, gathered)

    # ---- method == "clip": centered (at the cohort median) clipped mean.
    centers = jax.tree_util.tree_map(
        lambda g: _median_leaf(g, wmask)[0], gathered
    )
    # per-client squared deviation from the center, global over ALL leaves
    n = gw.shape[0]
    sq = jnp.zeros((n,), jnp.float32)
    for g, c in zip(
        jax.tree_util.tree_leaves(gathered), jax.tree_util.tree_leaves(centers)
    ):
        d = g.astype(jnp.float32) - c.astype(jnp.float32)[None]
        # non-finite deviations poison the norm ON PURPOSE: the client's
        # whole contribution then clips to zero below
        sq = sq + jnp.sum(d.reshape(n, -1) ** 2, axis=1)
    norm = jnp.sqrt(sq)
    scale = jnp.where(
        jnp.isfinite(norm),
        jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-12)),
        0.0,
    )
    total = jnp.sum(gw * wmask)
    coeff = gw * wmask * scale  # (n,)

    def clip_leaf(local, g, c):
        d = g - c[None]
        safe_d = jnp.where(jnp.isfinite(d), d, 0.0)
        numer = jnp.tensordot(coeff.astype(g.dtype), safe_d, axes=(0, 0))
        agg = c + numer / jnp.maximum(total, 1e-12).astype(g.dtype)
        return jnp.where(any_participant, agg, local)

    return jax.tree_util.tree_map(clip_leaf, trees, gathered, centers)


# ----------------------------------------------------------------- numpy
def robust_reduce_np(
    stacked: np.ndarray,
    weights: np.ndarray,
    method: str,
    trim_k: int = 1,
    clip_norm: float = 10.0,
    sq_norms: np.ndarray | None = None,
    fallback: np.ndarray | None = None,
) -> np.ndarray:
    """Numpy robust reduction over a (P, ...) stack of per-process
    contributions — the coordinator deployment's cross-host counterpart of
    :func:`robust_aggregate`, applied to the arrays
    ``multihost_utils.process_allgather`` already materializes.

    Semantics match the in-graph version per leaf: participation =
    ``weights > 0``, non-finite cells excluded, trimming/median per
    coordinate — including the m==0 coordinate (every contribution
    non-finite), which keeps the ``fallback`` value (the caller's local
    params, mirroring the in-graph ``m > 0`` guard; 0.0 when no fallback
    is given). ``clip`` needs the per-process GLOBAL deviation norm
    across every leaf — pass the summed squared deviations via
    ``sq_norms`` (see :func:`robust_reduce_tree_np`), else the leaf is
    clipped by its own norm.
    """
    validate_robust_method(method)
    w = np.asarray(weights, np.float64)
    x = np.asarray(stacked, np.float64)
    wmask = (w > 0).reshape((-1,) + (1,) * (x.ndim - 1))
    if method == "mean":
        total = float(np.sum(w))
        if total == 0:
            raise ValueError("mean reduction needs >= 1 participant")
        contrib = np.where(wmask > 0, x, 0.0)
        return np.einsum("p,p...->...", w, contrib) / total

    finite = np.isfinite(x) & (wmask > 0)
    vals = np.where(finite, x, np.inf)
    srt = np.sort(vals, axis=0)
    m = finite.sum(axis=0)
    pos = np.arange(x.shape[0]).reshape((-1,) + (1,) * (x.ndim - 1))
    fb = 0.0 if fallback is None else np.asarray(fallback, np.float64)
    if method == "trimmed_mean":
        k = np.minimum(trim_k, (m - 1) // 2)
        keep = (pos >= k) & (pos < m - k)
        denom = np.maximum(m - 2 * k, 1)
        out = np.where(keep, np.where(np.isfinite(srt), srt, 0.0), 0.0).sum(0)
        return np.where(m > 0, out / denom, fb)
    if method == "median":
        lo, hi = (m - 1) // 2, m // 2
        safe = np.where(np.isfinite(srt), srt, 0.0)
        lo_v = np.where(pos == lo, safe, 0.0).sum(0)
        hi_v = np.where(pos == hi, safe, 0.0).sum(0)
        return np.where(m > 0, 0.5 * (lo_v + hi_v), fb)

    # clip
    lo, hi = (m - 1) // 2, m // 2
    safe = np.where(np.isfinite(srt), srt, 0.0)
    center = 0.5 * (
        np.where(pos == lo, safe, 0.0).sum(0) + np.where(pos == hi, safe, 0.0).sum(0)
    )
    d = x - center[None]
    if sq_norms is None:
        d_flat = d.reshape(x.shape[0], -1)
        finite_rows = np.isfinite(d_flat).all(axis=1)
        sq_norms = np.where(
            finite_rows,
            (np.where(np.isfinite(d_flat), d_flat, 0.0) ** 2).sum(axis=1),
            np.inf,
        )
    norm = np.sqrt(sq_norms)
    scale = np.where(
        np.isfinite(norm), np.minimum(1.0, clip_norm / np.maximum(norm, 1e-12)), 0.0
    )
    coeff = w * (w > 0) * scale
    total = float(np.sum(w * (w > 0)))
    if total == 0:
        raise ValueError("clip reduction needs >= 1 participant")
    safe_d = np.where(np.isfinite(d), d, 0.0)
    return center + np.einsum("p,p...->...", coeff, safe_d) / total


def robust_reduce_tree_np(
    gathered_tree: Any,
    weights: np.ndarray,
    method: str,
    trim_k: int = 1,
    clip_norm: float = 10.0,
    fallback_tree: Any = None,
) -> Any:
    """Tree-wide numpy robust reduction: every leaf is a (P, ...) stack.
    For ``clip`` the per-process deviation norm is computed globally over
    all leaves first (matching the in-graph method), then each leaf is
    reduced with the shared scales. ``fallback_tree`` (the caller's LOCAL
    params, unstacked) supplies the kept value for coordinates where every
    contribution is non-finite — the in-graph ``m > 0`` guard."""
    validate_robust_method(method)
    leaves, treedef = jax.tree_util.tree_flatten(gathered_tree)
    leaves = [np.asarray(leaf, np.float64) for leaf in leaves]
    fb_leaves: list = [None] * len(leaves)
    if fallback_tree is not None:
        fb_leaves = jax.tree_util.tree_flatten(fallback_tree)[0]
    if method != "clip":
        out = [
            robust_reduce_np(leaf, weights, method, trim_k=trim_k, fallback=fb)
            for leaf, fb in zip(leaves, fb_leaves)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)
    # shared per-process squared deviation norm across all leaves
    n = leaves[0].shape[0]
    sq = np.zeros((n,), np.float64)
    for leaf in leaves:
        w = np.asarray(weights, np.float64)
        x = leaf
        wmask = (w > 0).reshape((-1,) + (1,) * (x.ndim - 1))
        finite = np.isfinite(x) & (wmask > 0)
        vals = np.where(finite, x, np.inf)
        srt = np.sort(vals, axis=0)
        m = finite.sum(axis=0)
        pos = np.arange(n).reshape((-1,) + (1,) * (x.ndim - 1))
        lo, hi = (m - 1) // 2, m // 2
        safe = np.where(np.isfinite(srt), srt, 0.0)
        center = 0.5 * (
            np.where(pos == lo, safe, 0.0).sum(0)
            + np.where(pos == hi, safe, 0.0).sum(0)
        )
        d = (x - center[None]).reshape(n, -1)
        finite_rows = np.isfinite(d).all(axis=1)
        sq_leaf = np.where(np.isfinite(d), d, 0.0) ** 2
        sq = sq + np.where(finite_rows, sq_leaf.sum(axis=1), np.inf)
    out = [
        robust_reduce_np(
            leaf, weights, "clip", clip_norm=clip_norm, sq_norms=sq
        )
        for leaf in leaves
    ]
    return jax.tree_util.tree_unflatten(treedef, out)
