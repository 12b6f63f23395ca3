"""The comparison that decides ``correct``: what the timed path produced in
its first steps against the plain reference, each number beside its limit.

The numbers (all gaps, 0 = equal):

  loss_gap    worst |program - reference| / |reference| over the first
              steps' losses of every client
  grad_gap    the first gradient as the optimizer got it (Adam's first
              moment after one step / (1 - b1)): per leaf and client, the gap
              between the program's norm and the reference's norm, over the
              larger of the reference's norm of that leaf and of the median
              leaf; worst leaf
  delta_gap   the same measure on the parameters' change after the followed
              steps; leaves whose reference gradient is under a thousandth
              of the median leaf's are left out (a key's bias under softmax
              moves under Adam by round-off alone). Two readings of it:
              ``delta_gap`` the worst leaf and ``delta_gap_median`` the
              median over the counted leaves, each the worst client.
              ``PERF.md`` gives both readings and says which limit holds
              which.
  sync_gap    cells whose configuration averages parameters at the end of a
              round: worst leaf of |after - mean over clients of before|
              (max-norm over the mean's max-norm), and 1.0 where the
              clients are not identical after the sync

A number that is not finite fails its limit. Limits live in
``chipbench/limits/<workload>.json``.
"""

from __future__ import annotations

import math

import numpy as np

ADAM_B1 = 0.9
TINY_GRAD_SHARE = 1e-3


def _leaves(tree, prefix=()):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], prefix + (k,))
    else:
        yield "/".join(prefix), np.asarray(tree, np.float64)


def leaf_norms(tree) -> dict[str, float]:
    return {name: float(np.linalg.norm(x)) for name, x in _leaves(tree)}


def norm_gaps(prog: dict[str, float], ref: dict[str, float], skip=()) -> dict[str, float]:
    """Per leaf: |‖prog‖ - ‖ref‖| / max(‖ref‖ of the leaf, of the median leaf)."""
    med = float(np.median(list(ref.values())))
    return {
        name: abs(prog[name] - r) / max(r, med, 1e-300)
        for name, r in ref.items() if name not in skip
    }


def tiny_grad_leaves(ref_grad_norms: dict[str, float]) -> set[str]:
    med = float(np.median(list(ref_grad_norms.values())))
    return {n for n, v in ref_grad_norms.items() if v < TINY_GRAD_SHARE * med}


def _worse(a: float, b: float) -> float:
    """The larger of the two, and not-a-number if either is."""
    return b if not b <= a else a


def compare_steps(program: dict, reference: dict) -> dict:
    """``program``: ``losses`` (steps, K), ``first_mu`` and ``deltas`` as lists
    (one per client) of {"user": tree, "news": tree}. ``reference``: the
    output of ``reference.follow_steps``. Returns the numbers and, for
    ``PERF.md``, the leaf that set each."""
    lp = np.asarray(program["losses"], np.float64)
    lr = np.asarray(reference["losses"], np.float64)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr))) if lp.shape == lr.shape else math.inf
    grad_gap, delta_gap, delta_median = 0.0, 0.0, 0.0
    worst = {"grad_gap": None, "delta_gap": None}
    skipped: set[str] = set()
    tables = []
    for c, (mu, d_prog, g_ref, d_ref) in enumerate(zip(
        program["first_mu"], program["deltas"],
        reference["first_grads"], reference["deltas"],
    )):
        g_ref_n = leaf_norms(g_ref)
        g_prog_n = {k: v / (1.0 - ADAM_B1) for k, v in leaf_norms(mu).items()}
        for name, gap in norm_gaps(g_prog_n, g_ref_n).items():
            if not gap <= grad_gap:
                grad_gap, worst["grad_gap"] = gap, f"client{c}:{name}"
        skip = tiny_grad_leaves(g_ref_n)
        skipped |= skip
        d_prog_n, d_ref_n = leaf_norms(d_prog), leaf_norms(d_ref)
        gaps = norm_gaps(d_prog_n, d_ref_n, skip)
        for name, gap in gaps.items():
            if not gap <= delta_gap:
                delta_gap, worst["delta_gap"] = gap, f"client{c}:{name}"
        delta_median = _worse(delta_median, float(np.median(list(gaps.values()))))
        tables.append({name: (g_ref_n[name], g_prog_n[name], d_ref_n[name], d_prog_n[name])
                       for name in g_ref_n})
    return {
        "numbers": {"loss_gap": loss_gap, "grad_gap": float(grad_gap), "delta_gap": float(delta_gap),
                    "delta_gap_median": delta_median},
        "worst_leaf": worst,
        "left_out_of_delta": sorted(skipped),
        "per_leaf": tables,
    }


def leaf_table(compared: dict) -> str:
    """The leaves of the client that set ``delta_gap``: norms of the first
    gradient and of the parameters' change, reference then program."""
    where = compared["worst_leaf"]["delta_gap"] or compared["worst_leaf"]["grad_gap"]
    if where is None:
        return "no leaf differs"
    c = int(where.split(":")[0].removeprefix("client"))
    rows = [f"client {c}: leaf | grad ref | grad program | change ref | change program"]
    for name, v in compared["per_leaf"][c].items():
        mark = " (left out of delta_gap)" if name in compared["left_out_of_delta"] else ""
        rows.append(f"  {name} | {v[0]:.4e} | {v[1]:.4e} | {v[2]:.4e} | {v[3]:.4e}{mark}")
    return "\n".join(rows)


def sync_gap(before: list, after: list) -> float:
    """``before`` / ``after``: per client {"user": tree, "news": tree} around
    the round-end sync, as the program's sync got and returned them."""
    worst = 0.0
    names = [n for n, _ in _leaves(before[0])]
    b = [dict(_leaves(t)) for t in before]
    a = [dict(_leaves(t)) for t in after]
    for name in names:
        mean = np.mean([t[name] for t in b], axis=0)
        scale = max(float(np.max(np.abs(mean))), 1e-300)
        for t in a:
            if not np.array_equal(t[name], a[0][name]):
                return 1.0
            worst = max(worst, float(np.max(np.abs(t[name] - mean))) / scale)
    return worst


def verdict(numbers: dict[str, float], limits: dict[str, float]) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}); every limit must have its number."""
    compared = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        fine = value is not None and math.isfinite(value) and value <= limit
        ok = ok and fine
        compared[name] = {"value": value, "limit": limit}
    return ok, compared
