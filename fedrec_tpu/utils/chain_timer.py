"""Differenced-chain timer used by ``bench.py`` and ``benchmarks/pallas_bench.py``.

On an attached chip the timing protocol is the plain one: the host clock
around work that ends in ``block_until_ready`` (or a host readback), after
a warm-up that compiled every shape. Differencing a 2x-length against a
1x-length chain is kept for ``bench.py`` until roadmap S0 replaces its
timing: it cancels whatever fixed per-chain cost (dispatch, readback) a
caller's ``chain`` closure pays, so short steps are not charged for it.

  * every chain must end in a synchronization the caller's ``chain``
    closure owns (a host readback or ``block_until_ready``);
  * the per-op time is the DIFFERENCE of a 2x-length and a 1x-length
    chain, which cancels the constant;
  * the differenced signal must dwarf timer jitter, not merely be
    positive: chains grow until ``iters * t_op >= target``;
  * a non-positive delta (jitter or warm-up residue in the 1x chain) must
    DOUBLE the chain, not jump via ``target/per_op``, which explodes
    straight to the iteration cap.
"""

from __future__ import annotations

from typing import Callable


def differenced_chain_seconds(
    chain: Callable[[int], float],
    iters: int,
    *,
    target: float = 0.3,
    cap: int = 2000,
    attempts: int = 4,
    accept_positive_at_cap: bool = False,
    label: str = "chain",
    trace: Callable[[str], None] | None = None,
) -> float:
    """Per-iteration seconds from differenced 1x/2x chains.

    ``chain(k)`` runs k synchronized iterations and returns wall seconds
    (including any fixed per-chain constant — it cancels). The caller
    warms up (compile + steady state) BEFORE calling this.

    ``accept_positive_at_cap``: accept any positive delta at the
    iteration cap OR on attempt exhaustion, raising only for a
    non-positive delta (pallas_bench's historical policy — op chains hit
    the cap on fast ops where the capped delta is still meaningful, and a
    jittery window's last positive reading beats a nulled evidence row);
    ``bench.py`` keeps the stricter raise-below-target policy for step
    chains. These two knobs are the ONLY policy difference between the
    call sites.
    """
    t1 = t2 = delta = float("nan")
    measured = iters
    for _ in range(attempts):
        measured = iters
        t1 = chain(measured)
        t2 = chain(2 * measured)
        delta = t2 - t1
        if trace is not None:
            trace(
                f"t1={t1:.2f} t2={t2:.2f} delta={delta:.2f} iters={measured}"
            )
        if delta >= target:
            return delta / measured
        if accept_positive_at_cap and measured >= cap:
            break
        if delta <= 0:
            # nonsense sign: jitter or warm-up residue landed in the 1x
            # chain — double and re-measure (see module docstring)
            iters = min(cap, 2 * measured)
            continue
        per_op = delta / measured
        iters = int(min(cap, max(2 * measured, target / per_op)))
    if accept_positive_at_cap and delta > 0:
        return delta / measured
    raise RuntimeError(
        f"differenced {label} time never cleared the jitter floor "
        f"(last t1={t1:.4f}, t2={t2:.4f}, iters={measured}); timings too "
        "jittery — rerun"
    )
