"""Evidence-driven ``model.attn_impl="auto"`` resolution.

``config.py`` used to hard-code the never-pallas comment ("dense XLA wins
at every size that fits") — true when written, but a policy frozen at one
measurement. This module reads the microbenchmark evidence
``benchmarks/pallas_bench.py`` writes (``benchmarks/pallas_bench.json``;
none is in the tree until that harness runs on a chip) and picks the MEASURED winner for the
model's (H, dtype) regime instead, falling back to the static defaults
whenever no applicable clean evidence exists.

Evidence is applicable only when ALL of:

  * a TPU backend is live (chip measurements say nothing about the CPU
    interpret path, where tier-1 runs — off-TPU this always returns None,
    so test behavior is deterministic);
  * the artifact is complete (no ``"partial"`` flag) and its provenance
    stamps the SAME installed jax version that is resolving now — a
    runtime bump invalidates kernel timings;
  * a row of the training-relevant op ("attention fwd+bwd") exists within
    2x of the model's history length, measured at the model's dtype (rows
    without a dtype tag are float32 — the pre-ISSUE-8 artifact schema).

The winner is the smallest non-null timing among {xla_ms -> "dense",
pallas_ms -> "pallas", chunked_ms -> "chunked"} on the nearest-H row
(log-space distance). Results are cached per (path, mtime, H, dtype,
backend) so the file is read once per process, not once per trace.
"""

from __future__ import annotations

import functools
import json
import math
from pathlib import Path

_DEFAULT_PATH = (
    Path(__file__).resolve().parents[2] / "benchmarks" / "pallas_bench.json"
)
_COLS = {"xla_ms": "dense", "pallas_ms": "pallas", "chunked_ms": "chunked"}


def _current_jax_version() -> str | None:
    from importlib import metadata

    try:
        return metadata.version("jax")
    except Exception:  # noqa: BLE001
        return None


@functools.lru_cache(maxsize=64)
def _resolve(path_str: str, mtime_ns: int, seq_len: int, dtype: str,
             backend: str) -> str | None:
    if backend != "tpu":
        return None
    try:
        artifact = json.loads(Path(path_str).read_text())
    except Exception:  # noqa: BLE001 — absent/corrupt artifact = no evidence
        return None
    if artifact.get("partial"):
        return None
    stamped = (
        (artifact.get("provenance") or {}).get("runtime_versions") or {}
    ).get("jax")
    if stamped is None or stamped != _current_jax_version():
        # unknowable or stale runtime: timings describe another jax
        return None
    best_row, best_dist = None, None
    for row in artifact.get("rows") or []:
        if row.get("op") != "attention fwd+bwd":
            continue
        if row.get("dtype", "float32") != dtype:
            continue
        h = row.get("H")
        if not h or not any(row.get(c) is not None for c in _COLS):
            continue
        dist = abs(math.log(h / seq_len))
        if best_dist is None or dist < best_dist:
            best_row, best_dist = row, dist
    if best_row is None or best_dist > math.log(2.0):
        return None  # no row within 2x of this regime
    timed = {
        impl: best_row[col]
        for col, impl in _COLS.items()
        if best_row.get(col) is not None
    }
    winner = min(timed, key=timed.get)
    if winner == "dense" and best_row["H"] < seq_len:
        # a dense win does NOT extrapolate upward: the score tensor is
        # O(L^2) and a row that fit at H says nothing about memory
        # feasibility at 2x H (the regime the chunk_threshold guard
        # exists for). O(L) winners (pallas/chunked) extrapolate fine;
        # dense evidence applies at its own H and below only.
        return None
    return winner


def measured_attn_impl(
    seq_len: int,
    dtype,
    path: Path | str | None = None,
    backend: str | None = None,
) -> str | None:
    """The measured attention winner for this (H, dtype) regime, or None
    when no provenance-clean evidence applies (caller falls back to the
    static defaults). ``backend``/``path`` are injectable for tests."""
    import jax
    import jax.numpy as jnp

    p = Path(path) if path is not None else _DEFAULT_PATH
    try:
        mtime = p.stat().st_mtime_ns
    except OSError:
        return None
    if backend is None:
        backend = jax.default_backend()
    return _resolve(
        str(p), mtime, int(seq_len), jnp.dtype(dtype).name, backend
    )
