"""Host/commit provenance stamp for benchmark artifacts.

Every ``benchmarks/*.json`` must self-describe where and when it was
measured (VERDICT r2 item 8: an artifact claiming 8 threads on a 1-core rig
was unexplainable because nothing recorded the host). Merge
``{"provenance": provenance()}`` into the payload at write time.
"""

from __future__ import annotations

import json
import os
import platform as _platform
import subprocess
import time
from pathlib import Path


def git_head(repo: Path | None = None) -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=repo or Path(__file__).resolve().parents[2],
        ).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001
        return "unknown"


def git_dirty(repo: Path | None = None) -> bool | None:
    """True when TRACKED files have uncommitted changes, None if unknown.

    Untracked scratch files deliberately don't count: the caller's question
    is "does the checkout still match the stamped commit's code", and a
    stray notes file answers nothing about that.
    """
    paths = git_dirty_paths(repo)
    return None if paths is None else bool(paths)


def git_dirty_paths(repo: Path | None = None) -> list[str] | None:
    """Tracked files with uncommitted changes at stamp time, None if unknown.

    Recorded so a later reader can decide whether measure-time dirtiness
    could have affected the measurement (e.g. a benchmark writing its own
    artifact dirties the tree harmlessly; an edited ``fedrec_tpu/`` module
    does not). ``-z`` (NUL-separated) because git C-quotes spaces and
    non-ASCII in line-oriented output, which would defeat any prefix match
    a consumer runs on these paths.
    """
    try:
        out = subprocess.run(
            # --no-renames: rename detection would print only the
            # destination, hiding a source moved out of a watched prefix
            ["git", "diff", "--name-only", "--no-renames", "-z", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=repo or Path(__file__).resolve().parents[2],
        )
        if out.returncode != 0:
            return None
        return sorted(p for p in out.stdout.split("\0") if p)
    except Exception:  # noqa: BLE001
        return None


def write_artifact(path: Path, payload: dict, partial: bool) -> None:
    """Atomic benchmark-artifact write with incremental-run staging.

    Benchmark harnesses stamp after every measured row so a run killed
    mid-way keeps completed rows as labeled evidence. Three disciplines
    keep that kill-safe AND clobber-safe:

      * ``partial=True`` stamps go to a ``<stem>.inprogress.json`` sidecar
        — the canonical artifact is replaced only by a COMPLETED run, so a
        killed re-run can never destroy previously banked complete
        evidence;
      * the ``"partial"`` flag is serialized FIRST (a torn tail can then
        never drop the flag while keeping the provenance block);
      * every write goes through a temp file + ``os.replace`` so no reader
        ever sees a half-written JSON.

    A completing write removes the sidecar.
    """
    sidecar = path.with_name(path.name[: -len(".json")] + ".inprogress.json"
                             if path.name.endswith(".json")
                             else path.name + ".inprogress")
    target = sidecar if partial else path
    # strip any incoming "partial" key first: a replayed payload (e.g. a
    # harness re-stamping a previously banked dict) could otherwise carry
    # partial=False into the spread and silently mark a sidecar complete —
    # the flag belongs to THIS write's `partial` argument alone
    payload = {k: v for k, v in payload.items() if k != "partial"}
    out = {"partial": True, **payload} if partial else payload
    tmp = target.with_name(target.name + ".tmp")
    tmp.write_text(json.dumps(out, indent=2))
    os.replace(tmp, target)
    if not partial:
        sidecar.unlink(missing_ok=True)


def runtime_versions() -> dict:
    """Installed jax/jaxlib versions via package metadata, read without
    importing jax (stamping must work in a process that stays off the
    device, e.g. a supervisor)."""
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib"):
        try:
            out[pkg] = metadata.version(pkg)
        except Exception:  # noqa: BLE001 — absent package stays absent
            pass
    return out


def provenance(**extra) -> dict:
    """Stamp: commit, wall time, machine, CPU count, installed jax/jaxlib
    versions, and the JAX backend actually in use (when JAX is already
    imported — never imports it)."""
    stamp = {
        "commit": git_head(),
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "hostname": _platform.node(),
        "machine": _platform.machine(),
        "nproc": os.cpu_count(),
        "dirty_paths": git_dirty_paths(),
        "runtime_versions": runtime_versions(),
    }
    import sys

    jax = sys.modules.get("jax")
    if jax is not None:
        stamp["jax_backend"] = jax.default_backend()
        stamp["jax_device"] = jax.devices()[0].device_kind
    stamp.update(extra)
    return stamp
