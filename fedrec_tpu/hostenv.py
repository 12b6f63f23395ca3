"""Environment for a subprocess that must run JAX on the CPU.

Fake-mesh tests, dry runs and the CPU legs of the benchmark harnesses start
child interpreters with the platform pinned to ``cpu`` and, where they need
a mesh, a forced host device count. This module is the one place that
builds that environment; it imports nothing but the stdlib, so entry points
that must not touch jax before they spawn (``__graft_entry__``, the
coordinator's supervisor) can use it.
"""

from __future__ import annotations

import os

_DEVCOUNT_FLAG = "--xla_force_host_platform_device_count="


def cpu_host_env(
    n_devices: int | None = None, base: dict | None = None
) -> dict[str, str]:
    """A copy of ``base`` (default ``os.environ``) for a CPU-host jax run:
    platform pinned to cpu, and — when ``n_devices`` is given — exactly one
    fake-device-count flag in ``XLA_FLAGS`` (other inherited flags are
    preserved)."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        kept = [
            t for t in env.get("XLA_FLAGS", "").split()
            if not t.startswith(_DEVCOUNT_FLAG)
        ]
        env["XLA_FLAGS"] = " ".join(kept + [f"{_DEVCOUNT_FLAG}{n_devices}"])
    return env


def fake_device_count(env: dict | None = None) -> int | None:
    """The configured fake-CPU device count, or None when absent/invalid."""
    flags = (os.environ if env is None else env).get("XLA_FLAGS", "")
    if _DEVCOUNT_FLAG not in flags:
        return None
    try:
        return int(flags.split(_DEVCOUNT_FLAG, 1)[1].split()[0])
    except (IndexError, ValueError):
        return None
