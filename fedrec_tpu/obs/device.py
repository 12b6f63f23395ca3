"""Device-layer watchdogs: XLA recompile accounting + HBM gauges.

Two signals the host-side registry/tracer could not see before:

* :class:`CompileWatchdog` — counts XLA backend compiles via
  ``jax.monitoring`` and attributes each to the jitted callable (and the
  argument-shape signature) that was executing when it fired.  A compile
  for a *new* (fn, shapes) signature is warmup; a compile for an
  already-seen signature is a RECOMPILE — the cache-thrash case a
  recompile storm is made of.  Storms (``storm_threshold`` compiles
  within ``storm_window_s``) bump a counter and warn on stderr with the
  shape provenance, because the usual cause — a batch dimension that
  varies per step — is invisible in wall-time metrics until the run is
  10× slower than the bench said.
* :func:`sample_device_memory` — ``device.memory_stats()`` gauges
  (bytes_in_use / peak / limit) sampled at round boundaries and stamped
  into the trace as an instant event inside the current ``fed_round``
  span.  On backends without allocator stats (CPU) it is a no-op.

``jax`` is imported lazily inside functions — the obs package stays
importable (and cheap) on artifact-reading boxes with no JAX.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Sequence

from fedrec_tpu.obs.registry import MetricsRegistry, get_registry

# substring match: the event is '/jax/core/compile/backend_compile_duration'
# on jax 0.4.x; newer jaxlibs rename the suffix but keep the stem
_COMPILE_EVENT_STEM = "backend_compile"

_MEMORY_STAT_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")

_tls = threading.local()
_install_lock = threading.Lock()
_listener_installed = False
_active: "CompileWatchdog | None" = None


def _call_stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def _on_event_duration(name: str, dur: float, **_kw: Any) -> None:
    # events fired by the cost hook's own AOT re-compile are telemetry
    # overhead, not program compiles — without the suppression they would
    # double-count xla.compile_seconds_total (and read as <unwatched>)
    if getattr(_tls, "suppress_compile_events", False):
        return
    wd = _active
    if wd is not None and _COMPILE_EVENT_STEM in name:
        wd._on_compile(float(dur))


def shape_signature(args: tuple, kwargs: dict | None = None) -> str:
    """Compact dtype[shape] signature of a call's array leaves — the
    provenance string a recompile is attributed to."""
    import jax

    parts: list[str] = []
    leaves = jax.tree_util.tree_leaves((args, kwargs or {}))
    for leaf in leaves[:64]:
        shape = getattr(leaf, "shape", None)
        if shape is None:
            parts.append(type(leaf).__name__)
        else:
            dt = str(getattr(leaf, "dtype", "?"))
            parts.append(f"{dt}[{','.join(str(d) for d in shape)}]")
    if len(leaves) > 64:
        parts.append(f"…+{len(leaves) - 64}")
    return " ".join(parts)


class CompileWatchdog:
    """Recompilation accounting with shape provenance.

    ``watch(fn, name)`` wraps a (jitted) callable; while a wrapped call is
    on the stack, any backend compile that fires is attributed to it.
    One module-level ``jax.monitoring`` listener is installed on first
    ``install()`` and dispatches to the ACTIVE watchdog (swap-able, so
    tests get fresh counts without leaking listeners — jax offers no
    per-listener removal).
    """

    def __init__(
        self,
        registry: MetricsRegistry | None = None,
        storm_threshold: int = 5,
        storm_window_s: float = 60.0,
        provenance_capacity: int = 100,
        cost_cb: Callable | None = None,
    ):
        self.registry = registry or get_registry()
        # obs.perf's compile-cost hook: called as cost_cb(fn, args,
        # kwargs, name) after any watched call during which a NEW
        # compilation fired, so the compiled executable's cost_analysis
        # (FLOPs / bytes accessed) can be recorded with fn provenance.
        # None (the default) keeps the pre-perf watch() behavior exactly.
        self.cost_cb = cost_cb
        self.storm_threshold = max(int(storm_threshold), 1)
        self.storm_window_s = float(storm_window_s)
        self._c_compiles = self.registry.counter(
            "xla.compiles_total", "XLA backend compiles, by watched callable",
            labels=("fn",),
        )
        self._c_recompiles = self.registry.counter(
            "xla.recompiles_total",
            "compiles for an already-seen (fn, shapes) signature — cache "
            "thrash, not warmup", labels=("fn",),
        )
        self._c_compile_secs = self.registry.counter(
            "xla.compile_seconds_total", "wall seconds spent in backend compiles"
        )
        self._c_storms = self.registry.counter(
            "xla.recompile_storms_total",
            f"windows with >= threshold compiles in {storm_window_s:g}s",
        )
        self._lock = threading.Lock()
        self._seen: set[tuple[str, str]] = set()
        self._provenance: list[dict] = []  # capacity-trimmed
        self._provenance_capacity = provenance_capacity
        self._recent: list[float] = []  # compile timestamps for storm detection
        self._storm_warned_at = 0.0

    # ---------------------------------------------------------- listener
    def install(self) -> "CompileWatchdog | None":
        """Make this the active watchdog; returns the previous one."""
        global _listener_installed, _active
        with _install_lock:
            if not _listener_installed:
                import jax

                jax.monitoring.register_event_duration_secs_listener(
                    _on_event_duration
                )
                _listener_installed = True
            prev, _active = _active, self
            return prev

    def _on_compile(self, dur_s: float) -> None:
        # one jitted dispatch can fire SEVERAL backend_compile events
        # (helper subcomputations compile separately) — so a "compilation"
        # is counted once per watched CALL, on its first event; later
        # events in the same call only accumulate compile seconds.
        stack = _call_stack()
        frame = stack[-1] if stack else None
        now = time.monotonic()
        new_compilation = frame is not None and not frame["counted"]
        recompile = False
        storm = False
        if frame is not None:
            frame["counted"] = True
        fn = frame["fn"] if frame else "<unwatched>"
        if new_compilation and frame["sig"] is None:
            # lazy: the signature is only materialized when a compile
            # actually fires — compile events run synchronously inside the
            # watched call, so the args are still live and readable
            frame["sig"] = shape_signature(frame["args"], frame["kwargs"])
        with self._lock:
            if new_compilation:
                token = (fn, frame["sig"])
                recompile = token in self._seen
                self._seen.add(token)
                self._provenance.append({
                    "fn": fn, "shapes": frame["sig"], "dur_s": dur_s,
                    "recompile": recompile, "t": now,
                })
                if len(self._provenance) > self._provenance_capacity:
                    del self._provenance[0]
                # storm = many compilations of the SAME callable inside the
                # window (beyond its bucketed-shape warmup); unrelated
                # programs warming up together are not a storm
                self._recent.append((now, fn))
                cutoff = now - self.storm_window_s
                self._recent = [e for e in self._recent if e[0] >= cutoff]
                n_fn = sum(1 for _, f in self._recent if f == fn)
                storm = (
                    n_fn >= self.storm_threshold
                    and now - self._storm_warned_at > self.storm_window_s
                )
                if storm:
                    self._storm_warned_at = now
        if new_compilation:
            self._c_compiles.inc(fn=fn)
            if recompile:
                self._c_recompiles.inc(fn=fn)
        self._c_compile_secs.inc(dur_s)
        if storm:
            self._c_storms.inc()
            import sys

            recent = [
                p for p in self.provenance() if p["fn"] == fn
            ][-self.storm_threshold:]
            shapes = "; ".join(p["shapes"][:80] for p in recent)
            print(
                f"[obs.device] RECOMPILE STORM: {fn} compiled {n_fn} times "
                f"within {self.storm_window_s:g}s — a per-step varying "
                f"shape is defeating the jit cache. Recent shapes: {shapes}",
                file=sys.stderr,
            )

    # -------------------------------------------------------------- watch
    def watch(self, fn: Callable, name: str) -> Callable:
        """Wrap ``fn`` so compiles during its calls carry (name, shapes)
        provenance. Pass-through otherwise (donation, outputs untouched)."""

        def wrapped(*args, **kwargs):
            stack = _call_stack()
            # sig stays None until a compile event actually fires: after
            # warmup no event ever does, so the hot dispatch path pays one
            # dict append instead of a tree walk + string format per call
            frame = {
                "fn": name,
                "sig": None,
                "args": args,
                "kwargs": kwargs,
                "counted": False,
            }
            stack.append(frame)
            try:
                return fn(*args, **kwargs)
            finally:
                stack.pop()
                # compile-cost hook (obs.perf): only after a call that
                # actually compiled — the steady-state dispatch path never
                # reaches it. Guarded: telemetry must never displace the
                # call's own result or exception.
                if frame["counted"] and self.cost_cb is not None:
                    # the hook's lowered.compile() is an AOT compile that
                    # does NOT share the jit dispatch cache — its own
                    # backend_compile events must not count as program
                    # compiles (suppressed above)
                    _tls.suppress_compile_events = True
                    try:
                        self.cost_cb(fn, args, kwargs, name)
                    except Exception:  # noqa: BLE001
                        pass
                    finally:
                        _tls.suppress_compile_events = False

        wrapped.__name__ = f"watched_{name}"
        wrapped.__wrapped__ = fn  # the jitted program: .lower(), ._cache_size()
        return wrapped

    # ------------------------------------------------------------ inspect
    def compiles(self, fn: str) -> int:
        return int(self._c_compiles.value(fn=fn))

    def recompiles(self, fn: str) -> int:
        return int(self._c_recompiles.value(fn=fn))

    def provenance(self) -> list[dict]:
        with self._lock:
            return list(self._provenance)


def set_active_watchdog(wd: "CompileWatchdog | None") -> "CompileWatchdog | None":
    """Swap the active watchdog without installing (tests); returns prev."""
    global _active
    with _install_lock:
        prev, _active = _active, wd
        return prev


# ------------------------------------------------------------------ memory
def sample_device_memory(
    registry: MetricsRegistry | None = None,
    tracer: Any = None,
    devices: Sequence[Any] | None = None,
    **annotations: Any,
) -> int:
    """Sample per-device allocator stats into gauges (+ one trace instant
    per device, so the sample lands inside the current ``fed_round`` span).
    Returns how many devices reported stats (0 on CPU — a clean no-op)."""
    registry = registry or get_registry()
    if devices is None:
        import jax

        devices = jax.local_devices()
    sampled = 0
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # backend without allocator stats
            stats = None
        if not stats:
            continue
        sampled += 1
        dev = str(getattr(d, "id", sampled - 1))
        ev: dict[str, Any] = {"device": dev, **annotations}
        for key in _MEMORY_STAT_KEYS:
            if key in stats:
                registry.gauge(
                    f"device.memory.{key}",
                    "device allocator stats sampled at round boundaries",
                    labels=("device",),
                ).set(float(stats[key]), device=dev)
                ev[key] = int(stats[key])
        if tracer is not None:
            tracer.instant("hbm", **ev)
    return sampled
