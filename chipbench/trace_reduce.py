"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read. Nothing but JAX reads the file
(``jax.profiler.ProfileData``).

What a TPU trace holds (seen on a v5e, jax 0.9): one plane per chip named
``/device:TPU:<n>`` whose line ``XLA Modules`` has one event per execution of
a compiled program (named ``<module>(<fingerprint>)``) and whose line
``XLA Ops`` has one event per executed HLO op; a plane ``/host:CPU`` whose
lines are host threads, where ``jax.profiler.TraceAnnotation`` events land.
All times are nanoseconds from the start of the trace.

The harness brackets the traced window with two annotations
(``chipbench_window_begin`` / ``chipbench_window_end``) that carry the host's
``perf_counter_ns`` as a stat: they bound the window on the trace's own
clock and give the one offset that puts the program's host spans (recorded
on ``perf_counter``) on the same timeline.

Busy time is the union of the intervals in which an op ran on the device,
clipped to the window; idle is the window less busy. Gaps are the idle
intervals, split over what the host was doing in them.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"
MARK_BEGIN, MARK_END = "chipbench_window_begin", "chipbench_window_end"
_FINGERPRINT = re.compile(r"\(\d+\)$")
_OP_TEXT = re.compile(r"^%?(?P<name>[\w.\-]+) = \(?(?P<shape>[a-z0-9]+\[[\d,]*\])[^ ]* ?.*? (?P<opcode>[a-z][\w\-]*)\(")


def find_xplane(logdir: str | Path) -> Path:
    files = sorted(Path(logdir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def module_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name)


def short_op(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction; keep its
    name, opcode and (first) output shape: ``copy.363 copy bf16[65536,50,768]``."""
    m = _OP_TEXT.match(event_name)
    return f"{m['name']} {m['opcode']} {m['shape']}" if m else event_name[:80]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def total(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def gaps_of(busy, lo: float, hi: float):
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def read_trace(path: str | Path) -> dict:
    """Raw events of a trace: ``marks`` {name: (start_ns, perf_counter_ns)}
    and ``devices`` {ordinal: {"modules": [(name, start, dur)], "ops": [...]}}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    marks: dict[str, tuple[float, int]] = {}
    devices: dict[int, dict] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {"modules": [], "ops": []})
            for line in plane.lines:
                key = {MODULE_LINE: "modules", OP_LINE: "ops"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    dev[key].append((e.name, float(e.start_ns), float(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in (MARK_BEGIN, MARK_END):
                        stats = dict(e.stats)
                        marks[e.name] = (float(e.start_ns), int(stats["t_ns"]))
    return {"marks": marks, "devices": devices}


def reduce_trace(raw: dict, host_spans: list[dict] | None = None) -> dict:
    """The reduction. ``host_spans``: spans of the program's tracer inside
    the window as {"name", "start_ns", "end_ns"} on ``perf_counter_ns``.

    Returns ``window_s``, ``busy_s`` (mean over chips) and ``busy_s_per_chip``,
    ``modules`` {name: {"count", "seconds"}} and ``ops`` {name: seconds}
    (means over chips, inside the window), and ``idle_by_host_activity``
    {activity: seconds} of the idlest chip."""
    marks = raw["marks"]
    if MARK_BEGIN not in marks or MARK_END not in marks:
        raise ValueError("the trace lacks the harness's window annotations")
    lo, hi = marks[MARK_BEGIN][0], marks[MARK_END][0]
    offset = lo - marks[MARK_BEGIN][1]     # trace_ns = perf_counter_ns + offset
    if not raw["devices"]:
        raise ValueError("the trace has no /device:TPU:<n> plane")
    n_dev = len(raw["devices"])
    modules: dict[str, dict] = {}
    ops: dict[str, float] = {}
    busy_per_chip: dict[int, float] = {}
    gaps_per_chip: dict[int, list] = {}
    for ordinal, dev in sorted(raw["devices"].items()):
        spans = [(s, s + d) for _, s, d in (dev["ops"] or dev["modules"])]
        busy = union(clip(spans, lo, hi))
        busy_per_chip[ordinal] = total(busy) / 1e9
        gaps_per_chip[ordinal] = gaps_of(busy, lo, hi)
        for name, s, d in dev["modules"]:
            if s >= lo and s + d <= hi:
                m = modules.setdefault(module_name(name), {"count": 0.0, "seconds": 0.0})
                m["count"] += 1.0 / n_dev
                m["seconds"] += d / 1e9 / n_dev
        for name, s, d in dev["ops"]:
            if s >= lo and s + d <= hi:
                key = short_op(name)
                ops[key] = ops.get(key, 0.0) + d / 1e9 / n_dev
    idlest = min(busy_per_chip, key=busy_per_chip.get)
    activity = attribute_gaps(
        gaps_per_chip[idlest],
        [(sp["name"], sp["start_ns"] + offset, sp["end_ns"] + offset) for sp in host_spans or []],
    )
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_per_chip.values()) / n_dev,
        "busy_s_per_chip": busy_per_chip,
        "idlest_chip": idlest,
        "modules": modules,
        "ops": ops,
        "idle_by_host_activity": activity,
    }


# host spans by how specific they are: a gap inside a ``dispatch`` span that
# is itself inside ``fed_round`` belongs to ``dispatch``
_SPECIFIC = ("batch_build", "h2d", "dispatch", "aggregate")


def attribute_gaps(gaps, spans) -> dict[str, float]:
    """Seconds of device idleness by what the host was doing: inside one of
    the round loop's spans, elsewhere inside a round, or between rounds."""
    out: dict[str, float] = {}
    specific = [(a, b, n) for n, a, b in spans if n in _SPECIFIC]
    rounds = union([(a, b) for n, a, b in spans if n == "fed_round"])
    for g0, g1 in gaps:
        covered = []
        for a, b, n in specific:
            lo, hi = max(a, g0), min(b, g1)
            if hi > lo:
                out[n] = out.get(n, 0.0) + (hi - lo) / 1e9
                covered.append((lo, hi))
        rest = gaps_of(union(covered), g0, g1)
        for r0, r1 in rest:
            inside = total(clip(rounds, r0, r1))
            if inside:
                out["round_other"] = out.get("round_other", 0.0) + inside / 1e9
            if (r1 - r0) - inside > 0:
                out["between_rounds"] = out.get("between_rounds", 0.0) + ((r1 - r0) - inside) / 1e9
    return out


def top(items: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(items.items(), key=lambda kv: -kv[1])[:n]]
