"""Device time by named scope, from a trace's op metadata.

``trace_reduce.py`` reads a trace through ``jax.profiler.ProfileData``, which
shows an op's name, start and duration but not its metadata. The metadata of
every executed HLO op holds ``tf_op``: the op's JAX name, the path of
``jax.named_scope``s it was traced under, e.g.
``jit(sharded_step)/jit(main)/transpose(jvp(TextEncoder))/trunk/layer_1/moe_experts/ragged_dot_general``.
This file reads the ``.xplane.pb`` itself (protobuf wire format, the few
fields it needs; nothing but the standard library) and sums, inside the
harness's window, each op's device time under the INNERMOST of the scopes
asked for: the one whose name comes last in the path, also inside
``jvp(...)``, ``transpose(...)``, a rematerialised forward or a fusion
named after its root. Ops under none of them go to ``""``; a kernel that
keeps no path is told by its HLO name (``KERNEL_SCOPES``).

A program that names no such scope (one from before the scopes were added)
reduces to ``{"": seconds}``: readers of a scope's metric then find nothing.
"""

from __future__ import annotations

import re
from pathlib import Path

from chipbench import trace_reduce


# --------------------------------------------------------- protobuf, by hand
def _varint(buf: bytes, at: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[at]
        at += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, at
        shift += 7


def _fields(buf: bytes):
    """(field number, wire type, value) of one message; value is an int for
    varints and fixed widths, bytes for length-delimited fields."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, at = _varint(buf, at)
        elif wire == 1:
            val, at = int.from_bytes(buf[at:at + 8], "little"), at + 8
        elif wire == 2:
            n, at = _varint(buf, at)
            val, at = buf[at:at + n], at + n
        elif wire == 5:
            val, at = int.from_bytes(buf[at:at + 4], "little"), at + 4
        else:
            raise ValueError(f"wire type {wire} in a trace")
        yield num, wire, val


def _map_entry(buf: bytes) -> tuple[int, bytes]:
    key, val = 0, b""
    for num, _, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


# XLA:TPU's own grouped-matmul kernel (what ``jax.lax.ragged_dot`` compiles
# to) keeps nothing of its op's path: its ``tf_op`` reads ``ragged-dot-none:``.
# An op without a path whose HLO name begins with one of these is counted
# under the scope beside it.
KERNEL_SCOPES = {"%ragged-dot": "moe_experts"}


def read_device_ops(path: str | Path, kernels: dict[str, str] | None = None
                    ) -> dict[int, list[tuple[str, float, float]]]:
    """{chip ordinal: [(tf_op, start_ns, duration_ns)]} of the ``XLA Ops``
    line of every ``/device:TPU:<n>`` plane; ``tf_op`` is "" where an op has
    none, and ``<scope>/<kernel prefix>`` for a kernel of ``kernels`` (default
    ``KERNEL_SCOPES``) that lost its path. Times are on
    ``trace_reduce.read_trace``'s clock."""
    kernels = KERNEL_SCOPES if kernels is None else kernels
    out: dict[int, list] = {}
    for num, _, plane in _fields(Path(path).read_bytes()):
        if num != 1:                                  # XSpace.planes
            continue
        name, lines, event_meta, stat_meta = "", [], {}, {}
        for f, _, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 3:
                lines.append(v)
            elif f == 4:
                k, m = _map_entry(v)
                event_meta[k] = m
            elif f == 5:
                k, m = _map_entry(v)
                stat_meta[k] = next((x.decode() for n, _, x in _fields(m) if n == 2), "")
        m = trace_reduce.DEVICE_PLANE.match(name)
        if not m:
            continue
        tf_op_ids = {k for k, v in stat_meta.items() if v == "tf_op"}
        op_of: dict[int, str] = {}
        for k, meta in event_meta.items():           # XEventMetadata: name 2, stats 5
            hlo = ""
            for f, _, stat in _fields(meta):
                if f == 2:
                    hlo = stat.decode()
                if f != 5:
                    continue
                st = dict((n, x) for n, _, x in _fields(stat))
                if st.get(1) in tf_op_ids:           # XStat: metadata_id 1, str 5, ref 7
                    op_of[k] = st[5].decode() if 5 in st else stat_meta.get(st.get(7), "")
            if "/" not in op_of.get(k, ""):
                op_of[k] = next((f"{scope}/{prefix.lstrip('%')}" for prefix, scope in kernels.items()
                                 if hlo.startswith(prefix)), op_of.get(k, ""))
        ops = out.setdefault(int(m.group(1)), [])
        for line in lines:
            fields = list(_fields(line))
            if next((v.decode() for f, _, v in fields if f == 2), "") != trace_reduce.OP_LINE:
                continue
            t0_ns = next((v for f, _, v in fields if f == 3), 0)        # XLine.timestamp_ns
            for f, _, ev in fields:
                if f != 4:
                    continue
                e = dict((n, x) for n, _, x in _fields(ev))            # metadata_id 1, offset_ps 2, duration_ps 3
                ops.append((op_of.get(e.get(1), ""), t0_ns + e.get(2, 0) / 1e3, e.get(3, 0) / 1e3))
    return out


# ------------------------------------------------------------- the reduction
def innermost(tf_op: str, scopes: tuple[str, ...]) -> str:
    """The scope of ``scopes`` named last in the op's path, "" for none."""
    best, where = "", -1
    for s in scopes:
        for m in re.finditer(rf"(?<![\w.]){re.escape(s)}(?![\w.])", tf_op):
            if m.start() > where:
                best, where = s, m.start()
    return best


def self_times(ops: list[tuple[str, float, float]]) -> list[tuple[str, float, float]]:
    """(tf_op, start_ns, self_ns): an op's duration less that of the ops that
    ran inside it. A ``while`` (a ``lax.map`` or ``scan``), a conditional or a
    call is one event that spans its body's events on the same line; summed
    as they are, a loop's time would count twice."""
    ordered = sorted(ops, key=lambda o: (o[1], -o[2]))
    own = [d for _, _, d in ordered]
    open_ops: list[tuple[float, int]] = []            # (end_ns, index), innermost last
    for i, (_, start, dur) in enumerate(ordered):
        while open_ops and start >= open_ops[-1][0]:
            open_ops.pop()
        if open_ops:
            own[open_ops[-1][1]] -= dur
        open_ops.append((start + dur, i))
    return [(name, start, max(t, 0.0)) for (name, start, _), t in zip(ordered, own)]


def reduce_scopes(path: str | Path, scopes: tuple[str, ...]) -> dict[str, float]:
    """Seconds of device time by innermost scope inside the harness's window,
    the mean over chips: each op's own time (``self_times``), ops that start
    and end inside the window."""
    marks = trace_reduce.read_trace(path)["marks"]
    lo, hi = marks[trace_reduce.MARK_BEGIN][0], marks[trace_reduce.MARK_END][0]
    devices = read_device_ops(path)
    out: dict[str, float] = {}
    for ops in devices.values():
        inside = [o for o in ops if o[1] >= lo and o[1] + o[2] <= hi]
        for tf_op, _, own in self_times(inside):
            key = innermost(tf_op, scopes)
            out[key] = out.get(key, 0.0) + own / 1e9 / len(devices)
    return out


def scope_ms_per_step(run: dict, names: tuple[str, ...]):
    """For the metric readers: milliseconds a step of the scopes named, from
    ``run["trace"]["scopes"]`` over the executions of the step's module
    inside the traced window. None where the trace names none of them."""
    trace = run.get("trace")
    if not trace or not trace.get("scopes"):
        return None
    m = trace["modules"].get(run["module_names"].get("train_step"))
    seconds = [trace["scopes"][n] for n in names if n in trace["scopes"]]
    if not seconds or not m or not m["count"]:
        return None
    return sum(seconds) / m["count"] * 1e3
