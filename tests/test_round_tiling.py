"""No time in a round that the tracer cannot name.

Under a clock that advances one tick a read, ``fed_round``'s children
(``round_prologue``, a step's ``batch_build`` / ``h2d`` / ``dispatch`` /
``step_keep``, ``aggregate``, ``device_wait``, ``round_end``,
``round_epilogue``) tile it; every child names its round and every per-step
span its step; a round that stalls names the span that grew
(``obs.tracing.RoundDigest``); every op of the lowered step that takes time
lies under a scope of ``train.step.DEVICE_SCOPES``; and a device trace the
program takes carries the clock that places the tracer's spans on it.
"""

from __future__ import annotations

import re
import time

import jax
import pytest

from fedrec_tpu.obs import Tracer
from fedrec_tpu.obs.tracing import RoundDigest, span_seconds, union_seconds
from fedrec_tpu.parallel import client_mesh, shard_fed_batch
from fedrec_tpu.train import build_fed_train_step
from fedrec_tpu.train.step import DEVICE_SCOPES

from test_round_loop import _sparse_trunk_trainer, _trainer


class Ticks:
    """A clock that advances one tick (a second) every time it is read."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


PER_STEP = ("batch_build", "h2d", "dispatch", "step_keep")

# make(tmp_path, tracer) -> Trainer, and the children that follow the steps
ROUNDS = {
    "cohort-param_avg": (
        lambda tmp, tr: _trainer(tmp, tracer=tr),
        ("aggregate", "device_wait", "round_end", "round_epilogue"),
    ),
    "single-worker-grad_avg": (
        lambda tmp, tr: _trainer(
            tmp, tracer=tr, fed__strategy="grad_avg", fed__num_clients=1,
            mesh=client_mesh(1, max_devices=1),
        ),
        ("device_wait", "round_end", "round_epilogue"),
    ),
    "decoupled": (
        lambda tmp, tr: _trainer(tmp, tracer=tr, model__text_encoder_mode="table"),
        ("news_update", "aggregate", "table_refresh", "device_wait", "round_end",
         "round_epilogue"),
    ),
    "sparse-expert-trunk": (
        lambda tmp, tr: _sparse_trunk_trainer(tmp, tracer=tr),
        ("device_wait", "round_end", "round_epilogue"),
    ),
}


def _rounds_children(tracer):
    """[(fed_round, [its top-level children in time order])]."""
    spans = sorted(
        (e for e in tracer.events() if e.get("ph") == "X"), key=lambda e: e["ts"]
    )
    out = []
    for whole in (e for e in spans if e["name"] == "fed_round"):
        lo, hi = whole["ts"], whole["ts"] + whole["dur"]
        inside = [e for e in spans if e is not whole and lo <= e["ts"] and e["ts"] + e["dur"] <= hi]
        top = [
            e for e in inside
            if not any(o is not e and o["ts"] <= e["ts"]
                       and e["ts"] + e["dur"] <= o["ts"] + o["dur"] for o in inside)
        ]
        out.append((whole, top))
    return out


@pytest.fixture(scope="module")
def traced_rounds(tmp_path_factory):
    """Three rounds of each trainer under the tick clock, run once."""
    cache = {}

    def run(kind):
        if kind not in cache:
            tracer = Tracer(clock=Ticks())
            t = ROUNDS[kind][0](tmp_path_factory.mktemp(kind), tracer)
            for r in range(3):
                t.train_round(r)
            cache[kind] = (t, _rounds_children(tracer))
        return cache[kind]

    return run


@pytest.mark.parametrize("kind", list(ROUNDS))
def test_children_tile_the_round_in_order(traced_rounds, kind):
    _, rounds = traced_rounds(kind)
    assert len(rounds) == 3
    unspanned = []
    for whole, children in rounds:
        names = [e["name"] for e in children]
        steps = names.count("dispatch")
        assert steps >= 2
        assert names == ["round_prologue", *PER_STEP * steps, *ROUNDS[kind][1]]
        # disjoint, in order, inside the round
        at = whole["ts"]
        for e in children:
            assert e["ts"] >= at
            at = e["ts"] + e["dur"]
        assert at <= whole["ts"] + whole["dur"]
        # a step's four spans, the one before its first and the three at the
        # round's end share their readings of the clock: nothing between them
        by_name = {e["name"]: e for e in children}
        chained = [e for e in children if e["name"] in PER_STEP]
        chained = [children[0], *chained]
        for a, b in zip(chained, chained[1:]):
            assert a["ts"] + a["dur"] == pytest.approx(b["ts"])
        for a, b in (("device_wait", "round_end"), ("round_end", "round_epilogue")):
            assert by_name[a]["ts"] + by_name[a]["dur"] == pytest.approx(by_name[b]["ts"])
        left = whole["dur"] - sum(e["dur"] for e in children)
        # what is left: the readings at the round's two ends and one where a
        # span after the steps opens on a reading of its own, however many
        # the steps
        assert 0 <= left / 1e6 <= len(ROUNDS[kind][1]) + 3
        unspanned.append((steps, left))
    # the same rounds leave the same ticks unspanned
    assert len(set(unspanned)) == 1


@pytest.mark.parametrize("kind", list(ROUNDS))
def test_every_child_names_its_round_and_every_step_span_its_step(traced_rounds, kind):
    _, rounds = traced_rounds(kind)
    for r, (whole, children) in enumerate(rounds):
        assert whole["args"]["step_num"] == r
        assert [e["args"]["round"] for e in children] == [r] * len(children)
        for name in PER_STEP:
            of_name = [e["args"]["step"] for e in children if e["name"] == name]
            assert of_name == list(range(len(of_name)))
        kept = [e["args"]["arrays"] for e in children if e["name"] == "step_keep"]
        end = next(e for e in children if e["name"] == "round_end")
        assert sum(kept) == end["args"]["arrays"]


@pytest.mark.parametrize("kind", list(ROUNDS))
def test_the_digest_sums_what_the_trace_holds(traced_rounds, kind):
    """``train.round_span_seconds{span}`` observed every child's seconds
    (``round_epilogue`` up to the digest) and the unspanned rest."""
    t, rounds = traced_rounds(kind)
    hist = {
        c["labels"]["span"]: c
        for c in t.registry.snapshot()["metrics"]["train.round_span_seconds"]["values"]
    }
    names = {e["name"] for _, children in rounds for e in children}
    assert names | {"unspanned"} <= set(hist)
    for name in names - {"round_epilogue"}:
        assert hist[name]["sum"] == pytest.approx(sum(
            e["dur"] for _, children in rounds for e in children if e["name"] == name
        ) / 1e6)
    assert hist["unspanned"]["count"] == len(rounds)
    # the histogram this one replaced (PR 32) is gone: one record of the interval
    assert not [m for m in t.registry.snapshot()["metrics"] if m.startswith("train.round_end")]


def _slow_counts(t):
    cells = t.registry.snapshot()["metrics"].get("train.slow_rounds_total", {}).get("values", [])
    return {c["labels"]["span"]: c["value"] for c in cells}


@pytest.mark.parametrize("span", ["round_prologue", "device_wait"])
def test_a_slow_round_names_the_span_that_grew(tmp_path, monkeypatch, capsys, span):
    """A stall planted under the tick clock: in round 1 (one round kept: no
    record) and in round 4 (four kept: one ``slow_round`` instant, one count,
    one line on stderr)."""
    clock = Ticks()
    tracer = Tracer(clock=clock)
    t = _trainer(tmp_path, tracer=tracer)
    stall = {"on": False}

    def stalled(fn):
        def wrapped(*args, **kw):
            if stall["on"]:
                clock.t += 500.0
            return fn(*args, **kw)
        return wrapped

    if span == "round_prologue":
        monkeypatch.setattr(t, "_round_weights", stalled(t._round_weights))
    else:
        import fedrec_tpu.train.trainer as trainer_module

        class Jax:
            """``jax`` with a ``block_until_ready`` that can stall."""
            block_until_ready = staticmethod(stalled(jax.block_until_ready))

            def __getattr__(self, name):
                return getattr(jax, name)

        monkeypatch.setattr(trainer_module, "jax", Jax())
    for r in range(5):
        stall["on"] = r in (1, 4)
        t.train_round(r)
        if r < 4:
            assert _slow_counts(t) == {}
    assert _slow_counts(t) == {span: 1.0}
    (instant,) = [e for e in tracer.events() if e.get("name") == "slow_round"]
    assert instant["ph"] == "i"
    assert instant["args"]["round"] == 4 and instant["args"]["span"] == span
    assert instant["args"]["excess_ms"] == pytest.approx(500e3)
    err = capsys.readouterr().err
    assert err.count("WARNING: round 4 took") == 1 and repr(span) in err


def test_a_round_within_the_spread_records_nothing():
    """10% and 50 ms both: a round 40 ms over a 1 s median, and one 20% over
    a 0.1 s median, pass unrecorded."""
    from fedrec_tpu.obs import MetricsRegistry

    for base, slow in ((1.0, 1.04), (0.1, 0.12)):
        now = [0.0]
        tracer, registry = Tracer(clock=lambda: now[0]), MetricsRegistry()
        digest = RoundDigest(tracer, registry)
        for r, wall in enumerate([base] * 4 + [slow]):
            digest.begin()
            now[0] += wall
            tracer.add_span("dispatch", dur_s=wall)
            digest.close(r, now[0])
        assert "slow_round" not in [e["name"] for e in tracer.events()]


def test_union_counts_an_interval_once():
    ev = lambda ts, dur: {"name": "x", "ph": "X", "ts": ts, "dur": dur}  # noqa: E731
    events = [ev(0, 10e6), ev(2e6, 3e6), ev(8e6, 6e6), {"ph": "i", "ts": 0}, ev(20e6, 1e6)]
    assert union_seconds(events, 0, 30e6) == pytest.approx(15.0)
    assert union_seconds(events, 5e6, 12e6) == pytest.approx(7.0)
    assert span_seconds(events) == {"x": pytest.approx(20.0)}


# ------------------------------------------------------------ device scopes
_LOC_NAME = re.compile(r'^loc\("([^"]*)"\(')
_TIMED_OPS = ("dot_general", "gather", "scatter", "ragged_dot", "sort", "reduce")
_SCOPE_WORD = re.compile(
    r"(?<![\w.])(" + "|".join(map(re.escape, DEVICE_SCOPES)) + r")(?![\w.])"
)


def _unscoped_ops(lowered) -> list[tuple[str, str]]:
    """(op, its name path) of the timed ops of a lowered program under no
    scope of ``DEVICE_SCOPES``. A jitted callee's ops are named from the
    callee down: the scope may sit on the call that reaches them."""
    funcs: dict[str, dict] = {}

    def name_of(op) -> str:
        m = _LOC_NAME.match(str(op.location))
        return m.group(1) if m else ""

    def walk(op, into):
        for region in op.regions:
            for block in region.blocks:
                for o in block.operations:
                    o = o.operation
                    if o.name == "func.func":
                        fn = str(o.attributes["sym_name"]).strip('"')
                        walk(o, funcs.setdefault(fn, {"ops": [], "calls": []}))
                        continue
                    if o.name == "func.call":
                        callee = str(o.attributes["callee"]).lstrip("@")
                        into["calls"].append((callee, name_of(o)))
                    elif o.name.split(".")[-1] in _TIMED_OPS:
                        into["ops"].append((o.name, name_of(o)))
                    walk(o, into)

    walk(lowered.compiler_ir().operation, {"ops": [], "calls": []})
    bare: list[tuple[str, str]] = []
    seen: set[str] = set()

    def visit(fn: str, path: str):
        """Only reached through calls under no scope so far."""
        if fn in seen:
            return
        seen.add(fn)
        for op, name in funcs[fn]["ops"]:
            if not _SCOPE_WORD.search(name):
                bare.append((op, f"{path}/{name}"))
        for callee, name in funcs[fn]["calls"]:
            if not _SCOPE_WORD.search(name):
                visit(callee, f"{path}/{name}")

    visit("main", "")
    return bare


def _lower_step(t):
    if t._host_dedup and t._encode_rows is None:
        t._choose_encode_rows(0)
    batch = next(iter(t._epoch_batch_iter(0, t._chaos_batch_keys(0), [])))
    step = build_fed_train_step(t.model, t.cfg, t.strategy, t.mesh, mode=t.mode)
    return step.lower(t.state, shard_fed_batch(t.mesh, batch, t.cfg), t._feature_table())


def _trunk_trainer(module: str):
    def make(tmp_path, tracer):
        import importlib

        from fedrec_tpu.obs import MetricsRegistry, set_registry, set_tracer
        from fedrec_tpu.train.trainer import Trainer

        tests = importlib.import_module(module)
        set_registry(MetricsRegistry())
        set_tracer(tracer)
        cfg = tests.trunk_cfg(1)
        return Trainer(cfg, tests.trunk_data(cfg), None, mesh=client_mesh(1, max_devices=1))
    return make


LOWERED = {
    "cohort-head-mode": ROUNDS["cohort-param_avg"][0],
    "single-worker-head-mode": ROUNDS["single-worker-grad_avg"][0],
    "decoupled": ROUNDS["decoupled"][0],
    "device-dedup-dpsgd": lambda tmp, tr: _trainer(
        tmp, tracer=tr, privacy__enabled=True, privacy__mechanism="dpsgd", privacy__sigma=1.0,
    ),
    "sparse-expert-trunk": _trunk_trainer("test_sparse_trunk"),
    "latent-trunk": _trunk_trainer("test_latent_trunk"),
    "window-trunk": _trunk_trainer("test_window_trunk"),
}


@pytest.mark.parametrize("kind", list(LOWERED))
def test_every_timed_op_of_the_lowered_step_lies_under_a_scope(tmp_path, kind):
    t = LOWERED[kind](tmp_path, Tracer())
    assert _unscoped_ops(_lower_step(t)) == []


@pytest.mark.parametrize("kind", ["cohort-head-mode"])
def test_every_timed_op_of_the_lowered_sync_lies_under_param_sync(tmp_path, kind):
    import jax.numpy as jnp

    from fedrec_tpu.train import build_param_sync

    t = LOWERED[kind](tmp_path, Tracer())
    sync = build_param_sync(t.cfg, t.mesh, t.strategy)
    lowered = sync.lower(t.state, jnp.ones((t.cfg.fed.num_clients,), jnp.float32))
    assert _unscoped_ops(lowered) == []
    assert "param_sync" in lowered.as_text(debug_info=True)


# ---------------------------------------------------------------- one clock
def test_a_device_trace_carries_the_clock_that_places_the_spans(tmp_path):
    """``profile_if`` stamps two ``fedrec_clock`` annotations with ``t_ns``;
    a span recorded between them lands between them on the trace's timeline
    by ``otherData.epoch_perf_counter_ns`` and the annotation's offset."""
    from jax.profiler import ProfileData

    from fedrec_tpu.utils.profiling import CLOCK_MARK, profile_if

    tracer = Tracer()
    with profile_if(True, str(tmp_path / "trace")) as logdir:
        with tracer.span("between"):
            jax.block_until_ready(jax.numpy.ones((64, 64)) @ jax.numpy.ones((64, 64)))
            time.sleep(0.01)
    assert logdir == str(tmp_path / "trace")
    (xplane,) = sorted((tmp_path / "trace").glob("plugins/profile/*/*.xplane.pb"))
    marks = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == CLOCK_MARK:
                        marks.append((float(e.start_ns), int(dict(e.stats)["t_ns"])))
    assert len(marks) == 2
    (first_at, first_t), (last_at, last_t) = sorted(marks)
    assert first_t < last_t
    doc = tracer.to_chrome()
    (span,) = [e for e in doc["traceEvents"] if e["name"] == "between"]
    epoch_ns = doc["otherData"]["epoch_perf_counter_ns"]
    offset = first_at - first_t                  # trace_ns = perf_counter_ns + offset
    start = epoch_ns + span["ts"] * 1e3 + offset
    end = start + span["dur"] * 1e3
    assert first_at <= start < end <= last_at
    # the two annotations agree on the offset to well under a millisecond
    assert abs((last_at - last_t) - offset) < 1e6
    # and the epoch is the tracer's own, on perf_counter_ns' scale
    assert abs(epoch_ns + span["ts"] * 1e3 - first_t) < 5e9
    assert first_t <= epoch_ns + span["ts"] * 1e3 <= last_t
