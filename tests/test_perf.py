"""Performance observability (``fedrec_tpu.obs.perf``): the shared
FLOPs/peaks model, the one-spelling roofline verdict, cost_analysis edge
cases (gauges skip, never raise), HBM attribution, the PerfMonitor round
digest + capture windows, the perf-regression gate, and the acceptance
pin that ``obs.perf`` disabled keeps the pre-perf programs byte-identical
(enabled vs disabled trajectories bit-equal — telemetry is observational).
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

from fedrec_tpu.config import ExperimentConfig
from fedrec_tpu.obs import MetricsRegistry, Tracer, set_registry, set_tracer
from fedrec_tpu.obs.tracing import span_seconds
from fedrec_tpu.obs.perf import (
    CHIP_PEAKS,
    PEAK_FLOPS,
    ROOFLINE_VERDICTS,
    VERDICT_INPUT_BOUND,
    CostAnalysisRecorder,
    PerfMonitor,
    analyze_compiled_cost,
    chip_peaks,
    flops_per_train_step,
    live_array_components,
    parse_capture_rounds,
    peak_flops,
    roofline_verdict,
)

from test_train import make_setup, small_cfg

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture()
def fresh_obs():
    reg, tr = MetricsRegistry(), Tracer()
    old_reg, old_tr = set_registry(reg), set_tracer(tr)
    try:
        yield reg, tr
    finally:
        set_registry(old_reg)
        set_tracer(old_tr)


# ------------------------------------------------------- shared flops model
def test_bench_imports_the_shared_flops_model():
    """Satellite: ONE definition serving bench, step_profile and the live
    gauges — bench re-exports the perf module's objects, step_profile
    imports them (lockstep-edit retirement, like PR 8's chain_timer)."""
    sys.path.insert(0, str(REPO))
    try:
        import bench
    finally:
        sys.path.remove(str(REPO))
    assert bench._flops_per_train_step is flops_per_train_step
    assert bench._PEAK_FLOPS is PEAK_FLOPS
    prof_src = (REPO / "benchmarks" / "step_profile.py").read_text()
    assert "from fedrec_tpu.obs.perf import" in prof_src
    assert "from bench import _flops_per_train_step" not in prof_src


def test_flops_model_scales_and_saturates_at_the_catalog():
    cfg = ExperimentConfig()
    base = flops_per_train_step(cfg, 64, 4096)
    assert base > 0
    # more batch = more flops; the text tower term saturates at num_news
    assert flops_per_train_step(cfg, 128, 4096) > base
    # a catalog smaller than the 3,520 slots bounds the text-tower term,
    # as it bounds the rows the compiled step encodes
    assert flops_per_train_step(cfg, 64, 256) < base
    # the text tower is priced at the size the round loop compiled it at,
    # never above the slots (work the step skipped is not counted)
    assert flops_per_train_step(cfg, 64, 4096, encode_rows=2880) < base
    assert flops_per_train_step(cfg, 64, 4096, encode_rows=10**6) == base


def test_chip_peaks_lookup():
    assert peak_flops("TPU v4", "bfloat16") == 275e12
    assert peak_flops("TPU v4", "float32") == 137e12
    assert peak_flops("cpu", "bfloat16") is None
    peaks = chip_peaks("TPU v5 lite pod slice")
    assert peaks == CHIP_PEAKS["v5 lite"] and peaks[2] == 819e9
    # what one v5e chip reports as device_kind (chip run, PR 22)
    assert chip_peaks("TPU v5 lite") == CHIP_PEAKS["v5 lite"]
    with pytest.raises(ValueError, match="CHIP_PEAKS"):
        chip_peaks("TPU v9 hypothetical")


# --------------------------------------------------------- roofline verdict
def test_roofline_verdict_one_spelling():
    # input-bound outranks everything, fractions included
    key, s = roofline_verdict(True, mfu=0.9, hbm_fraction=0.9)
    assert key == "input" and s == VERDICT_INPUT_BOUND
    assert s.startswith("input-bound")
    # no peaks known -> device-bound-pending-chip, not a fraction claim
    assert roofline_verdict(False)[0] == "device"
    # memory wins over compute at the historical 0.6 thresholds
    assert roofline_verdict(False, mfu=0.7, hbm_fraction=0.7)[0] == "memory"
    assert roofline_verdict(False, mfu=0.7, hbm_fraction=0.1)[0] == "compute"
    assert roofline_verdict(False, mfu=0.1, hbm_fraction=0.1)[0] == "headroom"
    # the key->string table is total and consistent
    for key in ("input", "memory", "compute", "headroom", "device"):
        assert key in ROOFLINE_VERDICTS


def test_parse_capture_rounds():
    assert parse_capture_rounds("") is None
    assert parse_capture_rounds("5") == (5, 1)
    assert parse_capture_rounds("3:2") == (3, 2)
    for bad in ("x", "3:", "3:0", "1:2:3"):
        with pytest.raises(ValueError):
            parse_capture_rounds(bad)


# ---------------------------------------------------- cost_analysis edges
class _Lowered:
    def __init__(self, cost):
        self._cost = cost

    def compile(self):
        return self

    def cost_analysis(self):
        if isinstance(self._cost, Exception):
            raise self._cost
        return self._cost


class _FakeJitted:
    def __init__(self, cost):
        self._cost = cost

    def lower(self, *a, **k):
        return _Lowered(self._cost)


def _cell(reg, name, **labels):
    from fedrec_tpu.obs.report import snapshot_value

    return snapshot_value(reg.snapshot(), name, labels or None)


def test_cost_recorder_none_and_raises(fresh_obs):
    """CPU-style backends returning None (or raising) must only count an
    'unavailable' outcome — no gauge cells, no exception."""
    reg, _ = fresh_obs
    rec = CostAnalysisRecorder(reg)
    rec(_FakeJitted(None), (), {}, "fn_none")
    rec(_FakeJitted(RuntimeError("no cost analysis")), (), {}, "fn_raise")
    rec(object(), (), {}, "fn_plain")  # no .lower at all
    snap = reg.snapshot()["metrics"]
    assert not snap.get("xla.cost_flops", {}).get("values")
    for fn in ("fn_none", "fn_raise", "fn_plain"):
        assert _cell(
            reg, "xla.cost_analyses_total", fn=fn, outcome="unavailable"
        ) == 1.0


def test_cost_recorder_partial_dict(fresh_obs):
    """A dict missing 'bytes accessed' publishes flops only — the absent
    keys SKIP, they don't become zeros (a zero would poison ratios)."""
    reg, _ = fresh_obs
    rec = CostAnalysisRecorder(reg)
    rec(_FakeJitted({"flops": 5e6}), (), {}, "fn_partial")
    assert _cell(reg, "xla.cost_flops", fn="fn_partial") == 5e6
    assert _cell(reg, "xla.cost_bytes_accessed", fn="fn_partial") is None
    assert _cell(reg, "xla.cost_arithmetic_intensity", fn="fn_partial") is None
    assert _cell(
        reg, "xla.cost_analyses_total", fn="fn_partial", outcome="ok"
    ) == 1.0
    # non-numeric values are ignored, not coerced
    rec(_FakeJitted({"flops": "banana"}), (), {}, "fn_garbage")
    assert _cell(
        reg, "xla.cost_analyses_total", fn="fn_garbage", outcome="unavailable"
    ) == 1.0
    # a LEGITIMATE 0.0 reading (copy/broadcast program) is data, not a
    # missing key: the gauge publishes 0.0 and the outcome is ok
    rec(_FakeJitted({"flops": 0.0, "bytes accessed": 64.0}), (), {}, "fn_zero")
    assert _cell(reg, "xla.cost_flops", fn="fn_zero") == 0.0
    assert _cell(reg, "xla.cost_bytes_accessed", fn="fn_zero") == 64.0
    assert _cell(
        reg, "xla.cost_analyses_total", fn="fn_zero", outcome="ok"
    ) == 1.0


def test_cost_recorder_multi_executable(fresh_obs):
    """Older jaxlibs return a LIST of dicts (one per executable): keys
    present sum across entries, keys absent in some entries still count."""
    reg, _ = fresh_obs
    rec = CostAnalysisRecorder(reg)
    rec(
        _FakeJitted([
            {"flops": 1e6, "bytes accessed": 2e6},
            {"flops": 3e6},
            "not-a-dict",
        ]),
        (), {}, "fn_multi",
    )
    assert _cell(reg, "xla.cost_flops", fn="fn_multi") == 4e6
    assert _cell(reg, "xla.cost_bytes_accessed", fn="fn_multi") == 2e6
    assert _cell(
        reg, "xla.cost_arithmetic_intensity", fn="fn_multi"
    ) == pytest.approx(2.0)


def test_cost_recorder_real_jit_via_watchdog(fresh_obs):
    """The real hook path: a watched jitted fn's FIRST (compiling) call
    fires the cost callback exactly once; warm calls never re-fire."""
    import jax
    import jax.numpy as jnp

    from fedrec_tpu.obs.device import CompileWatchdog, set_active_watchdog

    reg, _ = fresh_obs
    rec = CostAnalysisRecorder(reg)
    wd = CompileWatchdog(registry=reg, cost_cb=rec)
    prev = wd.install()
    try:
        f = wd.watch(jax.jit(lambda x: (x @ x).sum()), "matmul_fn")
        x = jnp.ones((32, 32), jnp.float32)
        f(x)
        total_after_compile = _cell(
            reg, "xla.cost_analyses_total", fn="matmul_fn", outcome="ok"
        ) or _cell(
            reg, "xla.cost_analyses_total", fn="matmul_fn",
            outcome="unavailable",
        )
        assert total_after_compile == 1.0
        f(x)  # warm: no compile event, no new analysis
        snap = reg.snapshot()["metrics"]
        rows = snap["xla.cost_analyses_total"]["values"]
        assert sum(
            r["value"] for r in rows if r["labels"].get("fn") == "matmul_fn"
        ) == 1.0
        # XLA:CPU does report cost_analysis — when it did, flops are real
        flops = _cell(reg, "xla.cost_flops", fn="matmul_fn")
        if flops is not None:
            assert flops > 0
    finally:
        set_active_watchdog(prev)


def test_cost_hook_own_compile_events_suppressed(fresh_obs):
    """The hook's AOT re-compile fires its own backend_compile events —
    they must NOT double-count xla.compile_seconds_total (nor land as
    <unwatched> program compiles)."""
    from fedrec_tpu.obs import device as dev

    reg, _ = fresh_obs

    def fake_jitted(x):
        # simulate the real compile event firing inside the watched call
        dev._on_event_duration("backend_compile_duration", 0.5)
        return x

    def cost_cb(fn, args, kwargs, name):
        # simulate the AOT re-compile's event inside the hook: suppressed
        dev._on_event_duration("backend_compile_duration", 2.0)

    wd = dev.CompileWatchdog(registry=reg, cost_cb=cost_cb)
    prev = dev.set_active_watchdog(wd)
    try:
        wd.watch(fake_jitted, "fake_fn")(1)
    finally:
        dev.set_active_watchdog(prev)
    assert _cell(reg, "xla.compile_seconds_total") == 0.5
    assert _cell(reg, "xla.compiles_total", fn="fake_fn") == 1.0


# --------------------------------------------------------- HBM attribution
def test_live_array_components_classifies_by_identity(fresh_obs):
    import jax.numpy as jnp

    reg, tr = fresh_obs
    params = {"w": jnp.ones((8, 8), jnp.float32)}
    table = jnp.ones((4, 16), jnp.float32)
    totals = live_array_components(
        {"params": params, "news_table": table, "batch": None},
        registry=reg, tracer=tr,
    )
    assert totals["params"] >= 8 * 8 * 4
    assert totals["news_table"] >= 4 * 16 * 4
    assert "batch" not in totals  # None trees register no bucket
    from fedrec_tpu.obs.report import snapshot_value

    snap = reg.snapshot()
    assert snapshot_value(
        snap, "hbm.component_bytes", {"component": "params"}
    ) == totals["params"]
    assert any(e["name"] == "hbm_components" for e in tr.events())


# ------------------------------------------------------ PerfMonitor digest
def _mk_monitor(reg, tr, device_kind, tmp_path=None, **pover):
    cfg = small_cfg()
    cfg.fed.num_clients = 4
    for k, v in pover.items():
        setattr(cfg.obs.perf, k, v)
    return cfg, PerfMonitor(
        cfg.obs.perf, cfg, num_news=64, registry=reg, tracer=tr,
        obs_dir=(str(tmp_path) if tmp_path else None),
        device_kind=device_kind,
    )


def test_monitor_round_digest_no_peaks(fresh_obs):
    """CPU (unknown chip): throughput + per-step phase gauges publish,
    MFU stays absent, and the verdict comes from the host/dispatch split
    only — 'input' when the host pipeline dominates, 'device' else."""
    reg, tr = fresh_obs
    cfg, mon = _mk_monitor(reg, tr, device_kind="cpu")
    steps = reg.counter("train.steps_total", "")
    mon.begin_round()
    steps.inc(4)
    tr.add_span("batch_build", dur_s=0.30)
    tr.add_span("h2d", dur_s=0.10)
    tr.add_span("dispatch", dur_s=0.20)
    out = mon.observe_round(0, 1.0, span_seconds(tr.events()))
    assert out["perf.samples_per_sec"] == pytest.approx(
        4 * cfg.fed.num_clients * cfg.data.batch_size, rel=1e-6
    )
    assert "perf.mfu" not in out
    assert out["perf.verdict"] == "input"  # 0.4 s host >= 0.2 s dispatch
    from fedrec_tpu.obs.report import snapshot_value

    snap = reg.snapshot()
    assert snapshot_value(snap, "perf.host_ms_per_step") == pytest.approx(100.0)
    assert snapshot_value(snap, "perf.dispatch_ms_per_step") == pytest.approx(50.0)
    assert snapshot_value(
        snap, "perf.roofline_rounds_total", {"verdict": "input"}
    ) == 1.0
    # second round, dispatch-dominant -> 'device' (no chip peaks)
    mon.begin_round()
    mark = tr.event_count()
    steps.inc(4)
    tr.add_span("dispatch", dur_s=0.5)
    digest = span_seconds(tr.events_since(mark))
    assert mon.observe_round(1, 0.6, digest)["perf.verdict"] == "device"


def test_monitor_untraced_round_publishes_no_verdict(fresh_obs):
    """A saturated tracer ring drops the round's phase spans — the digest
    must then publish NO verdict (counted on perf.untraced_rounds_total)
    rather than misreading the silence as 'not input-bound'."""
    reg, tr = fresh_obs
    tr.capacity = 1  # one span fits; everything after is dropped
    _, mon = _mk_monitor(reg, tr, device_kind="cpu")
    steps = reg.counter("train.steps_total", "")
    tr.add_span("dispatch", dur_s=0.1)  # fills the ring pre-round
    mon.begin_round()
    steps.inc(4)
    tr.add_span("batch_build", dur_s=0.4)  # dropped
    out = mon.observe_round(0, 1.0, {})
    assert "perf.verdict" not in out
    assert out["perf.samples_per_sec"] > 0  # wall-based gauges still land
    from fedrec_tpu.obs.report import snapshot_value

    snap = reg.snapshot()
    assert snapshot_value(snap, "perf.untraced_rounds_total") == 1.0
    assert not snap["metrics"].get(
        "perf.roofline_rounds_total", {}
    ).get("values")


def test_monitor_mfu_with_chip_peaks_and_eval_exclusion(fresh_obs):
    """With known peaks the MFU gauge publishes (hand-checkable against
    the analytic model), and the eval span is excluded from the
    efficiency denominators so eval-cadence rounds stay comparable."""
    reg, tr = fresh_obs
    cfg, mon = _mk_monitor(reg, tr, device_kind="TPU v4")
    steps = reg.counter("train.steps_total", "")
    mon.begin_round()
    steps.inc(8)
    tr.add_span("dispatch", dur_s=1.0)
    tr.add_span("eval", dur_s=1.0)
    out = mon.observe_round(0, 3.0, span_seconds(tr.events()))
    flops = 8 * cfg.fed.num_clients * flops_per_train_step(cfg, cfg.data.batch_size, 64)
    peak = peak_flops("TPU v4", cfg.model.dtype)
    # denominator is wall MINUS the eval span (2.0 s, not 3.0); the
    # unrounded gauge is the ground truth (log keys round at 6 digits)
    from fedrec_tpu.obs.report import snapshot_value

    assert snapshot_value(
        reg.snapshot(), "perf.mfu"
    ) == pytest.approx(flops / 2.0 / peak, rel=1e-6)
    assert "perf.mfu" in out
    assert out["perf.samples_per_sec"] == pytest.approx(
        8 * cfg.fed.num_clients * cfg.data.batch_size / 2.0, rel=1e-6
    )


def test_monitor_prices_the_text_tower_at_the_encode_size(fresh_obs):
    """Once the round loop has chosen the step's encode size, the published
    step FLOPs (and with them the MFU gauge) count that many text-tower
    rows: work the step skipped is never counted."""
    from fedrec_tpu.obs.report import snapshot_value

    reg, tr = fresh_obs
    cfg, mon = _mk_monitor(reg, tr, device_kind="TPU v4")
    every_slot = flops_per_train_step(cfg, cfg.data.batch_size, 64)
    assert snapshot_value(reg.snapshot(), "perf.step_flops") == every_slot
    mon.set_encode_rows(32)
    at_r = flops_per_train_step(cfg, cfg.data.batch_size, 64, encode_rows=32)
    assert at_r < every_slot
    assert mon.flops_per_step == at_r
    assert snapshot_value(reg.snapshot(), "perf.step_flops") == at_r


def test_monitor_capture_needs_obs_dir(fresh_obs):
    """An explicitly requested capture window without an obs dir fails
    fast at construction — silently-never-capture is a misconfiguration,
    not a preference."""
    reg, tr = fresh_obs
    with pytest.raises(ValueError, match="obs.dir"):
        _mk_monitor(reg, tr, "cpu", tmp_path=None, capture_rounds="1")
    with pytest.raises(ValueError, match="obs.dir"):
        _mk_monitor(reg, tr, "cpu", tmp_path=None, capture_drop=0.3)


def test_monitor_capture_window_and_pointer(fresh_obs, tmp_path):
    reg, tr = fresh_obs
    _, mon = _mk_monitor(reg, tr, "cpu", tmp_path, capture_rounds="1")
    assert mon.capture_before_round(0) is None
    logdir = mon.capture_before_round(1)
    assert logdir is not None and "perf_capture_r0001" in logdir
    mon.capture_after_round(1)
    assert Path(logdir).exists()
    recs = [
        json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()
    ]
    (ptr,) = [r for r in recs if r.get("kind") == "perf_capture"]
    assert ptr["logdir"] == logdir and ptr["reason"] == "configured"
    from fedrec_tpu.obs.report import snapshot_value

    assert snapshot_value(
        reg.snapshot(), "perf.captures_total", {"reason": "configured"}
    ) == 1.0


def test_monitor_capture_window_spans_its_rounds(fresh_obs, tmp_path):
    """A window of several rounds opens at its first round, stays the one
    open window through its last, and closes after it."""
    reg, tr = fresh_obs
    _, mon = _mk_monitor(reg, tr, "cpu", tmp_path, capture_rounds="3:2")
    assert mon.capture_before_round(2) is None  # [3,5) does not hold 2
    logdir = mon.capture_before_round(3)
    assert logdir is not None and "perf_capture_r0003" in logdir
    mon.capture_after_round(3)  # round 4 is still to come
    assert mon.capture_before_round(4) is None  # no second window inside
    mon.capture_after_round(4)
    assert Path(logdir).exists()
    (ptr,) = [
        r for r in map(
            json.loads, (tmp_path / "metrics.jsonl").read_text().splitlines()
        ) if r.get("kind") == "perf_capture"
    ]
    assert ptr["round"] == 3 and ptr["last_round"] == 4
    assert mon.capture_before_round(5) is None


def test_monitor_efficiency_drop_trigger(fresh_obs, tmp_path):
    reg, tr = fresh_obs
    _, mon = _mk_monitor(
        reg, tr, "cpu", tmp_path, capture_drop=0.5, capture_window=4
    )
    steps = reg.counter("train.steps_total", "")
    for r in range(3):  # healthy rounds build the trailing mean
        mon.begin_round()
        steps.inc(4)
        mon.observe_round(r, 1.0, {})
        assert mon.capture_before_round(r + 1) is None or r < 2
    mon.begin_round()
    steps.inc(1)  # 4x slower round -> > 50% below trailing mean
    mon.observe_round(3, 1.0, {})
    logdir = mon.capture_before_round(4)
    assert logdir is not None
    mon.capture_after_round(4)
    recs = [
        json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()
    ]
    assert any(r.get("reason") == "efficiency_drop" for r in recs)


# ------------------------------------------------- report / CLI extraction
def _write_obs_dir(tmp_path, reg, records=()):
    obs = tmp_path / "obs"
    obs.mkdir(exist_ok=True)
    for r in records:
        with open(obs / "metrics.jsonl", "a") as f:
            f.write(json.dumps(r) + "\n")
    reg.write_snapshot(obs / "metrics.jsonl")
    return obs


def test_perf_detail_report_and_cli(fresh_obs, tmp_path, capsys):
    from fedrec_tpu.cli.obs import main as obs_main
    from fedrec_tpu.obs.report import (
        build_report,
        perf_detail_from_snapshot,
        render_text,
    )

    reg, tr = fresh_obs
    _, mon = _mk_monitor(reg, tr, "TPU v4")
    steps = reg.counter("train.steps_total", "")
    mon.begin_round()
    steps.inc(4)
    tr.add_span("dispatch", dur_s=0.4)
    out = mon.observe_round(0, 0.5, span_seconds(tr.events()))
    mon.cost(_FakeJitted({"flops": 1e9, "bytes accessed": 5e8}), (), {},
             "train_step")
    live_array_components({"params": {}}, registry=reg)
    detail = perf_detail_from_snapshot(reg.snapshot())
    assert detail["samples_per_sec"] > 0
    assert detail["verdict_rounds"] == {"headroom": 1.0}
    assert detail["compile_cost"]["train_step"]["flops"] == 1e9
    report = build_report([], [reg.snapshot()])
    assert "perf" in report
    assert "## Perf" in render_text(report)

    obs = _write_obs_dir(
        tmp_path, reg,
        records=[{"step": 0, "round": 0, **out}],
    )
    assert obs_main(["perf", str(obs)]) == 0
    text = capsys.readouterr().out
    assert "Roofline verdicts" in text and "Compile cost" in text

    # a perf-less run exits 2 with an operator-grade hint
    reg2 = MetricsRegistry()
    obs2 = tmp_path / "obs2"
    obs2.mkdir()
    reg2.write_snapshot(obs2 / "metrics.jsonl")
    assert obs_main(["perf", str(obs2)]) == 2


def test_fleet_report_carries_perf(fresh_obs, tmp_path):
    from fedrec_tpu.obs.fleet import build_fleet_report, load_fleet_dir

    reg, tr = fresh_obs
    _, mon = _mk_monitor(reg, tr, "TPU v4")
    steps = reg.counter("train.steps_total", "")
    mon.begin_round()
    steps.inc(4)
    tr.add_span("dispatch", dur_s=0.4)
    mon.observe_round(0, 0.5, span_seconds(tr.events()))
    obs = _write_obs_dir(tmp_path, reg)
    (obs / "trace.json").write_text(json.dumps(tr.to_chrome()))
    workers = load_fleet_dir(obs)
    rep = build_fleet_report(workers)
    (wid,) = rep["perf"].keys()
    assert rep["perf"][wid]["samples_per_sec"] > 0
    assert rep["perf"][wid]["verdict"] == "headroom"


# -------------------------------------------------- trainer acceptance pin
def _run_small_trainer(tmp_path, tag, rounds=2, **obs_over):
    cfg = small_cfg(optim__user_lr=3e-3)
    cfg.model.text_encoder_mode = "head"
    cfg.fed.strategy = "param_avg"
    cfg.fed.num_clients = 4
    cfg.fed.rounds = rounds
    cfg.train.snapshot_dir = str(tmp_path / f"snap_{tag}")
    cfg.train.save_every = 1000
    cfg.train.eval_every = rounds
    for k, v in obs_over.items():
        if k in ("dir", "perf_enabled", "capture_rounds", "profile"):
            continue
        setattr(cfg.obs.perf, k, v)
    if obs_over.get("dir"):
        cfg.obs.dir = obs_over["dir"]
    cfg.obs.perf.enabled = bool(obs_over.get("perf_enabled"))
    cfg.obs.perf.capture_rounds = obs_over.get("capture_rounds", "")
    cfg.train.profile = bool(obs_over.get("profile"))
    data, _, token_states, _, _, _ = make_setup(cfg, num_train=64, seed=0)
    from fedrec_tpu.train.trainer import Trainer

    t = Trainer(cfg, data, np.asarray(token_states))
    t.run()
    return t


def test_trainer_perf_disabled_is_byte_identical(tmp_path):
    """The acceptance pin: obs.perf telemetry is OBSERVATIONAL — an
    enabled run's trajectory is bit-identical to a disabled run's, and a
    disabled run registers no perf instruments at all."""
    import jax

    reg1, tr1 = MetricsRegistry(), Tracer()
    old_reg, old_tr = set_registry(reg1), set_tracer(tr1)
    try:
        t_off = _run_small_trainer(tmp_path, "off", perf_enabled=False)
        off_leaves = [
            np.asarray(x) for x in jax.tree_util.tree_leaves(
                (t_off.state.user_params, t_off.state.news_params)
            )
        ]
        assert not any(
            name.startswith(("perf.", "hbm.", "xla.cost_"))
            for name in reg1.snapshot()["metrics"]
        )
        assert t_off.perf is None
    finally:
        set_registry(old_reg)
        set_tracer(old_tr)

    reg2, tr2 = MetricsRegistry(), Tracer()
    old_reg, old_tr = set_registry(reg2), set_tracer(tr2)
    try:
        t_on = _run_small_trainer(
            tmp_path, "on", perf_enabled=True,
            dir=str(tmp_path / "obs_on"), capture_rounds="1",
        )
        on_leaves = [
            np.asarray(x) for x in jax.tree_util.tree_leaves(
                (t_on.state.user_params, t_on.state.news_params)
            )
        ]
        names = reg2.snapshot()["metrics"]
        assert "perf.samples_per_sec" in names
        assert "hbm.component_bytes" in names
        assert any(
            p.name.startswith("perf_capture_r")
            for p in (tmp_path / "obs_on").iterdir()
        )
    finally:
        set_registry(old_reg)
        set_tracer(old_tr)

    for a, b in zip(off_leaves, on_leaves):
        np.testing.assert_array_equal(a, b)


def test_trainer_profile_routes_into_obs_dir(tmp_path):
    """Satellite: train.profile's jax.profiler trace lands inside obs.dir
    (not the /tmp default) with a metrics.jsonl pointer record."""
    reg, tr = MetricsRegistry(), Tracer()
    old_reg, old_tr = set_registry(reg), set_tracer(tr)
    try:
        obs = tmp_path / "obs_prof"
        _run_small_trainer(
            tmp_path, "prof", rounds=1, perf_enabled=False,
            dir=str(obs), profile=True,
        )
        assert (obs / "jax_profile").exists()
        recs = [
            json.loads(l)
            for l in (obs / "metrics.jsonl").read_text().splitlines()
        ]
        (ptr,) = [r for r in recs if r.get("kind") == "profile_trace"]
        assert ptr["logdir"] == str(obs / "jax_profile")
    finally:
        set_registry(old_reg)
        set_tracer(old_tr)


# ------------------------------------------------------------- perf gate
def _import_perf_gate():
    sys.path.insert(0, str(REPO / "benchmarks"))
    try:
        import perf_gate
    finally:
        sys.path.remove(str(REPO / "benchmarks"))
    return perf_gate


def test_perf_gate_bank_check_and_forced_regression(tmp_path, capsys):
    pg = _import_perf_gate()
    lanes = pg.measure_lanes(repeats=1)
    assert set(lanes) >= {
        "steps_per_sec", "batch_build_ms", "h2d_ms",
        "dispatch_gap_sync_ms", "dispatch_gap_prefetch_ms", "flops_per_step",
    }
    out = tmp_path / "perf_gate.json"
    baseline = pg.bank(out, lanes, repeats=1)
    assert out.exists() and "provenance" in baseline

    # a re-measure of the same seeded scenario passes
    import copy

    assert pg.check(baseline, copy.deepcopy(lanes)) == 0
    capsys.readouterr()

    # forced regression: steps/s cut 3x -> fail NAMING the lane
    bad = copy.deepcopy(lanes)
    bad["steps_per_sec"]["value"] /= 3.0
    assert pg.check(baseline, bad) == 1
    text = capsys.readouterr().out
    assert "PERF_GATE=FAIL" in text
    assert "REGRESSION lane steps_per_sec" in text

    # the exact lane allows ZERO drift: a FLOPs-model change must fail
    drifted = copy.deepcopy(lanes)
    drifted["flops_per_step"]["value"] *= 1.001
    assert pg.check(baseline, drifted) == 1
    assert "FLOPs model changed" in capsys.readouterr().out

    # a lane vanishing from the scenario fails too (drift, not silence)
    missing = copy.deepcopy(lanes)
    del missing["h2d_ms"]
    assert pg.check(baseline, missing) == 1
    assert "MISSING" in capsys.readouterr().out


def test_perf_gate_demo_clears_abs_floor(capsys):
    """The forced-regression corruption must fail even a tiny ms lane:
    10x a 0.05 ms baseline would hide under the 0.5 ms absolute grace
    floor, so the demo corruption is additive-aware."""
    pg = _import_perf_gate()
    base = {"value": 0.05, "unit": "ms", "direction": "higher_is_worse",
            "spread": 0.0, "kind": "timing"}
    corrupted = max(
        base["value"] * pg.DEMO_FACTOR,
        base["value"] + pg.DEMO_FACTOR * pg.ABS_FLOOR_MS,
    )
    now = dict(base, value=corrupted, simulated=True)
    assert pg.check({"lanes": {"h2d_ms": base}}, {"h2d_ms": now}) == 1
    assert "REGRESSION lane h2d_ms" in capsys.readouterr().out


def test_perf_gate_timing_noise_tolerance():
    """The noise-aware threshold: a jittery re-measure inside
    max(rel floor, 4x spread) passes; beyond it fails."""
    pg = _import_perf_gate()
    base = {"value": 100.0, "unit": "ms", "direction": "higher_is_worse",
            "spread": 5.0, "kind": "timing"}
    now_ok = dict(base, value=145.0)
    now_bad = dict(base, value=200.0)
    baseline = {"lanes": {"lane_ms": base}}
    assert pg.check(baseline, {"lane_ms": now_ok}) == 0
    assert pg.check(baseline, {"lane_ms": now_bad}) == 1
