"""Trainer x observability: round spans nest correctly in the round
loop, the DP accountant's ``privacy.epsilon_spent`` gauge tracks rounds, and the
``fedrec-obs`` report renders a real run's artifacts."""

from __future__ import annotations

import json

import numpy as np
import pytest

from fedrec_tpu.obs import MetricsRegistry, Tracer, set_registry, set_tracer
from fedrec_tpu.train.trainer import Trainer

from test_train import make_setup, small_cfg

# spans emitted INSIDE a federated round; checkpoint is _after_round work
ROUND_CHILD_SPANS = {"batch_build", "h2d", "dispatch", "aggregate", "eval"}


@pytest.fixture()
def fresh_obs():
    reg, tr = MetricsRegistry(), Tracer()
    old_reg, old_tr = set_registry(reg), set_tracer(tr)
    try:
        yield reg, tr
    finally:
        set_registry(old_reg)
        set_tracer(old_tr)


def _run_trainer(tmp_path, tag, rounds=2, privacy=False, prefetch=0):
    cfg = small_cfg(optim__user_lr=3e-3)
    cfg.model.text_encoder_mode = "head"  # joint mode
    cfg.fed.strategy = "param_avg"
    cfg.fed.rounds = rounds
    cfg.train.snapshot_dir = str(tmp_path / f"snap_{tag}")
    cfg.train.save_every = 1000
    cfg.train.eval_every = rounds  # one eval, on the final round
    cfg.data.prefetch_batches = prefetch
    cfg.obs.dir = str(tmp_path / f"obs_{tag}")
    if privacy:
        cfg.privacy.enabled = True
        cfg.privacy.sigma = 1.0  # explicit: the gauge needs no calibration run
    data, _, token_states, _, _, _ = make_setup(cfg, num_train=128, seed=0)
    t = Trainer(cfg, data, np.asarray(token_states))
    t.run()
    return cfg


def _trace_events(cfg):
    doc = json.loads((open(f"{cfg.obs.dir}/trace.json")).read())
    evs = doc["traceEvents"]
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts), "exported trace ts must be monotonic"
    return evs


def _assert_children_nest(evs, expect_chunks):
    """Every round-child span lies inside exactly one fed_round interval,
    and the fed_round spans' (step_num, num_rounds) args tile the run."""
    rounds = [e for e in evs if e["name"] == "fed_round"]
    assert [(e["args"]["step_num"], e["args"]["num_rounds"]) for e in rounds] \
        == expect_chunks
    intervals = [(e["ts"], e["ts"] + e["dur"]) for e in rounds]
    children = [e for e in evs if e["name"] in ROUND_CHILD_SPANS]
    assert children, "no round-child spans recorded"
    for c in children:
        inside = [
            (lo, hi) for lo, hi in intervals
            if lo - 1.0 <= c["ts"] and c["ts"] + c.get("dur", 0) <= hi + 1.0
        ]
        assert len(inside) == 1, (
            f"{c['name']} at ts={c['ts']} nests in {len(inside)} fed_round "
            f"intervals (want exactly 1)"
        )
    # distinct span names for the device/host correlation story
    assert len({e["name"] for e in evs}) >= 4


def test_round_spans_nest_host_driven(tmp_path, fresh_obs):
    reg, _ = fresh_obs
    cfg = _run_trainer(tmp_path, "host")
    evs = _trace_events(cfg)
    # one fed_round per round, each wrapping its own children
    _assert_children_nest(evs, expect_chunks=[(0, 1), (1, 1)])
    # the param_avg sync span shows up inside a round
    assert any(e["name"] == "aggregate" for e in evs)
    # one h2d and one dispatch span a step, with the attributes the
    # benchmark's per-step metrics read: the step's kind and count, and the
    # rows it encodes for the slots its batch gathers
    dispatches = [e for e in evs if e["name"] == "dispatch"]
    h2ds = [e for e in evs if e["name"] == "h2d"]
    steps = reg.counter("train.steps_total").value()
    assert len(dispatches) == len(h2ds) == steps > 0
    assert all(e["args"]["n"] == 1 for e in h2ds)
    slots = cfg.data.batch_size * (1 + cfg.data.npratio + cfg.data.max_his_len)
    for e in dispatches:
        assert e["args"]["kind"] == "step" and e["args"]["n"] == 1
        assert 0 < e["args"]["rows"] <= e["args"]["slots"] == slots
    assert reg.counter("train.rounds_total").value() == 2
    assert reg.get("train.round_seconds").cell()["count"] == 2


def test_epsilon_spent_gauge_tracks_rounds(tmp_path, fresh_obs):
    reg, _ = fresh_obs
    cfg = _run_trainer(tmp_path, "dp", privacy=True, prefetch=2)
    # the gauge holds the final round's spend
    eps_final = reg.gauge("privacy.epsilon_spent").value()
    assert eps_final is not None and eps_final > 0

    # per-round records carry the trajectory next to loss/AUC, increasing
    records = [
        json.loads(l) for l in open(f"{cfg.obs.dir}/metrics.jsonl")
        if '"registry_snapshot"' not in l
    ]
    traj = [r["privacy.epsilon_spent"] for r in records
            if "privacy.epsilon_spent" in r]
    assert len(traj) == 2 and traj[0] < traj[1]
    assert traj[1] == pytest.approx(eps_final, rel=1e-4)
    # prefetch health made it into the registry too
    assert reg.counter("data.prefetch.items_total").value() > 0

    # ...and the rendered report surfaces all of it
    from fedrec_tpu.obs import build_report, load_jsonl, load_trace, render_text

    recs, snaps = load_jsonl(f"{cfg.obs.dir}/metrics.jsonl")
    report = build_report(recs, snaps, load_trace(f"{cfg.obs.dir}/trace.json"))
    assert report["privacy"]["epsilon_spent"] == pytest.approx(eps_final, rel=1e-4)
    assert "prefetch" in report and "spans" in report
    text = render_text(report)
    assert "privacy.epsilon_spent" in text and "fed_round" in text

    # the final prometheus exposition names the gauge (dotted + sanitized)
    prom = open(f"{cfg.obs.dir}/prometheus.txt").read()
    assert "privacy.epsilon_spent" in prom and "privacy_epsilon_spent" in prom


def test_artifacts_written_when_training_dies(tmp_path, fresh_obs):
    """A run that aborts mid-round (a dispatch error) still leaves the obs
    artifact trio — the failed run is exactly the one whose telemetry is
    needed, and the steps it did dispatch must be in the dumped snapshot."""
    reg, _ = fresh_obs
    cfg = small_cfg()
    cfg.model.text_encoder_mode = "head"
    cfg.fed.strategy = "param_avg"
    cfg.fed.rounds = 1
    cfg.train.snapshot_dir = str(tmp_path / "snap")
    cfg.train.eval_every = 1000
    cfg.obs.dir = str(tmp_path / "obs")
    data, _, token_states, _, _, _ = make_setup(cfg, num_train=64, seed=0)
    t = Trainer(cfg, data, np.asarray(token_states))

    def device_lost(state, batch, table):
        raise RuntimeError("device lost")

    t.train_step = device_lost  # the round's one step raises at dispatch
    with pytest.raises(RuntimeError, match="device lost"):
        t.run()
    for f in ("metrics.jsonl", "trace.json", "prometheus.txt"):
        assert (tmp_path / "obs" / f).exists(), f"missing {f} after abort"
    # the dumped exposition carries the aborted round's evidence
    prom = (tmp_path / "obs" / "prometheus.txt").read_text()
    assert "train_steps_total" in prom and "train_encode_rows" in prom
    assert reg.counter("train.steps_total").value() == 1


def test_no_trace_capacity_blowup_config_roundtrip():
    """ObsConfig rides the config tree: overrides + to/from dict."""
    from fedrec_tpu.config import ExperimentConfig

    cfg = ExperimentConfig()
    cfg.apply_overrides(["obs.dir=/tmp/x", "obs.snapshot_every=5",
                         "obs.trace_capacity=1000"])
    d = cfg.to_dict()
    assert d["obs"]["dir"] == "/tmp/x"
    cfg2 = ExperimentConfig.from_dict(d)
    assert cfg2.obs.snapshot_every == 5 and cfg2.obs.trace_capacity == 1000
    with pytest.raises(KeyError):
        cfg.apply_overrides(["obs.nope=1"])
