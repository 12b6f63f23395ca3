"""Flight-recorder end-to-end: a forced-NaN Trainer run aborts via the
numeric sentry, leaves a complete ``flightrec/`` dump (batch + state +
manifest + registry snapshot), an exception abort dumps too, and ``fedrec-obs replay``
deterministically reproduces the non-finite step from the dump on CPU."""

from __future__ import annotations

import json

import numpy as np
import pytest

from fedrec_tpu.obs import (
    MetricsRegistry,
    Tracer,
    TrainingHealthError,
    set_registry,
    set_tracer,
)
from fedrec_tpu.train.trainer import Trainer

from test_train import make_setup, small_cfg

DUMP_FILES = ("manifest.json", "state.msgpack", "registry.json",
              "table.npy", "batch_000.npz")


@pytest.fixture()
def fresh_obs():
    reg, tr = MetricsRegistry(), Tracer()
    old_reg, old_tr = set_registry(reg), set_tracer(tr)
    try:
        yield reg, tr
    finally:
        set_registry(old_reg)
        set_tracer(old_tr)


def _nan_cfg(tmp_path, tag):
    cfg = small_cfg()
    cfg.model.text_encoder_mode = "head"  # joint mode
    cfg.fed.strategy = "param_avg"
    cfg.fed.rounds = 2
    cfg.optim.user_lr = float("inf")  # first update goes non-finite
    cfg.train.snapshot_dir = str(tmp_path / f"snap_{tag}")
    cfg.train.save_every = 1000
    cfg.train.eval_every = 1000
    cfg.obs.dir = str(tmp_path / f"obs_{tag}")
    return cfg


def _run_expect_abort(cfg):
    data, _, token_states, _, _, _ = make_setup(cfg, num_train=128, seed=0)
    t = Trainer(cfg, data, np.asarray(token_states))
    with pytest.raises(TrainingHealthError, match="nonfinite"):
        t.run()
    return t


def _assert_dump_complete(obs_dir):
    fr = obs_dir / "flightrec"
    for f in DUMP_FILES:
        assert (fr / f).exists(), f"missing flightrec/{f}"
    man = json.loads((fr / "manifest.json").read_text())
    assert man["kind"] == "flight_recorder_dump"
    assert man["trigger"]["kind"] == "nonfinite"
    assert man["offending"] is not None
    assert man["config"]["optim"]["user_lr"] == float("inf")
    return man


def test_host_driven_nan_dumps_and_replays(tmp_path, fresh_obs, capsys):
    reg, _ = fresh_obs
    cfg = _nan_cfg(tmp_path, "host")
    _run_expect_abort(cfg)
    man = _assert_dump_complete(tmp_path / "obs_host")
    assert man["trigger"]["round"] == 0 and man["trigger"]["step"] == 0
    # the round recorded its weights for replay's round-end sync, under
    # the keys fedrec-obs replay reads
    assert man["chunk_start_round"] == 0
    assert man["weights"] == {"0": [1.0] * cfg.fed.num_clients}
    assert reg.counter("health.nonfinite_steps_total").value() > 0
    # the obs artifact trio was also written by the failing exit path
    for f in ("metrics.jsonl", "trace.json", "prometheus.txt"):
        assert (tmp_path / "obs_host" / f).exists()

    # ---- replay: CPU re-execution reproduces the flag (exit 0)
    from fedrec_tpu.cli.obs import main as obs_main

    assert obs_main(["replay", str(tmp_path / "obs_host")]) == 0
    capsys.readouterr()  # drain earlier output before capturing the verdict
    assert obs_main(
        ["replay", str(tmp_path / "obs_host" / "flightrec"), "--json"]
    ) == 0
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["reproduced_nonfinite"] is True
    assert verdict["first_nonfinite"]["round"] == man["trigger"]["round"]
    assert verdict["first_nonfinite"]["step"] == man["trigger"]["step"]


def _device_lost(state, batch, table):
    raise RuntimeError("device lost")


def test_exception_abort_still_dumps(tmp_path, fresh_obs):
    """A mid-round abort that never reaches the health check (a dispatch
    error) dumps the ring + round-entry state with kind=exception."""
    cfg = small_cfg()
    cfg.model.text_encoder_mode = "head"
    cfg.fed.strategy = "param_avg"
    cfg.fed.rounds = 1
    cfg.train.snapshot_dir = str(tmp_path / "snap")
    cfg.train.eval_every = 1000
    cfg.obs.dir = str(tmp_path / "obs")
    data, _, token_states, _, _, _ = make_setup(cfg, num_train=64, seed=0)
    t = Trainer(cfg, data, np.asarray(token_states))
    t.train_step = _device_lost  # the round's one step raises at dispatch
    with pytest.raises(RuntimeError, match="device lost"):
        t.run()
    man = json.loads(
        (tmp_path / "obs" / "flightrec" / "manifest.json").read_text()
    )
    assert man["trigger"]["kind"] == "exception"
    assert man["trigger"]["error"] == "RuntimeError"
    assert man["records"] and man["state_file"] == "state.msgpack"


def test_healthy_run_no_dump_and_zero_recompiles(tmp_path, fresh_obs):
    """The steady-shape trainer path: no dump, finite health instruments
    published, exactly one train_step compile signature and ZERO
    recompiles after warmup (the acceptance pin for the watchdog)."""
    reg, _ = fresh_obs
    cfg = _nan_cfg(tmp_path, "ok")
    cfg.optim.user_lr = 3e-3  # healthy
    data, _, token_states, _, _, _ = make_setup(cfg, num_train=128, seed=0)
    t = Trainer(cfg, data, np.asarray(token_states))
    t.run()
    assert not (tmp_path / "obs_ok" / "flightrec").exists()
    assert reg.counter("health.nonfinite_steps_total").value() == 0
    assert reg.get("health.update_norm").cell()["count"] > 0
    compiles = reg.counter("xla.compiles_total", labels=("fn",))
    recompiles = reg.counter("xla.recompiles_total", labels=("fn",))
    assert compiles.value(fn="train_step") == 1  # one signature, one warmup
    assert recompiles.value(fn="train_step") == 0
    assert recompiles.value(fn="param_sync") == 0
