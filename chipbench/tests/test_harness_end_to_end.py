"""The harness end to end at a tiny size, on the CPU path of the TEST only
(``need_tpu=False``; the command itself refuses any device but a TPU), with a
throw-away configuration, traffic mix and cell added in a temporary
directory the way a later PR adds them: files and entries, no edit.

Also the faults a training cell can have, each planted under the harness in
the timed path: ``correct`` has to come out false."""

import json
import shutil
import subprocess
import sys
import time

import pytest

from chipbench import harness_training_rounds as harness
from conftest import ROOT, write_tiny_benchmark

# tiny-size limits, set the way the cells' limits are set (PERF.md): above
# what sound runs of the tiny cell read on seeds 1-12 (loss up to 8.1e-4,
# grad up to 1.8e-2, median leaf's change up to 1.7e-3) and below what the
# float8 control reads on seeds 21-23 (5.2e-3, 4.8e-2, 7.8e-3) and the faults
# read (test_control.py)
TINY_LIMITS = {
    "loss_gap": 2.5e-3, "grad_gap": 3.2e-2, "delta_gap_median": 3.7e-3, "sync_gap": 1e-5,
    "bad_batch_rows": 0, "rounds_failed": 0, "nonfinite_losses": 0, "compiled_in_window": 0,
}


def run_tiny(root, seed=3, clients=2, seconds=0.5):
    limits = dict(TINY_LIMITS)
    if clients == 1:
        limits.pop("sync_gap")
    workload = write_tiny_benchmark(root, limits, clients)
    return harness.run_cell(root, workload, seed, seconds, False, time.perf_counter(),
                            need_tpu=False, bench_dir=root / "chipbench")


def test_added_cell_runs_and_is_correct(tmp_path, capsys):
    line = run_tiny(tmp_path, seed=2**31 + 11)         # the driver's seeds are large
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert line["metrics"]["train_samples_per_s"]["unit"] == "samples/s"
    assert line["device"]["platform"] == "cpu"          # and so never a device metric
    assert list(line)[-1] == "compared"
    harness.print_result(line)
    out = capsys.readouterr()
    last = json.loads(out.out.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert "compared delta_gap_median" in out.err and "limit" in out.err.strip().splitlines()[-1]


def test_same_seed_same_inputs_and_single_worker_cell(tmp_path):
    a = run_tiny(tmp_path, seed=5, clients=1)
    b = run_tiny(tmp_path, seed=5, clients=1)
    assert a["correct"] and "sync_gap" not in a["compared"]
    assert a["compared"]["loss_gap"] == b["compared"]["loss_gap"]


def _plant(monkeypatch, fault):
    """Break the timed path under the harness: wrap what the trainer compiled."""
    import jax
    import jax.numpy as jnp

    build = harness.build_trainer

    def broken(cfg, data, table):
        trainer = build(cfg, data, table)
        step, sync = trainer.train_step, trainer.param_sync
        if fault == "state_unchanged":
            def bad_step(state, batch, tbl):
                kept = jax.tree_util.tree_map(jnp.copy, state)
                _, metrics = step(state, batch, tbl)
                return kept, metrics
            trainer.train_step = bad_step
        elif fault == "half_batch":
            def bad_step(state, batch, tbl):
                half = batch["labels"].shape[1] // 2
                fold = lambda x: jnp.concatenate([x[:, :half], x[:, :half]], axis=1)  # noqa: E731
                return step(state, {k: fold(v) for k, v in batch.items()}, tbl)
            trainer.train_step = bad_step
        elif fault == "no_sync":
            trainer.param_sync = lambda state, *rest: state
        return trainer

    monkeypatch.setattr(harness, "build_trainer", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "no_sync"])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    _plant(monkeypatch, fault)
    line = run_tiny(tmp_path, seed=4)
    assert line["correct"] is False
    over = {k for k, c in line["compared"].items() if not c["value"] <= c["limit"]}
    assert over, line["compared"]
    if fault == "no_sync":
        assert over == {"sync_gap"}
    if fault == "state_unchanged":
        assert line["compared"]["delta_gap_median"]["value"] == pytest.approx(1.0)


def test_command_refuses_a_cpu_and_a_bare_directory(tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin:/usr/local/bin"}
    args = ["--workload", "fed8.b64", "--seed", "1", "--seconds", "1", "--trace", "0"]
    run = subprocess.run([sys.executable, str(ROOT / "chipbench" / "run.py"), *args],
                         capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert run.returncode != 0 and run.stdout.strip() == "" and "needs a TPU" in run.stderr
    bare = tmp_path / "bare"
    shutil.copytree(ROOT / "chipbench", bare / "chipbench")
    shutil.copy(ROOT / "BENCHMARK.json", bare)
    run = subprocess.run([sys.executable, "chipbench/run.py", *args],
                         capture_output=True, text=True, env=env, cwd=bare, timeout=300)
    assert run.returncode != 0 and run.stdout.strip() == ""
