"""End-to-end tests for the `fedrec_tpu.cli.run` driver.

The reference's entry scripts take bare positional argv under torchrun
(reference ``main.py:178-184``: epochs, batch, save_every); this driver is
their single console surface. These tests exercise it the way an operator
would — as a subprocess on a fake CPU mesh — covering both the synthetic
corpus path and the reference ``UserData/`` artifact layout.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from fedrec_tpu.hostenv import cpu_host_env

REPO = str(Path(__file__).resolve().parents[1])

# every test here drives full CLI subprocesses — minutes, not seconds
pytestmark = pytest.mark.slow


def _run_cli(args: list[str], tmp_path, timeout: int = 300) -> str:
    env = cpu_host_env(2)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "fedrec_tpu.cli.run", *args],
        env=env, cwd=tmp_path,
        capture_output=True, text=True, timeout=timeout,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, f"cli.run failed:\n{out[-3000:]}"
    return out


def _reference_states(tmp_path) -> str:
    """Token states for the demo shard's 225 news: fedrec-run trains on
    random states only under --synthetic."""
    import numpy as np

    path = tmp_path / "token_states.npy"
    np.save(path, np.random.default_rng(0).standard_normal(
        (225, 50, 32), dtype=np.float32))
    return str(path)


def test_run_cli_synthetic_param_avg(tmp_path):
    """Two rounds of 2-client FedAvg on the synthetic corpus: exits 0,
    reports final metrics, and leaves a resumable snapshot tree."""
    out = _run_cli(
        ["2", "16", "1", "--strategy", "param_avg", "--clients", "2",
         "--synthetic", "--token-states", str(tmp_path / "no_states.npy"),
         "--set", "data.max_his_len=10",
         "--set", "model.bert_hidden=32", "--set", "model.news_dim=32",
         "--set", "model.num_heads=4", "--set", "model.head_dim=8",
         "--set", "model.query_dim=16"],
        tmp_path,
    )
    assert "final:" in out and "auc=" in out
    assert (tmp_path / "snapshots").exists()


def test_run_cli_reference_artifacts(tmp_path):
    """The reference demo shard (``/root/reference/UserData``: 225 news,
    4 train / 1 valid samples — SURVEY §2.1 'Shipped data sample') loads and
    trains through the same driver, given token states for its 225 news."""
    shard = "/root/reference/UserData"
    if not os.path.isdir(shard):
        pytest.skip("reference demo shard not present")
    out = _run_cli(
        ["1", "4", "1", "--strategy", "grad_avg", "--clients", "1",
         "--data-dir", shard, "--token-states", _reference_states(tmp_path),
         "--set", "model.bert_hidden=32", "--set", "model.news_dim=32",
         "--set", "model.num_heads=4", "--set", "model.head_dim=8",
         "--set", "model.query_dim=16", "--set", "data.max_his_len=10"],
        tmp_path,
    )
    assert "final:" in out


def test_recommend_cli_after_training(tmp_path):
    """Train -> serve round trip on the reference demo shard: the recommend
    driver restores the snapshot the run driver wrote and emits valid
    JSON-lines top-k recommendations for every known user. Training uses a
    2-client mesh while serving runs on a single device — the restore is
    template-free, so the snapshot's client dim must not matter."""
    shard = "/root/reference/UserData"
    if not os.path.isdir(shard):
        pytest.skip("reference demo shard not present")
    common = ["--set", "model.bert_hidden=32", "--set", "model.news_dim=32",
              "--set", "model.num_heads=4", "--set", "model.head_dim=8",
              "--set", "model.query_dim=16", "--set", "data.max_his_len=10"]
    _run_cli(["1", "2", "1", "--strategy", "param_avg", "--clients", "2",
              "--data-dir", shard,
              "--token-states", _reference_states(tmp_path), *common],
             tmp_path)
    assert (tmp_path / "snapshots").exists()

    env = cpu_host_env()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out_path = tmp_path / "recs.jsonl"
    # without --allow-random-states a missing token_states.npy is a HARD
    # error: random trunk states must never silently produce shippable
    # JSONL (ADVICE r2)
    denied = subprocess.run(
        [sys.executable, "-m", "fedrec_tpu.cli.recommend",
         "--data-dir", shard, "--snapshot-dir", str(tmp_path / "snapshots"),
         "--top-k", "5", "--out", str(out_path), *common],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=300,
    )
    assert denied.returncode == 2
    assert "no token states" in denied.stderr

    # serve on an EIGHT-device mesh against the 2-client training snapshot:
    # covers the sharded scorer CLI branch AND the mesh-mismatch regression
    # (restored params must come back as host arrays, not arrays committed
    # to the training run's smaller device set — fedrec_tpu/cli/recommend.py)
    env8 = cpu_host_env(8)
    env8["PYTHONPATH"] = REPO + os.pathsep + env8.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "fedrec_tpu.cli.recommend",
         "--data-dir", shard, "--snapshot-dir", str(tmp_path / "snapshots"),
         "--top-k", "5", "--out", str(out_path), "--allow-random-states",
         *common],
        env=env8, cwd=tmp_path, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # the training run persisted its resolved config; serving must use it
    assert "using training config" in proc.stderr
    assert "sharded over 8 devices" in proc.stderr

    import pickle
    with open(Path(shard) / "bert_nid2index.pkl", "rb") as f:
        nid2index = pickle.load(f)
    lines = [json.loads(ln) for ln in out_path.read_text().splitlines()]
    assert lines, "no recommendations written"
    for rec in lines:
        assert 0 < len(rec["news"]) <= 5
        assert len(rec["news"]) == len(rec["scores"])
        assert all(n in nid2index and nid2index[n] != 0 for n in rec["news"])
        assert rec["scores"] == sorted(rec["scores"], reverse=True)


def test_recommend_cli_from_coordinator_global(tmp_path):
    """The multi-process coordinator persists globals as flax msgpack
    ({user, news, round}, no client dim) rather than orbax; the recommend
    driver must serve from that format too — the distributed-training ->
    serving journey."""
    shard = "/root/reference/UserData"
    if not os.path.isdir(shard):
        pytest.skip("reference demo shard not present")

    import jax
    from flax import serialization

    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.data import load_mind_artifacts
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.train.state import init_client_state

    cfg = ExperimentConfig()
    cfg.apply_overrides([
        "model.bert_hidden=32", "model.news_dim=32", "model.num_heads=4",
        "model.head_dim=8", "model.query_dim=16", "data.max_his_len=10",
    ])
    data = load_mind_artifacts(shard)
    state = init_client_state(
        NewsRecommender(cfg.model), cfg, jax.random.PRNGKey(1),
        data.num_news, data.title_len,
    )
    snap_dir = tmp_path / "snapshots"
    snap_dir.mkdir()
    # two rounds present: the loader must pick the LATEST
    for r in (0, 1):
        blob = serialization.to_bytes(
            {"user": state.user_params, "news": state.news_params, "round": r}
        )
        (snap_dir / f"global_round_{r}.msgpack").write_bytes(blob)

    env = cpu_host_env()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out_path = tmp_path / "recs.jsonl"
    proc = subprocess.run(
        [sys.executable, "-m", "fedrec_tpu.cli.recommend",
         "--data-dir", shard, "--snapshot-dir", str(snap_dir),
         "--top-k", "4", "--out", str(out_path), "--allow-random-states",
         "--set", "model.bert_hidden=32", "--set", "model.news_dim=32",
         "--set", "model.num_heads=4", "--set", "model.head_dim=8",
         "--set", "model.query_dim=16", "--set", "data.max_his_len=10"],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "serving coordinator global round 1" in proc.stderr
    lines = [json.loads(ln) for ln in out_path.read_text().splitlines()]
    assert lines and all(0 < len(r["news"]) <= 4 for r in lines)


def test_run_cli_dp_epsilon(tmp_path):
    """--dp-epsilon wires calibration into the run: sigma is derived from
    (eps, delta) and reported, and training still completes."""
    out = _run_cli(
        ["1", "16", "1", "--strategy", "grad_avg", "--clients", "2",
         "--synthetic", "--token-states", str(tmp_path / "none.npy"),
         "--dp-epsilon", "10",
         "--set", "data.max_his_len=10",
         "--set", "model.bert_hidden=32", "--set", "model.news_dim=32",
         "--set", "model.num_heads=4", "--set", "model.head_dim=8",
         "--set", "model.query_dim=16"],
        tmp_path,
    )
    assert "DP enabled: eps=10" in out and "sigma=" in out
    assert "final:" in out


def test_recommend_cli_round_trip_cnn_head(tmp_path):
    """Train -> serve with the CNN text-head family: the persisted config
    must carry text_head_arch so serving rebuilds the SAME head to encode
    the catalog — a snapshot from one family restored into another is the
    exact failure the resume guard exists for, and the CLI must never hit
    it silently."""
    shard = "/root/reference/UserData"
    if not os.path.isdir(shard):
        pytest.skip("reference demo shard not present")
    common = ["--set", "model.bert_hidden=32", "--set", "model.news_dim=32",
              "--set", "model.num_heads=4", "--set", "model.head_dim=8",
              "--set", "model.query_dim=16", "--set", "data.max_his_len=10",
              "--set", "model.text_head_arch=cnn"]
    _run_cli(["1", "2", "1", "--strategy", "param_avg", "--clients", "2",
              "--data-dir", shard,
              "--token-states", _reference_states(tmp_path), *common],
             tmp_path)

    env = cpu_host_env()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out_path = tmp_path / "recs.jsonl"
    # NOTE: no --set overrides here — serving must pick the cnn arch up
    # from the persisted training config on its own
    proc = subprocess.run(
        [sys.executable, "-m", "fedrec_tpu.cli.recommend",
         "--data-dir", shard, "--snapshot-dir", str(tmp_path / "snapshots"),
         "--top-k", "5", "--out", str(out_path), "--allow-random-states"],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "using training config" in proc.stderr
    lines = [json.loads(ln) for ln in out_path.read_text().splitlines()]
    assert lines, "no recommendations written"
    for rec in lines:
        assert 0 < len(rec["news"]) <= 5
        assert rec["scores"] == sorted(rec["scores"], reverse=True)
