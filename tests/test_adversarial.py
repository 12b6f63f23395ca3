"""Adversarial composition: the pieces a real pod run combines at once.

VERDICT r2 item 5: 4 processes x int8 DCN compression x FedAdam x
sample-weighted disjoint shards x one killed peer x resume-from-snapshot.
Each piece is unit-tested elsewhere; THIS file tests the composition —
matching the reference's round loop (``server.py:72-105``) under the
failure story its report admits it cannot survive (Final_Report VII.a).
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from fedrec_tpu.hostenv import cpu_host_env

REPO = str(Path(__file__).resolve().parents[1])

pytestmark = pytest.mark.slow  # multi-process CLI drives

N_PROC = 4

WORKER = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    port, pid, snap, rounds, die_at = (
        sys.argv[1], int(sys.argv[2]), sys.argv[3], sys.argv[4],
        int(sys.argv[5]),
    )
    save_every = sys.argv[6] if len(sys.argv) > 6 else "1"
    if die_at >= 0:
        # deterministic mid-round crash: this peer dies INSIDE round
        # `die_at`'s local training, before its aggregate contribution
        from fedrec_tpu.train import trainer as trainer_mod

        _orig = trainer_mod.Trainer.train_round

        def dying(self, round_idx):
            if round_idx >= die_at:
                print("PEER_DYING", flush=True)
                os._exit(1)
            return _orig(self, round_idx)

        trainer_mod.Trainer.train_round = dying
    from fedrec_tpu.cli.coordinator import main
    sys.exit(main([
        rounds, "8", save_every,
        "--coordinator", f"127.0.0.1:{port}",
        "--num-processes", "4", "--process-id", str(pid),
        "--synthetic", "--synthetic-train", "640", "--synthetic-news", "128",
        "--clients", "1", "--server-trains",
        "--collective-timeout", "20",
        "--set", "model.bert_hidden=48", "--set", "data.max_his_len=10",
        "--set", "data.max_title_len=12", "--set", "model.news_dim=32",
        "--set", "model.num_heads=4", "--set", "model.head_dim=8",
        "--set", "model.query_dim=16", "--set", f"train.snapshot_dir={snap}",
        "--set", "fed.dcn_compress=int8", "--set", "fed.server_opt=adam",
        "--set", "fed.server_lr=0.05", "--set", "fed.weight_by_samples=true",
        "--set", "train.eval_every=1000",  # loss is the tracked signal here
        # tiny shards + few rounds: the reference lr 5e-5 only wobbles;
        # a visible descent is the signal under test
        "--set", "optim.user_lr=0.001", "--set", "optim.news_lr=0.001",
    ]))
    """
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch(tmp_path, dirs, rounds: int, die_pid: int = -1, die_at: int = -1,
            save_every: int = 1):
    port = _free_port()
    script = tmp_path / "adversarial_worker.py"
    script.write_text(WORKER)
    env = cpu_host_env()
    env.pop("XLA_FLAGS", None)  # 1 device/process
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(port), str(pid), str(dirs[pid]),
             str(rounds), str(die_at if pid == die_pid else -1),
             str(save_every)],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for pid in range(N_PROC)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("adversarial run wedged")
        outs.append(out)
    return procs, outs


def _round_losses(out: str) -> list[float]:
    losses = []
    for line in out.splitlines():
        if '"training_loss"' in line:
            try:
                losses.append(float(json.loads(line)["training_loss"]))
            except (json.JSONDecodeError, KeyError, TypeError):
                continue
    return losses


def test_adversarial_resume_bit_identical(tmp_path):
    """4 processes x int8 x FedAdam x weighted disjoint shards: a straight
    2-round run and a 1-round-then-resumed run produce BIT-identical
    global models (client state + FedAdam sidecar both restored through
    the delta-quantized aggregation)."""
    a_dirs = [tmp_path / f"a{i}" for i in range(N_PROC)]
    procs, outs = _launch(tmp_path, a_dirs, rounds=2)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"A proc {pid} failed:\n{out[-3000:]}"
        assert "done after 2 rounds" in out
        assert f"data shard {pid + 1}/4" in out  # disjoint shards engaged
    a_global = (a_dirs[0] / "global_round_1.msgpack").read_bytes()
    assert (a_dirs[0] / "server_opt_state.msgpack").exists()  # FedAdam sidecar
    assert not (a_dirs[1] / "server_opt_state.msgpack").exists()  # hub-only

    b_dirs = [tmp_path / f"b{i}" for i in range(N_PROC)]
    procs, outs = _launch(tmp_path, b_dirs, rounds=1)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"B1 proc {pid} failed:\n{out[-3000:]}"
    procs, outs = _launch(tmp_path, b_dirs, rounds=2)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"B2 proc {pid} failed:\n{out[-3000:]}"
    assert any("resumed local state at round 0" in o for o in outs)
    b_global = (b_dirs[0] / "global_round_1.msgpack").read_bytes()
    assert a_global == b_global  # bit-identical through int8 + FedAdam


def test_adversarial_kill_survivors_progress(tmp_path):
    """Same 4-process composition; process 3 dies INSIDE round 1's local
    training. Every survivor degrades instead of hanging and its
    per-round training loss decreases across the >=3 standalone rounds
    it completes — the failure story the reference's report concedes
    kills its whole job (Final_Report VII.a)."""
    c_dirs = [tmp_path / f"c{i}" for i in range(N_PROC)]
    procs, outs = _launch(tmp_path, c_dirs, rounds=4, die_pid=3, die_at=1)
    assert procs[3].returncode == 1 and "PEER_DYING" in outs[3]
    for pid in range(3):
        out = outs[pid]
        assert procs[pid].returncode == 0, f"C proc {pid} failed:\n{out[-3000:]}"
        assert "degrading to standalone" in out
        assert "done after 4 rounds" in out
        if pid != 0:
            # degraded CLIENTS leave the doomed runtime: snapshot + exec a
            # standalone continuation (the server finishes in-process)
            assert "respawning standalone" in out
            assert "resumed local state" in out
        losses = _round_losses(out)
        assert len(losses) >= 4, f"survivor {pid} logged {len(losses)} rounds"
        # loss decreases across the standalone rounds (and overall)
        assert losses[-1] < losses[0], (pid, losses)
        assert losses[-1] < losses[1], (pid, losses)


def test_adversarial_kill_before_first_snapshot(tmp_path):
    """Respawn's from-scratch branch: with save_every beyond the crash
    round NO local snapshot exists when the world breaks — the degraded
    client must still leave the runtime and redo its shard's rounds
    standalone from initialization."""
    d_dirs = [tmp_path / f"d{i}" for i in range(N_PROC)]
    procs, outs = _launch(
        tmp_path, d_dirs, rounds=3, die_pid=3, die_at=1, save_every=5
    )
    assert procs[3].returncode == 1 and "PEER_DYING" in outs[3]
    for pid in range(3):
        out = outs[pid]
        assert procs[pid].returncode == 0, f"D proc {pid} failed:\n{out[-3000:]}"
        assert "done after 3 rounds" in out
    for pid in (1, 2):
        assert "respawning standalone, resuming from scratch" in outs[pid]
        assert "resumed local state" not in outs[pid]
        # from-scratch redo: rounds 0..2 all retrained standalone
        losses = _round_losses(outs[pid])
        assert len(losses) >= 3, f"survivor {pid} logged {len(losses)} rounds"
        assert losses[-1] < losses[0], (pid, losses)
