"""Coordinator supervisor: a killed peer respawns and the run finishes.

``fedrec-coordinator --supervise`` wraps the worker in an auto-respawn
loop; when one of 4 peers dies mid-run (here: the deterministic
``chaos.kill_round``/``chaos.kill_process`` host fault — an ``os._exit``
at round entry, exactly a crash), every survivor's watchdog degrades it,
all workers exit with the retryable status, and the supervisors relaunch
the world, which re-rendezvouses and resumes from local snapshots.
test_elastic proves the manual stop-the-world restart works; THIS file
proves no operator has to perform it (ISSUE 5 satellite).
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from fedrec_tpu.hostenv import cpu_host_env

REPO = str(Path(__file__).resolve().parents[1])

pytestmark = pytest.mark.slow  # multi-process CLI drive with respawns

SUPERVISED_WORKER = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    port, nproc, pid, snap, rounds = (
        sys.argv[1], sys.argv[2], int(sys.argv[3]), sys.argv[4], sys.argv[5]
    )
    from fedrec_tpu.cli.coordinator import main
    sys.exit(main([
        rounds, "8", "1",
        "--supervise",
        "--coordinator", f"127.0.0.1:{port}",
        "--num-processes", nproc, "--process-id", str(pid),
        "--synthetic", "--synthetic-train", "320", "--synthetic-news", "64",
        "--clients", "1", "--server-trains",
        "--collective-timeout", "20",
        "--set", "model.bert_hidden=48", "--set", "data.max_his_len=10",
        "--set", "data.max_title_len=12", "--set", "model.news_dim=32",
        "--set", "model.num_heads=4", "--set", "model.head_dim=8",
        "--set", "model.query_dim=16", "--set", f"train.snapshot_dir={snap}",
        "--set", "train.eval_every=1000",
        "--set", "chaos.enabled=true",
        "--set", "chaos.kill_round=2", "--set", "chaos.kill_process=2",
    ]))
    """
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _logged_rounds(out: str) -> set[int]:
    rounds = set()
    for line in out.splitlines():
        if '"training_loss"' in line:
            try:
                rounds.add(int(json.loads(line)["round"]))
            except (json.JSONDecodeError, KeyError, TypeError, ValueError):
                continue
    return rounds


def test_supervisor_survives_peer_kill(tmp_path):
    rounds = 5
    port = _free_port()
    script = tmp_path / "supervised_worker.py"
    script.write_text(SUPERVISED_WORKER)
    env = cpu_host_env()
    env.pop("XLA_FLAGS", None)  # 1 device/process
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["FEDREC_SUPERVISE_MAX"] = "12"
    dirs = [tmp_path / f"d{i}" for i in range(4)]
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(port), "4", str(pid),
             str(dirs[pid]), str(rounds)],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for pid in range(4)
    ]
    outs = []
    for pid, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=540)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"supervised world wedged (process {pid})")
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"supervisor {pid} failed:\n{out[-4000:]}"

    # the chaos kill actually fired, and the supervisor respawned the world
    assert "dying at round 2" in outs[2], outs[2][-2000:]
    assert any("respawn" in o for o in outs), "no supervisor ever respawned"
    # marker guard: p2 died exactly once
    assert outs[2].count("dying at round 2") == 1
    assert (dirs[2] / "chaos_killed_p2").exists()

    # the run FINISHED: the server's log covers every round, including the
    # ones after the kill (re-trained by the relaunched world)
    server_rounds = _logged_rounds(outs[0])
    assert {0, 1, rounds - 1} <= server_rounds, sorted(server_rounds)
    # the killed peer rejoined and trained post-kill rounds too
    assert (rounds - 1) in _logged_rounds(outs[2]), sorted(
        _logged_rounds(outs[2])
    )
