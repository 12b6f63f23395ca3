"""Elastic world resize across resume: stop a coordinator deployment, add a
host, resume — training continues.

The reference's torchrun c10d rendezvous nominally supports elasticity but
no restart logic exists (SURVEY section 5.3; reference ``client.py:227``
just sets a 2-day timeout). Here elasticity falls out of the deployment
design rather than special-case code, and THIS file is the proof:

* the server's disk state is the only essential store — its local snapshot
  holds the global model and the round counter;
* every round starts with a counter negotiation (clients adopt the server's
  round, ``CoordinatorRuntime.start_round``) and a global fan-out
  (``sync_from_server``), so a brand-new process with random params and
  round 0 is fully integrated one fan-out later;
* data shards are re-dealt from the CURRENT world size at launch
  (``apply_process_sharding``), so growth/shrink rebalances the corpus.
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

ELASTIC_WORKER = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    port, nproc, pid, snap, rounds = (
        sys.argv[1], sys.argv[2], int(sys.argv[3]), sys.argv[4], sys.argv[5]
    )
    from fedrec_tpu.cli.coordinator import main
    sys.exit(main([
        rounds, "8", "1",
        "--coordinator", f"127.0.0.1:{port}",
        "--num-processes", nproc, "--process-id", str(pid),
        "--synthetic", "--synthetic-train", "640", "--synthetic-news", "128",
        "--clients", "1", "--server-trains",
        "--collective-timeout", "60",
        "--set", "model.bert_hidden=48", "--set", "data.max_his_len=10",
        "--set", "data.max_title_len=12", "--set", "model.news_dim=32",
        "--set", "model.num_heads=4", "--set", "model.head_dim=8",
        "--set", "model.query_dim=16", "--set", f"train.snapshot_dir={snap}",
        "--set", "fed.weight_by_samples=true",
        "--set", "train.eval_every=1000",  # loss is the tracked signal
        "--set", "optim.user_lr=0.001", "--set", "optim.news_lr=0.001",
    ]))
    """
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_world(tmp_path, dirs, rounds: int):
    port = _free_port()
    script = tmp_path / "elastic_worker.py"
    script.write_text(ELASTIC_WORKER)
    env = cpu_host_env()
    env.pop("XLA_FLAGS", None)  # 1 device/process
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(port), str(len(dirs)), str(pid),
             str(dirs[pid]), str(rounds)],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for pid in range(len(dirs))
    ]
    outs = []
    for pid, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("elastic world wedged")
        assert p.returncode == 0, f"process {pid} failed:\n{out[-3000:]}"
        outs.append(out)
    return outs


def _logged_rounds(out: str) -> list[tuple[int, float]]:
    recs = []
    for line in out.splitlines():
        if '"training_loss"' in line:
            try:
                r = json.loads(line)
                recs.append((int(r["round"]), float(r["training_loss"])))
            except (json.JSONDecodeError, KeyError, TypeError, ValueError):
                continue
    return recs


def _user_params(snap_dir: Path, pid: int):
    from flax import serialization

    raw = serialization.msgpack_restore(
        (snap_dir / f"local_state_p{pid}.msgpack").read_bytes()
    )
    return raw["state"]["user_params"]


def _leaves(tree) -> list[np.ndarray]:
    if isinstance(tree, dict):
        return [a for k in sorted(tree) for a in _leaves(tree[k])]
    return [np.asarray(tree)]


def test_elastic_grow_world_across_resume(tmp_path):
    """2-process deployment for rounds 0-2, then resumed as a 3-process
    world for rounds 3-5: the newcomer adopts the server's round counter and
    global model, shards re-deal 3-way, and learning continues."""
    dirs = [tmp_path / f"d{i}" for i in range(3)]

    outs1 = _run_world(tmp_path, dirs[:2], rounds=3)
    phase1 = [_logged_rounds(o) for o in outs1]
    assert [r for r, _ in phase1[0]] == [0, 1, 2]
    # 2-way shard deal in phase 1
    assert "data shard 1/2" in outs1[0] and "data shard 2/2" in outs1[1]

    outs2 = _run_world(tmp_path, dirs, rounds=6)
    phase2 = [_logged_rounds(o) for o in outs2]

    # every process — including the brand-new p2 with no snapshot — runs
    # exactly rounds 3..5: the stale/zero local counters adopted the server's
    for pid in range(3):
        assert [r for r, _ in phase2[pid]] == [3, 4, 5], outs2[pid][-2000:]

    # shards re-dealt across the NEW world, covering the corpus exactly
    counts = []
    for pid in range(3):
        assert f"data shard {pid + 1}/3" in outs2[pid]
        for line in outs2[pid].splitlines():
            if "data shard" in line:
                counts.append(int(line.rsplit(":", 1)[1].split()[0]))
    assert sorted(counts) == [213, 213, 214]  # 640 dealt 3 ways

    # learning carried over: the resumed world's first round starts from the
    # phase-1 global, not from scratch (fresh-init loss ~= ln(5) with the
    # positive at slot 0 of 5 candidates)
    assert phase2[0][0][1] < phase1[0][0][1]

    # the newcomer holds the SAME synced global as the veterans at the end
    # (param_avg syncs every round; local snapshots saved at round 5)
    p0, p1, p2 = (_leaves(_user_params(dirs[i], i)) for i in range(3))
    assert len(p0) == len(p2) > 0
    for a, b, c in zip(p0, p1, p2):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)

    # SHRINK: resume the 3-process world as 2 processes for rounds 6-8.
    # The removed host's snapshot (d2) simply lingers unused; the veterans'
    # shards re-deal 2-way over state trained on 3-way shards.
    outs3 = _run_world(tmp_path, dirs[:2], rounds=9)
    phase3 = [_logged_rounds(o) for o in outs3]
    for pid in range(2):
        assert [r for r, _ in phase3[pid]] == [6, 7, 8], outs3[pid][-2000:]
    counts3 = [
        int(line.rsplit(":", 1)[1].split()[0])
        for out in outs3 for line in out.splitlines() if "data shard" in line
    ]
    assert "data shard 1/2" in outs3[0] and "data shard 2/2" in outs3[1]
    assert sorted(counts3) == [320, 320]
    q0, q1 = (_leaves(_user_params(dirs[i], i)) for i in range(2))
    for a, b in zip(q0, q1):
        np.testing.assert_array_equal(a, b)
    # and the shrunk world kept learning from the grown world's global
    assert phase3[0][0][1] < phase2[0][0][1]
