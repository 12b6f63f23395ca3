"""Hierarchical federation: coordinator deployment x in-host client cohorts.

Two REAL processes (1 CPU device each) each train a 4-client in-host
federation via cohorts (k=4 on the single device) and aggregate cross-host
through the coordinator runtime — 2 hosts x 4 clients = an 8-way federation
on 2 devices. The reference needs one rank per client (torchrun, reference
``README.md:27-46``); this is the oversubscribed deployment shape a real pod
slice runs.
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

pytestmark = pytest.mark.slow  # multi-process CLI drive

WORKER = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    port, nproc, pid, snap = sys.argv[1], sys.argv[2], int(sys.argv[3]), sys.argv[4]
    from fedrec_tpu.cli.coordinator import main
    rc = main([
        "3", "8", "1",
        "--coordinator", f"127.0.0.1:{port}",
        "--num-processes", nproc, "--process-id", str(pid),
        "--synthetic", "--synthetic-train", "640", "--synthetic-news", "128",
        "--clients", "4", "--server-trains",
        "--collective-timeout", "60",
        "--set", "model.bert_hidden=48", "--set", "data.max_his_len=10",
        "--set", "data.max_title_len=12", "--set", "model.news_dim=32",
        "--set", "model.num_heads=4", "--set", "model.head_dim=8",
        "--set", "model.query_dim=16", "--set", f"train.snapshot_dir={snap}",
        "--set", "fed.weight_by_samples=true",
        "--set", "train.eval_every=1000",
        "--set", "optim.user_lr=0.001", "--set", "optim.news_lr=0.001",
    ])
    # prove the in-host federation really has 4 cohort clients on 1 device
    import jax
    from fedrec_tpu.parallel import client_mesh
    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.train.step import clients_per_device
    cfg = ExperimentConfig(); cfg.fed.num_clients = 4
    k = clients_per_device(cfg, client_mesh(4))
    print(f"COHORT_K {pid} {k} devices {len(jax.local_devices())}")
    sys.exit(rc)
    """
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_coordinator_with_in_host_cohorts(tmp_path):
    port = _free_port()
    script = tmp_path / "cohort_worker.py"
    script.write_text(WORKER)
    env = cpu_host_env(n_devices=1)  # 1 device/process -> in-host k must be 4
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(port), "2", str(pid),
             str(tmp_path / f"snap_{pid}")],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for pid in range(2)
    ]
    outs = []
    for pid, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("cohort coordinator world wedged")
        assert p.returncode == 0, f"process {pid} failed:\n{out[-3000:]}"
        assert f"COHORT_K {pid} 4 devices 1" in out
        outs.append(out)

    # every host completes all rounds with decreasing training loss
    for pid, out in enumerate(outs):
        recs = []
        for line in out.splitlines():
            if '"training_loss"' in line:
                try:
                    r = json.loads(line)
                    recs.append((int(r["round"]), float(r["training_loss"])))
                except (json.JSONDecodeError, KeyError, ValueError, TypeError):
                    continue
        rounds = [r for r, _ in recs]
        assert rounds == sorted(rounds) and len(recs) >= 3, (
            f"process {pid} logged rounds {rounds}"
        )
        assert recs[-1][1] < recs[0][1], f"process {pid} loss did not decrease"
