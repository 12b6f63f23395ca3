"""Trainer integration tests: full rounds, resume-from-snapshot equivalence,
and the multi-host coordinator over two real processes (CPU).

Module-marked ``slow``: these are the multi-round / multi-process
integration drives the marker exists for (~12 min on a 1-core CI host —
they alone would blow the tier-1 time budget). Iterate with
``-m 'not slow'``; CI runs everything.
"""

import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from fedrec_tpu.hostenv import cpu_host_env

pytestmark = pytest.mark.slow

REPO = str(Path(__file__).resolve().parents[1])

from fedrec_tpu.config import ExperimentConfig
from fedrec_tpu.data import make_synthetic_mind


def tiny_cfg(tmp_path=None, **over) -> ExperimentConfig:
    cfg = ExperimentConfig()
    cfg.model.news_dim = 32
    cfg.model.num_heads = 4
    cfg.model.head_dim = 8
    cfg.model.query_dim = 16
    cfg.model.bert_hidden = 48
    cfg.data.max_his_len = 10
    cfg.data.max_title_len = 12
    cfg.data.batch_size = 8
    cfg.fed.num_clients = 4
    cfg.fed.rounds = 2
    cfg.train.snapshot_dir = str(tmp_path) if tmp_path else ""
    for k, v in over.items():
        section, key = k.split("__")
        setattr(getattr(cfg, section), key, v)
    return cfg


def tiny_data(cfg):
    rng = np.random.default_rng(0)
    data = make_synthetic_mind(
        num_news=64, num_train=128, num_valid=32,
        title_len=cfg.data.max_title_len,
        his_len_range=(2, cfg.data.max_his_len),
        seed=0, popular_frac=0.2,
    )
    token_states = rng.standard_normal(
        (64, cfg.data.max_title_len, cfg.model.bert_hidden)
    ).astype(np.float32)
    return data, token_states


@pytest.mark.parametrize("strategy,mode", [
    ("param_avg", "joint"),
    ("grad_avg", "joint"),
    ("param_avg", "decoupled"),
])
def test_trainer_runs_rounds(tmp_path, strategy, mode):
    from fedrec_tpu.train.trainer import Trainer

    cfg = tiny_cfg(tmp_path / strategy / mode, fed__strategy=strategy)
    cfg.model.text_encoder_mode = "table" if mode == "decoupled" else "head"
    data, token_states = tiny_data(cfg)
    trainer = Trainer(cfg, data, token_states)
    history = trainer.run()
    assert len(history) == cfg.fed.rounds
    assert all(np.isfinite(h.train_loss) for h in history)
    assert history[-1].val_metrics and 0 <= history[-1].val_metrics["auc"] <= 1


def finetune_cfg(tmp_path, **over) -> ExperimentConfig:
    """Tiny-trunk finetune config (text_encoder_mode='finetune', 1-block
    DistilBERT-shaped trunk) — BASELINE config 5 at test scale."""
    cfg = tiny_cfg(tmp_path, **over)
    cfg.model.text_encoder_mode = "finetune"
    cfg.model.bert_hidden = 32
    cfg.model.trunk_layers = 1
    cfg.model.trunk_heads = 2
    cfg.model.trunk_ffn = 64
    cfg.model.trunk_vocab = 2000
    cfg.fed.num_clients = 2
    return cfg


def finetune_data(cfg):
    return make_synthetic_mind(
        num_news=48, num_train=32, num_valid=8,
        title_len=cfg.data.max_title_len, vocab=2000,
        his_len_range=(2, cfg.data.max_his_len), seed=0,
    )


def test_trainer_finetune_round(tmp_path):
    """In-loop trunk training end-to-end, INCLUDING evaluation (the round-1
    crash: evaluate() read self.token_states, which is None in this mode)."""
    from fedrec_tpu.train.trainer import Trainer

    cfg = finetune_cfg(tmp_path, fed__rounds=2, train__eval_protocol="sampled")
    data = finetune_data(cfg)
    trainer = Trainer(cfg, data, token_states=None)
    history = trainer.run()
    assert len(history) == cfg.fed.rounds
    assert all(np.isfinite(h.train_loss) for h in history)
    m = history[-1].val_metrics
    assert m and np.isfinite(m["loss"]) and 0 <= m["auc"] <= 1
    # the deterministic protocols share the finetune corpus-encode path
    full = trainer.evaluate_full()
    assert 0 <= full["auc"] <= 1


def test_trainer_finetune_resume_bit_identical(tmp_path):
    """Finetune-mode snapshots round-trip the full trunk + opt state."""
    import jax
    from fedrec_tpu.train.trainer import Trainer

    def flat_news(t):
        return np.concatenate(
            [np.ravel(x) for x in jax.tree_util.tree_leaves(t.state.news_params)]
        )

    cfg_a = finetune_cfg(tmp_path / "a", fed__rounds=2, train__save_every=1)
    data = finetune_data(cfg_a)
    t_a = Trainer(cfg_a, data, token_states=None)
    t_a.run()

    cfg_b = finetune_cfg(tmp_path / "b", fed__rounds=1, train__save_every=1)
    Trainer(cfg_b, data, token_states=None).run()
    cfg_b2 = finetune_cfg(tmp_path / "b", fed__rounds=2, train__save_every=1)
    t_b2 = Trainer(cfg_b2, data, token_states=None)
    assert t_b2.start_round == 1
    t_b2.run()
    np.testing.assert_allclose(
        flat_news(t_a), flat_news(t_b2), rtol=1e-6, atol=1e-7
    )


def test_trainer_evaluate_full_matches_bruteforce(tmp_path):
    """evaluate_full == a per-impression host loop over the same table:
    full-pool protocol (published-table parity) and the last-4 slice
    (reference client.py:159-160)."""
    import jax
    from fedrec_tpu.eval import compute_amn
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.train.trainer import Trainer

    cfg = tiny_cfg(tmp_path, fed__rounds=1)
    cfg.model.text_encoder_mode = "head"
    data, token_states = tiny_data(cfg)
    trainer = Trainer(cfg, data, token_states)

    for last_k in (None, 4):
        got = trainer.evaluate_full(last_k=last_k)

        user_params, news_params = trainer._client0_params()
        table = np.asarray(trainer._encode_corpus(news_params))
        ix = trainer.valid_ix
        rows = []
        for i in range(len(ix)):
            lens = int(ix.neg_lens[i])
            negs = ix.neg_pools[i, :lens]
            if last_k is not None:
                negs = negs[-last_k:]
            if len(negs) == 0:
                continue
            his = ix.history[i][None]
            user_vec = np.asarray(
                trainer.model.apply(
                    {"params": {"user_encoder": user_params}},
                    jax.numpy.asarray(table[his]),
                    method=NewsRecommender.encode_user,
                )
            )[0]
            scores = np.concatenate(
                [[table[ix.pos[i]] @ user_vec], table[negs] @ user_vec]
            )
            y_true = np.array([1] + [0] * len(negs))
            rows.append(compute_amn(y_true, scores))
        want = np.mean(np.array(rows), axis=0)
        for j, k in enumerate(("auc", "mrr", "ndcg5", "ndcg10")):
            assert got[k] == pytest.approx(want[j], rel=1e-3), (last_k, k)

    # determinism: a second call gives bit-identical results
    again = trainer.evaluate_full()
    assert again == trainer.evaluate_full()


def test_full_eval_sharded_matches_unsharded(tmp_path):
    """Mesh-sharded full-pool eval reproduces the single-device step: the
    per-impression math is identical, only the batch axis is split over
    the clients mesh (1/mesh.size of the eval wall time at corpus scale)."""
    from fedrec_tpu.train.step import build_full_eval_step
    from fedrec_tpu.train.trainer import Trainer

    cfg = tiny_cfg(tmp_path, fed__rounds=1)
    cfg.model.text_encoder_mode = "head"
    data, token_states = tiny_data(cfg)
    trainer = Trainer(cfg, data, token_states)
    assert trainer.mesh.size > 1  # the sharded step must actually be in play
    got = trainer.evaluate_full()
    got_last4 = trainer.evaluate_full(last_k=4)

    trainer.full_eval_step = build_full_eval_step(trainer.model, cfg)
    want = trainer.evaluate_full()
    want_last4 = trainer.evaluate_full(last_k=4)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-6), k
        assert got_last4[k] == pytest.approx(want_last4[k], rel=1e-6), k


def test_trainer_native_loader_round(tmp_path):
    """Full round with host batches assembled by the C++ engine."""
    from fedrec_tpu.data import native_batcher
    from fedrec_tpu.train.trainer import Trainer

    if not native_batcher.is_available():
        pytest.skip("native engine not built")
    cfg = tiny_cfg(tmp_path, data__native_loader=True, fed__rounds=1)
    cfg.model.text_encoder_mode = "head"
    data, token_states = tiny_data(cfg)
    trainer = Trainer(cfg, data, token_states)
    from fedrec_tpu.data.native_batcher import NativeTrainBatcher

    assert isinstance(trainer.batcher, NativeTrainBatcher)
    history = trainer.run()
    assert len(history) == 1 and np.isfinite(history[0].train_loss)


def test_trainer_resume_bit_identical(tmp_path):
    """Interrupted-and-resumed == uninterrupted (full state snapshot)."""
    from fedrec_tpu.train.trainer import Trainer

    # run A: 3 rounds straight through
    cfg_a = tiny_cfg(tmp_path / "a", fed__rounds=3, train__save_every=1)
    data, token_states = tiny_data(cfg_a)
    t_a = Trainer(cfg_a, data, token_states)
    t_a.run()
    params_a = np.asarray(
        np.concatenate([np.ravel(x) for x in
                        __import__("jax").tree_util.tree_leaves(t_a.state.user_params)])
    )

    # run B: 2 rounds, then a fresh Trainer resumes round 3
    cfg_b = tiny_cfg(tmp_path / "b", fed__rounds=2, train__save_every=1)
    t_b = Trainer(cfg_b, data, token_states)
    t_b.run()
    cfg_b2 = tiny_cfg(tmp_path / "b", fed__rounds=3, train__save_every=1)
    t_b2 = Trainer(cfg_b2, data, token_states)
    assert t_b2.start_round == 2
    t_b2.run()
    params_b = np.asarray(
        np.concatenate([np.ravel(x) for x in
                        __import__("jax").tree_util.tree_leaves(t_b2.state.user_params)])
    )
    np.testing.assert_allclose(params_a, params_b, rtol=1e-6, atol=1e-7)


def test_resume_wrong_user_tower_fails_with_guided_error(tmp_path):
    """Resuming under a different model family must name the knob (ADVICE
    r3), not surface a raw orbax tree-structure error: the Trainer persists
    config.json with the snapshot and validates the tree-shaping knobs
    against it before restore."""
    from fedrec_tpu.train.trainer import Trainer

    cfg = tiny_cfg(tmp_path, fed__rounds=1, train__save_every=1)
    data, token_states = tiny_data(cfg)
    Trainer(cfg, data, token_states).run()
    assert (tmp_path / "config.json").exists()

    cfg2 = tiny_cfg(tmp_path, fed__rounds=2, train__save_every=1)
    cfg2.model.user_tower = "gru"
    with pytest.raises(ValueError, match="user_tower"):
        Trainer(cfg2, data, token_states)
    # the incumbent config.json survives the failed resume attempt — it is
    # the record of what the snapshot was trained with
    import json

    saved = json.loads((tmp_path / "config.json").read_text())
    assert saved["model"]["user_tower"] == "mha"


def test_resume_wrong_text_head_arch_fails_with_guided_error(tmp_path):
    """The text-head family (and its conv width) shape the text_head
    subtree like user_tower shapes user_encoder — resuming a cnn-head
    snapshot with the additive config (or another kernel width) must name
    the knob, not surface a raw orbax tree error."""
    from fedrec_tpu.train.trainer import Trainer

    cfg = tiny_cfg(tmp_path, fed__rounds=1, train__save_every=1)
    cfg.model.text_encoder_mode = "head"
    cfg.model.text_head_arch = "cnn"
    data, token_states = tiny_data(cfg)
    Trainer(cfg, data, token_states).run()

    cfg2 = tiny_cfg(tmp_path, fed__rounds=2, train__save_every=1)
    cfg2.model.text_encoder_mode = "head"
    with pytest.raises(ValueError, match="text_head_arch"):
        Trainer(cfg2, data, token_states)

    cfg3 = tiny_cfg(tmp_path, fed__rounds=2, train__save_every=1)
    cfg3.model.text_encoder_mode = "head"
    cfg3.model.text_head_arch = "cnn"
    cfg3.model.cnn_kernel = 5
    with pytest.raises(ValueError, match="cnn_kernel"):
        Trainer(cfg3, data, token_states)


WORKER = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import jax
    from fedrec_tpu.parallel.multihost import (
        CoordinatorRuntime, aggregate_from_hosts, initialize_distributed,
    )

    port, pid = sys.argv[1], int(sys.argv[2])
    initialize_distributed(f"127.0.0.1:{port}", 2, pid)
    assert jax.process_count() == 2
    rt = CoordinatorRuntime()

    # server broadcast: both processes must end with process 0's params
    params = {"w": np.full((4,), float(jax.process_index() + 1), np.float32)}
    synced = rt.sync_from_server(params)
    np.testing.assert_allclose(np.asarray(synced["w"]), 1.0)

    # weighted aggregate: mean of (1.0, 3.0) = 2.0
    local = {"w": np.full((4,), 1.0 + 2.0 * jax.process_index(), np.float32)}
    agg = rt.aggregate(local)
    np.testing.assert_allclose(np.asarray(agg["w"]), 2.0)

    # dropout round: only process 0 reports -> aggregate == its params
    agg2 = aggregate_from_hosts(local, weight=1.0 if pid == 0 else 0.0)
    np.testing.assert_allclose(np.asarray(agg2["w"]), 1.0)

    # round negotiation: server's counter wins; -1 = stop
    assert rt.start_round(0, 2) == 0
    assert rt.start_round(1, 2) == 1
    assert rt.start_round(2, 2) == -1
    print("WORKER_OK", pid)
    """
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_coordinator_two_process_cpu(tmp_path):
    port = _free_port()
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    env = cpu_host_env()
    env.pop("XLA_FLAGS", None)  # drop any fake-device-count: 1 device/process  # single device per process
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(port), str(pid)],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            p.kill()
            pytest.fail("coordinator worker timed out")
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
        assert f"WORKER_OK {pid}" in out


FAULT_WORKER = textwrap.dedent(
    """
    import os, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import jax
    from fedrec_tpu.parallel.multihost import CoordinatorRuntime, initialize_distributed

    port, pid, rounds = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    initialize_distributed(f"127.0.0.1:{port}", 2, pid)
    rt = CoordinatorRuntime(collective_timeout_s=10.0)
    params = {"w": np.full((4,), 1.0 + pid, np.float32)}

    r = 0
    while True:
        nxt = rt.start_round(r, rounds)
        if nxt < 0:
            break
        r = nxt
        params = rt.sync_from_server(params)
        if pid == 1 and r == 1:
            print("WORKER_DYING", flush=True)
            os._exit(1)  # simulate an unplanned crash mid-round
        params = rt.aggregate(params)
        print(f"ROUND_DONE {pid} {r} degraded={rt.degraded}", flush=True)
        r += 1
    print(f"WORKER_DONE {pid} rounds={r} degraded={rt.degraded}", flush=True)
    rt.finalize(0)  # degraded world: skip the broken shutdown barrier
    """
)


@pytest.mark.slow
def test_coordinator_survives_peer_death(tmp_path):
    """A dead peer must not hang the survivor: the watchdog degrades it to
    standalone training and it completes ALL rounds (the reference hangs
    until a 2-day gloo timeout, client.py:227 / Final_Report VII.a)."""
    port = _free_port()
    script = tmp_path / "fault_worker.py"
    script.write_text(FAULT_WORKER)
    env = cpu_host_env()
    env.pop("XLA_FLAGS", None)  # drop any fake-device-count: 1 device/process
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    rounds = 4
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(port), str(pid), str(rounds)],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for pid in range(2)
    ]
    try:
        out1, _ = procs[1].communicate(timeout=180)
        assert "WORKER_DYING" in out1 and procs[1].returncode == 1
        out0, _ = procs[0].communicate(timeout=180)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("survivor hung after peer death")
    assert procs[0].returncode == 0, f"survivor failed:\n{out0[-3000:]}"
    assert f"WORKER_DONE 0 rounds={rounds} degraded=True" in out0


SLOW_PEER_WORKER = textwrap.dedent(
    """
    import os, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    from fedrec_tpu.parallel.multihost import CoordinatorRuntime, initialize_distributed

    port, pid, rounds, slow_pid = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4])
    )
    initialize_distributed(f"127.0.0.1:{port}", 2, pid)
    rt = CoordinatorRuntime(collective_timeout_s=5.0)
    params = {"w": np.full((4,), 1.0 + pid, np.float32)}

    r = 0
    while True:
        nxt = rt.start_round(r, rounds)
        if nxt < 0:
            break
        r = nxt
        params = rt.sync_from_server(params)
        if pid == slow_pid and r == 1:
            # SLOW, not dead: outlive the peer's 5 s watchdog, then recover
            print("WORKER_SLEEPING", flush=True)
            time.sleep(12.0)
        params = rt.aggregate(params)
        print(f"ROUND_DONE {pid} {r} degraded={rt.degraded}", flush=True)
        r += 1
    print(f"WORKER_DONE {pid} rounds={r} degraded={rt.degraded}", flush=True)
    rt.finalize(0)
    """
)


def _run_slow_peer(tmp_path, slow_pid: int, rounds: int = 3):
    port = _free_port()
    script = tmp_path / f"slow_peer_worker_{slow_pid}.py"
    script.write_text(SLOW_PEER_WORKER)
    env = cpu_host_env()
    env.pop("XLA_FLAGS", None)  # 1 device/process
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(port), str(pid), str(rounds),
             str(slow_pid)],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"a host WEDGED (slow_pid={slow_pid}) — the exact "
                        "failure the watchdog exists to prevent")
        outs.append(out)
    return procs, outs


@pytest.mark.slow
def test_coordinator_slow_server_recovers(tmp_path):
    """VERDICT r2 Weak #7, recoverable direction: the SERVER stalls past
    the watchdog, then wakes and keeps calling collectives. The client
    degrades at its timeout and finishes standalone; the recovered server
    finds a world that never answers again, hits its OWN watchdog, and
    also finishes all rounds standalone. Nobody wedges, both exit 0."""
    rounds = 3
    procs, outs = _run_slow_peer(tmp_path, slow_pid=0, rounds=rounds)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out[-3000:]}"
        assert f"WORKER_DONE {pid} rounds={rounds}" in out
    assert "WORKER_SLEEPING" in outs[0]
    assert f"WORKER_DONE 0 rounds={rounds} degraded=True" in outs[0]
    assert "degrading to standalone" in outs[1]
    assert f"WORKER_DONE 1 rounds={rounds} degraded=True" in outs[1]


@pytest.mark.slow
def test_coordinator_slow_client_bounded_termination(tmp_path):
    """Weak #7, the other direction: a CLIENT stalls past the watchdog.
    The server degrades, finishes standalone, and exits — which tears down
    the coordination service it hosts (it lives in process 0, a JAX
    platform constraint shared with torchrun's c10d rendezvous). The
    recovered client is then fatally terminated by its distributed
    runtime: a BOUNDED crash, never a wedge. This test pins exactly that
    contract: server completes all rounds degraded; client either finished
    standalone in time (rc 0) or was runtime-terminated — and both
    processes terminate well inside the harness timeout."""
    rounds = 3
    procs, outs = _run_slow_peer(tmp_path, slow_pid=1, rounds=rounds)
    assert procs[0].returncode == 0, f"server failed:\n{outs[0][-3000:]}"
    assert f"WORKER_DONE 0 rounds={rounds} degraded=True" in outs[0]
    assert "degrading to standalone" in outs[0]
    assert "WORKER_SLEEPING" in outs[1]
    if procs[1].returncode == 0:
        assert f"WORKER_DONE 1 rounds={rounds}" in outs[1]
    else:
        # runtime-terminated after the server left: bounded, documented
        assert "JAX distributed service detected fatal errors" in outs[1]


COORD_CLI = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    from fedrec_tpu.cli.coordinator import main
    port, pid, snap = sys.argv[1], sys.argv[2], sys.argv[3]
    rounds = sys.argv[4] if len(sys.argv) > 4 else "2"
    extra = sys.argv[5:]  # additional --set overrides
    code = main([
        rounds, "8", "1",
        "--coordinator", f"127.0.0.1:{port}",
        "--num-processes", "2", "--process-id", pid,
        "--synthetic", "--clients", "1",
        "--set", "model.bert_hidden=48", "--set", "data.max_his_len=10",
        "--set", "data.max_title_len=12", "--set", "model.news_dim=32",
        "--set", "model.num_heads=4", "--set", "model.head_dim=8",
        "--set", "model.query_dim=16", "--set", f"train.snapshot_dir={snap}",
        *extra,
    ])
    sys.exit(code)
    """
)


def _run_coord_cli(tmp_path, script, rounds, dirs, tag, extra=()):
    port = _free_port()
    env = cpu_host_env()
    env.pop("XLA_FLAGS", None)  # drop any fake-device-count: 1 device/process
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(port), str(pid), str(dirs[pid]),
             str(rounds), *extra],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            p.kill()
            pytest.fail(f"coordinator CLI ({tag}) timed out")
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"{tag} process {pid} failed:\n{out[-3000:]}"
    return outs


@pytest.mark.slow
def test_coordinator_cli_resume_bit_identical(tmp_path):
    """Multi-process resume restores full client state (opt + PRNG): a
    1-round run resumed for round 2 produces the same global model as an
    uninterrupted 2-round run."""
    script = tmp_path / "coord_cli.py"
    script.write_text(COORD_CLI)

    a_dirs = [tmp_path / "a0", tmp_path / "a1"]
    _run_coord_cli(tmp_path, script, 2, a_dirs, "straight")

    b_dirs = [tmp_path / "b0", tmp_path / "b1"]
    _run_coord_cli(tmp_path, script, 1, b_dirs, "first-leg")
    outs = _run_coord_cli(tmp_path, script, 2, b_dirs, "resumed")
    assert any("resumed local state at round 0" in o for o in outs)

    a = (a_dirs[0] / "global_round_1.msgpack").read_bytes()
    b = (b_dirs[0] / "global_round_1.msgpack").read_bytes()
    assert a == b


@pytest.mark.slow
def test_coordinator_cli_two_process(tmp_path):
    """Full client/server deployment: process 0 = non-training server."""
    port = _free_port()
    script = tmp_path / "coord_cli.py"
    script.write_text(COORD_CLI)
    env = cpu_host_env()
    env.pop("XLA_FLAGS", None)  # drop any fake-device-count: 1 device/process
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(port), str(pid), str(tmp_path / f"s{pid}")],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            p.kill()
            pytest.fail("coordinator CLI timed out")
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out[-3000:]}"
        assert "done after 2 rounds" in out


WEIGHTED_WORKER = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    from fedrec_tpu.parallel.multihost import CoordinatorRuntime, initialize_distributed

    port, pid = sys.argv[1], int(sys.argv[2])
    initialize_distributed(f"127.0.0.1:{port}", 2, pid)
    rt = CoordinatorRuntime(collective_timeout_s=30.0)
    params = {"w": np.full((4,), float(pid + 1), np.float32)}
    # classic FedAvg: process 0 weighs 1 sample, process 1 weighs 3
    agg = rt.aggregate(params, weight=float(1 + 2 * pid))
    want = (1.0 * 1 + 2.0 * 3) / 4.0  # = 1.75
    assert np.allclose(agg["w"], want), agg["w"]
    print(f"WEIGHTED_OK {pid}", flush=True)
    """
)


@pytest.mark.slow
def test_coordinator_aggregate_weight_by_samples(tmp_path):
    """aggregate(weight=n_k) reproduces the classic FedAvg weighted mean
    (the reference's server averages state_dicts UNWEIGHTED over unequal
    shards, server.py:37-55 — kept as the default for parity)."""
    port = _free_port()
    script = tmp_path / "weighted_worker.py"
    script.write_text(WEIGHTED_WORKER)
    env = cpu_host_env()
    env.pop("XLA_FLAGS", None)  # drop any fake-device-count: 1 device/process
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(port), str(pid)],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for pid in range(2)
    ]
    for pid, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            p.kill()
            pytest.fail("weighted aggregate worker timed out")
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
        assert f"WEIGHTED_OK {pid}" in out


@pytest.mark.slow
def test_coordinator_cli_server_opt(tmp_path):
    """Cross-host FedOpt in the coordinator: a neutral server optimizer
    (sgd lr=1, momentum=0) reproduces plain aggregation numerically, and
    FedAvgM (momentum=0.9) actually changes the global; optimizer state is
    hub-and-spoke — held by the server process only."""
    script = tmp_path / "coord_cli.py"
    script.write_text(COORD_CLI)

    plain = [tmp_path / "p0", tmp_path / "p1"]
    _run_coord_cli(tmp_path, script, 2, plain, "plain")

    neutral = [tmp_path / "n0", tmp_path / "n1"]
    _run_coord_cli(
        tmp_path, script, 2, neutral, "neutral",
        extra=["--set", "fed.server_opt=sgd", "--set", "fed.server_lr=1.0",
               "--set", "fed.server_momentum=0.0"],
    )
    from flax import serialization

    def flat_global(path):
        raw = serialization.msgpack_restore(path.read_bytes())
        import jax

        return np.concatenate([
            np.ravel(np.asarray(x))
            for x in jax.tree_util.tree_leaves((raw["user"], raw["news"]))
        ])

    # g + (m - g) is not bitwise m in float32: the subtraction leaves an
    # absolute error ~eps*|g| that is RELATIVELY huge on near-zero params,
    # so the tolerance needs an absolute floor
    np.testing.assert_allclose(
        flat_global(plain[0] / "global_round_1.msgpack"),
        flat_global(neutral[0] / "global_round_1.msgpack"),
        rtol=1e-4, atol=1e-5,
    )

    fedavgm = [tmp_path / "m0", tmp_path / "m1"]
    _run_coord_cli(
        tmp_path, script, 2, fedavgm, "fedavgm",
        extra=["--set", "fed.server_opt=sgd", "--set", "fed.server_lr=0.7",
               "--set", "fed.server_momentum=0.9"],
    )
    assert not np.allclose(
        flat_global(fedavgm[0] / "global_round_1.msgpack"),
        flat_global(plain[0] / "global_round_1.msgpack"),
        rtol=1e-4,
    )
    # hub-and-spoke: optimizer state lives ONLY on the server (process 0)
    assert (fedavgm[0] / "server_opt_state.msgpack").exists()
    assert not (fedavgm[1] / "server_opt_state.msgpack").exists()


def test_quantize_dequantize_bounds():
    """int8 round-trip error is bounded by scale/2 per element; zero tensors
    are exact; the decode-before-reduce masked weighted mean the coordinator
    applies to gathered stacks drops a w=0 contribution entirely. (The
    ad-hoc multihost quantizer this pinned moved into fedrec_tpu.comms.)"""
    from fedrec_tpu.comms import decode_leaf, encode_leaf, payload_nbytes

    rng = np.random.default_rng(0)
    p = rng.standard_normal((64, 32)).astype(np.float32)
    pay = encode_leaf(p, "int8")
    s = float(pay["scale"])
    assert pay["q"].dtype == np.int8 and s > 0
    np.testing.assert_allclose(
        decode_leaf(pay, "int8", p.shape), p, atol=s / 2 + 1e-9
    )
    assert payload_nbytes(pay) == p.size + 4  # real wire buffer: q + scale

    z = encode_leaf(np.zeros((4, 4), np.float32), "int8")
    assert float(z["scale"]) == 0.0 and not z["q"].any()

    # weighted mean over per-process DECODED stacks == hand-computed
    # dequantized mean; a dropped-out process (w=0) contributes nothing:
    # identical to the mean computed with that process excluded entirely
    ps = [rng.standard_normal((8,)).astype(np.float32) for _ in range(3)]
    dec = np.stack(
        [decode_leaf(encode_leaf(x, "int8"), "int8", x.shape) for x in ps]
    )
    w = np.asarray([1.0, 0.0, 2.0], np.float32)
    got = np.einsum("p,p...->...", w / w.sum(), dec)
    np.testing.assert_allclose(got, (1.0 * dec[0] + 2.0 * dec[2]) / 3.0,
                               rtol=1e-6)


def test_local_strategy_eval_averages_divergent_clients(tmp_path):
    """VERDICT r2 item 7: under strategy='local' clients diverge, so the
    reported metric must be the documented aggregate (mean of per-client
    metrics), not silently client 0."""
    from fedrec_tpu.train.trainer import Trainer

    cfg = tiny_cfg(tmp_path, fed__strategy="local", fed__rounds=1,
                   fed__num_clients=2)
    cfg.model.text_encoder_mode = "head"
    data, token_states = tiny_data(cfg)
    t = Trainer(cfg, data, token_states)
    assert t._clients_in_sync()  # replicated init
    t.train_round(0)
    assert not t._clients_in_sync()  # disjoint shards diverged them

    per = [t.evaluate_full(client=c) for c in range(2)]
    assert any(per[0][k] != per[1][k] for k in per[0]), "clients identical?"
    got = t.evaluate_full()
    for k in got:
        assert got[k] == pytest.approx(np.mean([m[k] for m in per]), rel=1e-6)
    assert t.last_per_client_metrics is not None
    assert len(t.last_per_client_metrics) == 2

    # sampled protocol resolves the same way
    got_s = t.evaluate()
    per_s = [t.evaluate(client=c) for c in range(2)]
    for k in got_s:
        assert got_s[k] == pytest.approx(np.mean([m[k] for m in per_s]), rel=1e-6)


def test_grad_avg_eval_uses_fast_path(tmp_path):
    """grad_avg keeps clients in bitwise lockstep; eval must detect the
    sync and report client-0 metrics without the per-client sweep."""
    from fedrec_tpu.train.trainer import Trainer

    cfg = tiny_cfg(tmp_path, fed__strategy="grad_avg", fed__num_clients=2)
    cfg.model.text_encoder_mode = "head"
    data, token_states = tiny_data(cfg)
    t = Trainer(cfg, data, token_states)
    t.train_round(0)
    assert t._clients_in_sync()
    got = t.evaluate_full()
    assert t.last_per_client_metrics is None  # fast path taken
    assert got == t.evaluate_full(client=0)


def test_quantize_delta_tighter_than_absolute():
    """Delta quantization (ADVICE r2): with a shared round-start base, the
    int8 error is bounded by the DELTA's range, not the parameter's — an
    outlier weight no longer destroys the whole tensor's resolution."""
    from fedrec_tpu.comms import decode_leaf, encode_leaf

    rng = np.random.default_rng(1)
    base = rng.standard_normal(512).astype(np.float32)
    base[0] = 100.0  # outlier WEIGHT (persists across rounds)
    delta = (1e-3 * rng.standard_normal(512)).astype(np.float32)
    p = base + delta

    # absolute quantization: error floor set by the outlier, ~0.4 worst case
    err_abs = np.max(np.abs(
        decode_leaf(encode_leaf(p, "int8"), "int8", p.shape) - p
    ))
    # delta quantization: error bounded by max|delta|/254 ~ 2e-5
    d_dec = decode_leaf(encode_leaf(p - base, "int8"), "int8", p.shape)
    err_d = np.max(np.abs((d_dec + base) - p))
    assert err_d < 1e-4 < err_abs
    # quantization bound max|delta|/254 plus the f32 rounding floor of the
    # subtraction/add at the outlier's magnitude (eps * 100 ~ 1.2e-5)
    assert err_d <= np.max(np.abs(delta)) / 254 + 2 ** -23 * 100 + 1e-7


def test_server_opt_requires_syncing_strategy(tmp_path):
    """fed.server_opt with a never-syncing strategy fails FAST instead of
    silently running plain behavior (ADVICE r2)."""
    from fedrec_tpu.train.trainer import Trainer

    cfg = tiny_cfg(tmp_path, fed__strategy="grad_avg", fed__server_opt="adam")
    cfg.model.text_encoder_mode = "head"
    data, token_states = tiny_data(cfg)
    with pytest.raises(ValueError, match="server_opt"):
        Trainer(cfg, data, token_states)


@pytest.mark.slow
def test_coordinator_cli_int8_compression(tmp_path):
    """fed.dcn_compress=int8 over two real processes: training completes and
    the final global matches the uncompressed run within the accumulated
    quantization-noise budget (contributions are ~0.2%-of-range accurate)."""
    script = tmp_path / "coord_cli.py"
    script.write_text(COORD_CLI)

    plain = [tmp_path / "p0", tmp_path / "p1"]
    _run_coord_cli(tmp_path, script, 2, plain, "plain")
    int8 = [tmp_path / "q0", tmp_path / "q1"]
    _run_coord_cli(
        tmp_path, script, 2, int8, "int8",
        extra=["--set", "fed.dcn_compress=int8"],
    )

    from flax import serialization

    def flat_global(path):
        raw = serialization.msgpack_restore(path.read_bytes())
        import jax

        return np.concatenate([
            np.ravel(np.asarray(x))
            for x in jax.tree_util.tree_leaves((raw["user"], raw["news"]))
        ])

    a = flat_global(plain[0] / "global_round_1.msgpack")
    b = flat_global(int8[0] / "global_round_1.msgpack")
    assert np.max(np.abs(a - b)) < 0.02, np.max(np.abs(a - b))
    assert not np.array_equal(a, b)  # compression actually engaged


def test_keep_best_snapshot_tracks_max_auc_and_survives_resume(tmp_path):
    """train.keep_best writes a full best-AUC snapshot dir (incl. its own
    config.json, so fedrec-recommend can serve it directly): the marker
    names the argmax-AUC round of the run, and a resumed run loads the
    incumbent best so a later worse round can never replace it."""
    import json

    from fedrec_tpu.train.checkpoint import SnapshotManager
    from fedrec_tpu.train.trainer import Trainer

    cfg = tiny_cfg(tmp_path, fed__rounds=4, train__save_every=1)
    cfg.train.keep_best = True
    cfg.train.eval_every = 1
    data, token_states = tiny_data(cfg)
    t = Trainer(cfg, data, token_states)
    history = t.run()

    best_dir = tmp_path / "best"
    marker = json.loads((best_dir / "best.json").read_text())
    aucs = [r.val_metrics["auc"] for r in history if r.val_metrics]
    assert marker["auc"] == pytest.approx(max(aucs))
    assert aucs[marker["round"]] == pytest.approx(max(aucs))
    # a full snapshot dir: restorable and self-describing
    assert (best_dir / "config.json").exists()
    assert SnapshotManager(best_dir).latest_round() == marker["round"]

    # resume: the incumbent best is loaded, not reset
    cfg2 = tiny_cfg(tmp_path, fed__rounds=5, train__save_every=1)
    cfg2.train.keep_best = True
    cfg2.train.eval_every = 1
    t2 = Trainer(cfg2, data, token_states)
    assert t2._best_auc == pytest.approx(marker["auc"])
    t2.run()
    marker2 = json.loads((best_dir / "best.json").read_text())
    assert marker2["auc"] >= marker["auc"]


def test_keep_best_torn_marker_restarts_tracking(tmp_path):
    """A marker that disagrees with the stored best round (crash between
    the snapshot save and the marker write) must not seed _best_auc — the
    stored snapshot's AUC is unknown, so tracking restarts and the next
    improvement rewrites both coherently. A malformed marker (null auc)
    degrades the same way instead of crashing __init__."""
    import json

    from fedrec_tpu.train.trainer import Trainer

    cfg = tiny_cfg(tmp_path, fed__rounds=2, train__save_every=1)
    cfg.train.keep_best = True
    cfg.train.eval_every = 1
    data, token_states = tiny_data(cfg)
    Trainer(cfg, data, token_states).run()

    best_dir = tmp_path / "best"
    marker = json.loads((best_dir / "best.json").read_text())
    (best_dir / "best.json").write_text(
        json.dumps({"round": marker["round"] + 7, "auc": 0.99})
    )
    cfg2 = tiny_cfg(tmp_path, fed__rounds=3, train__save_every=1)
    cfg2.train.keep_best = True
    cfg2.train.eval_every = 1
    t = Trainer(cfg2, data, token_states)
    assert t._best_auc is None

    (best_dir / "best.json").write_text(json.dumps({"auc": None}))
    cfg3 = tiny_cfg(tmp_path, fed__rounds=3, train__save_every=1)
    cfg3.train.keep_best = True
    t3 = Trainer(cfg3, data, token_states)
    assert t3._best_auc is None
