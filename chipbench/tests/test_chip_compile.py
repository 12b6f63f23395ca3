"""Rehearsal without the chip: each configuration's train step, at its full
size, compiled for a described v5e (on-chip-measurement guide, section 2).
Says whether the chip's compiler takes the program and how much of the 16 GB
one step needs; nothing runs, so it says nothing about results or times.

The topology is described inside a fixture, never at import, and these
compiles live in this one file."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from conftest import BENCH

HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler on this rig
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compile_step(devices, config: dict):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from chipbench import harness_training_rounds as harness
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.train import build_fed_train_step
    from fedrec_tpu.train.state import init_client_state, replicate_state

    cfg = harness.build_config(config, seed=0)
    s = config["shapes"]
    k, b = s["clients"], s["batch_per_client"]
    model = NewsRecommender(cfg.model)
    axis = cfg.fed.mesh_axis
    mesh = Mesh(np.array(devices), (axis,))
    per_client = NamedSharding(mesh, P(axis))
    spec = lambda shape, dtype, sh: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)  # noqa: E731
    state = jax.eval_shape(lambda: replicate_state(
        init_client_state(model, cfg, jax.random.PRNGKey(0), s["catalog_rows"], s["title_len"]),
        k, jax.random.PRNGKey(1),
    ))
    state = jax.tree_util.tree_map(lambda x: spec(x.shape, x.dtype, per_client), state)
    batch = {
        "candidates": spec((k, b, s["candidates"]), "int32", per_client),
        "history": spec((k, b, s["history"]), "int32", per_client),
        "labels": spec((k, b), "int32", per_client),
    }
    table = spec((s["catalog_rows"], s["title_len"], s["bert_hidden"]), cfg.model.dtype,
                 NamedSharding(mesh, P()))
    step = build_fed_train_step(model, cfg, get_strategy(cfg.fed.strategy), mesh, mode="joint")
    return step.lower(state, batch, table).compile()


@pytest.mark.parametrize("name", ["mind-small-central", "mind-small-fed8"])
def test_step_compiles_at_full_size_for_one_chip(topo, name):
    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    mem = compile_step(topo.devices[:1], config).memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{name}: arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, outputs "
          f"{mem.output_size_in_bytes / 1e9:.2f} GB, aliased {mem.alias_size_in_bytes / 1e9:.2f} GB, "
          f"together {total / 1e9:.2f} GB")
    assert 0.25 * HBM_BYTES < total < HBM_BYTES
