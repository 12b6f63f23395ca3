"""Device mesh + sharding helpers: the TPU-native communication backend.

This replaces the reference's entire L5 layer — torchrun/c10d rendezvous +
gloo ``init_process_group``/``all_reduce``/``broadcast`` + raw TCP side
channel (reference ``main.py:144``, ``Parameter_Averaging_main.py:146``,
``server.py:74-98``, ``client.py:191-210,256-264``) — with a
``jax.sharding.Mesh`` over a ``clients`` axis:

  * one federated client == one mesh slot (TPU core / pod chip)
  * grad / param averaging == ``lax.pmean`` over the axis, riding ICI
  * server broadcast / gather == sharding-induced XLA collectives; no file
    transfer channel exists because arrays are natively exchangeable
  * multi-host rendezvous == ``jax.distributed.initialize`` (see
    ``fedrec_tpu.parallel.multihost``)

On a single host the same code runs against N virtual CPU devices
(``--xla_force_host_platform_device_count=N``) — the JAX-native analogue of
the reference's localhost-gloo simulation (reference ``README.md:27-34``).
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

CLIENT_AXIS = "clients"
# the parameter-sharding axis (shard.fsdp > 1): client state at rest is
# sharded across it per fedrec_tpu.shard.policy; compute gathers on entry
FSDP_AXIS = "fsdp"


def client_mesh(
    num_clients: int,
    axis: str = CLIENT_AXIS,
    local: bool = True,
    max_devices: int | None = None,
) -> Mesh:
    """1-D mesh over the federated-client axis.

    ``local=True`` (default) builds the mesh from this process's addressable
    devices — correct for single-host simulation and for the coordinator
    deployment where each host trains its own clients and syncs over DCN.
    ``local=False`` uses the global device list for a single-controller
    multi-host SPMD mesh (all hosts must then feed globally-sharded arrays).

    When ``num_clients`` exceeds the device count, the mesh spans every
    device and each device hosts a COHORT of ``num_clients / n_devices``
    clients (the train/sync steps vmap over the in-device cohort and run
    collectives over ``(cohort, mesh)`` jointly — see
    ``fedrec_tpu.train.step.LOCAL_AXIS``). This is how a 32-client
    federation (BASELINE.json north star) runs on fewer chips, the
    TPU-native analogue of oversubscribing torchrun ranks onto one node
    (reference ``README.md:27-34``). Requires divisibility; on CPU test
    rigs use ``--xla_force_host_platform_device_count``.

    ``max_devices`` caps the device pool (mainly for equivalence tests:
    the same client count with different cohort factors).
    """
    devices = jax.local_devices() if local else jax.devices()
    if max_devices is not None:
        devices = devices[:max_devices]
    if num_clients <= len(devices):
        size = num_clients
    elif num_clients % len(devices) == 0:
        size = len(devices)
    else:
        raise ValueError(
            f"num_clients={num_clients} exceeds {len(devices)} available "
            "devices and is not divisible by the device count (cohort "
            "sharding needs equal cohorts); set XLA_FLAGS="
            "--xla_force_host_platform_device_count for simulation"
        )
    mesh_devices = mesh_utils.create_device_mesh(
        (size,), devices=devices[:size]
    )
    return Mesh(mesh_devices, (axis,))


def fed_mesh(cfg: Any, local: bool = True) -> Mesh:
    """Mesh for an ExperimentConfig: 1-D ``(clients,)``, 2-D
    ``(clients, seq)`` when ``fed.seq_shards > 1`` (long-history sequence
    parallelism — each client's history attention spans ``seq_shards`` chips
    via ring/Ulysses collectives, see ``fedrec_tpu.parallel.ring``), or 2-D
    ``(clients, fsdp)`` when ``shard.fsdp > 1`` (at-rest parameter/optimizer
    sharding per ``fedrec_tpu.shard.policy``; ``fsdp=1`` builds the exact
    1-D mesh, so the degenerate config is bit-identical to pure data
    parallelism by construction).
    """
    n_cli, n_seq = cfg.fed.num_clients, cfg.fed.seq_shards
    n_fsdp = getattr(getattr(cfg, "shard", None), "fsdp", 1)
    if n_fsdp > 1:
        if n_seq > 1:
            raise ValueError(
                f"shard.fsdp={n_fsdp} with fed.seq_shards={n_seq} is not "
                "supported: both claim the mesh's second axis — unset one "
                "of the two"
            )
        return _two_axis_mesh(
            cfg, n_cli, n_fsdp, FSDP_AXIS, "shard.fsdp", local
        )
    if n_seq <= 1:
        return client_mesh(n_cli, cfg.fed.mesh_axis, local=local)
    if cfg.data.max_his_len % n_seq != 0:
        raise ValueError(
            f"data.max_his_len={cfg.data.max_his_len} must be divisible by "
            f"fed.seq_shards={n_seq} to shard the history axis"
        )
    return _two_axis_mesh(
        cfg, n_cli, n_seq, cfg.fed.seq_axis, "fed.seq_shards", local
    )


def _two_axis_mesh(
    cfg: Any,
    n_cli: int,
    n_second: int,
    second_axis: str,
    flag: str,
    local: bool,
) -> Mesh:
    """A 2-D ``(clients, <second>)`` mesh with the same cohort policy as
    :func:`client_mesh` on the clients axis — shared by the seq-parallel
    and fsdp layouts so slot/cohort arithmetic cannot diverge."""
    devices = jax.local_devices() if local else jax.devices()
    cli_slots = len(devices) // n_second
    if cli_slots < 1:
        raise ValueError(
            f"{flag}={n_second} exceeds {len(devices)} devices; "
            "set XLA_FLAGS=--xla_force_host_platform_device_count for simulation"
        )
    if n_cli <= cli_slots:
        size = n_cli
    elif n_cli % cli_slots == 0:
        size = cli_slots  # cohorts: size*n_second devices, n_cli/size per slot
    else:
        raise ValueError(
            f"num_clients={n_cli} exceeds the {cli_slots} client slots of a "
            f"{len(devices)}-device mesh with {flag}={n_second} and is not "
            "divisible by the slot count (cohort sharding needs equal "
            "cohorts); set XLA_FLAGS=--xla_force_host_platform_device_count"
        )
    mesh_devices = mesh_utils.create_device_mesh(
        (size, n_second), devices=devices[: size * n_second]
    )
    return Mesh(mesh_devices, (cfg.fed.mesh_axis, second_axis))


def fed_batch_spec(key: str, cfg: Any, mesh: Mesh) -> P:
    """The ONE per-key batch layout rule: dim 0 over the clients axis;
    ``history``'s last dim additionally over the seq axis when sequence
    parallelism is on. Used by ``shard_fed_batch``."""
    if (
        cfg.fed.seq_shards > 1
        and cfg.fed.seq_axis in mesh.axis_names
        and key == "history"
    ):
        return P(cfg.fed.mesh_axis, None, cfg.fed.seq_axis)
    return P(cfg.fed.mesh_axis)


def shard_fed_batch(mesh: Mesh, batch: dict, cfg: Any) -> dict:
    """Shard a train batch for ``fed_mesh`` per ``fed_batch_spec``."""
    if cfg.fed.seq_shards <= 1 or cfg.fed.seq_axis not in mesh.axis_names:
        return shard_batch(mesh, batch, cfg.fed.mesh_axis)
    return {
        k: jax.device_put(
            np.asarray(v), NamedSharding(mesh, fed_batch_spec(k, cfg, mesh))
        )
        for k, v in batch.items()
    }


def client_sharding(mesh: Mesh, axis: str = CLIENT_AXIS) -> NamedSharding:
    """Leading-axis sharding: array dim 0 is the per-client dim."""
    return NamedSharding(mesh, P(axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, batch: Any, axis: str = CLIENT_AXIS) -> Any:
    """Device-put a pytree of (num_clients, ...) arrays with dim 0 sharded."""
    sharding = client_sharding(mesh, axis)
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(np.asarray(x), sharding), batch
    )
