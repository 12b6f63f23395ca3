"""Elastic membership: epoch formation, shrink-and-continue, rejoin,
reform signaling, the degraded-teardown edge, and checkpoint-backed
catalog/FSDP resharding (ISSUE 12).

Everything here is FAST: the membership service is exercised in-process
over localhost TCP with sub-second leases, the coordinator runtime's
teardown edge runs against a fake-collective stub (no real peers), and
the reshard exactness pins use the conftest's 8 fake CPU devices. The
full 4-process kill->shrink->rejoin drive lives in
``scripts/elastic_smoke.sh`` (``make elastic-smoke``).
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from fedrec_tpu.parallel.membership import (
    MembershipClient,
    MembershipError,
    MembershipServer,
    _rank_order,
    elastic_policy,
    publish_membership_metrics,
)


def _join_all(clients, timeout=15.0):
    out = [None] * len(clients)
    ths = [
        threading.Thread(target=lambda i=i: out.__setitem__(i, clients[i].join()))
        for i in range(len(clients))
    ]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout)
    assert all(a is not None for a in out), "a join never completed"
    return out


@pytest.fixture()
def server(request):
    # lease_ms: 800, or what the test asks for (indirect parameter)
    srv = MembershipServer(
        target_world=3, lease_ms=getattr(request, "param", 800),
        heartbeat_ms=200, formation_grace_ms=900,
    ).start()
    yield srv
    srv.stop()


def test_epoch_zero_forms_at_full_complement(server):
    clients = [
        MembershipClient(server.address, worker_id=str(i), join_timeout_s=15)
        for i in range(3)
    ]
    t0 = time.monotonic()
    asg = _join_all(clients)
    # full complement: formation is immediate, not grace-window-bound
    assert time.monotonic() - t0 < server.formation_grace_ms / 1e3
    assert [a.epoch for a in asg] == [0, 0, 0]
    assert sorted(a.rank for a in asg) == [0, 1, 2]
    assert all(a.world == 3 for a in asg)
    # one coordinator address for the whole epoch — rank 0's candidate
    assert len({a.coordinator for a in asg}) == 1
    # worker "0" holds rank 0 (numeric rank order)
    assert asg[0].rank == 0


# The one test that waits for one lease to run out while it keeps two others
# alive by hand: its lease is long against the stalls of a loaded machine
# (with 800 ms a survivor's lease ran out too, once in four runs under six
# xdist workers), and the wait for the expiry is a multiple of the lease.
@pytest.mark.parametrize("server", [2400], indirect=True)
def test_shrink_then_rejoin_epochs(server):
    clients = [
        MembershipClient(server.address, worker_id=str(i), join_timeout_s=15)
        for i in range(3)
    ]
    _join_all(clients)
    # worker 1 dies: stops heartbeating. Survivors keep renewing until the
    # reaper expires the lease and flags reform.
    deadline = time.monotonic() + 5 * server.lease_ms / 1e3
    reform = False
    while time.monotonic() < deadline and not reform:
        reform = clients[0].heartbeat()["reform"]
        clients[2].heartbeat()
        time.sleep(0.1)
    assert reform, "lease expiry never flagged reform"
    st = server.status()
    assert st["lease_misses"] == 1 and "1" not in st["members"]

    # shrink-and-continue: the survivors rejoin; the grace window closes
    # with 2 of 3 and epoch 1 forms at world 2
    asg1 = _join_all([clients[0], clients[2]])
    assert [a.epoch for a in asg1] == [1, 1]
    assert [a.world for a in asg1] == [2, 2]
    assert (asg1[0].rank, asg1[1].rank) == (0, 1)
    assert server.status()["shrinks"] == 1

    # rejoin: worker 1's (respawned) join knocks on the healthy epoch —
    # the live members learn via heartbeat, leave, and epoch 2 forms at
    # the full world again, immediately (everyone is back)
    rejoined = [None]
    knock = threading.Thread(
        target=lambda: rejoined.__setitem__(0, clients[1].join())
    )
    knock.start()
    deadline = time.monotonic() + 4.0
    while time.monotonic() < deadline:
        clients[2].heartbeat()
        if clients[0].heartbeat()["reform"]:
            break
        time.sleep(0.05)
    else:
        pytest.fail("a rejoining worker never triggered reform")
    asg2 = _join_all([clients[0], clients[2]])
    knock.join(10)
    assert rejoined[0] is not None
    assert rejoined[0].epoch == 2 and rejoined[0].world == 3
    assert {a.rank for a in asg2} | {rejoined[0].rank} == {0, 1, 2}
    st = server.status()
    assert st["shrinks"] == 1 and st["rejoins"] == 1
    assert [h["world"] for h in st["epoch_history"]] == [3, 2, 3]


def test_min_world_blocks_formation():
    srv = MembershipServer(
        target_world=3, min_world=2, lease_ms=500, heartbeat_ms=100,
        formation_grace_ms=200,
    ).start()
    try:
        lone = MembershipClient(srv.address, worker_id="7", join_timeout_s=15)
        got = [None]
        t = threading.Thread(target=lambda: got.__setitem__(0, lone.join()))
        t.start()
        time.sleep(1.0)
        # one joiner < min_world: the grace window expired but no epoch
        # formed — the joiner stays parked
        assert srv.status()["epoch"] == -1 and got[0] is None
        second = MembershipClient(srv.address, worker_id="8", join_timeout_s=15)
        asg2 = second.join()
        # outlast the joiner's own 15 s give-up: under a loaded 1-CPU
        # suite run a 10 s wait expired while the join was still live
        t.join(20)
        assert got[0] is not None and got[0].epoch == 0
        assert asg2.world == 2
    finally:
        srv.stop()


def test_join_formed_between_wait_timeout_and_lock_is_not_superseded(monkeypatch):
    """The parked join polls ``event.wait(0.2)`` and then takes the lock. An
    epoch that forms in between has cleared ``_joiners``: that is a seat,
    not a supersession (seen as a ``MembershipError`` in
    ``test_min_world_blocks_formation`` under six xdist workers)."""
    from fedrec_tpu.parallel import membership

    class TimedOutJustBeforeSet(threading.Event):
        def wait(self, timeout=None):
            return False

    joiner = membership._Joiner
    monkeypatch.setattr(
        membership, "_Joiner",
        lambda **kw: joiner(event=TimedOutJustBeforeSet(), **kw),
    )
    srv = MembershipServer(target_world=1).start()
    try:
        asg = MembershipClient(srv.address, worker_id="0", join_timeout_s=15).join()
        assert (asg.epoch, asg.world) == (0, 1)
    finally:
        srv.stop()


def test_policy_adopted_from_first_joiner():
    srv = MembershipServer(target_world=1).start()
    try:
        from fedrec_tpu.config import ElasticConfig

        el = ElasticConfig()
        el.lease_ms = 1234.0
        el.heartbeat_ms = 321.0
        el.formation_grace_ms = 555.0
        el.min_world = 1
        c = MembershipClient(srv.address, worker_id="0", join_timeout_s=15)
        asg = c.join(policy=elastic_policy(el))
        assert srv.lease_ms == 1234.0
        assert srv.formation_grace_ms == 555.0
        assert asg.lease_ms == 1234.0 and asg.heartbeat_ms == 321.0
    finally:
        srv.stop()


def test_policy_explicit_server_flags_win():
    srv = MembershipServer(target_world=1, lease_ms=9000.0).start()
    try:
        c = MembershipClient(srv.address, worker_id="0", join_timeout_s=15)
        asg = c.join(policy={"lease_ms": 1.0})
        assert asg.lease_ms == 9000.0
    finally:
        srv.stop()


def test_heartbeat_thread_latches_reform_and_counts_failures(server):
    clients = [
        MembershipClient(server.address, worker_id=str(i), join_timeout_s=15)
        for i in range(3)
    ]
    _join_all(clients)
    clients[0].start_heartbeat()
    # a stale-epoch worker knocking flags reform for the live members
    knock = MembershipClient(server.address, worker_id="9", join_timeout_s=15)
    got = [None]
    t = threading.Thread(target=lambda: got.__setitem__(0, knock.join()))
    t.start()
    deadline = time.monotonic() + 3.0
    while time.monotonic() < deadline and not clients[0].reform_pending:
        time.sleep(0.05)
    assert clients[0].reform_pending
    clients[0].close()
    # failures: point a client at a dead port
    server_gone = MembershipClient("127.0.0.1:1", worker_id="x")
    with pytest.raises((OSError, MembershipError)):
        server_gone.heartbeat()
    # the daemon loop counts instead of raising
    server_gone.assignment = None
    server_gone._stop.clear()
    server_gone.start_heartbeat()
    time.sleep(0.2)
    server_gone.close()
    # everyone rejoins so the parked knocker is released before teardown
    asg = _join_all([clients[1], clients[2]])
    t.join(10)
    assert got[0] is not None and got[0].world == 3
    assert asg[0].epoch == got[0].epoch


def test_rank_order_numeric_then_lexical():
    assert _rank_order(["10", "2", "0"]) == ["0", "2", "10"]
    assert _rank_order(["b", "2", "a"]) == ["2", "a", "b"]


def test_publish_membership_metrics_registers():
    from fedrec_tpu.obs import get_registry
    from fedrec_tpu.parallel.membership import EpochAssignment

    asg = EpochAssignment(
        epoch=3, rank=1, world=2, coordinator="h:1", lease_ms=1.0,
        heartbeat_ms=1.0,
    )
    publish_membership_metrics(assignment=asg, reforms=1)
    snap = get_registry().snapshot()["metrics"]
    assert snap["fed.membership_epoch"]["values"][0]["value"] == 3.0
    assert snap["fed.membership_world"]["values"][0]["value"] == 2.0
    assert snap["fed.membership_reforms_total"]["values"][0]["value"] >= 1.0
    # the PR-12 mirror gauges are retired: service totals live as REAL
    # counters in the service's own registry/artifacts (PR-13), never as
    # worker-side gauges a respawn would under-report through
    assert "fed.membership_shrinks" not in snap
    assert "fed.membership_rejoins" not in snap
    assert "fed.membership_lease_misses" not in snap


# ------------------------------------------------- reform signal plumbing
class _FakeMembership:
    def __init__(self, reform=False):
        self.reform_pending = reform


def _fake_runtime(monkeypatch, num_processes=1, process_id=0, **kw):
    import jax

    from fedrec_tpu.parallel.multihost import CoordinatorRuntime

    monkeypatch.setattr(jax, "process_index", lambda: process_id)
    monkeypatch.setattr(jax, "process_count", lambda: num_processes)
    return CoordinatorRuntime(**kw)


def test_start_round_reform_signal_single_process(monkeypatch):
    from fedrec_tpu.parallel.multihost import REFORM_SIGNAL

    rt = _fake_runtime(
        monkeypatch, membership=_FakeMembership(reform=True), epoch=4
    )
    # mid-run boundary: the server (sole process) emits the reform signal
    assert rt.start_round(2, 5) == REFORM_SIGNAL
    # a finished run stops cleanly even with a reform pending — the
    # rejoiner is not worth re-forming a world that is about to exit
    assert rt.start_round(5, 5) == -1


def test_start_round_without_membership_unchanged(monkeypatch):
    rt = _fake_runtime(monkeypatch)
    assert rt.start_round(2, 5) == 2
    assert rt.start_round(5, 5) == -1


# ---------------------------------------------- degraded-teardown edge
def test_shutdown_barrier_peer_death_flips_degraded(monkeypatch):
    """A peer dying DURING the shutdown barrier: ``degraded`` flips
    mid-teardown and ``jax.distributed.shutdown`` must NOT run — the
    degraded teardown path (finalize's os._exit) owns the exit."""
    import jax

    from fedrec_tpu.parallel import multihost as mh

    rt = _fake_runtime(
        monkeypatch, num_processes=2, process_id=1,
        collective_timeout_s=5.0,
    )

    def broken_barrier(name):
        raise RuntimeError("peer died at the barrier")

    monkeypatch.setattr(
        mh.multihost_utils, "sync_global_devices", broken_barrier
    )
    called = []
    monkeypatch.setattr(jax.distributed, "shutdown", lambda: called.append(1))
    rt._synchronized_shutdown()
    assert rt.degraded is True
    assert rt._shutdown_done is True
    assert called == [], "shutdown ran on a broken world"
    # idempotent: the atexit hook re-entering is a no-op
    rt._synchronized_shutdown()
    assert called == []


def test_shutdown_barrier_hang_is_bounded(monkeypatch):
    """The hang flavor: the barrier never returns; the watchdog (default
    60s when none configured — here stubbed small) degrades instead of
    wedging interpreter exit."""
    import jax

    from fedrec_tpu.parallel import multihost as mh

    rt = _fake_runtime(
        monkeypatch, num_processes=2, process_id=1,
        collective_timeout_s=0.2,
    )
    monkeypatch.setattr(
        mh.multihost_utils, "sync_global_devices",
        lambda name: time.sleep(30),
    )
    called = []
    monkeypatch.setattr(jax.distributed, "shutdown", lambda: called.append(1))
    t0 = time.monotonic()
    rt._synchronized_shutdown()
    assert time.monotonic() - t0 < 5.0
    assert rt.degraded and rt.degraded_by_timeout and called == []


def test_finalize_after_mid_teardown_degrade_exits_devicefree(monkeypatch):
    """finalize() on a world that broke AT the shutdown barrier must take
    the device-free os._exit path (any further teardown would hang or be
    fatally terminated by the coordination client)."""
    import os as _os

    import jax

    from fedrec_tpu.parallel import multihost as mh

    rt = _fake_runtime(
        monkeypatch, num_processes=2, process_id=1,
        collective_timeout_s=5.0,
    )
    monkeypatch.setattr(
        mh.multihost_utils, "sync_global_devices",
        lambda name: (_ for _ in ()).throw(RuntimeError("broken")),
    )
    monkeypatch.setattr(
        jax.distributed, "shutdown",
        lambda: pytest.fail("distributed shutdown ran on a broken world"),
    )

    class _Exited(BaseException):
        pass

    codes = []

    def fake_exit(code):
        codes.append(code)
        raise _Exited

    monkeypatch.setattr(_os, "_exit", fake_exit)
    with pytest.raises(_Exited):
        rt.finalize(0)
    assert codes == [0] and rt.degraded


def test_healthy_shutdown_runs_distributed_teardown(monkeypatch):
    import jax

    from fedrec_tpu.parallel import multihost as mh

    rt = _fake_runtime(
        monkeypatch, num_processes=2, process_id=1,  # non-server: no grace sleep
        collective_timeout_s=5.0,
    )
    monkeypatch.setattr(
        mh.multihost_utils, "sync_global_devices", lambda name: None
    )
    called = []
    monkeypatch.setattr(jax.distributed, "shutdown", lambda: called.append(1))
    rt._synchronized_shutdown()
    assert not rt.degraded and called == [1]


# ----------------------------------------------- rendezvous retry pieces
def test_attempt_address_schedule():
    from fedrec_tpu.parallel.multihost import _attempt_address

    assert _attempt_address(None, 2) is None
    assert _attempt_address("127.0.0.1:5000", 0) == "127.0.0.1:5000"
    assert _attempt_address("127.0.0.1:5000", 2) == "127.0.0.1:5002"


def test_probe_transport_timeout_and_error(monkeypatch):
    from fedrec_tpu.parallel import multihost as mh

    monkeypatch.setattr(
        mh.multihost_utils, "sync_global_devices",
        lambda name: time.sleep(30),
    )
    with pytest.raises(RuntimeError, match="timed out"):
        mh._probe_transport(0.2)
    monkeypatch.setattr(
        mh.multihost_utils, "sync_global_devices",
        lambda name: (_ for _ in ()).throw(ValueError("pair.cc broke")),
    )
    with pytest.raises(RuntimeError, match="probe failed"):
        mh._probe_transport(5.0)


def test_argv_value_helper():
    from fedrec_tpu.cli.coordinator import _argv_value

    assert _argv_value(["--membership", "h:1", "x"], "--membership") == "h:1"
    assert _argv_value(["--membership=h:2"], "--membership") == "h:2"
    assert _argv_value(["--other", "v"], "--membership") is None


# --------------------------------------------------- chaos rejoin holdoff
def test_rejoin_holdoff_marker_guarded(tmp_path):
    from fedrec_tpu.config import ChaosConfig
    from fedrec_tpu.fed.chaos import rejoin_holdoff

    chaos = ChaosConfig(
        enabled=True, kill_process=2, rejoin_delay_s=7.0
    )
    # not yet killed: no holdoff
    assert rejoin_holdoff(chaos, 2, tmp_path) == 0.0
    (tmp_path / "chaos_killed_p2").write_text("3")
    # wrong worker: no holdoff
    assert rejoin_holdoff(chaos, 1, tmp_path) == 0.0
    # the killed worker's first respawn holds off...
    assert rejoin_holdoff(chaos, 2, tmp_path) == 7.0
    assert (tmp_path / "chaos_rejoin_delayed_p2").exists()
    # ...and only the first (reform-driven respawns rejoin immediately)
    assert rejoin_holdoff(chaos, 2, tmp_path) == 0.0
    # disabled chaos: never
    chaos2 = ChaosConfig(enabled=False, kill_process=2, rejoin_delay_s=7.0)
    assert rejoin_holdoff(chaos2, 2, tmp_path) == 0.0


# ------------------------------------------------- ledger resize continuity
def test_ledger_resize_continuity():
    from fedrec_tpu.fed.population import ParticipationLedger

    src = ParticipationLedger(6)
    src.selected[:] = [5, 4, 3, 2, 1, 9]
    src.reported[:] = [4, 4, 2, 2, 1, 8]
    src.quarantine(1, 10)
    src.quarantine(5, 12)
    state = src.state_dict()

    # exact-match restore unchanged
    same = ParticipationLedger(6)
    same.load_state_dict(state)
    np.testing.assert_array_equal(same.selected, src.selected)

    # shrink: counters for surviving ids carry over, out-of-range
    # quarantines drop
    small = ParticipationLedger(4)
    with pytest.raises(ValueError):
        small.load_state_dict(state)
    small.load_state_dict(state, resize=True)
    np.testing.assert_array_equal(small.selected, [5, 4, 3, 2])
    assert small.quarantined == {1: 10}

    # grow: new ids start fresh
    big = ParticipationLedger(8)
    big.load_state_dict(state, resize=True)
    np.testing.assert_array_equal(big.selected, [5, 4, 3, 2, 1, 9, 0, 0])
    assert big.quarantined == {1: 10, 5: 12}


# ------------------------------------------- reshard exactness (catalog)
def test_catalog_recover_and_reshard_exact(rng):
    import jax
    from jax.sharding import Mesh

    from fedrec_tpu.shard import (
        ShardedNewsTable,
        lost_row_mask,
        recover_table_rows,
        reshard_table,
    )

    mesh8 = Mesh(np.array(jax.devices()).reshape(8), ("clients",))
    n, l, d = 100, 4, 8  # 100 rows over 8 shards: padding path
    full = rng.standard_normal((n, l, d)).astype(np.float32)
    tab = ShardedNewsTable.create(full, mesh8, "clients")
    r = tab.spec.rows_per_shard

    # the dead owners' row blocks are gone: poison them in the host copy
    surviving = np.asarray(tab.rows).copy()
    lost = (2, 5)
    for s in lost:
        surviving[s * r:(s + 1) * r] = np.nan

    mask = lost_row_mask(tab.spec, lost)
    assert mask.sum() == sum(
        max(0, min((s + 1) * r, n) - s * r) for s in lost
    )
    rows, recovered = recover_table_rows(surviving, lost, tab.spec, full)
    assert recovered == int(mask.sum()) > 0
    # ACCEPTANCE: no sharded-catalog rows lost across the shrink —
    # bit-exact vs the original table
    np.testing.assert_array_equal(rows, full)

    # commit to the SHRUNK world (8 -> 5 devices, new padding) and pin
    # table[ids] exactness for ids covering lost and surviving rows
    mesh5 = Mesh(np.array(jax.devices()[:5]), ("clients",))
    tab2 = reshard_table(rows, mesh5, "clients")
    assert tab2.spec.num_shards == 5
    ids = rng.integers(0, n, (64,))
    ids[:4] = [2 * r, 2 * r + 1, 5 * r, 5 * r + 1]  # definitely-lost rows
    np.testing.assert_array_equal(
        np.asarray(tab2.rows)[: tab2.spec.num_rows][ids], full[ids]
    )

    # surviving rows came from the LIVE copy, not the checkpoint: feed a
    # divergent checkpoint and check only lost rows read from it
    ckpt2 = full + 1.0
    rows2, _ = recover_table_rows(surviving, lost, tab.spec, ckpt2)
    np.testing.assert_array_equal(rows2[~mask], full[~mask])
    np.testing.assert_array_equal(rows2[mask], ckpt2[mask])

    # no checkpoint + lost rows = a loud failure, never silent loss
    with pytest.raises(ValueError, match="no table checkpoint"):
        recover_table_rows(surviving, lost, tab.spec, None)
    # nothing lost: checkpoint not needed
    rows3, rec3 = recover_table_rows(np.asarray(tab.rows), (), tab.spec, None)
    assert rec3 == 0
    np.testing.assert_array_equal(rows3, full)


def test_table_checkpoint_roundtrip(tmp_path, rng):
    from fedrec_tpu.train.checkpoint import (
        load_table_checkpoint,
        save_table_checkpoint,
    )

    rows = rng.standard_normal((10, 3, 4)).astype(np.float32)
    assert load_table_checkpoint(tmp_path) is None
    save_table_checkpoint(tmp_path, rows)
    back = load_table_checkpoint(tmp_path)
    np.testing.assert_array_equal(back, rows)
    # torn file degrades to None, not a crash
    p = tmp_path / "news_table.npy"
    p.write_bytes(p.read_bytes()[:7])
    assert load_table_checkpoint(tmp_path) is None


# --------------------------------------------- reshard exactness (FSDP)
def test_reshard_state_across_world_change():
    import jax

    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.parallel.mesh import client_mesh, fed_mesh
    from fedrec_tpu.shard import reshard_state

    rng = np.random.default_rng(3)
    state = {
        "w": rng.standard_normal((4, 64, 32)).astype(np.float32),
        "b": rng.standard_normal((4,)).astype(np.float32),
    }

    cfg = ExperimentConfig()
    cfg.fed.num_clients = 4
    cfg.shard.fsdp = 2
    cfg.shard.fsdp_min_size_mb = 0.0
    mesh = fed_mesh(cfg)
    placed = reshard_state(state, mesh, cfg)
    for k in state:
        np.testing.assert_array_equal(np.asarray(placed[k]), state[k])

    # the world shrank: re-commit the host-gathered state to a plain
    # 4-device client mesh (fsdp off) — value-exact re-placement
    cfg2 = ExperimentConfig()
    cfg2.fed.num_clients = 4
    host = jax.tree_util.tree_map(np.asarray, placed)
    placed2 = reshard_state(host, client_mesh(4, max_devices=4), cfg2)
    for k in state:
        np.testing.assert_array_equal(np.asarray(placed2[k]), state[k])


# ------------------------------------------------ report Membership section
def test_report_membership_section():
    from fedrec_tpu.obs.report import build_report, render_text

    def cell(v):
        return {"values": [{"labels": {}, "value": v}]}

    snap = {
        "kind": "registry_snapshot",
        "ts": 0,
        "metrics": {
            "fed.membership_epoch": cell(2.0),
            "fed.membership_world": cell(3.0),
            "fed.membership_shrinks": cell(1.0),
            "fed.membership_rejoins": cell(1.0),
            "fed.membership_lease_misses": cell(1.0),
            "fed.membership_reforms_total": cell(2.0),
            "shard.reshard_seconds": cell(0.25),
            "shard.reshard_rows_recovered_total": cell(100.0),
        },
    }
    report = build_report([], [snap])
    mem = report["membership"]
    assert mem["epoch"] == 2.0 and mem["world"] == 3.0
    assert mem["shrinks"] == 1.0 and mem["rejoins"] == 1.0
    assert mem["reshard_seconds"] == 0.25
    text = render_text(report)
    assert "## Membership" in text
    assert "epoch: 2, world: 3" in text
    assert "shrinks: 1, rejoins: 1" in text
    assert "rows recovered: 100" in text

    # fixed-world run: section absent
    report2 = build_report(
        [], [{"kind": "registry_snapshot", "ts": 0, "metrics": {}}]
    )
    assert "membership" not in report2
    assert "## Membership" not in render_text(report2)


def test_elastic_config_roundtrip():
    from fedrec_tpu.config import ExperimentConfig

    cfg = ExperimentConfig()
    cfg.apply_overrides(
        ["fed.elastic.lease_ms=2500", "fed.elastic.min_world=2",
         "chaos.rejoin_delay_s=9"]
    )
    assert cfg.fed.elastic.lease_ms == 2500.0
    assert cfg.fed.elastic.min_world == 2
    assert cfg.chaos.rejoin_delay_s == 9.0
    back = ExperimentConfig.from_dict(cfg.to_dict())
    assert back.fed.elastic.lease_ms == 2500.0
    assert back.chaos.rejoin_delay_s == 9.0
