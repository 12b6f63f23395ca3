"""Fleet-wide observability (``fedrec_tpu.obs.fleet``): correlation
keys, the telemetry collector (push / merge / late joiner / torn
connection), the offline ``worker_*`` merge, clock-offset estimation on
hand-made traces with KNOWN skew, straggler attribution on synthetic
span sets with a KNOWN critical path, counter-baseline continuity, and
the membership service's own artifact trio."""

from __future__ import annotations

import json
import socket
import threading
import time

import pytest

from fedrec_tpu.obs.fleet import (
    CollectorServer,
    FleetPusher,
    TelemetryCollector,
    WorkerData,
    WorkerTrace,
    attribute_critical_path,
    build_fleet_report,
    build_fleet_trace,
    counter_baseline,
    ensure_fleet_identity,
    estimate_clock_offsets,
    get_fleet_identity,
    load_fleet_dir,
    render_fleet_text,
    reset_fleet_identity,
    restore_counter_baseline,
    save_counter_baseline,
    set_fleet_identity,
)
from fedrec_tpu.obs.registry import MetricsRegistry, set_registry
from fedrec_tpu.obs.tracing import Tracer, set_tracer


@pytest.fixture()
def fresh_obs():
    """Swap in a fresh default registry/tracer and clear the process
    fleet identity, restoring everything afterwards."""
    prev_reg = set_registry(MetricsRegistry())
    prev_tr = set_tracer(Tracer())
    reset_fleet_identity()
    try:
        yield
    finally:
        reset_fleet_identity()
        set_registry(prev_reg)
        set_tracer(prev_tr)


# ------------------------------------------------------- correlation keys
def test_identity_stamps_spans_snapshots_and_records(fresh_obs, tmp_path):
    import io

    from fedrec_tpu.obs import get_registry, get_tracer
    from fedrec_tpu.utils.logging import MetricLogger

    set_fleet_identity("w3", rank=1, epoch=2)
    tracer = get_tracer()
    with tracer.span("fed_round", step_num=0):
        pass
    ev = tracer.events()[-1]
    assert ev["args"]["worker"] == "w3"
    assert ev["args"]["rank"] == 1
    assert ev["args"]["membership_epoch"] == 2
    assert ev["args"]["step_num"] == 0  # explicit args survive the merge

    snap = get_registry().snapshot()
    assert snap["fleet"] == {"worker": "w3", "rank": 1, "membership_epoch": 2}

    jsonl = tmp_path / "metrics.jsonl"
    logger = MetricLogger(stream=io.StringIO(), jsonl_path=str(jsonl))
    logger.log(0, {"round": 0, "training_loss": 1.0})
    rec = json.loads(jsonl.read_text().splitlines()[0])
    assert rec["worker"] == "w3" and rec["rank"] == 1
    assert rec["membership_epoch"] == 2
    assert rec["training_loss"] == 1.0


def test_ensure_identity_first_writer_wins(fresh_obs):
    set_fleet_identity("coordinator-stamped", rank=5)
    ident = ensure_fleet_identity(worker="0", rank=0)
    assert ident["worker"] == "coordinator-stamped"
    assert get_fleet_identity()["rank"] == 5


def test_no_identity_means_no_labels(fresh_obs):
    from fedrec_tpu.obs import get_registry, get_tracer

    with get_tracer().span("x"):
        pass
    assert "args" not in get_tracer().events()[-1]
    assert "fleet" not in get_registry().snapshot()


# ------------------------------------------------------- synthetic traces
def _mk_trace(epoch_unix, rounds, round_s, skew_s=0.0, phases=None,
              num_rounds=1, spacing=0.05):
    """Hand-made incarnation: one fed_round span per round (duration
    ``round_s[r]``), each preceded by optional phase child spans.  Round
    r starts at the shared barrier cadence ``i * spacing``; ``skew_s``
    shifts this incarnation's LOCAL clock (its epoch_unix stays
    truthful-looking but events land skewed — the drift the barrier
    alignment corrects)."""
    events = []
    for i, r in enumerate(rounds):
        start = i * spacing + skew_s
        dur = round_s[i]
        args = {"step_num": r}
        if num_rounds > 1:
            args["num_rounds"] = num_rounds
        for name, frac in (phases or {}).items():
            events.append({
                "name": name, "ph": "X", "ts": start * 1e6,
                "dur": dur * frac * 1e6, "pid": 1, "tid": 1,
            })
        events.append({
            "name": "fed_round", "ph": "X", "ts": start * 1e6,
            "dur": dur * 1e6, "pid": 1, "tid": 1, "args": args,
        })
    return WorkerTrace(epoch_unix=epoch_unix, events=events)


def test_clock_offset_recovers_known_skew():
    base = 1_000_000.0
    ref = _mk_trace(base, [0, 1, 2, 3], [0.01] * 4)
    # worker B's clock runs 5.0s ahead (epoch_unix identical, events
    # skewed): the barrier refinement must recover -5.0s
    skewed = _mk_trace(base, [0, 1, 2, 3], [0.01] * 4, skew_s=5.0)
    workers = {
        "0": WorkerData(worker="0", traces=[ref]),
        "1": WorkerData(worker="1", traces=[skewed]),
    }
    offsets = estimate_clock_offsets(workers)
    assert offsets[("0", 0)] == 0.0
    assert offsets[("1", 0)] == pytest.approx(-5.0, abs=1e-6)

    doc = build_fleet_trace(workers)
    starts = {}
    for e in doc["traceEvents"]:
        if e.get("name") == "fed_round":
            starts.setdefault(e["args"]["worker"], []).append(e["ts"])
    # after alignment both workers' round starts coincide
    for a, b in zip(sorted(starts["0"]), sorted(starts["1"])):
        assert a == pytest.approx(b, abs=1.0)  # µs


def test_clock_offset_no_shared_rounds_falls_back_to_wall():
    a = _mk_trace(1000.0, [0, 1], [0.01] * 2)
    b = _mk_trace(2000.0, [], [])
    b.events = [{"name": "membership_epoch_formed", "ph": "i", "ts": 0.0,
                 "pid": 1, "tid": 1, "args": {"epoch": 1, "world": 3}}]
    workers = {
        "0": WorkerData(worker="0", traces=[a]),
        "svc": WorkerData(worker="svc", traces=[b]),
    }
    offsets = estimate_clock_offsets(workers)
    assert offsets[("svc", 0)] == 0.0  # wall-clock anchor only


# -------------------------------------------------- straggler attribution
def test_critical_path_known_straggler():
    fast = _mk_trace(
        1000.0, [0, 1, 2], [0.010, 0.010, 0.010],
        phases={"dispatch": 0.8, "batch_build": 0.1},
    )
    # worker 1 gates round 1 only (3x slower), dominated by dispatch
    slow = _mk_trace(
        1000.0, [0, 1, 2], [0.010, 0.030, 0.010],
        phases={"dispatch": 0.8, "batch_build": 0.1},
    )
    workers = {
        "0": WorkerData(worker="0", traces=[fast]),
        "1": WorkerData(worker="1", traces=[slow]),
    }
    rows = attribute_critical_path(workers)
    assert [r["round"] for r in rows] == [0, 1, 2]
    r1 = rows[1]
    assert r1["critical_worker"] == "1"
    assert r1["phase"] == "dispatch"
    assert r1["gate_ms"] == pytest.approx(20.0, rel=0.2)
    assert set(r1["workers"]) == {"0", "1"}

    report = build_fleet_report(workers)
    assert report["critical_path"]["1"]["rounds"] >= 1
    text = render_fleet_text(report)
    assert "## Critical path (per round)" in text
    assert "Times on critical path" in text


def test_critical_path_chunked_rounds_split_evenly():
    # an older trace's one span covering rounds 0-2 on worker 0 vs
    # per-round spans on worker 1: every round still gets attributed
    chunk = _mk_trace(1000.0, [0], [0.03], num_rounds=3)
    per = _mk_trace(1000.0, [0, 1, 2], [0.002, 0.002, 0.002])
    workers = {
        "0": WorkerData(worker="0", traces=[chunk]),
        "1": WorkerData(worker="1", traces=[per]),
    }
    rows = attribute_critical_path(workers)
    assert [r["round"] for r in rows] == [0, 1, 2]
    assert all(set(r["workers"]) == {"0", "1"} for r in rows)


def test_gate_ms_is_marginal_delay_over_runner_up():
    # 3 workers ending at 10/11/14 ms: gate_ms is the straggler's
    # MARGINAL delay over the runner-up (14-11=3), NOT the fastest
    # member's total wait (14-10=4)
    workers = {
        "0": WorkerData(worker="0", traces=[_mk_trace(1000.0, [0], [0.010])]),
        "1": WorkerData(worker="1", traces=[_mk_trace(1000.0, [0], [0.011])]),
        "2": WorkerData(worker="2", traces=[_mk_trace(1000.0, [0], [0.014])]),
    }
    rows = attribute_critical_path(workers)
    assert rows[0]["critical_worker"] == "2"
    assert rows[0]["gate_ms"] == pytest.approx(3.0, abs=1e-6)


def test_single_worker_degrades_gracefully():
    tr = _mk_trace(1000.0, [0, 1], [0.01, 0.01])
    workers = {"0": WorkerData(worker="0", traces=[tr])}
    rows = attribute_critical_path(workers)
    assert all(r["critical_worker"] == "0" for r in rows)
    assert all(r["gate_ms"] == 0.0 for r in rows)


# ------------------------------------------------------------- collector
def _push_worker(address, wid, rounds=2, slow=False):
    reg = MetricsRegistry()
    reg.set_context(worker=wid, rank=int(wid))
    tr = Tracer()
    reg.counter("train.rounds_total", "rounds").inc(rounds)
    for r in range(rounds):
        start = tr.now()
        with tr.span("dispatch", kind="step", n=1):
            time.sleep(0.02 if slow else 0.002)
        tr.add_span("fed_round", dur_s=tr.now() - start, step_num=r)
    p = FleetPusher(address, worker=wid, registry=reg, tracer=tr)
    assert p.push()
    return p


def test_collector_push_merge_and_report(tmp_path):
    col = TelemetryCollector(tmp_path / "fleet")
    srv = CollectorServer(col).start()
    try:
        _push_worker(srv.address, "0")
        _push_worker(srv.address, "1", slow=True)
        st = col.status()
        assert st["pushes"] == 2
        assert set(st["workers"]) == {"0", "1"}
    finally:
        srv.stop()
    workers = load_fleet_dir(tmp_path / "fleet")
    assert set(workers) == {"0", "1"}
    assert workers["1"].last_snapshot()["fleet"]["worker"] == "1"
    report = build_fleet_report(workers)
    assert len(report["rounds"]) == 2
    assert all(r["critical_worker"] == "1" for r in report["rounds"])
    doc = build_fleet_trace(workers)
    assert doc["otherData"]["workers"] == {"0": 1, "1": 2}
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"fed_round", "dispatch", "process_name"} <= names


def test_collector_incremental_pushes_are_disjoint(tmp_path):
    col = TelemetryCollector(tmp_path)
    srv = CollectorServer(col).start()
    try:
        reg, tr = MetricsRegistry(), Tracer()
        p = FleetPusher(srv.address, worker="7", registry=reg, tracer=tr)
        with tr.span("fed_round", step_num=0):
            pass
        assert p.push()
        with tr.span("fed_round", step_num=1):
            pass
        assert p.push(final=True)
    finally:
        srv.stop()
    w = load_fleet_dir(tmp_path)["7"]
    spans = [e for t in w.traces for e in t.events
             if e["name"] == "fed_round"]
    # two pushes, two spans total — the second push shipped ONLY the new one
    assert len(spans) == 2
    assert sorted(s["args"]["step_num"] for s in spans) == [0, 1]


def test_collector_late_joiner(tmp_path):
    col = TelemetryCollector(tmp_path)
    srv = CollectorServer(col).start()
    try:
        _push_worker(srv.address, "0")
        time.sleep(0.05)
        _push_worker(srv.address, "2")  # joins after worker 0 finished
    finally:
        srv.stop()
    assert set(load_fleet_dir(tmp_path)) == {"0", "2"}


def test_collector_survives_torn_connection(tmp_path):
    col = TelemetryCollector(tmp_path)
    srv = CollectorServer(col).start()
    try:
        # half a JSON line, then hang up
        with socket.create_connection(("127.0.0.1", srv.port), timeout=5):
            pass
        with socket.create_connection(
            ("127.0.0.1", srv.port), timeout=5
        ) as c:
            c.sendall(b'{"cmd": "telemetry_pu')
        # garbage line
        with socket.create_connection(
            ("127.0.0.1", srv.port), timeout=5
        ) as c:
            c.sendall(b"not json at all\n")
            assert b"error" in c.recv(65536)
        # the collector still works afterwards
        _push_worker(srv.address, "0")
    finally:
        srv.stop()
    assert set(load_fleet_dir(tmp_path)) == {"0"}


def test_pusher_counts_failures_never_raises(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
    reg, tr = MetricsRegistry(), Tracer()
    p = FleetPusher(f"127.0.0.1:{dead_port}", worker="0",
                    registry=reg, tracer=tr, timeout_s=0.5)
    with tr.span("fed_round", step_num=0):
        pass
    assert p.push() is False
    assert p.failures == 1
    assert reg.counter("obs.fleet_push_failures_total").value() == 1.0
    # the unacknowledged events are NOT marked sent: a later successful
    # push would re-ship them
    assert p._sent_events == 0


def test_pusher_treats_empty_ack_as_failure():
    # a server that accepts and hangs up without a response line is NOT
    # an ack: the spans must stay unsent (re-shipped by the next push)
    srv = socket.create_server(("127.0.0.1", 0))
    port = srv.getsockname()[1]

    def hang_up():
        conn, _ = srv.accept()
        conn.recv(1 << 20)
        conn.close()

    t = threading.Thread(target=hang_up, daemon=True)
    t.start()
    try:
        reg, tr = MetricsRegistry(), Tracer()
        p = FleetPusher(f"127.0.0.1:{port}", worker="0",
                        registry=reg, tracer=tr, timeout_s=2.0)
        with tr.span("fed_round", step_num=0):
            pass
        assert p.push() is False
        assert p.failures == 1
        assert p._sent_events == 0
    finally:
        t.join(5)
        srv.close()


def test_pusher_backs_off_after_consecutive_failures():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
    reg, tr = MetricsRegistry(), Tracer()
    p = FleetPusher(f"127.0.0.1:{dead_port}", worker="0",
                    registry=reg, tracer=tr, timeout_s=0.2)
    for _ in range(p._BACKOFF_AFTER):
        assert p.push() is False
    assert p.failures == p._BACKOFF_AFTER
    # backoff engaged: round-cadence pushes SKIP (no new connect attempt,
    # so the failure counter stays put and no round stalls on the timeout)
    assert p.push() is False
    assert p.failures == p._BACKOFF_AFTER
    # ...but the once-per-run final push still tries — with ONE bounded
    # retry, so a dead endpoint costs exactly two counted attempts
    p._FINAL_RETRY_DELAY_S = 0.0
    assert p.push(final=True) is False
    assert p.failures == p._BACKOFF_AFTER + 2


def test_membership_server_routes_telemetry(tmp_path):
    from fedrec_tpu.parallel.membership import MembershipServer

    col = TelemetryCollector(tmp_path)
    srv = MembershipServer(target_world=1, collector=col).start()
    try:
        reg, tr = MetricsRegistry(), Tracer()
        with tr.span("fed_round", step_num=0):
            pass
        p = FleetPusher(srv.address, worker="0", registry=reg, tracer=tr)
        assert p.push()
        assert col.status()["pushes"] == 1
    finally:
        srv.stop()
    assert set(load_fleet_dir(tmp_path)) == {"0"}


def test_membership_server_without_collector_errors():
    from fedrec_tpu.parallel.membership import (
        MembershipClient,
        MembershipError,
        MembershipServer,
    )

    srv = MembershipServer(target_world=1).start()
    try:
        c = MembershipClient(srv.address, worker_id="x")
        with pytest.raises(MembershipError, match="telemetry collector"):
            c._call({"cmd": "telemetry_status"})
    finally:
        srv.stop()


# ------------------------------------------------------- offline fallback
def _write_worker_dir(root, wid, rounds, round_s, counters=None):
    reg = MetricsRegistry()
    reg.set_context(worker=wid, rank=int(wid))
    for name, v in (counters or {}).items():
        reg.counter(name).inc(v)
    tr = Tracer()
    for i, r in enumerate(rounds):
        start = tr.now()
        time.sleep(round_s[i])
        tr.add_span("fed_round", dur_s=tr.now() - start, step_num=r)
    d = root / f"worker_{wid}"
    d.mkdir(parents=True)
    reg.write_snapshot(d / "metrics.jsonl")
    tr.save(d / "trace.json")
    return d


def test_offline_worker_merge(tmp_path):
    _write_worker_dir(tmp_path, "0", [0, 1], [0.002, 0.002],
                      counters={"train.rounds_total": 2})
    _write_worker_dir(tmp_path, "1", [0, 1], [0.002, 0.01],
                      counters={"train.rounds_total": 2})
    workers = load_fleet_dir(tmp_path)
    assert set(workers) == {"0", "1"}
    report = build_fleet_report(workers)
    assert report["workers"]["0"]["rounds_total"] == 2
    assert report["rounds"][1]["critical_worker"] == "1"


def test_single_obs_dir_is_worker_zero(tmp_path):
    d = _write_worker_dir(tmp_path, "5", [0], [0.002])
    workers = load_fleet_dir(d)  # point AT the worker dir itself
    assert set(workers) == {"0"}
    assert len(workers["0"].traces) == 1


def test_tagged_incarnation_traces_win_over_latest(tmp_path):
    from fedrec_tpu.obs.report import dump_artifacts

    reg, tr = MetricsRegistry(), Tracer()
    with tr.span("fed_round", step_num=0):
        pass
    d = tmp_path / "worker_0"
    paths = dump_artifacts(d, registry=reg, tracer=tr, trace_tag="e0")
    assert "trace_tagged" in paths
    with tr.span("fed_round", step_num=1):
        pass
    dump_artifacts(d, registry=reg, tracer=tr, trace_tag="e1")
    w = load_fleet_dir(tmp_path)["0"]
    # the two tagged incarnations load; trace.json (a duplicate of the
    # newest tag) is skipped — no double-counted spans
    assert [t.tag for t in w.traces] == ["e0", "e1"]
    rounds = [e["args"]["step_num"] for t in w.traces for e in t.events
              if e["name"] == "fed_round"]
    assert sorted(rounds) == [0, 0, 1]


def test_load_fleet_dir_operator_errors(tmp_path):
    with pytest.raises(FileNotFoundError, match="no such directory"):
        load_fleet_dir(tmp_path / "nope")
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(FileNotFoundError, match="neither worker_"):
        load_fleet_dir(empty)


# -------------------------------------------------------- fleet CLI
def test_fleet_cli_report_and_trace(tmp_path, capsys):
    from fedrec_tpu.cli.obs import main as obs_main

    _write_worker_dir(tmp_path, "0", [0, 1], [0.002, 0.002])
    _write_worker_dir(tmp_path, "1", [0, 1], [0.002, 0.008])
    assert obs_main(["fleet", str(tmp_path), "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert set(report["workers"]) == {"0", "1"}
    assert all("critical_worker" in r for r in report["rounds"])

    out = tmp_path / "merged.json"
    assert obs_main(["fleet-trace", str(tmp_path), "-o", str(out)]) == 0
    doc = json.loads(out.read_text())
    assert len(doc["otherData"]["workers"]) == 2
    ts = [e["ts"] for e in doc["traceEvents"] if e.get("ph") != "M"]
    assert ts == sorted(ts)

    assert obs_main(["fleet", str(tmp_path / "missing")]) == 2


# ------------------------------------------------------ counter baselines
def test_counter_baseline_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("train.rounds_total", "rounds").inc(7)
    labeled = reg.counter("chaos.faults_total", "faults", labels=("kind",))
    labeled.inc(3, kind="drop")
    labeled.inc(2, kind="nan")
    reg.gauge("train.round_loss").set(1.5)  # gauges are NOT baselined
    save_counter_baseline(tmp_path, registry=reg, epoch=2)

    fresh = MetricsRegistry()
    epoch = restore_counter_baseline(tmp_path, registry=fresh)
    assert epoch == 2
    assert fresh.counter("train.rounds_total").value() == 7.0
    c = fresh.counter("chaos.faults_total", labels=("kind",))
    assert c.value(kind="drop") == 3.0
    assert c.value(kind="nan") == 2.0
    assert fresh.get("train.round_loss") is None

    # the respawned incarnation keeps counting — totals stay monotone
    fresh.counter("train.rounds_total").inc(3)
    assert fresh.counter("train.rounds_total").value() == 10.0


def test_counter_baseline_preserves_label_declaration_order(tmp_path):
    # label names NOT in alphabetical order: the restored registration
    # must keep declaration order, or the production re-registration that
    # follows would hit the registry's label-tuple identity check
    reg = MetricsRegistry()
    c = reg.counter("net.bytes_total", "b", labels=("path", "direction"))
    c.inc(9, path="dcn", direction="up")
    save_counter_baseline(tmp_path, registry=reg)

    fresh = MetricsRegistry()
    restore_counter_baseline(tmp_path, registry=fresh)
    # the production code registers with its own declaration order —
    # this must NOT raise, and the restored total must be visible
    c2 = fresh.counter("net.bytes_total", "b", labels=("path", "direction"))
    assert c2.value(path="dcn", direction="up") == 9.0


def test_counter_baseline_missing_and_torn(tmp_path):
    assert restore_counter_baseline(tmp_path) is None
    (tmp_path / "counters.json").write_text('{"kind": "counter_base')
    assert restore_counter_baseline(tmp_path, registry=MetricsRegistry()) is None


def test_counter_baseline_report_monotone(tmp_path):
    """The satellite contract: fedrec-obs report totals resume (not
    reset) across a respawn that restored the baseline."""
    d = tmp_path / "worker_0"
    reg = MetricsRegistry()
    reg.counter("train.rounds_total", "rounds").inc(5)
    reg.write_snapshot(d.mkdir(parents=True) or d / "metrics.jsonl")
    save_counter_baseline(d, registry=reg, epoch=0)

    # "respawn": a fresh registry restores the baseline, trains 2 more
    # rounds, appends its snapshot to the SAME event log
    reg2 = MetricsRegistry()
    restore_counter_baseline(d, registry=reg2)
    reg2.counter("train.rounds_total", "rounds").inc(2)
    reg2.write_snapshot(d / "metrics.jsonl")

    from fedrec_tpu.obs.report import load_jsonl, snapshot_value

    _, snaps = load_jsonl(d / "metrics.jsonl")
    totals = [snapshot_value(s, "train.rounds_total") for s in snaps]
    assert totals == [5.0, 7.0]
    assert totals == sorted(totals)


# ------------------------------------------- membership service artifacts
def test_membership_service_writes_own_trio(fresh_obs, tmp_path):
    from fedrec_tpu.parallel.membership import MembershipClient, MembershipServer

    obs_dir = tmp_path / "worker_membership"
    srv = MembershipServer(
        target_world=2, lease_ms=500, heartbeat_ms=100,
        formation_grace_ms=300, obs_dir=str(obs_dir),
    ).start()
    try:
        res = {}
        threads = [
            threading.Thread(
                target=lambda w=w: res.update({
                    w: MembershipClient(
                        srv.address, worker_id=w, join_timeout_s=10
                    ).join()
                })
            )
            for w in ("0", "1")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        assert res["0"].world == 2
    finally:
        srv.stop()
    for f in ("metrics.jsonl", "trace.json", "prometheus.txt"):
        assert (obs_dir / f).stat().st_size > 0
    prom = (obs_dir / "prometheus.txt").read_text()
    assert "fed_membership_shrinks_total" in prom
    assert "fed_membership_world" in prom
    # the service dir merges into the fleet like any worker
    workers = load_fleet_dir(tmp_path)
    assert "membership" in workers
    names = {e["name"] for t in workers["membership"].traces
             for e in t.events}
    assert "membership_epoch_formed" in names
    report = build_fleet_report(workers)
    assert report["workers"]["membership"]["role"] == "membership_service"
    assert report["membership"]["epoch_history"][0]["world"] == 2


def test_membership_shrink_counts_in_service_registry(fresh_obs):
    from fedrec_tpu.obs import get_registry
    from fedrec_tpu.parallel.membership import MembershipClient, MembershipServer

    srv = MembershipServer(
        target_world=2, lease_ms=300, heartbeat_ms=100,
        formation_grace_ms=200, min_world=1,
    ).start()
    try:
        res = {}
        threads = [
            threading.Thread(
                target=lambda w=w: res.update({
                    w: MembershipClient(
                        srv.address, worker_id=w, join_timeout_s=10
                    ).join()
                })
            )
            for w in ("0", "1")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        # worker 1 goes silent: lease expires, worker 0 re-joins alone —
        # the next epoch forms SMALLER (the shrink-and-continue path)
        c0 = MembershipClient(srv.address, worker_id="0", join_timeout_s=15)
        asg = c0.join()
        assert asg.world == 1
        reg = get_registry()
        assert reg.counter("fed.membership_shrinks_total").value() == 1.0
        assert reg.counter(
            "fed.membership_lease_misses_total"
        ).value() >= 1.0
    finally:
        srv.stop()
