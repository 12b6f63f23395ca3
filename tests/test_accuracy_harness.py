"""Wiring smoke tests for the accuracy harness (benchmarks/accuracy_run.py).

The harness is the source of every number in RESULTS.md, and its per-row
config routing has already bitten once: `fed.server_opt`'s default is the
STRING "none" (truthy), and a truthiness check silently pinned every fed
row to the FedAvgM operating point's lr. These tests drive the leg row
CONFIGS (not full training) and one 1-round dp-leg subprocess so routing
regressions fail in CI instead of in a 30-minute artifact run.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "benchmarks"))
sys.path.insert(0, str(REPO))


def test_leg_fed_lr_routing_semantics():
    """The lr operating points route by row, asserted on the RETURNED
    configs (not source text): in particular the fedavgm row — and ONLY
    it — gets the conservative local lr (the server_opt default "none"
    is the truthy STRING; a truthiness check regresses every row), and
    local_1client keeps its own optimum."""
    import accuracy_run as ar

    cfgs = {name: ar.fed_row_cfg(name, rounds=16) for name in ar.FED_ROWS}

    fa = cfgs["param_avg_8_fedavgm"]
    assert fa.fed.server_opt == "sgd"
    assert fa.fed.server_momentum == pytest.approx(0.5), (
        "the fedavgm row runs momentum 0.5 at the shared lr — the best "
        "point of the r5 (server_lr x momentum x local lr) sweep; m=0.9 "
        "collapses at lr 1e-2 and needs crippled 5e-4 locals"
    )
    assert fa.optim.user_lr == pytest.approx(1e-2), (
        "fedavgm trains at the SHARED sweep-optimum local lr since r5"
    )
    assert cfgs["local_1client"].optim.user_lr == pytest.approx(2e-3), (
        "local_1client takes 8x the steps/round of the federated rows; "
        "its measured optimum is 2e-3"
    )
    assert cfgs["cnn_head_8"].model.text_head_arch == "cnn"
    assert cfgs["gru_tower_8"].model.user_tower == "gru"
    for name in ("param_avg_8", "grad_avg_8", "param_avg_32_cohort",
                 "gru_tower_8", "cnn_head_8"):
        assert cfgs[name].fed.server_opt == "none"
        assert cfgs[name].optim.user_lr == pytest.approx(1e-2), (
            f"{name} must train at the shared sweep-optimum lr 1e-2 — a "
            "truthy server_opt check would silently pin it to the "
            "fedavgm operating point"
        )
        assert cfgs[name].optim.news_lr == cfgs[name].optim.user_lr


def test_leg_fed_32_client_step_equalization():
    import accuracy_run as ar

    cfgs = {name: ar.fed_row_cfg(name, rounds=16) for name in ar.FED_ROWS}
    assert cfgs["param_avg_32_cohort"].fed.local_epochs == 4, (
        "the 32-client row must train 4 local epochs (step equalization; "
        "VERDICT r3 #5) — its accuracy claim depends on it"
    )
    assert cfgs["param_avg_8"].fed.local_epochs == 1, (
        "8-client rows stay at 1 local epoch; equalization is the "
        "32-client row's compensation, not a global change"
    )


def test_leg_dp_row_routing_semantics():
    """dp_row_cfg routes the round-5 levers correctly: scope, batch and
    the sigma calibration per row — asserted on returned configs."""
    import accuracy_run as ar

    n_train = 8000
    cfgs = {n: ar.dp_row_cfg(n, rounds=32, n_train=n_train) for n in ar.DP_ROWS}

    assert not cfgs["nodp_tuned"].privacy.enabled
    for name in ("dp_eps50", "dp_eps10", "dp_eps3"):
        c = cfgs[name]
        assert c.privacy.enabled and c.privacy.dp_scope == "all"
        assert c.privacy.sigma > 0 and c.privacy.clip_norm == 1.0
        assert c.data.batch_size == 64
    assert cfgs["dp_eps10_user"].privacy.dp_scope == "user"
    assert cfgs["dp_eps10_user"].privacy.sigma == pytest.approx(
        cfgs["dp_eps10"].privacy.sigma
    ), "scope must not change the calibration (same mechanism, q, steps)"
    froz = cfgs["nodp_user_frozen"].privacy
    assert froz.enabled and froz.dp_scope == "user"
    assert froz.sigma <= 1e-10 and froz.clip_norm >= 1e5, (
        "the ceiling row must be the sigma->0 / inactive-clip limit, i.e. "
        "non-private user-only training"
    )
    # tighter privacy -> larger sigma at the same step budget
    assert (
        cfgs["dp_eps3"].privacy.sigma
        > cfgs["dp_eps10"].privacy.sigma
        > cfgs["dp_eps50"].privacy.sigma
    )
    # batch rows (if present) recalibrate sigma for their own q
    for name, spec in ar.DP_ROWS.items():
        b = spec.get("batch", 64)
        assert cfgs[name].data.batch_size == b
        if spec.get("eps") is not None:
            steps = max((n_train // 8) // b, 1) * 32 * 2
            assert cfgs[name].optim.decay_steps == steps


def test_leg_dp_row_filter_and_artifact_routing(monkeypatch, tmp_path):
    """FEDREC_DP_ROWS runs only the named rows (the chip queue's on-TPU
    proof is anchor+eps10, not the 7-row sweep), and the artifact routes
    to accuracy_dp_tpu.json off-CPU so the chip run can never clobber the
    CPU full-sweep artifact. _train is stubbed: this tests wiring."""
    import accuracy_run as ar

    calls = []

    def fake_train(cfg, data, states, on_round=None):
        calls.append(cfg)
        return {"curve": [{"auc": 0.6, "mrr": 0.3, "ndcg5": 0.3,
                           "ndcg10": 0.4, "round": 0, "train_loss": 1.0}]}

    class _FakeData:
        train_samples = list(range(800))
        valid_samples = list(range(100))
        num_news = 64

    monkeypatch.setattr(ar, "_train", fake_train)
    monkeypatch.setattr(ar, "HERE", tmp_path)
    monkeypatch.setattr(ar, "oracle_auc", lambda d, s: 0.77)
    monkeypatch.setattr(ar, "_small_corpus", lambda: (_FakeData(), None))
    monkeypatch.setenv("FEDREC_DP_ROWS", "nodp_tuned,dp_eps10")
    ar.leg_dp(rounds=1)
    assert len(calls) == 2
    # ANY subset — even a wedge CPU-fallback of the chip queue item —
    # writes the sidecar name, never the canonical full-sweep artifact
    art = json.loads((tmp_path / "accuracy_dp_tpu.json").read_text())
    assert set(art["runs"]) == {"nodp_tuned", "dp_eps10"}
    assert set(art["gap_to_anchor"]) == {"dp_eps10"}
    assert "user_frozen_ceiling_auc" not in art
    assert not (tmp_path / "accuracy_dp.json").exists()
    # a typo fails fast, before any training
    calls.clear()
    monkeypatch.setenv("FEDREC_DP_ROWS", "dp_eps_10")
    with pytest.raises(SystemExit, match="unknown rows"):
        ar.leg_dp(rounds=1)
    assert not calls
    # the anchor is auto-included when omitted
    calls.clear()
    monkeypatch.setenv("FEDREC_DP_ROWS", "dp_eps10")
    ar.leg_dp(rounds=1)
    assert len(calls) == 2
    # the full sweep on cpu owns the canonical artifact name
    calls.clear()
    monkeypatch.delenv("FEDREC_DP_ROWS")
    ar.leg_dp(rounds=1)
    assert len(calls) == len(ar.DP_ROWS)
    art = json.loads((tmp_path / "accuracy_dp.json").read_text())
    assert set(art["runs"]) == set(ar.DP_ROWS)


@pytest.mark.slow
def test_leg_dp_one_round_writes_schema(tmp_path):
    """One-round dp leg end-to-end in a subprocess: the artifact lands
    with the sweep rows, recipe record, non-private anchor, and gap
    fields. The harness writes its artifact at a fixed path next to
    itself, so the real artifact is backed up and restored around the
    run."""
    from fedrec_tpu.hostenv import cpu_host_env

    art = REPO / "benchmarks" / "accuracy_dp.json"
    backup = art.read_bytes() if art.exists() else None
    env = cpu_host_env(8)
    env["FEDREC_ACC_INNER"] = "1"
    env.pop("FEDREC_DP_ROWS", None)  # ambient filter would break the sweep
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    try:
        proc = subprocess.run(
            [sys.executable, str(REPO / "benchmarks" / "accuracy_run.py"),
             "--leg", "dp", "--dp-rounds", "1"],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=900,
        )
        assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
        import accuracy_run as ar

        d = json.loads(art.read_text())
        assert set(d["runs"]) == set(ar.DP_ROWS)
        assert d["recipe"]["lr_schedule"] == "cosine"
        assert d["recipe"]["clip_norm"] == 1.0
        eps_rows = {
            n for n, spec in ar.DP_ROWS.items() if spec.get("eps") is not None
        }
        # every dp row calibrated a sigma and recorded its epsilon + scope
        for name, run in d["runs"].items():
            if name in eps_rows:
                assert run["sigma"] > 0 and run["epsilon"] > 0
            assert run["dp_scope"] in ("all", "user")
            assert run["batch_size"] >= 1
        assert set(d["gap_to_anchor"]) == eps_rows
        assert d["user_frozen_ceiling_auc"] > 0
    finally:
        if backup is not None:
            art.write_bytes(backup)
        else:
            # no real artifact existed before the test: remove the 1-round
            # test artifact so write_report can never publish it as a real
            # DP sweep
            art.unlink(missing_ok=True)


def test_leg_dp_partial_flag_lifecycle(monkeypatch, tmp_path):
    """Each trained row stamps the artifact with "partial": true (a run
    killed mid-leg keeps its completed rows as labeled evidence); the
    completed leg drops the flag."""
    import accuracy_run as ar

    seen_flags = []

    def fake_train(cfg, data, states, on_round=None):
        return {"curve": [{"auc": 0.6, "mrr": 0.3, "ndcg5": 0.3,
                           "ndcg10": 0.4, "round": 0, "train_loss": 1.0}]}

    class _FakeData:
        train_samples = list(range(800))
        valid_samples = list(range(100))
        num_news = 64

    monkeypatch.setattr(ar, "_train", fake_train)
    monkeypatch.setattr(ar, "HERE", tmp_path)
    monkeypatch.setattr(ar, "oracle_auc", lambda d, s: 0.77)
    monkeypatch.setattr(ar, "_small_corpus", lambda: (_FakeData(), None))
    monkeypatch.setenv("FEDREC_DP_ROWS", "nodp_tuned,dp_eps10")

    art_path = tmp_path / "accuracy_dp_tpu.json"

    # observe each stamped state by wrapping the writer at its source
    import fedrec_tpu.utils.provenance as prov

    real = prov.write_artifact

    def spy(path, payload, partial):
        seen_flags.append(partial)
        real(path, payload, partial)

    monkeypatch.setattr(prov, "write_artifact", spy)
    ar.leg_dp(rounds=1)
    # one partial stamp per row, then the completing stamp
    assert seen_flags == [True, True, False]
    assert "partial" not in json.loads(art_path.read_text())
    # partial stamps staged in the sidecar, removed on completion — a
    # wedged re-run must never clobber banked complete evidence
    assert not (tmp_path / "accuracy_dp_tpu.inprogress.json").exists()


def test_write_report_skips_partial_artifacts(monkeypatch, tmp_path, capsys):
    """A partial artifact (incremental stamp of a run that never finished)
    must be excluded from RESULTS.md generation instead of KeyError-ing on
    its missing summary fields."""
    import accuracy_run as ar

    # minimal COMPLETE central artifact so the report has something to say
    (tmp_path / "accuracy_central.json").write_text(json.dumps({
        "leg": "central", "platform": "cpu", "device": "cpu",
        "corpus": {"num_news": 1, "train": 1, "valid": 1, "bert_hidden": 8},
        "oracle_auc": 0.7, "rounds_requested": 1,
        "config": {"mode": "head", "dtype": "float32", "lr": 1e-3,
                   "batch": 8},
        "curve": [{"round": 0, "train_loss": 1.0, "auc": 0.6, "mrr": 0.3,
                   "ndcg5": 0.3, "ndcg10": 0.4}],
        "wall_s": 1.0,
    }))
    # a PARTIAL bf16 artifact missing final_auc/auc_delta
    (tmp_path / "accuracy_bf16.json").write_text(json.dumps({
        "partial": True, "leg": "bf16", "platform": "tpu", "runs": {},
    }))
    monkeypatch.setattr(ar, "HERE", tmp_path)
    fake_repo = tmp_path / "repo"
    fake_repo.mkdir()
    monkeypatch.setattr(ar, "REPO", fake_repo)
    ar.write_report()
    report = (fake_repo / "RESULTS.md").read_text()
    assert "## Dtype tolerance" not in report
    assert "skipping accuracy_bf16.json" in capsys.readouterr().err
