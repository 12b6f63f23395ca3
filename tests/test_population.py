"""Cross-device cohort engine (ISSUE 6): logical-client population,
seeded cohort sampling, over-selection, round deadlines, quorum replays.

Acceptance pins:
* degenerate config (population == world, over_select=1.0, no deadline)
  reproduces the no-population trajectory BIT-identically;
* a sampled run under seeded dropout replays bit-identically from the
  chaos seed (cohort schedule AND parameters);
* sampler + participation ledger survive checkpoint restore: the
  post-resume cohort schedule is identical to an uninterrupted run
  (and with ``client_state="reset"`` the parameters are too);
* robust aggregation (trimmed_mean/median) trims over the REPORTING
  mask — dropped/deadline-cut clients never consume a trim slot.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax

from fedrec_tpu.config import ExperimentConfig
from fedrec_tpu.data import make_synthetic_mind
from fedrec_tpu.fed.chaos import FaultPlan, population_report
from fedrec_tpu.fed.population import (
    ClientPopulation,
    ParticipationLedger,
    QuorumFailure,
    build_cohort_plan,
    plan_round_weights,
)
from fedrec_tpu.fed.sampling import CohortSampler, validate_sampler_mode
from fedrec_tpu.obs import MetricsRegistry, Tracer, set_registry, set_tracer


# ------------------------------------------------------------- sampler
def test_sampler_draws_are_deterministic_and_distinct():
    a = CohortSampler(100, "uniform", seed=3)
    b = CohortSampler(100, "uniform", seed=3)
    d1, d2 = a.draw(5, 8), b.draw(5, 8)
    np.testing.assert_array_equal(d1, d2)
    assert len(np.unique(d1)) == 8  # without replacement
    # a different round, seed, or attempt rolls fresh dice
    assert not np.array_equal(d1, a.draw(6, 8))
    assert not np.array_equal(d1, CohortSampler(100, "uniform", seed=4).draw(5, 8))
    assert not np.array_equal(d1, a.draw(5, 8, attempt=1))


def test_sampler_full_coverage_keeps_ascending_ids():
    """The degenerate contract: k covering the whole eligible population
    returns ascending ids, so population == slots packs identity."""
    s = CohortSampler(8, "uniform", seed=0)
    np.testing.assert_array_equal(s.draw(0, 8), np.arange(8))
    np.testing.assert_array_equal(s.draw(0, 99), np.arange(8))
    np.testing.assert_array_equal(
        s.draw(0, 7, exclude={3}), [0, 1, 2, 4, 5, 6, 7]
    )


def test_sampler_exclusion_never_draws_quarantined():
    s = CohortSampler(32, "uniform", seed=1)
    for r in range(20):
        drawn = s.draw(r, 8, exclude={5, 9, 20})
        assert not ({5, 9, 20} & set(drawn.tolist()))
    assert s.draw(0, 4, exclude=set(range(32))).size == 0


def test_sampler_weighted_favors_data_rich_clients():
    counts = np.ones(64, np.int64)
    counts[:8] = 1000  # 8 data-rich clients
    s = CohortSampler(64, "weighted", seed=0, sample_counts=counts)
    hits = sum(int((s.draw(r, 8) < 8).sum()) for r in range(50))
    # uniform would select ~1 of the rich 8 per round (50 total)
    assert hits > 150


def test_sampler_skew_flattens_selection_histogram():
    uni = CohortSampler(64, "uniform", seed=0)
    skew = CohortSampler(64, "skew", seed=0)
    for r in range(60):
        for s in (uni, skew):
            c = s.draw(r, 8)
            s.record(c)
    # coverage sampling touches (nearly) everyone; uniform leaves a tail
    assert (skew.selection_counts > 0).sum() >= (uni.selection_counts > 0).sum()
    assert np.std(skew.selection_counts) < np.std(uni.selection_counts)


def test_sampler_state_roundtrip_resumes_identical_schedule():
    a = CohortSampler(64, "skew", seed=9)
    for r in range(5):
        a.record(a.draw(r, 8))
    b = CohortSampler(64, "skew", seed=9)
    b.load_state_dict(a.state_dict())
    for r in range(5, 10):
        ca, cb = a.draw(r, 8), b.draw(r, 8)
        np.testing.assert_array_equal(ca, cb)
        a.record(ca)
        b.record(cb)
    # config mismatch fails fast: the snapshot was written under a
    # different fed.population section
    with pytest.raises(ValueError, match="mismatch"):
        CohortSampler(32, "skew", seed=9).load_state_dict(a.state_dict())
    with pytest.raises(ValueError, match="mismatch"):
        CohortSampler(64, "uniform", seed=9).load_state_dict(a.state_dict())


def test_sampler_mode_validation():
    with pytest.raises(ValueError, match="unknown fed.population.sampler"):
        validate_sampler_mode("roulette")


# -------------------------------------------------------------- ledger
def test_ledger_commit_quarantine_and_roundtrip():
    led = ParticipationLedger(16)
    led.commit(np.array([1, 2, 3]), {
        "reported": np.array([1, 2]), "dropped": np.array([3]),
        "deadline_cut": np.array([2]),
    })
    assert led.selected[1] == 1 and led.reported[2] == 1
    assert led.dropped[3] == 1 and led.deadline_cut[2] == 1
    assert led.coverage() == 3 / 16
    led.quarantine(5, until_round=7)
    assert led.active_quarantine(6) == {5}
    assert led.active_quarantine(7) == set()  # expired entries pruned

    led.quarantine(9, until_round=4)
    other = ParticipationLedger(16)
    other.load_state_dict(led.state_dict())
    np.testing.assert_array_equal(other.selected, led.selected)
    assert other.quarantined == led.quarantined
    with pytest.raises(ValueError, match="population mismatch"):
        ParticipationLedger(8).load_state_dict(led.state_dict())


# ------------------------------------------------- chaos population sim
def _chaos(seed=0, **over):
    cfg = ExperimentConfig().chaos
    cfg.enabled = True
    cfg.seed = seed
    for k, v in over.items():
        setattr(cfg, k, v)
    return FaultPlan(cfg, num_clients=4)


def test_population_report_deterministic_and_attempt_rolls_fresh():
    plan = _chaos(pop_drop_rate=0.4, pop_straggle_ms=100.0)
    ids = np.arange(64)
    d1, l1 = population_report(plan, 3, ids)
    d2, l2 = population_report(plan, 3, ids)
    np.testing.assert_array_equal(d1, d2)
    np.testing.assert_array_equal(l1, l2)
    assert 0 < d1.sum() < 64
    assert (l1[~d1] > 0).all()
    d3, _ = population_report(plan, 3, ids, attempt=1)
    assert not np.array_equal(d1, d3)
    # chaos off: nobody drops, everybody reports instantly
    d0, l0 = population_report(None, 3, ids)
    assert not d0.any() and not l0.any()


def test_flaky_cohort_is_a_stable_client_property():
    plan = _chaos(pop_flaky_fraction=0.25, pop_flaky_drop_rate=1.0)
    flaky = [c for c in range(200) if plan.is_flaky(c)]
    assert 20 < len(flaky) < 80  # ~25% of 200
    assert flaky == [c for c in range(200) if plan.is_flaky(c)]
    # flaky clients drop at pop_flaky_drop_rate=1.0; others never
    # (pop_drop_rate defaults 0)
    dropped, _ = population_report(plan, 0, np.arange(200))
    np.testing.assert_array_equal(np.nonzero(dropped)[0], flaky)


# --------------------------------------------------------- cohort plan
def test_cohort_plan_overselection_packs_survivors():
    sampler = CohortSampler(256, "uniform", seed=2)
    plan_chaos = _chaos(pop_drop_rate=0.3)
    plan = build_cohort_plan(
        sampler, slots=8, round_idx=0, over_select=2.0, chaos=plan_chaos
    )
    assert len(plan.sampled) == 16  # ceil(8 * 2.0)
    survivors = [c for c in plan.sampled if c not in set(plan.start_dropped)]
    # survivors packed front-to-back in draw-priority order
    np.testing.assert_array_equal(plan.slot_clients[: len(survivors)][:8],
                                  survivors[:8])
    assert plan.slot_real.sum() == min(len(survivors), 8)
    assert plan.spares_unused == max(0, len(survivors) - 8)
    with pytest.raises(ValueError, match="over_select"):
        build_cohort_plan(sampler, 8, 0, over_select=0.5)


def test_plan_round_weights_deadline_cuts_the_straggle_tail():
    sampler = CohortSampler(256, "uniform", seed=2)
    chaos = _chaos(pop_straggle_ms=100.0, pop_straggle_sigma=1.0)
    plan = build_cohort_plan(sampler, 8, 0, 1.0, chaos=chaos)
    w_open, ev_open = plan_round_weights(plan, 0, deadline_ms=0.0, chaos=chaos)
    assert w_open.sum() == 8 and ev_open["deadline_cut"].size == 0
    # median latency is 100ms: a 100ms deadline cuts about half
    w_cut, ev_cut = plan_round_weights(plan, 0, deadline_ms=100.0, chaos=chaos)
    ncut = int(ev_cut["deadline_cut"].size)
    assert 0 < ncut < 8
    assert w_cut.sum() == 8 - ncut
    assert not (set(ev_cut["reported"].tolist())
                & set(ev_cut["deadline_cut"].tolist()))


# ----------------------------------------------------------- population
def test_population_shards_are_equal_disjoint_deterministic():
    pop = ClientPopulation(16, num_rows=259, data_seed=5)
    assert pop.shard_size == 259 // 16
    seen: set[int] = set()
    for c in range(16):
        rows = pop.shard_rows(c)
        assert len(rows) == pop.shard_size
        assert not (seen & set(rows.tolist()))
        seen.update(rows.tolist())
    np.testing.assert_array_equal(
        pop.shard_rows(3), ClientPopulation(16, 259, data_seed=5).shard_rows(3)
    )
    assert not np.array_equal(
        pop.shard_rows(3), ClientPopulation(16, 259, data_seed=6).shard_rows(3)
    )


def test_population_guards_empty_and_subbatch_shards():
    with pytest.raises(ValueError, match="empty shards"):
        ClientPopulation(1000, num_rows=100)
    with pytest.raises(ValueError, match="smaller than data.batch_size"):
        ClientPopulation(10, num_rows=100, batch_size=64)


def test_sidecar_store_lru_spills_and_restores(tmp_path):
    pop = ClientPopulation(
        8, num_rows=64, resident_cap=2, spill_dir=tmp_path / "spill"
    )
    mk = lambda c: {"m": np.full((3,), float(c)), "v": np.arange(2) + c}
    for c in range(5):
        pop.put_sidecar(c, mk(c))
    assert pop.resident_sidecars == 2 and pop.spill_count == 3
    for c in range(5):  # spilled and resident both round-trip exactly
        sc = pop.get_sidecar(c)
        np.testing.assert_array_equal(sc["m"], mk(c)["m"])
        np.testing.assert_array_equal(sc["v"], mk(c)["v"])
    assert pop.get_sidecar(7) is None  # never stored: caller's template
    pop.reset_sidecar(0)  # quarantine healing forgets the sidecar
    assert pop.get_sidecar(0) is None
    with pytest.raises(ValueError, match="structure changed"):
        pop.put_sidecar(6, {"different": np.zeros(1)})


# ====================================================== trainer-level
def _pop_trainer(pop=0, rounds=3, num_train=256, slots=4, snapshot_dir="",
                 **kw):
    from fedrec_tpu.train.trainer import Trainer

    set_registry(MetricsRegistry())
    set_tracer(Tracer())
    cfg = ExperimentConfig()
    cfg.model.news_dim = 32
    cfg.model.num_heads = 4
    cfg.model.head_dim = 8
    cfg.model.query_dim = 16
    cfg.model.bert_hidden = 48
    cfg.model.text_encoder_mode = "head"
    cfg.data.max_his_len = 10
    cfg.data.max_title_len = 12
    cfg.data.batch_size = 8
    cfg.fed.num_clients = slots
    cfg.fed.strategy = "param_avg"
    cfg.fed.rounds = rounds
    cfg.train.snapshot_dir = snapshot_dir
    cfg.train.eval_every = 1000
    cfg.fed.population.num_clients = pop
    for key, v in kw.items():
        obj = cfg
        parts = key.split(".")
        for p in parts[:-1]:
            obj = getattr(obj, p)
        setattr(obj, parts[-1], v)
    data = make_synthetic_mind(
        num_news=64, num_train=num_train, num_valid=64,
        title_len=12, his_len_range=(2, 10), seed=0, popular_frac=0.2,
    )
    states = np.random.default_rng(1).standard_normal(
        (64, 12, 48)
    ).astype(np.float32)
    return Trainer(cfg, data, states)


def _params_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves((a.user_params, a.news_params))
    lb = jax.tree_util.tree_leaves((b.user_params, b.news_params))
    return all(
        (np.asarray(x) == np.asarray(y)).all() for x, y in zip(la, lb)
    )


def test_degenerate_population_bit_identical_host_driven():
    """population == world, over_select=1.0, no deadline: the cohort
    engine must reproduce today's trajectory bit-identically."""
    t0 = _pop_trainer(pop=0)
    h0 = t0.run()
    t1 = _pop_trainer(pop=4)
    h1 = t1.run()
    assert [r.train_loss for r in h0] == [r.train_loss for r in h1]
    assert _params_equal(t0.state, t1.state)
    # the engine ran (identity cohorts), but swapped nothing
    assert t1.cohort_history == [(r, (0, 1, 2, 3)) for r in range(3)]
    assert t1.registry.counter("fed.cohort_slot_swaps_total").value() == 0


_CHAOS_KW = {
    "chaos.enabled": True,
    "chaos.pop_drop_rate": 0.3,
    "chaos.pop_straggle_ms": 50.0,
    "fed.population.over_select": 1.5,
    "fed.population.round_deadline_ms": 200.0,
    "fed.population.min_reports": 1,
    "fed.population.seed": 7,
}


def test_sampled_run_counts_events_and_replays_bit_identically():
    """Sampled world under seeded dropout + straggle + deadline: churn
    shows up in the registry, and the whole run replays bit-identically
    from the seeds (cohort schedule AND parameters)."""
    t0 = _pop_trainer(pop=32, rounds=4, **_CHAOS_KW)
    t0.run()
    reg = t0.registry
    assert reg.gauge("fed.population_clients").value() == 32.0
    assert reg.counter("fed.pop_dropouts_total").value() > 0
    assert reg.counter("fed.cohort_slot_swaps_total").value() > 0
    assert 0 < reg.gauge("fed.population_coverage").value() <= 1.0
    assert len(t0.cohort_history) == 4

    t1 = _pop_trainer(pop=32, rounds=4, **_CHAOS_KW)
    t1.run()
    assert t0.cohort_history == t1.cohort_history
    assert _params_equal(t0.state, t1.state)


def test_quorum_discards_round_and_exhaustion_aborts():
    """min_reports above what the dropout rate can deliver: the round is
    discarded and replayed with fresh draws, then the run aborts with the
    operator-grade sizing message once retries are exhausted."""
    t = _pop_trainer(
        pop=32, rounds=2,
        **{
            "chaos.enabled": True,
            "chaos.pop_drop_rate": 0.97,
            "fed.population.min_reports": 4,
            "fed.population.quorum_retries": 2,
            "fed.population.seed": 1,
        },
    )
    with pytest.raises(RuntimeError, match="failed quorum"):
        t.run()
    assert t.registry.counter("fed.quorum_replays_total").value() == 3
    # the discarded draws never skewed the schedule bookkeeping
    assert t.cohort_sampler.rounds_committed == 0
    assert len(t.cohort_history) == 0


def test_quorum_without_attempt_sensitive_dice_fails_fast():
    """Degenerate world, quorum unreachable via the (round-keyed)
    participation mask: every re-draw would recompute byte-identical
    weights, so the run aborts on the FIRST failure instead of burning
    quorum_retries on futile replays."""
    t = _pop_trainer(
        pop=4, rounds=2,
        **{
            "fed.participation": 0.5,  # 2 of 4 report, every round
            "fed.population.min_reports": 4,
            "fed.population.quorum_retries": 3,
        },
    )
    with pytest.raises(RuntimeError, match="retries skipped"):
        t.run()
    assert t.registry.counter("fed.quorum_replays_total").value() == 1


def test_rollback_quarantine_resets_sidecar_for_good(tmp_path):
    """ISSUE-6 review fix: after a quarantine's reset_sidecar, the
    replay's _install_cohort must NOT write the restored (possibly
    poisoned) sidecar back — the healed rejoin restarts from the
    template."""
    t = _pop_trainer(
        pop=32, rounds=1,
        **{"fed.robust.recover": True, "fed.robust.quarantine_rounds": 2},
    )
    t._ensure_cohort(0)
    victim_slot = 0
    logical = int(t._current_plan.slot_clients[victim_slot])
    t._capture_recovery_state()
    # poison the victim's stored sidecar so a write-back would be visible
    t.population.put_sidecar(
        logical, t._template_sidecar(logical)
    )
    assert t.population.get_sidecar(logical) is not None
    t._rollback_and_quarantine(
        {"client": victim_slot, "kind": "nonfinite", "round": 0}, 0
    )
    assert t.population.get_sidecar(logical) is None
    assert not t._slot_writeback[t._slot_occupants == logical].any()
    # the replay re-installs a cohort WITHOUT the quarantined client and
    # must not resurrect its sidecar from the restored slots
    t._ensure_cohort(0)
    assert logical not in set(t._current_plan.slot_clients.tolist())
    assert t.population.get_sidecar(logical) is None


def test_install_preserves_sidecar_of_client_repacked_to_new_slot():
    """Review fix: a client that stays at its old index as a weight-0 pad
    while being re-packed REAL into a different slot must carry its
    freshest sidecar to the new slot (write-back covers every persisted
    occupant, not just changed slots)."""
    from fedrec_tpu.fed.population import CohortPlan

    t = _pop_trainer(pop=8, slots=4)

    def plan(clients, real):
        c = np.asarray(clients, np.int64)
        return CohortPlan(
            round_idx=0, attempt=0, sampled=np.unique(c),
            start_dropped=np.zeros((0,), np.int64),
            slot_clients=c, slot_real=np.asarray(real, bool),
        )

    t._install_cohort(plan([0, 1, 2, 3], [True] * 4))
    # "train" client 3 in slot 3: bump its step counter
    host = t._host_state()
    step = np.array(host.step)
    step[3] = 7
    t.adopt_state(host.replace(step=step))
    # client 3 re-packs real into slot 0; its old slot 3 is now its pad
    t._install_cohort(plan([3, 4, 5, 3], [True, True, True, False]))
    assert int(np.array(t._host_state().step)[0]) == 7


def test_degenerate_slot_chaos_lands_in_the_ledger():
    """Review fix: slot-level chaos drops (not just population-level
    dice) must show up as dropped rounds — selected always equals
    reported + dropped + deadline_cut."""
    t = _pop_trainer(
        pop=4, rounds=3,
        **{"chaos.enabled": True, "chaos.drop_rate": 0.5, "chaos.seed": 2},
    )
    t.run()
    led = t.population.ledger
    assert t.registry.counter("fed.pop_dropouts_total").value() > 0
    assert led.selected.sum() == (
        led.reported.sum() + led.dropped.sum() + led.deadline_cut.sum()
    )


def test_checkpoint_restore_resumes_identical_cohort_schedule(tmp_path):
    """Snapshot at round r, restore, rounds r+1..r+k sample identical
    cohorts to an uninterrupted run; with client_state='reset' the
    resumed PARAMETERS are bit-identical too (persist mode is
    schedule-identical but warm sidecars of rotated-out clients restart
    from the template — the documented divergence)."""
    kw = {
        "chaos.enabled": True,
        "chaos.pop_drop_rate": 0.2,
        "fed.population.sampler": "skew",
        "fed.population.client_state": "reset",
        "train.save_every": 2,
    }
    ta = _pop_trainer(pop=32, rounds=6, snapshot_dir=str(tmp_path / "a"), **kw)
    ta.run()
    tb = _pop_trainer(pop=32, rounds=4, snapshot_dir=str(tmp_path / "b"), **kw)
    tb.run()
    tc = _pop_trainer(
        pop=32, rounds=6, snapshot_dir=str(tmp_path / "b"),
        **{**kw, "train.resume": True},
    )
    assert tc.start_round == 4
    tc.run()
    assert tb.cohort_history + tc.cohort_history == ta.cohort_history
    assert _params_equal(ta.state, tc.state)


def test_robust_trim_over_reporting_mask_replays_bit_identically():
    """fed.robust trimmed_mean under population dropouts: the trim count
    covers REPORTING clients only (weight-0 dropouts never consume a trim
    slot: the run stays finite and its dropouts are counted), and two
    Trainers of the same seeds agree bit-identically."""
    kw = {
        "chaos.enabled": True,
        "chaos.pop_drop_rate": 0.25,
        "fed.robust.method": "trimmed_mean",
    }
    t0 = _pop_trainer(pop=8, slots=8, rounds=3, **kw)
    h0 = t0.run()
    assert t0.registry.counter("fed.pop_dropouts_total").value() > 0
    assert all(np.isfinite(r.train_loss) for r in h0)
    t1 = _pop_trainer(pop=8, slots=8, rounds=3, **kw)
    h1 = t1.run()
    assert (t1.registry.counter("fed.pop_dropouts_total").value()
            == t0.registry.counter("fed.pop_dropouts_total").value())
    assert [r.train_loss for r in h0] == [r.train_loss for r in h1]
    assert _params_equal(t0.state, t1.state)


def test_trimmed_mean_trim_count_over_reporting_mask_unit():
    """Hand-computable: 8 slots, 3 non-reporters (participation draw or
    dropout), trim_k=1 — the trim drops the extreme REPORTING values, and
    the non-reporters' (arbitrarily poisoned) values never shift which
    values get trimmed."""
    from fedrec_tpu.fed import participation_mask, robust_reduce_np

    w = np.asarray(
        participation_mask(jax.random.PRNGKey(0), 8, 0.625), np.float32
    )
    assert w.sum() == 5  # 5 reporting, 3 cut
    vals = np.zeros((8, 1), np.float64)
    vals[w > 0, 0] = [10.0, 1.0, 2.0, 3.0, -10.0][: int(w.sum())]
    vals[w == 0, 0] = 1e12  # dropped clients: arbitrary garbage
    out = robust_reduce_np(vals, w, "trimmed_mean", trim_k=1)
    # trim the reporting extremes (+10, -10); mean the kept {1, 2, 3}
    np.testing.assert_allclose(out[0], 2.0)
    out_med = robust_reduce_np(vals, w, "median")
    np.testing.assert_allclose(out_med[0], 2.0)


def test_population_validation_errors():
    with pytest.raises(ValueError, match="below the device-slot count"):
        _pop_trainer(pop=2)
    with pytest.raises(ValueError, match="over_select"):
        _pop_trainer(pop=8, **{"fed.population.over_select": 0.9})
    with pytest.raises(ValueError, match="client_state"):
        _pop_trainer(pop=8, **{"fed.population.client_state": "pause"})
    with pytest.raises(ValueError, match="min_reports"):
        _pop_trainer(pop=8, **{"fed.population.min_reports": 5})
    with pytest.raises(ValueError, match="param-syncing strategy"):
        _pop_trainer(pop=8, **{"fed.strategy": "local"})
    with pytest.raises(ValueError, match="fed.participation"):
        _pop_trainer(pop=8, **{"fed.participation": 0.5})
    with pytest.raises(ValueError, match="unknown fed.population.sampler"):
        _pop_trainer(pop=8, **{"fed.population.sampler": "lottery"})


def test_report_renders_participation_section(tmp_path):
    from fedrec_tpu.obs.report import build_report, load_jsonl, render_text

    reg = MetricsRegistry()
    reg.gauge("fed.population_clients").set(1024)
    reg.gauge("fed.cohort_sampled").set(77)
    reg.gauge("fed.cohort_reporting").set(60)
    reg.counter("fed.pop_dropouts_total").inc(13)
    reg.counter("fed.deadline_cuts_total").inc(4)
    reg.counter("fed.quorum_replays_total").inc(1)
    reg.counter("fed.cohort_slot_swaps_total").inc(123)
    reg.gauge("fed.population_coverage").set(0.42)
    jsonl = tmp_path / "metrics.jsonl"
    reg.write_snapshot(jsonl)
    records, snapshots = load_jsonl(jsonl)
    report = build_report(records, snapshots)
    part = report["participation"]
    assert part["population"] == 1024
    assert part["cohort_reporting"] == 60
    assert part["quorum_replays"] == 1
    text = render_text(report)
    assert "## Participation" in text
    assert "dropouts: 13" in text and "deadline cuts: 4" in text
    assert "coverage: 42.0%" in text


# -------------------------------------------------- acceptance e2e
@pytest.mark.slow  # 64-slot cohort on CPU; chaos_smoke.sh runs a sibling
def test_dropout_tolerance_e2e_1024_clients(tmp_path):
    """ISSUE 6 acceptance: >= 1024 logical clients, 64-client cohorts,
    20% seeded dropout — a multi-round CPU run completes with correct
    participation weighting, the churn visible in the registry, and the
    whole run replays bit-identically from the chaos seed."""
    kw = {
        "data.batch_size": 2,
        "chaos.enabled": True,
        "chaos.pop_drop_rate": 0.2,
        "fed.population.over_select": 1.25,
        "fed.population.min_reports": 16,
        "fed.population.seed": 11,
        "obs.dir": str(tmp_path / "obs"),
    }
    t0 = _pop_trainer(pop=1024, slots=64, rounds=3, num_train=2048, **kw)
    h0 = t0.run()
    assert len(h0) == 3 and all(np.isfinite(r.train_loss) for r in h0)
    reg = t0.registry
    assert reg.counter("fed.pop_dropouts_total").value() > 0
    assert reg.gauge("fed.cohort_reporting").value() >= 16
    # ~20% of 80 sampled drop per round; the survivors fill >= quorum
    sampled = reg.gauge("fed.cohort_sampled").value()
    assert sampled == int(np.ceil(64 * 1.25))
    # the obs artifacts carry the Participation story
    from fedrec_tpu.obs.report import build_report, load_jsonl, render_text

    records, snapshots = load_jsonl(tmp_path / "obs" / "metrics.jsonl")
    text = render_text(build_report(records, snapshots))
    assert "## Participation" in text and "logical clients: 1024" in text

    t1 = _pop_trainer(pop=1024, slots=64, rounds=3, num_train=2048, **kw)
    t1.run()
    assert t0.cohort_history == t1.cohort_history
    assert _params_equal(t0.state, t1.state)
