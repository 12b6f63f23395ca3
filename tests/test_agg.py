"""Aggregation topologies (``fedrec_tpu/agg/``): the trajectory pins.

The acceptance bar (docs/DESIGN.md §5k):

* ``agg.mode=hierarchical`` with ``fed.robust.method=mean`` is BITWISE
  identical to flat on a seeded 3-round CPU trainer run — the tree of
  (sum(w*x), sum(w)) partials with one final divide IS the flat weighted
  mean, so the mode lowers to the unchanged collective;
* per-tier trimmed mean genuinely DIVERGES from the flat robust reduce
  (hand-computed fixture) but stays inside the cohort's coordinatewise
  envelope — the bounded-delta contract;
* the buffered quorum commit folds late entries staleness-weighted
  (1/(1+s), hand-computed), drops past ``agg.staleness_cap``, and a
  zero-staleness all-reporting commit equals the flat FedAvg mean;
* the buffer's checkpoint sidecar round-trips, and restoring it across a
  membership epoch change drops exactly the dead workers' entries;
* the lint schema auto-learned the ``agg.*`` knobs, so a typo'd knob
  fails fast at the override layer and in ``make check``.
"""

from __future__ import annotations

from pathlib import Path

import jax
import numpy as np
import pytest

from fedrec_tpu.agg.buffer import AggBuffer, BufferEntry
from fedrec_tpu.agg.commit import CommitPolicy, fold_commit, staleness_weight
from fedrec_tpu.agg.hierarchy import (
    build_tree,
    tree_critical_path_ms,
    tree_reduce_np,
)
from fedrec_tpu.config import ExperimentConfig
from fedrec_tpu.fed.robust import robust_reduce_tree_np

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------- tree plan


def test_build_tree_binary_over_eight():
    levels = build_tree(8, 2)
    assert levels[0] == [[0, 1], [2, 3], [4, 5], [6, 7]]
    assert levels[1] == [[0, 1], [2, 3]]
    assert levels[2] == [[0, 1]]


def test_build_tree_degenerate_and_errors():
    # count <= fanout: one group, one level — identical to flat
    assert build_tree(3, 4) == [[[0, 1, 2]]]
    assert build_tree(1, 2) == [[[0]]]
    with pytest.raises(ValueError):
        build_tree(0, 2)
    with pytest.raises(ValueError):
        build_tree(4, 1)


# ------------------------------------------------------- mean tree == flat


def _flat_wmean(stacks, w):
    w = np.asarray(w, np.float64)
    return [
        np.einsum("p,p...->...", w, np.asarray(s, np.float64)) / w.sum()
        for s in stacks
    ]


def test_mean_tree_exact_on_binary_representable():
    """Integer contributions and weights: every partial sum is exact, so
    the tree result EQUALS the flat weighted mean bit-for-bit whatever
    the summation order."""
    rng = np.random.default_rng(0)
    stacks = [
        rng.integers(-8, 9, size=(7, 5)).astype(np.float64),
        rng.integers(-8, 9, size=(7, 3, 2)).astype(np.float64),
    ]
    w = np.array([1, 2, 1, 4, 1, 2, 1], np.float64)
    for fanout in (2, 3, 7):
        out = tree_reduce_np(stacks, w, fanout, "mean")
        for got, want in zip(out, _flat_wmean(stacks, w)):
            assert (np.asarray(got) == want).all()


def test_mean_tree_allclose_on_random_with_nonparticipant():
    rng = np.random.default_rng(1)
    stacks = [rng.standard_normal((9, 4)), rng.standard_normal((9, 2, 3))]
    w = rng.uniform(0.5, 2.0, size=(9,))
    w[4] = 0.0  # a non-participant is masked, not averaged
    out = tree_reduce_np(stacks, w, 2, "mean")
    want = _flat_wmean([s[w > 0] for s in stacks], w[w > 0])
    for got, exp in zip(out, want):
        np.testing.assert_allclose(np.asarray(got), exp, rtol=1e-12)


def test_mean_tree_all_zero_weight_raises():
    with pytest.raises(ValueError):
        tree_reduce_np([np.ones((3, 2))], np.zeros((3,)), 2, "mean")


# ----------------------------------------- per-tier robust: bounded delta


def test_tiered_trimmed_mean_diverges_but_stays_bounded():
    """Hand-computed: 8 scalar contributions [0,1,2,100,3,4,5,6],
    trim_k=1. Flat trims {0, 100} -> mean(1..6) = 3.5. Fanout-4 tiers
    trim per group: [0,1,2,100] -> 1.5, [3,4,5,6] -> 4.5, and the pair
    level (m=2 clamps the trim to 0) means them -> 3.0. The trajectories
    genuinely diverge, but the tier output lives in the convex hull of
    its inputs, so the aggregate stays inside the cohort envelope."""
    vals = np.array([0.0, 1.0, 2.0, 100.0, 3.0, 4.0, 5.0, 6.0])
    stacks = [vals.reshape(8, 1)]
    w = np.ones((8,))
    flat = np.asarray(
        robust_reduce_tree_np(stacks, w, "trimmed_mean", trim_k=1)[0]
    )
    stats: dict = {}
    hier = np.asarray(
        tree_reduce_np(stacks, w, 4, "trimmed_mean", trim_k=1, stats=stats)[0]
    )
    assert flat[0] == 3.5
    assert hier[0] == 3.0            # the divergence is real...
    assert vals.min() <= hier[0] <= vals.max()   # ...and bounded
    assert abs(hier[0] - flat[0]) <= vals.max() - vals.min()
    # the stats out-param carries the parallel-deployment accounting
    assert stats["members"] == 8 and len(stats["levels"]) == 2
    assert tree_critical_path_ms(stats) >= 0.0


def test_tiered_zero_weight_tier_carries_fallback_masked():
    """An entire tier of non-participants contributes weight 0 and its
    fallback value is masked out one level up — the mean over the live
    tier is unaffected."""
    stacks = [np.array([[1.0], [3.0], [50.0], [60.0]])]
    w = np.array([1.0, 1.0, 0.0, 0.0])
    fallback = [np.array([999.0])]
    out = tree_reduce_np(
        stacks, w, 2, "trimmed_mean", trim_k=1, fallback_tree=fallback
    )
    assert np.asarray(out[0])[0] == 2.0


# ------------------------------------------------------------ commit fold


def _entry(worker, based_on, weight, leaves, round=0, epoch=0):
    return BufferEntry(
        worker=worker, round=round, epoch=epoch, based_on=based_on,
        weight=weight, arrival_ms=0.0,
        leaves=[np.asarray(x) for x in leaves],
    )


def test_staleness_weight_and_quorum_clamp():
    assert staleness_weight(0) == 1.0
    assert staleness_weight(1) == 0.5
    assert staleness_weight(3) == 0.25
    pol = CommitPolicy(quorum=6, staleness_cap=2)
    assert pol.quorum_for(8) == 6
    assert pol.quorum_for(4) == 4    # membership shrink: clamp, no deadlock
    assert CommitPolicy(quorum=0).quorum_for(5) == 5  # 0 = all-reporting
    with pytest.raises(ValueError):
        pol.quorum_for(0)


def test_fold_commit_zero_staleness_is_flat_weighted_mean():
    base = [np.zeros((3,), np.float32), np.ones((2, 2), np.float32)]
    rng = np.random.default_rng(2)
    deltas = [[rng.standard_normal(b.shape) for b in base] for _ in range(4)]
    w = [1.0, 2.0, 1.0, 4.0]
    entries = [
        _entry(str(i), based_on=5, weight=w[i], leaves=deltas[i])
        for i in range(4)
    ]
    out, stats = fold_commit(base, entries, 5, CommitPolicy(staleness_cap=2))
    assert stats.version == 6 and stats.folded == 4
    assert stats.late_folds == 0 and stats.stale_drops == 0
    for j, b in enumerate(base):
        want = b + _flat_wmean(
            [np.stack([d[j] for d in deltas])], np.asarray(w)
        )[0].astype(b.dtype)
        np.testing.assert_allclose(np.asarray(out[j]), want, rtol=1e-6)
        assert out[j].dtype == b.dtype   # the global keeps its dtype


def test_fold_commit_staleness_weighting_hand_computed():
    """Fresh delta 2 (weight 1) + one-commit-stale delta 0 (weight 1):
    effective weights (1, 0.5) -> fold = (1*2 + 0.5*0)/1.5 = 4/3."""
    base = [np.zeros((1,), np.float64)]
    entries = [
        _entry("fresh", based_on=7, weight=1.0, leaves=[np.array([2.0])]),
        _entry("late", based_on=6, weight=1.0, leaves=[np.array([0.0])]),
    ]
    out, stats = fold_commit(base, entries, 7, CommitPolicy(staleness_cap=2))
    np.testing.assert_allclose(np.asarray(out[0]), [4.0 / 3.0], rtol=1e-12)
    assert stats.late_folds == 1
    assert stats.mean_staleness == 0.5 and stats.max_staleness == 1


def test_fold_commit_stale_drop_and_all_dropped():
    base = [np.full((2,), 10.0)]
    pol = CommitPolicy(staleness_cap=1)
    entries = [
        _entry("ok", based_on=5, weight=1.0, leaves=[np.array([1.0, 1.0])]),
        _entry("dead", based_on=2, weight=1.0, leaves=[np.array([99.0, 99.0])]),
    ]
    out, stats = fold_commit(base, entries, 5, pol)
    assert stats.stale_drops == 1 and stats.folded == 1
    np.testing.assert_allclose(np.asarray(out[0]), [11.0, 11.0])
    # every entry past the cap: base unchanged, version still bumps (the
    # droppers' staleness must keep growing)
    out2, stats2 = fold_commit(base, [entries[1]], 5, pol)
    assert stats2.version == 6 and stats2.folded == 0
    np.testing.assert_allclose(np.asarray(out2[0]), np.asarray(base[0]))


def test_fold_commit_entry_from_the_future_raises():
    base = [np.zeros((1,))]
    e = _entry("w", based_on=9, weight=1.0, leaves=[np.array([1.0])])
    with pytest.raises(ValueError, match="ahead of"):
        fold_commit(base, [e], 8, CommitPolicy())


def test_fold_commit_robust_method_neutralizes_poison():
    """trimmed_mean over the delta stacks: one x1000-poisoned delta
    consumes a trim slot and the commit equals the honest fold."""
    base = [np.zeros((3,))]
    entries = [
        _entry(str(i), based_on=0, weight=1.0, leaves=[np.ones((3,))])
        for i in range(7)
    ]
    entries.append(
        _entry("evil", based_on=0, weight=1.0, leaves=[np.full((3,), 1000.0)])
    )
    out, stats = fold_commit(
        base, entries, 0, CommitPolicy(), method="trimmed_mean", trim_k=1
    )
    assert stats.folded == 8
    np.testing.assert_allclose(np.asarray(out[0]), np.ones((3,)))


# ------------------------------------------------------- buffer + sidecar


def test_buffer_repush_replaces_pending_entry():
    buf = AggBuffer()
    buf.add(_entry("w0", 0, 1.0, [np.array([1.0])], round=3))
    buf.add(_entry("w0", 0, 1.0, [np.array([2.0])], round=3))  # wire retry
    buf.add(_entry("w0", 0, 1.0, [np.array([3.0])], round=4))  # new round
    assert len(buf) == 2 and buf.pending_workers() == {"w0"}
    vals = sorted(float(e.leaves[0][0]) for e in buf.entries)
    assert vals == [2.0, 3.0]        # the retry replaced, never doubled
    assert len(buf.take_all()) == 2 and len(buf) == 0


def test_buffer_sidecar_round_trip():
    rng = np.random.default_rng(3)
    buf = AggBuffer(epoch=5)
    for i in range(3):
        buf.add(
            _entry(
                f"w{i}", based_on=7 + i, weight=1.5 * (i + 1),
                leaves=[rng.standard_normal((4,)), rng.standard_normal((2, 3))],
                round=9, epoch=5,
            )
        )
    blob = buf.state_bytes(round_idx=9, version=8)
    restored, round_idx, version = AggBuffer.load_state(blob)
    assert (round_idx, version, restored.epoch) == (9, 8, 5)
    assert len(restored) == 3
    for a, b in zip(buf.entries, restored.entries):
        assert (a.worker, a.round, a.epoch, a.based_on) == (
            b.worker, b.round, b.epoch, b.based_on,
        )
        assert a.weight == b.weight
        for la, lb in zip(a.leaves, b.leaves):
            assert (la == lb).all()


def test_buffer_rejects_foreign_blob_and_backwards_epoch():
    with pytest.raises(ValueError):
        AggBuffer.load_state(b"not an npz at all")
    import io

    fake = io.BytesIO()
    np.savez(fake, something=np.zeros((2,)))
    with pytest.raises(ValueError, match="agg-buffer"):
        AggBuffer.load_state(fake.getvalue())
    buf = AggBuffer(epoch=4)
    with pytest.raises(ValueError, match="backwards"):
        buf.advance_epoch(3)


def test_buffer_restore_across_membership_epoch_change():
    """The satellite pin: checkpoint the buffer mid-round, restore it,
    advance the membership epoch with one worker dead — exactly the dead
    worker's pending entries drop, and the next commit folds only the
    survivors (identical to a never-checkpointed twin)."""
    base = [np.zeros((2,), np.float32)]
    mk = lambda w, v: _entry(  # noqa: E731
        w, based_on=6, weight=1.0, leaves=[np.full((2,), v)], epoch=2
    )
    buf = AggBuffer(epoch=2)
    buf.add(mk("alive", 4.0))
    buf.add(mk("dead", 100.0))
    buf.add(mk("alive2", 2.0))

    restored, _, version = AggBuffer.load_state(buf.state_bytes(7, 6))
    dropped = restored.advance_epoch(3, drop_dead={"dead"})
    assert dropped == 1 and restored.epoch == 3
    assert restored.pending_workers() == {"alive", "alive2"}

    out, stats = fold_commit(
        base, restored.take_all(), version, CommitPolicy(staleness_cap=2)
    )
    assert stats.folded == 2
    np.testing.assert_allclose(np.asarray(out[0]), [3.0, 3.0])  # mean(4, 2)
    # the dead worker's 100.0 delta never resurrects
    twin, _ = fold_commit(
        base, [mk("alive", 4.0), mk("alive2", 2.0)], 6, CommitPolicy()
    )
    assert (np.asarray(out[0]) == np.asarray(twin[0])).all()


# -------------------------------------------- trainer trajectory pins


@pytest.fixture(scope="module")
def agg_data():
    from fedrec_tpu.data import make_synthetic_mind

    cfg = ExperimentConfig()
    data = make_synthetic_mind(
        num_news=64, num_train=128, num_valid=32, title_len=8
    )
    tok = np.random.default_rng(0).standard_normal(
        (data.num_news, 8, cfg.model.bert_hidden)
    ).astype(np.float32)
    return data, tok


def _agg_cfg(tmp: Path, tag: str, **agg) -> ExperimentConfig:
    cfg = ExperimentConfig()
    cfg.fed.rounds = 3
    cfg.fed.num_clients = 4
    cfg.fed.strategy = "param_avg"
    cfg.data.batch_size = 8
    cfg.data.npratio = 2
    cfg.data.max_title_len = 8
    cfg.data.max_his_len = 4
    cfg.train.save_every = 100
    cfg.train.snapshot_dir = str(tmp / tag)   # isolated: no cross-resume
    for k, v in agg.items():
        setattr(cfg.agg, k, v)
    return cfg


def _run_trainer(cfg, data, tok):
    from fedrec_tpu.train.trainer import Trainer

    t = Trainer(cfg, data, tok)
    history = t.run()
    leaves = [
        np.asarray(x)
        for x in jax.tree_util.tree_leaves(t._client0_params())
    ]
    return history, leaves, t


@pytest.fixture(scope="module")
def agg_trajectories(agg_data, tmp_path_factory):
    """One seeded 3-round CPU run per topology, isolated snapshot dirs.
    Flat is the reference trajectory the modes are pinned against."""
    data, tok = agg_data
    tmp = tmp_path_factory.mktemp("aggtraj")
    runs = {}
    runs["flat"] = _run_trainer(_agg_cfg(tmp, "flat"), data, tok)
    runs["hier"] = _run_trainer(
        _agg_cfg(tmp, "hier", mode="hierarchical"), data, tok
    )
    runs["async0"] = _run_trainer(
        _agg_cfg(tmp, "async0", mode="async", quorum=0), data, tok
    )
    runs["asyncq"] = _run_trainer(
        _agg_cfg(tmp, "asyncq", mode="async", quorum=3), data, tok
    )
    return runs


def test_hierarchical_mean_bit_identical_to_flat(agg_trajectories):
    """THE tentpole pin: agg.mode=hierarchical with the (default) mean
    robust method lowers to the flat collective — same floats, same
    trajectory, bit for bit after 3 rounds."""
    _, flat, _ = agg_trajectories["flat"]
    h_hist, hier, _ = agg_trajectories["hier"]
    assert len(h_hist) == 3
    assert all((a == b).all() for a, b in zip(flat, hier))


def test_async_all_reporting_matches_flat_mean(agg_trajectories):
    """quorum=0, no chaos: every commit is a zero-staleness all-reporting
    fold — mathematically the flat FedAvg mean. The fold runs in f64 on
    host against the f32 in-graph mean, so equality is allclose(1e-4)
    over 3 compounding rounds, not bitwise."""
    _, flat, _ = agg_trajectories["flat"]
    _, a0, t = agg_trajectories["async0"]
    assert all(
        np.allclose(a, b, atol=1e-4) for a, b in zip(flat, a0)
    )
    assert t._agg_version == 3 and len(t.agg_buffer) == 0


def test_async_quorum_buffers_the_straggler(agg_trajectories):
    """quorum=3 of 4 (chaos off -> deterministic zero latencies, stable
    sort): each round commits on slots {0,1,2} and buffers slot 3's
    delta, which folds late into the NEXT commit. After round 3 the
    version advanced once per round and exactly one entry is pending."""
    hist, leaves, t = agg_trajectories["asyncq"]
    assert len(hist) == 3
    assert t._agg_version == 3
    assert len(t.agg_buffer) == 1
    (pending,) = t.agg_buffer.entries
    assert pending.worker == "3" and pending.based_on == 2
    assert all(np.isfinite(leaf).all() for leaf in leaves)


def test_hierarchical_trimmed_runs_end_to_end(agg_data, tmp_path_factory):
    """The non-mean hierarchical path (_agg_hier_sync): per-tier trimmed
    mean over the live cohort. The trajectory legitimately diverges from
    flat (pinned at the reduce level above); here we pin that the wired
    trainer path runs and stays finite."""
    data, tok = agg_data
    cfg = _agg_cfg(
        tmp_path_factory.mktemp("aggtrim"), "hiertrim", mode="hierarchical"
    )
    cfg.fed.robust.method = "trimmed_mean"
    cfg.fed.rounds = 2
    hist, leaves, _ = _run_trainer(cfg, data, tok)
    assert len(hist) == 2
    assert all(np.isfinite(leaf).all() for leaf in leaves)


# ------------------------------------------------- config-contract guard


def test_lint_schema_learned_agg_knobs():
    """The config-contract analyzer derives its schema from config.py's
    dataclasses, so the agg section is auto-taught: a typo'd agg knob in
    source is a CC201 finding and `make check` fails."""
    from fedrec_tpu.analysis.config_contract import load_schema
    from fedrec_tpu.analysis.core import Project

    schema = load_schema(Project.load(REPO))
    assert schema is not None
    assert {"mode", "quorum", "staleness_cap", "tree_fanout"} <= (
        schema.section_keys.get("agg", set())
    )


def test_typoed_agg_knob_fails_fast():
    cfg = ExperimentConfig()
    with pytest.raises(KeyError, match="agg.quorom"):
        cfg.apply_overrides(["agg.quorom=3"])
    cfg.apply_overrides(["agg.quorum=3"])    # the real knob applies
    assert cfg.agg.quorum == 3


def test_trainer_rejects_bad_agg_config(agg_data, tmp_path):
    from fedrec_tpu.train.trainer import Trainer

    data, tok = agg_data

    def expect(msg, **mut):
        cfg = _agg_cfg(tmp_path, "guard")
        for path, v in mut.items():
            obj = cfg
            *head, last = path.split(".")
            for part in head:
                obj = getattr(obj, part)
            setattr(obj, last, v)
        with pytest.raises(ValueError, match=msg):
            Trainer(cfg, data, tok)

    expect("unknown agg.mode", **{"agg.mode": "asink"})
    expect("tree_fanout", **{"agg.mode": "hierarchical", "agg.tree_fanout": 1})
    expect("staleness_cap", **{"agg.mode": "async", "agg.staleness_cap": -1})
    expect(
        "requires a strategy that syncs",
        **{"agg.mode": "async", "fed.strategy": "grad_avg"},
    )
    # every CONCRETE codec composes with async now (entries are encoded
    # into the buffer); only the warmup-dependent "auto" stays rejected
    expect(
        "dcn_compress='auto'",
        **{"agg.mode": "async", "fed.dcn_compress": "auto"},
    )
    cfg = _agg_cfg(tmp_path, "guard_codec_ok")
    cfg.agg.mode = "async"
    cfg.fed.dcn_compress = "int8"
    Trainer(cfg, data, tok)   # must NOT raise
