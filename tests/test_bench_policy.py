"""Unit tests for bench.py's headline policies (ADVICE r3).

These policies decide what number the judge sees, and they only ever
execute on a live chip — so they are module-level functions tested here
with synthetic artifacts, not chip time:

  * ``_promote_best_sweep_row``: the headline is the best SWEEP row
    unconditionally — a lucky B=64 flagship reading must not
    be retained even when it beats every sweep row, and the derived
    flops/mfu fields must track the promoted row on every path (including
    peak=None, which previously left a stale B=64 flops value behind).
  * ``_baseline_ratios``: when our sweep extends past the largest B the
    torch baseline measured, the ratio is computed from our best rate
    among Bs the baseline ALSO measured — no unmeasured torch-stops-
    scaling assumption.
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import _baseline_ratios, _promote_best_sweep_row
from fedrec_tpu.utils.provenance import runtime_versions


def _flops_of(b):
    return 1000.0 * b  # linear stand-in: per-sample flops constant


def _ratios_stub(rate, our_sweep=None):
    return {"vs_baseline": rate / 10.0}


def flagship_out(value=12970.0):
    """An `out` dict as it looks after the B=64 flagship measurement."""
    return {
        "value": value,
        "sec_per_step": 64 / value,
        "encode_rows": 2560,
        "batch_size": 64,
        "headline_source": "flagship_b64",
        "flops_per_step": _flops_of(64),
        "mfu_estimate": 0.1,
    }


def test_promotion_is_unconditional_even_when_b64_beats_sweep():
    # an inflated fast-window B=64 reading (12,970) must NOT survive as the
    # headline when the stable sweep rows top out lower
    out = flagship_out(value=12970.0)
    sweep = {"128": 7000.0, "256": 9000.0}
    _promote_best_sweep_row(out, sweep, _flops_of, peak=197e12, ratios=_ratios_stub)
    assert out["headline_source"] == "b_sweep_uncapped"
    assert out["value"] == 9000.0
    assert out["batch_size"] == 256
    # the flagship point is preserved under b64_*, not promoted
    assert out["b64_samples_per_sec"] == 12970.0
    assert out["b64_encode_rows"] == 2560


def test_promotion_recomputes_flops_and_mfu_for_promoted_row():
    out = flagship_out()
    sweep = {"1024": 40000.0}
    _promote_best_sweep_row(out, sweep, _flops_of, peak=197e12, ratios=_ratios_stub)
    assert out["flops_per_step"] == _flops_of(1024)  # not the stale B=64 value
    dt = 1024 / 40000.0
    assert out["mfu_estimate"] == round(_flops_of(1024) / dt / 197e12, 4)


def test_promotion_peak_none_clears_mfu_but_sets_flops():
    # previously: peak=None left flops_per_step at the B=64 value while
    # batch_size/sec_per_step were overwritten — inconsistent artifact
    out = flagship_out()
    sweep = {"512": 30000.0}
    _promote_best_sweep_row(out, sweep, _flops_of, peak=None, ratios=_ratios_stub)
    assert out["flops_per_step"] == _flops_of(512)
    assert "mfu_estimate" not in out


def test_promotion_idempotent_b64_capture():
    # called after every sweep point: the b64_* capture happens exactly
    # once (first promotion), later calls must not clobber it with
    # already-promoted values
    out = flagship_out(value=3060.0)
    _promote_best_sweep_row(out, {"128": 7000.0}, _flops_of, None, _ratios_stub)
    first_b64 = out["b64_samples_per_sec"]
    _promote_best_sweep_row(
        out, {"128": 7000.0, "1024": 41000.0}, _flops_of, None, _ratios_stub
    )
    assert out["b64_samples_per_sec"] == first_b64 == 3060.0
    assert out["value"] == 41000.0


def test_promotion_noop_without_sweep_rows():
    out = flagship_out()
    _promote_best_sweep_row(out, {}, _flops_of, None, _ratios_stub)
    assert out["headline_source"] == "flagship_b64"
    assert out["value"] == flagship_out()["value"]


def _write_baseline(tmp_path, sweep):
    p = tmp_path / "baseline_host.json"
    p.write_text(
        json.dumps({"samples_per_sec": 5.0, "b_sweep_samples_per_sec": sweep})
    )
    return p


def test_ratio_clamps_to_baseline_measured_range(tmp_path):
    # baseline measured up to B=1024; our best row is at B=4096 — the
    # ratio must use our best rate among B<=1024 rows
    p = _write_baseline(
        tmp_path, {"64": 10.0, "1024": 18.0, "1024_dedup": 148.0}
    )
    ours = {"512": 33000.0, "1024": 41000.0, "4096": 90000.0}
    f = _baseline_ratios(p, 90000.0, our_sweep=ours)
    assert f["ratio_rate_used"] == 41000.0
    assert f["ratio_clamped_to_b"] == 1024
    assert f["vs_baseline"] == round(41000.0 / 148.0, 2)
    assert f["vs_reference_no_dedup"] == round(41000.0 / 18.0, 2)


def test_ratio_no_clamp_when_baseline_covers_our_max_b(tmp_path):
    p = _write_baseline(
        tmp_path,
        {"64": 10.0, "1024": 18.0, "4096": 20.0, "4096_dedup": 200.0},
    )
    ours = {"1024": 41000.0, "4096": 90000.0}
    f = _baseline_ratios(p, 90000.0, our_sweep=ours)
    assert "ratio_clamped_to_b" not in f
    assert f["vs_baseline"] == round(90000.0 / 200.0, 2)


def test_ratio_dedup_suffix_parses_for_max_b(tmp_path):
    # a baseline whose LARGEST measured B exists only as a _dedup row still
    # counts as measured at that B
    p = _write_baseline(tmp_path, {"64": 10.0, "2048_dedup": 160.0})
    ours = {"1024": 41000.0, "2048": 50000.0, "4096": 90000.0}
    f = _baseline_ratios(p, 90000.0, our_sweep=ours)
    assert f["ratio_clamped_to_b"] == 2048
    assert f["ratio_rate_used"] == 50000.0


def test_ratio_missing_baseline_returns_empty(tmp_path):
    assert _baseline_ratios(tmp_path / "nope.json", 100.0) == {}


def test_ratio_annotates_when_no_row_in_baseline_range(tmp_path):
    # every small-B point failed this window: no candidate <= base_max_b.
    # The ratio must carry an explicit beyond-range annotation instead of
    # silently reinstating the unmeasured-baseline comparison
    p = _write_baseline(tmp_path, {"64": 10.0, "1024_dedup": 148.0})
    f = _baseline_ratios(p, 90000.0, our_sweep={"2048": 90000.0})
    assert f["ratio_beyond_baseline_range"] is True
    assert f["vs_baseline"] == round(90000.0 / 148.0, 2)


def test_promotion_clamp_uses_b64_flagship_when_small_b_rows_failed(tmp_path):
    # the B=64 flagship is a measured in-range point — with it captured
    # under b64_*, a window where only B=2048 succeeded still clamps to a
    # measured row (the conservative dispatch-bound flagship), and a later
    # promotion that un-bites the clamp drops the stale annotations
    p = _write_baseline(tmp_path, {"64": 10.0, "1024_dedup": 148.0})

    def ratios(rate, our_sweep=None):
        return _baseline_ratios(p, rate, our_sweep)

    out = flagship_out(value=3000.0)
    _promote_best_sweep_row(out, {"2048": 50000.0}, _flops_of, None, ratios)
    assert out["ratio_rate_used"] == 3000.0  # the captured b64 flagship row
    assert out["ratio_clamped_to_b"] == 1024
    assert "ratio_beyond_baseline_range" not in out

    # B=1024 lands on a later call: clamp no longer bites, stale fields go
    _promote_best_sweep_row(
        out, {"2048": 50000.0, "1024": 60000.0}, _flops_of, None, ratios
    )
    assert out["value"] == 60000.0
    assert "ratio_rate_used" not in out
    assert "ratio_clamped_to_b" not in out


# ---------------------------------------------------------------------------
# provenance stamps: what git says about the tree, and what it says outside one


def _git(tmp, *args):
    import subprocess

    r = subprocess.run(
        ["git", *args], cwd=tmp, capture_output=True, text=True, check=True
    )
    return r.stdout.strip()


def _mini_repo(tmp_path):
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "config", "user.email", "t@t")
    _git(tmp_path, "config", "user.name", "t")
    (tmp_path / "fedrec_tpu").mkdir()
    (tmp_path / "fedrec_tpu" / "a.py").write_text("x = 1\n")
    (tmp_path / "README.md").write_text("v1\n")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "base")
    return _git(tmp_path, "rev-parse", "HEAD")


def test_git_dirty_paths_unquoted_with_spaces(tmp_path):
    from fedrec_tpu.utils.provenance import git_dirty_paths

    _mini_repo(tmp_path)
    (tmp_path / "fedrec_tpu" / "a b.py").write_text("z = 1\n")
    _git(tmp_path, "add", "fedrec_tpu/a b.py")
    assert git_dirty_paths(tmp_path) == ["fedrec_tpu/a b.py"]


def test_git_dirty_paths_records_staged_rename_source(tmp_path):
    from fedrec_tpu.utils.provenance import git_dirty_paths

    _mini_repo(tmp_path)
    _git(tmp_path, "mv", "fedrec_tpu/a.py", "notes.md")
    assert "fedrec_tpu/a.py" in git_dirty_paths(tmp_path)


def test_provenance_records_runtime_versions():
    from fedrec_tpu.utils.provenance import provenance, runtime_versions

    vers = runtime_versions()
    assert "jax" in vers and "jaxlib" in vers  # installed in this image
    assert provenance()["runtime_versions"] == vers


def test_git_calls_do_not_raise_outside_a_checkout(tmp_path):
    """The chip machine's copy of the repo is not a git checkout: every git
    helper degrades to its unknown sentinel, and a stamp is still made."""
    from fedrec_tpu.utils import provenance as prov

    assert prov.git_head(tmp_path) == "unknown"
    assert prov.git_dirty_paths(tmp_path) is None
    assert prov.git_dirty(tmp_path) is None
    gone = tmp_path / "not" / "there"
    assert prov.git_head(gone) == "unknown"
    assert prov.git_dirty_paths(gone) is None


def test_provenance_without_git_binary(monkeypatch, tmp_path):
    """No git on PATH at all (a sealed machine): still no exception."""
    from fedrec_tpu.utils import provenance as prov

    monkeypatch.setenv("PATH", str(tmp_path))
    stamp = prov.provenance()
    assert stamp["commit"] == "unknown" and stamp["dirty_paths"] is None
