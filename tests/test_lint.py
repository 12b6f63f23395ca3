"""fedrec-lint: per-analyzer fixture proofs + the self-run gate.

Layout (docs/ANALYSIS.md §4): every analyzer is pinned by one
TRUE-positive fixture (the defect is found) and one FALSE-positive /
suppression fixture (correct idioms stay silent).  The self-run test at
the bottom pins ``fedrec-lint`` exiting 0 on the repo tree itself, so any
future drift — an undocumented flag, an uncatalogued metric, a guard
missing from the feature matrix, a host sync in a step builder — fails
tier-1 right here.
"""

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from fedrec_tpu.analysis import (
    CODE_CATALOG,
    codes_table,
    finding_fingerprint,
    run_lint,
    write_baseline,
    write_docs_table,
)
from fedrec_tpu.analysis import donation, generic, trace_safety
from fedrec_tpu.analysis.core import Project, ProjectFile

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "fixtures" / "lint"


def load_fixture(name: str) -> ProjectFile:
    # fixtures load with a fedrec_tpu/-prefixed virtual path so the
    # per-file analyzers treat them as in-package sources
    src = (FIXTURES / name).read_text()
    import ast

    from fedrec_tpu.analysis.core import parse_suppressions

    return ProjectFile(
        path=f"fedrec_tpu/_fixture_/{name}",
        abspath=FIXTURES / name,
        src=src,
        tree=ast.parse(src),
        lines=src.splitlines(),
        suppressions=parse_suppressions(src),
    )


def apply_suppressions(pf: ProjectFile, findings):
    return [f for f in findings if not pf.suppressions.covers(f)]


# --------------------------------------------------------------- trace safety


def test_trace_safety_true_positives():
    pf = load_fixture("ts_true_positive.py")
    codes = sorted(f.code for f in trace_safety.analyze_file(pf))
    assert "TS101" in codes
    assert "TS102" in codes
    assert "TS103" in codes
    assert codes.count("TS104") == 2            # time.time AND random.random
    assert "TS105" in codes


def test_trace_safety_false_positives_and_suppression():
    pf = load_fixture("ts_false_positive.py")
    findings = apply_suppressions(pf, trace_safety.analyze_file(pf))
    assert findings == [], [f.format() for f in findings]
    # the suppression really did cover a live TS102 (not a silent no-op)
    raw = trace_safety.analyze_file(pf)
    assert any(f.code == "TS102" for f in raw)


def test_trace_safety_call_propagation():
    # the repo's real builder shape: local_step is only CALLED from (and
    # passed as a value into) the jitted sharded_step
    pf = load_fixture("ts_call_propagation.py")
    findings = trace_safety.analyze_file(pf)
    assert [f.code for f in findings] == ["TS101"]


def test_step_builders_are_traced_scopes():
    """Pin the production coverage: step.py's local_step and the sync body
    must be traced scopes, or the tentpole checks nothing that matters."""
    project = Project.load(REPO)
    pf = project.file("fedrec_tpu/train/step.py")
    traced = trace_safety._collect_traced_functions(pf.tree, pf.lines)
    names = {getattr(f, "name", "") for f in traced}
    for expected in ("local_step", "sharded_step", "local_sync",
                     "sharded_sync", "local_update", "sharded_update"):
        assert expected in names, (expected, sorted(names))
    rb = project.file("fedrec_tpu/fed/robust.py")
    rb_traced = trace_safety._collect_traced_functions(rb.tree, rb.lines)
    rb_names = {getattr(f, "name", "") for f in rb_traced}
    assert "robust_aggregate" in rb_names        # the explicit marker
    assert "robust_reduce_np" not in rb_names    # the numpy host twin


def test_traced_scope_marker():
    pf = load_fixture("ts_false_positive.py")
    traced = trace_safety._collect_traced_functions(pf.tree, pf.lines)
    names = {getattr(f, "name", "") for f in traced}
    assert "marked_aggregate" in names          # the explicit marker
    assert "host_side" not in names             # plain host code


# ------------------------------------------------------------------- donation


def test_donation_true_positive():
    pf = load_fixture("da_true_positive.py")
    findings = donation.analyze_file(pf)
    assert [f.code for f in findings] == ["DA501"]
    assert "`batch`" in findings[0].message


def test_donation_false_positives():
    pf = load_fixture("da_false_positive.py")
    findings = donation.analyze_file(pf)
    assert findings == [], [f.format() for f in findings]


# -------------------------------------------------------------------- generic


def test_generic_true_positives():
    pf = load_fixture("gl_true_positive.py")
    codes = sorted(f.code for f in generic.analyze_file(pf))
    assert codes == ["GL901", "GL902", "GL903"]


def test_generic_false_positives():
    pf = load_fixture("gl_false_positive.py")
    findings = generic.analyze_file(pf)
    assert findings == [], [f.format() for f in findings]


# ------------------------------------------------- project-level (miniproj)


@pytest.fixture()
def miniproj(tmp_path):
    dst = tmp_path / "miniproj"
    shutil.copytree(FIXTURES / "miniproj", dst)
    return dst


def run_mini(root, **kw):
    # default (unfiltered) roots: miniproj has no benchmarks/bench.py and
    # iter_python_files skips absent roots; a narrowed scan_roots would
    # count as a path FILTER and drop the docs/toml-level findings
    kw.setdefault("baseline_path", None)
    return run_lint(root, **kw)


def test_config_contract_on_miniproj(miniproj):
    codes = {}
    for f in run_mini(miniproj, analyzers=["config_contract"]).findings:
        codes.setdefault(f.code, []).append(f.message)
    assert any("fed.roundz" in m for m in codes["CC201"])
    assert any("data.dead_knob" in m for m in codes["CC202"])
    assert any("data.dead_knob" in m for m in codes["CC203"])
    # the documented/annotation-alias reads produced NO findings
    all_msgs = [m for ms in codes.values() for m in ms]
    assert not any("data.documented" in m for m in all_msgs)
    assert not any("data.batch_size" in m for m in all_msgs)


def test_metric_contract_on_miniproj(miniproj):
    found = run_mini(miniproj, analyzers=["metric_contract"]).findings
    by_code = {}
    for f in found:
        by_code.setdefault(f.code, []).append(f.message)
    assert any("app.missing_gauge" in m for m in by_code["MC301"])
    assert any("bad name!" in m for m in by_code["MC302"])
    assert any("app.good_total" in m for m in by_code["MC303"])
    # the catalogued, consistent metric is silent
    assert not any(
        "app.good_total" in m for m in by_code.get("MC301", [])
    )


def test_feature_matrix_on_miniproj(miniproj):
    found = run_mini(miniproj, analyzers=["feature_matrix"]).findings
    codes = {f.code for f in found}
    assert codes == {"FM401", "FM402", "FM403"}
    msgs = " ".join(f.message for f in found)
    assert "fixture-unclaimed" in msgs          # FM401 names the guard
    assert "ghost-rule" in msgs                 # FM402 names the rule
    # regenerating the docs table clears FM403 (and only FM403)
    assert write_docs_table(miniproj) is True
    after = {f.code for f in run_mini(miniproj, analyzers=["feature_matrix"]).findings}
    assert after == {"FM401", "FM402"}
    # idempotent: a second write changes nothing
    assert write_docs_table(miniproj) is False


# ------------------------------------------------- engine: baseline + filters


def test_baseline_accepts_and_resurrects(miniproj):
    res = run_mini(miniproj)
    assert res.findings
    bp = miniproj / "baseline.json"
    write_baseline(bp, res.all_fingerprints)
    clean = run_mini(miniproj, baseline_path="baseline.json")
    assert clean.findings == []
    assert clean.baselined == len(res.findings)
    # editing a flagged line resurrects exactly that finding
    app = miniproj / "fedrec_tpu" / "app.py"
    app.write_text(app.read_text().replace(
        "r = cfg.fed.roundz", "r = cfg.fed.roundz  # touched"
    ))
    dirty = run_mini(miniproj, baseline_path="baseline.json")
    assert [f.code for f in dirty.findings] == ["CC201"]


def test_fingerprint_survives_line_shift(miniproj):
    res = run_mini(miniproj)
    target = next(f for f in res.findings if f.code == "CC201")
    pf_lines = (miniproj / "fedrec_tpu" / "app.py").read_text().splitlines()
    fp_before = finding_fingerprint(target, pf_lines)
    # insert lines ABOVE: the fingerprint must not move
    shifted_lines = ["# shim", "# shim"] + pf_lines
    from fedrec_tpu.analysis import Finding

    shifted = Finding(
        path=target.path, line=target.line + 2, col=target.col,
        code=target.code, message=target.message,
    )
    assert finding_fingerprint(shifted, shifted_lines) == fp_before


def test_select_ignore_filters(miniproj):
    only_cc = run_mini(miniproj, select=["CC"])
    assert only_cc.findings and all(
        f.code.startswith("CC") for f in only_cc.findings
    )
    no_cc = run_mini(miniproj, ignore=["CC", "FM403"])
    assert not any(f.code.startswith("CC") for f in no_cc.findings)
    with pytest.raises(ValueError):
        run_mini(miniproj, analyzers=["nope"])


def test_path_scoped_run_keeps_full_project_context(miniproj):
    """Linting a subdirectory must NOT turn the unseen rest of the tree
    into false findings: project analyzers always see the full tree, and
    path args only filter which findings are reported."""
    res = run_lint(REPO, scan_roots=("fedrec_tpu/fed",))
    assert res.findings == [], "\n".join(f.format() for f in res.findings)
    assert res.files_scanned > 10          # full tree loaded, not just fed/
    # no double-loading when the requested root nests under a default one
    full = run_lint(REPO)
    assert res.files_scanned == full.files_scanned
    # the filter really bites — prove it on miniproj, which HAS findings:
    # config.py findings (CC202/CC203 anchor there) survive a config.py
    # scope, everything outside (docs FM403, app.py CC201/MC) is dropped
    scoped = run_mini(miniproj, scan_roots=("fedrec_tpu/config.py",))
    assert scoped.findings, "expected config.py-anchored findings"
    assert all(f.path == "fedrec_tpu/config.py" for f in scoped.findings)
    unfiltered_paths = {f.path for f in run_mini(miniproj).findings}
    assert "fedrec_tpu/app.py" in unfiltered_paths   # dropped by the scope
    # './'-prefixed and absolute spellings are normalized, not false-clean
    dotted = run_mini(miniproj, scan_roots=("./fedrec_tpu/config.py",))
    assert [f.code for f in dotted.findings] == [f.code for f in scoped.findings]
    absolute = run_mini(
        miniproj, scan_roots=(str(miniproj / "fedrec_tpu/config.py"),)
    )
    assert [f.code for f in absolute.findings] == [f.code for f in scoped.findings]
    with pytest.raises(ValueError, match="outside the repo root"):
        run_mini(miniproj, scan_roots=("/etc",))
    # a typo'd in-repo root must ERROR, not lint nothing and report clean
    with pytest.raises(ValueError, match="does not exist"):
        run_mini(miniproj, scan_roots=("fedrec_tpu/nope",))
    # spelling out the default roots is NOT a filter (one definition,
    # owned by the engine)
    assert run_mini(
        miniproj, scan_roots=("./fedrec_tpu", "benchmarks", "bench.py")
    ).filtered is False
    assert scoped.filtered is True


def test_skip_dirs_judged_inside_scan_root(tmp_path):
    # a repo living UNDER a directory named like a skip-dir must scan
    nested = tmp_path / "node_modules" / "repo"
    shutil.copytree(FIXTURES / "miniproj", nested)
    res = run_lint(nested, baseline_path=None)
    assert res.files_scanned > 0
    assert res.findings


def test_file_level_fingerprints_distinguish_messages(miniproj):
    from fedrec_tpu.analysis import Finding

    a = Finding(path="x.toml", line=0, col=0, code="FM402", message="rule A")
    b = Finding(path="x.toml", line=0, col=0, code="FM402", message="rule B")
    assert finding_fingerprint(a, []) != finding_fingerprint(b, [])


@pytest.mark.slow
def test_write_baseline_refuses_filtered_runs():
    res = subprocess.run(
        [sys.executable, "-m", "fedrec_tpu.cli.lint", "--root", str(REPO),
         "--select", "CC", "--write-baseline"],
        capture_output=True, text=True, cwd=str(REPO),
    )
    assert res.returncode == 2
    assert "unfiltered run" in res.stderr
    # an EMPTY --select is presence too, not a bypass: it must not slip
    # past the guard and wipe the baseline with zero fingerprints
    empty = subprocess.run(
        [sys.executable, "-m", "fedrec_tpu.cli.lint", "--root", str(REPO),
         "--select", "", "--write-baseline"],
        capture_output=True, text=True, cwd=str(REPO),
    )
    assert empty.returncode == 2
    assert "empty code list" in empty.stderr


def test_code_catalog_registered():
    codes = {c for c, _, _ in codes_table()}
    for family in ("TS101", "CC201", "MC301", "FM401", "DA501", "GL901"):
        assert family in codes
    assert all(desc for _, (desc, _) in CODE_CATALOG.items())


# ------------------------------------------------------------------ self-run


def test_fedrec_lint_clean_on_repo_tree():
    """THE drift gate: the repo's own tree must lint clean.

    If this fails you added an undocumented flag/metric, a guard missing
    from feature_matrix.toml, a stale docs table, a host sync in a traced
    scope, or generic-layer lint debt — fix the finding (docs/ANALYSIS.md
    maps every code), don't baseline it.
    """
    res = run_lint(REPO)
    assert res.findings == [], "\n".join(f.format() for f in res.findings)
    assert res.files_scanned > 50


@pytest.mark.slow
def test_fedrec_lint_cli_exit_codes():
    # subprocess round-trips of what test_fedrec_lint_clean_on_repo_tree
    # already proves in-process; slow-marked to keep tier-1 lean
    env_root = str(REPO)
    ok = subprocess.run(
        [sys.executable, "-m", "fedrec_tpu.cli.lint", "--root", env_root,
         "--format", "json"],
        capture_output=True, text=True, cwd=env_root,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    payload = json.loads(ok.stdout)
    assert payload["findings"] == []
    listing = subprocess.run(
        [sys.executable, "-m", "fedrec_tpu.cli.lint", "--list-codes"],
        capture_output=True, text=True, cwd=env_root,
    )
    assert listing.returncode == 0
    assert "TS101" in listing.stdout and "GL903" in listing.stdout
