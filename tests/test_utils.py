"""Tests for the observability utilities (`fedrec_tpu.utils`, `hostenv`)."""

from __future__ import annotations

import io
import json

import jax.numpy as jnp
import numpy as np

from fedrec_tpu.hostenv import cpu_host_env, fake_device_count
from fedrec_tpu.utils.logging import MetricLogger
from fedrec_tpu.utils.profiling import profile_if


def test_metric_logger_schema():
    """One JSON record per log call: step + elapsed + the 6-metric schema
    (reference ``client.py:182-189``), device scalars coerced to float."""
    buf = io.StringIO()
    logger = MetricLogger(use_wandb=False, stream=buf)
    logger.log(0, {
        "training_loss": jnp.float32(1.5), "valid_loss": 1.2,
        "valid_auc": np.float64(0.7), "valid_mrr": 0.3,
        "val_ndcg@5": 0.35, "val_ndcg@10": 0.42,
    })
    logger.log(1, {"training_loss": 1.4})
    logger.finish()

    lines = [json.loads(ln) for ln in buf.getvalue().splitlines()]
    assert [r["step"] for r in lines] == [0, 1]
    first = lines[0]
    assert first["training_loss"] == 1.5          # device scalar -> float
    assert isinstance(first["valid_auc"], float)
    assert set(first) >= {"step", "elapsed_sec", "training_loss", "valid_loss",
                          "valid_auc", "valid_mrr", "val_ndcg@5", "val_ndcg@10"}
    json.dumps(lines)  # everything serializable


def test_metric_logger_stringifies_non_numerics_and_flushes(tmp_path):
    """Non-float-coercible values land in the JSONL record as STRINGS (a
    dict/ndarray payload used to produce an unserializable or lossy line),
    the stream is flushed per line, and every numeric metric doubles as a
    registry gauge (the obs backend)."""
    from fedrec_tpu.obs import MetricsRegistry

    class FlushCounting(io.StringIO):
        flushes = 0

        def flush(self):
            type(self).flushes += 1
            super().flush()

    reg = MetricsRegistry()
    buf = FlushCounting()
    jsonl = tmp_path / "run.jsonl"
    logger = MetricLogger(stream=buf, jsonl_path=str(jsonl), registry=reg)
    logger.log(0, {
        "training_loss": 1.25,
        "numeric_string": "1.5",              # strings STAY strings
        "mode": "head",
        "payload": {"nested": [1, 2]},        # stringified, not dropped
        "arr": np.arange(3),                  # >1-element ndarray: stringified
        "p50_ms": None,                       # JSON null, NOT the string "None"
    })
    assert FlushCounting.flushes >= 1
    logger.finish()

    rec = json.loads(buf.getvalue().splitlines()[0])
    assert rec["training_loss"] == 1.25
    assert rec["numeric_string"] == "1.5"
    assert rec["mode"] == "head"
    assert isinstance(rec["payload"], str) and "nested" in rec["payload"]
    assert isinstance(rec["arr"], str)
    assert rec["p50_ms"] is None  # serving's pre-traffic percentiles stay null
    # the sidecar event log got the same line, already flushed to disk
    assert json.loads(jsonl.read_text().splitlines()[0]) == rec
    # registry backend: numerics became gauges, non-numerics did not
    assert reg.gauge("training_loss").value() == 1.25
    assert "mode" not in reg.names()
    assert reg.counter("log.records_total").value() == 1


def test_metric_logger_wandb_degrades_to_stdout(monkeypatch):
    """No wandb auth in this environment: use_wandb=True must not raise and
    must keep stdout logging working (the reference instead hardcoded an API
    key, ``client.py:214``)."""
    monkeypatch.delenv("WANDB_API_KEY", raising=False)
    monkeypatch.setenv("WANDB_MODE", "disabled")
    buf = io.StringIO()
    logger = MetricLogger(use_wandb=True, stream=buf)
    logger.log(0, {"training_loss": 1.0})
    logger.finish()
    assert json.loads(buf.getvalue().splitlines()[0])["training_loss"] == 1.0


def test_profile_if_writes_trace(tmp_path):
    """enabled=True wraps the region in a jax.profiler trace, YIELDS the
    logdir (the caller's handle on the artifact), and leaves a
    TensorBoard-compatible file; enabled=False is a no-op yielding None."""
    with profile_if(False, str(tmp_path / "off")) as where:
        jnp.ones((8, 8)).sum().block_until_ready()
    assert where is None
    assert not (tmp_path / "off").exists()

    logdir = tmp_path / "on"
    with profile_if(True, str(logdir)) as where:
        (jnp.ones((16, 16)) @ jnp.ones((16, 16))).block_until_ready()
    assert where == str(logdir)
    traces = list(logdir.rglob("*.xplane.pb"))
    assert traces, f"no trace written under {logdir}"


def test_cpu_host_env_recipe():
    """What ``cpu_host_env`` does: pins the CPU, leaves exactly one
    device-count flag, keeps every other variable and XLA flag."""
    base = {
        "JAX_PLATFORMS": "tpu",
        "XLA_FLAGS": "--xla_foo=1 --xla_force_host_platform_device_count=2",
        "OTHER": "kept",
    }
    env = cpu_host_env(8, base=base)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["OTHER"] == "kept"
    assert set(env) == set(base)  # nothing added, nothing dropped
    # exactly one devcount flag, other XLA flags preserved
    assert env["XLA_FLAGS"].count("device_count") == 1
    assert "--xla_foo=1" in env["XLA_FLAGS"]
    assert fake_device_count(env) == 8
    # n_devices=None leaves XLA_FLAGS untouched
    env2 = cpu_host_env(base=base)
    assert env2["XLA_FLAGS"] == base["XLA_FLAGS"]
    assert fake_device_count({"XLA_FLAGS": "--nope"}) is None
    # pure function: the base mapping is never mutated
    assert base["JAX_PLATFORMS"] == "tpu"
    assert fake_device_count(base) == 2


def test_git_provenance_helpers(tmp_path):
    """`git_head`/`git_dirty` report a real checkout honestly and degrade to
    their unknown sentinels outside one (bench.py's cached-result staleness
    flag is built on exactly these two answers)."""
    import subprocess

    from fedrec_tpu.utils.provenance import git_dirty, git_head

    # this repo: a short hex head; dirty is a definite bool
    head = git_head()
    assert head != "unknown" and all(c in "0123456789abcdef" for c in head)
    assert git_dirty() in (True, False)

    # a fresh repo with one commit: clean, then dirty after a TRACKED edit
    # (hermetic: the user's global/system git config must not leak in —
    # e.g. commit.gpgsign=true would fail the commit)
    import os

    repo = tmp_path / "r"
    repo.mkdir()
    env = dict(os.environ,
               GIT_CONFIG_GLOBAL="/dev/null", GIT_CONFIG_SYSTEM="/dev/null")
    run = lambda *a: subprocess.run(  # noqa: E731
        a, cwd=repo, capture_output=True, text=True, check=True, env=env
    )
    run("git", "init", "-q")
    (repo / "f").write_text("x")
    run("git", "add", "f")
    run("git", "-c", "user.email=t@t", "-c", "user.name=t",
        "commit", "-q", "-m", "x")
    assert git_dirty(repo) is False
    (repo / "untracked").write_text("x")
    assert git_dirty(repo) is False  # untracked scratch files don't count
    (repo / "f").write_text("y")
    assert git_dirty(repo) is True

    # not a repo at all -> sentinels, no raise
    bare = tmp_path / "bare"
    bare.mkdir()
    assert git_head(bare) == "unknown"
    assert git_dirty(bare) is None


def test_write_artifact_stages_partial_and_completes_atomically(tmp_path):
    # partial stamps go to the .inprogress sidecar (a wedged re-run must
    # never clobber banked complete evidence), with "partial" as the FIRST
    # serialized key (a torn tail then cannot keep the provenance block
    # while dropping the flag); completion replaces the canonical file,
    # removes the sidecar, and leaves no temp file behind
    import json

    from fedrec_tpu.utils.provenance import write_artifact

    p = tmp_path / "art.json"
    p.write_text(json.dumps({"banked": "complete evidence"}))
    side = tmp_path / "art.inprogress.json"

    write_artifact(p, {"a": 1, "provenance": {"jax_backend": "tpu"}}, True)
    # canonical untouched; sidecar carries the flagged partial
    assert json.loads(p.read_text()) == {"banked": "complete evidence"}
    raw = side.read_text()
    assert raw.index('"partial"') < raw.index('"provenance"')
    assert json.loads(raw)["partial"] is True

    write_artifact(p, {"a": 2}, False)
    d = json.loads(p.read_text())
    assert "partial" not in d and d["a"] == 2
    assert list(tmp_path.iterdir()) == [p]


def test_write_artifact_strips_replayed_partial_key(tmp_path):
    """A replayed payload already carrying a 'partial' key (e.g. a harness
    re-stamping a previously banked dict) must not override THIS write's
    flag: partial=False in the payload cannot mark a sidecar complete, and
    a stale partial=True cannot linger in a completing write (ADVICE r5)."""
    import json

    from fedrec_tpu.utils.provenance import write_artifact

    p = tmp_path / "art.json"
    side = tmp_path / "art.inprogress.json"

    # replayed complete payload, staged as partial: the sidecar must read
    # partial=True, serialized first, regardless of the stowaway key
    write_artifact(p, {"partial": False, "a": 1, "provenance": {}}, True)
    raw = side.read_text()
    assert json.loads(raw)["partial"] is True
    assert raw.index('"partial"') < raw.index('"provenance"')

    # replayed partial payload, completing write: no partial flag survives
    write_artifact(p, {"partial": True, "a": 2}, False)
    assert json.loads(p.read_text()) == {"a": 2}


# ------------------------------------------------------------ compile cache
def test_compile_cache_left_to_the_environment(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it, the program sets no
    directory in code."""
    import jax

    from fedrec_tpu.utils.compile_cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_path_inside_checkout(monkeypatch):
    """Unset: one fixed path under the checkout, the same on every call (a
    directory that moves never hits)."""
    from pathlib import Path

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from fedrec_tpu.utils.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first, second = enable_compile_cache(), enable_compile_cache()
        repo = Path(__file__).resolve().parents[1]
        assert first == second == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        # the tier-1 tests run with the cache off
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_native_library_is_stale_when_older_than_its_sources(tmp_path, monkeypatch):
    """git does not carry ``libfedrec_data.so``; a copied tree may carry a
    stale one, which must be rebuilt and not loaded without a word."""
    import os

    from fedrec_tpu.data import native_batcher

    lib = tmp_path / "libfedrec_data.so"
    src = tmp_path / "fedrec_data.cpp"
    mk = tmp_path / "Makefile"
    for f in (src, mk, lib):
        f.write_text("x")
    monkeypatch.setattr(native_batcher, "_NATIVE_DIR", tmp_path)
    monkeypatch.setattr(native_batcher, "_LIB_PATH", lib)
    now = lib.stat().st_mtime
    os.utime(src, (now - 10, now - 10))
    os.utime(mk, (now - 10, now - 10))
    assert not native_batcher._is_stale()
    os.utime(src, (now + 10, now + 10))
    assert native_batcher._is_stale()
    os.utime(src, (now - 10, now - 10))
    os.utime(mk, (now + 10, now + 10))
    assert native_batcher._is_stale()


# ------------------------------------------------- fedrec-run input refusal
def test_run_cli_refuses_to_train_on_random_states_for_real_data(
    tmp_path, capsys, synthetic_mind
):
    """Random token states are for ``--synthetic`` only: real ``--data-dir``
    artifacts without cached states exit 2 (as fedrec-serve does), and so
    does a data directory that is not there."""
    from fedrec_tpu.cli import run
    from fedrec_tpu.data.preprocess import write_artifacts

    assert run.main(["1", "4", "1", "--data-dir", str(tmp_path / "none")]) == 2
    assert "no data directory" in capsys.readouterr().err

    write_artifacts(synthetic_mind, tmp_path / "UserData")
    rc = run.main(["1", "4", "1", "--data-dir", str(tmp_path / "UserData")])
    assert rc == 2
    assert "no token states" in capsys.readouterr().err

