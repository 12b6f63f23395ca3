"""The per-test time limit of ``tests/conftest.py``: a test that waits past
its limit fails with a traceback naming where it waited, under the xdist
worker the driver's command runs tests in, and the run goes on."""

from __future__ import annotations

import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest


@pytest.mark.time_limit(1)
def test_limit_fires_in_this_worker_and_names_the_waiting_line():
    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception, match="time limit of 1 s") as exc:
        time.sleep(30)
    assert time.monotonic() - t0 < 5.0
    waited = [e for e in exc.traceback if Path(e.path) == Path(__file__)]
    assert "time.sleep(30)" in str(waited[-1].statement)


def test_a_test_past_its_limit_fails_and_the_next_one_runs(tmp_path):
    shutil.copy(Path(__file__).with_name("conftest.py"), tmp_path)
    (tmp_path / "test_waits.py").write_text(
        "import time\n"
        "import pytest\n"
        "@pytest.mark.time_limit(1)\n"
        "def test_waits():\n"
        "    time.sleep(30)\n"
        "def test_after():\n"
        "    pass\n"
    )
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "test_waits.py", "-p", "xdist", "-n", "1",
         "-p", "no:cacheprovider"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
    )
    assert run.returncode == 1, run.stdout + run.stderr
    assert "1 failed, 1 passed" in run.stdout
    assert ">       time.sleep(30)" in run.stdout
    assert "test_waits exceeded its time limit of 1 s" in run.stdout
    # the dump of every thread's stack lands in the report too
    assert 'test_waits.py", line 5 in test_waits' in run.stdout
