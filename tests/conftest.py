"""Test harness: fake an 8-device CPU mesh so multi-client SPMD paths run
without TPUs — the JAX-native analogue of the reference's localhost-gloo
``torchrun --nproc-per-node=N`` trick (reference ``README.md:27-34``).

Must set flags before jax initializes its backends, hence the env mutation at
import time.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# XLA:CPU runs each participant of an in-process collective on one thread of
# the PjRt client's pool and blocks it there until all have arrived. The pool
# has max(schedulable CPUs, devices) threads: 8 for the 8 fake devices on an
# 8-core machine. Eager collectives dispatched back to back
# (Trainer._clients_in_sync: one all-reduce per parameter leaf) then take each
# other's threads under load, none gets all 8, and after 40 s XLA aborts the
# process (seen under six xdist workers in test_shard_fsdp and
# test_obs_trainer). PJRT_NPROC sizes the pool: a loop of such collectives in
# 14 processes at once still deadlocks in half of them with 12 threads and in
# none with 16; child processes inherit it through cpu_host_env.
os.environ.setdefault("PJRT_NPROC", "32")

import faulthandler  # noqa: E402
import signal  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Every test gets a time limit of its own: one that waits forever fails,
# with a traceback of where it waited, and the run goes on (pytest-timeout
# is not installed). The slowest phases under six workers take 52-54 s, 61 s
# on a machine that is busy besides.
TIME_LIMIT_S = 120.0


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    """Arm SIGALRM around the phase; ``@pytest.mark.time_limit(seconds)``
    overrides the suite's limit. A no-op without ``setitimer`` or off the
    main thread, where no handler can be set."""
    if (not hasattr(signal, "setitimer")
            or threading.current_thread() is not threading.main_thread()):
        return (yield)
    mark = item.get_closest_marker("time_limit")
    limit = float(mark.args[0]) if mark else TIME_LIMIT_S

    def expired(signum, frame):
        faulthandler.dump_traceback(file=2, all_threads=True)
        # Failed derives from BaseException: no `except Exception` (or
        # OSError, as TimeoutError would be) in the code under test eats it
        pytest.fail(f"{item.nodeid} exceeded its time limit of {limit:g} s")

    old = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


# fixtures that build or tear down wait too: the same limit for each phase
pytest_runtest_setup = pytest_runtest_teardown = pytest_runtest_call


@pytest.fixture(scope="session")
def synthetic_mind():
    from fedrec_tpu.data import make_synthetic_mind

    return make_synthetic_mind(num_news=128, num_train=96, num_valid=24, seed=7)


@pytest.fixture(scope="session")
def reference_shard():
    """The tiny demo shard shipped with the reference (4 train / 1 valid)."""
    from fedrec_tpu.data import load_mind_artifacts

    path = "/root/reference/UserData"
    if not os.path.isdir(path):
        pytest.skip("reference UserData not available")
    return load_mind_artifacts(path)


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
