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

import numpy as np  # noqa: E402
import pytest  # noqa: E402


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
