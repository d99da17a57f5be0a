"""Test harness configuration.

Mirrors the reference's local-mode-Spark-as-cluster trick (SURVEY.md §4.1):
tests run on a *virtual 8-device CPU mesh* so sharded decode/sort/merge
exercises real multi-device semantics with no TPU attached. Must run
before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Nothing imports jax before this file (no pytest plugin does), so the
# env vars above are all it takes: jax reads JAX_PLATFORMS at import and
# the CPU client reads XLA_FLAGS when it is first created.

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running soak/chaos tests (excluded by tier-1)"
    )


@pytest.fixture()
def tmp_fs():
    from disq_tpu.fsw import PosixFileSystemWrapper

    return PosixFileSystemWrapper()


@pytest.fixture()
def mem_fs():
    from disq_tpu.fsw import MemoryFileSystemWrapper

    return MemoryFileSystemWrapper()
