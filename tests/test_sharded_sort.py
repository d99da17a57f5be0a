"""Multi-chip sort tests on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

import jax

from disq_tpu.sort.sharded import (
    make_mesh,
    sample_splitters,
    sharded_coordinate_sort,
)
from disq_tpu.sort.coordinate import coordinate_keys


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
    return make_mesh(8)


class TestShardedSort:
    @pytest.mark.parametrize("n", [0, 1, 7, 1000, 65_536, 100_001])
    def test_matches_numpy(self, mesh, n):
        rng = np.random.default_rng(n)
        keys = rng.integers(0, 1 << 62, n, dtype=np.uint64)
        sorted_keys, perm = sharded_coordinate_sort(keys, mesh)
        np.testing.assert_array_equal(sorted_keys, np.sort(keys))
        np.testing.assert_array_equal(keys[perm], np.sort(keys))

    def test_skewed_keys(self, mesh):
        # Heavy skew: 90% identical keys — stresses capacity/overflow path.
        rng = np.random.default_rng(5)
        keys = np.where(
            rng.random(50_000) < 0.9,
            np.uint64(42),
            rng.integers(0, 1 << 60, 50_000, dtype=np.uint64),
        )
        sorted_keys, perm = sharded_coordinate_sort(keys, mesh)
        np.testing.assert_array_equal(sorted_keys, np.sort(keys))

    def test_coordinate_key_order_semantics(self, mesh):
        # Unmapped (refid -1) must land after every mapped record.
        refid = np.array([1, -1, 0, 2, -1, 0], dtype=np.int32)
        pos = np.array([5, -1, 100, 1, -1, 2], dtype=np.int32)
        keys = coordinate_keys(refid, pos)
        sorted_keys, perm = sharded_coordinate_sort(keys, mesh)
        got = [(int(refid[i]), int(pos[i])) for i in perm]
        assert got == [(0, 2), (0, 100), (1, 5), (2, 1), (-1, -1), (-1, -1)]

    def test_splitters_deterministic(self):
        keys = np.arange(10_000, dtype=np.uint64)
        a = sample_splitters(keys, 8)
        b = sample_splitters(keys, 8)
        np.testing.assert_array_equal(a, b)


class TestResidentSortColdProcess:
    def test_first_call_in_a_fresh_process_under_the_guard(self):
        """``resident_coordinate_sort`` first thing in a process: its
        key build is traced cold under ``transfer_guard("disallow")``.
        On a chip a device array closed over by a jitted stage is read
        back (d2h) at that first trace and the guard raises; the CPU
        backend's d2h is no transfer, so what is held here as well is
        that the traced key build closes over no device array."""
        import os
        import subprocess
        import sys

        code = """
import numpy as np, jax, jax.numpy as jnp
from disq_tpu.runtime.mesh import batch_sharding, get_mesh, replicated
from disq_tpu.sort import sharded
from disq_tpu.sort.coordinate import coordinate_keys
mesh = get_mesh(4)
rng = np.random.default_rng(7)
n, m = 3000, 4096
refid = np.zeros(m, np.int32); pos = np.zeros(m, np.int32)
refid[:n] = rng.integers(-1, 3, n); pos[:n] = rng.integers(-1, 40, n)
r = jax.device_put(refid, batch_sharding(mesh))
p = jax.device_put(pos, batch_sharding(mesh))
order = sharded.resident_coordinate_sort(r, p, n, mesh)
want = np.argsort(coordinate_keys(refid[:n], pos[:n]), kind="stable")
assert np.array_equal(order, want)
n_arr = jax.device_put(jnp.asarray(np.int32(n)), replicated(mesh))
consts = sharded._resident_keys_compiled(mesh, "batch", 4).trace(
    r, p, n_arr).jaxpr.consts
assert not any(isinstance(c, jax.Array) for c in consts), consts
assert not isinstance(sharded.SENT32, jax.Array)
print("OK")
"""
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env={"PATH": "/usr/local/bin:/usr/bin:/bin",
                 "JAX_PLATFORMS": "cpu", "XLA_FLAGS":
                 "--xla_force_host_platform_device_count=8"})
        assert r.returncode == 0, r.stderr[-2000:]
        assert "OK" in r.stdout

    def test_the_guard_still_wraps_the_key_build(self):
        import inspect

        from disq_tpu.sort import sharded

        src = inspect.getsource(sharded.resident_coordinate_sort)
        guard = src.index('jax.transfer_guard("disallow")')
        assert guard < src.index("_resident_keys_compiled(") \
            < src.index("_psum_splitters(")
