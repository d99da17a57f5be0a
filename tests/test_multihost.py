"""Multi-host scaffold (SURVEY.md §5 distributed comm backend).

No multi-host hardware exists here: the axis planner is pure and
tested directly; the global mesh degrades to the local device set in
one process, and a psum over both mesh axes runs on the virtual
8-device mesh to prove the (dcn, shards) layering compiles and
executes.
"""

import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from disq_tpu.runtime.multihost import (
    global_mesh,
    initialize,
    plan_axes,
    process_count,
    process_id,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestPlanAxes:
    def test_splits(self):
        assert plan_axes(32, 4) == (4, 8)
        assert plan_axes(8, 1) == (1, 8)
        assert plan_axes(8, 8) == (8, 1)

    def test_rejects_uneven(self):
        with pytest.raises(ValueError):
            plan_axes(10, 4)
        with pytest.raises(ValueError):
            plan_axes(8, 0)

    def test_rejects_nonpositive(self):
        with pytest.raises(ValueError, match="n_processes"):
            plan_axes(8, -1)
        with pytest.raises(ValueError, match="n_devices_total"):
            plan_axes(0, 2)
        with pytest.raises(ValueError, match="n_devices_total"):
            plan_axes(-8, 2)


class TestProcessIdentity:
    def test_single_process_defaults(self, monkeypatch):
        monkeypatch.delenv("DISQ_TPU_PROCESS_ID", raising=False)
        monkeypatch.delenv("DISQ_TPU_PROCESS_COUNT", raising=False)
        assert process_id() == 0
        assert process_count() == 1

    def test_env_override_wins(self, monkeypatch):
        monkeypatch.setenv("DISQ_TPU_PROCESS_ID", "3")
        monkeypatch.setenv("DISQ_TPU_PROCESS_COUNT", "4")
        assert process_id() == 3
        assert process_count() == 4

    def test_garbage_env_falls_through(self, monkeypatch):
        monkeypatch.setenv("DISQ_TPU_PROCESS_ID", "nope")
        monkeypatch.setenv("DISQ_TPU_PROCESS_COUNT", "nah")
        assert process_id() == 0
        assert process_count() == 1

    def test_negative_env_rejected_like_count_clamps(self, monkeypatch):
        # a negative process id would corrupt cluster labeling; it must
        # fall through to the default the way process_count clamps >= 1
        monkeypatch.setenv("DISQ_TPU_PROCESS_ID", "-3")
        monkeypatch.setenv("DISQ_TPU_PROCESS_COUNT", "-2")
        assert process_id() == 0
        assert process_count() == 1

    def test_introspect_endpoint_labels_process_multiprocess_mode(
            self, tmp_path):
        """A worker launched with a distinct DISQ_TPU_PROCESS_ID (the
        multi-process labeling path, CPU-simulated) serves that id on
        /metrics (process_info series), /healthz and /progress."""
        code = (
            "import sys, json, urllib.request\n"
            "sys.path.insert(0, %r)\n"
            "from disq_tpu.runtime.introspect import "
            "start_introspect_server\n"
            "addr = start_introspect_server(0)\n"
            "m = urllib.request.urlopen("
            "'http://%%s/metrics' %% addr, timeout=10).read().decode()\n"
            "h = json.load(urllib.request.urlopen("
            "'http://%%s/healthz' %% addr, timeout=10))\n"
            "p = json.load(urllib.request.urlopen("
            "'http://%%s/progress' %% addr, timeout=10))\n"
            "print(json.dumps({'info': 'process_id=\"5\"' in m,"
            " 'healthz': h.get('process_id'),"
            " 'progress': p.get('process_id')}))\n" % REPO)
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=8",
                   DISQ_TPU_PROCESS_ID="5")
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, env=env,
                              cwd=REPO, timeout=120)
        assert proc.returncode == 0, proc.stderr
        doc = json.loads(proc.stdout.strip().splitlines()[-1])
        assert doc == {"info": True, "healthz": 5, "progress": 5}

    def test_introspect_endpoint_labels_process_single_mode(
            self, monkeypatch):
        """Single-process (no env override): the endpoints label
        process 0 — in-process, against a live ephemeral server."""
        from disq_tpu.runtime.introspect import (
            reset_introspection, start_introspect_server)

        monkeypatch.delenv("DISQ_TPU_PROCESS_ID", raising=False)
        try:
            addr = start_introspect_server(0)
            text = urllib.request.urlopen(
                f"http://{addr}/metrics", timeout=10).read().decode()
            assert 'disq_tpu_process_info{process_id="0"' in text
            doc = json.load(urllib.request.urlopen(
                f"http://{addr}/healthz", timeout=10))
            assert doc["process_id"] == 0
        finally:
            reset_introspection()


class TestGlobalMesh:
    def test_single_process_shape(self):
        mesh = global_mesh()
        assert mesh.shape["dcn"] == 1
        assert mesh.shape["shards"] == len(jax.devices())
        assert set(np.asarray(mesh.devices).ravel()) == set(jax.devices())

    def test_virtual_suite_placement_is_ordinal_sorted(self):
        """On the 8-virtual-device suite the single host row holds ALL
        local devices in ascending id order (the explicit
        (process_index, local ordinal) placement)."""
        mesh = global_mesh()
        arr = np.asarray(mesh.devices)
        assert arr.shape == (1, 8)
        row = list(arr[0])
        assert [d.id for d in row] == sorted(d.id for d in jax.devices())
        assert all(d.process_index == 0 for d in row)

    def test_local_ordinals_one_pass_matches_per_device_sort(self):
        """The O(n) ordinal map must equal the old per-device re-sort
        semantics: within each process group, ordinals are the rank of
        the device id."""
        from disq_tpu.runtime.multihost import _local_ordinals

        class Dev:
            def __init__(self, pid, did):
                self.process_index = pid
                self.id = did

            def __repr__(self):
                return f"Dev({self.process_index},{self.id})"

        devs = [Dev(1, 7), Dev(0, 5), Dev(1, 2), Dev(0, 9), Dev(0, 1)]
        ords = _local_ordinals(devs)
        # process 0 devices by id: 1 -> 0, 5 -> 1, 9 -> 2
        assert ords[devs[4]] == 0 and ords[devs[1]] == 1 \
            and ords[devs[3]] == 2
        # process 1: 2 -> 0, 7 -> 1
        assert ords[devs[2]] == 0 and ords[devs[0]] == 1

    def test_custom_axis_names(self):
        mesh = global_mesh(dcn_axis="hosts", ici_axis="local")
        assert mesh.axis_names == ("hosts", "local")
        assert mesh.shape["hosts"] == 1

    def test_initialize_single_process_noop(self):
        initialize(num_processes=1)  # must not raise or require network

    def test_collective_over_both_axes(self):
        from functools import partial
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        mesh = global_mesh()
        n = mesh.shape["dcn"] * mesh.shape["shards"]

        def body(x):
            # inner (ICI) reduction then outer (DCN) reduction — the
            # layering the sort/flagstat collectives use
            s = jax.lax.psum(x, "shards")
            return jax.lax.psum(s, "dcn")

        x = jnp.ones((n, 4))
        out = shard_map(
            body, mesh=mesh, in_specs=P(("dcn", "shards"), None),
            out_specs=P(("dcn", "shards"), None))(x)
        np.testing.assert_array_equal(np.asarray(out), np.full((n, 4), n))


class TestHierarchicalSort:
    """Two-stage (DCN, ICI) sort exchange (sort/sharded.py) on the
    virtual 8-device mesh arranged as hosts x local-devices."""

    def _mesh(self, dcn, ici):
        import numpy as np
        from jax.sharding import Mesh

        devs = np.array(jax.devices()[: dcn * ici]).reshape(dcn, ici)
        return Mesh(devs, ("dcn", "shards"))

    @pytest.mark.parametrize("shape", [(2, 4), (4, 2)])
    def test_matches_flat_sort(self, shape):
        import numpy as np
        from disq_tpu.sort.sharded import hierarchical_coordinate_sort

        rng = np.random.default_rng(0)
        keys = rng.integers(0, 1 << 48, 5000, dtype=np.uint64)
        got_keys, perm = hierarchical_coordinate_sort(
            keys, self._mesh(*shape))
        want = np.sort(keys, kind="stable")
        np.testing.assert_array_equal(got_keys, want)
        np.testing.assert_array_equal(keys[perm], got_keys)

    def test_skewed_keys_retry_or_fallback(self):
        import numpy as np
        from disq_tpu.sort.sharded import hierarchical_coordinate_sort

        # heavy skew: 90% identical keys forces bucket overflow retries
        rng = np.random.default_rng(1)
        keys = np.where(
            rng.random(4000) < 0.9, np.uint64(42),
            rng.integers(0, 1 << 40, 4000, dtype=np.uint64))
        got_keys, perm = hierarchical_coordinate_sort(
            keys, self._mesh(2, 4))
        np.testing.assert_array_equal(got_keys, np.sort(keys))
        np.testing.assert_array_equal(keys[perm], got_keys)

    def test_empty_and_tiny(self):
        import numpy as np
        from disq_tpu.sort.sharded import hierarchical_coordinate_sort

        k0, p0 = hierarchical_coordinate_sort(
            np.zeros(0, np.uint64), self._mesh(2, 4))
        assert len(k0) == 0 and len(p0) == 0
        k1, p1 = hierarchical_coordinate_sort(
            np.array([7, 3, 5], np.uint64), self._mesh(2, 4))
        np.testing.assert_array_equal(k1, [3, 5, 7])
        np.testing.assert_array_equal(
            np.array([7, 3, 5], np.uint64)[p1], k1)

    def test_single_host_degenerates(self):
        import numpy as np
        from disq_tpu.sort.sharded import hierarchical_coordinate_sort

        rng = np.random.default_rng(2)
        keys = rng.integers(0, 1 << 40, 999, dtype=np.uint64)
        got, _ = hierarchical_coordinate_sort(keys, self._mesh(1, 8))
        np.testing.assert_array_equal(got, np.sort(keys))

    def test_duplicate_key_tie_order_matches_flat(self):
        # duplicate coordinates are the norm in real BAM; ties must
        # come back in original-index order on BOTH exchange shapes or
        # multi-host output would diverge from single-host output
        import numpy as np
        from disq_tpu.sort.sharded import (
            hierarchical_coordinate_sort,
            sharded_coordinate_sort,
        )

        rng = np.random.default_rng(3)
        keys = rng.integers(0, 50, 3000, dtype=np.uint64)  # heavy ties
        flat_keys, flat_perm = sharded_coordinate_sort(keys)
        hier_keys, hier_perm = hierarchical_coordinate_sort(
            keys, self._mesh(2, 4))
        np.testing.assert_array_equal(flat_keys, hier_keys)
        np.testing.assert_array_equal(flat_perm, hier_perm)
        # and both equal the stable host argsort
        np.testing.assert_array_equal(
            flat_perm, np.argsort(keys, kind="stable"))

    def test_whole_records_through_hierarchical_exchange(self, tmp_path):
        # sharded_sort_read_batch over a (dcn, shards) mesh: the WHOLE
        # record rides the two-stage exchange; result must be
        # byte-identical to the flat-mesh path
        import numpy as np
        from disq_tpu.sort.sharded import make_mesh, sharded_sort_read_batch
        from tests.bam_oracle import (
            DEFAULT_REFS,
            make_bam_bytes,
            synth_records,
        )
        from disq_tpu.api import ReadsStorage

        recs = synth_records(4000, seed=23, sorted_coord=False)
        p = tmp_path / "in.bam"
        p.write_bytes(make_bam_bytes(DEFAULT_REFS, recs))
        batch = ReadsStorage.make_default().read(str(p)).reads

        flat_b, flat_perm = sharded_sort_read_batch(batch, make_mesh())
        hier_b, hier_perm = sharded_sort_read_batch(
            batch, self._mesh(2, 4))
        np.testing.assert_array_equal(flat_perm, hier_perm)
        for f in ("refid", "pos", "mapq", "bin", "flag", "next_refid",
                  "next_pos", "tlen", "name_offsets", "names",
                  "cigar_offsets", "cigars", "seq_offsets", "seqs",
                  "quals", "tag_offsets", "tags"):
            np.testing.assert_array_equal(
                getattr(flat_b, f), getattr(hier_b, f), err_msg=f)
