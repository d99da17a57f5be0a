"""Device-resident read pipeline.

The decisive assertion is the transfer guard: the parse → keys → sort
→ flagstat step runs under ``jax.transfer_guard("disallow")``, so ANY
intermediate device↔host copy of record columns raises — residency is
proven by execution, not by reading a trace.
"""

import numpy as np
import pytest

import jax

from tests.bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records
from disq_tpu.runtime.device_pipeline import run_device_pipeline


def _shard(n=800, seed=3):
    """Decoded payload + record offsets via the framework's own walk."""
    import gzip
    import struct

    raw = make_bam_bytes(DEFAULT_REFS, synth_records(n, seed=seed))
    payload = gzip.decompress(raw)
    (l_text,) = struct.unpack_from("<i", payload, 4)
    p = 8 + l_text
    (n_ref,) = struct.unpack_from("<i", payload, p)
    p += 4
    for _ in range(n_ref):
        (l_name,) = struct.unpack_from("<i", payload, p)
        p += 4 + l_name + 4
    offs = [p]
    while p < len(payload):
        (bs,) = struct.unpack_from("<i", payload, p)
        p += 4 + bs
        offs.append(p)
    blob = np.frombuffer(payload, np.uint8)
    return blob, np.asarray(offs, np.int64)


class TestDevicePipeline:
    def test_transfer_guard_and_correctness(self):
        blob, offs = _shard()
        n = len(offs) - 1
        keys, order, stats = run_device_pipeline(blob, offs, interpret=True)
        # independent oracle: parse the records host-side
        import struct

        refid = np.empty(n, np.int64)
        pos = np.empty(n, np.int64)
        flag = np.empty(n, np.int64)
        for i in range(n):
            r, p_, _ln, _mq, _bn, _nc, f, _ls = struct.unpack_from(
                "<iiBBHHHi", blob, int(offs[i]) + 4)
            refid[i], pos[i], flag[i] = r, p_, f
        hi = np.where(refid < 0, 0x7FFFFFFF, refid).astype(np.uint64)
        want_keys = np.sort((hi << np.uint64(32))
                            | (pos + 1).astype(np.uint64))
        np.testing.assert_array_equal(keys, want_keys)
        assert stats["total"] == n
        assert stats["mapped"] == int((flag & 0x4).__eq__(0).sum())
        # permutation really is a permutation
        assert sorted(order.tolist()) == list(range(n))

    def test_transfer_guard_catches_host_roundtrip(self):
        # the guard only bites when host and device genuinely differ —
        # on the CPU backend np.asarray of a "device" array is free, so
        # the decisive guard run happens in the TPU CI lane
        # (disq_tpu.ops.tpu_ci run_device_pipeline row)
        if jax.default_backend() == "cpu":
            pytest.skip("guard is vacuous on the CPU backend")
        x = jax.device_put(np.arange(8))
        with pytest.raises(Exception):
            with jax.transfer_guard("disallow"):
                np.asarray(x) + 1

    def test_empty_shard(self):
        blob = np.zeros(0, np.uint8)
        keys, order, stats = run_device_pipeline(
            blob, np.zeros(1, np.int64), interpret=True)
        assert len(keys) == 0 and stats["total"] == 0


class TestDeviceColumns:
    def test_device_backed_dataset_columns(self, tmp_path):
        from disq_tpu.api import ReadsStorage

        raw = make_bam_bytes(DEFAULT_REFS, synth_records(300, seed=6))
        p = tmp_path / "a.bam"
        p.write_bytes(raw)
        ds = ReadsStorage.make_default().read(str(p))
        cols = ds.device_columns()
        assert set(cols) >= {"refid", "pos", "flag", "mapq"}
        for v in cols.values():
            assert isinstance(v, jax.Array)
        np.testing.assert_array_equal(np.asarray(cols["pos"]), ds.reads.pos)

    def test_device_columns_sharded(self, tmp_path):
        from disq_tpu.api import ReadsStorage
        from disq_tpu.sort.sharded import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P

        raw = make_bam_bytes(DEFAULT_REFS, synth_records(256, seed=7))
        p = tmp_path / "b.bam"
        p.write_bytes(raw)
        ds = ReadsStorage.make_default().read(str(p))
        mesh = make_mesh(8)
        cols = ds.device_columns(NamedSharding(mesh, P("shards")))
        assert len(cols["flag"].sharding.device_set) == 8
