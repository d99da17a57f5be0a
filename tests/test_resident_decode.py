"""HBM-resident fused decode (``runtime/columnar.py`` + the fused
routes through ``bgzf/codec.py`` / ``bam/source.py``).

The identity contract: every field of a device-parsed ``ColumnarBatch``
is byte-equal (dtype included) to the host parser's output on the seed
fixtures — under the plain host inflate route, through the full read
path at executor widths 1 and 4, with the device decode service on,
and after a coordinate sort from the resident keys. The laziness
contract: a column crosses d2h once at most (no double-booking of
``device.transfer`` bytes), and columns never fetched are booked into
``device.d2h_avoided_bytes`` at release.
"""

import gzip
import struct
from dataclasses import fields as dc_fields

import numpy as np
import pytest

from bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records
from disq_tpu.runtime.tracing import (
    REGISTRY, reset_telemetry, spans, stop_span_log)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    stop_span_log()
    reset_telemetry()
    yield
    stop_span_log()
    reset_telemetry()


ALL_FIELDS = (
    "refid", "pos", "mapq", "bin", "flag", "next_refid", "next_pos",
    "tlen", "name_offsets", "names", "cigar_offsets", "cigars",
    "seq_offsets", "seqs", "quals", "tag_offsets", "tags",
)


def _decoded_shard(n=300, seed=3):
    """Decoded BAM payload + record offsets via an independent walk."""
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
    offs = np.asarray(offs, np.int64)
    return blob[offs[0]:], offs - offs[0]


def _bam_file(tmp_path, n=72, blocksize=320, seed=21, tail=0):
    recs = synth_records(n, seed=seed, unmapped_tail=tail)
    src = tmp_path / "in.bam"
    src.write_bytes(make_bam_bytes(DEFAULT_REFS, recs,
                                   blocksize=blocksize))
    return str(src)


def _assert_identical(got, want):
    for f in ALL_FIELDS:
        a, b = getattr(got, f), getattr(want, f)
        assert a.dtype == b.dtype, (f, a.dtype, b.dtype)
        np.testing.assert_array_equal(a, b, err_msg=f)


class TestColumnarIdentity:
    def test_from_blob_every_field_matches_host_parser(self):
        from disq_tpu.bam.codec import decode_records
        from disq_tpu.runtime.columnar import ColumnarBatch

        rec, offs = _decoded_shard()
        cb = ColumnarBatch.from_blob(rec, offs, n_ref=len(DEFAULT_REFS))
        host = decode_records(rec, offs, n_ref=len(DEFAULT_REFS))
        assert cb.device_backed and cb.count == host.count
        _assert_identical(cb, host)
        # materialized form too (to_read_batch composes device fixed +
        # host ragged)
        _assert_identical(cb.to_read_batch(), host)
        cb.release()

    def test_bad_refids_raise_like_decode_records(self):
        from disq_tpu.runtime.columnar import ColumnarBatch

        rec, offs = _decoded_shard(n=40, seed=5)
        with pytest.raises(ValueError, match="refID out of range"):
            ColumnarBatch.from_blob(rec, offs, n_ref=1)

    def test_malformed_sections_raise_like_host_parser(self):
        from disq_tpu.bam.codec import decode_records
        from disq_tpu.runtime.columnar import ColumnarBatch

        rec, offs = _decoded_shard(n=30, seed=23)
        bad = rec.copy()
        # blow up record 0's l_seq (i32 at +20: 4B block_size + 16B of
        # refid/pos/l_rn·mapq·bin/n_cigar·flag) so its sections
        # overflow the record — chain-valid, host parser rejects it
        bad[offs[0] + 20: offs[0] + 24] = np.frombuffer(
            struct.pack("<i", 1 << 20), np.uint8)
        with pytest.raises(ValueError) as host_err:
            decode_records(bad, offs, n_ref=len(DEFAULT_REFS))
        with pytest.raises(ValueError) as dev_err:
            ColumnarBatch.from_blob(bad, offs, n_ref=len(DEFAULT_REFS))
        # identical error semantics: the resident build defers to the
        # host parser as the authority, so message + coordinates match
        assert str(dev_err.value) == str(host_err.value)
        # negative l_seq takes the same route
        bad[offs[0] + 20: offs[0] + 24] = np.frombuffer(
            struct.pack("<i", -7), np.uint8)
        with pytest.raises(ValueError):
            ColumnarBatch.from_blob(bad, offs, n_ref=len(DEFAULT_REFS))

    def test_fixed_columns_survive_release_via_host_blob(self):
        from disq_tpu.bam.codec import decode_records
        from disq_tpu.runtime.columnar import ColumnarBatch

        rec, offs = _decoded_shard(n=50, seed=29)
        cb = ColumnarBatch.from_blob(rec, offs, n_ref=len(DEFAULT_REFS))
        host = decode_records(rec, offs, n_ref=len(DEFAULT_REFS))
        cb.flagstat()
        cb.release()
        # the retained host blob rebuilds any column after release —
        # consistent with ragged access, instead of raising
        np.testing.assert_array_equal(cb.refid, host.refid)
        _assert_identical(cb, host)

    def test_empty_blob_is_host_backed_empty(self):
        from disq_tpu.runtime.columnar import ColumnarBatch

        cb = ColumnarBatch.from_blob(
            np.zeros(0, np.uint8), np.zeros(1, np.int64))
        assert not cb.device_backed and cb.count == 0


class TestLazyFetch:
    def test_column_fetch_books_once(self):
        from disq_tpu.runtime.columnar import ColumnarBatch

        rec, offs = _decoded_shard(n=100, seed=7)
        cb = ColumnarBatch.from_blob(rec, offs, n_ref=len(DEFAULT_REFS))
        d2h = REGISTRY.counter("device.bytes_to_host")
        base = d2h.total()
        _ = cb.pos
        first = d2h.total() - base
        assert first == 4 * cb.count
        _ = cb.pos  # cached: NO second transfer — no double-booking
        assert d2h.total() - base == first
        assert sum(1 for s in spans()
                   if s["name"] == "columnar.batch.fetch") == 1
        cb.release()

    def test_release_books_unfetched_columns_as_avoided(self):
        from disq_tpu.runtime.columnar import (
            FIXED_COLUMNS, ColumnarBatch)

        rec, offs = _decoded_shard(n=100, seed=7)
        cb = ColumnarBatch.from_blob(rec, offs, n_ref=len(DEFAULT_REFS))
        n = cb.count
        _ = cb.pos  # one fetched column
        avoided = REGISTRY.counter("device.d2h_avoided_bytes")
        base = avoided.total()
        cb.release()
        # every REACHABLE fixed column except the fetched one stayed
        # resident (the 4 parse-only fields are not d2h candidates and
        # must not inflate the metric)
        want = 4 * n * (len(FIXED_COLUMNS) - 1)
        assert avoided.total() - base == want
        rel = [s for s in spans()
               if s["name"] == "columnar.batch.release"]
        assert rel and rel[0]["labels"]["avoided_bytes"] == want
        # hbm released
        assert REGISTRY.gauge("device.hbm_bytes").state()["last"] == 0

    def test_flagstat_consumes_on_device(self):
        from disq_tpu.bam.codec import decode_records
        from disq_tpu.ops.flagstat import flagstat_counts
        from disq_tpu.runtime.columnar import (
            FIXED_COLUMNS, ColumnarBatch)

        rec, offs = _decoded_shard(n=120, seed=9)
        cb = ColumnarBatch.from_blob(rec, offs, n_ref=len(DEFAULT_REFS))
        host = decode_records(rec, offs, n_ref=len(DEFAULT_REFS))
        h2d = REGISTRY.counter("device.bytes_to_device")
        base = h2d.total()
        got = cb.flagstat()
        # zero h2d re-upload: the flag column was already resident
        assert h2d.total() == base
        # oracle from the host parse — cb.flag itself stays unfetched,
        # so the consumed flag column books as avoided at release
        assert got == flagstat_counts(np.asarray(host.flag))
        avoided = REGISTRY.counter("device.d2h_avoided_bytes")
        a0 = avoided.total()
        cb.release()
        assert avoided.total() - a0 == 4 * cb.count * len(FIXED_COLUMNS)

    def test_materialize_uses_host_parse_not_d2h(self):
        from disq_tpu.bam.codec import decode_records
        from disq_tpu.runtime.columnar import ColumnarBatch

        rec, offs = _decoded_shard(n=90, seed=13)
        cb = ColumnarBatch.from_blob(rec, offs, n_ref=len(DEFAULT_REFS))
        host = decode_records(rec, offs, n_ref=len(DEFAULT_REFS))
        d2h = REGISTRY.counter("device.bytes_to_host")
        base = d2h.total()
        _assert_identical(cb.to_read_batch(), host)
        # materialization runs the full host parse for the ragged
        # columns anyway — the fixed columns come from it (byte-equal
        # by contract), not from a pointless per-column d2h fetch
        assert d2h.total() == base
        avoided = REGISTRY.counter("device.d2h_avoided_bytes")
        a0 = avoided.total()
        cb.release()
        # ...and the host-sourced columns are neither transferred nor
        # "avoided": the host did the work, no d2h was saved
        assert avoided.total() == a0

    def test_concurrent_fetch_and_materialize_book_once(self):
        import threading

        from disq_tpu.bam.codec import decode_records
        from disq_tpu.runtime.columnar import ColumnarBatch

        rec, offs = _decoded_shard(n=150, seed=19)
        cb = ColumnarBatch.from_blob(rec, offs, n_ref=len(DEFAULT_REFS))
        host = decode_records(rec, offs, n_ref=len(DEFAULT_REFS))
        d2h = REGISTRY.counter("device.bytes_to_host")
        base = d2h.total()
        # writer-pipeline shape: several threads hit the same shared
        # batch at once (column fetch + full materialization)
        barrier = threading.Barrier(8)
        outs, errs = [None] * 8, []

        def hit(i):
            try:
                barrier.wait()
                if i % 2:
                    outs[i] = cb.pos
                else:
                    outs[i] = cb.to_read_batch()
            except Exception as e:  # noqa: BLE001 — assert below
                errs.append(e)

        ts = [threading.Thread(target=hit, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        # the pos fetch crossed d2h AT MOST once (the materializing
        # threads may win the race first, in which case pos comes from
        # the host parse and nothing moves); never W times
        assert d2h.total() - base in (0, 4 * cb.count)
        for i in range(8):
            if i % 2:
                np.testing.assert_array_equal(outs[i], host.pos)
            else:
                _assert_identical(outs[i], host)
        cb.release()

    def test_pickle_spill_rebuilds_device_backed(self):
        import pickle

        from disq_tpu.bam.codec import decode_records
        from disq_tpu.runtime.columnar import ColumnarBatch

        rec, offs = _decoded_shard(n=40, seed=17)
        cb = ColumnarBatch.from_blob(rec, offs, n_ref=len(DEFAULT_REFS))
        host = decode_records(rec, offs, n_ref=len(DEFAULT_REFS))
        d2h = REGISTRY.counter("device.bytes_to_host")
        base = d2h.total()
        # the ReadLedger spill path: pickling must carry HOST data only
        # (no implicit d2h of the resident columns)
        blob = pickle.dumps(cb)
        assert d2h.total() == base
        cb2 = pickle.loads(blob)
        assert cb2.device_backed and cb2.count == cb.count
        _assert_identical(cb2, host)
        avoided = REGISTRY.counter("device.d2h_avoided_bytes")
        a0 = avoided.total()
        cb.release()
        booked = avoided.total() - a0
        assert booked > 0  # the original books its own avoidance once
        cb2.release()
        # the restored copy fetched every column — nothing re-booked
        assert avoided.total() - a0 == booked
        # host-backed batches round-trip as plain host wrappers
        cb3 = pickle.loads(pickle.dumps(ColumnarBatch.from_host(host)))
        assert not cb3.device_backed
        _assert_identical(cb3, host)

    def test_read_ledger_fingerprint_includes_resident_knob(
            self, tmp_path):
        from disq_tpu.runtime.errors import DisqOptions
        from disq_tpu.runtime.executor import read_ledger_for_storage

        base = str(tmp_path / "ledger")

        class _S:
            _options = DisqOptions(read_ledger=base)

        class _SR:
            _options = DisqOptions(read_ledger=base,
                                   resident_decode=True)

        a = read_ledger_for_storage(_S(), "p.bam", 4)
        assert a.manifest._state["params"]["resident_decode"] is False
        a.manifest.mark_done(a.STAGE, 0, {})
        # toggling the knob between runs resets the ledger: the resumed
        # run must not serve host-form spills to a resident read
        b = read_ledger_for_storage(_SR(), "p.bam", 4)
        assert b.manifest._state["params"]["resident_decode"] is True
        assert not b.manifest.is_done(b.STAGE, 0)

    def test_device_pipeline_result_is_lazy_and_books_once(self):
        from disq_tpu.runtime.device_pipeline import run_device_pipeline

        rec, offs = _decoded_shard(n=80, seed=11)
        res = run_device_pipeline(rec, offs, interpret=True)
        d2h = REGISTRY.counter("device.bytes_to_host")
        base = d2h.total()
        stats = res.stats
        assert stats["total"] == len(offs) - 1
        once = d2h.total() - base
        assert once == 48  # the 12-field i32 count row only
        _ = res.stats
        assert d2h.total() - base == once  # cached — no double-booking
        avoided = REGISTRY.counter("device.d2h_avoided_bytes")
        a0 = avoided.total()
        res.release()
        # keys (2 x u32 x n) + order (i32 x n) never fetched
        assert avoided.total() - a0 == 12 * (len(offs) - 1)


class TestResidentReadPath:
    @pytest.mark.parametrize("workers", [1, 4])
    def test_read_identity_and_device_concat(self, tmp_path, workers):
        from disq_tpu.api import ReadsStorage
        from disq_tpu.runtime.columnar import ColumnarBatch

        path = _bam_file(tmp_path)
        host = ReadsStorage.make_default().read(path)
        ds = (ReadsStorage.make_default()
              .split_size(16000 if workers == 1 else 3000)
              .executor_workers(workers).resident_decode().read(path))
        assert isinstance(ds.reads, ColumnarBatch)
        assert ds.reads.device_backed  # multi-shard concat stays resident
        assert ds.count() == host.count()
        _assert_identical(ds.reads, host.reads)
        assert ds.flagstat() == host.flagstat()
        ds.reads.release()

    def test_multi_shard_concat_joins_blob_lazily(self, tmp_path):
        from disq_tpu.api import ReadsStorage
        from disq_tpu.runtime.columnar import ColumnarBatch

        path = _bam_file(tmp_path)
        ds = (ReadsStorage.make_default().split_size(3000)
              .resident_decode().read(path))
        cb = ds.reads
        assert isinstance(cb, ColumnarBatch) and cb.device_backed
        # the shard blobs are held as parts: a device-only consumer
        # never pays the O(bytes) join
        assert cb._blob is None and cb._blob_parts
        cb.flagstat()
        assert cb._blob is None
        _ = cb.names  # first ragged access joins, once
        assert cb._blob is not None and cb._blob_parts is None
        cb.release()

    def test_env_knob_enables_resident(self, tmp_path, monkeypatch):
        from disq_tpu.api import ReadsStorage
        from disq_tpu.runtime.columnar import ColumnarBatch

        path = _bam_file(tmp_path, n=60)
        monkeypatch.setenv("DISQ_TPU_RESIDENT_DECODE", "1")
        ds = ReadsStorage.make_default().read(path)
        assert isinstance(ds.reads, ColumnarBatch)
        assert ds.reads.device_backed
        ds.reads.release()

    def test_disabled_path_builds_nothing(self, tmp_path):
        from disq_tpu.api import ReadsStorage
        from disq_tpu.bam.columnar import ReadBatch
        from disq_tpu.runtime import columnar

        path = _bam_file(tmp_path, n=60)
        built = columnar.device_batches_built()
        ds = ReadsStorage.make_default().read(path)
        assert type(ds.reads) is ReadBatch
        assert columnar.device_batches_built() == built

    def test_coordinate_sort_from_resident_keys_identical(
            self, tmp_path):
        from disq_tpu.api import ReadsStorage

        path = _bam_file(tmp_path, n=200, seed=13, tail=5)
        host = ReadsStorage.make_default().read(path).coordinate_sorted()
        res = (ReadsStorage.make_default().resident_decode()
               .read(path).coordinate_sorted())
        _assert_identical(res.reads, host.reads)
        # the u64 key vectors stayed on device
        assert REGISTRY.counter("device.d2h_avoided_bytes").total() > 0

    def test_interval_read_decodes_only_selected_blocks(self, tmp_path):
        """BAI traversal with resident decode: only the BAI-selected
        chunks' blocks inflate+parse (position-invariant random
        access), output identical to the host path."""
        from disq_tpu.api import (
            BaiWriteOption, Interval, ReadsStorage, TraversalParameters)

        path = _bam_file(tmp_path, n=300, seed=17)
        storage = ReadsStorage.make_default()
        sorted_path = str(tmp_path / "sorted.bam")
        storage.write(storage.read(path).coordinate_sorted(),
                      sorted_path, BaiWriteOption.ENABLE)
        tp = TraversalParameters(intervals=(
            Interval(DEFAULT_REFS[0][0], 1, 20_000),))
        host = storage.read(sorted_path, traversal=tp)
        res = (ReadsStorage.make_default().resident_decode()
               .read(sorted_path, traversal=tp))
        assert 0 < res.count() < 300  # a genuine subset was selected
        assert res.count() == host.count()
        _assert_identical(res.reads, host.reads)
        # the chunk decode went through the fused parse: build spans
        # exist, and each parsed a bounded chunk — fewer records than
        # the whole file holds
        built = [s for s in spans()
                 if s["name"] == "columnar.batch.build"]
        assert built
        assert all(s["labels"]["records"] < 300 for s in built)

    def test_depth_consumes_resident_batch(self, tmp_path):
        from disq_tpu.api import ReadsStorage

        path = _bam_file(tmp_path, n=120, seed=19)
        host = ReadsStorage.make_default().read(path)
        res = (ReadsStorage.make_default().resident_decode().read(path))
        dh = host.depth(window=4096)
        dr = res.depth(window=4096)
        assert dh.keys() == dr.keys()
        for k in dh:
            np.testing.assert_array_equal(dh[k], dr[k])


class TestResidentWithDeviceService:
    """Interpret-mode SIMD inflate through the decode service is the
    expensive part of these runs, so the service-route identity and
    fault-isolation legs are ``slow``-marked (the tier-1 budget keeps
    the fast resident read-path identity above; slow CI and the chaos
    smoke wrapper run these, per the PR1 soak convention). The
    keep_device assembly leg stays tier-1: it is the single-launch
    direct route."""

    @pytest.mark.slow
    @pytest.mark.parametrize("workers", [1, 4])
    def test_service_identity(self, tmp_path, monkeypatch, workers):
        """Fused decode with the SIMD inflate kernel + cross-shard
        decode service on: every field byte-equal to the host path."""
        from disq_tpu.api import ReadsStorage
        from disq_tpu.runtime import device_service
        from disq_tpu.runtime.columnar import ColumnarBatch

        path = _bam_file(tmp_path)
        host = ReadsStorage.make_default().read(path)
        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        monkeypatch.setenv("DISQ_TPU_DEVICE_SERVICE", "1")
        monkeypatch.setenv("DISQ_TPU_SERVICE_FLUSH_MS", "40")
        try:
            ds = (ReadsStorage.make_default()
                  .split_size(16000 if workers == 1 else 3000)
                  .executor_workers(workers).resident_decode()
                  .read(path))
        finally:
            device_service.shutdown_service()
        assert isinstance(ds.reads, ColumnarBatch)
        assert ds.reads.device_backed
        _assert_identical(ds.reads, host.reads)
        ds.reads.release()

    def test_keep_device_assembly_identity(self, tmp_path, monkeypatch):
        """Direct SIMD route (no service): the kernel's still-resident
        output chunks are assembled + parsed in place — no blob
        re-upload — and every field still matches the host parser."""
        from disq_tpu.api import ReadsStorage
        from disq_tpu.runtime.columnar import ColumnarBatch

        path = _bam_file(tmp_path, n=48, blocksize=256)
        host = ReadsStorage.make_default().read(path)
        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        ds = (ReadsStorage.make_default().split_size(16000)
              .resident_decode().read(path))
        assert isinstance(ds.reads, ColumnarBatch)
        _assert_identical(ds.reads, host.reads)
        ds.reads.release()

    @pytest.mark.slow
    def test_faultfs_bitflip_quarantines_owner_shard_only(
            self, tmp_path, monkeypatch):
        """Corrupt-lane isolation is unchanged by the resident path:
        a bit-flipped payload under QUARANTINE at executor_workers=4
        through the service books exactly the owner shard's block; the
        salvaged shard decodes host-side, the rest stay resident."""
        from disq_tpu.api import ReadsStorage
        from disq_tpu.bgzf.guesser import find_block_table
        from disq_tpu.fsw import (
            FaultInjectingFileSystemWrapper,
            FaultSpec,
            PosixFileSystemWrapper,
            register_filesystem,
        )
        from disq_tpu.runtime import device_service
        from disq_tpu.runtime.errors import DisqOptions, ErrorPolicy

        path = _bam_file(tmp_path)
        fs = PosixFileSystemWrapper()
        blocks = [b for b in find_block_table(fs, path) if b.usize > 0]
        victim = blocks[len(blocks) // 2]
        register_filesystem("fault", FaultInjectingFileSystemWrapper(
            PosixFileSystemWrapper(),
            [FaultSpec(kind="bitflip", path_substr="in.bam",
                       offset=victim.pos + 24, bit=5)],
        ))
        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        monkeypatch.setenv("DISQ_TPU_DEVICE_SERVICE", "1")
        monkeypatch.setenv("DISQ_TPU_SERVICE_FLUSH_MS", "40")
        opts = DisqOptions(
            error_policy=ErrorPolicy.QUARANTINE,
            retry_backoff_s=0.0,
            quarantine_dir=str(tmp_path / "q"),
            resident_decode=True,
        )
        try:
            ds = (ReadsStorage.make_default().split_size(3000)
                  .options(opts).executor_workers(4)
                  .read("fault://" + path))
        finally:
            device_service.shutdown_service()
        assert ds.counters.quarantined_blocks == 1
        assert 0 < ds.count() < 72


class TestDeviceColumnsResident:
    def test_device_columns_zero_upload(self, tmp_path):
        import jax

        from disq_tpu.api import ReadsStorage

        path = _bam_file(tmp_path, n=80)
        ds = ReadsStorage.make_default().resident_decode().read(path)
        h2d = REGISTRY.counter("device.bytes_to_device")
        base = h2d.total()
        cols = ds.device_columns()
        assert h2d.total() == base  # already resident: no upload
        host = ReadsStorage.make_default().read(path)
        for name in ("refid", "pos", "flag", "mapq"):
            assert isinstance(cols[name], jax.Array)
            np.testing.assert_array_equal(
                np.asarray(cols[name]), getattr(host.reads, name))
        ds.reads.release()
