"""Regression tests for the round-1 advisor findings (ADVICE.md) and the
round-1 review's silent-fallback item (a poisoned mesh sort must
raise)."""

import numpy as np
import pytest

import jax

from disq_tpu.sort.coordinate import coordinate_keys, coordinate_sort_batch
from disq_tpu.sort.sharded import make_mesh, sharded_sort_read_batch

from tests.bam_oracle import synth_records
from tests.test_bam_codec import _blob

from disq_tpu.bam import decode_records


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8
    return make_mesh(8)


def _batch(n=400, seed=7):
    return decode_records(_blob(synth_records(n, seed=seed, unmapped_tail=4)))


def _assert_batches_equal(a, b):
    for col in (
        "refid", "pos", "mapq", "bin", "flag", "next_refid", "next_pos",
        "tlen", "name_offsets", "names", "cigar_offsets", "cigars",
        "seq_offsets", "seqs", "quals", "tag_offsets", "tags",
    ):
        np.testing.assert_array_equal(
            getattr(a, col), getattr(b, col), err_msg=col
        )


class TestShardedSortReadBatch:
    """ADVICE #1: sharded_sort_read_batch previously had no tests."""

    def test_matches_stable_argsort(self, mesh):
        batch = _batch()
        keys = coordinate_keys(batch.refid, batch.pos)
        want = batch.take(np.argsort(keys, kind="stable"))
        got, perm = sharded_sort_read_batch(batch, mesh)
        _assert_batches_equal(got, want)
        np.testing.assert_array_equal(
            perm, np.argsort(keys, kind="stable")
        )

    def test_skew_capacity_retry(self, mesh):
        # 90% of records at one coordinate: the first exchange overflows a
        # shard's capacity at factor 1.0 and the retry loop doubles it.
        batch = _batch(600, seed=11)
        skew = np.random.default_rng(0).random(batch.count) < 0.9
        batch.refid = np.where(skew, 1, batch.refid).astype(np.int32)
        batch.pos = np.where(skew, 777, batch.pos).astype(np.int32)
        keys = coordinate_keys(batch.refid, batch.pos)
        want = batch.take(np.argsort(keys, kind="stable"))
        got, _ = sharded_sort_read_batch(batch, mesh, capacity_factor=1.0)
        _assert_batches_equal(got, want)

    def test_all_identical_keys_fallback(self, mesh):
        # Every key identical: all records route to a single shard, which
        # cannot fit under any per-shard capacity; the host fallback must
        # still produce the stable order.
        batch = _batch(320, seed=13)
        batch.refid = np.full(batch.count, 2, dtype=np.int32)
        batch.pos = np.full(batch.count, 1234, dtype=np.int32)
        keys = coordinate_keys(batch.refid, batch.pos)
        want = batch.take(np.argsort(keys, kind="stable"))
        got, _ = sharded_sort_read_batch(batch, mesh, capacity_factor=1.0)
        _assert_batches_equal(got, want)


class TestRaggedBytesOnMesh:
    """Name/cigar/seq/qual/tag bytes travel through
    the sort exchange itself — the success path never touches the
    host-side segment gather."""

    def test_no_host_segment_gather(self, mesh, monkeypatch):
        import disq_tpu.bam.columnar as columnar

        batch = _batch(500, seed=23)
        keys = coordinate_keys(batch.refid, batch.pos)
        want = batch.take(np.argsort(keys, kind="stable"))  # before patch

        def boom(*a, **k):
            raise AssertionError("host segment gather used on mesh path")

        monkeypatch.setattr(columnar, "segment_gather", boom)
        got, _ = sharded_sort_read_batch(batch, mesh)
        _assert_batches_equal(got, want)

    def test_empty_ragged_sections(self, mesh):
        # strip tags entirely: the tag section is zero-length for every
        # record, so its scatter/rebuild handles tot == 0
        batch = _batch(200, seed=29)
        batch.tags = np.zeros(0, np.uint8)
        batch.tag_offsets = np.zeros(batch.count + 1, np.int64)
        assert batch.tags.size == 0
        keys = coordinate_keys(batch.refid, batch.pos)
        want = batch.take(np.argsort(keys, kind="stable"))
        got, _ = sharded_sort_read_batch(batch, mesh)
        _assert_batches_equal(got, want)
        assert got.tags.size == 0

    def test_oversize_record_falls_back(self, mesh):
        from disq_tpu.sort import sharded as sh

        batch = _batch(100, seed=31)
        keys = coordinate_keys(batch.refid, batch.pos)
        want = batch.take(np.argsort(keys, kind="stable"))
        # shrink the cap so the padded matrix route is refused
        old = sh._MAX_RAGGED_BYTES
        try:
            sh._MAX_RAGGED_BYTES = 8
            got, _ = sharded_sort_read_batch(batch, mesh)
        finally:
            sh._MAX_RAGGED_BYTES = old
        _assert_batches_equal(got, want)


class TestNoSilentFallback:
    """A poisoned mesh sort must raise, not silently degrade
    to the host argsort."""

    def test_poisoned_mesh_sort_raises(self, monkeypatch):
        import disq_tpu.sort.sharded as sharded

        def boom(*a, **k):
            raise RuntimeError("poisoned mesh sort")

        monkeypatch.setattr(sharded, "sharded_coordinate_sort", boom)
        batch = _batch(50)
        with pytest.raises(RuntimeError, match="poisoned"):
            coordinate_sort_batch(batch, use_mesh=True)

    def test_single_device_uses_host_path(self, monkeypatch):
        import disq_tpu.sort.sharded as sharded

        monkeypatch.setattr(
            sharded, "sharded_coordinate_sort",
            lambda *a, **k: (_ for _ in ()).throw(AssertionError("called")),
        )
        monkeypatch.setattr(jax, "devices", lambda *a: [object()])
        batch = _batch(50)
        keys = coordinate_keys(batch.refid, batch.pos)
        got = coordinate_sort_batch(batch, use_mesh=True)
        _assert_batches_equal(got, batch.take(np.argsort(keys, kind="stable")))


class TestBcfGtMissingSentinel:
    """ADVICE #2: int MISSING sentinel inside a GT vector renders '.'."""

    def test_missing_int8(self):
        from disq_tpu.vcf.bcf import _gt_to_text, _T_INT8

        # diploid: allele 1, then the int8 MISSING sentinel (-128).
        assert _gt_to_text([4, -128], _T_INT8) == "1/."

    def test_missing_leading(self):
        from disq_tpu.vcf.bcf import _gt_to_text, _T_INT16

        assert _gt_to_text([-32768, 5], _T_INT16) == ".|1"


class TestBcfMixedIdxHeaders:
    """ADVICE #5: implicit ids assigned sequentially in declaration
    order, skipping explicit IDX indices (htslib behavior)."""

    def test_sequential_skipping_used(self):
        from disq_tpu.vcf.bcf import BcfDictionaries
        from disq_tpu.vcf.header import VcfHeader

        text = "\n".join(
            [
                "##fileformat=VCFv4.2",
                '##FILTER=<ID=PASS,Description="ok">',
                '##INFO=<ID=AA,Number=1,Type=Integer,Description="x",IDX=5>',
                '##INFO=<ID=BB,Number=1,Type=Integer,Description="x">',
                '##INFO=<ID=CC,Number=1,Type=Integer,Description="x",IDX=1>',
                '##INFO=<ID=DD,Number=1,Type=Integer,Description="x">',
                "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO",
            ]
        ) + "\n"
        d = BcfDictionaries(VcfHeader(text))
        assert d.string_index["PASS"] == 0
        assert d.string_index["AA"] == 5
        assert d.string_index["CC"] == 1
        # Explicit IDX lines register in pass 1 (0 PASS, 1 CC, 5 AA);
        # implicit lines then take sequential free indices in declaration
        # order: BB -> 2, DD -> 3. No index is ever assigned twice.
        assert d.string_index["BB"] == 2
        assert d.string_index["DD"] == 3
        assert len(set(d.string_index.values())) == len(d.string_index)


class TestRansTruncatedStreams:
    """ADVICE #3/#4: truncated or corrupt rANS streams must error, not
    silently decode garbage."""

    def test_native_truncated_body_errors(self):
        from disq_tpu.native import rans_encode0_native, rans_decode_native

        raw = bytes(np.random.default_rng(3).integers(0, 40, 4096, dtype=np.uint8))
        stream = bytearray(rans_encode0_native(raw))
        assert rans_decode_native(bytes(stream)) == raw
        # Chop renorm bytes off the tail but fix up comp_size so the
        # header still matches the (shorter) body.
        cut = 16
        short = bytearray(stream[:-cut])
        comp = int.from_bytes(stream[1:5], "little") - cut
        short[1:5] = comp.to_bytes(4, "little")
        with pytest.raises(ValueError):
            rans_decode_native(bytes(short))

    def test_device_rejects_huge_state(self):
        from disq_tpu.native import rans_encode0_native
        from disq_tpu.ops.rans_simd import rans0_decode_simd
        from disq_tpu.cram.rans import _read_freq_table0

        raw = bytes(np.random.default_rng(4).integers(0, 8, 1024, dtype=np.uint8))
        stream = bytearray(rans_encode0_native(raw))
        body_off = 9
        _, toff = _read_freq_table0(bytes(stream[body_off:]), 0)
        # Overwrite state word 0 with a value >= 2^31.
        stream[body_off + toff: body_off + toff + 4] = (0x80000001).to_bytes(
            4, "little"
        )
        with pytest.raises(ValueError, match="2\\^31"):
            rans0_decode_simd([bytes(stream)], interpret=True)


class TestEncodeContainerSlackRejected:
    """ADVICE r5 #2: the bulk QS/RN encoders in ``encode_container``
    copy the batch's flat qual/name arrays verbatim — a batch whose
    offsets don't tile those arrays exactly (slack at either end) used
    to emit silently wrong bytes; it must error instead."""

    def _sliced_views_ok(self):
        # sanity: ReadBatch.slice rebases offsets, so normal sink
        # slicing passes the validation
        from disq_tpu.cram.codec import encode_container

        b = _batch(50).slice(10, 40)
        container, _ = encode_container(b, int(b.refid[0]), 0)
        assert container

    def test_slack_in_flat_arrays_rejected(self):
        import dataclasses

        import numpy as np

        from disq_tpu.cram.codec import encode_container

        self._sliced_views_ok()
        b = _batch(30)
        # append slack bytes to the flat arrays without touching offsets
        bad = dataclasses.replace(
            b,
            seqs=np.concatenate([b.seqs, np.zeros(7, np.uint8)]),
            quals=np.concatenate([b.quals, np.zeros(7, np.uint8)]),
        )
        with pytest.raises(ValueError, match="seq_offsets"):
            encode_container(bad, int(bad.refid[0]), 0)
        bad = dataclasses.replace(
            b, names=np.concatenate([b.names, np.zeros(3, np.uint8)]))
        with pytest.raises(ValueError, match="name_offsets"):
            encode_container(bad, int(bad.refid[0]), 0)
        # quals shorter than seqs (per-record lengths must agree)
        bad = dataclasses.replace(b, quals=b.quals[:-1])
        with pytest.raises(ValueError, match="seq_offsets"):
            encode_container(bad, int(bad.refid[0]), 0)
