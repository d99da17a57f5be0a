"""The resident write copies bytes it already holds (ISSUE 45): a batch
that holds its records' bytes is written from them and its pending
order, shard by shard, with no host parse and no re-encode, and the
BAM, BAI and SBI are byte for byte the column encoder's of the same
records."""

import numpy as np
import pytest

from bam_oracle import DEFAULT_REFS, synth_records
from disq_tpu.runtime.tracing import REGISTRY
from test_alignment_ends import _blob, _resident, no_native  # noqa: F401


def _sorted(batch):
    from disq_tpu.sort.coordinate import coordinate_sort_batch

    out = coordinate_sort_batch(batch, keep_resident=True)
    assert out.encode_source()[2] is not None  # a pending order
    return out


def _unsorted(n=70, seed=3):
    """Unsorted records, unmapped ones in their middle: the sort has to
    bring those last."""
    recs = synth_records(n, seed=seed)
    recs[n // 2: n // 2] = synth_records(0, unmapped_tail=4)
    return recs


def _dirty_pads(blob, offsets):
    """Set the pad nibble after every odd-length sequence in ``blob``
    (in place); returns how many."""
    from disq_tpu.bam.codec import decode_records

    rb = decode_records(blob, offsets)
    l_seq = np.diff(rb.seq_offsets)
    odd = np.flatnonzero(l_seq % 2 == 1)
    last = (offsets[odd] + 36 + np.diff(rb.name_offsets)[odd] + 1
            + 4 * np.diff(rb.cigar_offsets)[odd] + l_seq[odd] // 2)
    blob[last] |= 0x0F
    return len(odd)


# -- the batch shapes ---------------------------------------------------------


def source_order():
    return _resident(synth_records(70, seed=1, sorted_coord=True,
                                   unmapped_tail=3))


def permuted():
    return _sorted(_resident(synth_records(70, seed=2)))


def filtered_then_permuted():
    batch = _resident(synth_records(90, seed=4))
    kept = batch.filter(np.asarray(batch.mapq) >= 20)
    assert 0 < kept.count < 90 and kept.device_backed
    return _sorted(kept)


def flags_patched_after_the_sort():
    batch = _sorted(_resident(synth_records(70, seed=5)))
    batch.or_flags(np.arange(70) % 3 == 0, 0x400)
    return batch


def dirty_pad_nibbles_in_source_order():
    blob, offsets = _blob(synth_records(70, seed=6, sorted_coord=True))
    blob = blob.copy()
    assert _dirty_pads(blob, offsets) > 10
    from disq_tpu.runtime.columnar import ColumnarBatch

    return ColumnarBatch.from_blob(blob, offsets)


def dirty_pad_nibbles_permuted():
    blob, offsets = _blob(synth_records(70, seed=7))
    blob = blob.copy()
    assert _dirty_pads(blob, offsets) > 10
    from disq_tpu.runtime.columnar import ColumnarBatch

    return _sorted(ColumnarBatch.from_blob(blob, offsets))


def unmapped_reads_last():
    batch = _sorted(_resident(_unsorted()))
    assert (np.asarray(batch.refid)[-4:] == -1).all()
    return batch


def concat_with_unjoined_parts():
    from disq_tpu.runtime.columnar import ColumnarBatch

    batch = ColumnarBatch.concat([_resident(synth_records(40, seed=8)),
                                  _resident(_unsorted(30, seed=9))])
    assert batch._blob is None and len(batch._blob_parts) == 2
    return _sorted(batch)


SHAPES = [source_order, permuted, filtered_then_permuted,
          flags_patched_after_the_sort, dirty_pad_nibbles_in_source_order,
          dirty_pad_nibbles_permuted, unmapped_reads_last,
          concat_with_unjoined_parts]


# -- the write ----------------------------------------------------------------


def _header():
    from disq_tpu.bam.header import SamHeader

    return SamHeader.build(DEFAULT_REFS).with_sort_order("coordinate")


def _write(reads, path, num_shards):
    from disq_tpu import ReadsStorage
    from disq_tpu.api import BaiWriteOption, ReadsDataset, SbiWriteOption

    (ReadsStorage.make_default().writer_workers(2).num_shards(num_shards)
     .write(ReadsDataset(header=_header(), reads=reads), str(path),
            BaiWriteOption.ENABLE, SbiWriteOption.ENABLE))
    return tuple(open(str(path) + ext, "rb").read()
                 for ext in ("", ".bai", ".sbi"))


def _written(how):
    return REGISTRY.counter("bam.write.encoded_records").value(how=how)


def _both_ways(batch, tmp_path, num_shards):
    """Write ``batch`` as it is, then its ``to_read_batch()``, with the
    same storage; holds the first to the bytes alone and the files to
    each other."""
    n = batch.count
    parses = REGISTRY.counter("columnar.batch.materializations")
    before = parses.total(), _written("bytes"), _written("columns")
    held = batch.encode_source()[0].tobytes()
    got = _write(batch, tmp_path / "bytes.bam", num_shards)
    assert parses.total() == before[0]
    assert batch._ragged_rb is None
    assert _written("bytes") == before[1] + n
    assert _written("columns") == before[2]
    # the nibble is zeroed in the copy: the batch's blob is as it was
    assert batch.encode_source()[0].tobytes() == held
    want = _write(batch.to_read_batch(), tmp_path / "columns.bam",
                  num_shards)
    assert _written("bytes") == before[1] + n
    assert _written("columns") == before[2] + n
    for ext, a, b in zip(("bam", "bai", "sbi"), got, want):
        assert a == b, f"the .{ext} differs"
    assert len(got[1]) > 8 and len(got[2]) > 8


@pytest.mark.parametrize("num_shards", [1, 5])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda f: f.__name__)
def test_files_are_the_column_encoders_byte_for_byte(
        tmp_path, shape, num_shards):
    _both_ways(shape(), tmp_path, num_shards)


@pytest.mark.parametrize("shape", [source_order, unmapped_reads_last],
                         ids=lambda f: f.__name__)
def test_a_shard_of_zero_records(tmp_path, monkeypatch, shape):
    from disq_tpu.bam import sink

    monkeypatch.setattr(
        sink, "shard_bounds", lambda _storage, count: (
            4, np.array([0, 0, count // 2, count, count], np.int64)))
    _both_ways(shape(), tmp_path, 4)


@pytest.mark.parametrize("shape", [source_order, dirty_pad_nibbles_permuted],
                         ids=lambda f: f.__name__)
def test_without_the_native_library(tmp_path, no_native, shape):
    _both_ways(shape(), tmp_path, 3)


@pytest.mark.parametrize("shape", SHAPES, ids=lambda f: f.__name__)
def test_a_cut_is_the_encoding_of_the_slice(shape):
    """``encoded_slice`` and what the index builders read off its
    ``_LazySlice`` against the column encoder's bytes and the slice's
    own columns, on a cut that is neither end."""
    from disq_tpu.bam.codec import encode_records_with_offsets
    from disq_tpu.bam.sink import _LazySlice

    batch = shape()
    lo, hi = batch.count // 3, batch.count - 5
    parses = REGISTRY.counter("columnar.batch.materializations").total()
    blob, offs = batch.encoded_slice(lo, hi)
    part = _LazySlice(batch, lo, hi, (blob, offs))
    got = {c: getattr(part, c) for c in ("refid", "pos", "flag")}
    ends = part.alignment_ends()
    assert part._part is None and part.count == hi - lo
    assert REGISTRY.counter(
        "columnar.batch.materializations").total() == parses
    want = batch.to_read_batch().slice(lo, hi)
    want_blob, want_offs = encode_records_with_offsets(want)
    assert blob.dtype == np.uint8 and blob.tobytes() == want_blob
    assert offs.dtype == np.int64
    np.testing.assert_array_equal(offs, want_offs)
    for c, col in got.items():
        assert col.dtype == getattr(want, c).dtype, c
        np.testing.assert_array_equal(col, getattr(want, c), c)
    assert ends.dtype == np.int32
    np.testing.assert_array_equal(ends, want.alignment_ends())
    # anything else is answered by a real slice: it only pays
    np.testing.assert_array_equal(part.names, want.names)
    assert part._part is not None


def test_a_batch_without_record_bytes_is_written_from_its_columns(tmp_path):
    """A ``ReadBatch`` and a host-built ``ColumnarBatch``: the column
    branch as it stood."""
    from disq_tpu.bam.codec import decode_records
    from disq_tpu.runtime.columnar import ColumnarBatch

    rb = decode_records(*_blob(synth_records(
        50, seed=10, sorted_coord=True)))
    hosted = ColumnarBatch.from_host(rb)
    assert hosted.encode_source() is None
    assert hosted.encoded_slice(0, 50) is None
    before = _written("bytes"), _written("columns")
    a = _write(rb, tmp_path / "a.bam", 3)
    b = _write(hosted, tmp_path / "b.bam", 3)
    assert a == b
    assert (_written("bytes"), _written("columns")) == (
        before[0], before[1] + 100)


@pytest.mark.parametrize("source", ["cigar", "cached", "ragged"])
def test_ends_of_a_stretch_are_the_stretch_of_the_ends(source):
    batch = _sorted(_resident(_unsorted()))
    if source == "cached":
        batch.alignment_ends()
    elif source == "ragged":
        batch.to_read_batch()
    got = batch.alignment_ends(10, 40)
    assert batch.ends_source == ("cached" if source == "cached" else "cigar")
    want = batch.to_read_batch().alignment_ends()
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want[10:40])
    np.testing.assert_array_equal(batch.alignment_ends(), want)
    np.testing.assert_array_equal(batch.alignment_ends(0, 74), want)


def test_the_deflate_takes_an_array_of_no_bytes():
    from disq_tpu.bgzf.codec import deflate_blob

    comp, sizes = deflate_blob(np.zeros(0, np.uint8))
    assert comp == b"" and len(sizes) == 0
