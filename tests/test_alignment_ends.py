"""Alignment ends without the host parse (ISSUE 31).

A ``ColumnarBatch`` that holds record bytes answers
``alignment_ends()`` / ``reference_lengths()`` from a CIGAR-only pass
over them (``ops/markdup.reference_spans_from_blob``: one sequential C
pass, numpy when the native library is missing), part by part over a
concat's un-joined blobs, and keeps the result in source order on a
holder its ``permuted()`` views share.  The contracts:

- the values (dtype included) are ``ReadBatch``'s on the oracle's
  corpus, whatever the batch has been through, with no host record
  parse (``columnar.batch.materializations``) and no blob join;
- ``read -> count -> flagstat -> depth`` on a resident dataset, on one
  device and on a four-device mesh, gives the host path's depth from
  that pass alone and says so in its spans and its counter;
- a CIGAR section that does not fit its record raises ``ValueError``.
"""

import pickle

import numpy as np
import pytest

from bam_oracle import (
    DEFAULT_REFS, ORecord, encode_record, make_bam_bytes, ref_span,
    synth_paired_records, synth_records)
from disq_tpu.runtime.tracing import (
    REGISTRY, reset_telemetry, spans, stop_span_log)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    stop_span_log()
    reset_telemetry()
    yield
    stop_span_log()
    reset_telemetry()


def _edge_records():
    """Every CIGAR shape the pass has to get right, by hand."""
    every_op = [(5, "H"), (3, "S"), (10, "M"), (2, "I"), (4, "D"),
                (100, "N"), (5, "="), (6, "X"), (1, "P"), (7, "M"),
                (2, "S"), (9, "H")]
    long_cigar = [(1 + k % 3, "MID"[k % 3]) for k in range(900)]
    return [
        ORecord(name="every_op", refid=0, pos=100, flag=0,
                cigar=every_op, seq="A" * 35),
        ORecord(name="cigarless_mapped", refid=1, pos=77, flag=0,
                seq="ACG"),
        ORecord(name="unmapped", refid=-1, pos=-1, flag=4, seq="ACGTA"),
        ORecord(name="placed_unmapped", refid=2, pos=500, flag=4 | 1,
                seq="ACGTACG"),
        ORecord(name="odd_length", refid=0, pos=9, flag=16,
                cigar=[(7, "M")], seq="ACGTACG"),
        ORecord(name="no_seq", refid=0, pos=3, flag=0, cigar=[(4, "M")]),
        ORecord(name="long_cigar", refid=1, pos=10_000, flag=0,
                cigar=long_cigar,
                seq="A" * sum(n for n, op in long_cigar if op in "MI")),
        ORecord(name="only_clips_and_insert", refid=0, pos=42, flag=0,
                cigar=[(3, "S"), (5, "I"), (2, "S")], seq="A" * 10),
        ORecord(name="lead_clip", refid=0, pos=1, flag=0,
                cigar=[(4, "S"), (6, "M")], seq="A" * 10),
        ORecord(name="trail_clip", refid=0, pos=2, flag=16,
                cigar=[(6, "M"), (4, "H")], seq="A" * 6),
        ORecord(name="n" * 200, refid=2, pos=1 << 28, flag=0,
                cigar=[(1 << 27, "N"), (1, "M")], seq="A"),
        ORecord(name="deletion_only", refid=1, pos=0, flag=0,
                cigar=[(50, "D")]),
    ]


CORPORA = {
    "edge": _edge_records() * 3,
    "synth": synth_records(150, seed=31, unmapped_tail=5),
    "paired": synth_paired_records(90, seed=7),
}
VIEWS = ("one_part", "concat3", "concat3_cached", "permuted", "filtered",
         "filtered_uncached", "or_flags", "released", "pickled",
         "numpy_fallback")


@pytest.fixture()
def no_native(monkeypatch):
    """The native library masked, as on a host with no toolchain: every
    entry point raises ``ImportError`` and the numpy routes run."""
    import disq_tpu.native as native

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_error", "masked by the test")


def _blob(records):
    from disq_tpu.bam.codec import scan_record_offsets

    blob = np.frombuffer(
        b"".join(encode_record(r) for r in records), np.uint8)
    return blob, scan_record_offsets(blob)


def _resident(records):
    from disq_tpu.runtime.columnar import ColumnarBatch

    batch = ColumnarBatch.from_blob(*_blob(records))
    assert batch.device_backed
    return batch


def _host(records):
    from disq_tpu.bam.codec import decode_records

    return decode_records(*_blob(records))


def _counter(name):
    return REGISTRY.counter(name).total()


def _assert_spans_equal(batch, want):
    """Ends and reference lengths, dtype included, equal ``want``'s (a
    host ``ReadBatch``); no record was host-parsed for them."""
    parses = _counter("columnar.batch.materializations")
    ends, reflen = batch.alignment_ends(), batch.reference_lengths()
    assert ends.dtype == want.alignment_ends().dtype == np.int32
    assert reflen.dtype == want.reference_lengths().dtype == np.int64
    np.testing.assert_array_equal(ends, want.alignment_ends())
    np.testing.assert_array_equal(reflen, want.reference_lengths())
    assert _counter("columnar.batch.materializations") == parses
    assert batch._ragged_rb is None
    if batch._order is None:
        # what is handed out is what is kept (a permuted view hands
        # out a copy): an in-place edit must raise
        assert not ends.flags.writeable and not reflen.flags.writeable


@pytest.mark.parametrize("view", VIEWS)
@pytest.mark.parametrize("corpus", sorted(CORPORA))
def test_ends_equal_the_host_parsers(corpus, view, request):
    records = CORPORA[corpus]
    n = len(records)
    host = _host(records)
    # the oracle's own arithmetic, so the host parser is held too
    np.testing.assert_array_equal(
        host.alignment_ends(),
        [r.pos + max(ref_span(r), 1) for r in records])
    rng = np.random.default_rng(len(view) + n)

    if view == "one_part":
        batch = _resident(records)
        batch.alignment_ends()
        assert batch.ends_source == "cigar"
        _assert_spans_equal(batch, host)
        assert batch.ends_source == "cached"
        assert _counter("columnar.batch.ends_from_cigar") == n
        _assert_spans_equal(batch, host)      # the second ask is free
        assert _counter("columnar.batch.ends_from_cigar") == n
    elif view in ("concat3", "concat3_cached"):
        from disq_tpu.runtime.columnar import ColumnarBatch

        cuts = [0, n // 3, n // 3 + 1, n]
        parts = [_resident(records[a:b]) for a, b in zip(cuts, cuts[1:])]
        if view == "concat3_cached":
            for p in parts:
                p.alignment_ends()
        batch = ColumnarBatch.concat(parts)
        asked = _counter("columnar.batch.ends_from_cigar")
        batch.reference_lengths()
        assert batch.ends_source == (
            "cached" if view == "concat3_cached" else "cigar")
        _assert_spans_equal(batch, host)
        # the parts stay as they were: nothing joined them
        assert batch._blob is None and len(batch._blob_parts) == 3
        assert _counter("columnar.batch.ends_from_cigar") == (
            asked if view == "concat3_cached" else asked + n)
    elif view == "permuted":
        order = rng.permutation(n)
        source = _resident(records)
        batch = source.permuted(order)
        _assert_spans_equal(batch, host.take(order))
        # one pass serves the view, a view of the view, and the source
        assert source._span_cache.spans is not None
        again = rng.permutation(n)
        _assert_spans_equal(batch.permuted(again),
                            host.take(order).take(again))
        _assert_spans_equal(source, host)
        assert _counter("columnar.batch.ends_from_cigar") == n
    elif view in ("filtered", "filtered_uncached"):
        mask = rng.random(n) < 0.6
        source = _resident(records).permuted(rng.permutation(n))
        want = host.take(source._order)
        if view == "filtered":
            source.alignment_ends()
        batch = source.filter(mask)
        batch.alignment_ends()
        assert batch.ends_source == (
            "cached" if view == "filtered" else "cigar")
        _assert_spans_equal(batch, want.filter(mask))
    elif view == "or_flags":
        batch = _resident(records)
        batch.alignment_ends()
        mask = rng.random(n) < 0.5
        batch.or_flags(mask, 0x400)
        _assert_spans_equal(batch, host)
        assert batch.ends_source == "cached"      # a flag moves no end
        fresh = _resident(records)
        fresh.or_flags(mask, 0x400)
        _assert_spans_equal(fresh, host)
        np.testing.assert_array_equal(
            fresh.flag, host.flag | np.where(mask, 0x400, 0))
    elif view == "released":
        batch = _resident(records)
        batch.alignment_ends()
        batch.release()
        _assert_spans_equal(batch, host)
        assert batch.ends_source == "cached"      # host data: kept
        late = _resident(records)
        late.release()                  # the bytes are held: still no parse
        _assert_spans_equal(late, host)
    elif view == "pickled":
        order = rng.permutation(n)
        source = _resident(records).permuted(order)
        source.alignment_ends()
        batch = pickle.loads(pickle.dumps(source))
        assert batch._span_cache.spans is None    # a spill recomputes
        _assert_spans_equal(batch, host.take(order))
    else:
        request.getfixturevalue("no_native")
        batch = _resident(records)
        _assert_spans_equal(batch, host)
        (ends_span,) = [s for s in spans()
                        if s["name"] == "columnar.batch.ends"]
        assert ends_span["labels"]["source"] == "numpy"


def test_a_parse_someone_paid_for_answers_before_the_bytes():
    """Order of preference: cached, then a materialised host parse
    (free), then the record bytes, then a host batch's own columns."""
    from disq_tpu.runtime.columnar import ColumnarBatch

    records = CORPORA["synth"]
    host = _host(records)
    batch = _resident(records).permuted(np.arange(len(records))[::-1])
    batch.to_read_batch()
    np.testing.assert_array_equal(
        batch.alignment_ends(), host.alignment_ends()[::-1])
    assert batch.ends_source == "ragged"
    assert _counter("columnar.batch.ends_from_cigar") == 0
    wrapped = ColumnarBatch.from_host(host)
    np.testing.assert_array_equal(
        wrapped.reference_lengths(), host.reference_lengths())
    assert wrapped.ends_source == "host"


@pytest.mark.parametrize("route", ["native", "numpy"])
@pytest.mark.parametrize("fault", ["n_cigar", "l_read_name", "cut_short"])
def test_a_cigar_section_past_its_record_raises(route, fault, request):
    from disq_tpu.ops.markdup import reference_spans_from_blob

    if route == "numpy":
        request.getfixturevalue("no_native")
    records = CORPORA["edge"]
    blob, offsets = _blob(records)
    import disq_tpu.native as native

    reference_spans_from_blob(blob, offsets)
    assert native.loaded() == (route == "native")
    bad = blob.copy()
    at = int(offsets[4])                # "odd_length": 7M, a short record
    if fault == "n_cigar":
        bad[at + 16: at + 18] = (0xFF, 0xFF)
    elif fault == "l_read_name":
        bad[at + 12] = 0xFF
    else:
        bad, offsets = bad[: int(offsets[-1]) - 30], offsets.copy()
    with pytest.raises(ValueError, match="record"):
        reference_spans_from_blob(bad, offsets)
    if fault != "cut_short":
        # and through a batch that was built before its bytes went bad
        batch = _resident(records)
        batch._blob = bad
        with pytest.raises(ValueError, match="record"):
            batch.alignment_ends()


def _resident_file(tmp_path, n=260):
    recs = synth_records(n, seed=31, unmapped_tail=9) + _edge_records()
    path = tmp_path / "in.bam"
    path.write_bytes(make_bam_bytes(DEFAULT_REFS, recs, blocksize=900))
    return str(path), recs


@pytest.mark.parametrize("mesh", [None, 4], ids=["one_device", "mesh4"])
def test_the_read_chain_never_parses(tmp_path, mesh):
    """read -> count -> flagstat -> depth on a resident dataset of
    several shards: the host path's answers, no host parse, no join,
    every record's end from the CIGAR pass, and the spans that say so."""
    from disq_tpu.api import ReadsStorage
    from disq_tpu.runtime.columnar import ColumnarBatch

    path, recs = _resident_file(tmp_path)
    host = ReadsStorage.make_default().read(path)
    want = (host.count(), host.flagstat(), host.depth(64))
    st = (ReadsStorage.make_default().resident_decode()
          .split_size(6000).executor_workers(2))
    if mesh is not None:
        st = st.mesh(mesh)
    ds = st.read(path)
    batch = ds.reads
    assert isinstance(batch, ColumnarBatch) and batch.device_backed
    assert (batch.mesh is not None) == (mesh is not None)
    n_parts = len(batch._blob_parts)
    assert n_parts > 1
    reset_telemetry()
    got = (ds.count(), ds.flagstat(), ds.depth(64))
    assert got[:2] == want[:2]
    assert sorted(got[2]) == sorted(want[2])
    for r in want[2]:
        np.testing.assert_array_equal(got[2][r], want[2][r])
    assert batch._ragged_rb is None and batch._rb is None
    assert batch._blob is None and len(batch._blob_parts) == n_parts
    assert _counter("columnar.batch.materializations") == 0
    assert _counter("columnar.batch.ends_from_cigar") == len(recs)
    by_name = {}
    for s in spans():
        by_name.setdefault(s["name"], []).append(s["labels"])
    assert by_name["ops.depth.prepare"] == [
        {"records": len(recs), "ends": "cigar"}]
    (ends,) = by_name["columnar.batch.ends"]
    assert ends["records"] == len(recs) and ends["source"] == "native"
    assert ends["bytes"] == int(batch._offsets[-1])
    kernels = [lb for lb in by_name["device.kernel"]
               if lb.get("kernel") == "depth"]
    assert len(kernels) == 1
    assert kernels[0].get("devices") == mesh
    assert REGISTRY.counter("device.kernel_launches").value(
        kernel="depth") == 1
    # a second depth call finds the ends where the first left them
    ds.depth(64)
    assert [lb["ends"] for lb in (
        s["labels"] for s in spans()
        if s["name"] == "ops.depth.prepare")] == ["cigar", "cached"]
    assert _counter("columnar.batch.ends_from_cigar") == len(recs)
    batch.release()


def test_pileup_agrees_with_its_host_path_through_the_shared_routine(
        tmp_path):
    from disq_tpu.api import ReadsStorage
    from disq_tpu.ops.pileup import region_pileup

    path, recs = _resident_file(tmp_path)
    host = ReadsStorage.make_default().read(path).reads
    ds = (ReadsStorage.make_default().resident_decode()
          .split_size(6000).read(path))
    reset_telemetry()
    for refid, start, end in ((0, 0, 4000), (1, 9_000, 12_000),
                              (2, 400, 900)):
        np.testing.assert_array_equal(
            region_pileup(ds.reads, refid, start, end),
            region_pileup(host, refid, start, end))
    # three regions, one CIGAR pass, no host parse
    assert _counter("columnar.batch.ends_from_cigar") == len(recs)
    assert _counter("columnar.batch.materializations") == 0
    ds.reads.release()
