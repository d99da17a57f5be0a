"""The operators of the chain (filter -> coordinate sort -> markdup)
against the plain reference ``tests/reference_markdup.py`` (ISSUE 32).

One parametrised test: a resident batch and a host batch, the native
sweep and its numpy fallback, and the three shapes a batch reaches
markdup in (its blob already in order; ``permuted()`` with a pending
order; compacted by the filter and then permuted), on seeded
``wgs30x``-shaped records and on a corpus of edge cases.  Held to the
reference: the kept set, ``examined``, ``duplicates``, the marked set,
the flag bytes of the patched record blob and the device flag column.
A last case holds the fallback's memory to its chunk bound.
"""

import json
import os
import sys

import numpy as np
import pytest

import reference_markdup as ref
from bam_oracle import ORecord, decode_all, encode_record

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

VIEW = "-q 20"


def _wgs30x_records():
    """The benchmark generator's records through the benchmark's own
    encoder and the oracle's sequential decoder."""
    from benchmark import gen, reference

    with open(os.path.join(REPO, "benchmark", "configs", "wgs30x.json")) as f:
        cfg = json.load(f)
    return decode_all(
        reference.encode_records(gen.generate(700, 2147484001, cfg)))


def _edge_records():
    q = lambda *v: bytes(v)  # noqa: E731

    def one(name, pos, flag, cigar, qual, refid=0, mapq=40):
        return ORecord(name=name, refid=refid, pos=pos, mapq=mapq,
                       flag=flag, cigar=cigar, seq="A" * len(qual),
                       qual=qual)

    return [
        # forward reads clipped at a contig's start: the unclipped 5'
        # position is negative, and the three share it
        one("neg_hs", 3, 0, [(2, "H"), (5, "S"), (20, "M")], q(30) * 25),
        one("neg_s", 2, 0, [(6, "S"), (20, "M")], q(31) * 26),
        one("neg_h", 0, 0, [(4, "H"), (20, "M")], q(29) * 20),
        # reverse reads: the key is the end with its trailing clips
        one("rev_plain", 100, 16, [(30, "M")], q(20) * 30),
        one("rev_s", 100, 16, [(26, "M"), (4, "S")], q(40) * 30),
        one("rev_sh", 105, 16, [(3, "S"), (20, "M"), (2, "S"), (3, "H")],
            q(15) * 25),
        one("rev_at_start", 0, 16, [(5, "M")], q(22) * 5),
        one("rev_at_start_2", 0, 16, [(2, "M"), (3, "S")], q(23) * 5),
        # same place, the other strand: not a duplicate of those
        one("fwd_same_place", 100, 0, [(30, "M")], q(20) * 30),
        # equal scores: the earlier record stays, whatever follows
        one("tie_a", 500, 0, [(10, "M")], q(30) * 10),
        one("tie_b", 500, 0, [(10, "M")], q(30) * 10),
        one("tie_c", 498, 0, [(2, "S"), (8, "M")], q(20) * 15),
        # below 15 and the 0xFF of "no qualities" count nothing
        one("low_q", 500, 0, [(10, "M")], q(14) * 10 + q(255) * 10),
        # no CIGAR (spans one base), no sequence (scores 0)
        one("no_cigar", 700, 0, [], q(30) * 4),
        one("no_cigar_rev", 700, 16, [], q(30) * 4),
        one("no_cigar_rev_2", 700, 16, [(1, "M")], q(31)),
        one("no_seq", 700, 0, [(4, "M")], b""),
        # duplicate bits the input carries stay, on winner and loser
        one("was_dup_wins", 900, 0x400, [(10, "M")], q(41) * 10),
        one("was_dup_loses", 900, 0x400, [(10, "M")], q(16) * 10),
        one("beaten_by_was_dup", 900, 0, [(10, "M")], q(17) * 10),
        # 0x904 and placeless records share keys with examined ones:
        # never examined, never marked, and they beat nobody
        one("secondary", 900, 0x100, [(10, "M")], q(41) * 12),
        one("supplementary", 900, 0x800, [(10, "M")], q(41) * 12),
        one("unmapped_placed", 900, 0x4, [], q(41) * 12),
        one("no_reference", 900, 0, [(10, "M")], q(41) * 12, refid=-1),
        # the filter's: below the MAPQ floor, though it would win
        one("low_mapq", 900, 0, [(10, "M")], q(41) * 30, mapq=19),
        one("other_contig", 900, 0, [(10, "M")], q(18) * 10, refid=1),
        # every op in one CIGAR, clips at both ends
        one("every_op", 2000, 16,
            [(5, "H"), (3, "S"), (10, "M"), (2, "I"), (4, "D"), (100, "N"),
             (5, "="), (6, "X"), (1, "P"), (7, "M"), (2, "S"), (9, "H")],
            q(25) * 35),
    ]


CORPORA = {"wgs30x": _wgs30x_records, "edge": _edge_records}


@pytest.fixture(params=["native", "numpy"])
def sweep(request, monkeypatch):
    """The native library loaded, or masked as on a host with no
    toolchain (every entry point then raises ``ImportError``)."""
    import disq_tpu.native as native

    if request.param == "numpy":
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_load_error", "masked by the test")
    return request.param


def _batch(records, resident):
    from disq_tpu.bam.codec import decode_records, scan_record_offsets
    from disq_tpu.runtime.columnar import ColumnarBatch

    blob = np.frombuffer(
        b"".join(encode_record(r) for r in records), np.uint8)
    offsets = scan_record_offsets(blob)
    if resident:
        batch = ColumnarBatch.from_blob(blob, offsets)
        assert batch.device_backed
        return batch
    return decode_records(blob, offsets)


def _blob_flags(batch):
    """The flag bytes of the record blob, in the batch's order."""
    blob, offsets, order = batch.encode_source()
    off = offsets[:-1] if order is None else offsets[:-1][order]
    return blob[off + 18].astype(np.uint16) | (
        blob[off + 19].astype(np.uint16) << 8)


@pytest.mark.parametrize("view", ["source_order", "permuted",
                                  "compacted_then_permuted"])
@pytest.mark.parametrize("resident", [True, False],
                         ids=["resident", "host"])
@pytest.mark.parametrize("corpus", sorted(CORPORA))
def test_the_operators_equal_the_plain_reference(corpus, resident, view,
                                                 sweep):
    from disq_tpu.runtime.oppipe import OpPipeline
    from disq_tpu.runtime.tracing import reset_telemetry, spans

    records = CORPORA[corpus]()
    want = ref.chain(records, VIEW)
    kept = [records[i] for i in want["kept"]]
    assert 0 < want["duplicates"] < want["examined"] < len(kept) \
        < len(records)
    if view == "source_order":      # the blob already holds the answer's
        batch, ops = _batch(kept, resident), ["markdup"]
    elif view == "permuted":        # the kept records, in input order
        passed = sorted(want["kept"])
        batch, ops = _batch([records[i] for i in passed], resident), [
            "sort", "markdup"]
    else:
        batch, ops = _batch(records, resident), [
            ("filter", VIEW), "sort", "markdup"]
    reset_telemetry()
    result = OpPipeline(*ops).run([batch])
    out, = result.batches
    assert result.stats["markdup"] == {
        "examined": want["examined"], "duplicates": want["duplicates"],
        "boundary_flips": 0}
    assert out.count == len(kept)
    if resident:                    # nothing has parsed a record
        assert out.device_backed and out._ragged_rb is None
    flags = np.array(want["flags"], np.uint16)
    marked = np.array(want["marked"])
    got = np.asarray(out.flag)
    assert got.dtype == np.uint16
    np.testing.assert_array_equal(got, flags)
    before = np.array([r.flag for r in kept], np.uint16)
    np.testing.assert_array_equal(got != before,
                                  marked & ((before & 0x400) == 0))
    if not resident:
        assert [out.name(i) for i in range(out.count)] == [
            r.name for r in kept]
        return
    np.testing.assert_array_equal(
        np.asarray(out.device_columns()["flag"]), flags)
    np.testing.assert_array_equal(_blob_flags(out), flags)
    assert (out._order is None) == (view == "source_order")
    keys, = [s for s in spans() if s["name"] == "ops.markdup.keys"]
    assert keys["labels"]["source"] == sweep
    assert keys["labels"]["spans"] == "swept"
    assert keys["labels"]["records"] == len(kept)
    # the sweep left the reference spans where depth and the BAI look
    assert out.alignment_ends() is not None and out.ends_source == "cached"
    # the kept set, record for record, in the reference's order
    assert out._ragged_rb is None
    assert [out.name(i) for i in range(out.count)] == [r.name for r in kept]


def test_the_sweeps_memory_is_bounded_by_its_chunk(monkeypatch):
    """The numpy fallback never indexes the qualities of more records
    than its chunk bound holds, however many the blob has; the marks
    are the unchunked arithmetic's."""
    import disq_tpu.native as native
    from disq_tpu.ops import markdup

    records = CORPORA["wgs30x"]()
    blob = np.frombuffer(
        b"".join(encode_record(r) for r in records), np.uint8)
    from disq_tpu.bam.codec import scan_record_offsets

    offsets = scan_record_offsets(blob)
    fast = markdup.key_sweep_from_blob(blob, offsets)
    assert native.loaded()
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_error", "masked by the test")
    bound = 10 * 150
    monkeypatch.setattr(markdup, "SWEEP_CHUNK_BASES", bound)
    sound = markdup._flat_segments
    indexed = []

    def watched(base, lens, stride=1):
        if stride == 1:             # the quality bytes (4: the op words)
            indexed.append(int(np.sum(lens)))
        return sound(base, lens, stride)

    monkeypatch.setattr(markdup, "_flat_segments", watched)
    slow = markdup.key_sweep_from_blob(blob, offsets)
    bases = sum(len(r.seq) for r in records)
    assert bases > 50 * bound and sum(indexed) == bases
    assert max(indexed) <= bound and len(indexed) >= bases // bound
    for a, b in zip(fast, slow):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    # a record longer than the bound is a chunk of its own
    monkeypatch.setattr(markdup, "SWEEP_CHUNK_BASES", 7)
    del indexed[:]
    tiny = markdup.key_sweep_from_blob(blob, offsets)
    assert max(indexed) == max(len(r.seq) for r in records)
    np.testing.assert_array_equal(tiny[4], fast[4])
