"""BAI interval-traversal tests (baseline config 3 path, SURVEY.md §3.2)."""

import numpy as np
import pytest

from disq_tpu import BaiWriteOption, ReadsStorage, SbiWriteOption, TraversalParameters
from disq_tpu.api import Interval

from tests.bam_oracle import DEFAULT_REFS, make_bam_bytes, parse_bam, ref_span, synth_records


@pytest.fixture(scope="module")
def indexed_bam(tmp_path_factory):
    """A coordinate-sorted, BAI-indexed BAM written by the framework."""
    records = synth_records(1500, seed=11, unmapped_tail=12)
    raw = str(tmp_path_factory.mktemp("trav") / "raw.bam")
    with open(raw, "wb") as f:
        f.write(make_bam_bytes(DEFAULT_REFS, records, blocksize=700))
    storage = ReadsStorage.make_default().num_shards(4)
    ds = storage.read(raw)
    out = str(tmp_path_factory.mktemp("trav") / "sorted.bam")
    storage.write(ds, out, BaiWriteOption.ENABLE, SbiWriteOption.ENABLE, sort=True)
    with open(out, "rb") as f:
        _, _, sorted_recs = parse_bam(f.read())
    return out, sorted_recs


def _expect_overlapping(records, contig_id, beg0, end0):
    out = []
    for r in records:
        if r.refid != contig_id:
            continue
        span = max(ref_span(r), 1)
        if r.pos < end0 and r.pos + span > beg0:
            out.append(r.name)
    return out


class TestTraversal:
    @pytest.mark.parametrize(
        "contig,start,end",
        [("chr1", 1, 5000), ("chr1", 40_000, 60_000), ("chr2", 1, 50_000),
         ("chrM", 1, 16_569)],
    )
    def test_interval_query_matches_brute_force(self, indexed_bam, contig, start, end):
        path, sorted_recs = indexed_bam
        contig_id = [n for n, _ in DEFAULT_REFS].index(contig)
        ds = ReadsStorage.make_default().read(
            path, TraversalParameters(intervals=[Interval(contig, start, end)])
        )
        expect = _expect_overlapping(sorted_recs, contig_id, start - 1, end)
        got = [ds.reads.name(i) for i in range(ds.reads.count)]
        assert sorted(got) == sorted(expect)

    def test_empty_interval(self, indexed_bam):
        path, _ = indexed_bam
        ds = ReadsStorage.make_default().read(
            path, TraversalParameters(intervals=[Interval("chr2", 49_990, 49_999)])
        )
        # May be empty or tiny; must not crash and must only contain chr2
        assert np.all(ds.reads.refid == 1) or ds.reads.count == 0

    def test_unplaced_unmapped_only(self, indexed_bam):
        path, sorted_recs = indexed_bam
        ds = ReadsStorage.make_default().read(
            path, TraversalParameters(intervals=[], traverse_unplaced_unmapped=True)
        )
        expect = [r.name for r in sorted_recs if r.refid == -1]
        got = [ds.reads.name(i) for i in range(ds.reads.count)]
        assert sorted(got) == sorted(expect)
        assert len(got) == 12

    def test_intervals_plus_unmapped(self, indexed_bam):
        path, sorted_recs = indexed_bam
        ds = ReadsStorage.make_default().read(
            path,
            TraversalParameters(
                intervals=[Interval("chr1", 1, 100_000)],
                traverse_unplaced_unmapped=True,
            ),
        )
        expect = [r.name for r in sorted_recs if r.refid == 0] + [
            r.name for r in sorted_recs if r.refid == -1
        ]
        assert ds.reads.count == len(expect)

    def test_missing_bai_raises(self, tmp_path):
        records = synth_records(10, with_edge_cases=False)
        p = str(tmp_path / "noidx.bam")
        with open(p, "wb") as f:
            f.write(make_bam_bytes(DEFAULT_REFS, records))
        with pytest.raises(FileNotFoundError, match="bai"):
            ReadsStorage.make_default().read(
                p, TraversalParameters(intervals=[Interval("chr1", 1, 10)])
            )


class TestRegressionsFromReview:
    def test_long_read_name_rejected(self):
        from disq_tpu.bam.codec import encode_records
        from disq_tpu.bam.columnar import ReadBatch
        import numpy as np

        from tests.bam_oracle import ORecord, encode_record
        from disq_tpu.bam.codec import decode_records

        rec = ORecord(name="x" * 100, refid=0, pos=1, seq="ACGT", qual=b"\x10" * 4)
        batch = decode_records(encode_record(rec))
        # Forge an oversized name by stretching offsets
        batch.names = np.zeros(300, dtype=np.uint8) + ord("a")
        batch.name_offsets = np.array([0, 300], dtype=np.int64)
        with pytest.raises(ValueError, match="254"):
            encode_records(batch)

    def test_bgzf_reader_tell_at_eof(self):
        import io

        from disq_tpu.bgzf import BgzfReader, compress_to_bgzf

        payload = b"z" * 100_000
        comp = compress_to_bgzf(payload)
        r = BgzfReader(io.BytesIO(comp))
        assert r.read(-1) == payload
        r.read(1)  # push into EOF state
        # tell must point at end-of-data (the terminator block), not at
        # the stale last data block start.
        assert (r.tell_virtual() >> 16) >= len(comp) - 28

    def test_all_formats_dispatch(self):
        # Every format in the matrix resolves to a real source; missing
        # files fail with FileNotFoundError, not dispatch errors.
        for ext in (".bam", ".sam", ".cram"):
            with pytest.raises(FileNotFoundError):
                ReadsStorage.make_default().read("definitely-missing" + ext)


# -- the interval read against the plain reference --------------------------
#
# Seeded records of the benchmark's generator in a file this module
# writes itself: small BGZF blocks (a record or two each), so that a
# chunk is a run of blocks, runs have gaps between them and a chunk
# begins inside a block; the BAI comes from the program's own builder,
# which the read then has to answer from.

import json
import os
import time

from benchmark import gen, reference, reference_intervals
from tests.bam_oracle import _o_bgzf_block, make_header_bytes

SPARSE_RECORDS = 3000
BLOCK_PAYLOAD = 1900
EMPTY_CONTIG = ("chrEmpty", 5_000_000)


def sparse_config():
    """wgs30x_exome's record shape at 1x, so that 3,000 records reach
    past the first 128 kb bin of each contig."""
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "wgs30x_exome.json")) as f:
        cfg = json.load(f)
    cfg["coverage"] = 1
    cfg["targets"] = dict(cfg["targets"], bp_per_gene=150_000)
    return cfg


def write_indexed(path, truth, refs, payload):
    """``truth`` (coordinate order) as a BAM of ``payload``-byte BGZF
    blocks with the BAI the program's builder gives for it."""
    from disq_tpu.index.bai import build_bai

    head = make_header_bytes(refs, "coordinate")
    data = head + reference.encode_records(truth)
    size = reference.record_sizes(truth) + 4
    end = len(head) + np.cumsum(size)
    blocks = [_o_bgzf_block(data[o: o + payload])
              for o in range(0, len(data), payload)]
    at = np.zeros(len(blocks) + 1, np.int64)
    np.cumsum([len(b) for b in blocks], out=at[1:])
    voffset = lambda o: (at[o // payload] << 16) | (o % payload)  # noqa: E731
    with open(path, "wb") as f:
        f.write(b"".join(blocks) + _o_bgzf_block(b""))
    ends = reference_intervals.alignment_ends(truth).astype(np.int32)
    bai = build_bai(truth.refid, truth.pos, ends, truth.flag,
                    voffset(end - size).astype(np.uint64),
                    voffset(end).astype(np.uint64), len(refs))
    with open(path + ".bai", "wb") as f:
        f.write(bai.to_bytes())


@pytest.fixture(scope="module")
def sparse_bam(tmp_path_factory):
    cfg = sparse_config()
    truth = gen.generate(SPARSE_RECORDS, 20260929, cfg)
    truth = truth.take(reference.coordinate_order(truth))
    refs = [(c["name"], c["length"]) for c in cfg["contigs"]] + [EMPTY_CONTIG]
    path = str(tmp_path_factory.mktemp("sparse") / "sparse.bam")
    write_indexed(path, truth, refs, BLOCK_PAYLOAD)
    return path, truth, cfg, refs


def _uncovered_base(truth, refid):
    """A position inside the reads of a contig that no record covers."""
    rows = np.flatnonzero(truth.refid == refid)
    pos = truth.pos[rows].astype(np.int64)
    reach = np.maximum.accumulate(
        reference_intervals.alignment_ends(truth)[rows])
    gap = np.flatnonzero(pos[1:] > reach[:-1] + 2)
    return int(reach[gap[len(gap) // 2]]) + 1


def _zero_span_record(truth):
    """A placed record whose CIGAR consumes no reference."""
    rows = np.flatnonzero((truth.refid >= 0) & (truth.cigar_len == 0))
    return int(truth.refid[rows[0]]), int(truth.pos[rows[0]])


def _case_targets(name, truth, cfg):
    """(contig index, start0, end0) triples of a case, as given to the
    program (unmerged, in the order given)."""
    if name == "clustered":
        r, s, e = reference_intervals.targets(cfg, SPARSE_RECORDS)
        return list(zip(r.tolist(), s.tolist(), e.tolist()))
    if name == "abut_and_overlap":
        # 100 bp of padding each side makes the first two abut and the
        # last two overlap
        return [(0, 900, 1200), (0, 1200, 1500), (0, 1400, 1700),
                (1, 5000, 5300), (1, 5250, 5400)]
    if name == "bin_edges":
        return [(0, 16300, 16500), (1, 131000, 131200), (2, 32700, 32800)]
    if name == "chunk_inside_block":
        return [(0, 40_000, 40_600), (0, 90_000, 90_600)]
    if name == "zero_span_record":
        r, p = _zero_span_record(truth)
        # it is the first base of one target, the last of another, and
        # one base off a third on either side
        return [(r, p, p + 40), (r, p - 40, p + 1), (r, p - 90, p)]
    if name == "contig_without_reads":
        return [(3, 1000, 2000), (3, 2_000_000, 2_000_500),
                (2, 20_000, 21_000)]
    if name == "empty_result":
        g = _uncovered_base(truth, 1)
        return [(1, g, g + 1)]
    if name in ("unsorted", "with_unplaced"):
        out = [(2, 70_000, 70_300), (0, 120_000, 120_400), (1, 300, 700),
               (0, 2000, 2300), (2, 100, 400), (0, 60_000, 60_200)]
        return out
    raise KeyError(name)


CASES = ("clustered", "abut_and_overlap", "bin_edges", "chunk_inside_block",
         "zero_span_record", "contig_without_reads", "empty_result",
         "unsorted", "with_unplaced")


@pytest.mark.parametrize("resident", [False, True],
                         ids=["host", "resident"])
@pytest.mark.parametrize("case", CASES)
def test_interval_read_equals_the_plain_reference(sparse_bam, case, resident):
    from disq_tpu.fsw.filesystem import resolve_path
    from disq_tpu.traversal.bai_query import plan_traversal

    path, truth, cfg, refs = sparse_bam
    given = _case_targets(case, truth, cfg)
    unplaced = case == "with_unplaced"
    traversal = TraversalParameters(
        intervals=[Interval(refs[r][0], s + 1, e) for r, s, e in given],
        traverse_unplaced_unmapped=unplaced)
    merged = reference_intervals.merge(
        *(np.array(col, np.int64) for col in zip(*given)))
    keep = reference_intervals.kept(truth, merged)
    if unplaced:
        keep = np.concatenate([keep, np.flatnonzero(truth.refid < 0)])
    want = truth.take(keep)

    storage = ReadsStorage.make_default().executor_workers(2)
    if resident:
        storage = storage.resident_decode()
    ds = storage.read(path, traversal)

    assert ds.count() == want.count
    checks = reference.Checks()
    if want.count:
        reference.columns_differing(ds.reads, want, checks, case)
        assert checks.ok, [r for r in checks.rows if not r[3]]
        assert bool(getattr(ds.reads, "device_backed", False)) == resident
    else:
        assert case == "empty_result"
    # the counters are the shards': what was decoded, what was returned
    c = ds.counters
    assert c.blocks > 0 and c.records == want.count
    assert c.bytes_uncompressed > c.bytes_compressed > 0
    assert ds.telemetry_report()["counters"]["blocks"] == c.blocks

    fs, p = resolve_path(path)
    plan = plan_traversal(fs, p, ds.header, traversal, 2)
    if case == "chunk_inside_block":
        # the two targets' bins, and the one-block chunks the 128 kb
        # bin above holds of reads across a 16 kb edge
        assert (plan.chunks[:, 0] & 0xFFFF).all()
        assert len(plan.chunks) >= 2
    if case == "clustered":
        # runs with gaps between: fewer blocks than the file holds
        assert len(plan.chunks) > 3
        assert c.blocks < SPARSE_RECORDS * 350 // BLOCK_PAYLOAD // 2
    if case == "unsorted":
        again = storage.read(path, TraversalParameters(
            intervals=sorted(traversal.intervals,
                             key=lambda iv: (iv.contig, iv.start))))
        assert np.array_equal(again.reads.names, ds.reads.names)
        if resident:
            again.reads.release()
    if resident and want.count:
        ds.reads.release()


def test_the_sources_full_target_list_is_planned_in_seconds(tmp_path):
    """200,000 targets in 20,000 genes against the index of a 3.1 Gbp
    genome: array work, not a Python list a target."""
    from disq_tpu.bam.header import SamHeader
    from disq_tpu.fsw.filesystem import resolve_path
    from disq_tpu.index.bai import BaiIndex, RefIndex
    from disq_tpu.traversal.bai_query import plan_traversal

    n_contig, length = 24, 129_000_000
    refs, vo = [], 0
    for _ in range(n_contig):
        r = RefIndex()
        r.linear = np.zeros(length >> 14, np.uint64)
        for w in range(length >> 14):
            beg = vo
            vo += 18 * 21_000       # 18 blocks a 16 kb bin
            r.bins[4681 + w] = [(beg << 16, (vo << 16) | 1234)]
            r.linear[w] = beg << 16
            if w % 8 == 7:          # a straddler a 128 kb bin
                r.bins[585 + (w >> 3)] = [
                    ((vo - 21_000) << 16, ((vo - 21_000) << 16) | 400)]
        r.n_mapped, r.ref_end = 1, vo << 16
        refs.append(r)
    path = str(tmp_path / "genome.bam")
    with open(path, "wb") as f:
        f.write(_o_bgzf_block(b""))
    with open(path + ".bai", "wb") as f:
        f.write(BaiIndex(refs).to_bytes())
    header = SamHeader.build([(f"c{c}", length) for c in range(n_contig)])
    rng = np.random.default_rng(1)
    intervals = []
    for _ in range(20_000):
        c, s = int(rng.integers(0, n_contig)), int(
            rng.integers(1, length - 40_000))
        intervals += [Interval(f"c{c}", s + k * 3100, s + k * 3100 + 330)
                      for k in range(10)]
    rng.shuffle(intervals)
    fs, p = resolve_path(path)
    t0 = time.perf_counter()
    plan = plan_traversal(
        fs, p, header, TraversalParameters(intervals=intervals), 4)
    assert time.perf_counter() - t0 < 5.0
    assert len(intervals) == 200_000 > len(plan.table)
    # a gene's targets share its two or three bins: far fewer chunks
    # than targets, each a run of whole bins, in file order
    assert 20_000 <= len(plan.chunks) < 60_000
    assert (plan.chunks[1:, 0] >> 16 > plan.chunks[:-1, 1] >> 16).all()
    assert sum(hi - lo for lo, hi in plan.tasks) == len(plan.chunks)
