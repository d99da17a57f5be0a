"""Resident operator suite (ISSUE 20): filter / markdup / pileup /
rgstats on the columnar currency, chained by ``runtime/oppipe.py``.

Golden contracts, each against the pure-NumPy record-at-a-time oracles
in ``bam_oracle.py`` (shared code: none):

- device paths == oracle on synthetic paired fixtures with duplicate
  clusters (including clip-shifted keys), unmapped / secondary /
  supplementary exclusions, and RG tags — at executor widths 1 and 4,
  with the device decode service off and on, and on 2/4/8-device
  meshes;
- duplicate clusters straddling shard seams resolve exactly through
  the driver-side boundary-key merge;
- the chained resident pipeline (filter → sort → markdup → rgstats)
  produces stats AND written bytes identical to the host-materializing
  path, with ``device.d2h_avoided_bytes`` > 0 and ZERO host record
  materializations on the resident leg (registry deltas).
"""

import numpy as np
import pytest

from bam_oracle import (
    DEFAULT_REFS, make_bam_bytes, oracle_markdup, oracle_pileup,
    oracle_rgstats, parse_bam, synth_paired_records, synth_records)
from disq_tpu.runtime.tracing import (
    REGISTRY, reset_telemetry, stop_span_log)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    stop_span_log()
    reset_telemetry()
    yield
    stop_span_log()
    reset_telemetry()


PAIRED = synth_paired_records(120, seed=41)


@pytest.fixture(scope="module")
def paired_bam(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("ops") / "paired.bam")
    with open(path, "wb") as f:
        f.write(make_bam_bytes(DEFAULT_REFS, PAIRED, blocksize=900))
    return path


def _storage(resident=True, workers=1, mesh=None, split=6000):
    from disq_tpu.api import ReadsStorage

    st = (ReadsStorage.make_default().split_size(split)
          .executor_workers(workers))
    if resident:
        st = st.resident_decode()
    if mesh is not None:
        st = st.mesh(mesh)
    return st


def _rec_key(r):
    return (r.name, r.flag & 0xFFF ^ (r.flag & 0x400), r.refid, r.pos)


def _marked_keys(batch):
    """{(name, flag sans 0x400, refid, pos)} of duplicate-flagged
    records — mate-safe identity for comparing against the oracle."""
    flag = np.asarray(batch.flag)
    out = set()
    for i in np.nonzero(flag & 0x400)[0]:
        out.add((batch.name(int(i)), int(flag[i]) & ~0x400,
                 int(batch.refid[i]), int(batch.pos[i])))
    return out


ORACLE_DUPS = {
    (r.name, r.flag & ~0x400, r.refid, r.pos)
    for r, d in zip(PAIRED, oracle_markdup(PAIRED)) if d
}


class TestFilterGrammar:
    def test_parse_and_reject(self):
        from disq_tpu.ops.rfilter import parse_read_filter

        rf = parse_read_filter("-f 0x1 -F 0x904 -q 30 -s 7.25")
        assert rf.require_flags == 0x1 and rf.exclude_flags == 0x904
        assert rf.min_mapq == 30 and rf.seed == 7
        assert abs(rf.subsample - 0.25) < 1e-9
        for bad in ("-z 3", "-q", "-q x", "-s 3", "-s -1.5", "oops"):
            with pytest.raises(ValueError):
                parse_read_filter(bad)

    def test_builders_validate_eagerly(self):
        from disq_tpu.api import ReadsStorage
        from disq_tpu.runtime.errors import DisqOptions

        with pytest.raises(ValueError):
            DisqOptions().with_read_filter("-q nope")
        with pytest.raises(ValueError):
            ReadsStorage.make_default().read_filter("-s 3")
        st = ReadsStorage.make_default().read_filter("-q 10")
        assert st._options.read_filter == "-q 10"

    def test_subsample_mates_travel_together(self, paired_bam):
        ds = (_storage(resident=True).read_filter("-s 5.4")
              .read(paired_bam))
        flag = np.asarray(ds.reads.flag)
        names = [ds.reads.name(i) for i in range(ds.count())]
        # name-hash keying: both mates of a kept pair are kept
        pair_names = [n for n, f in zip(names, flag) if f & 0x1]
        from collections import Counter

        by = Counter(pair_names)
        full = {n for n, c in by.items() if n.startswith("p")}
        orig = Counter(r.name for r in PAIRED if r.flag & 0x1)
        for n in full:
            assert by[n] == orig[n], f"pair {n} was split by -s"
        assert 0 < ds.count() < len(PAIRED)


class TestGoldenMarkdup:
    @pytest.mark.parametrize("workers", [1, 4])
    @pytest.mark.parametrize("resident", [False, True])
    def test_matches_oracle(self, paired_bam, workers, resident):
        ds = _storage(resident=resident, workers=workers,
                      split=3000).read(paired_bam)
        ds2, stats = ds.pipeline("markdup")
        assert _marked_keys(ds2.reads) == ORACLE_DUPS
        assert stats["markdup"]["duplicates"] == len(ORACLE_DUPS)

    @pytest.mark.parametrize("mesh", [2, 4, 8])
    def test_mesh_matches_oracle(self, paired_bam, mesh):
        ds = _storage(resident=True, mesh=mesh).read(paired_bam)
        ds2, stats = ds.pipeline("markdup")
        assert _marked_keys(ds2.reads) == ORACLE_DUPS
        assert stats["markdup"]["duplicates"] == len(ORACLE_DUPS)

    @pytest.mark.slow
    @pytest.mark.parametrize("workers", [1, 4])
    def test_device_service_matches_oracle(self, paired_bam,
                                           monkeypatch, workers):
        from disq_tpu.runtime import device_service

        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        monkeypatch.setenv("DISQ_TPU_DEVICE_SERVICE", "1")
        monkeypatch.setenv("DISQ_TPU_SERVICE_FLUSH_MS", "40")
        try:
            ds = _storage(resident=True, workers=workers,
                          split=3000).read(paired_bam)
        finally:
            device_service.shutdown_service()
        ds2, stats = ds.pipeline("markdup")
        assert _marked_keys(ds2.reads) == ORACLE_DUPS


class TestBoundarySeam:
    def test_straddling_cluster_resolves_exactly(self, paired_bam):
        """Shards cut mid-cluster: per-shard markdup under-marks, the
        driver merge restores the global truth."""
        from disq_tpu.runtime.oppipe import MarkdupOp, OpPipeline

        ds = _storage(resident=True, split=3000).read(paired_bam)
        # cut the (coordinate-sorted) batch into 4 coordinate slices —
        # seams land inside clusters by construction of the fixture
        rb = ds.reads.to_read_batch()
        n = rb.count
        cuts = [0, n // 4, n // 2, 3 * n // 4, n]
        shards = []
        for lo, hi in zip(cuts, cuts[1:]):
            m = np.zeros(n, bool)
            m[lo:hi] = True
            shards.append(rb.filter(m))
        res = OpPipeline(MarkdupOp()).run(shards)
        got = set()
        for b in res.batches:
            got |= _marked_keys(b)
        assert got == ORACLE_DUPS
        assert res.stats["markdup"]["duplicates"] == len(ORACLE_DUPS)
        assert res.stats["markdup"]["boundary_flips"] >= 0


class TestGoldenPileup:
    @pytest.mark.parametrize("resident", [False, True])
    def test_matches_oracle(self, paired_bam, resident):
        from disq_tpu.ops.pileup import region_pileup

        ds = _storage(resident=resident).read(paired_bam)
        want = oracle_pileup(PAIRED, 0, 0, 20_000)
        got = region_pileup(ds.reads, 0, 0, 20_000)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("mesh", [2, 4, 8])
    def test_mesh_matches_oracle(self, paired_bam, mesh):
        from disq_tpu.ops.pileup import region_pileup

        ds = _storage(resident=True, mesh=mesh).read(paired_bam)
        want = oracle_pileup(PAIRED, 0, 0, 20_000)
        np.testing.assert_array_equal(
            region_pileup(ds.reads, 0, 0, 20_000), want)

    def test_region_bound(self, paired_bam):
        from disq_tpu.ops.pileup import MAX_REGION_BP, region_pileup

        ds = _storage(resident=False).read(paired_bam)
        with pytest.raises(ValueError, match="bound"):
            region_pileup(ds.reads, 0, 0, MAX_REGION_BP + 1)


class TestGoldenRgStats:
    @pytest.mark.parametrize("resident", [False, True])
    def test_matches_oracle(self, paired_bam, resident):
        from disq_tpu.ops.rgstats import read_group_stats

        ds = _storage(resident=resident).read(paired_bam)
        assert read_group_stats(ds.reads) == oracle_rgstats(PAIRED)

    @pytest.mark.parametrize("mesh", [2, 4, 8])
    def test_mesh_matches_oracle(self, paired_bam, mesh):
        from disq_tpu.ops.rgstats import read_group_stats

        ds = _storage(resident=True, mesh=mesh).read(paired_bam)
        assert read_group_stats(ds.reads) == oracle_rgstats(PAIRED)

    def test_untagged_file_is_one_none_group(self, tmp_path):
        from disq_tpu.ops.rgstats import read_group_stats

        recs = synth_records(40, seed=3)
        p = tmp_path / "plain.bam"
        p.write_bytes(make_bam_bytes(DEFAULT_REFS, recs))
        ds = _storage(resident=True).read(str(p))
        got = read_group_stats(ds.reads)
        assert list(got) == ["(none)"]
        assert got == oracle_rgstats(recs)


class TestResidentChain:
    """The acceptance gate: filter → sort → markdup → rgstats chained
    resident vs the host-materializing path — identical stats AND
    identical written bytes, zero host materializations on the
    resident leg, and d2h actually avoided."""

    SPEC = "-F 0x800 -q 0"

    def _run(self, paired_bam, resident):
        ds = _storage(resident=resident, split=4000).read(paired_bam)
        return ds.pipeline(("filter", self.SPEC), "sort", "markdup",
                           "rgstats")

    def test_stats_and_written_bytes_identical(self, paired_bam,
                                               tmp_path):
        from disq_tpu.api import ReadsStorage
        from disq_tpu.runtime.columnar import ColumnarBatch

        mat = REGISTRY.counter("columnar.batch.materializations")
        avoided = REGISTRY.counter("device.d2h_avoided_bytes")
        m0 = mat.total()
        res_ds, res_stats = self._run(paired_bam, resident=True)
        assert isinstance(res_ds.reads, ColumnarBatch)
        assert res_ds.reads.device_backed
        # the fully resident chain never host-parsed a record, and
        # the compaction/sort/reduce stages consumed columns on device
        # instead of fetching them
        assert mat.total() == m0
        assert avoided.total() > 0
        host_ds, host_stats = self._run(paired_bam, resident=False)
        assert res_stats == host_stats
        assert res_stats["markdup"]["duplicates"] > 0
        out_res = str(tmp_path / "res.bam")
        out_host = str(tmp_path / "host.bam")
        st = ReadsStorage.make_default()
        st.write(res_ds, out_res)
        # nor did its write: the records' bytes are copied, not parsed
        assert mat.total() == m0
        st.write(host_ds, out_host)
        res_bytes = open(out_res, "rb").read()
        assert res_bytes == open(out_host, "rb").read()
        # the duplicate bits landed in the written records
        _text, _refs, recs = parse_bam(res_bytes)
        assert sum((r.flag >> 10) & 1 for r in recs) \
            == res_stats["markdup"]["duplicates"]
        res_ds.reads.release()

    def test_oracle_truth_of_chain(self, paired_bam):
        """The chained stats equal the oracles composed the same way
        (filter, then global markdup, then rgstats of the marked
        set)."""
        from bam_oracle import MARKDUP_EXCLUDE_O  # noqa: F401

        import copy

        _res_ds, stats = self._run(paired_bam, resident=True)
        keep = [copy.deepcopy(r) for r in PAIRED
                if not (r.flag & 0x800)]
        keep.sort(key=lambda r: (
            r.refid if r.refid >= 0 else 1 << 30, r.pos))
        for r, d in zip(keep, oracle_markdup(keep)):
            if d:
                r.flag |= 0x400
        want = oracle_rgstats(keep)
        assert stats["rgstats"] == want
        assert stats["markdup"]["duplicates"] == sum(
            (r.flag >> 10) & 1 for r in keep)


class TestCompactionPath:
    def test_device_filter_books_compact_span(self, paired_bam):
        from disq_tpu.runtime.tracing import spans

        ds = _storage(resident=True).read_filter("-q 30") \
            .read(paired_bam)
        assert ds.count() > 0
        assert any(s["name"] == "columnar.batch.compact"
                   for s in spans())
        host = _storage(resident=False).read_filter("-q 30") \
            .read(paired_bam)
        assert ds.count() == host.count()
        np.testing.assert_array_equal(
            np.asarray(ds.reads.pos), np.asarray(host.reads.pos))
        np.testing.assert_array_equal(
            np.asarray(ds.reads.names), np.asarray(host.reads.names))

    def test_filtered_batch_concat_and_pickle(self, paired_bam):
        import pickle

        ds = _storage(resident=True, split=3000).read_filter("-q 30") \
            .read(paired_bam)
        cb = ds.reads  # multi-shard concat of compacted shards
        rt = pickle.loads(pickle.dumps(cb))
        np.testing.assert_array_equal(
            np.asarray(rt.names), np.asarray(cb.names))
        np.testing.assert_array_equal(
            np.asarray(rt.pos), np.asarray(cb.pos))
