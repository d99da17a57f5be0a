"""Parallel write path: the ShardWritePipeline unit contract (ordering,
bounded window, stage retry, inline workers=1), byte-identity of
parallel vs sequential output for every sink at writer_workers in
{1, 4, 8} (including merged .bai/.sbi/.tbi/.crai indexes), write-side
fault injection, and StageManifest resume mid-write with workers>1."""

import os
import threading
import time

import numpy as np
import pytest

from bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records
from disq_tpu import ReadsStorage, VariantsStorage
from disq_tpu.runtime.executor import (
    ShardWritePipeline,
    WriteShardTask,
    run_write_stage,
    writer_for_storage,
)

WORKER_COUNTS = [1, 4, 8]


# ---------------------------------------------------------------------------
# unit: the write pipeline itself


class TestWritePipelineUnit:
    def _tasks(self, n, log=None, sleep=0.0):
        def mk(i):
            def encode():
                if sleep:
                    time.sleep(sleep)
                return i * 10

            def deflate(p):
                return p + 1

            def stage(p):
                if log is not None:
                    log.append(i)
                return p * 2

            return WriteShardTask(shard_id=i, encode=encode,
                                  deflate=deflate, stage=stage)

        return [mk(i) for i in range(n)]

    @pytest.mark.parametrize("workers", WORKER_COUNTS)
    def test_ordered_results(self, workers):
        pipe = ShardWritePipeline(workers=workers)
        results = list(pipe.map_ordered(self._tasks(17, sleep=0.001)))
        assert [r.shard_id for r in results] == list(range(17))
        assert [r.value for r in results] == [(i * 10 + 1) * 2
                                              for i in range(17)]

    def test_empty_tasks(self):
        assert list(ShardWritePipeline(workers=4).map_ordered([])) == []

    def test_optional_stages_pass_through(self):
        tasks = [WriteShardTask(shard_id=0, encode=lambda: 7)]
        out = list(ShardWritePipeline(workers=1).map_ordered(tasks))
        assert out[0].value == 7

    def test_sequential_runs_inline_in_order(self):
        log = []
        pipe = ShardWritePipeline(workers=1)
        for res in pipe.map_ordered(self._tasks(5, log=log)):
            # workers=1 is the inline path: shard i+1's stage must not
            # have run before shard i was emitted
            assert log == list(range(res.shard_id + 1))

    def test_bounded_in_flight_window(self):
        pipe = ShardWritePipeline(workers=2, prefetch_shards=3)
        release = threading.Event()

        def mk(i):
            def encode():
                if i == 0:
                    release.wait(timeout=30)
                return i

            return WriteShardTask(shard_id=i, encode=encode)

        it = iter(pipe.map_ordered([mk(i) for i in range(12)]))
        time.sleep(0.2)
        assert pipe.stats.max_in_flight <= pipe.stats.window
        release.set()
        assert [r.value for r in it] == list(range(12))
        assert pipe.stats.shards == 12

    @pytest.mark.parametrize("workers", [1, 4])
    def test_error_propagates(self, workers):
        def boom(_):
            raise ValueError("stage broke")

        tasks = [WriteShardTask(shard_id=0, encode=lambda: 1),
                 WriteShardTask(shard_id=1, encode=lambda: 1, stage=boom)]
        it = ShardWritePipeline(workers=workers).map_ordered(tasks)
        assert next(it).shard_id == 0
        with pytest.raises(ValueError, match="stage broke"):
            list(it)

    @pytest.mark.parametrize("workers", [1, 4])
    def test_transient_stage_retried(self, workers):
        from disq_tpu.runtime.errors import ShardRetrier, TransientIOError

        fails = {"n": 2}

        def stage(p):
            if fails["n"] > 0:
                fails["n"] -= 1
                raise TransientIOError("blip")
            return p

        retrier = ShardRetrier(max_retries=4, backoff_s=0.0)
        tasks = [WriteShardTask(shard_id=0, encode=lambda: 5, stage=stage,
                                retrier=retrier)]
        out = list(ShardWritePipeline(workers=workers).map_ordered(tasks))
        assert out[0].value == 5
        assert retrier.retried == 2
        fails["n"] = 2

    def test_writer_for_storage_defaults(self):
        pipe = writer_for_storage(ReadsStorage.make_default())
        assert pipe.workers == 1
        pipe = writer_for_storage(
            ReadsStorage.make_default().writer_workers(6, 9))
        assert pipe.workers == 6 and pipe.prefetch_shards == 9

    def test_invalid_worker_count_rejected(self):
        with pytest.raises(ValueError, match="writer_workers"):
            ReadsStorage.make_default().writer_workers(0)

    def test_run_write_stage_skips_completed_shards(self, tmp_path):
        from disq_tpu.runtime import StageManifest

        manifest = StageManifest(str(tmp_path / "m.json"))
        manifest.mark_done("s", 1, {"cached": True})
        ran = []

        def make_task(k):
            def encode():
                ran.append(k)
                return {"fresh": k}

            return WriteShardTask(shard_id=k, encode=encode)

        infos = run_write_stage(ShardWritePipeline(workers=2), 3,
                                make_task, manifest=manifest,
                                stage_name="s")
        assert sorted(ran) == [0, 2]
        assert infos == [{"fresh": 0}, {"cached": True}, {"fresh": 2}]
        # fresh shards were recorded as they completed
        assert manifest.completed_shards("s") == [0, 1, 2]


# ---------------------------------------------------------------------------
# byte identity across writer_workers for every sink


@pytest.fixture(scope="module")
def reads_ds():
    raw = make_bam_bytes(
        DEFAULT_REFS, synth_records(2600, seed=21, sorted_coord=True),
        blocksize=600, sort_order="coordinate")
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "in.bam")
        with open(p, "wb") as f:
            f.write(raw)
        yield ReadsStorage.make_default().read(p)


@pytest.fixture(scope="module")
def variants_ds():
    from disq_tpu.api import VariantsDataset
    from disq_tpu.vcf.columnar import parse_vcf_lines
    from disq_tpu.vcf.header import VcfHeader

    header = ("##fileformat=VCFv4.3\n"
              "##contig=<ID=chr1,length=248956422>\n"
              '##INFO=<ID=DP,Number=1,Type=Integer,Description="d">\n'
              "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\n")
    lines = [f"chr1\t{10 + 5 * i}\t.\tA\tG\t50\tPASS\tDP={i % 9}"
             for i in range(2400)]
    h = VcfHeader.from_text(header)
    batch = parse_vcf_lines([l.encode() for l in lines], h.contig_names)
    return VariantsDataset(header=h, variants=batch)


def _tree_bytes(root):
    out = {}
    for dirpath, _dirs, files in os.walk(root):
        for fn in files:
            p = os.path.join(dirpath, fn)
            out[os.path.relpath(p, root)] = open(p, "rb").read()
    return out


class TestByteIdentityAcrossWriterWorkers:
    @pytest.mark.parametrize("workers", [4, 8])
    def test_bam_single_with_indexes(self, reads_ds, tmp_path, workers):
        from disq_tpu.api import BaiWriteOption, SbiWriteOption

        base = tmp_path / "seq.bam"
        par = tmp_path / "par.bam"
        opts = (BaiWriteOption.ENABLE, SbiWriteOption.ENABLE)
        ReadsStorage.make_default().num_shards(7).write(
            reads_ds, str(base), *opts)
        (ReadsStorage.make_default().num_shards(7)
         .writer_workers(workers).write(reads_ds, str(par), *opts))
        assert par.read_bytes() == base.read_bytes()
        assert (tmp_path / "par.bam.bai").read_bytes() == \
            (tmp_path / "seq.bam.bai").read_bytes()
        assert (tmp_path / "par.bam.sbi").read_bytes() == \
            (tmp_path / "seq.bam.sbi").read_bytes()

    @pytest.mark.parametrize("workers", [4])
    def test_bam_multiple(self, reads_ds, tmp_path, workers):
        from disq_tpu.api import (
            FileCardinalityWriteOption,
            ReadsFormatWriteOption,
        )

        opts = (ReadsFormatWriteOption.BAM,
                FileCardinalityWriteOption.MULTIPLE)
        base = tmp_path / "seq-dir"
        par = tmp_path / "par-dir"
        ReadsStorage.make_default().num_shards(6).write(
            reads_ds, str(base), *opts)
        (ReadsStorage.make_default().num_shards(6)
         .writer_workers(workers).write(reads_ds, str(par), *opts))
        assert _tree_bytes(par) == _tree_bytes(base)
        assert len(_tree_bytes(par)) == 6

    @pytest.mark.parametrize("workers", [4])
    def test_sam_single(self, reads_ds, tmp_path, workers):
        base = tmp_path / "seq.sam"
        par = tmp_path / "par.sam"
        ReadsStorage.make_default().num_shards(6).write(reads_ds, str(base))
        (ReadsStorage.make_default().num_shards(6)
         .writer_workers(workers).write(reads_ds, str(par)))
        assert par.read_bytes() == base.read_bytes()

    @pytest.mark.parametrize("workers", [4, 8])
    def test_cram_single_with_crai(self, reads_ds, tmp_path, workers):
        from disq_tpu.api import CraiWriteOption

        base = tmp_path / "seq.cram"
        par = tmp_path / "par.cram"
        ReadsStorage.make_default().num_shards(6).write(
            reads_ds, str(base), CraiWriteOption.ENABLE)
        (ReadsStorage.make_default().num_shards(6)
         .writer_workers(workers)
         .write(reads_ds, str(par), CraiWriteOption.ENABLE))
        assert par.read_bytes() == base.read_bytes()
        assert (tmp_path / "par.cram.crai").read_bytes() == \
            (tmp_path / "seq.cram.crai").read_bytes()

    @pytest.mark.parametrize("workers", [4])
    def test_cram_multiple(self, reads_ds, tmp_path, workers):
        base = tmp_path / "seq-cram-dir"
        par = tmp_path / "par-cram-dir"
        from disq_tpu.api import (
            FileCardinalityWriteOption,
            ReadsFormatWriteOption,
        )

        opts = (ReadsFormatWriteOption.CRAM,
                FileCardinalityWriteOption.MULTIPLE)
        ReadsStorage.make_default().num_shards(5).write(
            reads_ds, str(base), *opts)
        (ReadsStorage.make_default().num_shards(5)
         .writer_workers(workers).write(reads_ds, str(par), *opts))
        assert _tree_bytes(par) == _tree_bytes(base)

    @pytest.mark.parametrize("workers", [4, 8])
    @pytest.mark.parametrize("ext", [".vcf", ".vcf.bgz"])
    def test_vcf_single(self, variants_ds, tmp_path, workers, ext):
        from disq_tpu.api import TabixIndexWriteOption

        opts = (TabixIndexWriteOption.ENABLE,) if ext == ".vcf.bgz" else ()
        base = tmp_path / ("seq" + ext)
        par = tmp_path / ("par" + ext)
        VariantsStorage.make_default().num_shards(6).write(
            variants_ds, str(base), *opts)
        (VariantsStorage.make_default().num_shards(6)
         .writer_workers(workers).write(variants_ds, str(par), *opts))
        assert par.read_bytes() == base.read_bytes()
        if opts:
            assert (tmp_path / ("par" + ext + ".tbi")).read_bytes() == \
                (tmp_path / ("seq" + ext + ".tbi")).read_bytes()

    @pytest.mark.parametrize("workers", [4])
    def test_vcf_multiple(self, variants_ds, tmp_path, workers):
        from disq_tpu.api import VariantsFormatWriteOption

        base = tmp_path / "seq-vcf-dir"
        par = tmp_path / "par-vcf-dir"
        VariantsStorage.make_default().num_shards(5).write(
            variants_ds, str(base), VariantsFormatWriteOption.VCF_BGZ)
        (VariantsStorage.make_default().num_shards(5)
         .writer_workers(workers)
         .write(variants_ds, str(par), VariantsFormatWriteOption.VCF_BGZ))
        assert _tree_bytes(par) == _tree_bytes(base)

    @pytest.mark.parametrize("workers", [4, 8])
    def test_bcf_single(self, variants_ds, tmp_path, workers):
        base = tmp_path / "seq.bcf"
        par = tmp_path / "par.bcf"
        VariantsStorage.make_default().num_shards(6).write(
            variants_ds, str(base))
        (VariantsStorage.make_default().num_shards(6)
         .writer_workers(workers).write(variants_ds, str(par)))
        assert par.read_bytes() == base.read_bytes()
        # and it reads back
        ds = VariantsStorage.make_default().read(str(par))
        assert ds.count() == variants_ds.count()


# ---------------------------------------------------------------------------
# write-side fault injection


class TestWriteFaultInjection:
    def _fault_fs(self, faults, seed=0):
        from disq_tpu.fsw import (
            FaultInjectingFileSystemWrapper,
            PosixFileSystemWrapper,
            register_filesystem,
        )

        fsw = FaultInjectingFileSystemWrapper(
            PosixFileSystemWrapper(), faults, seed=seed)
        register_filesystem("fault", fsw)
        return fsw

    def test_write_transient_raises_then_retries(self, tmp_path):
        from disq_tpu.fsw import FaultSpec
        from disq_tpu.runtime.errors import TransientIOError

        fsw = self._fault_fs([FaultSpec(kind="transient", op="write",
                                        path_substr="x.bin", times=1)])
        with pytest.raises(TransientIOError):
            fsw.write_all("fault://" + str(tmp_path / "x.bin"), b"abc")
        # the schedule is exhausted (times=1): the retry lands
        fsw.write_all("fault://" + str(tmp_path / "x.bin"), b"abc")
        assert (tmp_path / "x.bin").read_bytes() == b"abc"

    def test_write_truncate_damages_staged_bytes(self, tmp_path):
        from disq_tpu.fsw import FaultSpec

        fsw = self._fault_fs([FaultSpec(kind="truncate", op="write",
                                        path_substr="y.bin",
                                        truncate_bytes=2, times=1)])
        fsw.write_all("fault://" + str(tmp_path / "y.bin"), b"abcdef")
        assert (tmp_path / "y.bin").read_bytes() == b"abcd"

    def test_read_specs_do_not_fire_on_writes(self, tmp_path):
        from disq_tpu.fsw import FaultSpec

        fsw = self._fault_fs([
            FaultSpec(kind="transient", path_substr="z.bin"),  # op="read"
        ])
        fsw.write_all("fault://" + str(tmp_path / "z.bin"), b"q")
        assert fsw.fired_counts() == [("transient", 0)]

    def test_write_specs_do_not_fire_on_reads(self, tmp_path):
        from disq_tpu.fsw import FaultSpec

        p = tmp_path / "w.bin"
        p.write_bytes(b"payload")
        fsw = self._fault_fs([
            FaultSpec(kind="transient", op="write", path_substr="w.bin"),
        ])
        assert fsw.read_range("fault://" + str(p), 0, 7) == b"payload"
        assert fsw.fired_counts() == [("transient", 0)]

    @pytest.mark.parametrize("workers", [1, 4])
    def test_parallel_write_absorbs_write_transients(
            self, reads_ds, tmp_path, workers):
        """Transient blips on part staging are retried per shard; the
        merged output must be byte-identical to a fault-free write."""
        from disq_tpu import DisqOptions
        from disq_tpu.fsw import FaultSpec

        clean = tmp_path / "clean.bam"
        ReadsStorage.make_default().num_shards(6).write(reads_ds, str(clean))

        out = tmp_path / "faulted.bam"
        fsw = self._fault_fs(
            [FaultSpec(kind="transient", op="write", probability=0.25)],
            seed=1)  # Random(1)'s first draw is 0.134 < 0.25: at least
                     # one fault fires no matter the thread interleaving
        st = (ReadsStorage.make_default().num_shards(6)
              .options(DisqOptions(max_retries=8, retry_backoff_s=0.0))
              .writer_workers(workers))
        st.write(reads_ds, "fault://" + str(out))
        assert out.read_bytes() == clean.read_bytes()
        assert any(n for _k, n in fsw.fired_counts())


# ---------------------------------------------------------------------------
# manifest resume mid-write under concurrency


def _write_counting_fs():
    """Posix wrapper that logs every write_all path."""
    from disq_tpu.fsw import PosixFileSystemWrapper

    class _Counting(PosixFileSystemWrapper):
        def __init__(self):
            self.writes = []

        def write_all(self, path, data):
            self.writes.append(path)
            super().write_all(path, data)

    return _Counting()


class TestManifestResumeParallel:
    @pytest.mark.parametrize("workers", [4])
    def test_crash_then_resume_skips_staged_shards(
            self, reads_ds, tmp_path, workers):
        from disq_tpu import DisqOptions
        from disq_tpu.api import (
            BaiWriteOption,
            SbiWriteOption,
            StageManifestWriteOption,
        )
        from disq_tpu.fsw import (
            FaultInjectingFileSystemWrapper,
            FaultSpec,
            register_filesystem,
        )
        from disq_tpu.runtime import StageManifest
        from disq_tpu.runtime.errors import TransientIOError

        out = str(tmp_path / "out.bam")
        mpath = str(tmp_path / "write.manifest")
        opts = (StageManifestWriteOption(mpath), BaiWriteOption.ENABLE,
                SbiWriteOption.ENABLE)

        # Every attempt to stage shard 3's part faults: its retrier
        # exhausts and the write dies mid-run — a deterministic crash.
        counting = _write_counting_fs()
        fsw = FaultInjectingFileSystemWrapper(
            counting,
            [FaultSpec(kind="transient", op="write",
                       path_substr="part-00003")],
        )
        register_filesystem("fault", fsw)
        st = (ReadsStorage.make_default().num_shards(6)
              .options(DisqOptions(max_retries=1, retry_backoff_s=0.0))
              .writer_workers(workers))
        with pytest.raises(TransientIOError):
            st.write(reads_ds, "fault://" + out, *opts)

        # Staged shards survived and are recorded in the manifest —
        # in whatever completion order the pipeline reached them.
        manifest = StageManifest(mpath)
        done = manifest.completed_shards("bam.parts")
        assert done and 3 not in done
        for k in done:
            assert os.path.exists(out + f".parts/part-{k:05d}")

        # Resume fault-free: completed shards are not re-staged.
        counting.writes.clear()
        fsw.reset()
        fsw.faults.clear()
        st.write(reads_ds, "fault://" + out, *opts)
        for k in done:
            assert not any(
                w.endswith(f"part-{k:05d}") for w in counting.writes
            ), f"staged shard {k} was re-written on resume"
        assert not os.path.exists(mpath)           # commit removed it
        assert not os.path.exists(out + ".parts")  # staging cleaned

        # The resumed file and indexes are identical to a clean write.
        clean = str(tmp_path / "clean.bam")
        ReadsStorage.make_default().num_shards(6).write(
            reads_ds, clean, BaiWriteOption.ENABLE, SbiWriteOption.ENABLE)
        assert open(out, "rb").read() == open(clean, "rb").read()
        assert open(out + ".bai", "rb").read() == \
            open(clean + ".bai", "rb").read()
        assert open(out + ".sbi", "rb").read() == \
            open(clean + ".sbi", "rb").read()


# ---------------------------------------------------------------------------
# the shard deflate: record voffsets from the blocks' compressed sizes

from disq_tpu.bgzf.block import BGZF_MAX_PAYLOAD as BLOCK  # noqa: E402

# by id: the lengths of the records in the blob
VOFFSET_RECORDS = {
    "many_records": lambda: np.random.default_rng(7).integers(40, 200, 800),
    "one_straddles_a_block": lambda: [BLOCK - 10, 50, 100],
    "one_ends_at_a_block": lambda: [1000, BLOCK - 1000, 100, 7],
    "one_is_longer_than_a_block": lambda: [30, 2 * BLOCK + 17, 5],
    "no_records": lambda: [],
}


@pytest.mark.parametrize("name", VOFFSET_RECORDS)
def test_voffsets_seek_to_each_records_bytes(name):
    """Every voffset ``bgzf_compress_with_voffsets`` computes from the
    blocks' compressed sizes must seek (via the framework's BgzfReader)
    to that record's exact bytes, and every end must be the next
    record's start."""
    import io

    from disq_tpu.bam.sink import bgzf_compress_with_voffsets
    from disq_tpu.bgzf.block import BGZF_EOF_MARKER
    from disq_tpu.bgzf.codec import BgzfReader

    rec_lens = np.asarray(VOFFSET_RECORDS[name](), np.int64)
    offs = np.zeros(len(rec_lens) + 1, np.int64)
    np.cumsum(rec_lens, out=offs[1:])
    blob = np.random.default_rng(8).integers(
        0, 24, int(offs[-1]), np.uint8).tobytes()
    comp, voffs, end_voffs = bgzf_compress_with_voffsets(blob, offs)
    assert len(voffs) == len(end_voffs) == len(rec_lens)
    assert voffs.dtype == end_voffs.dtype == np.uint64
    np.testing.assert_array_equal(voffs[1:], end_voffs[:-1])
    reader = BgzfReader(io.BytesIO(comp + BGZF_EOF_MARKER))
    for i in range(0, len(rec_lens), 97 if len(rec_lens) > 97 else 1):
        reader.seek_virtual(int(voffs[i]))
        want = blob[int(offs[i]): int(offs[i + 1])]
        assert reader.read_exact(len(want)) == want
    if len(rec_lens):
        # the last end is where the blob ends: nothing is left to read
        reader.seek_virtual(int(end_voffs[-1]))
        assert reader.read(1) == b""


def test_a_default_write_does_no_device_work(reads_ds, tmp_path,
                                             monkeypatch):
    """No device service is started and no kernel is launched."""
    from disq_tpu.api import BaiWriteOption
    from disq_tpu.runtime import device_service
    from disq_tpu.runtime.tracing import REGISTRY

    monkeypatch.delenv("DISQ_TPU_DEVICE_SERVICE", raising=False)
    device_service.shutdown_service()
    launches = REGISTRY.counter("device.kernel_launches")
    before = launches.total()
    (ReadsStorage.make_default().num_shards(4)
     .write(reads_ds, str(tmp_path / "host.bam"), BaiWriteOption.ENABLE,
            sort=True))
    assert launches.total() == before
    assert device_service.service_if_running() is None


def test_quarantined_read_then_write(tmp_path):
    """A corrupt block quarantined on read loses exactly its own
    records; the write of the surviving dataset re-reads to exactly
    those records: the owner shard's loss never spreads."""
    from disq_tpu import DisqOptions
    from disq_tpu.bgzf.block import parse_block_header

    n_rec = 153
    data = make_bam_bytes(
        DEFAULT_REFS, synth_records(150, seed=11, unmapped_tail=3),
        blocksize=900)
    # corrupt the DEFLATE payload of the 3rd block
    layout, pos = [], 0
    while pos < len(data):
        layout.append(pos)
        pos += parse_block_header(data, pos)
    bad = bytearray(data)
    bad[layout[3] + 20] ^= 0xFF
    bad_path = str(tmp_path / "bad.bam")
    with open(bad_path, "wb") as f:
        f.write(bytes(bad))
    opts = DisqOptions(
        error_policy="quarantine", quarantine_dir=str(tmp_path / "quar"))
    ds = ReadsStorage.make_default().options(opts).read(bad_path)
    assert ds.counters.quarantined_blocks == 1
    assert 0 < n_rec - ds.count() <= 40
    out = str(tmp_path / "salvaged.bam")
    ReadsStorage.make_default().num_shards(3).write(ds, out)
    got = ReadsStorage.make_default().read(out)
    assert got.count() == ds.count()
    for col in ("pos", "flag", "names", "seqs", "quals", "tags"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got.reads, col)),
            np.asarray(getattr(ds.reads, col)), err_msg=col)


# ---------------------------------------------------------------------------
# telemetry: write spans + gauge reach the registry


def test_write_emits_spans_and_gauge(reads_ds, tmp_path):
    from disq_tpu.runtime import tracing

    tracing.reset_telemetry()
    (ReadsStorage.make_default().num_shards(6).writer_workers(4)
     .write(reads_ds, str(tmp_path / "t.bam")))
    rep = tracing.phase_report()
    for name in ("bam.write.encode", "bam.write.deflate",
                 "bam.write.stage", "bam.write.merge"):
        assert name in rep, name
        assert rep[name]["calls"] >= 1
    gauges = tracing.gauge_report()
    assert gauges["writer.in_flight"]["max"] >= 2
    # per-shard spans carry the shard label
    shard_spans = [s for s in tracing.spans()
                   if s["name"] == "bam.write.encode"]
    assert sorted(s["labels"]["shard"] for s in shard_spans) == list(range(6))
