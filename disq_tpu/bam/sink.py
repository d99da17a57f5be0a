"""BamSink — single-file and multi-file BAM write paths.

Reference parity: ``impl/formats/bam/BamSink.java`` +
``HeaderlessBamOutputFormat`` + ``AnySamSinkMultiple`` (SURVEY.md §2.4,
call stack §3.3). Single-file protocol: shards write *headerless,
terminatorless* BGZF parts to a temp dir, each emitting part-local BAI /
SBI index fragments; the driver writes a header-only BGZF prefix,
concatenates prefix + parts, appends the 28-byte terminator, and merges
the index fragments by shifting each part's virtual offsets by its
absolute start position.

TPU-first twist: per-record virtual offsets inside a part are computed
*vectorized* — the canonical BGZF blocking is deterministic (65280-byte
payload per block), so ``voffset(u) = (block_comp_start[u // 65280] << 16)
| (u % 65280)`` is array arithmetic over the record-offset vector, not a
per-record stream query. This is what makes index construction a
"segmented scan over sorted virtual offsets" (BASELINE.json north star).

Shards run through the shard write pipeline
(``runtime/executor.ShardWritePipeline``): encode (slice + record
encode) → deflate (BGZF + voffset/index arithmetic) → stage (durable
part + fragment writes), overlapped across shards at
``DisqOptions.writer_workers > 1`` with byte-identical output; the
deterministic per-shard bytes + ordered concat of the part-merge
protocol are what make that safe.
"""

from __future__ import annotations

import os
from typing import List, Sequence, Tuple

import numpy as np

from disq_tpu.api import (
    BaiWriteOption,
    SbiWriteOption,
    StageManifestWriteOption,
    TempPartsDirectoryWriteOption,
    WriteOption,
)
from disq_tpu.bam.codec import encode_records, encode_records_with_offsets
from disq_tpu.bam.columnar import ReadBatch
from disq_tpu.bam.header import SamHeader
from disq_tpu.bgzf.block import BGZF_EOF_MARKER, BGZF_MAX_PAYLOAD
from disq_tpu.bgzf.codec import compress_to_bgzf, deflate_blob
from disq_tpu.fsw.filesystem import FileSystemWrapper, resolve_path
from disq_tpu.index.bai import BaiIndex, build_bai, merge_bai_fragments
from disq_tpu.index.sbi import SbiIndex
from disq_tpu.util import resolve_num_shards, shard_bounds

SBI_GRANULARITY = 4096  # htsjdk SBIIndexWriter default


def _batch_digest(batch) -> int:
    """Content fingerprint for resume-safety: a manifest written against
    one dataset must not adopt staged parts encoded from another. CRC32
    over every column (one vectorized pass; ~GB/s, negligible next to
    deflate)."""
    import zlib

    crc = 0
    for col in (
        batch.refid, batch.pos, batch.mapq, batch.flag, batch.tlen,
        batch.names, batch.cigars, batch.seqs, batch.quals, batch.tags,
    ):
        crc = zlib.crc32(np.ascontiguousarray(col).tobytes(), crc)
    return crc


def _pickle_dumps(obj) -> bytes:
    import pickle

    return pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)


def _pickle_loads(data: bytes):
    import pickle

    return pickle.loads(data)


def _opt_enabled(options: Sequence[WriteOption], cls, default: bool) -> bool:
    for o in options:
        if isinstance(o, cls):
            return bool(o.value)
    return default


def bgzf_compress_with_voffsets(
    blob: bytes, record_offsets: np.ndarray
) -> Tuple[bytes, np.ndarray, np.ndarray]:
    """Deflate ``blob`` into canonical BGZF (no terminator) and return
    (compressed bytes, start voffsets, end voffsets) for the records whose
    uncompressed offsets are ``record_offsets`` ((N+1,): starts + end):
    array arithmetic over the per-block compressed sizes."""
    comp, csizes = deflate_blob(blob)
    block_comp_start = np.zeros(len(csizes) + 1, dtype=np.int64)
    np.cumsum(csizes, out=block_comp_start[1:])
    offs = record_offsets.astype(np.int64)
    block_idx = offs // BGZF_MAX_PAYLOAD
    within = offs % BGZF_MAX_PAYLOAD
    voffs = (block_comp_start[block_idx].astype(np.uint64) << np.uint64(16)) | within.astype(np.uint64)
    return comp, voffs[:-1], voffs[1:]


class _LazySlice:
    """Deferred shard slice for the write from a batch's record bytes:
    what a shard's index fragments ask of it comes without a host
    parse. ``refid``, ``pos`` and ``flag`` are read from the shard's
    ``encoded`` bytes at its record offsets (so they are the index of
    what is written, and cost no d2h), and the alignment ends come from
    the batch's span cache for this shard's records alone. Any other
    attribute comes from a real slice: a reader of a ragged column
    only pays."""

    __slots__ = ("_batch", "_lo", "_hi", "_part", "_encoded")

    def __init__(self, batch, lo: int, hi: int, encoded) -> None:
        self._batch = batch
        self._lo, self._hi = lo, hi
        self._part = None
        self._encoded = encoded

    @property
    def count(self) -> int:
        return self._hi - self._lo

    def _mat(self):
        if self._part is None:
            self._part = self._batch.slice(self._lo, self._hi)
        return self._part

    def alignment_ends(self):
        return self._batch.alignment_ends(self._lo, self._hi)

    def __getattr__(self, name: str):
        # only what no slot or property answers lands here
        at = _FIELD_AT.get(name)
        if at is None:
            return getattr(self._mat(), name)
        blob, offs = self._encoded
        dtype = np.dtype(at[1])
        idx = offs[:-1, None] + np.arange(at[0], at[0] + dtype.itemsize)
        return blob[idx].view(dtype)[:, 0]


# the fixed fields an index fragment reads, as (byte in the record,
# dtype): what ``bam/codec.py`` packs there
_FIELD_AT = {"refid": (4, "<i4"), "pos": (8, "<i4"), "flag": (18, "<u2")}


class BamSink:
    """Single-file BAM write (``FileCardinalityWriteOption.SINGLE``).

    A batch that holds its records' bytes (a resident read's
    ``ColumnarBatch``, also filtered, ``permuted()`` or flag-patched:
    ``encode_source()`` is not None) is written from those bytes: each
    shard copies its records out of the blob in the pending order
    (``ColumnarBatch.encoded_slice``), no record is parsed and none is
    encoded again; the files are the column encoder's, byte for byte.
    Any other batch (a ``ReadBatch``, a host-built ``ColumnarBatch``)
    is sliced and encoded from its columns."""

    def __init__(self, storage=None):
        self._storage = storage

    def _num_shards(self) -> int:
        return resolve_num_shards(self._storage)

    def save(
        self, dataset, path: str, options: Sequence[WriteOption] = ()
    ) -> None:
        fs, path = resolve_path(path)
        header: SamHeader = dataset.header
        batch: ReadBatch = dataset.reads
        write_bai = _opt_enabled(options, BaiWriteOption, False)
        write_sbi = _opt_enabled(options, SbiWriteOption, False)
        temp_dir = next(
            (o.path for o in options if isinstance(o, TempPartsDirectoryWriteOption)),
            path + ".parts",
        )
        if write_bai and header.sort_order != "coordinate":
            raise ValueError(
                "BAI requires a coordinate-sorted header; "
                "sort first (ReadsStorage.write(..., sort=True))"
            )

        manifest = None
        manifest_opt = next(
            (o for o in options if isinstance(o, StageManifestWriteOption)), None
        )
        n_shards, bounds = shard_bounds(self._storage, batch.count)
        if manifest_opt is not None:
            from disq_tpu.runtime import StageManifest

            manifest = StageManifest(
                manifest_opt.path,
                params={
                    "target": path,
                    "records": int(batch.count),
                    "digest": _batch_digest(batch),
                    "n_shards": int(n_shards),
                    "bai": write_bai,
                    "sbi": write_sbi,
                },
            )
        fs.mkdirs(temp_dir)
        try:
            self._write_parts_and_merge(
                fs, header, batch, path, temp_dir, n_shards, bounds,
                write_bai, write_sbi, manifest,
            )
        except BaseException:
            # Idempotent write protocol (SURVEY.md §5): the merge is the
            # commit point. Without a manifest the staging dir never
            # outlives save(); with one, staged parts survive the failure
            # so a re-run resumes shard-level instead of starting over.
            if manifest is None:
                fs.delete(temp_dir, recursive=True)
            raise
        else:
            # Commit order matters: retire the manifest FIRST. A crash
            # between the two steps then leaks only a stale staging dir
            # (harmless; recreated next run) rather than a manifest whose
            # recorded part paths no longer exist.
            if manifest is not None:
                manifest.finish()
            fs.delete(temp_dir, recursive=True)

    # -- pipeline stage bodies (encode → deflate → stage) -------------------

    def _encode_shard(self, batch, bounds, k):
        """Stage 1: shard ``k``'s records as BAM bytes, by what the
        batch is. From a batch that holds its records' bytes, a host
        copy of them in the batch's order (``encoded_slice``: no parse,
        no encode, the writers side by side). Else a slice of the
        columns and the CPU record encode. ``bam.write.slice`` times
        the cut either way, and ``bam.write.encoded_records{how}``
        counts the records by it."""
        lo, hi = int(bounds[k]), int(bounds[k + 1])
        from disq_tpu.runtime.tracing import counter, span

        encoded_slice = getattr(batch, "encoded_slice", None)
        with span("bam.write.slice", shard=k, records=hi - lo):
            encoded = encoded_slice(lo, hi) if encoded_slice else None
            part = (batch.slice(lo, hi) if encoded is None
                    else _LazySlice(batch, lo, hi, encoded))
        how = "bytes"
        if encoded is None:
            encoded, how = encode_records_with_offsets(part), "columns"
        counter("bam.write.encoded_records").inc(hi - lo, how=how)
        return (part, *encoded)

    def _deflate_shard(self, header, write_bai, write_sbi, payload):
        """Stage 2 (native-threaded CPU): BGZF deflate, vectorized
        voffset arithmetic, and index-fragment build."""
        from disq_tpu.runtime import check_voffsets, debug_enabled

        part, blob, rec_offs = payload
        comp, voffs, end_voffs = bgzf_compress_with_voffsets(blob, rec_offs)
        if debug_enabled():
            check_voffsets(voffs)
        sbi_frag = bai_frag = None
        if write_sbi:
            sbi_frag = SbiIndex.build(
                voffs, int(end_voffs[-1]) if part.count else 0,
                0, granularity=SBI_GRANULARITY,
            )
        if write_bai:
            bai_frag = build_bai(
                part.refid, part.pos, part.alignment_ends(),
                part.flag, voffs, end_voffs, header.n_ref,
            )
        return comp, sbi_frag, bai_frag

    def _stage_shard(self, fs, temp_dir, k, frag_cache, payload) -> dict:
        """Stage 3 (I/O): durably write the part (and pickled index
        fragments when checkpointing); returns the shard's manifest
        record. Fragments land in ``frag_cache`` in memory and are
        pickled beside the part only when checkpointing (frag_cache is
        None ⇒ persist — the manifest path always resumes from disk)."""
        comp, sbi_frag, bai_frag = payload
        part_path = os.path.join(temp_dir, f"part-{k:05d}")
        fs.write_all(part_path, comp)
        info = {"part": part_path, "len": len(comp), "sbi": None, "bai": None}
        persist = frag_cache is None
        if sbi_frag is not None:
            info["sbi"] = part_path + ".sbi-frag"
            if persist:
                fs.write_all(info["sbi"], _pickle_dumps(sbi_frag))
        if bai_frag is not None:
            info["bai"] = part_path + ".bai-frag"
            if persist:
                fs.write_all(info["bai"], _pickle_dumps(bai_frag))
        if frag_cache is not None:
            frag_cache[k] = (sbi_frag, bai_frag)
        return info

    def _write_one_part(
        self, fs, header, batch, temp_dir, bounds, write_bai, write_sbi, k,
        frag_cache=None,
    ) -> dict:
        """Whole-shard unit (encode + deflate + stage in one call) —
        the sequential manifest path's work function, and the
        composition the pipeline stages split apart."""
        from disq_tpu.runtime.tracing import span

        with span("bam.write.encode", shard=k):
            payload = self._encode_shard(batch, bounds, k)
        with span("bam.write.deflate", shard=k):
            payload = self._deflate_shard(header, write_bai, write_sbi,
                                          payload)
        with span("bam.write.stage", shard=k):
            return self._stage_shard(fs, temp_dir, k, frag_cache, payload)

    @staticmethod
    def _part_byte_ranges(batch, bounds):
        """Exact uncompressed output byte range of every part within
        the merged record stream (the ``encode_records`` size
        arithmetic at shard bounds) — the write-lease locality hint.
        Computed only when write leasing is armed; None when the batch
        can't answer cheaply (the leases then stay FIFO, the truth)."""
        try:
            name_len = np.diff(batch.name_offsets)
            n_cigar = np.diff(batch.cigar_offsets)
            l_seq = np.diff(batch.seq_offsets)
            tag_len = np.diff(batch.tag_offsets)
        except Exception:  # noqa: BLE001 — hint-only, never fail a save
            return None
        sizes = (36 + (name_len + 1) + 4 * n_cigar + (l_seq + 1) // 2
                 + l_seq + tag_len).astype(np.int64)
        cum = np.zeros(len(sizes) + 1, np.int64)
        np.cumsum(sizes, out=cum[1:])
        return [(int(cum[int(bounds[k])]), int(cum[int(bounds[k + 1])]))
                for k in range(len(bounds) - 1)]

    def _make_write_task(self, fs, header, batch, temp_dir, bounds,
                         write_bai, write_sbi, k, frag_cache,
                         byte_range=None):
        from disq_tpu.runtime.executor import (
            WriteShardTask,
            write_retrier_for_storage,
        )
        from disq_tpu.runtime.tracing import wrap_span

        return WriteShardTask(
            byte_range=byte_range,
            shard_id=k,
            encode=wrap_span(
                "bam.write.encode",
                lambda: self._encode_shard(batch, bounds, k),
                shard=k),
            deflate=wrap_span(
                "bam.write.deflate",
                lambda p: self._deflate_shard(
                    header, write_bai, write_sbi, p), shard=k),
            stage=wrap_span(
                "bam.write.stage",
                lambda p: self._stage_shard(
                    fs, temp_dir, k, frag_cache, p), shard=k),
            # temp_dir carries the output's scheme, so the part writes
            # share the destination filesystem's breaker.
            retrier=write_retrier_for_storage(self._storage, temp_dir),
            what="bam.part",
        )

    def _write_parts_and_merge(
        self, fs, header, batch, path, temp_dir, n_shards, bounds,
        write_bai, write_sbi, manifest=None,
    ) -> None:
        from disq_tpu.runtime import trace_phase
        from disq_tpu.runtime.executor import (
            run_write_stage,
            write_retrier_for_storage,
            writer_for_storage,
        )

        pipeline = writer_for_storage(self._storage)
        # Checkpointed: fragments must survive the process, so each
        # shard pickles them beside its part (frag_cache unused);
        # resumed shards reload from disk below.
        frag_cache = None if manifest is not None else {}

        def one_part(k):
            return self._write_one_part(
                fs, header, batch, temp_dir, bounds,
                write_bai, write_sbi, k)

        with trace_phase("bam.write.parts"):
            from disq_tpu.runtime.scheduler import write_leasing_armed

            leasing = write_leasing_armed(self._storage)
            if (manifest is not None and pipeline.workers == 1
                    and not leasing):
                # Historical sequential-checkpoint path: run_stage
                # owns skip/retry/RuntimeError semantics per shard.
                infos = manifest.run_stage(
                    "bam.parts", n_shards, one_part)
            else:
                # byte ranges feed write-lease locality scoring;
                # off-path saves skip the O(n) size walk entirely
                ranges = (self._part_byte_ranges(batch, bounds)
                          if leasing and manifest is not None
                          else None)
                infos = run_write_stage(
                    pipeline, n_shards,
                    lambda k: self._make_write_task(
                        fs, header, batch, temp_dir, bounds,
                        write_bai, write_sbi, k, frag_cache,
                        byte_range=(ranges[k] if ranges else None)),
                    manifest=manifest, stage_name="bam.parts",
                    storage=self._storage, path=path, fs=fs,
                )
        part_paths = [i["part"] for i in infos]
        part_lens = [i["len"] for i in infos]

        def _frag(k: int, which: int, key: str):
            if frag_cache is not None and k in frag_cache:
                return frag_cache[k][which]
            return _pickle_loads(fs.read_all(infos[k][key]))

        sbi_frags = [
            _frag(k, 0, "sbi") for k in range(n_shards) if infos[k]["sbi"]
        ]
        bai_frags = [
            _frag(k, 1, "bai") for k in range(n_shards) if infos[k]["bai"]
        ]

        # Driver side: header-only BGZF prefix, concat, terminator.
        # Every durable driver write runs under the same transient
        # retry budget the staged parts get (atomic create makes a
        # retried write/concat safe).
        driver = write_retrier_for_storage(self._storage, path)
        with trace_phase("bam.write.merge"):
            header_comp = compress_to_bgzf(
                header.to_bam_bytes(), with_terminator=False)
            header_path = os.path.join(temp_dir, "_header")
            driver.call(fs.write_all, header_path, header_comp,
                        what="bam.merge")
            term_path = os.path.join(temp_dir, "_terminator")
            driver.call(fs.write_all, term_path, BGZF_EOF_MARKER,
                        what="bam.merge")
            driver.call(fs.concat, [header_path] + part_paths + [term_path],
                        path, what="bam.merge")

        part_starts = np.zeros(len(part_lens) + 1, dtype=np.int64)
        np.cumsum(part_lens, out=part_starts[1:])
        part_starts = part_starts[:-1] + len(header_comp)
        file_length = fs.get_file_length(path)
        if write_sbi:
            merged = SbiIndex.merge(sbi_frags, list(part_starts), file_length)
            driver.call(fs.write_all, path + ".sbi", merged.to_bytes(),
                        what="bam.merge")
        if write_bai:
            merged_bai = merge_bai_fragments(bai_frags, list(part_starts))
            driver.call(fs.write_all, path + ".bai", merged_bai.to_bytes(),
                        what="bam.merge")


class BamSinkMultiple:
    """Directory-of-complete-BAMs write (``MULTIPLE`` cardinality;
    ref: ``AnySamSinkMultiple.java``)."""

    def __init__(self, storage=None):
        self._storage = storage

    def save(self, dataset, path: str, options: Sequence[WriteOption] = ()) -> None:
        from disq_tpu.runtime.executor import (
            WriteShardTask,
            run_write_stage,
            write_retrier_for_storage,
            writer_for_storage,
        )
        from disq_tpu.runtime.tracing import wrap_span

        fs, path = resolve_path(path)
        header: SamHeader = dataset.header
        batch: ReadBatch = dataset.reads
        n_shards, bounds = shard_bounds(self._storage, batch.count)
        fs.mkdirs(path)
        header_bytes = header.to_bam_bytes()

        def make_task(k):
            def encode():
                part = batch.slice(int(bounds[k]), int(bounds[k + 1]))
                return header_bytes + encode_records(part)

            def stage(data):
                p = os.path.join(path, f"part-r-{k:05d}.bam")
                fs.write_all(p, data)
                return p

            return WriteShardTask(
                shard_id=k,
                encode=wrap_span("bam.write.encode", encode, shard=k),
                deflate=wrap_span(
                    "bam.write.deflate", compress_to_bgzf, shard=k),
                stage=wrap_span("bam.write.stage", stage, shard=k),
                retrier=write_retrier_for_storage(self._storage, path),
                what="bam.part",
            )

        # no manifest ⇒ no durable side: the write-leasing path stays
        # off for directory-of-BAMs saves regardless of scheduler mode
        run_write_stage(writer_for_storage(self._storage), n_shards,
                        make_task, storage=self._storage, path=path)
