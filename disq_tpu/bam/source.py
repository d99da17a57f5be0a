"""BamSource — the parallel BAM read path.

Reference parity: ``impl/formats/bam/BamSource.java`` (SURVEY.md §2.4,
call stack §3.1): header read on the host ("driver"); the file is cut
into byte-range splits; each split resolves its first whole-record
boundary — via the ``.sbi`` splitting index when present, else the
``BgzfBlockGuesser`` + ``BamRecordGuesser`` chain — and decodes records
from its own boundary up to the *next* split's boundary, reading past its
byte-range end to finish the straddling record ("first owner" rule).

TPU-first shape: each split yields a columnar ``ReadBatch`` (not record
objects); split workers are host-side and feed device shards. Interval
traversal (``.bai``) lives in ``disq_tpu.traversal``.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from disq_tpu.bam.codec import (
    decode_records,
    scan_record_offsets,
    scan_record_offsets_tolerant,
)
from disq_tpu.bam.columnar import ReadBatch
from disq_tpu.bam.guesser import BamRecordGuesser
from disq_tpu.bam.header import SamHeader
from disq_tpu.bgzf.block import (
    BGZF_EOF_MARKER,
    BgzfBlock,
    make_virtual_offset,
)
from disq_tpu.bgzf.codec import BgzfReader, inflate_blocks
from disq_tpu.bgzf.guesser import (
    BgzfBlockGuesser,
    _walk_blocks_collect,
    walk_blocks_salvage,
)
from disq_tpu.fsw.filesystem import (
    FileSystemWrapper,
    PathSplit,
    compute_path_splits,
    resolve_path,
)
from disq_tpu.index.sbi import SbiIndex


def read_header(fs: FileSystemWrapper, path: str) -> Tuple[SamHeader, int]:
    """Host-side header read; returns (header, virtual offset of the first
    record) — the analogue of ``AbstractSamSource#getFileHeader``."""
    with fs.open(path) as raw:
        r = BgzfReader(raw)
        header = SamHeader.from_bam_stream(r)
        return header, r.tell_virtual()


class BamSource:
    def __init__(self, storage=None):
        self._storage = storage
        self._last_counters = []

    @property
    def split_size(self) -> int:
        return getattr(self._storage, "_split_size", 128 * 1024 * 1024)

    # -- public -------------------------------------------------------------

    def get_reads(self, path: str, traversal=None):
        from disq_tpu.api import ReadsDataset
        from disq_tpu.runtime import (
            check_read_batch,
            debug_enabled,
            trace_phase,
        )

        from disq_tpu.runtime.errors import context_for_storage

        fs, path = resolve_path(path)
        ctx = context_for_storage(self._storage, path)
        with trace_phase("bam.read.header"):
            header, first_voffset = ctx.retrier.call(
                read_header, fs, path, what="header")
        if traversal is not None:
            from disq_tpu.runtime.columnar import concat_batches
            from disq_tpu.traversal.bai_query import read_with_traversal

            # Index-driven reads run their chunk runs through the shard
            # executor like splits: transient faults retry per shard,
            # and the kept shards concatenate as a whole-file read's do
            # (device-backed when every one of them is).
            with trace_phase("bam.read.traversal"):
                batch = concat_batches(read_with_traversal(
                    fs, path, header, traversal, self, ctx))
            return ReadsDataset(header=header, reads=batch,
                                counters=self._reduced_counters(ctx))
        with trace_phase("bam.read.splits"):
            from disq_tpu.runtime.columnar import concat_batches

            batches = self.read_split_batches(
                fs, path, header, first_voffset, ctx=ctx)
            # all-resident shards concatenate ON DEVICE and the dataset
            # stays a device-backed ColumnarBatch (lazy d2h per column);
            # any host shard (salvage paths, disabled knob) materializes
            # the whole read host-side exactly as before
            batch = concat_batches(batches)
        if debug_enabled():
            check_read_batch(batch, n_ref=header.n_ref)
        return ReadsDataset(header=header, reads=batch,
                            counters=self._reduced_counters(ctx))

    def _reduced_counters(self, ctx):
        from disq_tpu.runtime import reduce_counters

        counters = reduce_counters(self._last_counters)
        # Header/boundary-phase retries happened outside any shard.
        counters.retried_reads += ctx.retrier.retried
        counters.skipped_blocks += ctx.skipped_blocks
        counters.quarantined_blocks += ctx.quarantined_blocks
        return counters

    # -- split machinery ----------------------------------------------------

    def read_split_batches(
        self,
        fs: FileSystemWrapper,
        path: str,
        header: SamHeader,
        first_voffset: int,
        split_size: Optional[int] = None,
        ctx=None,
    ) -> List[ReadBatch]:
        """One columnar batch per split — the unit that maps 1:1 onto
        device shards in the distributed pipeline. ``ctx`` (a
        ``ShardErrorContext``) carries the error policy; each shard gets
        its own retrier + corrupt-block counters via ``ctx.for_shard``.

        Splits run through the shard-pipeline executor
        (``runtime/executor.py``): stage A range-reads + walks the
        split's compressed blocks, stage B inflates and decodes
        records, stage C emits batches in split order — so with
        ``DisqOptions.executor_workers > 1`` the I/O of split i+1
        overlaps the inflate of split i while output stays
        byte-identical to the sequential path."""
        import functools

        from disq_tpu.runtime.errors import context_for_storage
        from disq_tpu.runtime.executor import read_ledger_for_storage

        if ctx is None:
            ctx = context_for_storage(self._storage, path)
        splits = compute_path_splits(fs, path, split_size or self.split_size)
        sbi = ctx.retrier.call(self._try_load_sbi, fs, path, what="sbi")
        boundaries = self._split_boundaries(
            fs, path, header, first_voffset, splits, sbi, ctx=ctx
        )
        tasks = []
        shard_ctxs = []
        for i in range(len(splits)):
            lo, hi = boundaries[i], boundaries[i + 1]
            shard_ctx = ctx.for_shard(i)
            shard_ctxs.append(shard_ctx)
            tasks.append(self._shard_task(
                shard_ctx,
                functools.partial(
                    self._fetch_range, fs, path, lo, hi, shard_ctx),
                functools.partial(
                    self._decode_fetched, header, ctx=shard_ctx),
                # Compressed byte window (coffsets) — the scheduler's
                # locality coordinate.
                (lo >> 16, (hi >> 16) + 1)))
        return self._run_shard_tasks(
            fs, path, tasks, shard_ctxs,
            read_ledger_for_storage(self._storage, path, len(tasks)))

    def read_chunk_runs(self, fs, path, header, runs, keep, ctx) -> list:
        """One batch per run of virtual-offset chunks — an indexed
        read's shards, through the same executor stages as splits:
        ``runs`` holds ``((k, 2)`` chunk array, tag) pairs in file
        order, and ``keep(batch, tag)`` is applied to each decoded run
        inside its decode stage, so that what it drops is gone before
        the shard is emitted.  The blocks of a run's chunks are one
        batch to the inflater."""
        import functools

        def decode(shard_ctx, tag, fetched):
            batch, stats = self._decode_fetched(
                header, fetched, ctx=shard_ctx)
            return keep(batch, tag), stats

        tasks = []
        shard_ctxs = []
        for i, (chunks, tag) in enumerate(runs):
            shard_ctx = ctx.for_shard(i)
            shard_ctxs.append(shard_ctx)
            tasks.append(self._shard_task(
                shard_ctx,
                functools.partial(
                    self._fetch_chunks, fs, path, chunks, shard_ctx),
                functools.partial(decode, shard_ctx, tag),
                (int(chunks[0, 0]) >> 16, (int(chunks[-1, 1]) >> 16) + 1)))
        return self._run_shard_tasks(fs, path, tasks, shard_ctxs, None)

    def _shard_task(self, shard_ctx, fetch, decode, byte_range):
        from disq_tpu.runtime import ShardTask
        from disq_tpu.runtime.errors import DisqOptions, deadline_fallback_for

        opts = getattr(self._storage, "_options", None) or DisqOptions()
        return ShardTask(
            shard_id=shard_ctx.shard_id,
            fetch=fetch,
            decode=decode,
            retrier=shard_ctx.retrier,
            what=f"shard{shard_ctx.shard_id}",
            # Deadline escalation terminal under skip/quarantine:
            # an over-budget shard is set aside as one empty batch.
            deadline_fallback=deadline_fallback_for(
                opts, shard_ctx,
                lambda: (ReadBatch.empty(), (0, 0, 0))),
            byte_range=byte_range,
        )

    def _run_shard_tasks(self, fs, path, tasks, shard_ctxs, ledger) -> list:
        """The tasks through the shard executor; the emitted batches in
        shard order, their counters on ``_last_counters``."""
        from disq_tpu.runtime import ShardCounters
        from disq_tpu.runtime.executor import executor_for_storage
        from disq_tpu.runtime.introspect import note_shard_counters
        from disq_tpu.runtime.scheduler import scheduled_map_ordered

        out = []
        self._last_counters = []
        # scheduler off (default): scheduled_map_ordered IS
        # map_ordered_resumable; on: this process leases shards from
        # the shared cross-host queue and emits only the ones it wins.
        for res in scheduled_map_ordered(
                self._storage, fs, path,
                executor_for_storage(self._storage), tasks, ledger):
            batch, stats = res.value
            shard_ctx = shard_ctxs[res.shard_id]
            c = ShardCounters(
                shard_id=res.shard_id,
                records=batch.count,
                blocks=stats[0],
                bytes_compressed=stats[1],
                bytes_uncompressed=stats[2],
                wall_seconds=res.wall_seconds,
                skipped_blocks=shard_ctx.skipped_blocks,
                quarantined_blocks=shard_ctx.quarantined_blocks,
                retried_reads=shard_ctx.retrier.retried,
            )
            self._last_counters.append(c)
            note_shard_counters("read", c)  # live /progress feed
            out.append(batch)
        return out

    def _try_load_sbi(self, fs: FileSystemWrapper, path: str) -> Optional[SbiIndex]:
        sbi_path = path + ".sbi"
        if fs.exists(sbi_path):
            return SbiIndex.from_bytes(fs.read_all(sbi_path))
        return None

    def _data_end_voffset(self, fs: FileSystemWrapper, path: str) -> int:
        """Virtual offset one past the last record: EOF minus terminator."""
        length = fs.get_file_length(path)
        tail = fs.read_range(path, max(0, length - len(BGZF_EOF_MARKER)), len(BGZF_EOF_MARKER))
        end = length - len(BGZF_EOF_MARKER) if tail == BGZF_EOF_MARKER else length
        return make_virtual_offset(end, 0)

    def _split_boundaries(
        self,
        fs: FileSystemWrapper,
        path: str,
        header: SamHeader,
        first_voffset: int,
        splits: List[PathSplit],
        sbi: Optional[SbiIndex],
        ctx=None,
    ) -> List[int]:
        """Virtual offsets b[0..n]: split i decodes records in
        [b[i], b[i+1]). b[0] = first record (from the header read);
        b[n] = end of data.

        Transient-fault retry is *per boundary* (each boundary guess is
        a handful of reads), not around the whole phase — a whole-phase
        retry would re-execute every read and never converge under a
        sustained fault rate."""
        def _call(fn, *args, what):
            if ctx is None:
                return fn(*args)
            return ctx.retrier.call(fn, *args, what=what)

        from disq_tpu.runtime.tracing import span

        end_vo = _call(self._data_end_voffset, fs, path, what="data_end")
        bounds = [first_voffset]
        for shard, s in enumerate(splits[1:], 1):
            if sbi is not None:
                vo = sbi.first_offset_at_or_after(s.start)
            else:
                # ``window_bytes``: how far the search window grew (a
                # record longer than the first window makes it grow)
                with span("bam.split.guess", shard=shard) as grew:
                    vo = _call(self._guess_record_voffset, fs, path,
                               header, s.start, ctx, grew, what="boundary")
                if vo is None:
                    vo = end_vo
            bounds.append(max(min(vo, end_vo), bounds[-1]))
        bounds.append(end_vo)
        return bounds

    def _guess_record_voffset(
        self,
        fs: FileSystemWrapper,
        path: str,
        header: SamHeader,
        file_offset: int,
        ctx=None,
        grew: Optional[dict] = None,
    ) -> Optional[int]:
        """First record boundary at-or-after ``file_offset`` (SURVEY §3.1:
        BgzfBlockGuesser → BamRecordGuesser over a decompressed window).
        ``grew["window_bytes"]`` is left at the compressed window the
        search ended with.

        Under a skip/quarantine ``ctx``, a corrupt block inside the
        search window is stepped over *silently* (per good-block run) —
        the shard that owns the block does the counting/quarantining
        when it decodes; counting here would double-book it."""
        from disq_tpu.runtime.errors import TruncatedReadError

        if file_offset == 0:
            raise ValueError("offset 0 is resolved by the header read")
        bg = BgzfBlockGuesser(fs, path)
        block_start = bg.guess_block_start(file_offset)
        if block_start is None:
            return None
        g = BamRecordGuesser(header.n_ref, [s.length for s in header.sequences])
        file_length = fs.get_file_length(path)
        # Decompress a window and search; a single huge record (long-read
        # BAMs) can exceed any fixed window, so grow geometrically until a
        # boundary is found or the window reaches EOF.
        window_csize = 4 * 0x10000
        while True:
            if grew is not None:
                grew["window_bytes"] = window_csize
            try:
                window_blocks, data = _walk_blocks_collect(
                    fs, path, block_start, block_start + window_csize,
                    file_length,
                )
            except TruncatedReadError:
                raise  # short range read: retried by the phase retrier
            except ValueError:
                if ctx is None:
                    raise
                # Malformed block header in the window: salvage-walk it
                # (silently — the owning shard books the corruption;
                # STRICT still raises with coordinates) and search each
                # good run.
                from disq_tpu.runtime.errors import inflate_blocks_salvage

                window_blocks, data, gaps = walk_blocks_salvage(
                    fs, path, block_start, block_start + window_csize,
                    file_length, ctx, owned_until=block_start,
                )
                if not window_blocks:
                    return None
                payloads = inflate_blocks_salvage(
                    data, window_blocks, block_start, ctx.silent())
                u_vo = self._search_payload_runs(g, window_blocks, payloads)
                if u_vo is not None:
                    return u_vo
                if window_blocks[-1].end >= file_length or (
                        gaps and gaps[-1][1] >= file_length):
                    return None
                window_csize *= 4
                continue
            if not window_blocks:
                return None
            try:
                window = inflate_blocks(
                    data, window_blocks, base=block_start, as_array=True
                )
            except ValueError as e:
                u_vo = self._guess_around_corruption(
                    path, g, window_blocks, data, block_start, ctx, e
                )
                if u_vo is not None:
                    return u_vo
                u = None
            else:
                u = g.find_first_record(window)
            at_eof = window_blocks[-1].end >= file_length
            if u is not None:
                # Map window offset u back to a (block, within) voffset
                # using the block usize table (ISIZE is verified on
                # inflate, so cumulative usize == window offsets).
                acc = 0
                for b in window_blocks:
                    if u < acc + b.usize:
                        return make_virtual_offset(b.pos, u - acc)
                    acc += b.usize
                return None
            if at_eof:
                return None
            window_csize *= 4

    def _guess_around_corruption(
        self, path, g, window_blocks, data, base, ctx, err
    ) -> Optional[int]:
        """Boundary search when the window holds a corrupt block: under
        STRICT (or no ctx) apply the policy — which raises with the
        block's coordinates; otherwise search each good run and return a
        virtual offset directly."""
        from disq_tpu.runtime.errors import (
            ErrorPolicy,
            ShardErrorContext,
            inflate_blocks_salvage,
        )

        if ctx is None:
            silent = ShardErrorContext(policy=ErrorPolicy.STRICT, path=path)
        else:
            silent = ctx.silent()
        payloads = inflate_blocks_salvage(data, window_blocks, base, silent)
        if all(p is not None for p in payloads):
            raise err  # batch inflate bug, not corruption — surface it
        return self._search_payload_runs(g, window_blocks, payloads)

    def _search_payload_runs(self, g, blocks, payloads) -> Optional[int]:
        """First record boundary across the contiguous good runs of a
        salvaged window: each run is searched independently (never
        spliced across a corrupt hole, which could chain-validate a
        false boundary)."""
        n = len(blocks)
        i = 0
        while i < n:
            if payloads[i] is None:
                i += 1
                continue
            j = i
            while j + 1 < n and payloads[j + 1] is not None:
                j += 1
            blob = np.frombuffer(
                b"".join(payloads[i: j + 1]), dtype=np.uint8)
            u = g.find_first_record(blob)
            if u is not None:
                acc = 0
                for k in range(i, j + 1):
                    if u < acc + len(payloads[k]):
                        return make_virtual_offset(
                            blocks[k].pos, u - acc)
                    acc += len(payloads[k])
            i = j + 1
        return None

    def _fetch_range(
        self,
        fs: FileSystemWrapper,
        path: str,
        lo_voffset: int,
        hi_voffset: int,
        ctx,
    ) -> Optional[Tuple]:
        """``_fetch_range_inner`` under a per-split ``bam.split.fetch``
        span carrying the shard id and virtual-offset range — one
        timeline event per split fetch, replayable by
        ``scripts/trace_report.py``."""
        from disq_tpu.runtime.tracing import span

        with span("bam.split.fetch", shard=ctx.shard_id,
                  lo=lo_voffset, hi=hi_voffset, path=path):
            return self._fetch_range_inner(
                fs, path, lo_voffset, hi_voffset, ctx)

    def _fetch_chunks(self, fs: FileSystemWrapper, path: str,
                      chunks: np.ndarray, ctx) -> list:
        """Stage A of a run of virtual-offset chunks (an indexed read's
        shard): each chunk's blocks range-read and walked on their own,
        so that the blocks between two chunks are neither read nor
        decoded.  One ``bam.split.fetch`` span a run."""
        from disq_tpu.runtime.tracing import span

        with span("bam.split.fetch", shard=ctx.shard_id,
                  lo=int(chunks[0, 0]), hi=int(chunks[-1, 1]), path=path,
                  chunks=len(chunks)):
            fetched = []
            skipped = quarantined = 0
            for lo, hi in chunks.tolist():
                fetched.append(
                    self._fetch_range_inner(fs, path, lo, hi, ctx))
                # each walk starts its counts anew: the run's are the sum
                skipped += ctx.skipped_blocks
                quarantined += ctx.quarantined_blocks
            ctx.skipped_blocks, ctx.quarantined_blocks = skipped, quarantined
            return fetched

    def _fetch_range_inner(
        self,
        fs: FileSystemWrapper,
        path: str,
        lo_voffset: int,
        hi_voffset: int,
        ctx,
    ) -> Optional[Tuple]:
        """Stage A: range-read and walk the compressed blocks covering
        [lo, hi) virtual space — from lo's block through hi's block,
        i.e. past the split's byte-range end when a record straddles it.
        Returns the staged payload for ``_decode_fetched`` (None for an
        empty range).

        ``ctx`` (``ShardErrorContext``) governs corrupt block *headers*
        found by the salvage walk; a retried attempt resets the
        corrupt-block counters here so the previous attempt's blocks
        are never double-counted (quarantine sidecar writes are
        idempotent)."""
        from disq_tpu.runtime.errors import TruncatedReadError

        ctx.skipped_blocks = 0
        ctx.quarantined_blocks = 0
        if hi_voffset <= lo_voffset:
            return None
        lo_block = lo_voffset >> 16
        hi_block, hi_u = hi_voffset >> 16, hi_voffset & 0xFFFF
        length = fs.get_file_length(path)
        # Walk blocks from lo_block through hi_block (inclusive iff hi_u>0);
        # the walk stages the compressed bytes so inflation re-uses them.
        want_end = hi_block + (1 if hi_u > 0 else 0)
        gaps = []
        try:
            blocks, data = _walk_blocks_collect(
                fs, path, lo_block, max(want_end, lo_block + 1), length
            )
        except TruncatedReadError:
            raise  # short range read: the shard retrier re-reads
        except ValueError:
            # A corrupt block HEADER breaks the BSIZE chain itself:
            # re-walk one block at a time, policy-handling each corrupt
            # span and re-syncing with the block guesser.
            blocks, data, gaps = walk_blocks_salvage(
                fs, path, lo_block, max(want_end, lo_block + 1), length,
                ctx, owned_until=hi_block,
            )
        return blocks, data, gaps, lo_voffset, hi_voffset

    def _decode_fetched(
        self,
        header: SamHeader,
        fetched: Optional[Tuple],
        ctx,
    ) -> Tuple[ReadBatch, Tuple[int, int, int]]:
        """``_decode_fetched_inner`` under a per-split
        ``bam.split.decode`` span carrying the shard id.

        A configured read filter (``DisqOptions.read_filter`` /
        ``DISQ_TPU_READ_FILTER``) applies HERE — inside the decode
        stage, per shard, before any d2h or host materialization —
        covering the resident, host, and salvage inner paths alike
        (and the BAI traversal route, which decodes through this
        method too)."""
        from disq_tpu.runtime.tracing import span

        with span("bam.split.decode", shard=ctx.shard_id):
            inner = (self._decode_chunks if isinstance(fetched, list)
                     else self._decode_fetched_inner)
            batch, stats = inner(header, fetched, ctx)
            rf = self._read_filter()
            if rf is not None and batch.count:
                from disq_tpu.ops.rfilter import apply_read_filter

                batch = apply_read_filter(batch, rf)
            return batch, stats

    def _read_filter(self):
        """The storage's parsed ``ReadFilter``, or None — the operator
        module is only imported once a spec is actually set (the
        suite-off zero-work guard)."""
        import os

        opts = getattr(self._storage, "_options", None)
        spec = getattr(opts, "read_filter", None) if opts else None
        if spec is None:
            spec = os.environ.get("DISQ_TPU_READ_FILTER") or None
        if not spec:
            return None
        from disq_tpu.ops.rfilter import parse_read_filter

        return parse_read_filter(spec)

    def _decode_fetched_inner(
        self,
        header: SamHeader,
        fetched: Optional[Tuple],
        ctx,
    ) -> Tuple[ReadBatch, Tuple[int, int, int]]:
        """Stage B: inflate + record-decode a staged range.

        Returns (batch, (blocks, compressed bytes, uncompressed bytes))
        where the stats count only blocks *owned* by this range —
        ``pos ∈ [lo_block, hi_block)`` — so a block straddling a split
        boundary is attributed to exactly one side and reduced totals
        match the file.

        ``ctx`` (``ShardErrorContext``) governs corrupt blocks: the
        fault-free fast path is the one batched inflate below; only when
        it fails does the per-block salvage path run, applying the
        policy (strict raise with coordinates / skip / quarantine).

        With resident decode on (``DisqOptions.resident_decode`` /
        ``DISQ_TPU_RESIDENT_DECODE``) the fault-free fast path parses
        the shard into a device-backed ``ColumnarBatch`` in the same
        launch chain as the device codecs — when the SIMD inflate
        kernel decoded the blocks, its still-HBM-resident output is
        parsed in place (no re-upload), or, decoded through the decode
        service, the service's padded host buffer is uploaded as it is
        (no staging copy).  Every salvage/tolerant path
        stays host-side, so error semantics (and owner-shard
        quarantine accounting) are identical.
        """
        from disq_tpu.runtime.columnar import resident_decode_enabled
        from disq_tpu.runtime.errors import inflate_blocks_salvage

        if fetched is None:
            return ReadBatch.empty(), (0, 0, 0)
        blocks, data, gaps, lo_voffset, hi_voffset = fetched
        lo_block, lo_u = lo_voffset >> 16, lo_voffset & 0xFFFF
        hi_block, hi_u = hi_voffset >> 16, hi_voffset & 0xFFFF
        if not blocks:
            return ReadBatch.empty(), (0, 0, 0)
        # Consecutive split ranges partition [first_block, data_end) in
        # block space, so this never under/over-counts across a whole read
        # (a sub-block range owns nothing: its block belongs to whichever
        # range starts at or before the block's start).
        owned = [b for b in blocks if b.pos < hi_block]
        stats = (
            len(owned),
            sum(b.csize for b in owned),
            sum(b.usize for b in owned),
        )
        if gaps:
            # Corrupt-header spans already handled by the salvage walk:
            # inflate per block and splice None sentinels at each gap so
            # record runs break there (a record straddling INTO a gap
            # must not concatenate across it).
            payloads = inflate_blocks_salvage(
                data, blocks, lo_block, ctx, owned_until=hi_block
            )
            merged = sorted(
                list(zip(blocks, payloads))
                + [(BgzfBlock(pos=lo, csize=hi - lo, usize=0), None)
                   for lo, hi in gaps],
                key=lambda bp: bp[0].pos,
            )
            batch = self._decode_runs(
                header, [b for b, _ in merged], [p for _, p in merged],
                lo_u, hi_block, hi_u, ctx=ctx,
            )
            return batch, stats
        resident = resident_decode_enabled(self._storage)
        # the device parse indexes with i32: a (pathological) >=2 GiB
        # decoded shard silently demotes to the host path instead of
        # tripping the corruption handler on valid data
        if resident and sum(b.usize for b in blocks) >= 2 ** 31:
            resident = False
        dev_handle = staged = None
        try:
            if resident:
                blob, dev_handle = inflate_blocks(
                    data, blocks, base=lo_block, as_array=True,
                    keep_device=True)
                if isinstance(dev_handle, np.ndarray):
                    # the service route's slot holds the padded host
                    # buffer the blob heads, not a device handle
                    staged, dev_handle = dev_handle, None
            else:
                blob = inflate_blocks(
                    data, blocks, base=lo_block, as_array=True)
        except ValueError as first_err:
            # At least one block is corrupt: per-block salvage under the
            # policy (STRICT raises CorruptBlockError with coordinates).
            payloads = inflate_blocks_salvage(
                data, blocks, lo_block, ctx, owned_until=hi_block
            )
            if all(p is not None for p in payloads):
                # The batch inflate failed but every block decodes alone:
                # a codec-path bug, not data corruption — surface it.
                raise first_err
            batch = self._decode_runs(
                header, blocks, payloads, lo_u, hi_block, hi_u, ctx=ctx
            )
            return batch, stats
        if hi_u > 0:
            acc_before_hi = sum(b.usize for b in blocks if b.pos < hi_block)
            end_u = acc_before_hi + hi_u
        else:
            end_u = len(blob)
        record_bytes = blob[lo_u:end_u]
        try:
            offsets = scan_record_offsets(record_bytes)
            if resident:
                from disq_tpu.runtime.columnar import ColumnarBatch

                words = (dev_handle.assemble()
                         if dev_handle is not None else None)
                dev_handle = None
                # mesh-native build (runtime/mesh.py): with the knob
                # armed the parse shards over the batch axis and the
                # batch carries its mesh so sort/flagstat/depth stay
                # one sharded program; mesh_for_storage is two
                # attribute reads when off
                from disq_tpu.runtime.mesh import mesh_for_storage

                batch = ColumnarBatch.from_blob(
                    record_bytes, offsets, n_ref=header.n_ref,
                    device_words=words, origin=lo_u, staged=staged,
                    mesh=mesh_for_storage(self._storage))
            else:
                batch = decode_records(
                    record_bytes, offsets, n_ref=header.n_ref)
        except ValueError as e:
            if dev_handle is not None:
                dev_handle.release()
                dev_handle = None
            # Record framing/content damage inside intact BGZF blocks
            # (corruption that predates compression, so no single block
            # is identifiable): STRICT raises with the shard's
            # coordinates; skip/quarantine keep the clean prefix found
            # by the tolerant scan.
            ctx.handle_corrupt_block(
                e, block_offset=lo_block, virtual_offset=lo_voffset,
                kind="record run",
            )
            try:
                offsets = scan_record_offsets_tolerant(record_bytes)
                batch = decode_records(
                    record_bytes, offsets, n_ref=header.n_ref)
            except ValueError:
                batch = ReadBatch.empty()
        return batch, stats

    def _decode_chunks(
        self,
        header: SamHeader,
        fetched: list,
        ctx,
    ) -> Tuple[ReadBatch, Tuple[int, int, int]]:
        """Stage B of a run of chunks (``_fetch_chunks``): every block
        of every chunk in ONE batch to the inflater — with the decode
        service on, one submission, so that blocks of several chunks
        share launches — then each chunk's records cut out at its
        virtual offsets and all of them parsed as one batch.  The stats
        count every block: chunks share none (a chunk that begins in
        the block another ends in was merged into it by the index
        query).

        Anything out of the ordinary (a corrupt-header gap from the
        salvage walk, a block that fails to inflate, damaged record
        framing) goes chunk by chunk through ``_decode_fetched_inner``,
        which applies the error policy with the block's coordinates."""
        from disq_tpu.runtime.columnar import (
            concat_batches, resident_decode_enabled)

        parts = [f for f in fetched if f is not None and f[0]]
        every = [b for f in parts for b in f[0]]
        stats = (len(every), sum(b.csize for b in every),
                 sum(b.usize for b in every))

        def apart():
            return concat_batches([
                self._decode_fetched_inner(header, f, ctx)[0]
                for f in parts]), stats

        if not parts:
            return ReadBatch.empty(), stats
        if any(f[2] for f in parts):
            return apart()
        resident = (resident_decode_enabled(self._storage)
                    and stats[2] < 2 ** 31)
        # the chunks' compressed bytes end to end, their blocks rebased
        # onto the joined buffer
        joined, at = [], 0
        for blocks, data, _gaps, lo, _hi in parts:
            joined += [BgzfBlock(pos=b.pos - (lo >> 16) + at,
                                 csize=b.csize, usize=b.usize)
                       for b in blocks]
            at += len(data)
        try:
            blob = inflate_blocks(
                b"".join(f[1] for f in parts), joined, base=0,
                as_array=True)
            segments, offsets, at, total = [], [], 0, 0
            for blocks, _data, _gaps, lo, hi in parts:
                hi_block, hi_u = hi >> 16, hi & 0xFFFF
                size = sum(b.usize for b in blocks)
                end_u = size if hi_u == 0 else hi_u + sum(
                    b.usize for b in blocks if b.pos < hi_block)
                seg = blob[at + (lo & 0xFFFF): at + end_u]
                at += size
                if len(seg):
                    offsets.append(scan_record_offsets(seg)[:-1] + total)
                    segments.append(seg)
                    total += len(seg)
            offsets.append(np.array([total], np.int64))
            offsets = np.concatenate(offsets)
            record_bytes = (np.concatenate(segments) if segments
                            else blob[:0])
            if resident:
                from disq_tpu.runtime.columnar import ColumnarBatch
                from disq_tpu.runtime.mesh import mesh_for_storage

                # a run's decoded size follows where its chunks' blocks
                # fall: coarse upload shapes, or every run compiles
                return ColumnarBatch.from_blob(
                    record_bytes, offsets, n_ref=header.n_ref,
                    mesh=mesh_for_storage(self._storage),
                    coarse=True), stats
            return decode_records(
                record_bytes, offsets, n_ref=header.n_ref), stats
        except ValueError:
            return apart()

    def _decode_runs(
        self,
        header: SamHeader,
        blocks,
        payloads,
        lo_u: int,
        hi_block: int,
        hi_u: int,
        ctx=None,
    ) -> ReadBatch:
        """Decode the contiguous runs of good blocks around skipped
        corrupt ones. A record straddling INTO a corrupt block is
        dropped (its tail bytes are gone); after a gap, the first record
        boundary is re-found with the ``BamRecordGuesser`` — exactly the
        machinery that already resolves split starts. ``ctx`` governs
        record-framing damage *inside* a good run (or a false post-gap
        re-sync): without it the strict scan raises as before."""
        guesser = BamRecordGuesser(
            header.n_ref, [s.length for s in header.sequences]
        )
        batches: List[ReadBatch] = []
        n = len(blocks)
        i = 0
        while i < n:
            if payloads[i] is None:
                i += 1
                continue
            j = i
            while j + 1 < n and payloads[j + 1] is not None:
                j += 1
            run_blocks = blocks[i: j + 1]
            run_payloads = payloads[i: j + 1]
            blob = np.frombuffer(b"".join(run_payloads), dtype=np.uint8)
            start_u = lo_u if i == 0 else 0
            if hi_u > 0 and any(b.pos == hi_block for b in run_blocks):
                end_u = (
                    sum(len(p) for b, p in zip(run_blocks, run_payloads)
                        if b.pos < hi_block)
                    + hi_u
                )
            else:
                end_u = len(blob)
            seg = blob[start_u:end_u]
            after_gap = i > 0 and payloads[i - 1] is None
            ends_at_gap = j + 1 < n  # next block was skipped
            if after_gap and len(seg):
                first = guesser.find_first_record(seg)
                if first is None:
                    i = j + 1
                    continue
                seg = seg[first:]
            if len(seg) == 0:
                i = j + 1
                continue
            try:
                offsets = (
                    scan_record_offsets_tolerant(seg)
                    if ends_at_gap
                    else scan_record_offsets(seg)
                )
                batches.append(
                    decode_records(seg, offsets, n_ref=header.n_ref))
            except ValueError as e:
                if ctx is None:
                    raise
                ctx.handle_corrupt_block(
                    e, block_offset=int(run_blocks[0].pos),
                    virtual_offset=make_virtual_offset(
                        int(run_blocks[0].pos), 0),
                    kind="record run",
                )
                try:
                    batches.append(decode_records(
                        seg, scan_record_offsets_tolerant(seg),
                        n_ref=header.n_ref))
                except ValueError:
                    pass  # keep the other runs
            i = j + 1
        if not batches:
            return ReadBatch.empty()
        return ReadBatch.concat(batches)
