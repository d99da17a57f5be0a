"""Indexed (interval) traversal — the BAI query read path.

Reference parity: the traversal branch of ``BamSource`` (SURVEY.md §3.2):
resolve ``path + ".bai"``, map intervals → chunk lists of virtual-offset
pairs (coalesced), decode only those chunks, then apply an exact
per-record overlap filter; unplaced-unmapped records are read from a
dedicated tail chunk after the last mapped chunk when
``traverse_unplaced_unmapped`` is set.

Key invariant kept from the reference: chunk bounds are *virtual
offsets*, so decode never sees a partial record.

The shape of a read (``read_with_traversal``): one plan
(``plan_traversal``: the intervals sorted and merged per contig into an
``IntervalTable``, the chunks of all of them from the index in array
work, consecutive chunks grouped into shard tasks of about a launch's
lanes an executor worker), then the executor's fetch → decode stages as
a whole-file read builds them, one task a chunk run, so that the fetch
of run i+1 overlaps the inflate of run i and the blocks of several
chunks share launches.  Each shard is held to the table as it is decoded
(``IntervalTable.keep``): a sorted-table search a record, on the device
for a resident batch (``jit_interval_overlap``), then compacted, so that
what the intervals drop never crosses d2h.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from typing import List, Optional, Tuple

import numpy as np

from disq_tpu.bam.columnar import ReadBatch
from disq_tpu.bam.header import SamHeader
from disq_tpu.fsw.filesystem import FileSystemWrapper
from disq_tpu.index.bai import BaiIndex, coalesce_chunks
from disq_tpu.util import bucket_pow2

def _resolve_bai(fs: FileSystemWrapper, path: str) -> BaiIndex:
    for cand in (path + ".bai", path[:-4] + ".bai" if path.endswith(".bam") else None):
        if cand and fs.exists(cand):
            return BaiIndex.from_bytes(fs.read_all(cand))
    raise FileNotFoundError(f"no .bai index found for {path}")


@functools.lru_cache(maxsize=1)
def _overlap_program():
    """The device program of the overlap test (built on first use: this
    module imports without jax)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def interval_overlap(refid, pos, ends, first, t_start, t_end):
        # within the record's contig (``first``: where each contig's
        # intervals begin in the table), the first interval that ends
        # after the record's start: the only one that can hold it,
        # since merged intervals do not overlap and their ends ascend
        size = t_end.shape[0]
        ref = jnp.clip(refid, 0, first.shape[0] - 2)
        stop = jnp.where(refid == ref, first[ref + 1], 0)

        def step(_, bounds):
            lo, hi = bounds
            mid = (lo + hi) >> 1
            after = t_end[jnp.minimum(mid, size - 1)] > pos
            open_ = lo < hi
            return (jnp.where(open_ & ~after, mid + 1, lo),
                    jnp.where(open_ & after, mid, hi))

        lo, _ = jax.lax.fori_loop(
            0, size.bit_length() + 1, step,
            (jnp.minimum(first[ref], stop), stop))
        return (lo < stop) & (t_start[jnp.minimum(lo, size - 1)] < ends)

    return interval_overlap


@dataclasses.dataclass(frozen=True)
class IntervalTable:
    """The queried intervals of all contigs as one table: 0-based
    half-open, sorted by (reference id, start), overlapping and abutting
    ones merged, so that ends ascend with starts and a record overlaps
    at most the first interval that ends after its start."""

    refid: np.ndarray    # (t,) i32
    start0: np.ndarray   # (t,) i32
    end0: np.ndarray     # (t,) i32

    @classmethod
    def build(cls, header: SamHeader, intervals) -> "IntervalTable":
        index = {}
        refid = np.empty(len(intervals), np.int64)
        beg = np.empty(len(intervals), np.int64)
        end = np.empty(len(intervals), np.int64)
        for i, iv in enumerate(intervals):
            r = index.get(iv.contig)
            if r is None:
                r = index[iv.contig] = header.ref_index(iv.contig)
            # 1-based closed interval → 0-based half-open
            refid[i], beg[i], end[i] = r, iv.start - 1, iv.end
        held = end > beg
        order = np.lexsort((beg[held], refid[held]))
        refid, beg, end = (a[held][order] for a in (refid, beg, end))
        # one key a record end: the running maximum stays inside a
        # contig because reference ids ascend
        reach = np.maximum.accumulate((refid << 32) | end)
        first = np.ones(len(beg), bool)
        first[1:] = ((refid[1:] << 32) | beg[1:]) > reach[:-1]
        starts = np.flatnonzero(first)
        last = (np.append(starts[1:], len(beg)) - 1)[: len(starts)]
        return cls(refid[starts].astype(np.int32),
                   beg[starts].astype(np.int32),
                   (reach[last] & 0xFFFFFFFF).astype(np.int32))

    def __len__(self) -> int:
        return len(self.refid)

    def by_contig(self):
        """``(reference id, starts, ends)`` of each contig that has
        intervals."""
        cuts = np.flatnonzero(np.diff(self.refid)) + 1
        for lo, hi in zip(np.append(0, cuts), np.append(cuts, len(self))):
            if hi > lo:
                yield int(self.refid[lo]), self.start0[lo:hi], self.end0[lo:hi]

    def mask(self, refid: np.ndarray, pos: np.ndarray,
             ends: np.ndarray) -> np.ndarray:
        """Record-overlaps-any-interval over host columns: ``start0 <
        end`` and ``end0 > pos`` for some interval of the record's
        contig (``ends`` are ``alignment_ends()``: exclusive, a record
        whose CIGAR consumes no reference one base long)."""
        if len(self) == 0 or len(refid) == 0:
            return np.zeros(len(refid), bool)
        rid = np.asarray(refid, np.int64)
        at = np.searchsorted(
            (self.refid.astype(np.int64) << 32) | self.end0,
            (rid << 32) | np.asarray(pos, np.int64), "right")
        hit = np.minimum(at, len(self) - 1)
        return ((at < len(self)) & (rid >= 0) & (self.refid[hit] == rid)
                & (self.start0[hit] < np.asarray(ends)))

    def _device_table(self):
        """The table on the device: where each contig's intervals begin
        (one entry a reference id up to the last that has any, and the
        table's length after them), the starts and the ends, each
        padded to a power of two; uploaded once a table."""
        with _TABLE_LOCK:
            held = self.__dict__.get("_dev")
            if held is None:
                import jax.numpy as jnp

                from disq_tpu.runtime.tracing import count_transfer

                first = np.searchsorted(
                    self.refid, np.arange(int(self.refid[-1]) + 2)
                ).astype(np.int32)
                cols = [np.pad(c, (0, bucket_pow2(len(c)) - len(c)),
                               mode="edge")
                        for c in (first, self.start0, self.end0)]
                count_transfer("h2d", sum(c.nbytes for c in cols))
                held = tuple(jnp.asarray(c) for c in cols)
                object.__setattr__(self, "_dev", held)
            return held

    def device_mask(self, batch) -> np.ndarray:
        """``mask`` of a resident batch, on its device columns: the
        ends go up (4 B a record), the mask comes back (1 B)."""
        import jax

        from disq_tpu.runtime.tracing import count_transfer, device_span

        refid, pos, ends = batch.interval_operands()
        table = self._device_table()
        with device_span("device.kernel", kernel="interval_overlap",
                         records=batch.count) as fence:
            with jax.transfer_guard("disallow"):
                hit = _overlap_program()(refid, pos, ends, *table)
                jax.block_until_ready(hit)
            fence.sync(hit)
        out = np.asarray(hit)
        count_transfer("d2h", out.nbytes)
        return out[: batch.count]

    def keep(self, batch):
        """The records of a decoded shard that overlap the table, as a
        batch of the kind it was given."""
        from disq_tpu.runtime.tracing import span

        with span("traversal.overlap", records=int(batch.count)) as labels:
            if batch.count == 0:
                labels["kept"] = 0
                return batch
            resident = (getattr(batch, "device_backed", False)
                        and batch.mesh is None and len(self) > 0)
            mask = (self.device_mask(batch) if resident else self.mask(
                batch.refid, batch.pos, batch.alignment_ends()))
            labels["kept"] = int(np.count_nonzero(mask))
        return batch.filter(mask)


_TABLE_LOCK = threading.Lock()


def chunks_for_table(bai: BaiIndex, table: IntervalTable) -> np.ndarray:
    """``(k, 2)`` i64 coalesced virtual-offset chunks, in file order,
    of everything the table's intervals may overlap."""
    found = [bai.chunks_for_ranges(refid, start0, end0)
             for refid, start0, end0 in table.by_contig()]
    found = [c for c in found if len(c)]
    if not found:
        return np.zeros((0, 2), np.int64)
    both = np.concatenate(found)
    return coalesce_chunks(both[:, 0], both[:, 1])


def chunks_for_intervals(
    header: SamHeader, bai: BaiIndex, intervals
) -> List[Tuple[int, int]]:
    """Intervals → coalesced (start, end) virtual-offset chunks."""
    table = IntervalTable.build(header, list(intervals))
    return [(int(b), int(e)) for b, e in chunks_for_table(bai, table)]


def overlap_mask(
    batch: ReadBatch, header: SamHeader, intervals,
    ends: np.ndarray = None,
) -> np.ndarray:
    """Vectorized record-overlaps-any-interval mask (0-based half-open)
    over host columns.

    ``ends`` takes precomputed ``batch.alignment_ends()`` — the cigar
    walk is the dominant cost here, and callers that filter the same
    batch repeatedly (the serving plane's parsed-chunk cache) pay it
    once instead of per query."""
    if batch.count == 0:
        return np.zeros(0, dtype=bool)
    if ends is None:
        ends = batch.alignment_ends()
    return IntervalTable.build(header, list(intervals)).mask(
        batch.refid, batch.pos, ends)


@dataclasses.dataclass(frozen=True)
class TraversalPlan:
    """What one traversal read decodes: the table its records are held
    to (None: no intervals were given), the interval chunks in file
    order, the runs of them that are one shard task each (index pairs
    into ``chunks``), the start of the unplaced tail (None: not asked
    for) and the blocks the plan expects to decode (from the compressed
    extent; the shard counters have the true count)."""

    table: Optional[IntervalTable]
    chunks: np.ndarray
    tasks: List[Tuple[int, int]]
    tail_start: Optional[int]
    blocks: int


def _block_bytes(fs: FileSystemWrapper, path: str, voffset: int) -> int:
    """Compressed size of the BGZF block a virtual offset lies in: the
    plan's measure of a file's blocks.  Where no block header is there
    to read, the largest a block can be (the fetch will say what is
    wrong with the file)."""
    import struct

    from disq_tpu.bgzf.block import BGZF_MAX_BLOCK_SIZE

    head = fs.read_range(path, voffset >> 16, 18)
    if len(head) < 18 or head[:2] != b"\x1f\x8b":
        return BGZF_MAX_BLOCK_SIZE
    return struct.unpack_from("<H", head, 16)[0] + 1


def plan_traversal(
    fs: FileSystemWrapper, path: str, header: SamHeader, traversal,
    workers: int = 1,
) -> TraversalPlan:
    """Resolve the index and plan the read (module docstring): work
    linear in intervals + chunks but for their two sorts."""
    from disq_tpu.runtime.device_service import LANES
    from disq_tpu.runtime.tracing import span

    intervals = traversal.intervals
    with span("traversal.plan",
              intervals=len(intervals or ())) as labels:
        bai = _resolve_bai(fs, path)
        table, chunks = None, np.zeros((0, 2), np.int64)
        if intervals is not None:
            table = IntervalTable.build(header, list(intervals))
            chunks = chunks_for_table(bai, table)
        tasks, blocks = [], 0
        if len(chunks):
            # a task's blocks are one submission to the decode service:
            # at most a launch's lanes for each executor worker (a
            # chunk is never cut), so that a pass's launches are full,
            # its tasks many, and what a full task decodes to has one
            # upper bound and so one shape
            size = _block_bytes(fs, path, int(chunks[0, 0]))
            spans = ((chunks[:, 1] >> 16) - (chunks[:, 0] >> 16)) // size + 1
            blocks = int(spans.sum())
            room, start, held = LANES * max(1, workers), 0, 0
            for i, n in enumerate(spans.tolist()):
                if held and held + n > room:
                    tasks.append((start, i))
                    start, held = i, 0
                held += n
            tasks.append((start, len(chunks)))
        tail_start = None
        if traversal.traverse_unplaced_unmapped:
            # Tail chunk: from the end of the last mapped chunk (max
            # ref_end over all refs; fall back to start of data).
            tail_start = max((r.ref_end for r in bai.refs), default=0)
        labels["chunks"] = len(chunks)
        labels["blocks"] = blocks
    return TraversalPlan(table, chunks, tasks, tail_start, blocks)


def read_with_traversal(
    fs: FileSystemWrapper,
    path: str,
    header: SamHeader,
    traversal,
    source,
    ctx,
) -> list:
    """The §3.2 call stack: BAI → chunks → bounded decode → exact
    filter, through the shard executor.  Returns the kept batch of each
    shard task in file order; ``source`` holds their counters."""
    from disq_tpu.runtime.errors import DisqOptions
    from disq_tpu.runtime.tracing import counter

    opts = getattr(source._storage, "_options", None) or DisqOptions()
    plan = ctx.retrier.call(
        plan_traversal, fs, path, header, traversal,
        getattr(opts, "executor_workers", 1), what="traversal plan")
    runs = [(plan.chunks[lo:hi], plan.table) for lo, hi in plan.tasks]
    if plan.tail_start is not None:
        from disq_tpu.bam.source import read_header

        start = plan.tail_start or ctx.retrier.call(
            read_header, fs, path, what="header")[1]
        end = ctx.retrier.call(
            source._data_end_voffset, fs, path, what="data_end")
        runs.append((np.array([[start, end]], np.int64), None))

    def kept(batch, table):
        counter("traversal.decoded_records").inc(int(batch.count))
        if table is not None:
            batch = table.keep(batch)
        elif batch.count:
            batch = batch.filter(batch.refid == -1)
        counter("traversal.returned_records").inc(int(batch.count))
        return batch

    batches = source.read_chunk_runs(fs, path, header, runs, kept, ctx)
    counter("traversal.chunks").inc(sum(len(c) for c, _ in runs))
    counter("traversal.blocks").inc(
        sum(c.blocks for c in source._last_counters))
    return batches
