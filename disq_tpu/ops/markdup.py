"""Duplicate marking on coordinate-sorted batches (the ``samtools
markdup`` family), resident on device.

Two records are duplicates when they share the key **(refid, unclipped
5' position, orientation)** — the unclipped 5' end undoes soft/hard
clips: ``pos - leading clips`` for forward reads, ``alignment end +
trailing clips - 1`` for reverse reads. Within each key group the
**best-score** record (sum of base qualities >= 15, ties broken by
first appearance — stable) stays the representative; every other
member gets flag ``0x400``. Records flagged unmapped / secondary /
supplementary (``0x904``) are never examined and never marked.

Resident batches never host-parse: the key columns (flag / refid /
pos / clip extents / qual score) are derived **from the raw record
bytes** the batch already holds — the fixed fields by numpy, reference
span, clips and score by one sweep with memory O(records)
(``key_sweep_from_blob``: a sequential C pass, numpy in bounded chunks
where the native library is absent; the same host-assist precedent as
``ops/depth.py``'s bound math) — uploaded once, and the group scan — a
stable device lexsort + segment-boundary detection, the same machinery
family as ``sort_permutation`` — marks duplicates in one launch. The
duplicate bits are written back through ``ColumnarBatch.or_flags``: the
resident flag column and the record blob bytes both carry ``0x400``,
so the resident write path emits bytes identical to a host-marked
file. Host ``ReadBatch`` inputs run the same key math over their
columns with a numpy lexsort — the kept/marked sets are identical.

**Shard-seam scope.** Marking one shard sees only that shard's
records. For exactness across seams, ``merge_boundary_duplicates``
runs a driver-side second pass: each shard exports its surviving
representatives whose key position lies within ``boundary_bp`` of the
shard's coordinate range edges; groups spanning shards re-elect one
global representative (best score, then earliest shard, then earliest
record — the same total order as within a shard) and the losers'
duplicate bits are flipped in place. Exact whenever every read's
clipped span is <= ``boundary_bp`` (default 512, covering short-read
data); longer spans only ever under-mark, never over-mark.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

MARKDUP_EXCLUDE = 0x4 | 0x100 | 0x800
DEFAULT_BOUNDARY_BP = 512
_SCORE_MIN_Q = 15


# -- raw-record-byte column extraction (no host record parse) ----------------


def _u16(blob: np.ndarray, off: np.ndarray) -> np.ndarray:
    return blob[off].astype(np.int64) | (blob[off + 1].astype(np.int64) << 8)


def _i32(blob: np.ndarray, off: np.ndarray) -> np.ndarray:
    v = (blob[off].astype(np.uint32)
         | (blob[off + 1].astype(np.uint32) << 8)
         | (blob[off + 2].astype(np.uint32) << 16)
         | (blob[off + 3].astype(np.uint32) << 24))
    return v.astype(np.int64) - ((v >> 31).astype(np.int64) << 32)


def _flat_segments(base: np.ndarray, lens: np.ndarray,
                   stride: int = 1) -> Tuple[np.ndarray, np.ndarray]:
    """Flat element indices for N variable-length segments: segment i
    contributes ``base[i] + stride*j`` for j < lens[i]. Returns (flat
    source indices, (N+1,) segment offsets)."""
    seg_off = np.zeros(len(lens) + 1, dtype=np.int64)
    np.cumsum(lens, out=seg_off[1:])
    total = int(seg_off[-1])
    if total == 0:
        return np.zeros(0, np.int64), seg_off
    seg = np.repeat(np.arange(len(lens)), lens)
    within = np.arange(total, dtype=np.int64) - seg_off[seg]
    return base[seg] + stride * within, seg_off


def _segment_sums(contrib: np.ndarray, seg_off: np.ndarray) -> np.ndarray:
    """Per-segment sums over a flat contribution vector (reduceat with
    the empty-segment quirk masked, as ``ReadBatch.reference_lengths``)."""
    n = len(seg_off) - 1
    if n == 0:
        return np.zeros(0, np.int64)
    sums = np.add.reduceat(
        np.concatenate([contrib, [0]]),
        np.minimum(seg_off[:-1], len(contrib)))
    return np.where(np.diff(seg_off) == 0, 0, sums)


def record_fields_from_blob(blob: np.ndarray, offsets: np.ndarray,
                            order: Optional[np.ndarray] = None
                            ) -> Dict[str, np.ndarray]:
    """Fixed fields straight from the record bytes — no d2h fetch of
    the resident columns, no host record parse. ``order`` maps
    logical record index -> blob record index (``permuted()``)."""
    off = np.asarray(offsets[:-1], dtype=np.int64)
    if order is not None:
        off = off[np.asarray(order, dtype=np.int64)]
    return {
        "refid": _i32(blob, off + 4),
        "pos": _i32(blob, off + 8),
        "l_read_name": blob[off + 12].astype(np.int64),
        "n_cigar": _u16(blob, off + 16),
        "flag": _u16(blob, off + 18),
        "l_seq": _i32(blob, off + 20),
        "_off": off,
    }


def cigar_arrays_from_blob(blob: np.ndarray,
                           fields: Dict[str, np.ndarray]
                           ) -> Tuple[np.ndarray, np.ndarray]:
    """(flat u32 cigar op-words, (N+1,) offsets) from the blob."""
    base = fields["_off"] + 36 + fields["l_read_name"]
    src, seg_off = _flat_segments(base, fields["n_cigar"], stride=4)
    words = (blob[src].astype(np.uint32)
             | (blob[src + 1].astype(np.uint32) << 8)
             | (blob[src + 2].astype(np.uint32) << 16)
             | (blob[src + 3].astype(np.uint32) << 24))
    return words, seg_off


def clip_and_span(cigars: np.ndarray, cigar_offsets: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(reference span, leading clip bases, trailing clip bases) per
    record from a flat cigar vector — vectorized; clips (S=4 / H=5)
    legally appear only as the outermost one or two ops per end."""
    cigars = np.asarray(cigars, dtype=np.uint32)
    seg_off = np.asarray(cigar_offsets, dtype=np.int64)
    op = (cigars & 0xF).astype(np.int64)
    ln = (cigars >> 4).astype(np.int64)
    span = _segment_sums(np.where(np.isin(op, (0, 2, 3, 7, 8)), ln, 0),
                         seg_off)
    n = len(seg_off) - 1
    ncig = np.diff(seg_off)
    lead = np.zeros(n, np.int64)
    trail = np.zeros(n, np.int64)
    if len(cigars):
        is_clip = np.isin(op, (4, 5))
        limit = len(cigars) - 1
        # leading: first op, plus the second when the first was a clip
        # (H then S); symmetric from the tail
        prev_clip = np.ones(n, bool)
        for k in (0, 1):
            at = np.minimum(seg_off[:-1] + k, limit)
            hit = (ncig > k) & is_clip[at] & prev_clip
            lead += np.where(hit, ln[at], 0)
            prev_clip = hit
        prev_clip = np.ones(n, bool)
        for k in (1, 2):
            at = np.clip(seg_off[1:] - k, 0, limit)
            hit = (ncig >= k) & is_clip[at] & prev_clip
            trail += np.where(hit, ln[at], 0)
            prev_clip = hit
    return span, lead, trail


def reference_spans_from_blob(blob: np.ndarray, offsets: np.ndarray,
                              base: int = 0
                              ) -> Tuple[np.ndarray, np.ndarray, int]:
    """``(pos i32, reference length i64, CIGAR op words walked)`` of the
    records of ``blob``
    from their fixed fields and CIGAR op words alone — the one
    blob -> CIGAR-span routine of the tree
    (``ColumnarBatch.reference_lengths`` / ``alignment_ends``, and
    through them depth, pileup, the interval filters and the CRAM
    writer). ``offsets`` are these records' (N+1,) offsets in a larger
    blob of which ``blob`` is the part that starts at byte ``base``.
    A record whose name and CIGAR sections do not fit inside it raises
    the host parser's ``ValueError`` instead of being read past. The
    sequential C pass when the native library loads, else the numpy
    helpers above — equal values either way, and equal to
    ``ReadBatch.reference_lengths``."""
    try:
        from disq_tpu.native import reference_lengths_native

        return reference_lengths_native(blob, offsets, base)
    except ImportError:
        pass
    offsets = np.asarray(offsets, dtype=np.int64) - base
    rec_len = np.diff(offsets)
    bad = (rec_len < 36) | (offsets[:-1] < 0) | (offsets[1:] > len(blob))
    if not bad.any():
        fields = record_fields_from_blob(blob, offsets)
        bad = 36 + fields["l_read_name"] + 4 * fields["n_cigar"] > rec_len
    if bad.any():
        raise ValueError(
            f"record {int(np.nonzero(bad)[0][0])}: malformed sections")
    span, _lead, _trail = clip_and_span(
        *cigar_arrays_from_blob(blob, fields))
    return (fields["pos"].astype(np.int32), span,
            int(fields["n_cigar"].sum()))


def qual_scores_from_blob(blob: np.ndarray,
                          fields: Dict[str, np.ndarray]) -> np.ndarray:
    """Per-record duplicate score = sum of base qualities >= 15 (the
    samtools convention; the 0xFF "missing quals" sentinel scores 0)."""
    lseq = fields["l_seq"]
    qbase = (fields["_off"] + 36 + fields["l_read_name"]
             + 4 * fields["n_cigar"] + (lseq + 1) // 2)
    src, seg_off = _flat_segments(qbase, lseq)
    q = blob[src].astype(np.int64)
    return qual_scores_from_flat(q, seg_off)


def qual_scores_from_flat(q: np.ndarray, seg_off: np.ndarray) -> np.ndarray:
    contrib = np.where((q >= _SCORE_MIN_Q) & (q != 0xFF), q, 0)
    return _segment_sums(contrib.astype(np.int64),
                         np.asarray(seg_off, dtype=np.int64))


# what the numpy sweep may index at once: the quality bytes of one
# chunk of records, never of the whole blob
SWEEP_CHUNK_BASES = 1 << 22


def key_sweep_from_blob(blob: np.ndarray, offsets: np.ndarray):
    """``(pos i32, reference length, leading clip, trailing clip,
    score)`` (the last four i64) of every record of ``blob``, in the
    blob's order: what duplicate marking needs of the CIGAR and the
    qualities, in one sweep whose memory is O(records). The sequential
    C pass when the native library loads; else the numpy helpers above
    over chunks of records whose qualities together stay under
    ``SWEEP_CHUNK_BASES`` (a record longer than that is a chunk of its
    own) — equal values either way. A record whose sections do not fit
    inside it raises the host parser's ``ValueError``."""
    try:
        from disq_tpu.native import markdup_keys_native

        return markdup_keys_native(blob, offsets)
    except ImportError:
        pass
    offsets = np.asarray(offsets, dtype=np.int64)
    n = len(offsets) - 1
    rec_len = np.diff(offsets)
    bad = (rec_len < 36) | (offsets[:-1] < 0) | (offsets[1:] > len(blob))
    if not bad.any():
        fields = record_fields_from_blob(blob, offsets)
        lseq = fields["l_seq"]
        bad = (lseq < 0) | (36 + fields["l_read_name"]
                            + 4 * fields["n_cigar"] + (lseq + 1) // 2
                            + lseq > rec_len)
    if bad.any():
        raise ValueError(
            f"record {int(np.nonzero(bad)[0][0])}: malformed sections")
    span, lead, trail = clip_and_span(*cigar_arrays_from_blob(blob, fields))
    score = np.empty(n, np.int64)
    bases = np.zeros(n + 1, np.int64)
    np.cumsum(lseq, out=bases[1:])
    lo = 0
    while lo < n:
        hi = max(lo + 1, int(np.searchsorted(
            bases, bases[lo] + SWEEP_CHUNK_BASES, side="right")) - 1)
        score[lo:hi] = qual_scores_from_blob(
            blob, {k: v[lo:hi] for k, v in fields.items()})
        lo = hi
    return fields["pos"].astype(np.int32), span, lead, trail, score


# -- key construction --------------------------------------------------------


def markdup_keys(flag: np.ndarray, refid: np.ndarray, pos: np.ndarray,
                 span: np.ndarray, lead: np.ndarray, trail: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(unclipped 5' position i64, orientation {0,1}, examined mask)."""
    f = np.asarray(flag, dtype=np.int64)
    reverse = (f & 0x10) != 0
    upos = np.where(reverse,
                    np.asarray(pos, np.int64) + np.maximum(span, 1) - 1
                    + trail,
                    np.asarray(pos, np.int64) - lead)
    valid = ((f & MARKDUP_EXCLUDE) == 0) & (np.asarray(refid) >= 0)
    return upos, reverse.astype(np.int8), valid


def _mark_dups_host(refid, upos, orient, score, valid) -> np.ndarray:
    """The group scan in numpy (host batches + the device kernel's
    oracle): stable lexsort by (key, score desc), every non-first
    group member is a duplicate."""
    n = len(upos)
    if n == 0:
        return np.zeros(0, bool)
    idx = np.arange(n, dtype=np.int64)
    hi = np.where(valid, np.asarray(refid, np.int64), np.int64(1) << 40)
    up = np.where(valid, upos, idx)
    order = np.lexsort((-np.asarray(score, np.int64),
                        orient.astype(np.int64), up, hi))
    sh, su, so = hi[order], up[order], orient[order]
    new_grp = np.ones(n, bool)
    new_grp[1:] = (sh[1:] != sh[:-1]) | (su[1:] != su[:-1]) \
        | (so[1:] != so[:-1])
    dup = np.zeros(n, bool)
    dup[order] = ~new_grp & valid[order]
    return dup


@functools.lru_cache(maxsize=1)
def _markdup_kernel():
    """The resident group scan: one stable lexsort over the packed key
    columns + a shifted-compare segment-boundary detection + a scatter
    back to record order — all on device; only the (n,) bool duplicate
    mask crosses d2h (the blob flag patch needs it host-side anyway)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def markdup_group_scan(refid, upos, orient, negscore, valid, n):
        # u32/i32 keys only — jax's default 32-bit mode would silently
        # truncate an i64 sentinel
        m = refid.shape[0]
        idx = jnp.arange(m, dtype=jnp.int32)
        live = valid & (idx < n)
        # excluded + padded lanes get unique keys (refid above every
        # real one, upos = own index) so each is its own group and can
        # never mark or be marked
        hi = jnp.where(live, refid.astype(jnp.uint32),
                       jnp.uint32(0xFFFFFFFF))
        up = jnp.where(live, upos, idx)
        order = jnp.lexsort((negscore, orient, up, hi))
        sh, su, so = hi[order], up[order], orient[order]
        first = jnp.ones((1,), bool)
        new_grp = jnp.concatenate([
            first,
            (sh[1:] != sh[:-1]) | (su[1:] != su[:-1]) | (so[1:] != so[:-1]),
        ])
        dup_sorted = ~new_grp & live[order]
        dup = jnp.zeros(m, bool).at[order].set(dup_sorted)
        return dup, jnp.sum(live.astype(jnp.int32)), \
            jnp.sum(dup_sorted.astype(jnp.int32))

    return markdup_group_scan


# -- per-shard marking -------------------------------------------------------


@dataclass
class MarkdupResult:
    """One shard's marking outcome + the seam-merge inputs."""

    dup_mask: np.ndarray
    examined: int
    duplicates: int
    boundary_flips: int = 0
    # surviving representatives near the shard's coordinate edges:
    # parallel arrays (refid, upos, orient, score, record index)
    candidates: Dict[str, np.ndarray] = field(default_factory=dict)

    def stats(self) -> Dict[str, int]:
        return {"examined": int(self.examined),
                "duplicates": int(self.duplicates),
                "boundary_flips": int(self.boundary_flips)}


def _key_columns(batch) -> Tuple[Dict[str, np.ndarray], bool]:
    """(flag/refid/pos/upos inputs + score, resident?) for any batch
    flavor — resident batches derive everything from their record
    blob, host batches from their columns."""
    from disq_tpu.runtime.columnar import ColumnarBatch

    if isinstance(batch, ColumnarBatch) and batch.device_backed:
        src = batch.encode_source()
        if src is not None:
            from disq_tpu import native
            from disq_tpu.runtime.tracing import span

            blob, offsets, order = src
            # ``bytes`` is what the sweep reads: the whole blob, also
            # where the batch selects some of its records
            with span("ops.markdup.keys", records=batch.count,
                      bytes=int(offsets[-1] - offsets[0])) as labels:
                fields = record_fields_from_blob(blob, offsets, order)
                reflen, lead, trail, score = batch.clips_and_scores()
                labels["source"] = "native" if native.loaded() else "numpy"
                labels["spans"] = batch.ends_source
            return {"flag": fields["flag"], "refid": fields["refid"],
                    "pos": fields["pos"], "span": reflen, "lead": lead,
                    "trail": trail, "score": score}, True
    flag = np.asarray(batch.flag, np.int64)
    refid = np.asarray(batch.refid, np.int64)
    pos = np.asarray(batch.pos, np.int64)
    span, lead, trail = clip_and_span(batch.cigars, batch.cigar_offsets)
    seg_off = np.asarray(batch.seq_offsets, np.int64)
    score = qual_scores_from_flat(
        np.asarray(batch.quals, np.int64), seg_off)
    return {"flag": flag, "refid": refid, "pos": pos, "span": span,
            "lead": lead, "trail": trail, "score": score}, False


def _apply_mask(batch, dup_mask: np.ndarray):
    """Write 0x400 back: in place for ColumnarBatch (device column +
    blob bytes), a fresh flag column for a host ReadBatch."""
    from disq_tpu.runtime.columnar import ColumnarBatch

    if isinstance(batch, ColumnarBatch):
        batch.or_flags(dup_mask, 0x400)
        return batch
    batch.flag = np.where(dup_mask, batch.flag | np.uint16(0x400),
                          batch.flag).astype(batch.flag.dtype)
    return batch


def register_counters() -> None:
    """The operator's counters at 0, so that a reader tells "did not
    move" (no record examined, no duplicate found) from "no such
    counter" (telemetry may have been reset since the last call)."""
    from disq_tpu.runtime.tracing import counter

    for name in ("ops.markdup.examined", "ops.markdup.duplicates"):
        counter(name).inc(0)


def markdup_batch(batch, boundary_bp: int = DEFAULT_BOUNDARY_BP
                  ) -> Tuple[object, MarkdupResult]:
    """Mark duplicates within one (coordinate-sorted) batch. Returns
    the marked batch (same object for ColumnarBatch — flags patched in
    place) and a ``MarkdupResult`` carrying the seam-merge candidates."""
    from disq_tpu.runtime.tracing import counter, span

    n = int(batch.count)
    with span("ops.markdup.apply", records=n):
        if n == 0:
            return batch, MarkdupResult(np.zeros(0, bool), 0, 0)
        cols, resident = _key_columns(batch)
        upos, orient, valid = markdup_keys(
            cols["flag"], cols["refid"], cols["pos"],
            cols["span"], cols["lead"], cols["trail"])
        if resident:
            dup, examined, dups = _mark_dups_resident(
                cols["refid"], upos, orient, cols["score"], valid, n)
        else:
            dup = _mark_dups_host(cols["refid"], upos, orient,
                                  cols["score"], valid)
            examined, dups = int(valid.sum()), int(dup.sum())
        batch = _apply_mask(batch, dup)
        counter("ops.markdup.examined").inc(int(examined))
        counter("ops.markdup.duplicates").inc(int(dups))
        res = MarkdupResult(dup, int(examined), int(dups))
        res.candidates = _boundary_candidates(
            cols, upos, orient, valid, dup, boundary_bp)
    return batch, res


def _mark_dups_resident(refid, upos, orient, score, valid, n):
    """Launch the device group scan with bucket-padded key uploads
    (matching the resident columns' padding policy so jit shapes
    bucket identically)."""
    from disq_tpu.runtime.tracing import count_transfer, device_span
    from disq_tpu.util import bucket_pow2

    import jax
    import jax.numpy as jnp

    padded = bucket_pow2(n)
    cols = {}
    for name, arr, dt in (("refid", refid, np.int32),
                          ("upos", upos, np.int32),
                          ("orient", orient, np.int32),
                          ("negscore", -np.asarray(score), np.int32)):
        h = np.zeros(padded, dt)
        h[:n] = arr
        count_transfer("h2d", h.nbytes)
        cols[name] = jnp.asarray(h)
    v = np.zeros(padded, bool)
    v[:n] = valid
    count_transfer("h2d", v.nbytes)
    n_dev = jnp.asarray(np.int32(n))
    with device_span("device.kernel", kernel="markdup",
                     records=n) as fence:
        with jax.transfer_guard("disallow"):
            dup, examined, dups = _markdup_kernel()(
                cols["refid"], cols["upos"], cols["orient"],
                cols["negscore"], jnp.asarray(v), n_dev)
            jax.block_until_ready(dup)
        fence.sync(dup)
    mask = np.asarray(dup[:n])
    count_transfer("d2h", mask.nbytes + 8)
    return mask, int(examined), int(dups)


def _boundary_candidates(cols, upos, orient, valid, dup,
                         boundary_bp: int) -> Dict[str, np.ndarray]:
    """Surviving representatives whose key position lies within
    ``boundary_bp`` of the shard's coordinate extremes — the only
    records a cross-shard group can reach."""
    live = valid & ~dup
    if not live.any() or boundary_bp <= 0:
        return {}
    pos = cols["pos"]
    refid = cols["refid"]
    sel = np.zeros(len(pos), bool)
    # 2x margin: a group member's upos can sit up to one clipped span
    # past its pos, and pos up to one span from the seam — over-
    # inclusion only costs merge-pool size, never correctness
    w = 2 * boundary_bp
    for rid in np.unique(refid[live]):
        on_ref = live & (refid == rid)
        lo, hi = pos[on_ref].min(), pos[on_ref].max()
        near = ((pos <= lo + w) | (pos >= hi - w)
                | (upos <= lo + w) | (upos >= hi - w))
        sel |= on_ref & near
    if not sel.any():
        return {}
    idx = np.nonzero(sel)[0]
    return {"refid": refid[idx].astype(np.int64),
            "upos": upos[idx].astype(np.int64),
            "orient": orient[idx].astype(np.int64),
            "score": np.asarray(cols["score"])[idx].astype(np.int64),
            "index": idx.astype(np.int64)}


def merge_boundary_duplicates(
    shards: Sequence[Tuple[object, MarkdupResult]],
) -> int:
    """Driver-side seam pass (markdup's documented exactness
    mechanism): pool every shard's boundary candidates, re-group by
    key, and demote all but the global best representative of each
    cross-shard group — best score, then earliest shard, then
    earliest record, the same total order the within-shard scan used.
    Flips land back in each shard's batch (``or_flags``) and
    ``MarkdupResult`` in place. Returns the number of flips."""
    from disq_tpu.runtime.tracing import counter, span

    with span("ops.markdup.boundary_merge", shards=len(shards)):
        pool = [(si, r.candidates) for si, (_b, r) in enumerate(shards)
                if r.candidates]
        if len(pool) < 2:
            return 0
        refid = np.concatenate([c["refid"] for _si, c in pool])
        upos = np.concatenate([c["upos"] for _si, c in pool])
        orient = np.concatenate([c["orient"] for _si, c in pool])
        score = np.concatenate([c["score"] for _si, c in pool])
        index = np.concatenate([c["index"] for _si, c in pool])
        shard = np.concatenate([
            np.full(len(c["index"]), si, np.int64) for si, c in pool])
        order = np.lexsort((index, shard, -score, orient, upos, refid))
        r_, u_, o_ = refid[order], upos[order], orient[order]
        new_grp = np.ones(len(order), bool)
        new_grp[1:] = (r_[1:] != r_[:-1]) | (u_[1:] != u_[:-1]) \
            | (o_[1:] != o_[:-1])
        # only members of a group that spans >1 shard flip; a group
        # wholly inside one shard already elected this exact winner
        grp_id = np.cumsum(new_grp) - 1
        s_ = shard[order]
        multi = np.zeros(grp_id[-1] + 1, bool)
        firsts = s_[new_grp]
        np.logical_or.at(multi, grp_id, s_ != firsts[grp_id])
        lose = ~new_grp & multi[grp_id]
        flips = 0
        for si, (batch, res) in enumerate(shards):
            mine = lose & (s_ == si)
            if not mine.any():
                continue
            local = index[order][mine]
            mask = np.zeros(len(res.dup_mask), bool)
            mask[local] = True
            _apply_mask(batch, mask)
            res.dup_mask = res.dup_mask | mask
            res.duplicates += int(mask.sum())
            res.boundary_flips += int(mask.sum())
            flips += int(mask.sum())
        if flips:
            counter("ops.markdup.boundary_flips").inc(flips)
        return flips
