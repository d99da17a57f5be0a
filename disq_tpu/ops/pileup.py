"""Region pileup — per-BASE coverage over one reference interval, the
base-granularity generalization of ``ops/depth.py``'s windowed depth.

Same two device primitives (difference-array scatter-add + cumsum), at
window = 1 base over just the queried region: depth for base b =
number of mapped alignments whose reference span covers b. Mapped
records only (``flag & 0x4`` clear, matching ``window_depth``);
secondary/supplementary/duplicate records count unless the caller
filtered them (compose with ``ops/rfilter``).

Mesh-aware via the exact ``shard_map`` + ``lax.psum`` machinery of
``_depth_psum`` — integer adds reassociate freely, so the sharded
reduction is bit-identical to the single-device scatter.

A resident ``ColumnarBatch`` never host-parses records here: the
alignment ends come from the CIGAR pass over the raw record bytes
that the batch runs and keeps (``ColumnarBatch.alignment_ends`` over
``ops/markdup.reference_spans_from_blob``), as ``window_depth``'s do.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

# responses and scatter spaces stay bounded: one query's region
MAX_REGION_BP = 1 << 22


def _span_bounds(batch) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray]:
    """(refid, pos, end, mapped mask) for any batch flavor — resident
    batches derive the cigar spans from their record blob."""
    from disq_tpu.ops.markdup import record_fields_from_blob
    from disq_tpu.runtime.columnar import ColumnarBatch

    if isinstance(batch, ColumnarBatch) and batch.device_backed:
        src = batch.encode_source()
        if src is not None:
            blob, offsets, order = src
            fields = record_fields_from_blob(blob, offsets, order)
            refid, pos, flag = fields["refid"], fields["pos"], fields["flag"]
            end = np.asarray(batch.alignment_ends(), np.int64)
            return refid, pos, end, (flag & 0x4) == 0
    refid = np.asarray(batch.refid, np.int64)
    pos = np.asarray(batch.pos, np.int64)
    end = np.asarray(batch.alignment_ends(), np.int64)
    return refid, pos, end, (np.asarray(batch.flag) & 0x4) == 0


def region_pileup(batch, refid: int, start: int, end: int) -> np.ndarray:
    """int32 per-base coverage for ``[start, end)`` on ``refid``.

    Books ``ops.pileup.records`` with the number of overlapping
    alignments scattered; the scatter itself runs on device (psum-
    reduced over the batch's mesh when it carries one)."""
    from disq_tpu.ops.depth import _depth_global, _depth_psum
    from disq_tpu.runtime.tracing import counter, span

    import jax.numpy as jnp

    length = int(end) - int(start)
    if length <= 0:
        return np.zeros(0, np.int32)
    if length > MAX_REGION_BP:
        raise ValueError(
            f"pileup region of {length} bp exceeds the {MAX_REGION_BP} "
            "bp bound; query a smaller interval")
    with span("ops.pileup.apply", records=int(batch.count),
              region_bp=length):
        rid, pos, ends, mapped = _span_bounds(batch)
        sel = mapped & (rid == refid) & (pos < end) & (ends > start)
        counter("ops.pileup.records").inc(int(sel.sum()))
        if not sel.any():
            return np.zeros(length, np.int32)
        # clip to the region's base space: the difference array is
        # length+2 wide in _depth_psum's sentinel scheme, so bounds
        # clamp onto [0, length-1]
        b_lo = np.clip(pos[sel] - start, 0, length - 1).astype(np.int32)
        b_hi = np.clip(ends[sel] - 1 - start, 0, length - 1).astype(np.int32)
        mesh = getattr(batch, "mesh", None)
        if mesh is not None:
            return _depth_psum(b_lo, b_hi, length, mesh)
        return np.asarray(_depth_global(
            jnp.asarray(b_lo), jnp.asarray(b_hi), n_windows=length))
