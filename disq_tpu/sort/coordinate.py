"""Coordinate sort — first-class in this framework.

Upstream disq does NOT sort (SURVEY.md §2.1 note: ``write`` trusts
``header.getSortOrder()``; GATK does a Spark ``sortBy`` shuffle before
calling it). Here the sort is owned by the framework: the single-host
path below sorts a columnar batch by the 64-bit coordinate key; the
multi-chip path (``disq_tpu.sort.sharded``) buckets records across the
device mesh with a psum histogram + all_to_all exchange over ICI and
reuses the same key.

SAM coordinate order: ascending refID (unmapped refID=-1 LAST), then
ascending pos; ties keep input order (stable).
"""

from __future__ import annotations

import numpy as np

from disq_tpu.bam.columnar import ReadBatch

# Key layout: (refid+1) in the high 32 bits with unmapped (refid -1)
# remapped ABOVE all real refs, pos+1 in the low 32. Monotone w.r.t.
# coordinate order, so one u64 radix/merge sort suffices.


def coordinate_keys(refid: np.ndarray, pos: np.ndarray) -> np.ndarray:
    rid = refid.astype(np.int64)
    rid = np.where(rid < 0, np.int64(0x7FFFFFFF), rid)
    return (rid.astype(np.uint64) << np.uint64(32)) | (
        (pos.astype(np.int64) + 1).astype(np.uint64) & np.uint64(0xFFFFFFFF)
    )


def coordinate_sort_batch(batch: ReadBatch, use_mesh: bool = True,
                          keep_resident: bool = False) -> ReadBatch:
    """Sort a batch into coordinate order.

    A device-backed ``ColumnarBatch`` (the HBM-resident fused-decode
    currency) sorts from its resident refid/pos columns: key build +
    lexsort run on device and only the (n,) i32 permutation crosses
    d2h — the u64 key vectors never materialize host-side. Otherwise
    the permutation comes from the device mesh when more than one
    device is attached (psum/all_to_all exchange,
    ``disq_tpu.sort.sharded``); ragged columns are reordered host-side
    by one vectorized segment gather either way.

    ``keep_resident`` returns ``batch.permuted(order)`` instead of
    materializing host records: the sorted batch stays a device-backed
    ``ColumnarBatch`` whose fixed columns were permuted on device and
    whose record bytes the write copies in that order
    (``ColumnarBatch.encoded_slice``) — whether the permutation came
    from the single-chip lexsort or the multi-chip psum/all_to_all
    exchange.
    """
    from disq_tpu.runtime.columnar import ColumnarBatch
    from disq_tpu.runtime.tracing import span

    if isinstance(batch, ColumnarBatch):
        if batch.device_backed and batch.count > 0:
            # resident sort-key extraction: byte-identical to the host
            # argsort (same key, both stable), zero key traffic.  A
            # mesh-sharded batch routes through the multi-chip
            # psum-histogram exchange (sharded.resident_coordinate_sort)
            # with the same byte-identity contract — rows ride as the
            # least-significant lexsort component, so duplicate keys
            # keep original-index order at any device count.
            order = batch.sort_permutation()
            # with the permutation's fetch (``stage=fetch``, booked
            # where it crosses d2h) this is what lies between the sort
            # kernel and the write: the records gathered on the host
            with span("sort.gather", stage="gather", records=batch.count):
                if keep_resident and batch.holds_bytes:
                    return batch.permuted(order)
                return batch.take(order)
        resident_src = batch if keep_resident else None
        batch = batch.to_read_batch()
    else:
        resident_src = None
    keys = coordinate_keys(batch.refid, batch.pos)
    order = None
    if use_mesh and batch.count > 0:
        # Deliberate: only "mesh has a single device" selects the host
        # path. A real failure inside the sharded sort must propagate —
        # swallowing it here would let a broken mesh path silently degrade
        # to the host argsort and never fail a test.
        import jax

        if len(jax.devices()) > 1:
            from disq_tpu.sort.sharded import sharded_coordinate_sort

            _, order = sharded_coordinate_sort(keys)
    if order is None:
        order = np.argsort(keys, kind="stable")
    if resident_src is not None and resident_src.holds_bytes:
        return resident_src.permuted(order)
    return batch.take(order)
