"""Per-read-group statistics (reads / duplicate rate / MAPQ histogram
per ``RG``), resolved at parse and reduced on device.

The ``RG:Z`` tag is a *ragged* attribute, so the id column is resolved
host-side — an exact per-record walk of the BAM tag region (tag, type,
typed value; ``Z``/``H`` NUL-terminated, ``B`` counted) over either
the raw record blob (resident batches — no host record parse) or the
host tag column, with a vectorized ``RGZ`` pre-scan so RG-less files
skip the walk entirely. Dense ids then upload once (4 B/record) and
the reduction — one bincount over ``rg * 256 + mapq`` plus a
duplicate-bit scatter-add — runs on device against the *resident*
mapq/flag columns; with a mesh attached it shards over the batch axis
and merges via ``lax.psum`` like ``flagstat_resident_sharded``
(integer adds ⇒ bit-exact at any device count).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import numpy as np

NO_RG = "(none)"

# BAM tag value sizes by type char: A c C s S i I f
_TYPE_SIZE = {65: 1, 99: 1, 67: 1, 115: 2, 83: 2, 105: 4, 73: 4, 102: 4}


def _walk_rg(buf, s: int, e: int) -> Optional[bytes]:
    """Exact tag walk of one record's tag region — returns the RG:Z
    value or None."""
    while s + 3 <= e:
        t0, t1, tp = buf[s], buf[s + 1], buf[s + 2]
        s += 3
        if tp in (90, 72):  # Z / H: NUL-terminated
            z = s
            while z < e and buf[z] != 0:
                z += 1
            if t0 == 82 and t1 == 71 and tp == 90:
                return bytes(buf[s:z])
            s = z + 1
        elif tp == 66:  # B: subtype + i32 count + payload
            if s + 5 > e:
                break
            sub = buf[s]
            cnt = int.from_bytes(buf[s + 1: s + 5], "little")
            s += 5 + _TYPE_SIZE.get(sub, 1) * cnt
        else:
            s += _TYPE_SIZE.get(tp, 1)
    return None


def _has_rgz(flat: np.ndarray) -> bool:
    """Vectorized pre-scan: can any ``RG:Z`` tag exist at all? A real
    one always contains the literal bytes ``RGZ`` — no false
    negatives, so a miss skips the per-record walk."""
    if len(flat) < 3:
        return False
    return bool(np.any((flat[:-2] == 82) & (flat[1:-1] == 71)
                       & (flat[2:] == 90)))


def read_group_ids(batch) -> Tuple[np.ndarray, List[str]]:
    """(dense i32 RG id per record, id -> name). Records without an RG
    tag map to the trailing ``(none)`` group when any exist."""
    from disq_tpu.ops.markdup import record_fields_from_blob
    from disq_tpu.runtime.columnar import ColumnarBatch

    n = int(batch.count)
    spans: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None
    if isinstance(batch, ColumnarBatch) and batch.device_backed:
        src = batch.encode_source()
        if src is not None:
            blob, offsets, order = src
            fields = record_fields_from_blob(blob, offsets, order)
            lseq = fields["l_seq"]
            tag_lo = (fields["_off"] + 36 + fields["l_read_name"]
                      + 4 * fields["n_cigar"] + (lseq + 1) // 2 + lseq)
            off = np.asarray(offsets, np.int64)
            rec_len = np.diff(off)
            if order is not None:
                rec_len = rec_len[np.asarray(order, np.int64)]
            spans = (blob, tag_lo, fields["_off"] + rec_len)
    if spans is None:
        off = np.asarray(batch.tag_offsets, np.int64)
        spans = (np.asarray(batch.tags), off[:-1], off[1:])
    flat, lo, hi = spans
    ids = np.full(n, -1, np.int32)
    names: List[str] = []
    if n and _has_rgz(flat):
        by_name: Dict[bytes, int] = {}
        buf = memoryview(np.ascontiguousarray(flat))
        for i in range(n):
            rg = _walk_rg(buf, int(lo[i]), int(hi[i]))
            if rg is None:
                continue
            rid = by_name.get(rg)
            if rid is None:
                rid = by_name[rg] = len(by_name)
                names.append(rg.decode("utf-8", "replace"))
            ids[i] = rid
    if (ids < 0).any() and names:
        ids = np.where(ids < 0, np.int32(len(names)), ids)
        names = names + [NO_RG]
    elif not names:
        ids = np.zeros(n, np.int32)
        names = [NO_RG]
    return ids, names


@functools.lru_cache(maxsize=8)
def _rg_kernel(n_rg: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rgstats_reduce(rg, mapq, flag, n):
        m = rg.shape[0]
        valid = (jnp.arange(m, dtype=jnp.int32) < n).astype(jnp.int32)
        comb = rg * 256 + mapq.astype(jnp.int32)
        hist = jnp.zeros(n_rg * 256, jnp.int32).at[comb].add(valid)
        dupbit = ((flag.astype(jnp.int32) >> 10) & 1) * valid
        dups = jnp.zeros(n_rg, jnp.int32).at[rg].add(dupbit)
        return hist, dups

    return rgstats_reduce


@functools.lru_cache(maxsize=8)
def _rg_psum_kernel(mesh, axis: str, n_rg: int, per: int):
    """The mesh form: each device bincounts its batch-axis slice
    locally, one psum over ICI merges the histograms."""
    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    def body(rg, mapq, flag, n):
        i = lax.axis_index(axis)
        base = (i * per).astype(jnp.int32)
        valid = ((base + jnp.arange(per, dtype=jnp.int32)) <
                 n).astype(jnp.int32)
        comb = rg * 256 + mapq.astype(jnp.int32)
        hist = jnp.zeros(n_rg * 256, jnp.int32).at[comb].add(valid)
        dupbit = ((flag.astype(jnp.int32) >> 10) & 1) * valid
        dups = jnp.zeros(n_rg, jnp.int32).at[rg].add(dupbit)
        return lax.psum(hist, axis), lax.psum(dups, axis)

    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()),
        out_specs=(P(), P())))


def _reduce_resident(batch, ids: np.ndarray, n_rg: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Device reduction against the resident mapq/flag columns: only
    the (n_rg*256,) histogram row crosses d2h."""
    from disq_tpu.runtime.mesh import MESH_AXIS, batch_sharding, shard_count
    from disq_tpu.runtime.tracing import count_transfer, device_span

    import jax
    import jax.numpy as jnp

    dev = batch._dev_snapshot()
    n = int(batch.count)
    padded = int(dev["mapq"].shape[0])
    rg_host = np.zeros(padded, np.int32)
    rg_host[:n] = ids
    count_transfer("h2d", rg_host.nbytes)
    mesh = batch.mesh
    if mesh is not None:
        n_dev = shard_count(mesh)
        per = padded // n_dev
        rg_d = jax.device_put(jnp.asarray(rg_host), batch_sharding(mesh))
        from jax.sharding import NamedSharding, PartitionSpec as P

        n_arr = jax.device_put(
            jnp.asarray(np.int32(n)), NamedSharding(mesh, P()))
        with device_span("device.kernel", kernel="rgstats",
                         records=n, devices=n_dev) as fence:
            with jax.transfer_guard("disallow"):
                hist, dups = _rg_psum_kernel(mesh, MESH_AXIS, n_rg, per)(
                    rg_d, dev["mapq"], dev["flag"], n_arr)
                jax.block_until_ready(hist)
            fence.sync(hist)
    else:
        n_arr = jnp.asarray(np.int32(n))
        rg_d = jnp.asarray(rg_host)
        with device_span("device.kernel", kernel="rgstats",
                         records=n) as fence:
            with jax.transfer_guard("disallow"):
                hist, dups = _rg_kernel(n_rg)(
                    rg_d, dev["mapq"], dev["flag"], n_arr)
                jax.block_until_ready(hist)
            fence.sync(hist)
    h, d = np.asarray(hist), np.asarray(dups)
    count_transfer("d2h", h.nbytes + d.nbytes)
    batch._consume_on_device("mapq", 4 * n)
    batch._consume_on_device("flag", 4 * n)
    return h.reshape(n_rg, 256), d


def read_group_stats(batch) -> Dict[str, Dict[str, object]]:
    """{rg name: {reads, duplicates, dup_rate, mean_mapq, mapq_hist}}
    — the operator-suite per-RG reduction. Resident batches reduce on
    device from the resident mapq/flag columns; host batches bincount
    in numpy (identical integers either way)."""
    from disq_tpu.runtime.columnar import ColumnarBatch
    from disq_tpu.runtime.tracing import span

    n = int(batch.count)
    with span("ops.rgstats.apply", records=n):
        ids, names = read_group_ids(batch)
        n_rg = len(names)
        resident = (isinstance(batch, ColumnarBatch) and batch.device_backed
                    and n > 0)
        if resident:
            hist, dups = _reduce_resident(batch, ids, n_rg)
        else:
            mapq = np.asarray(batch.mapq, np.int64) if n else np.zeros(0)
            flag = np.asarray(batch.flag, np.int64) if n else np.zeros(0)
            comb = ids.astype(np.int64) * 256 + mapq
            hist = np.bincount(comb.astype(np.int64),
                               minlength=n_rg * 256).reshape(n_rg, 256)
            dups = np.bincount(ids, weights=(flag >> 10) & 1,
                               minlength=n_rg).astype(np.int64)
        out: Dict[str, Dict[str, object]] = {}
        mq = np.arange(256)
        for rid, name in enumerate(names):
            h = hist[rid]
            reads = int(h.sum())
            d = int(dups[rid])
            out[name] = {
                "reads": reads,
                "duplicates": d,
                "dup_rate": round(d / reads, 6) if reads else 0.0,
                "mean_mapq": round(float((h * mq).sum() / reads), 3)
                if reads else 0.0,
                "mapq_hist": h.astype(int).tolist(),
            }
        return out
