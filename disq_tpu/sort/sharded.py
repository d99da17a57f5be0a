"""Multi-chip coordinate sort over a `jax.sharding.Mesh`.

This replaces the Spark ``sortBy`` shuffle that the reference relies on
its caller to run (SURVEY.md §2.9, §3.3): the only all-to-all in disq's
world. TPU-native design (BASELINE.json north star; scaling-book recipe:
pick a mesh, annotate shardings, let XLA insert collectives):

1. each shard holds ``per_shard`` coordinate keys + row ids. Keys are
   **u32 pairs** (hi = remapped refID, lo = pos+1) rather than one u64 —
   TPUs are 32-bit-native and this framework keeps x64 emulation off the
   hot path by construction;
2. splitters (device count − 1 quantiles, sampled on host) define the
   target shard of every key — a *range* partition, so after the exchange
   the shards concatenate into global order;
3. ``shard_map`` stage: group local keys by destination (one stable local
   lexsort), scatter into a fixed-capacity ``(n_shards, cap)`` send
   buffer, ``lax.all_to_all`` over the mesh axis (rides ICI on real
   hardware), then one local lexsort of the received buffer;
4. sentinel padding (``0xFFFFFFFF`` pairs) sorts to the end and is
   dropped by the validity count; a ``psum`` over per-destination counts
   flags capacity overflow (``ok``) without host round-trips inside the
   step.

Everything is static-shape and jit-compatible: no data-dependent Python
control flow (XLA traces once); capacity overflow is handled by re-running
with a larger ``capacity_factor`` (a host-side decision) — the
deterministic, restartable-phase-plan shape from SURVEY.md §5.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# a numpy scalar, not ``jnp.uint32(...)``: a device array made at import
# and closed over by the jitted stages is read back (d2h) the first
# time one of them is traced, and under ``resident_coordinate_sort``'s
# ``transfer_guard("disallow")`` that read raises on a real chip
SENT32 = np.uint32(0xFFFFFFFF)


def make_mesh(n_devices: Optional[int] = None, axis: str = "shards") -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.array(devs[:n]), (axis,))


def split_u64_keys(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host u64 coordinate keys → (hi, lo) u32 pairs for the device sort."""
    return (
        (keys >> np.uint64(32)).astype(np.uint32),
        (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32),
    )


def sample_splitters(keys: np.ndarray, n_shards: int, oversample: int = 64) -> np.ndarray:
    """Host-side quantile splitters ((n_shards-1,) u64) for the range
    partition. Deterministic (seeded): part of the restartable phase plan."""
    if n_shards <= 1 or len(keys) == 0:
        return np.zeros(max(n_shards - 1, 0), dtype=np.uint64)
    rng = np.random.default_rng(0)
    m = min(len(keys), n_shards * oversample)
    sample = np.sort(rng.choice(keys, size=m, replace=False))
    qs = (np.arange(1, n_shards) * m) // n_shards
    return sample[qs].astype(np.uint64)


def _dest_shard(hi, lo, s_hi, s_lo):
    """Range-partition destination: number of splitters strictly less-or-
    equal (side='right' semantics) computed by broadcast compare —
    O(S·m) u32 ops, MXU/VPU-friendly, no 64-bit arithmetic."""
    le = (s_hi[:, None] < hi[None, :]) | (
        (s_hi[:, None] == hi[None, :]) & (s_lo[:, None] <= lo[None, :])
    )
    return jnp.sum(le, axis=0, dtype=jnp.int32)


def _group_scatter(bucket, nb, cap, arrs, fills):
    """Group-by-destination scatter shared by every exchange stage:
    stable-sort by bucket, rank within each group, scatter each array
    into a fixed-capacity (nb, cap) send buffer (phantom bucket ``nb``
    and over-capacity entries fall outside and are dropped), and return
    the per-bucket valid counts for overflow detection."""
    order = jnp.argsort(bucket, stable=True)
    b_g = bucket[order]
    group_start = jnp.searchsorted(b_g, b_g, side="left")
    within = jnp.arange(b_g.shape[0]) - group_start
    outs = []
    for a, fill in zip(arrs, fills):
        buf_shape = (nb, cap) + a.shape[1:]
        buf = jnp.full(buf_shape, fill, dtype=a.dtype)
        outs.append(buf.at[b_g, within].set(a[order], mode="drop"))
    counts = jnp.bincount(
        jnp.where(b_g < nb, b_g, 0),
        weights=(b_g < nb).astype(jnp.int32), length=nb,
    ).astype(jnp.int32)
    return outs, counts


def _sort_stage(hi, lo, rows, s_hi, s_lo, *, axis: str, n_shards: int, cap: int):
    """Per-shard body under shard_map. hi/lo/rows: (1, per_shard) blocks
    with sentinel padding; s_hi/s_lo: (n_shards-1,) replicated."""
    hi, lo, rows = hi.reshape(-1), lo.reshape(-1), rows.reshape(-1)
    valid = ~((hi == SENT32) & (lo == SENT32))
    dest = _dest_shard(hi, lo, s_hi, s_lo)
    # Invalid (padding) entries route to a phantom bucket n_shards so they
    # group after every real bucket and never inflate a real rank.
    dest = jnp.where(valid, dest, n_shards)
    (send_hi, send_lo, send_rows), counts = _group_scatter(
        dest, n_shards, cap, (hi, lo, rows), (SENT32, SENT32, 0))
    ok = jnp.all(lax.psum((counts > cap).astype(jnp.int32), axis) == 0)
    # The exchange — rides ICI on real hardware.
    recv_hi = lax.all_to_all(send_hi, axis, split_axis=0, concat_axis=0)
    recv_lo = lax.all_to_all(send_lo, axis, split_axis=0, concat_axis=0)
    recv_rows = lax.all_to_all(send_rows, axis, split_axis=0, concat_axis=0)
    fh, fl, fr = recv_hi.reshape(-1), recv_lo.reshape(-1), recv_rows.reshape(-1)
    # rows as the least-significant tie-break: duplicate keys keep
    # original-index order on EVERY exchange shape (the hierarchical
    # path's arrival order differs from the flat path's, so relying on
    # arrival stability would make tie order topology-dependent)
    final = jnp.lexsort((fr, fl, fh))
    out_hi, out_lo, out_rows = fh[final], fl[final], fr[final]
    n_valid = jnp.sum(~((out_hi == SENT32) & (out_lo == SENT32))).astype(jnp.int32)
    return out_hi[None], out_lo[None], out_rows[None], n_valid[None], ok[None]


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "capacity_factor"))
def sharded_sort_step(
    hi: jax.Array,
    lo: jax.Array,
    rows: jax.Array,
    s_hi: jax.Array,
    s_lo: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "shards",
    capacity_factor: float = 2.0,
):
    """One full sort exchange over the mesh.

    Inputs (n_shards, per_shard), sharded over ``axis`` on dim 0, sentinel-
    padded. Returns (hi, lo, rows, valid_counts, ok): each output shard
    holds its key range ascending with sentinel tail; concatenating shards
    trimmed to their valid counts yields the global order.
    """
    n_shards = mesh.shape[axis]
    per_shard = hi.shape[1]
    cap = min(int(per_shard * capacity_factor / n_shards) + 1, per_shard)
    body = functools.partial(_sort_stage, axis=axis, n_shards=n_shards, cap=cap)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None), P(None), P(None)),
        out_specs=(P(axis, None), P(axis, None), P(axis, None), P(axis), P(axis)),
    )(hi, lo, rows, s_hi, s_lo)


def _sort_stage_payload(
    hi, lo, rows, vals, s_hi, s_lo, *, axis: str, n_shards: int, cap: int
):
    """As ``_sort_stage``, but the exchange also carries a fixed-width
    payload matrix — whole records ride the ICI all_to_all, not just
    keys. vals: (1, per_shard, W) u32 blocks."""
    hi, lo, rows = hi.reshape(-1), lo.reshape(-1), rows.reshape(-1)
    vals = vals.reshape(hi.shape[0], -1)
    w = vals.shape[1]
    valid = ~((hi == SENT32) & (lo == SENT32))
    dest = _dest_shard(hi, lo, s_hi, s_lo)
    dest = jnp.where(valid, dest, n_shards)
    (send_hi, send_lo, send_rows, send_vals), counts = _group_scatter(
        dest, n_shards, cap, (hi, lo, rows, vals), (SENT32, SENT32, 0, 0))
    ok = jnp.all(lax.psum((counts > cap).astype(jnp.int32), axis) == 0)
    recv_hi = lax.all_to_all(send_hi, axis, split_axis=0, concat_axis=0)
    recv_lo = lax.all_to_all(send_lo, axis, split_axis=0, concat_axis=0)
    recv_rows = lax.all_to_all(send_rows, axis, split_axis=0, concat_axis=0)
    recv_vals = lax.all_to_all(send_vals, axis, split_axis=0, concat_axis=0)
    fh, fl, fr = recv_hi.reshape(-1), recv_lo.reshape(-1), recv_rows.reshape(-1)
    fv = recv_vals.reshape(-1, w)
    final = jnp.lexsort((fr, fl, fh))
    out_hi, out_lo, out_rows = fh[final], fl[final], fr[final]
    out_vals = fv[final]
    n_valid = jnp.sum(~((out_hi == SENT32) & (out_lo == SENT32))).astype(jnp.int32)
    return (
        out_hi[None], out_lo[None], out_rows[None], out_vals[None],
        n_valid[None], ok[None],
    )


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "capacity_factor"))
def sharded_sort_payload_step(
    hi, lo, rows, vals, s_hi, s_lo, *,
    mesh: Mesh, axis: str = "shards", capacity_factor: float = 2.0,
):
    """One sort exchange moving keys AND a (n_shards, per_shard, W)
    u32 payload (the packed fixed record columns)."""
    n_shards = mesh.shape[axis]
    per_shard = hi.shape[1]
    cap = min(int(per_shard * capacity_factor / n_shards) + 1, per_shard)
    body = functools.partial(
        _sort_stage_payload, axis=axis, n_shards=n_shards, cap=cap
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(axis, None), P(axis, None), P(axis, None), P(axis, None, None),
            P(None), P(None),
        ),
        out_specs=(
            P(axis, None), P(axis, None), P(axis, None), P(axis, None, None),
            P(axis), P(axis),
        ),
    )(hi, lo, rows, vals, s_hi, s_lo)


# Packed fixed-column layout for the record exchange (all u32):
_PAYLOAD_COLS = (
    "refid", "pos", "flag_mapq", "bin", "next_refid", "next_pos", "tlen",
    # ragged section byte-lengths (name, cigar, seq, qual, tags) — the
    # offset arrays are rebuilt from these by prefix sum after the sort
    "len_name", "len_cig", "len_seq", "len_qual", "len_tag",
)

# Padded-matrix caps: per-record ragged bytes, and the whole matrix
# (pathological batches ride the host fallback instead of OOMing).
_MAX_RAGGED_BYTES = 64 * 1024
_MAX_RAGGED_MATRIX = 2 << 30


def _ragged_lens(batch):
    name_len = np.diff(batch.name_offsets).astype(np.int64)
    cig_len = (np.diff(batch.cigar_offsets) * 4).astype(np.int64)
    seq_len = np.diff(batch.seq_offsets).astype(np.int64)
    tag_len = np.diff(batch.tag_offsets).astype(np.int64)
    return (name_len, cig_len, seq_len, seq_len, tag_len)


def _ragged_scatter(batch, lens, parent_u32: np.ndarray,
                    col_off_words: int) -> None:
    """Pack each record's ragged bytes (name|cigar|seq|qual|tags) into
    ``parent_u32[i, col_off_words:]`` via flat byte indexing into the
    CONTIGUOUS parent (a column-slice view's reshape would silently
    copy). This is what rides the all_to_all — whole records move on
    the mesh, no host-side segment gather afterwards."""
    n = batch.count
    assert parent_u32.flags.c_contiguous
    flat = parent_u32.view(np.uint8).reshape(-1)
    stride = parent_u32.shape[1] * 4
    sources = (
        batch.names,
        np.ascontiguousarray(batch.cigars).view(np.uint8)
        if batch.cigars.size else np.zeros(0, np.uint8),
        batch.seqs, batch.quals, batch.tags,
    )
    start = np.zeros(n, dtype=np.int64)
    row_base = np.arange(n, dtype=np.int64) * stride + col_off_words * 4
    for ln, src in zip(lens, sources):
        tot = int(ln.sum())
        if tot:
            # byte k of record i lands at row_base[i] + start[i] + k
            intra = np.arange(tot, dtype=np.int64) - np.repeat(
                np.concatenate([[0], np.cumsum(ln)[:-1]]), ln)
            dst = np.repeat(row_base + start, ln) + intra
            flat[dst] = np.asarray(src, dtype=np.uint8)[:tot]
        start += ln


def _rebuild_ragged(parent_u32: np.ndarray, col_off_words: int,
                    lens_cols: np.ndarray):
    """Inverse of ``_ragged_scatter`` for the post-exchange rows:
    contiguous (n, W) u32 + (n, 5) lengths → per-section concatenated
    arrays and prefix-sum offsets. Flat index gathers — O(total bytes),
    no (n, width) mask temporaries."""
    n = parent_u32.shape[0]
    parent_u32 = np.ascontiguousarray(parent_u32)
    flat = parent_u32.view(np.uint8).reshape(-1)
    stride = parent_u32.shape[1] * 4
    row_base = np.arange(n, dtype=np.int64) * stride + col_off_words * 4
    start = np.zeros(n, dtype=np.int64)
    out = []
    for s in range(5):
        ln = lens_cols[:, s].astype(np.int64)
        tot = int(ln.sum())
        if tot:
            intra = np.arange(tot, dtype=np.int64) - np.repeat(
                np.concatenate([[0], np.cumsum(ln)[:-1]]), ln)
            src = np.repeat(row_base + start, ln) + intra
            data = flat[src]
        else:
            data = np.zeros(0, np.uint8)
        offs = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(ln, out=offs[1:])
        out.append((data, offs))
        start += ln
    return out


def sharded_sort_read_batch(batch, mesh: Optional[Mesh] = None,
                            axis: str = "shards",
                            capacity_factor: float = 2.0):
    """Coordinate-sort a ``ReadBatch`` with the WHOLE record riding the
    mesh exchange: fixed columns packed as u32 and every ragged column
    (name/cigar/seq/qual/tags) packed into a padded byte matrix, all
    moved by the same all_to_all. Offsets are rebuilt from the carried
    section lengths by prefix sum — there is no host-side segment
    gather on the success path (SURVEY.md §2.9/§3.3:
    the sort shuffle IS the collective).

    Returns (sorted_batch, permutation).
    """
    from disq_tpu.bam.columnar import ReadBatch  # local: avoid cycle
    from disq_tpu.sort.coordinate import coordinate_keys

    mesh = mesh or make_mesh()
    # a two-axis mesh (runtime/multihost.global_mesh's (dcn, shards))
    # routes through the hierarchical two-stage exchange; the contract
    # is explicit: the trailing axis (named by ``axis``) is ICI, the
    # leading one is the DCN/host boundary — a swapped mesh would
    # silently invert the bandwidth layering
    two_level = len(mesh.axis_names) == 2
    if two_level:
        if mesh.axis_names[-1] != axis:
            raise ValueError(
                f"two-axis mesh must be (dcn_axis, {axis!r}) with the "
                f"per-host ICI axis last; got {mesh.axis_names}")
        dcn_axis, ici_axis = mesh.axis_names
        n_shards = mesh.shape[dcn_axis] * mesh.shape[ici_axis]
    else:
        n_shards = mesh.shape[axis]
    n = batch.count
    if n == 0:
        return batch, np.zeros(0, dtype=np.int64)
    keys = coordinate_keys(np.asarray(batch.refid), np.asarray(batch.pos))
    per_shard = -(-n // n_shards)
    padded = per_shard * n_shards
    keys_p = np.full(padded, np.uint64(0xFFFFFFFFFFFFFFFF), dtype=np.uint64)
    keys_p[:n] = keys
    hi_p, lo_p = split_u64_keys(keys_p)
    rows_p = np.zeros(padded, dtype=np.uint32)
    rows_p[:n] = np.arange(n, dtype=np.uint32)
    lens = _ragged_lens(batch)
    rw_bytes = int(sum(lens).max()) if n else 0
    rw_words = -(-rw_bytes // 4)
    nfixed = len(_PAYLOAD_COLS)
    # refuse BEFORE allocating: a pathological record (or sheer batch
    # size) must not OOM building the padded matrix
    if (rw_bytes > _MAX_RAGGED_BYTES
            or padded * (nfixed + rw_words) * 4 > _MAX_RAGGED_MATRIX):
        order = np.argsort(keys, kind="stable")
        return batch.take(order), order
    vals_p = np.zeros((padded, nfixed + rw_words), dtype=np.uint32)
    _ragged_scatter(batch, lens, vals_p, nfixed)
    vals_p[:n, 0] = np.asarray(batch.refid).view(np.uint32)
    vals_p[:n, 1] = np.asarray(batch.pos).view(np.uint32)
    vals_p[:n, 2] = (
        np.asarray(batch.flag).astype(np.uint32)
        | (np.asarray(batch.mapq).astype(np.uint32) << 16)
    )
    vals_p[:n, 3] = np.asarray(batch.bin).astype(np.uint32)
    vals_p[:n, 4] = np.asarray(batch.next_refid).view(np.uint32)
    vals_p[:n, 5] = np.asarray(batch.next_pos).view(np.uint32)
    vals_p[:n, 6] = np.asarray(batch.tlen).view(np.uint32)
    for s in range(5):
        vals_p[:n, 7 + s] = lens[s].astype(np.uint32)
    splitters = sample_splitters(keys, n_shards)
    s_hi, s_lo = split_u64_keys(splitters)
    if two_level:
        kshape = (mesh.shape[dcn_axis], mesh.shape[ici_axis], per_shard)
        shard_k = NamedSharding(mesh, P(dcn_axis, ici_axis, None))
        shard_v = NamedSharding(mesh, P(dcn_axis, ici_axis, None, None))
        repl = NamedSharding(mesh, P())
        step = functools.partial(
            hierarchical_sort_payload_step, mesh=mesh,
            dcn_axis=dcn_axis, ici_axis=ici_axis)
    else:
        kshape = (n_shards, per_shard)
        shard_k = NamedSharding(mesh, P(axis, None))
        shard_v = NamedSharding(mesh, P(axis, None, None))
        repl = NamedSharding(mesh, P(None))
        step = functools.partial(
            sharded_sort_payload_step, mesh=mesh, axis=axis)
    args = (
        jax.device_put(hi_p.reshape(kshape), shard_k),
        jax.device_put(lo_p.reshape(kshape), shard_k),
        jax.device_put(rows_p.reshape(kshape), shard_k),
        jax.device_put(vals_p.reshape(kshape + (-1,)), shard_v),
        jax.device_put(s_hi, repl),
        jax.device_put(s_lo, repl),
    )
    for _ in range(3):
        oh, ol, orows, ovals, counts, ok = step(
            *args, capacity_factor=capacity_factor
        )
        if bool(jnp.all(ok)):
            cnt = np.asarray(counts).reshape(-1)
            ovals_h = np.asarray(ovals).reshape(
                (n_shards, -1) + np.asarray(ovals).shape[-1:])
            orows_h = np.asarray(orows).reshape(n_shards, -1)
            vh = np.concatenate(
                [ovals_h[i, : cnt[i]] for i in range(n_shards)]
            )
            perm = np.concatenate(
                [orows_h[i, : cnt[i]] for i in range(n_shards)]
            ).astype(np.int64)
            # every byte of the record arrived through the all_to_all;
            # rebuild offsets from the carried section lengths
            (names, name_off), (cig_b, _cigoff), (seqs, seq_off), \
                (quals, _qoff), (tags, tag_off) = _rebuild_ragged(
                    vh, nfixed, vh[:, 7:12])
            cigars = np.ascontiguousarray(cig_b).view("<u4")
            cigar_off = np.zeros(len(vh) + 1, dtype=np.int64)
            np.cumsum(vh[:, 8].astype(np.int64) // 4, out=cigar_off[1:])
            sorted_batch = ReadBatch(
                refid=vh[:, 0].view(np.int32),
                pos=vh[:, 1].view(np.int32),
                mapq=(vh[:, 2] >> 16).astype(np.uint8),
                bin=vh[:, 3].astype(np.uint16),
                flag=(vh[:, 2] & 0xFFFF).astype(np.uint16),
                next_refid=vh[:, 4].view(np.int32),
                next_pos=vh[:, 5].view(np.int32),
                tlen=vh[:, 6].view(np.int32),
                name_offsets=name_off, names=names,
                cigar_offsets=cigar_off, cigars=cigars,
                seq_offsets=seq_off, seqs=seqs, quals=quals,
                tag_offsets=tag_off, tags=tags,
            )
            return sorted_batch, perm
        capacity_factor *= 2.0
    # Skew defeated the capacity retries: host fallback.
    from disq_tpu.sort.coordinate import coordinate_sort_batch

    order = np.argsort(keys, kind="stable")
    return coordinate_sort_batch(batch, use_mesh=False), order


def _keys_exchange_host_wrapper(
    keys_np: np.ndarray, n_shards: int, put, run,
    capacity_factor: float, max_retries: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Shared pad/splitter/retry/trim protocol around a keys-only sort
    exchange. ``put(hi, lo, rows, s_hi, s_lo, per_shard)`` places the
    padded host arrays on the mesh; ``run(args, cf)`` executes one
    exchange and returns (hi, lo, rows, counts, ok). Retries with a
    doubled capacity on the (rare, skew-driven) overflow signal, and
    falls back to one host argsort only if skew defeats
    ``max_retries`` capacity doublings."""
    n = len(keys_np)
    if n == 0:
        return keys_np.copy(), np.zeros(0, dtype=np.int64)
    per_shard = -(-n // n_shards)
    padded = per_shard * n_shards
    keys_p = np.full(padded, np.uint64(0xFFFFFFFFFFFFFFFF), dtype=np.uint64)
    keys_p[:n] = keys_np
    hi_p, lo_p = split_u64_keys(keys_p)
    rows_p = np.zeros(padded, dtype=np.uint32)
    rows_p[:n] = np.arange(n, dtype=np.uint32)
    splitters = sample_splitters(keys_np, n_shards)
    s_hi, s_lo = split_u64_keys(splitters)
    args = put(hi_p, lo_p, rows_p, s_hi, s_lo, per_shard)
    for _ in range(max_retries):
        oh, ol, orows, counts, ok = run(args, capacity_factor)
        if bool(jnp.all(ok)):
            oh_h = np.asarray(oh).reshape(n_shards, -1)
            ol_h = np.asarray(ol).reshape(n_shards, -1)
            or_h = np.asarray(orows).reshape(n_shards, -1)
            cnt = np.asarray(counts).reshape(-1)
            out_keys = np.concatenate(
                [
                    (oh_h[i, : cnt[i]].astype(np.uint64) << np.uint64(32))
                    | ol_h[i, : cnt[i]].astype(np.uint64)
                    for i in range(n_shards)
                ]
            )
            out_rows = np.concatenate(
                [or_h[i, : cnt[i]] for i in range(n_shards)]
            ).astype(np.int64)
            return out_keys, out_rows
        capacity_factor *= 2.0
    from disq_tpu.runtime.tracing import counter

    counter("device.mesh.sort_host_fallback").inc()
    order = np.argsort(keys_np, kind="stable")
    return keys_np[order], order


def sharded_coordinate_sort(
    keys_np: np.ndarray,
    mesh: Optional[Mesh] = None,
    axis: str = "shards",
    capacity_factor: float = 2.0,
    max_retries: int = 3,
) -> Tuple[np.ndarray, np.ndarray]:
    """Host convenience wrapper: u64 keys → (sorted keys, permutation)
    over the flat 1-D mesh exchange (protocol in
    ``_keys_exchange_host_wrapper``)."""
    mesh = mesh or make_mesh()
    n_shards = mesh.shape[axis]

    def put(hi_p, lo_p, rows_p, s_hi, s_lo, per_shard):
        shard2d = NamedSharding(mesh, P(axis, None))
        repl = NamedSharding(mesh, P(None))
        return (
            jax.device_put(hi_p.reshape(n_shards, per_shard), shard2d),
            jax.device_put(lo_p.reshape(n_shards, per_shard), shard2d),
            jax.device_put(rows_p.reshape(n_shards, per_shard), shard2d),
            jax.device_put(s_hi, repl),
            jax.device_put(s_lo, repl),
        )

    def run(args, cf):
        return sharded_sort_step(*args, mesh=mesh, axis=axis,
                                 capacity_factor=cf)

    return _keys_exchange_host_wrapper(
        keys_np, n_shards, put, run, capacity_factor, max_retries)


# ---------------------------------------------------------------------------
# Hierarchical (DCN, ICI) exchange — the multi-host layering.


def _two_stage_exchange(
    arrs, fills, s_hi, s_lo, *, dcn_axis: str, ici_axis: str,
    n_hosts: int, per_host: int, cap1: int, cap2: int,
):
    """Two-stage exchange of every array in ``arrs`` (whose first two
    entries must be the hi/lo key columns; trailing dims ride along):
    stage 1 groups by destination HOST and exchanges over the DCN axis
    (each device talks to its same-ordinal peer on every other host —
    n_hosts-1 large messages instead of n_devices-1 small ones crossing
    the network); stage 2 groups by destination device within the host
    and exchanges over the ICI axis. Returns (exchanged arrays, ok)."""
    n_shards = n_hosts * per_host

    def stage(arrs, bucket, nb, cap, axis):
        sends, counts = _group_scatter(bucket, nb, cap, arrs, fills)
        ok = (counts <= cap).all()
        recv = [lax.all_to_all(s, axis, split_axis=0, concat_axis=0)
                for s in sends]
        return [r.reshape((-1,) + r.shape[2:]) for r in recv], ok

    hi, lo = arrs[0], arrs[1]
    valid = ~((hi == SENT32) & (lo == SENT32))
    dest = jnp.where(valid, _dest_shard(hi, lo, s_hi, s_lo), n_shards)
    dest_host = dest // per_host            # phantom -> n_hosts
    arrs1, ok1 = stage(arrs, dest_host, n_hosts, cap1, dcn_axis)

    hi1, lo1 = arrs1[0], arrs1[1]
    valid1 = ~((hi1 == SENT32) & (lo1 == SENT32))
    dest1 = jnp.where(valid1, _dest_shard(hi1, lo1, s_hi, s_lo), n_shards)
    my_host = lax.axis_index(dcn_axis)
    local = jnp.where(
        valid1, dest1 - my_host * per_host, per_host)  # phantom
    final_arrs, ok2 = stage(arrs1, local, per_host, cap2, ici_axis)
    # all-devices ok: reduce over both axes
    ok = lax.psum(
        lax.psum((~ok1 | ~ok2).astype(jnp.int32), dcn_axis), ici_axis) == 0
    return final_arrs, ok


def _hier_geometry(mesh, dcn_axis, ici_axis, per_shard, capacity_factor):
    """(n_hosts, per_host, cap1, cap2) — the single source of the
    two-stage capacity formulas for both step wrappers."""
    n_hosts = mesh.shape[dcn_axis]
    per_host = mesh.shape[ici_axis]
    cap1 = min(int(per_shard * capacity_factor / n_hosts) + 1, per_shard)
    cap2 = min(int(per_shard * capacity_factor / per_host) + 1,
               n_hosts * cap1)
    return n_hosts, per_host, cap1, cap2


def _finish_two_level(fh, fl, fr, ok, fv=None):
    """Final local order + validity count for a two-stage exchange.
    rows tie-break: the two-stage arrival order differs from the flat
    exchange's, so duplicate keys MUST be ordered by original index
    here or multi-host output would diverge from single-host output."""
    final = jnp.lexsort((fr, fl, fh))
    out_hi, out_lo, out_rows = fh[final], fl[final], fr[final]
    n_valid = jnp.sum(
        ~((out_hi == SENT32) & (out_lo == SENT32))).astype(jnp.int32)
    head = (out_hi[None, None], out_lo[None, None], out_rows[None, None])
    if fv is not None:
        head = head + (fv[final][None, None],)
    return head + (n_valid[None, None], ok[None, None])


def _sort_stage_2level(
    hi, lo, rows, s_hi, s_lo, *, dcn_axis: str, ici_axis: str,
    n_hosts: int, per_host: int, cap1: int, cap2: int,
):
    """Keys-only two-stage body under shard_map over a (dcn, shards)
    mesh (``runtime/multihost.global_mesh``). Device (h, j) ends up
    holding global range chunk h*per_host + j, so concatenation order
    matches the flat exchange."""
    (fh, fl, fr), ok = _two_stage_exchange(
        [hi.reshape(-1), lo.reshape(-1), rows.reshape(-1)],
        (SENT32, SENT32, 0), s_hi, s_lo,
        dcn_axis=dcn_axis, ici_axis=ici_axis,
        n_hosts=n_hosts, per_host=per_host, cap1=cap1, cap2=cap2)
    return _finish_two_level(fh, fl, fr, ok)


def _sort_stage_2level_payload(
    hi, lo, rows, vals, s_hi, s_lo, *, dcn_axis: str, ici_axis: str,
    n_hosts: int, per_host: int, cap1: int, cap2: int,
):
    """As ``_sort_stage_2level`` but the WHOLE record (fixed columns +
    padded ragged bytes) rides both stages of the exchange."""
    m = hi.reshape(-1).shape[0]
    (fh, fl, fr, fv), ok = _two_stage_exchange(
        [hi.reshape(-1), lo.reshape(-1), rows.reshape(-1),
         vals.reshape(m, -1)],
        (SENT32, SENT32, 0, 0), s_hi, s_lo,
        dcn_axis=dcn_axis, ici_axis=ici_axis,
        n_hosts=n_hosts, per_host=per_host, cap1=cap1, cap2=cap2)
    return _finish_two_level(fh, fl, fr, ok, fv)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "dcn_axis", "ici_axis", "capacity_factor"))
def hierarchical_sort_step(
    hi, lo, rows, s_hi, s_lo, *, mesh: Mesh,
    dcn_axis: str = "dcn", ici_axis: str = "shards",
    capacity_factor: float = 2.0,
):
    """One two-stage sort exchange over a (dcn, shards) mesh.

    Inputs (n_hosts, per_host, per_shard), sharded over both mesh axes
    on dims 0/1, sentinel-padded like ``sharded_sort_step``. Returns
    (hi, lo, rows, valid_counts, ok) with the same global-order
    concatenation contract as the flat exchange.
    """
    n_hosts, per_host, cap1, cap2 = _hier_geometry(
        mesh, dcn_axis, ici_axis, hi.shape[2], capacity_factor)
    body = functools.partial(
        _sort_stage_2level, dcn_axis=dcn_axis, ici_axis=ici_axis,
        n_hosts=n_hosts, per_host=per_host, cap1=cap1, cap2=cap2)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(dcn_axis, ici_axis, None), P(dcn_axis, ici_axis, None),
            P(dcn_axis, ici_axis, None), P(None), P(None),
        ),
        out_specs=(
            P(dcn_axis, ici_axis, None), P(dcn_axis, ici_axis, None),
            P(dcn_axis, ici_axis, None), P(dcn_axis, ici_axis),
            P(dcn_axis, ici_axis),
        ),
    )(hi, lo, rows, s_hi, s_lo)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "dcn_axis", "ici_axis", "capacity_factor"))
def hierarchical_sort_payload_step(
    hi, lo, rows, vals, s_hi, s_lo, *, mesh: Mesh,
    dcn_axis: str = "dcn", ici_axis: str = "shards",
    capacity_factor: float = 2.0,
):
    """Two-stage exchange moving keys AND the (n_hosts, per_host,
    per_shard, W) u32 record payload — whole records cross DCN once in
    host-sized messages, then fan out over ICI."""
    n_hosts, per_host, cap1, cap2 = _hier_geometry(
        mesh, dcn_axis, ici_axis, hi.shape[2], capacity_factor)
    body = functools.partial(
        _sort_stage_2level_payload, dcn_axis=dcn_axis, ici_axis=ici_axis,
        n_hosts=n_hosts, per_host=per_host, cap1=cap1, cap2=cap2)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(dcn_axis, ici_axis, None), P(dcn_axis, ici_axis, None),
            P(dcn_axis, ici_axis, None),
            P(dcn_axis, ici_axis, None, None), P(None), P(None),
        ),
        out_specs=(
            P(dcn_axis, ici_axis, None), P(dcn_axis, ici_axis, None),
            P(dcn_axis, ici_axis, None),
            P(dcn_axis, ici_axis, None, None),
            P(dcn_axis, ici_axis), P(dcn_axis, ici_axis),
        ),
    )(hi, lo, rows, vals, s_hi, s_lo)


def hierarchical_coordinate_sort(
    keys_np: np.ndarray, mesh: Mesh,
    dcn_axis: str = "dcn", ici_axis: str = "shards",
    capacity_factor: float = 2.0, max_retries: int = 3,
) -> Tuple[np.ndarray, np.ndarray]:
    """u64 keys → (sorted keys, permutation) over a (dcn, shards) mesh
    (see ``runtime/multihost.global_mesh``). Same contract and retry
    protocol as ``sharded_coordinate_sort``; the exchange runs in two
    stages so inter-host traffic crosses DCN once, in host-sized
    messages, and the fan-out to devices rides ICI."""
    n_hosts = mesh.shape[dcn_axis]
    per_host = mesh.shape[ici_axis]
    n_shards = n_hosts * per_host

    def put(hi_p, lo_p, rows_p, s_hi, s_lo, per_shard):
        shard3d = NamedSharding(mesh, P(dcn_axis, ici_axis, None))
        repl = NamedSharding(mesh, P())
        shape3 = (n_hosts, per_host, per_shard)
        return (
            jax.device_put(hi_p.reshape(shape3), shard3d),
            jax.device_put(lo_p.reshape(shape3), shard3d),
            jax.device_put(rows_p.reshape(shape3), shard3d),
            jax.device_put(s_hi, repl),
            jax.device_put(s_lo, repl),
        )

    def run(args, cf):
        return hierarchical_sort_step(
            *args, mesh=mesh, dcn_axis=dcn_axis, ici_axis=ici_axis,
            capacity_factor=cf)

    return _keys_exchange_host_wrapper(
        keys_np, n_shards, put, run, capacity_factor, max_retries)


# ---------------------------------------------------------------------------
# Resident multi-chip sort (ROADMAP item 3 tentpole b): the coordinate
# sort consumed straight from a mesh-sharded ColumnarBatch — keys never
# exist on the host; splitters come from per-device key histograms
# exchanged via lax.psum (the SNIPPETS north-star "psum histogram
# exchange") instead of a host sample.


@functools.lru_cache(maxsize=16)
def _resident_keys_compiled(mesh: Mesh, axis: str, n_shards: int):
    """Key build over batch-sharded refid/pos columns: same formula as
    the single-device ``coord_perm`` (unmapped → 0x7FFFFFFF, bucket
    padding → full-sentinel pairs) plus global row ids, reshaped to the
    (n_shards, per) exchange layout with zero resharding."""
    def mesh_sort_keys(refid, pos, n):
        m = refid.shape[0]
        valid = jnp.arange(m, dtype=jnp.int32) < n
        rid = jnp.where(refid < 0, jnp.uint32(0x7FFFFFFF),
                        refid.astype(jnp.uint32))
        hi = jnp.where(valid, rid, SENT32)
        lo = jnp.where(valid, (pos + 1).astype(jnp.uint32), SENT32)
        rows = jnp.arange(m, dtype=jnp.uint32)
        shp = (n_shards, m // n_shards)
        return hi.reshape(shp), lo.reshape(shp), rows.reshape(shp)

    out_sh = NamedSharding(mesh, P(axis, None))
    return jax.jit(mesh_sort_keys, out_shardings=(out_sh, out_sh, out_sh))


def _key_byte(hi, lo, level: int):
    """Byte ``level`` (7 = most significant) of the (hi, lo) u64 key."""
    if level >= 4:
        return (hi >> jnp.uint32(8 * (level - 4))) & jnp.uint32(0xFF)
    return (lo >> jnp.uint32(8 * level)) & jnp.uint32(0xFF)


@functools.lru_cache(maxsize=64)
def _hist_level_compiled(mesh: Mesh, axis: str, n_cuts: int, level: int):
    """One refinement level of the psum-histogram splitter search:
    every device bins byte ``level`` of its LOCAL keys restricted to
    each cut's already-resolved prefix (levels above ``level``), then
    one ``lax.psum`` over the mesh axis makes the (n_cuts, 256)
    histogram global. Only that small table crosses d2h per level —
    the keys themselves never move."""
    def mesh_sort_hist_level(hi, lo, pref):
        hi, lo = hi.reshape(-1), lo.reshape(-1)
        valid = ~((hi == SENT32) & (lo == SENT32))
        tgt = _key_byte(hi, lo, level).astype(jnp.int32)
        rows = []
        for c in range(n_cuts):
            mask = valid
            for up in range(level + 1, 8):
                mask = mask & (
                    _key_byte(hi, lo, up).astype(jnp.int32) == pref[c, up])
            rows.append(jnp.bincount(
                jnp.where(mask, tgt, 256), length=257)[:256])
        hist = jnp.stack(rows).astype(jnp.int32)
        return lax.psum(hist, axis)

    return jax.jit(shard_map(
        mesh_sort_hist_level, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(None, None)),
        out_specs=P(None, None)))


def _psum_splitters(hi2, lo2, n: int, mesh: Mesh, axis: str,
                    n_shards: int) -> Tuple[np.ndarray, np.ndarray]:
    """Exact quantile splitters for the range partition, computed by
    MSB→LSB byte refinement over psum'd per-device histograms: level 7
    bins the top byte of every key; each cut picks the bin its target
    rank falls in, subtracts the mass below it, and descends — after 8
    levels the accumulated bytes ARE the key value at that rank.
    Monotone by construction (prefix order = key order), so the range
    partition stays valid; returns (s_hi, s_lo) u32 pairs."""
    from disq_tpu.runtime.tracing import count_transfer, counter

    n_cuts = n_shards - 1
    if n_cuts <= 0 or n == 0:
        z = np.zeros(max(n_cuts, 0), dtype=np.uint32)
        return z, z.copy()
    # 0-indexed target ranks among the n valid keys (value-at-quantile,
    # like sample_splitters' sample[qs])
    remaining = np.array(
        [max(0, ((c + 1) * n) // n_shards - 1) for c in range(n_cuts)],
        dtype=np.int64)
    pref = np.full((n_cuts, 8), -1, dtype=np.int32)
    repl = NamedSharding(mesh, P(None, None))
    for level in range(7, -1, -1):
        pref_dev = jax.device_put(jnp.asarray(pref), repl)
        hist = np.asarray(_hist_level_compiled(
            mesh, axis, n_cuts, level)(hi2, lo2, pref_dev))
        # the psum fans each device's (n_cuts, 257) partial over ICI;
        # the prefix table replicates h2d per device
        counter("device.mesh.exchange_bytes").inc(
            (hist.nbytes + 4 * n_cuts) * n_shards)
        count_transfer("h2d", pref.nbytes)
        count_transfer("d2h", hist.nbytes)
        cum = np.cumsum(hist, axis=1)
        for c in range(n_cuts):
            v = int(np.searchsorted(cum[c], remaining[c], side="right"))
            v = min(v, 255)
            pref[c, level] = v
            if v > 0:
                remaining[c] -= int(cum[c, v - 1])
    key = np.zeros(n_cuts, dtype=np.uint64)
    for level in range(8):
        key |= pref[:, level].astype(np.uint64) << np.uint64(8 * level)
    return split_u64_keys(key)


def resident_coordinate_sort(
    refid_dev, pos_dev, n: int, mesh: Mesh,
    axis: Optional[str] = None,
    capacity_factor: float = 2.0, max_retries: int = 3,
) -> np.ndarray:
    """Multi-chip coordinate sort of a RESIDENT batch-sharded column
    pair (tentpole b): key build, psum-histogram splitters, and the
    all_to_all range exchange all run on the mesh — the only d2h is
    the per-level histogram table and the final row-id permutation.

    Byte-identity contract: rows ride as the least-significant lexsort
    component, so duplicate coordinates keep original-index order and
    the returned permutation equals the host
    ``np.argsort(keys, kind="stable")`` exactly — sorted BAM + BAI
    built from it are byte-identical to the single-device output at
    any device count."""
    from disq_tpu.runtime.mesh import MESH_AXIS
    from disq_tpu.runtime.tracing import (
        count_transfer, counter, device_span, span)

    if axis is None:
        axis = MESH_AXIS if MESH_AXIS in mesh.axis_names \
            else mesh.axis_names[0]
    n_shards = int(mesh.shape[axis])
    m = int(refid_dev.shape[0])
    per_shard = m // n_shards
    # staged pre-guard with its mesh placement (4 bytes, replicated) —
    # an implicit reshard inside the guard would raise
    n_arr = jax.device_put(
        jnp.asarray(np.int32(n)), NamedSharding(mesh, P()))
    with device_span("device.kernel", kernel="mesh_sort_keys",
                     records=n, devices=n_shards) as fence:
        with jax.transfer_guard("disallow"):
            hi2, lo2, rows2 = _resident_keys_compiled(
                mesh, axis, n_shards)(refid_dev, pos_dev, n_arr)
            jax.block_until_ready(rows2)
        fence.sync(rows2)
    # eight histogram levels, each a psum and a d2h of its table
    with span("sort.mesh.splitters", records=n, devices=n_shards):
        s_hi_np, s_lo_np = _psum_splitters(
            hi2, lo2, n, mesh, axis, n_shards)
    repl = NamedSharding(mesh, P(None))
    s_hi = jax.device_put(jnp.asarray(s_hi_np), repl)
    s_lo = jax.device_put(jnp.asarray(s_lo_np), repl)
    count_transfer("h2d", s_hi_np.nbytes + s_lo_np.nbytes)
    cf = capacity_factor
    for _ in range(max_retries):
        cap = min(int(per_shard * cf / n_shards) + 1, per_shard)
        with device_span("device.kernel", kernel="mesh_sort_exchange",
                         records=n, devices=n_shards) as fence:
            oh, ol, orows, counts, ok = sharded_sort_step(
                hi2, lo2, rows2, s_hi, s_lo,
                mesh=mesh, axis=axis, capacity_factor=cf)
            fence.sync(counts)
        # send buffers: 3 u32 arrays of (n_shards, cap) per device
        counter("device.mesh.exchange_bytes").inc(
            3 * 4 * cap * n_shards * n_shards)
        if bool(jnp.all(ok)):
            with span("sort.gather", stage="fetch", records=n):
                cnt = np.asarray(counts).reshape(-1)
                or_h = np.asarray(orows).reshape(n_shards, -1)
                count_transfer("d2h", cnt.nbytes + or_h.nbytes)
                return np.concatenate(
                    [or_h[i, : cnt[i]] for i in range(n_shards)]
                ).astype(np.int64)
        cf *= 2.0
    # pathological skew defeated the capacity retries: fetch the key
    # columns once and finish on host (counted — this is the documented
    # fallback, not an implicit copy)
    counter("device.mesh.sort_host_fallback").inc()
    hi_h = np.asarray(hi2).reshape(-1)[:n]
    lo_h = np.asarray(lo2).reshape(-1)[:n]
    count_transfer("d2h", hi_h.nbytes + lo_h.nbytes)
    keys = (hi_h.astype(np.uint64) << np.uint64(32)) | \
        lo_h.astype(np.uint64)
    return np.argsort(keys, kind="stable")
