"""ColumnarBatch — the host-or-device columnar record batch, the
universal currency between sources, device ops, and sinks.

ROADMAP item 1 ("HBM-resident fused decode"): the split decode path
inflates on device, ships the decoded blob d2h, re-parses every record
on host, and re-uploads whichever columns a device op wants — the
round-trip that pins device e2e at ~7.7 MB/s against a far higher
kernel ceiling. ``ColumnarBatch`` removes it: the fused path parses
the decoded blob into fixed columns **on device, in the same launch
chain as the inflate kernels** (``runtime/device_pipeline.
parse_columns_resident``; when the SIMD inflate ran, its still-resident
output chunks are compacted in HBM by ``assemble_device_words`` so the
payload bytes never round-trip), and the parsed columns stay resident:

- **Lazy d2h.** Attribute access (``batch.pos``, ``batch.flag``, …)
  fetches that one column, once — repeated access returns the host
  cache, so ``device.transfer`` bytes are never double-booked. Columns
  a caller never touches never cross d2h; their bytes (and columns
  consumed on device) are booked into ``device.d2h_avoided_bytes`` at
  release — a later host fetch un-marks a consumed column first, so
  nothing is ever counted both as moved and as avoided.
- **Resident consumers.** ``flagstat()`` feeds the device flag column
  straight into the flagstat kernel (zero h2d re-upload);
  ``sort_permutation()`` builds coordinate keys and the lexsort
  permutation on device and fetches only the (n,) i32 order — the u64
  key vectors never move. ``ops/depth.py`` and every existing
  ``ReadBatch`` consumer work unchanged through the lazy properties.
  ``alignment_ends()`` / ``reference_lengths()`` come from a
  CIGAR-only pass over the record bytes, cached on the batch: depth,
  pileup and the interval filters never host-parse a record.
- **Host interop.** Ragged columns (names / cigars / seqs / quals /
  tags) come lazily from the host copy of the decoded blob (which the
  read path holds anyway for CRC verification and the record-offset
  scan); ``to_read_batch()`` / ``take()`` / ``concat()`` materialize a
  plain ``ReadBatch`` when host-side work (sorting gathers, sinks)
  needs it. ``concat`` of all-device batches stays device-backed.

Enablement: ``DisqOptions.resident_decode`` /
``ReadsStorage.resident_decode()`` / env ``DISQ_TPU_RESIDENT_DECODE``.
Disabled (the default), sources return plain host ``ReadBatch`` objects
and this module allocates nothing on device —
``scripts/check_overhead.py`` asserts ``device_batches_built() == 0``
on that path.
"""

from __future__ import annotations

import functools
import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

from disq_tpu.bam.columnar import ReadBatch
from disq_tpu.util import bucket_pow2 as _bucket_n

# The 12 fields the Pallas parse kernel emits (ops/parse._FIELD_ORDER);
# the 8 ReadBatch fixed columns are a subset with narrowed dtypes.
PARSE_FIELDS = (
    "block_size", "refid", "pos", "l_read_name", "mapq", "bin",
    "n_cigar", "flag", "l_seq", "next_refid", "next_pos", "tlen",
)
FIXED_COLUMNS = ("refid", "pos", "mapq", "bin", "flag",
                 "next_refid", "next_pos", "tlen")
_COL_DTYPE = {
    "refid": np.int32, "pos": np.int32, "mapq": np.uint8,
    "bin": np.uint16, "flag": np.uint16, "next_refid": np.int32,
    "next_pos": np.int32, "tlen": np.int32,
}
_RAGGED = ("name_offsets", "names", "cigar_offsets", "cigars",
           "seq_offsets", "seqs", "quals", "tag_offsets", "tags")


_stats_lock = threading.Lock()
_device_batches_built = 0
_resident_live_bytes = 0


def device_batches_built() -> int:
    """Process-lifetime count of device-backed builds — the
    check_overhead invariant: 0 whenever resident decode is off."""
    with _stats_lock:
        return _device_batches_built


def _note_build(resident_delta: int) -> None:
    global _device_batches_built, _resident_live_bytes
    from disq_tpu.runtime.tracing import observe_gauge

    with _stats_lock:
        if resident_delta >= 0:
            _device_batches_built += 1
        _resident_live_bytes = max(
            0, _resident_live_bytes + resident_delta)
        live = _resident_live_bytes
    observe_gauge("columnar.batch.resident_bytes", live)


def resident_decode_enabled(storage) -> bool:
    """True when the fused HBM-resident decode path is on for this
    storage: ``DisqOptions.resident_decode`` or the
    ``DISQ_TPU_RESIDENT_DECODE`` env knob."""
    opts = getattr(storage, "_options", None)
    if opts is not None and getattr(opts, "resident_decode", False):
        return True
    from disq_tpu.runtime.debug import env_flag

    return env_flag("DISQ_TPU_RESIDENT_DECODE")


@functools.lru_cache(maxsize=1)
def _jax_fns():
    """Lazily-built jitted helpers (this module must import without
    jax on the disabled path)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def record_check(refid, next_refid, lrn, ncig, lseq, lens, n_ref):
        # Eager corrupt-record detection mirroring the host parser
        # (bam/codec.decode_records): impossible refIDs (when n_ref is
        # known, i.e. >= 0) or record sections overflowing the record
        # length. Restructured as slack comparisons so every term stays
        # inside i32 (lens < 2^31 is guaranteed by the from_blob size
        # guard). Returns one boolean — the only d2h of the check.
        neg = lseq < 0
        over = lseq > lens
        lseq_c = jnp.clip(lseq, 0, lens)
        head = 36 + lrn + 4 * ncig + (lseq_c + 1) // 2
        bad = neg | over | (head > (lens - lseq_c))
        refbad = ((refid >= n_ref) | (refid < -1)
                  | (next_refid >= n_ref) | (next_refid < -1))
        bad = bad | ((n_ref >= 0) & refbad)
        return jnp.any(bad)

    @jax.jit
    def coord_perm(refid, pos, n):
        # Coordinate keys + stable lexsort on device. Padded tail
        # entries (the bucket-padded parse duplicates the last record)
        # get a key above every real one — unmapped maps to 0x7FFFFFFF
        # — so order[:n] is exactly the real permutation.
        m = refid.shape[0]
        valid = jnp.arange(m, dtype=jnp.int32) < n
        rid = jnp.where(refid < 0, jnp.uint32(0x7FFFFFFF),
                        refid.astype(jnp.uint32))
        hi = jnp.where(valid, rid, jnp.uint32(0xFFFFFFFF))
        lo = (pos + 1).astype(jnp.uint32)
        return jnp.lexsort((lo, hi)).astype(jnp.int32)

    @jax.jit
    def place(out, part, at):
        # every column of ``part`` written into ``out``'s from ``at``
        # on, whole with its padding: what comes next overwrites it
        def one(col, new):
            wide = jnp.pad(col, (0, new.shape[0]))
            return jax.lax.dynamic_update_slice(
                wide, new, (at,))[: col.shape[0]]

        return {name: one(out[name], part[name]) for name in out}

    @jax.jit
    def edge_fill(out, n):
        # the tail past ``n`` duplicates the last record, as the
        # bucket-padded parse leaves it
        return {name: jnp.where(jnp.arange(col.shape[0]) < n, col,
                                col[n - 1])
                for name, col in out.items()}

    return {"jax": jax, "jnp": jnp, "coord_perm": coord_perm,
            "record_check": record_check, "place": place,
            "edge_fill": edge_fill}


class _SpanCache:
    """The ``(alignment ends i32, reference lengths i64)`` of a record
    blob, in the blob's own (source) order, and the lock under which
    they are computed once. One holder per blob: ``permuted()`` views
    share their source's, so whichever asks first pays the CIGAR pass
    for all of them."""

    __slots__ = ("lock", "spans")

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.spans = None

    def keep(self, ends: np.ndarray, reflen: np.ndarray) -> None:
        # handed out as they are kept: a caller's in-place edit must
        # raise, not move every later answer
        ends.flags.writeable = reflen.flags.writeable = False
        self.spans = (ends, reflen)


class ColumnarBatch:
    """N alignment records with fixed columns resident on device (or a
    thin wrapper over a host ``ReadBatch``). Duck-compatible with
    ``ReadBatch``: every column attribute returns host numpy (lazily
    fetched, cached), so existing consumers work unchanged while
    device ops consume the resident columns without re-upload."""

    def __init__(self) -> None:
        # built via from_blob / from_host — never directly
        self._n = 0
        self._dev: Optional[Dict[str, object]] = None
        self._blob: Optional[np.ndarray] = None
        self._blob_parts: Optional[List[np.ndarray]] = None
        self._offsets: Optional[np.ndarray] = None
        # logical record -> record of the held blob (None = the blob's
        # own order, all of it): a permutation after ``permuted()``, a
        # selection of ``_n`` of the blob's records after a ``filter``
        # that kept its source's bytes. The device columns are already
        # gathered by it; every reader of the bytes indexes
        # ``_offsets`` through it
        self._order: Optional[np.ndarray] = None
        self._n_ref: Optional[int] = None
        # batch-axis device mesh (runtime/mesh.py) the resident columns
        # are sharded over; None = plain single-device residency.
        # Carried through permuted()/concat() so every downstream
        # consumer (sort, flagstat, depth, encode) sees one sharded
        # program instead of re-deriving placement per stage.
        self._mesh = None
        self._cache: Dict[str, np.ndarray] = {}
        self._consumed: Dict[str, int] = {}
        self._ragged_rb: Optional[ReadBatch] = None
        self._rb: Optional[ReadBatch] = None
        # host data about the record bytes, not about the device
        # columns: release() and or_flags() leave it alone
        self._span_cache = _SpanCache()
        # what the last ask for ends / reference lengths was answered
        # from: cached | ragged | cigar | host (``_span``), or swept
        # (``clips_and_scores``)
        self.ends_source: Optional[str] = None
        self._hbm = 0
        self._released = False
        # True when no other batch holds this batch's record blob (a
        # filter's compacted copy, the join of a concat's parts, an
        # earlier copy-on-write): ``or_flags`` may then patch it in
        # place. Handing the blob to a ``permuted()`` or filtered
        # child clears it on both sides
        self._blob_owned = False
        # lazy state is shared across threads (writer pipeline workers
        # slice the same dataset batch concurrently): the lock makes
        # each lazy build/fetch happen once — unlocked, W workers
        # would each host-parse the whole blob and concurrent fetches
        # of one column would double-book device.transfer
        self._lock = threading.RLock()

    # -- construction -------------------------------------------------------

    @classmethod
    def from_host(cls, batch: ReadBatch) -> "ColumnarBatch":
        self = cls()
        self._n = batch.count
        self._rb = batch
        self._ragged_rb = batch
        return self

    @classmethod
    def from_blob(
        cls,
        blob: np.ndarray,
        offsets: np.ndarray,
        n_ref: Optional[int] = None,
        device_words=None,
        origin: int = 0,
        interpret: Optional[bool] = None,
        mesh=None,
        coarse: bool = False,
        staged: Optional[np.ndarray] = None,
    ) -> "ColumnarBatch":
        """Fused device build: one upload (skipped when
        ``device_words`` carries the inflate kernels' still-resident
        output) + one gather/parse launch chain; fixed columns stay in
        HBM until fetched or released.

        ``blob``/``offsets`` are the host record bytes + record-offset
        manifest (held for ragged columns and identity with the host
        parser); ``origin`` rebases the offsets into ``device_words``,
        or into ``staged`` (the decode service's padded buffer, of
        which ``blob`` is the bytes from ``origin`` on: uploaded whole,
        not copied), when that blob covers more than the record range;
        ``coarse`` uploads the blob at ``parse_columns_resident``'s
        coarse shapes."""
        from disq_tpu.runtime.device_pipeline import parse_columns_resident
        from disq_tpu.runtime.tracing import span

        n = len(offsets) - 1
        if n <= 0:
            return cls.from_host(ReadBatch.empty())
        if interpret is None:
            from disq_tpu.util import pallas_interpret

            interpret = pallas_interpret()
        self = cls()
        self._n = n
        self._blob = blob
        self._offsets = np.asarray(offsets, dtype=np.int64)
        self._n_ref = n_ref
        self._mesh = mesh
        with span("columnar.batch.build", records=n,
                  bytes=int(offsets[-1])):
            # origin rebases offsets into a full-shard blob (the
            # device's, or the service's staged host buffer); the copy
            # path stages exactly the record slice, so its offsets are
            # already correct
            whole = device_words is not None or staged is not None
            cols, _word_bytes, _ = parse_columns_resident(
                blob, self._offsets, words_dev=device_words,
                origin=origin if whole else 0,
                interpret=interpret, mesh=mesh, coarse=coarse,
                staged=staged)
            # keep only the 8 reachable fixed columns resident (plus
            # next_refid for validation below); the 4 parse-only
            # length fields are derivable from the ragged offsets and
            # would pin 50% extra HBM with no consumer
            self._dev = {k: cols[k] for k in FIXED_COLUMNS}
        # Residency: the fixed columns (bucket-padded i32). The word
        # blob itself is released with the launch chain — nothing
        # downstream reads it on device (ragged comes from the host
        # copy the CRC/scan already required).
        padded = int(cols["pos"].shape[0])
        self._hbm = len(self._dev) * padded * 4
        from disq_tpu.runtime.tracing import track_hbm

        track_hbm(self._hbm)
        _note_build(self._hbm)
        # same eager corrupt-record contract as decode_records: a
        # chain-valid shard with impossible refIDs OR record sections
        # overflowing their record (the host parser's "sections exceed
        # block_size" bound) must fail HERE, so the source's
        # except-ValueError salvage path applies exactly as on the host
        # route. The check is a device reduction — one boolean crosses
        # d2h; padded lanes get a maximal record length so they never
        # flag.
        from disq_tpu.runtime.tracing import count_transfer

        rec_len = np.empty(padded, np.int32)
        rec_len[:n] = self._offsets[1:] - self._offsets[:-1]
        rec_len[n:] = np.iinfo(np.int32).max
        count_transfer("h2d", rec_len.nbytes)
        fns = _jax_fns()
        bad = fns["record_check"](
            cols["refid"], cols["next_refid"], cols["l_read_name"],
            cols["n_cigar"], cols["l_seq"], rec_len,
            np.int32(-1 if n_ref is None else n_ref))
        if bool(bad):
            self._release(book_avoided=False)
            from disq_tpu.bam.codec import decode_records

            # the host parser is the authority on the error (exact
            # message + record coordinates); if the device predicate
            # was somehow conservative, serve its host batch instead
            host = decode_records(blob, self._offsets, n_ref=n_ref)
            return cls.from_host(host)
        return self

    # -- identity -----------------------------------------------------------

    @property
    def device_backed(self) -> bool:
        return self._dev is not None

    @property
    def mesh(self):
        """The batch-axis mesh the resident columns shard over, or
        None (single-device residency / host-backed)."""
        return self._mesh if self._dev is not None else None

    @property
    def count(self) -> int:
        return self._n

    def __len__(self) -> int:
        return self._n

    # -- lazy column access -------------------------------------------------

    def _fetch_col(self, name: str) -> np.ndarray:
        arr = self._cache.get(name)
        if arr is not None:
            return arr
        with self._lock:
            arr = self._cache.get(name)
            if arr is not None:  # lost the race: fetched once, by them
                return arr
            if self._dev is None:
                if self._rb is not None:
                    return getattr(self._rb, name)
                # released device columns, but the host blob is still
                # held for ragged parsing — rebuild from it instead of
                # failing only on fixed-column access
                if self._blob is not None or self._blob_parts:
                    return getattr(self._ragged_source(), name)
                raise RuntimeError(
                    f"column {name!r} of a released ColumnarBatch — "
                    "fetch before release(), or keep the batch alive")
            from disq_tpu.runtime.tracing import count_transfer, span

            nbytes = 4 * self._n
            with span("columnar.batch.fetch", column=name, bytes=nbytes):
                raw = np.asarray(self._dev[name][: self._n])
            count_transfer("d2h", raw.nbytes)
            dt = _COL_DTYPE.get(name)
            arr = (raw.astype(dt)
                   if dt is not None and raw.dtype != dt else raw)
            self._cache[name] = arr
            # a column that DID cross d2h after all is no longer avoided
            # — consumption marks are provisional until release books
            # them
            self._consumed.pop(name, None)
            return arr

    def _consume_on_device(self, key: str, nbytes: int) -> None:
        """Mark a column (or derived result) consumed on device without
        a host fetch — d2h the split path would have paid. Booked into
        ``device.d2h_avoided_bytes`` at release (not here), so a later
        host fetch of the same column un-marks it instead of
        double-counting."""
        with self._lock:
            if key in self._consumed or key in self._cache:
                return
            self._consumed[key] = nbytes

    # fixed columns (device-parsed; lazily fetched)
    refid = property(lambda self: self._fetch_col("refid"))
    pos = property(lambda self: self._fetch_col("pos"))
    mapq = property(lambda self: self._fetch_col("mapq"))
    bin = property(lambda self: self._fetch_col("bin"))
    flag = property(lambda self: self._fetch_col("flag"))
    next_refid = property(lambda self: self._fetch_col("next_refid"))
    next_pos = property(lambda self: self._fetch_col("next_pos"))
    tlen = property(lambda self: self._fetch_col("tlen"))

    # -- ragged columns (host blob, parsed lazily once) ---------------------

    def _host_blob(self) -> Optional[np.ndarray]:
        """The record bytes as one host array, joining a concat's
        per-shard parts on first need (under the instance lock). The
        join is a fresh array that only this batch holds."""
        with self._lock:
            if self._blob is None and self._blob_parts is not None:
                from disq_tpu.runtime.tracing import span

                with span("columnar.batch.join",
                          parts=len(self._blob_parts),
                          bytes=sum(map(len, self._blob_parts))):
                    self._blob = np.concatenate(self._blob_parts)
                self._blob_parts = None
                self._blob_owned = True
            return self._blob

    def _ragged_source(self) -> ReadBatch:
        if self._ragged_rb is None:
            with self._lock:
                if self._ragged_rb is None:
                    from disq_tpu.bam.codec import decode_records
                    from disq_tpu.runtime.tracing import counter, span

                    with span("columnar.batch.materialize",
                              records=self._n, how="parse") as labels:
                        # this batch's records alone, in its order
                        blob, offsets = self._logical_bytes()
                        labels["bytes"] = len(blob)
                        rb = decode_records(
                            blob, offsets, n_ref=self._n_ref)
                    self._ragged_rb = rb
                    # the operator-suite resident-leg witness: a fully
                    # resident chain never host-parses records
                    counter("columnar.batch.materializations").inc()
        return self._ragged_rb

    def __getattr__(self, name: str):
        if name in _RAGGED:
            return getattr(self._ragged_source(), name)
        raise AttributeError(name)

    # -- pickling (ReadLedger crash-resume spills) --------------------------

    def __reduce__(self):
        """Spill as HOST data, never as device arrays: pickling the
        resident columns would be an uncounted implicit d2h, and the
        restored copy would re-book their avoidance on release. A
        device-backed batch spills its records' bytes in logical order
        (a pending order folded into them: a selection's source blob
        is not spilled whole) and re-runs the fused build on load (a
        resumed resident read stays device-backed with fresh, correct
        accounting); a host-backed one spills its plain ``ReadBatch``."""
        if self._blob is not None or self._blob_parts is not None:
            return (_rebuild_from_blob,
                    (*self._logical_bytes(), self._n_ref))
        return (_rebuild_from_host, (self.to_read_batch(),))

    def _logical_bytes(self):
        """``(record bytes, (n + 1,) offsets)`` in logical order: the
        held blob as it is, or, under a pending order, one gather by
        it (the copy a compaction would have made)."""
        blob = self._host_blob()
        if self._order is None:
            return blob, self._offsets
        from disq_tpu.bam.columnar import segment_gather

        return segment_gather(blob, self._offsets, self._order)

    # -- ReadBatch interop --------------------------------------------------

    def to_read_batch(self) -> ReadBatch:
        """Materialize as one plain ``ReadBatch``. The ragged columns
        force the full host parse anyway, and its fixed columns are
        byte-equal to the device-parsed ones (the identity contract) —
        so materialization takes them from the host parse instead of
        paying a pointless 32 B/record d2h fetch; columns sourced this
        way are cached as fetched so ``release`` books them neither as
        transferred nor as avoided (the host did the work, no transfer
        was saved)."""
        if self._rb is None:
            with self._lock:
                if self._rb is not None:
                    return self._rb
                rag = self._ragged_source()
                if self._dev is not None:
                    for name in FIXED_COLUMNS:
                        if name not in self._cache:
                            self._cache[name] = getattr(rag, name)
                            self._consumed.pop(name, None)
                self._rb = rag
        return self._rb

    def take(self, indices: np.ndarray) -> ReadBatch:
        return self.to_read_batch().take(indices)

    def filter(self, mask: np.ndarray) -> "ReadBatch | ColumnarBatch":
        """Keep records where ``mask`` is true. Device-backed batches
        compact ON DEVICE (operator-suite tentpole a): the fixed
        columns are gathered by the kept indices in HBM — records the
        mask drops never cross d2h — and the result is a device-backed
        batch in one of two forms (``_compact_device``): the kept
        records' bytes copied into a blob of its own, in source order
        with no pending order, or the source's blob shared under a
        pending order that selects the kept records. Either way
        concat / pickle / ``encode_source`` / ``encoded_slice`` give
        the kept records in logical order. Host-backed batches
        materialize as before."""
        mask = np.asarray(mask)
        if self._dev_snapshot() is None or self._offsets is None:
            return self.to_read_batch().filter(mask)
        return self._compact_device(np.nonzero(mask)[0])

    def _gathered(self, dev, idx: np.ndarray) -> "ColumnarBatch":
        """A new device-backed batch of the logical records ``idx``:
        the fixed columns gathered on device (one small index upload,
        bucket-padded by its last entry; zero column round-trips), on
        this batch's mesh. It holds no record bytes yet."""
        from disq_tpu.runtime.tracing import count_transfer, track_hbm

        k = len(idx)
        pad = _bucket_n(k) - k
        idx_host = np.empty(k + pad, np.int32)
        idx_host[:k] = idx
        idx_host[k:] = idx[-1] if k else 0
        count_transfer("h2d", idx_host.nbytes)
        idx_dev = _jax_fns()["jnp"].asarray(idx_host)
        out = ColumnarBatch()
        out._n = k
        out._n_ref = self._n_ref
        out._dev = {name: dev[name][idx_dev] for name in FIXED_COLUMNS}
        if self._mesh is not None:
            # the gather may have collapsed placement — restore the
            # canonical batch sharding so downstream stages keep the
            # one-sharded-program shape (moved bytes are booked into
            # device.mesh.reshard_bytes, not h2d/d2h: nothing crosses
            # the host)
            from disq_tpu.runtime.mesh import mesh_put

            out._dev = {name: mesh_put(col, self._mesh)
                        for name, col in out._dev.items()}
            out._mesh = self._mesh
        out._hbm = len(out._dev) * (k + pad) * 4
        track_hbm(out._hbm)
        _note_build(out._hbm)
        return out

    def _share_bytes(self, out: "ColumnarBatch", src: np.ndarray) -> None:
        """``out`` holds this batch's record bytes, offsets and span
        cache as they are, under the pending order ``src`` (logical
        record of ``out`` -> record of the blob). Neither side owns
        the bytes from here on."""
        with self._lock:
            out._blob = self._blob
            out._blob_parts = self._blob_parts
            self._blob_owned = False
        out._offsets = self._offsets
        out._span_cache = self._span_cache
        out._order = src

    def _compact_device(self, keep: np.ndarray) -> "ReadBatch | ColumnarBatch":
        """Device compaction gather behind ``filter``: ``keep`` holds
        the kept logical indices, ascending. The 8 fixed columns are
        gathered on the device either way; the record bytes take one
        of two forms, said by the span's and the counter's ``how``:

        - ``copied``: the kept records' bytes gathered into a fresh
          blob that the result owns, source order, no pending order;
        - ``deferred``: no byte moves. The result shares the blob, the
          offsets and the span cache, as ``permuted()``'s does, under
          the pending order ``order[keep]``; whoever wants the bytes
          in order (the write's ``encoded_slice``, a concat, a spill)
          gathers by it, once.

        Deferred when the copy would free less than it costs: a
        deferred result pins the whole blob for as long as it lives,
        so a copy of ``k`` records frees ``held - k`` records' bytes,
        and that is no more than it writes when ``2 * k >= held``
        (``held`` the records the blob holds, which is this batch's
        count unless it is deferred itself). The waste is then at
        most as large as the answer. A filter that keeps one record
        in eight (an interval read's) would pin eight times its answer
        and keeps copying. What the code can see decides, not an
        option."""
        from disq_tpu.runtime.tracing import counter, span

        dev = self._dev_snapshot()
        keep = np.asarray(keep, dtype=np.int64)
        k = len(keep)
        if k == 0:
            return ColumnarBatch.from_host(ReadBatch.empty())
        with span("columnar.batch.compact", records=self._n,
                  kept=k) as labels:
            # logical -> blob record index via any pending order
            src = self._order[keep] if self._order is not None else keep
            out = self._gathered(dev, keep)
            offsets = self._offsets
            if 2 * k >= len(offsets) - 1:
                how, moved = "deferred", 0
                self._share_bytes(out, src)
                kept_bytes = int((offsets[src + 1] - offsets[src]).sum())
            else:
                from disq_tpu.bam.columnar import segment_gather

                how = "copied"
                out._blob, out._offsets = segment_gather(
                    self._host_blob(), offsets, src)
                out._blob_owned = True
                spans = self._span_cache.spans
                if spans is not None:
                    out._span_cache.keep(spans[0][src], spans[1][src])
                kept_bytes = moved = len(out._blob)
            labels["how"], labels["bytes"] = how, moved
            # the kept records' bytes: gathered | left where they were
            counter("columnar.batch.compact_bytes").inc(kept_bytes, how=how)
        return out

    def or_flags(self, mask: np.ndarray, bits: int = 0x400) -> None:
        """OR ``bits`` into the flag of every record where ``mask`` is
        true — duplicate marking's write-back. Three synchronized
        views update: the resident flag column (in HBM, one small mask
        upload), the host record blob's flag bytes (copy-on-write
        unless this batch owns its blob, which a batch that shares
        its source's never does: the source's bytes and a sibling's
        are not patched; span ``columnar.batch.patch``), and any host
        caches (dropped so the next fetch re-derives). The blob patch
        is what makes the resident write path's output byte-identical
        to a host-marked file."""
        idx = np.nonzero(np.asarray(mask))[0]
        if len(idx) == 0:
            return
        lo_b, hi_b = bits & 0xFF, (bits >> 8) & 0xFF
        with self._lock:
            if self._offsets is not None:
                from disq_tpu.runtime.tracing import span

                with span("columnar.batch.patch",
                          records=len(idx)) as labels:
                    blob = self._host_blob()
                    labels["bytes"] = len(blob)
                    labels["copied"] = int(not self._blob_owned)
                    if not self._blob_owned:
                        blob = blob.copy()
                        self._blob_owned = True
                    src = (self._order[idx]
                           if self._order is not None else idx)
                    off = self._offsets[src]
                    if lo_b:
                        blob[off + 18] |= np.uint8(lo_b)
                    if hi_b:
                        blob[off + 19] |= np.uint8(hi_b)
                    self._blob = blob
            dev = self._dev
            if dev is not None:
                from disq_tpu.runtime.tracing import count_transfer

                fns = _jax_fns()
                jnp = fns["jnp"]
                padded = int(dev["flag"].shape[0])
                m = np.zeros(padded, np.int32)
                m[idx] = 1
                count_transfer("h2d", m.nbytes)
                new_flag = jnp.where(
                    jnp.asarray(m) != 0, dev["flag"] | bits, dev["flag"])
                if self._mesh is not None:
                    from disq_tpu.runtime.mesh import mesh_put

                    new_flag = mesh_put(new_flag, self._mesh)
                dev["flag"] = new_flag
            elif self._rb is not None:
                self._rb.flag[idx] |= np.uint16(bits)
            # host-side derived views are stale now
            self._cache.pop("flag", None)
            if self._ragged_rb is not None and self._offsets is not None:
                self._ragged_rb = None
                self._rb = None

    def slice(self, start: int, stop: int) -> ReadBatch:
        return self.to_read_batch().slice(start, stop)

    # decoded views / derived (delegate to the materialized forms)
    def name(self, i: int) -> str:
        return self._ragged_source().name(i)

    def sequence(self, i: int) -> str:
        return self._ragged_source().sequence(i)

    def cigar_string(self, i: int) -> str:
        return self._ragged_source().cigar_string(i)

    def qual_string(self, i: int) -> str:
        return self._ragged_source().qual_string(i)

    def reference_lengths(self) -> np.ndarray:
        return self._span(1)

    def alignment_ends(self, lo: int = 0,
                       hi: Optional[int] = None) -> np.ndarray:
        """0-based exclusive end positions, ``ReadBatch``'s values. A
        batch that holds record bytes derives them from the CIGAR op
        words alone, part by part, and keeps them (``_span``): no blob
        join, no host record parse. ``lo``/``hi`` ask for the logical
        records ``[lo, hi)`` alone (a write shard's): what is kept is
        then indexed by that stretch of a pending order, not by all
        of it."""
        return self._span(0, lo, hi)

    def _span(self, which: int, lo: int = 0,
              hi: Optional[int] = None) -> np.ndarray:
        """The ends i32 (0) or the reference lengths i64 (1) of the
        logical records ``[lo, hi)`` (all of them by default), from
        what the batch holds, in this order: an earlier CIGAR pass
        (``cached``), a host parse some consumer already paid for
        (``ragged``; a host-built batch's own columns, ``host``), the
        record bytes (``cigar``). Notes which on ``ends_source``."""
        cut = slice(lo, self._n if hi is None else hi)
        spans = self._span_cache.spans
        if spans is not None:
            self.ends_source = "cached"
        else:
            rb = self._ragged_rb
            # already in logical order, and free; a part of a batch
            # that holds bytes takes the CIGAR pass, which is kept,
            # over a pass of the parse's columns a part
            if rb is not None and (self._offsets is None
                                   or cut == slice(0, self._n)):
                self.ends_source = (
                    "ragged" if self._offsets is not None else "host")
                return (rb.reference_lengths() if which
                        else rb.alignment_ends())[cut]
            spans = self._spans_from_cigar()
            self.ends_source = "cigar"
        col = spans[which]
        return col[cut] if self._order is None else col[self._order[cut]]

    def _spans_from_cigar(self):
        """The CIGAR pass over the record bytes as they are held — one
        blob or a concat's un-joined parts, whose record ranges the
        rebased offsets give — cached in source order."""
        from disq_tpu import native
        from disq_tpu.ops.markdup import reference_spans_from_blob
        from disq_tpu.runtime.tracing import counter, span

        cache = self._span_cache
        with cache.lock:
            if cache.spans is not None:
                return cache.spans
            with self._lock:
                parts = (self._blob_parts if self._blob is None
                         else [self._blob])
            offsets = self._offsets
            n = len(offsets) - 1
            with span("columnar.batch.ends", records=n,
                      bytes=int(offsets[-1] - offsets[0])) as labels:
                pos = np.empty(n, np.int32)
                reflen = np.empty(n, np.int64)
                lo = base = ops = 0
                for part in parts:
                    hi = min(n, int(np.searchsorted(
                        offsets, base + len(part))))
                    try:
                        pos[lo:hi], reflen[lo:hi], walked = (
                            reference_spans_from_blob(
                                part, offsets[lo: hi + 1], base))
                        ops += walked
                    except ValueError as e:
                        raise ValueError(
                            f"records from {lo}: {e}") from None
                    lo, base = hi, base + len(part)
                if lo != n:
                    raise ValueError(
                        f"record {lo}: record offsets past the blob")
                ends = pos + np.maximum(reflen, 1).astype(np.int32)
                labels["source"] = (
                    "native" if native.loaded() else "numpy")
            counter("columnar.batch.ends_from_cigar").inc(n)
            counter("columnar.batch.cigar_ops").inc(ops)
            cache.keep(ends, reflen)
            return cache.spans

    def clips_and_scores(self):
        """``(reference lengths, leading clips, trailing clips,
        scores)``, i64 each, in logical order: duplicate marking's
        inputs from one sweep over the record bytes
        (``ops/markdup.key_sweep_from_blob``), no host record parse.
        The sweep reads the whole blob, in its own order, also where a
        pending order selects some of its records: what it leaves in
        the span cache serves the source and every view of it.
        The reference lengths are the span cache's where it holds them
        (``ends_source`` then says ``cached``); else the sweep's are
        kept there with their ends (``swept``). Clips and scores are
        not kept: markdup is their only reader."""
        from disq_tpu.ops.markdup import key_sweep_from_blob

        pos, reflen, lead, trail, score = key_sweep_from_blob(
            self._host_blob(), self._offsets)
        cache = self._span_cache
        with cache.lock:
            self.ends_source = "cached"
            if cache.spans is None:
                cache.keep(pos + np.maximum(reflen, 1).astype(np.int32),
                           reflen)
                self.ends_source = "swept"
            reflen = cache.spans[1]
        out = (reflen, lead, trail, score)
        return out if self._order is None \
            else tuple(a[self._order] for a in out)

    # -- resident device consumers ------------------------------------------

    def _dev_snapshot(self) -> Optional[Dict[str, object]]:
        """The device column dict, taken under the lock — safe to use
        after a concurrent ``release()`` (jax arrays are immutable;
        release only drops references), so kernel launches run
        lock-free and never stall other lazy-column access."""
        with self._lock:
            return self._dev

    def device_columns(self) -> Dict[str, object]:
        """The fixed columns as device arrays in ReadBatch dtypes —
        zero transfers (the resident form IS the device form)."""
        dev = self._dev_snapshot()
        if dev is None:
            raise ValueError("host-backed batch has no device columns")
        jnp = _jax_fns()["jnp"]
        return {
            name: dev[name][: self._n].astype(
                jnp.dtype(_COL_DTYPE[name]))
            for name in FIXED_COLUMNS
        }

    def interval_operands(self):
        """``(refid, pos, ends)`` as device arrays of the columns'
        padded length, for a kernel that holds records to reference
        spans: the two resident columns as they are, and the exclusive
        alignment ends uploaded beside them (4 B a record; from the
        CIGAR pass over the record bytes, which the batch keeps)."""
        dev = self._dev_snapshot()
        if dev is None:
            raise ValueError("host-backed batch has no device columns")
        from disq_tpu.runtime.tracing import count_transfer

        ends = np.empty(int(dev["pos"].shape[0]), np.int32)
        ends[: self._n] = self.alignment_ends()
        ends[self._n:] = ends[self._n - 1]
        count_transfer("h2d", ends.nbytes)
        return dev["refid"], dev["pos"], _jax_fns()["jnp"].asarray(ends)

    def flagstat(self) -> Dict[str, int]:
        """flagstat over the resident flag column — no h2d re-upload,
        d2h is the 48-byte count row."""
        dev = self._dev_snapshot()
        if dev is None:
            from disq_tpu.ops.flagstat import flagstat_counts

            return flagstat_counts(np.asarray(self.flag))
        if self._mesh is not None:
            from disq_tpu.ops.flagstat import flagstat_resident_sharded

            out = flagstat_resident_sharded(
                dev["flag"], self._n, self._mesh)
        else:
            from disq_tpu.ops.flagstat import flagstat_resident

            out = flagstat_resident(dev["flag"], self._n)
        self._consume_on_device("flag", 4 * self._n)
        return out

    def sort_permutation(self) -> np.ndarray:
        """Coordinate-sort permutation from the resident refid/pos
        columns: keys + lexsort run on device, only the (n,) i32 order
        crosses d2h — the u64 key vectors never move."""
        dev = self._dev_snapshot()
        if dev is None:
            from disq_tpu.sort.coordinate import coordinate_keys

            return np.argsort(
                coordinate_keys(self.refid, self.pos), kind="stable")
        if self._mesh is not None:
            from disq_tpu.sort.sharded import resident_coordinate_sort

            out = resident_coordinate_sort(
                dev["refid"], dev["pos"], self._n, self._mesh)
            self._consume_on_device("sort_keys", 8 * self._n)
            return out
        fns = _jax_fns()
        jax, jnp = fns["jax"], fns["jnp"]
        from disq_tpu.runtime.tracing import (
            count_transfer, device_span, span)

        n_dev = jnp.asarray(np.int32(self._n))  # staged pre-guard
        with device_span("device.kernel", kernel="coordinate_keys",
                         records=self._n) as fence:
            with jax.transfer_guard("disallow"):
                order = fns["coord_perm"](
                    dev["refid"], dev["pos"], n_dev)
                jax.block_until_ready(order)
            fence.sync(order)
        with span("sort.gather", stage="fetch", records=self._n):
            out = np.asarray(order[: self._n])
        count_transfer("d2h", out.nbytes)
        # the 8-byte-per-record key vector stayed on device
        self._consume_on_device("sort_keys", 8 * self._n)
        return out

    # -- resident permutation (the resident sort's output) -------------------

    def permuted(self, order: np.ndarray) -> "ColumnarBatch":
        """A reordered batch that STAYS device-backed: the fixed
        columns are gathered by ``order`` on device (one small index
        upload, zero column round-trips), and the host record blob is
        kept with the permutation so ragged access materializes
        lazily — exactly like the unpermuted batch.  This is the sort
        output the write copies from (``encoded_slice``), with no host
        record materialization.  Falls back to a host-backed batch when
        the device columns are gone (released / host-built)."""
        order = np.asarray(order, dtype=np.int64)
        if len(order) != self._n:
            raise ValueError(
                f"permutation of {len(order)} over {self._n} records")
        dev = self._dev_snapshot()
        if dev is None or self._offsets is None:
            return ColumnarBatch.from_host(self.to_read_batch().take(order))
        out = self._gathered(dev, order)
        self._share_bytes(
            out, self._order[order] if self._order is not None else order)
        return out

    @property
    def holds_bytes(self) -> bool:
        """True when the batch holds its records' bytes
        (``encode_source()`` is not None), asked without joining a
        concat's parts: whoever joins them owns the join, so a caller
        that only wants to know must not be the one."""
        with self._lock:
            return self._offsets is not None and (
                self._blob is not None or self._blob_parts is not None)

    def encode_source(self):
        """The ``(record blob, record offsets, pending order or None)``
        triple a reader of the record bytes needs, or None when this batch
        holds no host record blob (host-built batches encode through
        the classic ``encode_records`` path). The order maps each of
        this batch's records to a record of the blob: a permutation of
        them all, or, after a filter that kept its source's bytes, a
        selection of ``count`` of them. Index ``offsets`` through it."""
        if not self.holds_bytes:
            return None
        return self._host_blob(), self._offsets, self._order

    def encoded_slice(self, lo: int, hi: int):
        """The logical records ``[lo, hi)`` as the BAM writer's bytes,
        ``(uint8 array, (hi - lo + 1,) record offsets)``, or None when
        the batch holds no record bytes (``encode_source``). The
        records are copied, not parsed: gathered from the blob by that
        stretch of a pending order (per-record memcpy, the GIL
        released: writers' shards gather side by side), or, in source
        order, a view of the blob's own stretch. The bytes are the
        column encoder's (``bam/codec.py``) of the same records: it
        keeps every field as it was read but the pad nibble after an
        odd number of bases, which it writes as zero; so is it here,
        in the copy (a view is copied first), never in the blob.

        Runs under ``columnar.batch.materialize{how=bytes}``: the batch
        brings these records into the form a host consumer takes, as
        ``how=parse`` does for all of them at once; no
        ``columnar.batch.materializations`` is booked, no record is
        parsed."""
        src = self.encode_source()
        if src is None:
            return None
        from disq_tpu.bam.columnar import segment_gather
        from disq_tpu.runtime.tracing import span

        blob, offsets, order = src
        with span("columnar.batch.materialize", records=hi - lo,
                  how="bytes") as labels:
            if order is not None:
                out, offs = segment_gather(blob, offsets, order[lo:hi])
            else:
                out = blob[offsets[lo]: offsets[hi]]
                offs = offsets[lo: hi + 1] - offsets[lo]
            labels["bytes"] = len(out)
            at = offs[:-1]
            at = at[(out[at + 20] & 1) != 0]  # l_seq is odd
            if len(at):
                n_cigar = out[at + 16] + (out[at + 17].astype(np.int64) << 8)
                l_seq = out[at[:, None] + np.arange(20, 24)].view("<i4")
                # the last packed-sequence byte of each
                at = (at + 36 + out[at + 12] + 4 * n_cigar
                      + l_seq[:, 0] // 2)
                at = at[(out[at] & 0x0F) != 0]
                if len(at):
                    if order is None:
                        out = out.copy()
                    out[at] &= 0xF0
        return out, offs

    # -- concat -------------------------------------------------------------

    @classmethod
    def concat(cls, batches: Sequence) -> "ReadBatch | ColumnarBatch":
        """Concatenate mixed ``ReadBatch`` / ``ColumnarBatch`` shards.
        All device-backed ⇒ the result stays device-backed (fixed
        columns concatenated on device, host blobs rebased for ragged);
        otherwise everything materializes to one host ``ReadBatch``.

        CONSUMING: device-backed inputs are released into the result
        (their residency moves to the concatenated columns) — keep
        using the returned batch, not the inputs."""
        batches = list(batches)
        if not batches:
            return ReadBatch.empty()
        if len(batches) == 1:
            return batches[0]
        # empty shards (deadline fallbacks, ranges past end-of-data)
        # are neutral: they must not demote an all-resident read
        nonempty = [b for b in batches if len(b)]
        if not nonempty:
            return ReadBatch.empty()
        if len(nonempty) == 1:
            return nonempty[0]
        batches = nonempty
        resident = [b for b in batches
                    if isinstance(b, ColumnarBatch) and b.device_backed]
        if len(resident) == len(batches):
            fns = _jax_fns()
            jnp = fns["jnp"]
            self = cls()
            self._n = sum(b._n for b in batches)
            self._n_ref = batches[0]._n_ref
            # bucket-pad the concatenated columns like from_blob does
            # (edge pads duplicate the last record): exact-length
            # results would retrace every downstream jit once per
            # distinct total record count
            pad = _bucket_n(self._n) - self._n
            mesh = batches[0]._mesh
            if mesh is None:
                # each shard's padded columns written whole at its
                # offset: the programs are keyed by power-of-two
                # lengths alone, where slices at the shards' exact
                # counts compile anew for every file
                cols = {name: jnp.zeros(self._n + pad, col.dtype)
                        for name, col in batches[0]._dev.items()}
                at = 0
                for b in batches:
                    cols = fns["place"](cols, b._dev, np.int32(at))
                    at += b._n
                self._dev = fns["edge_fill"](cols, np.int32(self._n))
            else:
                self._dev = {
                    name: jnp.pad(
                        jnp.concatenate(
                            [b._dev[name][: b._n] for b in batches]),
                        (0, pad), mode="edge")
                    for name in FIXED_COLUMNS
                }
            # mesh carriage: a concat of same-mesh shards stays one
            # sharded program (the slice/concat/pad above may have
            # collapsed placement — normalize back to batch sharding)
            if mesh is not None and all(
                    b._mesh is mesh for b in batches):
                from disq_tpu.runtime.mesh import mesh_put

                self._dev = {name: mesh_put(col, mesh)
                             for name, col in self._dev.items()}
                self._mesh = mesh
            # host blobs join LAZILY (first ragged access / pickle):
            # a flagstat-only multi-shard read never pays the
            # O(total-decoded-bytes) memcpy or its transient 2x host
            # RAM peak. An input under a pending order (a sort's, a
            # filter's that kept its source's bytes) has its columns
            # in logical order already; its bytes follow here, by the
            # one gather a compaction would have made
            parts: List[np.ndarray] = []
            offs = np.zeros(self._n + 1, dtype=np.int64)
            held = []
            at = 1
            pos = 0
            for b in batches:
                spans = b._span_cache.spans
                if b._order is not None:
                    blob, b_offs = b._logical_bytes()
                    parts.append(blob)
                    if spans is not None:
                        spans = (spans[0][b._order], spans[1][b._order])
                else:
                    parts.extend(b._blob_parts if b._blob_parts is not None
                                 else [b._blob])
                    b_offs = b._offsets
                    b._blob_owned = False  # the result holds it too
                held.append(spans)
                offs[at: at + b._n] = b_offs[1:] + pos
                at += b._n
                pos += int(b_offs[-1])
            self._blob_parts = parts
            self._offsets = offs
            if all(sp is not None for sp in held):
                self._span_cache.keep(
                    np.concatenate([sp[0] for sp in held]),
                    np.concatenate([sp[1] for sp in held]))
            self._hbm = len(self._dev) * (self._n + pad) * 4
            from disq_tpu.runtime.tracing import track_hbm

            track_hbm(self._hbm)
            _note_build(self._hbm)
            for b in batches:
                # inputs live on inside the concat — release their
                # residency without booking avoidance
                b._release(book_avoided=False)
            return self
        return ReadBatch.concat([as_read_batch(b) for b in batches])

    # -- release ------------------------------------------------------------

    def _release(self, book_avoided: bool = True) -> None:
        with self._lock:
            if self._released or self._dev is None:
                self._released = True
                return
            self._released = True
            if book_avoided:
                # only the 8 reachable fixed columns can ever be
                # fetched — the 4 parse-only fields (block_size,
                # lengths) are not d2h candidates and must not inflate
                # the metric
                avoided = sum(
                    4 * self._n
                    for name in FIXED_COLUMNS
                    if name not in self._cache
                    and name not in self._consumed)
                total = avoided + sum(self._consumed.values())
                from disq_tpu.runtime.tracing import counter, record_span

                if total:
                    counter("device.d2h_avoided_bytes").inc(total)
                record_span("columnar.batch.release", 0.0,
                            records=self._n, avoided_bytes=total)
            self._dev = None
            if self._hbm:
                from disq_tpu.runtime.tracing import track_hbm

                track_hbm(-self._hbm)
                _note_build(-self._hbm)
                self._hbm = 0

    def release(self) -> None:
        """Drop the device columns. Reachable columns never fetched,
        plus everything consumed on device (flagstat's flag column,
        sort keys), book into ``device.d2h_avoided_bytes`` — the d2h
        bytes the lazy fetch skipped — and a ``columnar.batch.release``
        span records the batch's total avoidance for
        ``trace_report --analyze``."""
        self._release(book_avoided=True)

    def __del__(self) -> None:  # pragma: no cover - GC timing
        try:
            self._release(book_avoided=True)
        except Exception:  # noqa: BLE001 — interpreter shutdown
            pass


def _rebuild_from_blob(blob, offsets, n_ref) -> "ColumnarBatch":
    """Unpickle target for a spilled device-backed batch (module-level
    so pickle resolves it by name)."""
    return ColumnarBatch.from_blob(blob, offsets, n_ref=n_ref)


def _rebuild_from_host(batch: ReadBatch) -> "ColumnarBatch":
    """Unpickle target for a spilled host-backed batch."""
    return ColumnarBatch.from_host(batch)


def as_read_batch(batch) -> ReadBatch:
    """Whatever a source emitted (host ReadBatch or ColumnarBatch) as a
    plain host ReadBatch."""
    if isinstance(batch, ColumnarBatch):
        return batch.to_read_batch()
    return batch


def concat_batches(batches: Sequence) -> "ReadBatch | ColumnarBatch":
    """Shard concat for the read paths: stays device-resident when
    every shard is, else materializes host-side. Consuming — see
    ``ColumnarBatch.concat``: device-backed inputs are released into
    the result."""
    return ColumnarBatch.concat(batches)
