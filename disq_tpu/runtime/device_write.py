"""Symmetric device write path — resident encode + fused SIMD deflate.

The write-side mirror of ``runtime/device_pipeline.py`` (ROADMAP open
item 5): the read path fuses inflate → parse → columnar so decoded
bytes never round-trip d2h; this module fuses the inverse, sort →
encode → deflate, so *encoded* bytes never round-trip h2d↔d2h between
the stages.  Compressed blocks are the only thing small enough to be
worth moving (the Compressed-Resident direction, PAPERS.md
arxiv 2606.18900), so compression happens where the data already
lives:

- ``ResidentShardEncoder`` uploads a ``ColumnarBatch``'s record blob
  as device words ONCE per write (shards share the array — jax arrays
  are immutable, so the write pipeline's workers slice it
  concurrently), and ``encode_shard`` gathers each shard's records —
  in the sort permutation's order — into a block-aligned device word
  blob.  BAM encode of an unmodified record is byte-identical to its
  source bytes (``bam/codec.py``'s encode∘decode identity), so the
  permuted-record gather IS the record encode, as one device launch.
- ``EncodedShard.deflate`` feeds that still-resident blob straight
  into ``ops/deflate.py``'s 128-lane entropy coder: each chunk's
  (cw, 128) word columns are built by an on-device reshape/transpose
  (no staging arena, no payload re-upload — h2d per chunk is the
  (1,128) byte counts plus the once-per-table LUTs), and d2h carries
  ONLY the occupied compressed prefix + end-bit row.  The per-block
  csizes flow back for the voffset/BAI arithmetic exactly as the host
  path's do.

The host keeps what it already owns: the pre-encode record blob (the
decode path holds it for CRC verification and ragged columns), from
which block CRC32/ISIZE footers and the rare expanded-lane host-zlib
fallback are served — no device bytes cross d2h for either.

Enablement: ``DisqOptions.device_deflate`` / env
``DISQ_TPU_DEVICE_DEFLATE`` + a sorted device-backed batch
(``ColumnarBatch.permuted``).  Disabled, this module is never imported
and allocates nothing (``scripts/check_overhead.py``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

from disq_tpu.bgzf.block import BGZF_MAX_PAYLOAD

#: BGZF payload blocking in LE u32 words — BGZF_MAX_PAYLOAD (65280) is
#: 4-aligned, so every block of a block-aligned blob starts word-aligned
#: and a chunk's (cw, 128) columns are a pure reshape/transpose.
BLOCK_WORDS = BGZF_MAX_PAYLOAD // 4


@functools.lru_cache(maxsize=1)
def _jax():
    import jax
    import jax.numpy as jnp

    return jax, jnp


@functools.lru_cache(maxsize=32)
def _gather_compiled(total_words: int):
    """Per-output-byte record gather: out byte ``b`` belongs to the
    record found by searchsorted over the destination offsets and reads
    the source blob at that record's start plus the within-record
    offset — the exact inverse of ``device_pipeline.
    assemble_device_words``'s per-byte compaction."""
    jax, jnp = _jax()

    def gather(blob_words, src_starts, dst_offsets):
        total = dst_offsets[-1]

        def byte_at(b):
            i = jnp.searchsorted(dst_offsets, b, side="right") - 1
            i = jnp.clip(i, 0, src_starts.shape[0] - 1)
            src = src_starts[i] + (b - dst_offsets[i])
            w = blob_words[jnp.clip(src >> 2, 0, blob_words.shape[0] - 1)]
            byte = (w >> (((src & 3) << 3).astype(jnp.uint32))) \
                & jnp.uint32(0xFF)
            return jnp.where(b < total, byte, jnp.uint32(0))

        w_iota = jnp.arange(total_words, dtype=jnp.int32) << 2
        out = byte_at(w_iota)
        out = out | (byte_at(w_iota + 1) << 8)
        out = out | (byte_at(w_iota + 2) << 16)
        out = out | (byte_at(w_iota + 3) << 24)
        return out

    return jax.jit(gather)


class EncodedShard:
    """One shard's permuted records as a device-resident, block-aligned
    word blob — the unit the fused deflate consumes."""

    def __init__(self, encoder: "ResidentShardEncoder", lo: int, hi: int,
                 words, nbytes: int,
                 record_offsets: np.ndarray) -> None:
        self._encoder = encoder
        self._lo, self._hi = lo, hi
        self._words = words
        self.nbytes = nbytes
        #: (n+1,) shard-local uncompressed record offsets — the
        #: voffset/index arithmetic input (mirrors
        #: ``encode_records_with_offsets``'s second return)
        self.record_offsets = record_offsets
        self.n_blocks = max(0, -(-nbytes // BGZF_MAX_PAYLOAD))
        self._host: Optional[np.ndarray] = None
        self._hbm = int(words.size) * 4 if words is not None else 0
        if self._hbm:
            from disq_tpu.runtime.tracing import track_hbm

            track_hbm(self._hbm)

    # -- host mirror (CRC/ISIZE footers + expanded-lane fallback) -----------

    def host_payload(self) -> np.ndarray:
        """The shard's encoded bytes gathered from the HOST record blob
        the batch already holds (the read path's CRC/ragged copy) —
        serves the BGZF footers and the host-zlib fallback with zero
        d2h."""
        if self._host is None:
            from disq_tpu.bam.codec import _ragged_gather

            enc = self._encoder
            starts = enc._src_starts[self._lo: self._hi]
            lens = enc._lens[self._lo: self._hi]
            self._host, _ = _ragged_gather(enc._blob_u8, starts, lens)
        return self._host

    # -- fused deflate -------------------------------------------------------

    def deflate(self) -> Tuple[bytes, np.ndarray]:
        """Device deflate of the resident blob: (compressed bytes,
        per-block csizes) — the ``deflate_blob`` contract, with the
        encode → deflate handoff entirely in HBM.  Launches ride the
        shared adaptive dispatch window (chunk ``c+1`` is in flight
        while chunk ``c``'s compressed prefix fetches and finalizes on
        host), and the per-lane finalize/fallback/accounting is the
        one shared ``ops/deflate.finalize_chunk`` every route uses."""
        from disq_tpu.ops import deflate as DF
        from disq_tpu.ops import inflate_simd as IS
        from disq_tpu.runtime.tracing import span
        from disq_tpu.util import bucket_pow2

        _jax_mod, jnp = _jax()
        if self.nbytes == 0:
            return b"", np.zeros(0, dtype=np.int64)
        host = self.host_payload()
        n_blocks = self.n_blocks
        table = DF.DeflateTable(
            np.bincount(host, minlength=256).astype(np.int64), n_blocks)
        cw = bucket_pow2(BLOCK_WORDS)
        chunk_geom = [(c0, min(DF.LANES, n_blocks - c0))
                      for c0 in range(0, n_blocks, DF.LANES)]
        chunk_bytes = cw * DF.LANES * 4 + table.out_bytes * DF.LANES
        window = IS.dispatch_window(len(chunk_geom), chunk_bytes)

        def launch(ci: int):
            c0, nl = chunk_geom[ci]
            clen = np.zeros((1, DF.LANES), np.int32)
            for j in range(nl):
                b = c0 + j
                clen[0, j] = (min((b + 1) * BGZF_MAX_PAYLOAD,
                                  self.nbytes) - b * BGZF_MAX_PAYLOAD)
            seg = self._words[c0 * BLOCK_WORDS: (c0 + nl) * BLOCK_WORDS]
            cols = jnp.transpose(seg.reshape(nl, BLOCK_WORDS))
            cols = jnp.pad(
                cols, ((0, cw - BLOCK_WORDS), (0, DF.LANES - nl)))
            return DF.launch_resident(cols, clen, table, cw), clen

        blocks: list = [None] * n_blocks
        launched: list = [launch(ci)
                          for ci in range(min(window, len(chunk_geom)))]
        for ci, (c0, nl) in enumerate(chunk_geom):
            handle, clen = launched[ci]
            launched[ci] = None
            with span("device.deflate.encode", blocks=nl):
                bodies, end = DF.fetch_chunk(handle, table, nl)
                if ci + window < len(chunk_geom):
                    launched.append(launch(ci + window))
                payloads = [
                    host[(c0 + j) * BGZF_MAX_PAYLOAD:
                         (c0 + j) * BGZF_MAX_PAYLOAD + int(clen[0, j])]
                    for j in range(nl)
                ]
                # expanded lanes reroute inline: the writer pipeline
                # already overlaps shards, so this worker IS the
                # shard's own thread (no dispatcher to unblock)
                DF.finalize_chunk(
                    bodies, end, table, payloads,
                    lambda j, blk, c0=c0: blocks.__setitem__(
                        c0 + j, blk),
                    lambda flagged, c0=c0, payloads=payloads: [
                        blocks.__setitem__(
                            c0 + j, DF.host_block(payloads[j]))
                        for j in flagged])
        out = bytearray()
        sizes = np.empty(n_blocks, dtype=np.int64)
        for i in range(n_blocks):
            sizes[i] = len(blocks[i])
            out += blocks[i]
        self.release()
        return bytes(out), sizes

    def release(self) -> None:
        if self._hbm:
            from disq_tpu.runtime.tracing import track_hbm

            track_hbm(-self._hbm)
            self._hbm = 0
        self._words = None

    def __del__(self) -> None:  # pragma: no cover - GC timing
        try:
            self.release()
        except Exception:  # noqa: BLE001 — interpreter shutdown
            pass


class ResidentShardEncoder:
    """Per-write driver of the resident encode: one record-blob upload,
    then a per-shard device gather of the (sorted) record bytes.

    Built from a ``ColumnarBatch`` whose ``encode_source()`` is
    available — i.e. a fused-decode batch, optionally ``permuted()`` by
    the coordinate sort.  Thread-safe for the write pipeline: shards
    only read the shared immutable device blob."""

    def __init__(self, batch) -> None:
        from disq_tpu.runtime.device_pipeline import upload_blob_words
        from disq_tpu.runtime.tracing import count_transfer, span, track_hbm

        src = batch.encode_source()
        if src is None:
            raise ValueError(
                "batch holds no host record blob — resident encode "
                "needs a fused-decode ColumnarBatch")
        blob, offsets, order = src
        blob = np.asarray(blob, dtype=np.uint8)
        offsets = np.asarray(offsets, dtype=np.int64)
        if int(offsets[-1]) >= 2 ** 31:
            raise ValueError(
                f"record blob is {int(offsets[-1])} bytes; the device "
                "write path indexes with i32 — split below 2 GiB")
        self._blob_u8 = blob
        lens = np.diff(offsets)
        if order is not None:
            self._src_starts = offsets[:-1][order]
            self._lens = lens[order]
        else:
            self._src_starts = offsets[:-1].copy()
            self._lens = lens
        n = len(self._lens)
        self._perm_off = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(self._lens, out=self._perm_off[1:])
        with span("device.transfer", direction="h2d",
                  site="write_blob") as labels:
            self._words, up = upload_blob_words(blob)
            labels["bytes"] = up
        count_transfer("h2d", up)
        self._hbm = up
        track_hbm(up)

    @property
    def count(self) -> int:
        return len(self._lens)

    def encode_shard(self, lo: int, hi: int) -> EncodedShard:
        """Gather records [lo, hi) of the (permuted) batch into a
        block-aligned device word blob — the resident record encode.
        Only the small per-record index vectors cross h2d."""
        from disq_tpu.runtime.device_pipeline import _pad_quantum
        from disq_tpu.runtime.tracing import count_transfer, device_span
        from disq_tpu.util import bucket_pow2

        jax, jnp = _jax()
        n = hi - lo
        local_off = self._perm_off[lo: hi + 1] - self._perm_off[lo]
        nbytes = int(local_off[-1])
        if n <= 0 or nbytes == 0:
            return EncodedShard(self, lo, hi, None, 0,
                                np.zeros(1, dtype=np.int64))
        n_blocks = -(-nbytes // BGZF_MAX_PAYLOAD)
        total_words = _pad_quantum(n_blocks * BLOCK_WORDS)
        # bucket-padded index uploads (pads repeat the end so padded
        # output bytes read a real record and compile shapes quantize)
        nb_pad = bucket_pow2(max(1, n))
        starts_pad = np.empty(nb_pad, np.int32)
        starts_pad[:n] = self._src_starts[lo:hi]
        starts_pad[n:] = self._src_starts[hi - 1]
        dst_pad = np.empty(nb_pad + 1, np.int32)
        dst_pad[: n + 1] = local_off
        dst_pad[n + 1:] = nbytes
        count_transfer("h2d", starts_pad.nbytes + dst_pad.nbytes)
        starts_dev = jnp.asarray(starts_pad)
        dst_dev = jnp.asarray(dst_pad)
        with device_span("device.kernel", kernel="encode_resident",
                         records=n) as fence:
            with jax.transfer_guard("disallow"):
                words = _gather_compiled(total_words)(
                    self._words, starts_dev, dst_dev)
                jax.block_until_ready(words)
            fence.sync(words)
        # the deflate chunking below reads exactly the block span
        words = words[: n_blocks * BLOCK_WORDS]
        return EncodedShard(self, lo, hi, words, nbytes, local_off)

    def release(self) -> None:
        if self._hbm:
            from disq_tpu.runtime.tracing import track_hbm

            track_hbm(-self._hbm)
            self._hbm = 0
        self._words = None

    def __del__(self) -> None:  # pragma: no cover - GC timing
        try:
            self.release()
        except Exception:  # noqa: BLE001 — interpreter shutdown
            pass


def resident_encoder_for(storage, batch) -> Optional[ResidentShardEncoder]:
    """The encoder for one sink write, or None when the device write
    path is off or the batch cannot encode resident (no host record
    blob — e.g. a plain host ``ReadBatch``).  The sink then falls back
    to host encode with (still service-routable) deflate."""
    from disq_tpu.bgzf.codec import device_deflate_enabled
    from disq_tpu.runtime.columnar import ColumnarBatch

    if not device_deflate_enabled(storage):
        return None
    if not isinstance(batch, ColumnarBatch):
        return None
    if batch.encode_source() is None:
        return None
    try:
        return ResidentShardEncoder(batch)
    except ValueError:
        # e.g. a concatenated record blob past the i32 indexing bound:
        # exactly the "cannot encode resident" case — host encode (with
        # routed deflate) handles any size
        return None
