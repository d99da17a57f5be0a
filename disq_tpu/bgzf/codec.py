"""BGZF inflate/deflate: block framing and the codec entry points.

Replaces htsjdk's ``BlockCompressedInputStream`` / ``OutputStream``
(SURVEY.md §2.8). A block has one host decoder (the native C++
threaded codec of ``disq_tpu.native`` when built, else host zlib) and
one device kernel (``ops/inflate_simd``); both share this module's
block framing.

**Canonical deflate pin** (the byte-identity contract: a sorted BAM and
its BAI written twice, by any path that uses host deflate, are the same
bytes): raw DEFLATE, zlib level 6, memLevel 8, default strategy. All BGZF output
in this framework uses exactly these parameters, so repeated writes of the
same records are byte-identical.

**Host-vs-device inflate policy.** The default codec path is the
threaded C++ host inflater; the 128-lane SIMD Pallas kernel is the
opt-in (``DISQ_TPU_DEVICE_INFLATE=1``). Its rate on the chip is the
``inflate_simd_*`` rows that ``disq_tpu.ops.tpu_ci`` writes into
``TPU_KERNELS.json``. On a one-chip dev box the host path wins and
stays the default. The device path exists because the ratio that
matters at fleet scale is per-CHIP: TPU pods scale chips, not host
cores, and the device path leaves the host free for IO. It also keeps
decompressed shards HBM-resident for the downstream parse/sort kernels
instead of round-tripping through host memory. Flip the default only
when device-side decode is measured faster end-to-end on the target
topology; until then the flag is the opt-in.
"""

from __future__ import annotations

import io
import struct
import zlib
from typing import BinaryIO, List, Sequence

from disq_tpu.bgzf.block import (
    BGZF_EOF_MARKER,
    BGZF_FOOTER_SIZE,
    BGZF_HEADER_SIZE,
    BGZF_MAX_PAYLOAD,
    BgzfBlock,
    build_block_header,
    make_virtual_offset,
    parse_block_header,
)

CANONICAL_LEVEL = 6
CANONICAL_MEMLEVEL = 8


def inflate_block(data: bytes, offset: int = 0, verify_crc: bool = True) -> bytes:
    """Inflate one BGZF block whose header begins at ``offset``."""
    total = parse_block_header(data, offset)
    # Compressed payload sits between the (variable-length) header and the
    # 8-byte footer. Header length = 12 + XLEN.
    xlen = struct.unpack_from("<H", data, offset + 10)[0]
    hdr_len = 12 + xlen
    payload = data[offset + hdr_len: offset + total - BGZF_FOOTER_SIZE]
    crc, isize = struct.unpack_from("<II", data, offset + total - BGZF_FOOTER_SIZE)
    try:
        out = zlib.decompress(payload, wbits=-15, bufsize=isize or 1)
    except zlib.error as e:
        # corrupt deflate bits fail BEFORE the CRC check — keep the
        # framework's ValueError contract for corrupt inputs
        raise ValueError(f"corrupt DEFLATE stream in BGZF block: {e}") from e
    if len(out) != isize:
        raise ValueError(f"BGZF ISIZE mismatch: {len(out)} != {isize}")
    if verify_crc and zlib.crc32(out) != crc:
        raise ValueError("BGZF CRC mismatch")
    return out


def inflate_blocks(
    data: bytes, blocks: Sequence[BgzfBlock], base: int = 0,
    verify_crc: bool = True, as_array: bool = False,
    keep_device: bool = False,
):
    """Inflate many blocks from a staged buffer. ``base`` is the file
    offset at which ``data[0]`` sits, so ``BgzfBlock.pos`` (absolute)
    indexes correctly into the buffer.

    Uses the threaded C++ batch inflater when built (blocks are
    independent raw-DEFLATE streams — embarrassingly parallel); falls
    back to per-block host zlib. Set ``DISQ_TPU_DEVICE_INFLATE=1`` to
    route through the 128-lane SIMD Pallas kernel instead
    (``inflate_blocks_device``; CRC checked on host).

    ``keep_device`` changes the return to ``(blob, handle)``: on the
    device path's direct route the handle is the still-HBM-resident
    kernel output (``DeviceBlobHandle``) the fused resident-decode
    chain parses without re-uploading; on its service route it is the
    padded host buffer ``blob`` is the head of (a uint8 array of the
    parse's upload shape), which the chain uploads as it is; the host
    path returns ``(blob, None)`` and the caller copies the blob into
    an upload buffer.
    """
    import numpy as np

    if not blocks:
        empty = np.empty(0, dtype=np.uint8) if as_array else b""
        return (empty, None) if keep_device else empty
    from disq_tpu.runtime.debug import env_flag
    from disq_tpu.runtime.tracing import span

    with span("codec.inflate.batch", blocks=len(blocks)):
        return _inflate_blocks_timed(
            data, blocks, base, verify_crc, as_array, env_flag,
            keep_device)


def _inflate_blocks_timed(data, blocks, base, verify_crc, as_array,
                          env_flag, keep_device=False):
    import numpy as np

    if env_flag("DISQ_TPU_DEVICE_INFLATE"):
        # as_array flows through: the SIMD path assembles the blob
        # straight from the kernel's transposed output (no bytes join)
        return inflate_blocks_device(
            data, blocks, base, verify_crc=verify_crc,
            as_array=as_array, keep_device=keep_device)
    try:
        from disq_tpu.native import inflate_blocks_native

        arr = np.frombuffer(data, dtype=np.uint8)
        off = np.array([b.pos - base for b in blocks], dtype=np.int64)
        csize = np.array([b.csize for b in blocks], dtype=np.int32)
        usize = np.array([b.usize for b in blocks], dtype=np.int32)
        # Header length = 12 + XLEN (XLEN varies across writers).
        xlen = arr[off + 10].astype(np.int32) | (
            arr[off + 11].astype(np.int32) << 8
        )
        out = inflate_blocks_native(
            arr, off, 12 + xlen, csize, usize, verify_crc=verify_crc,
            as_array=as_array,
        )
        return (out, None) if keep_device else out
    except ImportError:
        pass
    parts = [
        inflate_block(data, b.pos - base, verify_crc=verify_crc) for b in blocks
    ]
    out = b"".join(parts)
    out = np.frombuffer(out, dtype=np.uint8) if as_array else out
    return (out, None) if keep_device else out


def inflate_blocks_device(
    data: bytes, blocks: Sequence[BgzfBlock], base: int = 0,
    verify_crc: bool = True, as_array: bool = False,
    keep_device: bool = False,
):
    """Device path of ``inflate_blocks``: the 128-lane SIMD Pallas
    kernel (``ops/inflate_simd``) with ISIZE validated against the
    kernel's per-lane output length and CRC on host.  It is reached by
    one of two routes:

    - With ``DISQ_TPU_DEVICE_SERVICE=1`` the block batch is submitted
      to the cross-shard decode service (``runtime/device_service.py``):
      blocks from concurrently-decoding shards coalesce into full
      128-lane launches, and the decoded bytes land in one contiguous
      blob with no per-block ``bytes`` round-trips.
    - Otherwise the direct launch loop
      (``inflate_simd.inflate_payloads_simd``) runs this call's blocks
      alone.

    Payloads are sliced as ``memoryview``\\ s (nothing here copies the
    compressed bytes).  Every block is held to its footer's CRC32
    before this returns.  On the service route the service does it,
    launch by launch as the lanes land, on the host pool
    (``submit_inflate(crcs=...)``): the blocks' CRCs are read here and
    go with the submission, and ``result()`` hands out checked bytes.
    On the direct route ``_verify_block_crcs`` checks the whole batch
    once the last launch is back, threaded, with the device idle under
    it (as the service route's check was for a pass's last split until
    it moved under the launches).  The span
    ``codec.inflate.verify{blocks, bytes}`` runs from the device's
    answer to the return: the direct route's check, and on both the
    ``tobytes`` copy.  ``as_array`` returns the blob as a uint8 array
    instead of bytes.

    ``keep_device`` returns ``(blob, handle)``: on the direct route a
    ``DeviceBlobHandle`` (the kernel's output chunks stay resident in
    HBM for the fused parse chain), on the service route the padded
    buffer the service decoded into (``Submission.base``; ``blob`` is
    its head), which the parse uploads whole."""
    import numpy as np

    if not blocks:
        empty = np.empty(0, dtype=np.uint8) if as_array else b""
        return (empty, None) if keep_device else empty
    mv = memoryview(data)
    payloads = []
    for b in blocks:
        off = b.pos - base
        xlen = struct.unpack_from("<H", data, off + 10)[0]
        payloads.append(mv[off + 12 + xlen: off + b.csize - BGZF_FOOTER_SIZE])
    usizes = [b.usize for b in blocks]
    from disq_tpu.runtime import device_service

    handle = None
    on_service = device_service.enabled()
    if on_service:
        crcs = [_footer_crc(data, b, base)
                for b in blocks] if verify_crc else None
        sub = device_service.get_service().submit_inflate(
            payloads, usizes, crcs=crcs, padded=keep_device)
        blob, offsets = sub.result()
        handle = sub.base
    else:
        from disq_tpu.ops.inflate_simd import inflate_payloads_simd

        blob, offsets, *kept = inflate_payloads_simd(
            payloads, usizes=usizes, as_array=True,
            keep_device=keep_device)
        if kept:
            handle = kept[0]
    # the device has answered: what follows is this thread's own host
    # work on the decoded blob (``codec.inflate.batch`` minus it is the
    # wait for the device)
    from disq_tpu.runtime.tracing import span

    with span("codec.inflate.verify", blocks=len(blocks),
              bytes=len(blob)):
        if verify_crc and not on_service:
            try:
                _verify_block_crcs(data, blocks, base, blob, offsets)
            except BaseException:
                if handle is not None:
                    handle.release()
                raise
        out = blob if as_array else blob.tobytes()
    return (out, handle) if keep_device else out


def _footer_crc(data, block: BgzfBlock, base: int) -> int:
    """The CRC32 a block's footer states for its decoded bytes."""
    return struct.unpack_from(
        "<I", data, block.pos - base + block.csize - BGZF_FOOTER_SIZE)[0]


def _verify_block_crcs(data, blocks, base, blob, offsets) -> None:
    """The direct route's batch CRC check (the service route checks
    launch by launch: ``device_service.Submission.check``) of
    device-decoded output against the BGZF footers, over zero-copy blob
    slices (no per-block bytes), after the batch's last launch.  Big
    batches fan out over the shared pool — ``zlib.crc32`` releases the
    GIL.  Booked into ``codec.inflate.crc_blocks{at=tail}``."""
    from disq_tpu.runtime.tracing import counter

    counter("codec.inflate.crc_blocks").inc(len(blocks), at="tail")

    def check(i: int) -> None:
        if zlib.crc32(blob[int(offsets[i]): int(offsets[i + 1])]) \
                != _footer_crc(data, blocks[i], base):
            raise ValueError(f"BGZF CRC mismatch at block {i}")

    if len(blocks) >= 32:
        from disq_tpu.util import shared_host_pool

        for _ in shared_host_pool().map(check, range(len(blocks))):
            pass
    else:
        for i in range(len(blocks)):
            check(i)


def deflate_blob(blob: bytes) -> tuple[bytes, "np.ndarray"]:
    """Deflate a payload into canonical BGZF blocks (no terminator);
    returns (compressed bytes, per-block compressed sizes). The sizes
    vector is what makes write-side virtual offsets computable by array
    arithmetic (BamSink). Native-threaded when built."""
    import numpy as np

    if len(blob) == 0:
        return b"", np.zeros(0, dtype=np.int64)
    pay_off = np.arange(0, len(blob) + BGZF_MAX_PAYLOAD, BGZF_MAX_PAYLOAD, dtype=np.int64)
    pay_off[-1] = len(blob)
    try:
        from disq_tpu.native import deflate_blocks_native

        rows, sizes = deflate_blocks_native(blob, pay_off, level=CANONICAL_LEVEL)
        # Compact row prefixes with a vectorized gather: a boolean
        # prefix mask per chunk of rows (bounded chunks keep the mask
        # allocation small, so peak memory stays ~compressed size, not
        # 3x the padded buffer — and no per-block Python loop on the
        # hot write path).
        out_off = np.zeros(len(sizes) + 1, dtype=np.int64)
        np.cumsum(sizes, out=out_off[1:])
        out = np.empty(int(out_off[-1]), dtype=np.uint8)
        chunk = 256  # 256 rows × 65600-byte stride ⇒ ≤16 MiB of mask
        cols = np.arange(rows.shape[1])
        for lo in range(0, rows.shape[0], chunk):
            hi = min(lo + chunk, rows.shape[0])
            keep = cols < sizes[lo:hi, None]
            out[out_off[lo]: out_off[hi]] = rows[lo:hi][keep]
        return out.tobytes(), sizes.astype(np.int64)
    except ImportError:
        parts = [
            deflate_block(blob[int(pay_off[i]): int(pay_off[i + 1])])
            for i in range(len(pay_off) - 1)
        ]
        return b"".join(parts), np.array([len(p) for p in parts], dtype=np.int64)


def deflate_block(payload: bytes) -> bytes:
    """Payload (≤65280 bytes) → one complete canonical BGZF block."""
    if len(payload) > BGZF_MAX_PAYLOAD:
        raise ValueError(f"payload too large for one BGZF block: {len(payload)}")
    c = zlib.compressobj(CANONICAL_LEVEL, zlib.DEFLATED, -15, CANONICAL_MEMLEVEL)
    comp = c.compress(payload) + c.flush()
    total = BGZF_HEADER_SIZE + len(comp) + BGZF_FOOTER_SIZE
    if total > 0x10000:
        # Incompressible worst case: store at level 0 (still DEFLATE framing).
        c = zlib.compressobj(0, zlib.DEFLATED, -15, CANONICAL_MEMLEVEL)
        comp = c.compress(payload) + c.flush()
        total = BGZF_HEADER_SIZE + len(comp) + BGZF_FOOTER_SIZE
    return (
        build_block_header(total)
        + comp
        + struct.pack("<II", zlib.crc32(payload), len(payload))
    )


def compress_to_bgzf(data: bytes, with_terminator: bool = True) -> bytes:
    """Whole buffer → BGZF bytes (blocks of ≤65280 payload)."""
    comp, _ = deflate_blob(data)
    return comp + BGZF_EOF_MARKER if with_terminator else comp


def decompress_bgzf(data: bytes) -> bytes:
    """Whole BGZF buffer → decompressed bytes (walks the BSIZE chain)."""
    out = []
    pos = 0
    while pos < len(data):
        total = parse_block_header(data, pos)
        out.append(inflate_block(data, pos))
        pos += total
    return b"".join(out)


class BgzfWriter:
    """Streaming BGZF writer with virtual-offset tracking.

    The write-side analogue of htsjdk ``BlockCompressedOutputStream``:
    buffers payload to 65280 bytes, emits canonical blocks, and reports
    ``tell_virtual()`` — the virtual offset the *next* written byte will
    have — which is what index builders (BAI/SBI/TBI) record.

    ``write_terminator=False`` produces a *headerless/terminatorless part*
    for the single-file merge protocol (reference: ``BamSink`` writes
    parts with no terminator; ``Merger`` appends one 28-byte terminator at
    the end — SURVEY.md §3.3).
    """

    def __init__(self, stream: BinaryIO, write_terminator: bool = True):
        self._stream = stream
        self._buf = bytearray()
        self._block_start = 0  # compressed bytes emitted so far
        self._terminate = write_terminator
        self._closed = False

    def tell_virtual(self) -> int:
        return make_virtual_offset(self._block_start, len(self._buf))

    @property
    def compressed_bytes_written(self) -> int:
        return self._block_start

    def write(self, data: bytes) -> int:
        view = memoryview(data)
        while view:
            room = BGZF_MAX_PAYLOAD - len(self._buf)
            take = min(room, len(view))
            self._buf += view[:take]
            view = view[take:]
            if len(self._buf) == BGZF_MAX_PAYLOAD:
                self._flush_block()
        return len(data)

    def _flush_block(self) -> None:
        if not self._buf:
            return
        block = deflate_block(bytes(self._buf))
        self._stream.write(block)
        self._block_start += len(block)
        self._buf.clear()

    def flush(self) -> None:
        """Flush buffered payload as a (possibly short) block."""
        self._flush_block()

    def close(self) -> None:
        if self._closed:
            return
        self._flush_block()
        if self._terminate:
            self._stream.write(BGZF_EOF_MARKER)
        self._stream.flush()
        self._closed = True

    def __enter__(self) -> "BgzfWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class BgzfReader(io.RawIOBase):
    """Seekable decompressed view of a BGZF stream with virtual-offset
    seek — the read-side analogue of htsjdk ``BlockCompressedInputStream``.

    Used by header readers and the record guesser; bulk decode goes
    through the batched ``inflate_blocks`` path instead.
    """

    def __init__(self, stream: BinaryIO):
        self._stream = stream
        self._block_start = 0      # file offset of current block
        self._next_block = 0       # file offset of next block to read
        self._ublock = b""         # decompressed current block
        self._upos = 0             # position within _ublock
        self._eof = False

    def _load_block_at(self, file_offset: int) -> bool:
        self._stream.seek(file_offset)
        # Loop on short reads (buffering/flaky streams can return fewer
        # bytes than asked without being at EOF); b"" IS EOF.
        header = b""
        while len(header) < BGZF_HEADER_SIZE:
            chunk = self._stream.read(BGZF_HEADER_SIZE - len(header))
            if not chunk:
                break
            header += chunk
        if not header:
            self._eof = True
            self._ublock = b""
            self._upos = 0
            # Position the virtual offset AT end-of-data, not at the stale
            # previous block start.
            self._block_start = file_offset
            return False
        if len(header) < BGZF_HEADER_SIZE:
            # Partial header then EOF: the file ends mid-header —
            # deterministic at-rest damage, same classification as a
            # mid-block EOF below.
            raise ValueError(
                f"BGZF file ends mid-header at {file_offset}")
        total = parse_block_header(header)
        # Loop on short reads: a buffering stream (or a flaky remote
        # behind one) may return fewer bytes than asked without being at
        # EOF. A read returning b"" IS EOF — the file ends mid-block,
        # which is deterministic at-rest damage, not a transient fault
        # (same classification as the chain walk in bgzf/guesser.py).
        rest = b""
        want = total - BGZF_HEADER_SIZE
        while len(rest) < want:
            chunk = self._stream.read(want - len(rest))
            if not chunk:
                raise ValueError(
                    f"BGZF file ends mid-block at {file_offset}")
            rest += chunk
        self._ublock = inflate_block(header + rest)
        self._upos = 0
        self._block_start = file_offset
        self._next_block = file_offset + total
        self._eof = False
        return True

    def readable(self) -> bool:
        return True

    def seekable(self) -> bool:
        return True

    def tell_virtual(self) -> int:
        if self._upos == len(self._ublock) and not self._eof:
            # Positioned at the end of a block == start of the next.
            return make_virtual_offset(self._next_block, 0)
        return make_virtual_offset(self._block_start, self._upos)

    def seek_virtual(self, voffset: int) -> None:
        coffset, uoffset = voffset >> 16, voffset & 0xFFFF
        if coffset != self._block_start or not self._ublock:
            if not self._load_block_at(coffset) and uoffset != 0:
                raise ValueError(f"virtual offset past EOF: {voffset:#x}")
        if uoffset > len(self._ublock):
            raise ValueError(f"uoffset beyond block: {voffset:#x}")
        self._upos = uoffset

    def read(self, n: int = -1) -> bytes:
        out = bytearray()
        while n != 0:
            if self._upos >= len(self._ublock):
                if self._eof or not self._load_block_at(self._next_block):
                    break
            avail = len(self._ublock) - self._upos
            take = avail if n < 0 else min(n, avail)
            out += self._ublock[self._upos: self._upos + take]
            self._upos += take
            if n > 0:
                n -= take
        return bytes(out)

    def read_exact(self, n: int) -> bytes:
        data = self.read(n)
        if len(data) != n:
            raise EOFError(f"wanted {n} bytes, got {len(data)}")
        return data
