"""Device DEFLATE encode — dynamic-Huffman literal coding on TPU.

The write-side counterpart of ``disq_tpu.ops.inflate_simd`` (SURVEY.md §7
step 5: "per-shard BGZF deflate (kernel or host)"). The reference's
write hot loop is htsjdk ``BlockCompressedOutputStream`` + zlib
``Deflater`` (SURVEY.md §2.8); the canonical byte-identity pin in this
framework stays host zlib level 6 (``disq_tpu.bgzf.codec``). This
module is the *device* alternative behind ``DISQ_TPU_DEVICE_DEFLATE``:
output bytes differ from the pin but are valid DEFLATE/BGZF.

Design — TPU-first, not a zlib translation:

- **No LZ77 matching.** Match finding is a serial hash-chain walk with
  data-dependent control flow — the worst possible shape for a vector
  machine. Literal-only entropy coding drops that entirely; on BAM
  payloads (4-bit packed bases, small-alphabet quals) a per-call
  Huffman table still gets a useful fraction of zlib's ratio, and the
  encode becomes three embarrassingly parallel array passes.
- **Everything per-byte runs on device** (one jit over ALL blocks of a
  shard at once): code/length LUT gathers, the bit-offset exclusive
  cumsum, and a scatter-add of each code's ≤3 contributing bytes.
  Huffman codes never overlap in bit space, so scatter-*add* is exactly
  bitwise OR — no atomics, no conflicts, pure data parallelism.
- **Host does the O(alphabet) work**: histogram → length-limited
  Huffman code (boundary package-merge, exact, ≤15 bits), the RFC 1951
  §3.2.7 dynamic header (code-length RLE + 7-bit-limited CL code), and
  BGZF framing (CRC32 via zlib's C loop).
- One shared table per call: every block's header is bit-identical, so
  all blocks start their body at the same bit offset — which is what
  lets a single ``(B, P)`` batched kernel encode every block.
- A block whose encoding would expand past the BGZF 64 KiB bound falls
  back to a stored (BTYPE=00) block — same escape hatch the canonical
  zlib path uses.

Oracle: ``zlib.decompress(stream, -15)`` must reproduce the payload
bit-exactly; tests also round-trip whole BGZF files through the reader.

The canonical host-zlib path stays the default (its bytes are the
byte-identity pin); enable this one with ``DISQ_TPU_DEVICE_DEFLATE=1``.
Ratio-wise, on entropy-dominated payloads (packed bases, quals) it
lands within a few percent of zlib level 6, occasionally beating it (no
LZ77 matches exist to lose); on match-heavy payloads the missing LZ77
stage shows. ``TPU_KERNELS.json`` carries both ratios.
"""

from __future__ import annotations

import functools
import struct
import threading
import zlib
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from disq_tpu.bgzf.block import BGZF_MAX_PAYLOAD as BLOCK_PAYLOAD
from disq_tpu.runtime.tracing import (
    count_transfer as _count_transfer,
    counter as _counter,
    span as _span,
)

# bam/sink.py computes write-side virtual offsets as offs // the shared
# BGZF_MAX_PAYLOAD (0xFF00), so the device path MUST chunk payload at
# exactly that boundary — hence the import rather than a local constant.
_EOB = 256  # end-of-block symbol
_MAX_BITS = 15
_CL_MAX_BITS = 7
_CL_ORDER = [16, 17, 18, 0, 8, 7, 9, 6, 10, 5, 11, 4, 12, 3, 13, 2, 14, 1, 15]


# ---------------------------------------------------------------------------
# host: length-limited Huffman (boundary package-merge)


def limited_huffman_lengths(freqs: np.ndarray, limit: int) -> np.ndarray:
    """Exact optimal length-limited code lengths (package-merge).

    Returns per-symbol bit lengths; zero for absent symbols. The code is
    always *complete* (Kraft sum == 1) for ≥2 present symbols — zlib's
    inflate rejects incomplete literal codes in dynamic blocks.
    """
    freqs = np.asarray(freqs, dtype=np.int64)
    present = np.nonzero(freqs > 0)[0]
    lengths = np.zeros(len(freqs), dtype=np.int32)
    if len(present) == 0:
        return lengths
    if len(present) == 1:
        lengths[present[0]] = 1
        return lengths
    if len(present) > (1 << limit):
        raise ValueError(f"{len(present)} symbols cannot fit in {limit} bits")
    # Boundary package-merge: `limit` rounds of (sort, pair) over the
    # original items; the first 2n-2 items of the final list, counted by
    # symbol multiplicity, give each symbol's code length.
    items = sorted((int(freqs[s]), (int(s),)) for s in present)
    packages: List[Tuple[int, Tuple[int, ...]]] = []
    for _ in range(limit):
        merged = sorted(packages + items)
        packages = [
            (merged[i][0] + merged[i + 1][0], merged[i][1] + merged[i + 1][1])
            for i in range(0, len(merged) - 1, 2)
        ]
    for _, syms in packages[: 2 * len(present) - 2]:
        for s in syms:
            lengths[s] += 1
    return lengths


def canonical_codes(lengths: np.ndarray) -> np.ndarray:
    """RFC 1951 §3.2.2 canonical code assignment from bit lengths."""
    lengths = np.asarray(lengths)
    max_len = int(lengths.max()) if lengths.size else 0
    bl_count = np.bincount(lengths, minlength=max_len + 1)
    bl_count[0] = 0
    next_code = np.zeros(max_len + 2, dtype=np.int64)
    code = 0
    for bits in range(1, max_len + 1):
        code = (code + int(bl_count[bits - 1])) << 1
        next_code[bits] = code
    codes = np.zeros(len(lengths), dtype=np.int64)
    for s in range(len(lengths)):
        l = int(lengths[s])
        if l:
            codes[s] = next_code[l]
            next_code[l] += 1
    return codes


def _reverse_bits(v: np.ndarray, nbits: np.ndarray) -> np.ndarray:
    """Huffman codes are emitted MSB-first into DEFLATE's LSB-first
    stream — i.e. bit-reversed."""
    out = np.zeros_like(v)
    vv = v.copy()
    maxb = int(nbits.max()) if nbits.size else 0
    for _ in range(maxb):
        out = (out << 1) | (vv & 1)
        vv >>= 1
    # codes shorter than maxb were over-rotated; shift back
    return out >> (maxb - nbits)


class _BitWriter:
    """Host-side LSB-first bit accumulator (header bits only)."""

    def __init__(self) -> None:
        self.acc = 0
        self.nbits = 0

    def write(self, value: int, nbits: int) -> None:
        self.acc |= value << self.nbits
        self.nbits += nbits

    def write_code(self, code: int, nbits: int) -> None:
        rev = 0
        for _ in range(nbits):
            rev = (rev << 1) | (code & 1)
            code >>= 1
        self.write(rev, nbits)


def _rle_code_lengths(all_lens: np.ndarray) -> List[Tuple[int, int]]:
    """RFC 1951 §3.2.7 run-length encoding of the code-length sequence:
    (symbol, extra-bits-value) pairs over alphabet {0..18}."""
    out: List[Tuple[int, int]] = []
    i, n = 0, len(all_lens)
    while i < n:
        v = int(all_lens[i])
        j = i
        while j < n and int(all_lens[j]) == v:
            j += 1
        run = j - i
        if v == 0:
            while run >= 11:
                r = min(run, 138)
                out.append((18, r - 11))
                run -= r
            while run >= 3:
                r = min(run, 10)
                out.append((17, r - 3))
                run -= r
            out += [(0, -1)] * run
        else:
            out.append((v, -1))
            run -= 1
            while run >= 3:
                r = min(run, 6)
                out.append((16, r - 3))
                run -= r
            out += [(v, -1)] * run
        i = j
    return out


def build_dynamic_header(
    lit_lens: np.ndarray, dist_lens: np.ndarray
) -> Tuple[int, int]:
    """BFINAL+BTYPE+the full dynamic table header → (bits_value, nbits),
    LSB-first packed."""
    w = _BitWriter()
    w.write(1, 1)   # BFINAL: every BGZF block is a single final block
    w.write(2, 2)   # BTYPE=10 dynamic
    hlit = len(lit_lens) - 257
    hdist = len(dist_lens) - 1
    seq = _rle_code_lengths(np.concatenate([lit_lens, dist_lens]))
    cl_freq = np.zeros(19, dtype=np.int64)
    for sym, _ in seq:
        cl_freq[sym] += 1
    cl_lens = limited_huffman_lengths(cl_freq, _CL_MAX_BITS)
    cl_codes = canonical_codes(cl_lens)
    hclen_lens = [int(cl_lens[s]) for s in _CL_ORDER]
    hclen = len(hclen_lens)
    while hclen > 4 and hclen_lens[hclen - 1] == 0:
        hclen -= 1
    w.write(hlit, 5)
    w.write(hdist, 5)
    w.write(hclen - 4, 4)
    for k in range(hclen):
        w.write(hclen_lens[k], 3)
    for sym, extra in seq:
        w.write_code(int(cl_codes[sym]), int(cl_lens[sym]))
        if sym == 16:
            w.write(extra, 2)
        elif sym == 17:
            w.write(extra, 3)
        elif sym == 18:
            w.write(extra, 7)
    return w.acc, w.nbits


# ---------------------------------------------------------------------------
# device: 128-lane batched body encode (the inflate_simd dispatch layout)
#
# One launch encodes <= 128 BGZF block payloads, one per lane, packed
# into the SAME (cw, 128) LE-word column layout the SIMD inflate/rANS
# kernels use — so the launches share ``ops/inflate_simd``'s pooled
# staging arenas (``ARENAS`` keyed ("deflate", cw)), its ``_pack_chunk``
# packer, and its adaptive ``dispatch_window``.  The per-call Huffman
# code/length LUTs are uploaded once per table (``DeflateTable.luts``)
# and stay device-resident across every chunk launch of that call.

LANES = 128  # mirrors ops/inflate_simd.LANES (not imported: this module
#              must import without jax for the disabled-path guard)

#: Per-call observability: blocks encoded, blocks
#: the entropy coder expanded that host zlib re-deflated
#: (``host_fallback``), and of those the ones zlib also expanded and
#: stored (BTYPE=00, ``stored_fallback``).
last_stats = {"blocks": 0, "stored_fallback": 0, "host_fallback": 0}

#: Process-lifetime device-work accounting for the zero-overhead guard
#: (``scripts/check_overhead.py``): with device deflate off, every
#: entry must stay 0 — no kernel launches, no LUT uploads, no arenas.
device_stats = {"launches": 0, "lut_uploads": 0, "device_blocks": 0}


@functools.lru_cache(maxsize=16)
def _compiled(cw: int, out_bytes: int):
    """The batched lane encoder for one (comp words, output bound)
    geometry: (cw, 128) u32 payload columns + (1, 128) byte counts →
    (128, out_bytes) u8 lanes-major body bytes (bits [base_bits,
    base_bits + body_bits) populated; the header region below
    ``base_bits`` is all-zero for the host to OR in) plus the (1, 128)
    per-lane end bit offsets.  ``base_bits`` stays traced so one
    compile serves every header of the same geometry."""
    import jax
    import jax.numpy as jnp

    def encode(comp, clen, code_lut, len_lut, base_bits):
        P = cw * 4
        # LE word columns → lanes-major byte symbols (128, P)
        parts = [((comp >> jnp.uint32(8 * k)) & jnp.uint32(0xFF))
                 for k in range(4)]
        sym = jnp.transpose(
            jnp.stack(parts, axis=1).reshape(P, LANES)).astype(jnp.int32)
        n = clen.reshape(LANES)
        valid = jnp.arange(P)[None, :] < n[:, None]
        lens = jnp.where(valid, len_lut[sym], 0)
        # Exclusive cumsum of code lengths → each code's start bit.
        starts = base_bits + jnp.cumsum(lens, axis=1) - lens
        codes = jnp.where(valid, code_lut[sym], 0).astype(jnp.uint32)
        shift = (starts & 7).astype(jnp.uint32)
        v = codes << shift                      # ≤ 15+7 = 22 bits
        # Bit starts are monotonic within a lane and lanes are laid out
        # consecutively, so the flattened target byte indices are
        # SORTED — a sorted segment-sum, which XLA lowers far better
        # than a general scatter. Codes occupy disjoint bit ranges, so
        # add == bitwise-or.
        row_base = jnp.arange(LANES)[:, None] * out_bytes
        out_flat = jnp.zeros(LANES * out_bytes, dtype=jnp.int32)
        for k, part in enumerate(
            (v & 0xFF, (v >> 8) & 0xFF, (v >> 16) & 0xFF)
        ):
            ids = (row_base + (starts >> 3) + k).reshape(-1)
            out_flat = out_flat + jax.ops.segment_sum(
                jnp.where(valid, part, 0).astype(jnp.int32).reshape(-1),
                ids, num_segments=LANES * out_bytes,
                indices_are_sorted=True,
            )
        end_bits = (base_bits + jnp.sum(lens, axis=1)).astype(
            jnp.int32).reshape(1, LANES)
        return out_flat.reshape(LANES, out_bytes).astype(jnp.uint8), end_bits

    # clen (1,128) i32 is donated to back the same-shaped end_bits
    # output (the body buffer has no aliasable input); CPU jax has no
    # donation and would warn on every launch, so gate on backend.
    donate = (1,) if jax.default_backend() == "tpu" else ()
    return jax.jit(encode, donate_argnums=donate)


def bucket_for(payloads: Sequence) -> int:
    """The arena/compile word-column bucket for one lane chunk — the
    inflate_simd sizing policy applied to uncompressed payloads."""
    from disq_tpu.util import bucket_pow2

    return bucket_pow2(max(len(p) for p in payloads) // 4 + 2)


class DeflateTable:
    """One shared dynamic-Huffman literal table: the host O(alphabet)
    work (package-merge + RFC 1951 §3.2.7 header) done once, plus the
    2 KB code/length LUT pair uploaded to the device ONCE and reused by
    every chunk launch encoding under this table."""

    __slots__ = ("lit_lens", "header_bits", "header_bytes", "eob_rev",
                 "eob_len", "max_code", "out_bytes", "_rev", "_luts",
                 "_lock")

    def __init__(self, freq: np.ndarray, eob_count: int) -> None:
        with _span("device.deflate.table"):
            lit_freq = np.concatenate(
                [np.asarray(freq, np.int64), [max(1, int(eob_count))]])
            self.lit_lens = limited_huffman_lengths(lit_freq, _MAX_BITS)
            # A non-empty payload always yields >= 2 present symbols (a
            # literal plus EOB), which zlib's dynamic decoder requires.
            assert np.count_nonzero(self.lit_lens) >= 2
            lit_codes = canonical_codes(self.lit_lens)
            dist_lens = np.array([1], np.int32)  # single 1-bit dist code
            acc, nbits = build_dynamic_header(self.lit_lens, dist_lens)
            # 4096-bit allowance covers the RFC-worst dynamic header
            # (~3700 bits: 258 CL-coded lengths at <=7 bits + extras).
            assert nbits < 4096
            self.header_bits = nbits
            self.header_bytes = acc.to_bytes((nbits + 7) // 8, "little")
            self._rev = _reverse_bits(lit_codes, self.lit_lens)
            self.eob_rev = int(self._rev[_EOB])
            self.eob_len = int(self.lit_lens[_EOB])
            # Output bound from the ACTUAL max literal code length, with
            # the static header allowance; rounded to 8 KiB buckets so
            # out_bytes (a static jit arg) hits a handful of compiled
            # variants, not one per payload histogram.
            self.max_code = int(self.lit_lens[:256].max())
            ob = (4096 + BLOCK_PAYLOAD * self.max_code + _MAX_BITS) // 8 + 2
            self.out_bytes = (ob + 8191) // 8192 * 8192
            self._luts: Optional[Tuple[Any, Any]] = None
            self._lock = threading.Lock()

    def luts(self) -> Tuple[Any, Any]:
        """The (code, length) LUTs as device-resident arrays — uploaded
        once per table, shared by every chunk launch."""
        with self._lock:
            if self._luts is None:
                import jax
                import jax.numpy as jnp

                code = jnp.asarray(self._rev[:256].astype(np.uint32))
                length = jnp.asarray(self.lit_lens[:256].astype(np.int32))
                jax.block_until_ready(length)
                _count_transfer("h2d", 256 * 8)
                device_stats["lut_uploads"] += 1
                self._luts = (code, length)
            return self._luts


def pack_chunk(payloads: Sequence, cw: Optional[int] = None):
    """Pack one <=128-lane payload chunk into a pooled staging arena
    (host work only); returns ``(comp, clen, arena, cw)`` for
    ``submit_chunk``.  Payloads may be ``memoryview`` slices — nothing
    here copies the uncompressed bytes besides the arena pack."""
    from disq_tpu.ops import inflate_simd as IS

    if cw is None:
        cw = bucket_for(payloads)
    arena = IS.ARENAS.acquire(("deflate", cw), lambda: IS._PackArena(cw))
    try:
        comp, clen = IS._pack_chunk(payloads, cw, arena)
    except BaseException:
        IS.ARENAS.release(("deflate", cw), arena)
        raise
    return comp, clen, arena, cw


def submit_chunk(packed, table: DeflateTable):
    """Upload a packed chunk and launch the batched encoder; returns an
    opaque handle for ``fetch_chunk``."""
    import jax.numpy as jnp

    from disq_tpu.ops import inflate_simd as IS

    comp, clen, arena, cw = packed
    try:
        _count_transfer("h2d", comp.nbytes + clen.nbytes)
        code_lut, len_lut = table.luts()
        fn = _compiled(cw, table.out_bytes)
        device_stats["launches"] += 1
        out = fn(jnp.asarray(comp), jnp.asarray(clen), code_lut,
                 len_lut, jnp.int32(table.header_bits))
    except BaseException:
        IS.ARENAS.release(("deflate", cw), arena)
        raise
    return out, arena, cw


def launch_chunk(payloads: Sequence, table: DeflateTable,
                 cw: Optional[int] = None):
    """``pack_chunk`` then ``submit_chunk`` (the decode service calls
    the two itself, a span round each)."""
    return submit_chunk(pack_chunk(payloads, cw), table)


def release_chunk_arena(handle) -> None:
    from disq_tpu.ops import inflate_simd as IS

    _out, arena, cw = handle
    IS.ARENAS.release(("deflate", cw), arena)


def launch_resident(comp_cols, clen: np.ndarray,
                    table: DeflateTable, cw: int):
    """Launch the encoder over an ALREADY-device-resident (cw, 128)
    word-column chunk (the fused resident-encode path,
    ``runtime/device_write.py``): h2d is the (1,128) byte counts plus
    the once-per-table LUTs — the payload bytes never re-upload."""
    import jax.numpy as jnp

    _count_transfer("h2d", clen.nbytes)
    code_lut, len_lut = table.luts()
    fn = _compiled(cw, table.out_bytes)
    device_stats["launches"] += 1
    out = fn(comp_cols, jnp.asarray(clen), code_lut, len_lut,
             jnp.int32(table.header_bits))
    return out, None, cw


def fetch_chunk(handle, table: DeflateTable, lanes: int,
                labels: Optional[dict] = None):
    """Materialize one launched chunk: wait for the device
    (``device.launch.wait``), then fetch (``device.launch.d2h``) ONLY
    the occupied body prefix — d2h carries compressed bytes, not the
    worst-case buffer (the inverse of the readback-bound economics in
    the module header).  The wait covers the encoder, the end-bit row
    (4 B a lane, which says how wide the prefix is) and the device
    slice that cuts the bodies to it, so that the d2h span is the copy
    alone and its ``bytes`` are known as it opens; the copy blocked on
    the slice before, so no fence is added.  ``labels`` are the
    spans', as in ``inflate_simd._fetch_chunk``."""
    import jax

    bodies_dev, end_dev = handle[0]
    if labels is None:
        labels = {"kind": "deflate", "lanes": lanes}
    _counter("device.kernel_launches").inc(kernel="deflate_simd")
    with _span("device.launch.wait", **labels):
        end = np.asarray(end_dev).reshape(-1)
        top = int(end[:lanes].max()) if lanes else 0
        need = (top + table.eob_len + 7) // 8 + 2
        # quantize the fetch width so slice shapes hit a small compile
        # cache instead of one executable per chunk
        need = min(table.out_bytes, (need + 1023) // 1024 * 1024)
        prefix = jax.block_until_ready(bodies_dev[:, :need])
    with _span("device.launch.d2h", bytes=prefix.nbytes, **labels):
        bodies = np.asarray(prefix)
    _count_transfer("d2h", bodies.nbytes + end.nbytes)
    return bodies, end


# ---------------------------------------------------------------------------
# public: BGZF-framed device deflate


def _bgzf_frame(stream: bytes, payload) -> bytes:
    from disq_tpu.bgzf.block import build_block_header

    bsize = 18 + len(stream) + 8
    if bsize > 0x10000:
        raise ValueError("compressed BGZF block exceeds 64 KiB")
    return (
        build_block_header(bsize)
        + stream
        + struct.pack("<II", zlib.crc32(payload), len(payload))
    )


frame_block = _bgzf_frame  # public alias (service / resident paths)


def _stored_stream(payload: bytes) -> bytes:
    """BTYPE=00 stored block (the incompressible-data escape hatch)."""
    n = len(payload)
    return bytes([1]) + struct.pack("<HH", n, n ^ 0xFFFF) + payload


def finalize_stream(body_row: np.ndarray, end_bit: int,
                    table: DeflateTable) -> bytes:
    """One lane of a fetched chunk → its raw DEFLATE stream: slice the
    body bytes to the real length, OR in the shared header bits and the
    trailing EOB code (codes never overlap in bit space, so OR is
    exact)."""
    total_bits = end_bit + table.eob_len
    stream = bytearray(body_row[: (total_bits + 7) // 8].tobytes())
    for k, hb in enumerate(table.header_bytes):
        stream[k] |= hb
    acc = table.eob_rev << (end_bit & 7)
    for k in range((table.eob_len + (end_bit & 7) + 7) // 8):
        if (end_bit >> 3) + k < len(stream):
            stream[(end_bit >> 3) + k] |= (acc >> (8 * k)) & 0xFF
    return bytes(stream)


def host_deflate_stream(payload) -> bytes:
    """Host-zlib fallback stream for a lane the entropy coder expanded:
    the canonical level-6 raw deflate, degrading to a stored block when
    zlib expands too (truly incompressible data).  Shares the BGZF
    framing with the device lanes."""
    c = zlib.compressobj(6, zlib.DEFLATED, -15, 8)
    s = c.compress(payload) + c.flush()
    if len(s) >= len(payload) + 5:
        last_stats["stored_fallback"] += 1
        return _stored_stream(bytes(payload))
    return s


def host_block(payload) -> bytes:
    """One complete BGZF block via the host-zlib fallback (the
    expanded/oversize escape hatch of the service and resident paths,
    mirroring ``inflate_simd.host_inflate``)."""
    return _bgzf_frame(host_deflate_stream(payload), payload)


def expanded(stream: bytes, payload) -> bool:
    """True when the entropy-coded stream is no smaller than a stored
    block of the payload would be — the lane must reroute to host."""
    return len(stream) >= len(payload) + 5


def finalize_chunk(bodies: np.ndarray, end: np.ndarray,
                   table: DeflateTable, payloads: Sequence,
                   deliver, host_route) -> List[int]:
    """The ONE per-lane finalize shared by every dispatch route
    (``deflate_blob_device``, the service's ``_DeflateEngine``, the
    resident ``EncodedShard.deflate``): slice + OR header/EOB, frame
    device-encoded lanes through ``deliver(j, block)``, and hand the
    entropy-expanded lane indices to ``host_route(flagged)`` — with
    ALL accounting (``device.deflate.*`` counters, ``last_stats``,
    ``device.host_fallback_blocks{reason=expanded}``) done here so the
    three routes count identically: blocks/bytes_in/bytes_out cover
    device-encoded lanes only; host fallbacks book under the fallback
    counter, never the device byte totals."""
    flagged: List[int] = []
    n_dev = b_in = b_out = 0
    for j, p in enumerate(payloads):
        stream = finalize_stream(bodies[j], int(end[j]), table)
        if expanded(stream, p):
            flagged.append(j)
            continue
        block = _bgzf_frame(stream, p)
        n_dev += 1
        b_in += len(p)
        b_out += len(block)
        device_stats["device_blocks"] += 1
        deliver(j, block)
    if n_dev:
        _counter("device.deflate.blocks").inc(n_dev)
        _counter("device.deflate.bytes_in").inc(b_in)
        _counter("device.deflate.bytes_out").inc(b_out)
    if flagged:
        last_stats["host_fallback"] += len(flagged)
        _counter("device.host_fallback_blocks").inc(
            len(flagged), reason="expanded")
        host_route(flagged)
    return flagged


def deflate_blob_device(blob) -> Tuple[bytes, np.ndarray]:
    """Deflate a payload into BGZF blocks on device; returns
    (compressed bytes, per-block compressed sizes) — the same contract
    as the canonical ``disq_tpu.bgzf.codec.deflate_blob``.

    Dispatch shape (the inflate_simd layout): one shared Huffman table
    per call from the global histogram (LUTs uploaded once, device-
    resident across chunks), payload memoryviews packed into pooled
    staging arenas in <=128-lane chunks, an adaptive window of launches
    in flight, and a compressed-only d2h fetch per chunk.  Lanes the
    entropy coder expanded reroute to host zlib (fanned over the shared
    host pool when several flag at once) with
    ``device.host_fallback_blocks{reason=expanded}`` accounting."""
    # reset first so an exception mid-encode can never leave a previous
    # call's counts attributed to this one
    last_stats.update(blocks=0, stored_fallback=0, host_fallback=0)
    if len(blob) == 0:
        return b"", np.zeros(0, dtype=np.int64)
    from disq_tpu.ops import inflate_simd as IS

    data = (np.frombuffer(blob, dtype=np.uint8)
            if not isinstance(blob, np.ndarray) else blob)
    mv = memoryview(data)
    n_blocks = (len(data) + BLOCK_PAYLOAD - 1) // BLOCK_PAYLOAD
    payloads = [
        mv[i * BLOCK_PAYLOAD: min((i + 1) * BLOCK_PAYLOAD, len(data))]
        for i in range(n_blocks)
    ]
    # One shared table per call, from the global histogram (+EOB once
    # per block): every block's header is bit-identical, so all lanes
    # start their body at the same bit offset — which is what lets one
    # batched kernel encode every lane.
    table = DeflateTable(
        np.bincount(data, minlength=256).astype(np.int64), n_blocks)
    cw = bucket_for(payloads)
    chunks = [payloads[lo: lo + LANES]
              for lo in range(0, n_blocks, LANES)]
    chunk_bytes = (cw + 1) * LANES * 4 + table.out_bytes * LANES
    window = IS.dispatch_window(len(chunks), chunk_bytes)
    blocks: List[Optional[bytes]] = [None] * n_blocks
    launched: List[Any] = []

    def host_route_at(base: int):
        # expanded lanes reroute to host zlib — off the caller's
        # critical path when several flag at once (mirrors the inflate
        # service's host fan-out)
        def route(flagged: List[int]) -> None:
            def one(j: int) -> None:
                blocks[base + j] = host_block(payloads[base + j])

            if len(flagged) > 2:
                from disq_tpu.util import shared_host_pool

                for _ in shared_host_pool().map(one, flagged):
                    pass
            else:
                for j in flagged:
                    one(j)

        return route

    try:
        for ids in chunks[:window]:
            launched.append(launch_chunk(ids, table, cw))
        for ci, chunk in enumerate(chunks):
            handle = launched[ci]
            bodies, end = fetch_chunk(handle, table, len(chunk))
            launched[ci] = None
            release_chunk_arena(handle)
            if ci + window < len(chunks):
                launched.append(
                    launch_chunk(chunks[ci + window], table, cw))
            base = ci * LANES
            finalize_chunk(
                bodies, end, table, chunk,
                lambda j, blk, base=base: blocks.__setitem__(
                    base + j, blk),
                host_route_at(base))
    finally:
        for entry in launched:
            if entry is not None:
                release_chunk_arena(entry)
    out = bytearray()
    sizes = np.empty(n_blocks, dtype=np.int64)
    for i in range(n_blocks):
        sizes[i] = len(blocks[i])
        out += blocks[i]
    last_stats["blocks"] = n_blocks
    return bytes(out), sizes
