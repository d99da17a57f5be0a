"""ctypes bindings for the C++ host runtime (``native/disq_host.cpp``).

Auto-builds the shared library with g++ on first use (cached next to
this module, rebuilt whenever the SHA-256 of ``native/disq_host.cpp``
differs from the one baked into the library — mtimes do not survive a
copy of the tree); import fails cleanly when no toolchain is present,
and every caller falls back to the pure-Python/numpy path — the native
layer is an accelerator, never a requirement. ``build_variant()`` says
which inflate was linked; entry points that report host rates
(``chip_smoke.py``) call it and fail when the library cannot be built.

Byte-identity note: the deflate path uses the same zlib with the same
parameters as the Python pin (level 6, memLevel 8, raw), so outputs are
identical whichever path runs.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(os.path.dirname(_HERE)), "native", "disq_host.cpp")
_SO = os.path.join(_HERE, "libdisq_host.so")

_lock = threading.Lock()
_lib = None
_load_error: Exception | None = None


def _src_hash() -> str:
    import hashlib

    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _so_build_info() -> tuple[str, str]:
    """(source sha256, inflate variant) baked into the library file, or
    ("", "") for a library built before the build stamp existed."""
    import re

    with open(_SO, "rb") as f:
        m = re.search(rb"disq-build:(\w+):(\w+):", f.read())
    return (m.group(1).decode(), m.group(2).decode()) if m else ("", "")


def _build() -> None:
    # Unique temp name: concurrent first-use builds in sibling processes
    # must not interleave output into the same file; os.replace is atomic.
    tmp = f"{_SO}.{os.getpid()}.tmp"
    base = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp,
            f'-DDISQ_SRC_HASH="{_src_hash()}"']
    # Prefer the libdeflate inflate/CRC fast path; retry zlib-only when
    # libdeflate headers/libs are absent on this host.  The retry is
    # loud: the zlib variant inflates 2-3x slower, and a host rate taken
    # on it must not pass for the libdeflate one (``build_variant()``).
    variants = [
        base + ["-DDISQ_HAVE_LIBDEFLATE", "-ldeflate", "-lz", "-pthread"],
        base + ["-lz", "-pthread"],
    ]
    try:
        err = None
        for cmd in variants:
            try:
                subprocess.run(cmd, check=True, capture_output=True)
                os.replace(tmp, _SO)
                if err is not None:
                    import warnings

                    warnings.warn(
                        "disq_tpu native library built WITHOUT libdeflate "
                        "(zlib inflate): "
                        + err.stderr.decode(errors="replace").strip()[-300:],
                        RuntimeWarning, stacklevel=2)
                return
            except subprocess.CalledProcessError as e:
                err = e
        raise err
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _bind(lib: ctypes.CDLL) -> None:
    """Resolve and prototype every exported symbol. A stale prebuilt
    .so missing any newer symbol raises AttributeError HERE (inside the
    guarded load path), never at first call."""
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.disq_scan_bam_offsets.restype = ctypes.c_int64
    lib.disq_scan_bam_offsets.argtypes = [u8p, ctypes.c_int64, i64p, ctypes.c_int64]
    lib.disq_count_bam_records.restype = ctypes.c_int64
    lib.disq_count_bam_records.argtypes = [u8p, ctypes.c_int64]
    lib.disq_bgzf_walk.restype = ctypes.c_int64
    lib.disq_bgzf_walk.argtypes = [
        u8p, ctypes.c_int64, ctypes.c_int64, i64p, i32p, i32p,
        ctypes.c_int64,
    ]
    lib.disq_bgzf_inflate_many.restype = ctypes.c_int64
    lib.disq_bgzf_inflate_many.argtypes = [
        u8p, i64p, i32p, i32p, i32p, ctypes.c_int64, u8p, i64p,
        ctypes.c_int32, ctypes.c_int32,
    ]
    lib.disq_crc32_check.restype = ctypes.c_int64
    lib.disq_crc32_check.argtypes = [
        u8p, i64p, i64p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint32),
    ]
    lib.disq_bgzf_deflate_many.restype = ctypes.c_int64
    lib.disq_bgzf_deflate_many.argtypes = [
        u8p, i64p, ctypes.c_int64, u8p, ctypes.c_int64, i32p,
        ctypes.c_int32, ctypes.c_int32,
    ]
    u16p = ctypes.POINTER(ctypes.c_uint16)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    lib.disq_bam_fixed_columns.restype = ctypes.c_int64
    lib.disq_bam_fixed_columns.argtypes = [
        u8p, ctypes.c_int64, i64p, ctypes.c_int64, i32p, i32p, u8p,
        u16p, u16p, i32p, i32p, i32p, i64p, i64p, i64p, i64p,
    ]
    lib.disq_bam_fill_ragged.restype = ctypes.c_int64
    lib.disq_bam_fill_ragged.argtypes = [
        u8p, i64p, ctypes.c_int64, i64p, u8p, i64p, u32p, i64p, u8p,
        u8p, i64p, u8p,
    ]
    lib.disq_bam_reference_lengths.restype = ctypes.c_int64
    lib.disq_bam_reference_lengths.argtypes = [
        u8p, ctypes.c_int64, i64p, ctypes.c_int64, ctypes.c_int64, i32p,
        i64p, i64p,
    ]
    lib.disq_bam_markdup_keys.restype = ctypes.c_int64
    lib.disq_bam_markdup_keys.argtypes = [
        u8p, ctypes.c_int64, i64p, ctypes.c_int64, i32p, i64p, i64p, i64p,
        i64p,
    ]
    lib.disq_rans_encode0.restype = ctypes.c_int64
    lib.disq_rans_encode0.argtypes = [u8p, ctypes.c_int64, u8p, ctypes.c_int64]
    lib.disq_rans_encode1.restype = ctypes.c_int64
    lib.disq_rans_encode1.argtypes = [u8p, ctypes.c_int64, u8p, ctypes.c_int64]
    lib.disq_rans_decode.restype = ctypes.c_int64
    lib.disq_rans_decode.argtypes = [u8p, ctypes.c_int64, u8p, ctypes.c_int64]
    lib.disq_bam_encode.restype = ctypes.c_int64
    lib.disq_bam_encode.argtypes = [
        u8p, i64p, ctypes.c_int64, i32p, i32p, u8p, u16p, u16p, i32p,
        i32p, i32p, i64p, u8p, i64p, u32p, i64p, u8p, u8p, i64p, u8p,
    ]
    lib.disq_segment_gather.restype = ctypes.c_int64
    lib.disq_segment_gather.argtypes = [
        u8p, ctypes.c_int64, i64p, ctypes.c_int64, i64p, ctypes.c_int64,
        i64p, u8p, ctypes.c_int64,
    ]


def _load() -> ctypes.CDLL:
    global _lib, _load_error
    if _lib is not None:
        return _lib
    if _load_error is not None:
        # Failed once (no toolchain / broken build): don't re-spawn g++
        # on every hot-path call.
        raise ImportError(f"native library unavailable: {_load_error}")
    with _lock:
        if _lib is not None:
            return _lib
        if _load_error is not None:
            raise ImportError(f"native library unavailable: {_load_error}")
        try:
            have_src = os.path.exists(_SRC)
            for attempt in (0, 1):
                try:
                    # staleness is keyed on the source's hash, which the
                    # build bakes into the library — mtimes do not
                    # survive a copy of the tree
                    if attempt or not os.path.exists(_SO) or (
                        have_src and _so_build_info()[0] != _src_hash()
                    ):
                        _build()
                    lib = ctypes.CDLL(_SO)
                    _bind(lib)
                    break
                except AttributeError:
                    # stale prebuilt .so missing a newer symbol: one
                    # rebuild attempt when the source is present, else a
                    # clean ImportError so every caller's Python
                    # fallback engages
                    if attempt or not have_src:
                        raise
        except (OSError, subprocess.CalledProcessError,
                AttributeError) as e:
            _load_error = e
            raise ImportError(f"cannot load native library: {e}") from e
        _lib = lib
        return lib


def loaded() -> bool:
    """Whether the library is loaded: what a span that covers a routine
    with a numpy fallback labels its route from, after the call."""
    return _lib is not None


def build_variant() -> str:
    """Which inflate the loaded library was built with: ``libdeflate``
    or ``zlib`` (the retry when libdeflate is absent — 2-3x slower at
    inflate, same bytes).  Raises ImportError when it cannot build."""
    _load()
    return _so_build_info()[1]


def _as_u8(buf) -> np.ndarray:
    if isinstance(buf, np.ndarray):
        return np.ascontiguousarray(buf, dtype=np.uint8)
    return np.frombuffer(buf, dtype=np.uint8)


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


DEFAULT_THREADS = max(1, (os.cpu_count() or 1))


def scan_bam_offsets_native(buf, base: int = 0) -> np.ndarray:
    """BAM record-offset scan; returns (N+1,) int64 offsets (+``base``)."""
    lib = _load()
    arr = _as_u8(buf)
    n = lib.disq_count_bam_records(_ptr(arr, ctypes.c_uint8), len(arr))
    if n < 0:
        raise ValueError(f"corrupt BAM record at offset {-(n + 1)}")
    out = np.empty(n + 1, dtype=np.int64)
    got = lib.disq_scan_bam_offsets(
        _ptr(arr, ctypes.c_uint8), len(arr), _ptr(out, ctypes.c_int64), n + 1
    )
    if got != n:
        raise ValueError(f"corrupt BAM record at offset {-(got + 1)}")
    if base:
        out += base
    return out


def walk_bgzf_blocks_native(
    buf, stop: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Walk BGZF headers in ``buf`` (which starts at a block start),
    collecting every complete block whose start is ``< stop``. Returns
    (rel_pos i64, csize i32, usize i32) arrays; stops cleanly at a block
    straddling the buffer end."""
    lib = _load()
    arr = _as_u8(buf)
    max_out = len(arr) // 28 + 1  # minimal BGZF block is 28 bytes
    rel = np.empty(max_out, dtype=np.int64)
    cs = np.empty(max_out, dtype=np.int32)
    us = np.empty(max_out, dtype=np.int32)
    n = lib.disq_bgzf_walk(
        _ptr(arr, ctypes.c_uint8), len(arr), stop,
        _ptr(rel, ctypes.c_int64), _ptr(cs, ctypes.c_int32),
        _ptr(us, ctypes.c_int32), max_out,
    )
    if n < 0:
        raise ValueError(f"malformed BGZF block header at offset {-(n + 1)}")
    return rel[:n], cs[:n], us[:n]


def inflate_blocks_native(
    data, block_off: np.ndarray, hdr_len: np.ndarray, csize: np.ndarray,
    usize: np.ndarray, verify_crc: bool = True, nthreads: int | None = None,
    as_array: bool = False,
):
    """Batched BGZF inflate; returns the concatenated payload as bytes,
    or zero-copy as a uint8 array when ``as_array`` (hot read path —
    skips a full payload memcpy)."""
    lib = _load()
    arr = _as_u8(data)
    block_off = np.ascontiguousarray(block_off, dtype=np.int64)
    hdr_len = np.ascontiguousarray(hdr_len, dtype=np.int32)
    csize = np.ascontiguousarray(csize, dtype=np.int32)
    usize = np.ascontiguousarray(usize, dtype=np.int32)
    out_off = np.zeros(len(usize) + 1, dtype=np.int64)
    np.cumsum(usize, out=out_off[1:])
    out = np.empty(int(out_off[-1]), dtype=np.uint8)
    rc = lib.disq_bgzf_inflate_many(
        _ptr(arr, ctypes.c_uint8), _ptr(block_off, ctypes.c_int64),
        _ptr(hdr_len, ctypes.c_int32), _ptr(csize, ctypes.c_int32),
        _ptr(usize, ctypes.c_int32), len(usize),
        _ptr(out, ctypes.c_uint8), _ptr(out_off, ctypes.c_int64),
        1 if verify_crc else 0, nthreads or DEFAULT_THREADS,
    )
    if rc == len(usize) + 1:
        raise MemoryError("libdeflate decompressor allocation failed")
    if rc > 0:
        raise ValueError(f"BGZF inflate failed at block {rc - 1}")
    if rc < 0:
        raise ValueError(f"BGZF CRC mismatch at block {-rc - 1}")
    return out if as_array else out.tobytes()


def crc32_check_native(blob: np.ndarray, offsets: np.ndarray, idx,
                       expect: np.ndarray) -> int:
    """CRC32 of the blocks ``idx`` of a decoded blob where they lie
    (block ``i`` is ``blob[offsets[i]:offsets[i + 1]]``) against
    ``expect[i]``, in one call without the interpreter lock; returns
    the position in ``idx`` of the first block that differs, or -1."""
    lib = _load()
    arr = _as_u8(blob)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    idx = np.ascontiguousarray(idx, dtype=np.int64)
    expect = np.ascontiguousarray(expect, dtype=np.uint32)
    return int(lib.disq_crc32_check(
        _ptr(arr, ctypes.c_uint8), _ptr(offsets, ctypes.c_int64),
        _ptr(idx, ctypes.c_int64), len(idx),
        _ptr(expect, ctypes.c_uint32)))


def decode_records_native(buf, offsets: np.ndarray):
    """Full pass-2 decode in C: returns the dict of ReadBatch columns."""
    lib = _load()
    arr = _as_u8(buf)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = len(offsets) - 1
    c_u8, c_i32, c_i64 = ctypes.c_uint8, ctypes.c_int32, ctypes.c_int64
    c_u16, c_u32 = ctypes.c_uint16, ctypes.c_uint32
    refid = np.empty(n, np.int32)
    pos = np.empty(n, np.int32)
    mapq = np.empty(n, np.uint8)
    bin_ = np.empty(n, np.uint16)
    flag = np.empty(n, np.uint16)
    next_refid = np.empty(n, np.int32)
    next_pos = np.empty(n, np.int32)
    tlen = np.empty(n, np.int32)
    name_len = np.empty(n, np.int64)
    n_cigar = np.empty(n, np.int64)
    l_seq = np.empty(n, np.int64)
    tag_len = np.empty(n, np.int64)
    rc = lib.disq_bam_fixed_columns(
        _ptr(arr, c_u8), len(arr), _ptr(offsets, c_i64), n,
        _ptr(refid, c_i32), _ptr(pos, c_i32), _ptr(mapq, c_u8),
        _ptr(bin_, c_u16), _ptr(flag, c_u16), _ptr(next_refid, c_i32),
        _ptr(next_pos, c_i32), _ptr(tlen, c_i32), _ptr(name_len, c_i64),
        _ptr(n_cigar, c_i64), _ptr(l_seq, c_i64), _ptr(tag_len, c_i64),
    )
    if rc != 0:
        raise ValueError(f"record {-(rc + 1)}: malformed sections")

    def cum(lens):
        off = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(lens, out=off[1:])
        return off

    name_off, cigar_off, seq_off, tag_off = (
        cum(name_len), cum(n_cigar), cum(l_seq), cum(tag_len)
    )
    names = np.empty(int(name_off[-1]), np.uint8)
    cigars = np.empty(int(cigar_off[-1]), np.uint32)
    seqs = np.empty(int(seq_off[-1]), np.uint8)
    quals = np.empty(int(seq_off[-1]), np.uint8)
    tags = np.empty(int(tag_off[-1]), np.uint8)
    rc = lib.disq_bam_fill_ragged(
        _ptr(arr, c_u8), _ptr(offsets, c_i64), n,
        _ptr(name_off, c_i64), _ptr(names, c_u8),
        _ptr(cigar_off, c_i64), _ptr(cigars, c_u32),
        _ptr(seq_off, c_i64), _ptr(seqs, c_u8), _ptr(quals, c_u8),
        _ptr(tag_off, c_i64), _ptr(tags, c_u8),
    )
    if rc != 0:
        raise ValueError("ragged fill failed")
    return dict(
        refid=refid, pos=pos, mapq=mapq, bin=bin_, flag=flag,
        next_refid=next_refid, next_pos=next_pos, tlen=tlen,
        name_offsets=name_off, names=names,
        cigar_offsets=cigar_off, cigars=cigars,
        seq_offsets=seq_off, seqs=seqs, quals=quals,
        tag_offsets=tag_off, tags=tags,
    )


def reference_lengths_native(buf, offsets: np.ndarray, base: int = 0):
    """``(pos i32, reference length i64, op words walked)`` of the
    records of ``buf`` from its fixed fields and CIGAR op words alone,
    one sequential C pass.  ``offsets`` are the (N+1,) record offsets
    of these records in a larger blob of which ``buf`` is the part
    starting at byte ``base``."""
    lib = _load()
    arr = _as_u8(buf)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = max(0, len(offsets) - 1)
    pos = np.empty(n, np.int32)
    reflen = np.empty(n, np.int64)
    ops = np.zeros(1, np.int64)
    rc = lib.disq_bam_reference_lengths(
        _ptr(arr, ctypes.c_uint8), len(arr),
        _ptr(offsets, ctypes.c_int64), base, n,
        _ptr(pos, ctypes.c_int32), _ptr(reflen, ctypes.c_int64),
        _ptr(ops, ctypes.c_int64))
    if rc != 0:
        raise ValueError(f"record {-(rc + 1)}: malformed sections")
    return pos, reflen, int(ops[0])


def markdup_keys_native(buf, offsets: np.ndarray):
    """``(pos i32, reference length, leading clip, trailing clip, score)``
    (the last four i64) of the records of ``buf`` at ``offsets``, one
    sequential C pass over their CIGAR op words and quality bytes."""
    lib = _load()
    arr = _as_u8(buf)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = max(0, len(offsets) - 1)
    pos = np.empty(n, np.int32)
    out = [np.empty(n, np.int64) for _ in range(4)]
    rc = lib.disq_bam_markdup_keys(
        _ptr(arr, ctypes.c_uint8), len(arr),
        _ptr(offsets, ctypes.c_int64), n, _ptr(pos, ctypes.c_int32),
        *(_ptr(a, ctypes.c_int64) for a in out))
    if rc != 0:
        raise ValueError(f"record {-(rc + 1)}: malformed sections")
    return (pos, *out)


def encode_records_native(batch) -> tuple[bytes, np.ndarray]:
    """Columns → record bytes + (N+1,) record offsets, one C pass."""
    lib = _load()
    n = batch.count
    c_u8, c_i32, c_i64 = ctypes.c_uint8, ctypes.c_int32, ctypes.c_int64
    c_u16, c_u32 = ctypes.c_uint16, ctypes.c_uint32
    name_len = np.diff(batch.name_offsets)
    n_cigar = np.diff(batch.cigar_offsets)
    l_seq = np.diff(batch.seq_offsets)
    tag_len = np.diff(batch.tag_offsets)
    sizes = 4 + 32 + (name_len + 1) + 4 * n_cigar + (l_seq + 1) // 2 + l_seq + tag_len
    rec_off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(sizes, out=rec_off[1:])
    out = np.empty(int(rec_off[-1]), np.uint8)

    def c_arr(a, dt, ct):
        return _ptr(np.ascontiguousarray(a, dtype=dt), ct)

    rc = lib.disq_bam_encode(
        _ptr(out, c_u8), _ptr(rec_off, c_i64), n,
        c_arr(batch.refid, np.int32, c_i32), c_arr(batch.pos, np.int32, c_i32),
        c_arr(batch.mapq, np.uint8, c_u8), c_arr(batch.bin, np.uint16, c_u16),
        c_arr(batch.flag, np.uint16, c_u16),
        c_arr(batch.next_refid, np.int32, c_i32),
        c_arr(batch.next_pos, np.int32, c_i32),
        c_arr(batch.tlen, np.int32, c_i32),
        c_arr(batch.name_offsets, np.int64, c_i64), c_arr(batch.names, np.uint8, c_u8),
        c_arr(batch.cigar_offsets, np.int64, c_i64), c_arr(batch.cigars, np.uint32, c_u32),
        c_arr(batch.seq_offsets, np.int64, c_i64), c_arr(batch.seqs, np.uint8, c_u8),
        c_arr(batch.quals, np.uint8, c_u8),
        c_arr(batch.tag_offsets, np.int64, c_i64), c_arr(batch.tags, np.uint8, c_u8),
    )
    if rc != 0:
        i = -(rc + 1)
        raise ValueError(
            f"record {i}: name or CIGAR field exceeds BAM limits "
            "(254 name bytes / 65535 CIGAR ops)"
        )
    return out.tobytes(), rec_off


def rans_encode0_native(raw) -> bytes:
    """rANS 4x8 order-0 encode (CRAM 3.0 §13); full stream incl. the
    9-byte header. Byte-identical to the Python codec's output."""
    lib = _load()
    arr = _as_u8(raw)
    n = len(arr)
    cap = 9 + 771 + 16 + (n * 3) // 2 + 64
    out = np.empty(cap, dtype=np.uint8)
    got = lib.disq_rans_encode0(
        _ptr(arr, ctypes.c_uint8), n, _ptr(out, ctypes.c_uint8), cap
    )
    if got < 0:
        raise ValueError("rANS encode buffer too small")
    return out[:got].tobytes()


def rans_encode1_native(raw) -> bytes:
    """rANS 4x8 order-1 encode (htslib wire format); byte-identical to
    the Python codec's rans_encode_order1."""
    lib = _load()
    arr = _as_u8(raw)
    n = len(arr)
    cap = 9 + 256 * 775 + 16 + (n * 3) // 2 + 64
    out = np.empty(cap, dtype=np.uint8)
    got = lib.disq_rans_encode1(
        _ptr(arr, ctypes.c_uint8), n, _ptr(out, ctypes.c_uint8), cap
    )
    if got < 0:
        raise ValueError("rANS o1 encode buffer too small")
    return out[:got].tobytes()


def rans_decode_native(data) -> bytes:
    """rANS 4x8 decode, order 0 or 1; ``data`` is the full stream."""
    import struct

    lib = _load()
    arr = _as_u8(data)
    if len(arr) < 9:
        raise ValueError("truncated rANS stream")
    raw_size = struct.unpack_from("<I", arr, 5)[0]
    out = np.empty(raw_size, dtype=np.uint8)
    rc = lib.disq_rans_decode(
        _ptr(arr, ctypes.c_uint8), len(arr), _ptr(out, ctypes.c_uint8),
        raw_size,
    )
    if rc != 0:
        raise ValueError(f"rANS decode failed (code {rc})")
    return out.tobytes()


def deflate_blocks_native(
    payload, payload_offsets: np.ndarray, level: int = 6,
    nthreads: int | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Batched canonical BGZF deflate.

    Returns (blocks_buffer, block_sizes): block i's bytes are
    ``blocks_buffer[i * 65600 : i * 65600 + block_sizes[i]]``.
    """
    lib = _load()
    arr = _as_u8(payload)
    pay_off = np.ascontiguousarray(payload_offsets, dtype=np.int64)
    nblocks = len(pay_off) - 1
    stride = 65600
    out = np.empty(nblocks * stride, dtype=np.uint8)
    sizes = np.zeros(nblocks, dtype=np.int32)
    rc = lib.disq_bgzf_deflate_many(
        _ptr(arr, ctypes.c_uint8), _ptr(pay_off, ctypes.c_int64), nblocks,
        _ptr(out, ctypes.c_uint8), stride, _ptr(sizes, ctypes.c_int32),
        level, nthreads or DEFAULT_THREADS,
    )
    if rc != 0:
        raise ValueError(f"BGZF deflate failed at block {rc - 1}")
    return out.reshape(nblocks, stride), sizes


def segment_gather_native(
    flat: np.ndarray, offsets: np.ndarray, indices: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Ragged segment gather (per-segment C memcpy). Same contract as
    ``bam.columnar.segment_gather``: returns (new_flat, new_offsets)."""
    lib = _load()
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    indices = np.ascontiguousarray(indices, dtype=np.int64)
    nseg = len(offsets) - 1
    if len(indices) and (
        int(indices.min()) < -nseg or int(indices.max()) >= nseg
    ):
        raise IndexError("segment index out of range")
    if len(indices) and int(indices.min()) < 0:
        # numpy negative-index semantics; the C loop needs them absolute
        indices = np.where(indices < 0, indices + nseg, indices)
    flat_c = np.ascontiguousarray(flat)
    # Mirror of the native-side validation (ADVICE r5 #1): a
    # non-monotone offsets table would turn into a negative length —
    # which the old C loop cast to a huge size_t OOB memcpy — and an
    # offsets[-1] past the flat buffer would read beyond it.
    if nseg > 0:
        if int(offsets[0]) < 0 or np.any(np.diff(offsets) < 0):
            raise ValueError(
                "segment_gather: offsets must be non-negative and "
                "monotone non-decreasing")
        if int(offsets[-1]) > len(flat_c):
            raise ValueError(
                f"segment_gather: offsets[-1]={int(offsets[-1])} exceeds "
                f"flat length {len(flat_c)}")
    lens = np.diff(offsets)[indices]
    new_off = np.zeros(len(indices) + 1, dtype=np.int64)
    np.cumsum(lens, out=new_off[1:])
    out = np.empty(int(new_off[-1]), dtype=flat_c.dtype)
    rc = lib.disq_segment_gather(
        _ptr(flat_c.view(np.uint8), ctypes.c_uint8), len(flat_c),
        _ptr(offsets, ctypes.c_int64), nseg,
        _ptr(indices, ctypes.c_int64), len(indices),
        _ptr(new_off, ctypes.c_int64),
        _ptr(out.view(np.uint8), ctypes.c_uint8),
        flat_c.dtype.itemsize,
    )
    if rc != 0:
        raise ValueError(f"segment_gather failed validation (code {rc})")
    return out, new_off
