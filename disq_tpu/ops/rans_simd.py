"""128-lane SIMD rANS-4x8 order-0 decode — lane-parallel streams.

Applies the lane-parallel architecture of ``ops/inflate_simd.py``
(one stream per vector lane) to CRAM's rANS order-0 external-block codec
(htsjdk ``RANSExternalCompressor`` / htslib ``rANS_static``; CRAM 3.0
§13 — SURVEY.md §2.8 CRAM row): 128 independent streams decode at
once, one per vector lane, with every piece of decoder state a
``(1, 128)`` vector.

rANS maps onto lanes even better than DEFLATE because the decode
schedule is *position-oblivious*: the 4 interleaved states of stream
``l`` decode output bytes ``4k+j`` (state ``j``, superstep ``k``) at
the same ``k`` for every lane. Consequences the kernel exploits:

- **Uniform output stores.** All lanes emit output word ``k`` at
  superstep ``k``, so the store is a dynamic *uniform-row* tile write
  (8-row tiles accumulated in registers, one ``pl.ds`` store per 8
  supersteps) — no per-lane one-hot output sweep at all, unlike
  DEFLATE where each lane's write position diverges.
- **Fixed 4 bytes/lane/superstep.** No predicated state machine: each
  superstep decodes exactly one symbol per interleaved state (masked
  past each lane's ``raw_size``), so throughput is deterministic.
- **One-sweep symbol lookup.** The slot→symbol step is
  ``s = |{r in 1..256 : cum[r] <= x & 0xFFF}|`` — a single masked
  compare-and-sum over the per-lane ``(257,128)`` cumulative table, no
  4096-slot table build and no binary search.

Renormalization bytes stream through a per-lane 96-bit bit-buffer
``(lo, mid, hi)`` refilled one 32-bit word per one-hot gather over the
packed compressed columns; the two refill sites per superstep are gated
on ``lax.cond(any(cnt <= thresh))`` so flush lanes skip the sweep. A
symbol needs at most 2 renorm bytes (byte-wise renorm from >= 2^11), so
a superstep consumes at most 64 bits/lane; site A (entry, lanes
``cnt <= 64`` topped up when any lane ``<= 48``) and site B (mid, when
any lane ``<= 32``) keep every active lane at >= 32 valid bits per
half-superstep.

All arithmetic is int32-safe: states stay < 2^31 (checked host-side),
``freq * (x >> 12) <= 4095 * (2^19 - 1) + 4095 < 2^31``.

Error codes in meta row 1: 0 ok · 6 renorm consumed past the announced
compressed length (host re-decode adjudicates).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from disq_tpu.ops.inflate_simd import (
    ARENAS,
    LANES,
    _bucket,
    _fetch_chunk as _inflate_fetch_chunk,
    _gather,
    _gather_ref_win,
    _pack_chunk,
    _PackArena,
    _riota,
    dispatch_window,
)
from disq_tpu.runtime.tracing import (
    count_transfer as _count_transfer,
    counter as _counter,
)

RANS_LOW = 1 << 23
TF_SHIFT = 12
TOTFREQ = 1 << TF_SHIFT

MAX_DEVICE_CSIZE = 8192 * 4 - 16   # renorm-byte cap; bigger -> host
MAX_DEVICE_RAW = 65536             # output cap; bigger -> host

# Cumulative dispatch diagnostics (callers snapshot before/after), same
# contract as ops/inflate_simd.last_stats.
last_stats = {"device_lanes": 0, "host_big": 0, "host_fallback": 0}
_U32 = jnp.uint32
_I32 = jnp.int32


def _rans0_simd_kernel(
    comp_ref, clen_ref, raw_ref, states_ref, freq_ref, cum_ref,
    out_ref, meta_ref,
    *, cw: int, ow: int,
):
    zrow = jnp.zeros((1, LANES), _I32)
    zrow_u = jnp.zeros((1, LANES), _U32)

    clen = clen_ref[...]
    raw = raw_ref[...]
    cum_all = cum_ref[...]
    freq_all = freq_ref[...]
    r257 = _riota(257)

    def refill_site(lo, mid, hi, cnt, in_w, thresh):
        """Insert one comp word at bit offset ``cnt`` for lanes with
        cnt <= 64, under a whole-warp gate so flush supersteps skip the
        comp sweep. cnt is always a multiple of 8 (refills add 32,
        renorm consumes 8)."""

        def do(lo, mid, hi, cnt, in_w):
            # windowed: lanes consume comp in near-lockstep, so the
            # sweep usually touches one slab of the comp columns
            w = _gather_ref_win(
                comp_ref, jnp.minimum(in_w, cw - 1)).astype(_U32)
            do_l = cnt <= 64
            cu = (cnt & 31).astype(_U32)
            wlo = w << cu
            whi = jnp.where(cu > 0, w >> ((_U32(32) - cu) & _U32(31)), zrow_u)
            seg0 = do_l & (cnt < 32)
            seg1 = do_l & (cnt >= 32) & (cnt < 64)
            seg2 = do_l & (cnt == 64)
            lo = jnp.where(seg0, lo | wlo, lo)
            mid = jnp.where(seg0, mid | whi, jnp.where(seg1, mid | wlo, mid))
            hi = jnp.where(seg1, hi | whi, jnp.where(seg2, hi | w, hi))
            cnt = cnt + jnp.where(do_l, 32, 0)
            in_w = in_w + jnp.where(do_l, 1, 0)
            return lo, mid, hi, cnt, in_w

        return lax.cond(
            jnp.any(cnt <= thresh), do,
            lambda lo, mid, hi, cnt, in_w: (lo, mid, hi, cnt, in_w),
            lo, mid, hi, cnt, in_w)

    def consume8(lo, mid, hi, cnt, need):
        """Drop 8 low bits for lanes in ``need`` (fixed shift — cheap)."""
        lo2 = (lo >> 8) | (mid << 24)
        mid2 = (mid >> 8) | (hi << 24)
        hi2 = hi >> 8
        return (jnp.where(need, lo2, lo), jnp.where(need, mid2, mid),
                jnp.where(need, hi2, hi), cnt - jnp.where(need, 8, 0))

    def decode_state(x, pos_j, lo, mid, hi, cnt, used):
        """One rANS decode step for one interleaved state across all
        lanes. Returns (symbol, new state, buffer, used)."""
        active = pos_j < raw
        m = x & (TOTFREQ - 1)
        s = jnp.sum(
            jnp.where((r257 >= 1) & (cum_all <= m),
                      jnp.ones((257, LANES), _I32), 0),
            axis=0, keepdims=True)
        s = jnp.minimum(s, 255)
        c = _gather(cum_all, s)
        f = _gather(freq_all, s)
        xn = f * (x >> TF_SHIFT) + m - c
        for _ in range(2):   # <= 2 renorm bytes per symbol
            need = active & (xn < RANS_LOW)
            b = (lo & _U32(0xFF)).astype(_I32)
            xn = jnp.where(need, (xn << 8) | b, xn)
            lo, mid, hi, cnt = consume8(lo, mid, hi, cnt, need)
            used = used + jnp.where(need, 1, 0)
        x = jnp.where(active, xn, x)
        sym = jnp.where(active, s, zrow)
        return sym, x, lo, mid, hi, cnt, used

    def superstep(k, carry):
        (lo, mid, hi, cnt, in_w, x0, x1, x2, x3, used, acc) = carry
        pos0 = k * 4
        lo, mid, hi, cnt, in_w = refill_site(lo, mid, hi, cnt, in_w, 48)
        s0, x0, lo, mid, hi, cnt, used = decode_state(
            x0, pos0, lo, mid, hi, cnt, used)
        s1, x1, lo, mid, hi, cnt, used = decode_state(
            x1, pos0 + 1, lo, mid, hi, cnt, used)
        lo, mid, hi, cnt, in_w = refill_site(lo, mid, hi, cnt, in_w, 32)
        s2, x2, lo, mid, hi, cnt, used = decode_state(
            x2, pos0 + 2, lo, mid, hi, cnt, used)
        s3, x3, lo, mid, hi, cnt, used = decode_state(
            x3, pos0 + 3, lo, mid, hi, cnt, used)
        packed = (s0.astype(_U32) | (s1.astype(_U32) << 8)
                  | (s2.astype(_U32) << 16) | (s3.astype(_U32) << 24))
        # accumulate into the 8-row register tile; flush once per tile
        # (uniform-row dynamic tile store — no one-hot output sweep)
        acc = jnp.where(_riota(8) == (k & 7), packed, acc)

        @pl.when((k & 7) == 7)
        def _():
            out_ref[pl.ds((k >> 3) * 8, 8), :] = acc

        return (lo, mid, hi, cnt, in_w, x0, x1, x2, x3, used, acc)

    # exactly the supersteps this chunk needs, rounded to whole tiles
    mr = jnp.max(raw)
    nsteps = (((mr + 3) >> 2) + 7) & ~7
    init = (
        zrow_u, zrow_u, zrow_u, zrow, zrow,
        states_ref[0:1, :], states_ref[1:2, :],
        states_ref[2:3, :], states_ref[3:4, :],
        zrow, jnp.zeros((8, LANES), _U32),
    )
    final = lax.fori_loop(0, nsteps, superstep, init)
    used = final[9]
    status = jnp.where(used > clen, 6, 0)
    meta_ref[...] = jnp.concatenate([used, status, zrow, zrow], axis=0)


@functools.lru_cache(maxsize=16)
def _compiled(cw: int, ow: int, interpret: bool,
              transpose: bool = False, donate: bool = False):
    kernel = functools.partial(_rans0_simd_kernel, cw=cw, ow=ow)
    call = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((ow, LANES), _U32),
            jax.ShapeDtypeStruct((4, LANES), _I32),
        ),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 6,
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        interpret=interpret,
    )
    if transpose:
        inner = call

        def call(*args):
            # lanes-major output — see inflate_simd._compiled
            words, meta = inner(*args)
            return jnp.transpose(words), meta

    nums = ()
    if donate and not interpret:
        # donate only what the runtime can alias (see
        # inflate_simd._compiled): states (4,128) i32 backs the meta
        # output exactly; comp backs the words output when shapes match
        donatable = [3]
        out_words = (LANES, ow) if transpose else (ow, LANES)
        if (cw, LANES) == out_words:
            donatable.insert(0, 0)
        nums = tuple(donatable)
    return jax.jit(call, donate_argnums=nums)


def _parse_stream(k: int, s: bytes):
    """Host-side header/table parse (O(alphabet) per stream — the
    per-byte loop is the kernel's)."""
    import struct

    from disq_tpu.cram.rans import _read_freq_table0

    order, comp_size, raw_size = struct.unpack_from("<BII", s, 0)
    if order != 0:
        raise ValueError(f"stream {k}: kernel handles order-0 only")
    if raw_size == 0:
        return None
    body = bytes(s[9: 9 + comp_size])
    freqs, off = _read_freq_table0(body, 0)
    if int(freqs.sum()) != TOTFREQ:
        raise ValueError(f"stream {k}: frequency table sum != 4096")
    states = np.frombuffer(body, dtype="<u4", count=4, offset=off)
    if int(states.max(initial=0)) >= 1 << 31:
        raise ValueError(f"stream {k}: corrupt rANS state word >= 2^31")
    # a valid encoder leaves every final state in [RANS_LOW, RANS_LOW<<8)
    # (unused states of a short stream stay exactly RANS_LOW); below the
    # bound the host renorm loop takes >2 bytes/symbol and the kernels'
    # 2-step unroll would silently diverge from it
    if int(states.min(initial=RANS_LOW)) < RANS_LOW:
        raise ValueError(f"stream {k}: corrupt rANS state word < 2^23")
    cum = np.zeros(257, dtype=np.int64)
    np.cumsum(freqs, out=cum[1:])
    return raw_size, body[off + 16:], states, freqs, cum


def _host_decode0(s: bytes) -> bytes:
    import struct

    from disq_tpu.cram.rans import _decode0

    try:
        from disq_tpu.native import rans_decode_native

        return rans_decode_native(s)
    except ImportError:
        _order, comp_size, raw_size = struct.unpack_from("<BII", s, 0)
        return _decode0(memoryview(s)[9: 9 + comp_size], raw_size)


def kernel_geometry(metas):
    """(cw, ow) bucket the production wrapper compiles for a set of
    parsed streams — single source of truth (the TPU CI lane's
    kernel-only row builds its launch with this too)."""
    max_c = max(len(m[1]) for m in metas)
    max_r = max(m[0] for m in metas)
    cw = _bucket((max_c + 8) // 4 + 2)
    ow = min(_bucket(max(8, (max_r + 3) // 4)), MAX_DEVICE_RAW // 4)
    return cw, ow


def _rans_arena(cw: int) -> _PackArena:
    """Staging arena for one rANS chunk: the shared comp/clen columns
    plus the per-lane table arrays as reusable extras."""
    arena = _PackArena(cw)
    arena.extras = {
        "raws": np.zeros((1, LANES), np.int32),
        "states": np.zeros((4, LANES), np.int32),
        "freq": np.zeros((256, LANES), np.int32),
        "cum": np.zeros((257, LANES), np.int32),
    }
    return arena


def pack_lane_tables(metas, cw: int, arena: Optional[_PackArena] = None):
    """Kernel input arrays for <=128 parsed streams: packed renorm
    columns + (clen, raw, states, freq, cum) lane tables.  With an
    ``arena`` (from ``_rans_arena``) every array is written in place;
    stale ``raws`` are zeroed so unused lanes stay inactive (their
    leftover state/freq/cum columns are never read as symbols — the
    kernel masks everything on ``pos < raw``)."""
    comp, clen = _pack_chunk([m[1] for m in metas], cw, arena)
    if arena is None:
        raws = np.zeros((1, LANES), np.int32)
        states = np.zeros((4, LANES), np.int32)
        freq = np.zeros((256, LANES), np.int32)
        cum = np.zeros((257, LANES), np.int32)
    else:
        ex = arena.extras
        raws, states, freq, cum = (
            ex["raws"], ex["states"], ex["freq"], ex["cum"])
        raws[:] = 0
    for i, (raw_size, _renorm, st, fr, cm) in enumerate(metas):
        raws[0, i] = raw_size
        states[:, i] = st.astype(np.int64).astype(np.int32)
        freq[:, i] = fr
        cum[:, i] = cm
    return comp, clen, raws, states, freq, cum


def _fetch_chunk(handle, lanes: int, labels=None):
    """Materialize one launched rANS chunk (the inflate codec's wait
    and d2h spans, ``kind="rans"``) and book the D2H bytes; returns
    (lanes-major u8 view, meta)."""
    return _inflate_fetch_chunk(
        handle, lanes, labels or {"kind": "rans", "lanes": lanes},
        kernel="rans_simd")


def rans0_decode_simd(
    streams: Sequence[bytes], interpret: Optional[bool] = None,
) -> List[bytes]:
    """Decode order-0 rANS 4x8 streams (full streams incl. the 9-byte
    header) on the 128-lane SIMD kernel, 128 streams per launch.

    Streams past the device caps go to the host codec; lanes that fail
    in-kernel (renorm overran ``comp_size``) are re-decoded on host,
    which raises the same exceptions the host path always has.
    """
    if interpret is None:
        from disq_tpu.util import pallas_interpret

        interpret = pallas_interpret()
    n = len(streams)
    if n == 0:
        return []
    metas = [_parse_stream(k, s) for k, s in enumerate(streams)]
    big = {
        k for k, m in enumerate(metas)
        if m is not None
        and (len(m[1]) > MAX_DEVICE_CSIZE or m[0] > MAX_DEVICE_RAW)
    }
    live = [k for k, m in enumerate(metas) if m is not None and k not in big]
    out: List[Optional[bytes]] = [
        b"" if metas[k] is None else None for k in range(n)
    ]
    if not live:
        for k in big:
            last_stats["host_big"] += 1
            out[k] = _host_decode0(streams[k])
        return [o if o is not None else b"" for o in out]

    cw, ow = kernel_geometry([metas[k] for k in live])
    fn = _compiled(cw, ow, bool(interpret), True, True)

    chunks = [live[lo: lo + LANES] for lo in range(0, len(live), LANES)]
    # inputs: comp + clen + raws + states + freq + cum columns
    chunk_bytes = (cw + 1 + 1 + 4 + 256 + 257) * LANES * 4 \
        + (ow + 4) * LANES * 4
    window = dispatch_window(len(chunks), chunk_bytes)
    launched: List = []

    def launch(chunk):
        arena = ARENAS.acquire(("rans", cw), lambda: _rans_arena(cw))
        args = pack_lane_tables([metas[k] for k in chunk], cw, arena)
        _count_transfer("h2d", sum(a.nbytes for a in args))
        return fn(*(jnp.asarray(a) for a in args)), arena

    try:
        for chunk in chunks[:window]:
            launched.append(launch(chunk))
        # oversize streams decode on host while the first window is in
        # flight on device
        for k in big:
            last_stats["host_big"] += 1
            _counter("device.host_fallback_blocks").inc(reason="oversize")
            out[k] = _host_decode0(streams[k])
        for ci, chunk in enumerate(chunks):
            handle, arena = launched[ci]
            lanes_u8, meta = _fetch_chunk(handle, len(chunk))
            launched[ci] = None
            ARENAS.release(("rans", cw), arena)
            if ci + window < len(chunks):
                launched.append(launch(chunks[ci + window]))
            for i, k in enumerate(chunk):
                raw_size = metas[k][0]
                if int(meta[1, i]) != 0:
                    last_stats["host_fallback"] += 1
                    _counter("device.host_fallback_blocks").inc(
                        reason="flagged")
                    out[k] = _host_decode0(streams[k])
                else:
                    last_stats["device_lanes"] += 1
                    out[k] = lanes_u8[i, :raw_size].tobytes()
    finally:
        # abandoned window (host fallback raised): return the arenas
        for entry in launched:
            if entry is not None:
                ARENAS.release(("rans", cw), entry[1])
    return [o if o is not None else b"" for o in out]
