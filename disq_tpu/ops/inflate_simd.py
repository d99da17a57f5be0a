"""128-lane SIMD raw-DEFLATE inflate — lane-parallel streams.

The north-star device codec (SURVEY.md §2.8 row 1, §7 step 2; reference
behavior: htsjdk ``BlockCompressedInputStream`` + zlib ``Inflater``).
DEFLATE entropy decode is bit-serial inside a stream, so the kernel is
**lane-parallel SIMD** across streams: 128 independent DEFLATE streams,
one per vector lane, every piece of decoder state a ``(1, 128)``
vector.

Per superstep (one ``lax.while_loop`` iteration), every lane advances
its own predicated state machine by pure vector selects; rare events
(table finalization, dyn-block entry, table-phase stores) are gated
with ``pl.when``, the far-history sweeps behind a ``lax.cond``
whole-warp gate, and the sweep of the compressed words behind a
``lax.cond`` on the loop counter (once in ``COMP_PERIOD`` supersteps:
no reduction across the lanes). The phases of a superstep run in a
fixed order, each on the state the one before left: phase A (header /
stored / one table-build step / one literal-or-length symbol, or a
literal pair), phase B (the distance of the length phase A has just
read), then the copy phase (the lanes inside a match, those phase B
has just put there included). So a token costs one superstep: a literal (two when the
next token is a literal too), or a match's length + distance + first
copy chunk together; a long match then takes one superstep more per chunk. An
emit is placed at the output's byte offset ``off`` and may run past the
output word's boundary: a literal pair is taken at every offset, and a
copy chunk runs to the end of the fourth output word for d >= 16
(``16 - off`` bytes), of the second for d >= 8 (``8 - off``), of the
first below (``4 - off``), so a short match is one chunk wherever it
starts. ``meta`` row 2 carries the launch's superstep count (counter
``device.inflate.supersteps``), row 3 how many of them read history
past the ring (``device.inflate.far_supersteps``), row 4 each lane's
count of copy chunks that ran past the word boundary they started in
(summed over the lanes: ``device.inflate.crossing_chunks``), row 5 the
supersteps in which the compressed buffer was swept
(``device.inflate.comp_fetches``). A lane
emits 1-2 bytes per literal superstep, up to 4 per stored superstep,
and up to 4, 8 or 16 per copy superstep (d < 8 / d >= 8 / d >= 16).
All data-dependent indexing is a one-hot sweep: the row gather
``sum(where(row_iota == idx, data, 0))``, pure compares, selects and a
sublane reduction, which Mosaic lowers for any row count (whether
``take_along_axis`` would now lower as well has not been tried on this
compiler), and its tile form ``_gather_tile``, which selects a lane's
aligned 8-row tile (one stored register) instead of a lane's row: the
same sweep, eight consecutive words a lane instead of one. The writes
are its mirror image, ``_scatter_tile``: a superstep's up-to-four
output words are placed once, on single registers, in two (8,128) tile
patches, and a sweep merges the patches into a lane's two aligned
tiles with two compares a stored register. Big-buffer
sweeps (the comp window, output RMW, far-history reads) are
additionally *windowed*: lanes advance in rough lockstep, so each
slab's sweep is skipped when the live tile window [min, max] misses
it. The compressed words are not read where they are consumed: a lane
takes at most one word at each of a superstep's two refill sites, so
the ``_COMP_TILES`` aligned tiles from the one that holds its next
word cover everything it can take in ``COMP_PERIOD`` supersteps; the
loop carries them, one windowed tile sweep of ``comp_ref`` refreshes
them on that schedule, and a refill picks its word out of the carried
registers (``_pick_word``). Bool (1,128)
vectors are carried as i32 across ``lax.cond`` branches, unsigned
reductions and min run in i32: workarounds written against an earlier
Mosaic. This installation (jax 0.9.0, libtpu 0.0.34) compiles the
kernel as it stands: the narrow geometries (comp up to 4 MB + out 8 MB
+ tables whole in VMEM) with no ``vmem_limit_bytes``, the wide one
(comp 8 MB, for BGZF payloads over 32,752 bytes) with the limit raised
to 32 MiB for that geometry alone; whether it would also take the
constructs those workarounds avoid is untested.

Huffman decoding is bit-serial canonical (puff-style count/first/offset
walk) rather than root-table driven: the per-length arrays are (16,128)
columns read at *compile-time* row indices inside the unrolled 15-step
code walk (free), leaving exactly one one-hot gather per symbol (the
sorted-symbol table). This removes the 512-entry per-lane root-table
construction sweep entirely — dynamic table build reduces to counting
sorts over the code-length arrays.

Memory (v1): compressed words, output words, all tables and a ring of
each lane's last 4 KiB of output live whole in VMEM, at every geometry:
a payload up to ``NARROW_CSIZE`` (32,752 bytes) launches at cw <= 8192
(12.5 MB with the full output), a wider one, up to BGZF's largest
(``MAX_DEVICE_CSIZE``, 65,510 bytes), at cw 16384 (16.5 MB: past the
16 MiB scoped default, so ``_compiled`` raises ``vmem_limit_bytes`` for
that geometry and leaves the others' programs as they were; a v5e core
has 128 MiB). The sweeps of ``comp_ref`` are windowed by slab, so a
superstep pays for the live window and not for cw. A copy step needs
up to five consecutive history words a lane; they lie inside two
aligned 8-word tiles, so it reads them with two tile sweeps of the
1,024-row ring and, in the supersteps where some lane's distance
reaches past the ring (``meta`` row 3 counts them), two windowed tile
sweeps of the (OW,128) output that share their slab gates. An emit's
up-to-four output words lie inside two aligned tiles as well, so a
superstep writes them as two tile patches: OR-ed into the output's live
slabs behind the same kind of gates (the hull of the live tiles: two
reductions), and merged under their byte masks into all of the ring
(rows recycle, so bytes are replaced) in every superstep in which some
lane emits. Correct and Mosaic-friendly, but the sweeps scale with
buffer size; the windowed slab gates above are what bounds them, and a
gate is not free (PERF.md §5).

Error codes in meta row 1: 0 ok · 1 bad btype · 2 stored-LEN mismatch ·
3 bad Huffman code · 4 invalid distance · 5 output overflow · 6 ran past
the compressed payload · 7 code-length repeat overflow · 8 ISIZE
mismatch (host-side).
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from disq_tpu.runtime.tracing import (
    count_transfer as _count_transfer,
    counter as _counter,
    gauge as _gauge,
    span as _span,
    track_hbm as _track_hbm,
)

LANES = 128

# Cumulative dispatch diagnostics (callers snapshot before/after):
# device_lanes = payloads decoded in-kernel; host_big = payloads over
# the comp cap routed to host by design; host_fallback = lanes the
# kernel flagged (nonzero status / usize mismatch) that host zlib then
# re-inflated — for well-formed in-cap streams this must stay 0.
last_stats = {"device_lanes": 0, "host_big": 0, "host_fallback": 0}

_MAXLENS = 320          # 288 lit/len + 32 dist code lengths
_SLAB = 2048            # slab rows for big-buffer one-hot ops (VMEM temps)
RING_W = 1024           # history ring: last 4 KiB per lane, word rows
RING_SAFE = 4096 - 8    # max distance served by the ring
# The comp cap is BGZF's largest payload: a block is at most 65,536
# bytes with its 18-byte header and 8-byte footer.  A raw stream over
# the cap, or one whose output is over MAX_DEVICE_USIZE, goes to the host.
MAX_DEVICE_CSIZE = 65536 - 26
MAX_DEVICE_USIZE = 65536
# Payloads up to here launch at cw <= 8192, the standing geometry; wider
# ones at cw 16384, whose buffers are past the scoped-VMEM default
# (``_compiled``).  The decode service queues the two apart.
NARROW_CSIZE = 8192 * 4 - 16
# A comp sweep serves COMP_PERIOD supersteps. A lane takes at most two
# words a superstep (one a refill site), so from word in_w it reads
# in_w .. in_w + 2 * COMP_PERIOD - 1: all inside the _COMP_TILES aligned
# 8-word tiles from tile in_w >> 3 on, which the loop carries. A power
# of two: the schedule is a mask of the loop counter.
COMP_PERIOD = 4
_WIDE_VMEM_LIMIT = 32 << 20
_COMP_TILES = (2 * COMP_PERIOD + 14) // 8
_U32 = jnp.uint32
_I32 = jnp.int32

# Lane states.
_HEADER, _SLEN, _SNLEN, _SCOPY = 0, 1, 2, 3
_TBHDR, _TBCLLEN, _TBCODELEN = 4, 5, 6
_DECODE, _COPY, _DONE, _ERR = 7, 8, 9, 10

_NLIT = 288  # literal/length alphabet size
# Length codes 257..285 (RFC 1951 §3.2.5).
_LBASE = np.array(
    [3, 4, 5, 6, 7, 8, 9, 10, 11, 13, 15, 17, 19, 23, 27, 31, 35, 43, 51,
     59, 67, 83, 99, 115, 131, 163, 195, 227, 258], dtype=np.int32)
_LEXT = np.array(
    [0, 0, 0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 2, 2, 2, 2, 3, 3, 3, 3, 4, 4, 4,
     4, 5, 5, 5, 5, 0], dtype=np.int32)
# Distance codes 0..29 (padded to the 32-symbol alphabet).
_DBASE = np.array(
    [1, 2, 3, 4, 5, 7, 9, 13, 17, 25, 33, 49, 65, 97, 129, 193, 257, 385,
     513, 769, 1025, 1537, 2049, 3073, 4097, 6145, 8193, 12289, 16385,
     24577, 0, 0], dtype=np.int32)
_DEXT = np.array(
    [0, 0, 0, 0, 1, 1, 2, 2, 3, 3, 4, 4, 5, 5, 6, 6, 7, 7, 8, 8, 9, 9, 10,
     10, 11, 11, 12, 12, 13, 13, 0, 0], dtype=np.int32)
# Order in which code-length code lengths are stored (RFC 1951 §3.2.7).
_CLORDER = np.array(
    [16, 17, 18, 0, 8, 7, 9, 6, 10, 5, 11, 4, 12, 3, 13, 2, 14, 1, 15],
    dtype=np.int32)
# Fixed-Huffman code lengths (RFC 1951 §3.2.6), lit then dist.
_FIXED_LENS = np.concatenate(
    [np.full(144, 8), np.full(112, 9), np.full(24, 7), np.full(8, 8),
     np.full(32, 5)]
).astype(np.int32)


def _canonical_np(lens: np.ndarray, maxbits: int):
    """count / first-code / symbol-offset arrays + (len,sym)-sorted
    symbol list for a canonical Huffman code (puff's decode walk)."""
    cnt = np.zeros(maxbits + 1, np.uint32)
    for l in lens:
        if l:
            cnt[l] += 1
    first = np.zeros(maxbits + 1, np.uint32)
    off = np.zeros(maxbits + 1, np.uint32)
    for l in range(2, maxbits + 1):
        first[l] = (first[l - 1] + cnt[l - 1]) << 1
        off[l] = off[l - 1] + cnt[l - 1]
    symidx = np.array(
        [s for l in range(1, maxbits + 1) for s in np.nonzero(lens == l)[0]],
        np.int32,
    )
    return cnt, first, off, symidx


_FLENS_L = _FIXED_LENS[:_NLIT]
_FLENS_D = _FIXED_LENS[_NLIT:]
_FCNT_L, _FFIRST_L, _FOFF_L, _FSYM_L = _canonical_np(_FLENS_L, 15)
_FCNT_D, _FFIRST_D, _FOFF_D, _FSYM_D = _canonical_np(_FLENS_D, 15)
_FSYM_L_PAD = np.zeros(_MAXLENS, np.int32)
_FSYM_L_PAD[: len(_FSYM_L)] = _FSYM_L
_FSYM_D_PAD = np.zeros(32, np.int32)
_FSYM_D_PAD[: len(_FSYM_D)] = _FSYM_D


def _riota(rows: int) -> jnp.ndarray:
    return lax.broadcasted_iota(_I32, (rows, LANES), 0)


def _gather_ref(ref, rows, slab: int = _SLAB):
    """One-hot row gather reading a (possibly large) REF slab-wise so no
    full-buffer temporary materializes (scoped-vmem stack is ~16 MB
    minus persistent buffers). OR-merge works because exactly one slab
    contains each lane's row and misses contribute zero."""
    r = ref.shape[0]
    if r <= slab:
        return _gather(ref[...], rows)
    acc = None
    for s in range(0, r, slab):
        sl = min(slab, r - s)
        g = _gather(ref[s:s + sl, :], rows - s)
        acc = g if acc is None else acc | g
    return acc


def _gather_ref_win(ref, rows, slab: int = _SLAB):
    """Windowed one-hot row gather: like ``_gather_ref`` but each
    slab's sweep is skipped (``lax.cond``) when no lane's row lands in
    it. Lanes decode at similar rates, so the live row window [min,
    max] usually spans one or two slabs and the other sweeps vanish —
    the big-buffer gathers drop from O(R) to O(window). Row -1 (the
    folded-miss convention) never anchors the window."""
    r = ref.shape[0]
    if r <= slab:
        return _gather(ref[...], rows)
    rmin = jnp.min(jnp.where(rows < 0, jnp.int32(r), rows))
    rmax = jnp.max(rows)
    acc = jnp.zeros((1, LANES), ref.dtype)
    for s in range(0, r, slab):
        sl = min(slab, r - s)

        def hit(s=s, sl=sl):
            return _gather(ref[s:s + sl, :], rows - s)

        g = lax.cond(
            (rmax >= s) & (rmin < s + sl), hit,
            lambda: jnp.zeros((1, LANES), ref.dtype))
        acc = acc | g
    return acc


def _gather(data, rows):
    """One-hot row gather: data (R,128), rows (1,128) → (1,128).
    The kernel's only per-lane dynamic-index read. Unsigned data is
    bitcast through i32 — Mosaic has no unsigned reductions."""
    r = data.shape[0]
    unsigned = data.dtype == jnp.uint32
    if unsigned:
        data = lax.bitcast_convert_type(data, _I32)
    g = jnp.sum(
        jnp.where(_riota(r) == rows, data, jnp.zeros_like(data)),
        axis=0,
        keepdims=True,
    )
    return lax.bitcast_convert_type(g, _U32) if unsigned else g


def _gather_tile(data, tiles):
    """One-hot tile gather: data (R,128) with R a multiple of 8, tiles
    (1,128) per-lane tile index → (8,128), lane l holding
    ``data[8 * tiles[l] : 8 * tiles[l] + 8, l]``. Tile -1 matches
    nothing and gives zeros, as row -1 does in ``_gather``. An (R,128)
    buffer is stored as R/8 registers of 8 sublanes × 128 lanes, so this
    sweep costs what ``_gather``'s does (a compare, a select and an
    accumulate a register, with no sublane reduction at the end) and
    returns eight consecutive words a lane where that returns one."""
    nt = data.shape[0] // 8
    unsigned = data.dtype == jnp.uint32
    if unsigned:
        data = lax.bitcast_convert_type(data, _I32)
    data = data.reshape(nt, 8, LANES)
    ti = lax.broadcasted_iota(_I32, (nt, 8, LANES), 0)
    t = jnp.sum(
        jnp.where(ti == tiles[None], data, jnp.zeros_like(data)), axis=0)
    return lax.bitcast_convert_type(t, _U32) if unsigned else t


def _pick_word(tiles, first, rows):
    """Word ``rows[l]`` of lane l out of the consecutive (8,128) tiles
    ``tiles``, whose first is the lane's tile ``first[l]``: ``rows -
    8 * first`` lies in [0, 8 * len(tiles)), and the pick is a one-hot
    over those few registers (no ref access, no gate)."""
    return _gather(jnp.concatenate(tiles, axis=0), rows - (first << 3))


def _tile_hull(tiles, n_tiles: int):
    """(min, max) over the live tiles of the (1,128) index vectors
    ``tiles``, as two scalars: the window the slab gates test. Tile -1
    never anchors it; with no live tile the max is -1 (and the min
    ``n_tiles``), so every gate stays shut."""
    lo = functools.reduce(
        jnp.minimum, [jnp.where(t < 0, jnp.int32(n_tiles), t) for t in tiles])
    return jnp.min(lo), jnp.max(functools.reduce(jnp.maximum, tiles))


def _gather_tiles_ref_win(ref, tiles, slab: int = _SLAB):
    """Windowed tile gather over a (possibly large) REF: one
    ``_gather_tile`` sweep for each (1,128) index vector of ``tiles``,
    behind ``_gather_ref_win``'s slab gates. The vectors share the
    gates: a slab's sweeps are skipped (``lax.cond``) when the hull
    [min, max] of all their live tiles misses it, and tile -1 never
    anchors the hull. A gate and the hull's two reductions cost more
    than the sweep they guard (PERF.md §5), so vectors that are read
    together are gated together."""
    r = ref.shape[0]
    if r <= slab:
        return tuple(_gather_tile(ref[...], t) for t in tiles)
    tmin, tmax = _tile_hull(tiles, r // 8)
    zeros = (jnp.zeros((8, LANES), ref.dtype),) * len(tiles)
    acc = zeros
    for s in range(0, r, slab):
        sl = min(slab, r - s)

        def hit(s=s, sl=sl):
            return tuple(
                _gather_tile(ref[s:s + sl, :], t - s // 8) for t in tiles)

        got = lax.cond(
            (tmax >= s // 8) & (tmin < (s + sl) // 8), hit, lambda: zeros)
        acc = tuple(a | g for a, g in zip(acc, got))
    return acc


def _scatter_tile(data, tiles, patches, masks=None):
    """Tile scatter, ``_gather_tile``'s mirror image: data (R,128) with
    R a multiple of 8, ``tiles`` two (1,128) per-lane tile indices and
    ``patches`` an (8,128) patch for each → data with lane l's tile
    ``tiles[i][l]`` merged with ``patches[i][:, l]``, every other tile
    as it was. Without ``masks`` the patch is OR-ed in (bytes that land
    once in a zeroed buffer); with them (an (8,128) bit mask a patch)
    it replaces the bits under the mask and keeps the rest. Tile -1
    matches nothing; a lane's two tiles differ (or match nothing). The
    sweep is two compares a stored register, whatever the patches
    hold: a superstep's up-to-four output words are placed in their
    two patches once, on single registers, not once a register of the
    buffer."""
    nt = data.shape[0] // 8
    cur = data.reshape(nt, 8, LANES)
    ti = lax.broadcasted_iota(_I32, (nt, 8, LANES), 0)
    hit0, hit1 = (ti == t[None] for t in tiles)
    if masks is None:
        zero = jnp.zeros_like(patches[0])
        new = cur | jnp.where(
            hit0, patches[0], jnp.where(hit1, patches[1], zero))
    else:
        # both merged tiles are made on single registers' operands and
        # then chosen: 0.07 us a superstep under choosing the mask and
        # the patch first and merging once (PERF.md §5, PR 37)
        new = jnp.where(
            hit0, (cur & ~masks[0]) | patches[0],
            jnp.where(hit1, (cur & ~masks[1]) | patches[1], cur))
    return new.reshape(data.shape)


def _scatter_tiles_ref_win(ref, tiles, patches, hull, slab: int = _SLAB):
    """Windowed tile scatter into a (possibly large) zero-initialised
    REF: ``_scatter_tile``'s OR-merge slab-wise (no full-buffer
    temporary), behind ``_gather_tiles_ref_win``'s slab gates: a slab's
    sweep is skipped (``pl.when``) when ``hull``, the ``_tile_hull`` of
    ``tiles``, misses it. Two reductions for the hull, whatever the
    patches hold; the caller makes them, and may gate more on them."""
    r = ref.shape[0]
    tmin, tmax = hull
    for s in range(0, r, slab):
        sl = min(slab, r - s)

        @pl.when((tmax >= s // 8) & (tmin < (s + sl) // 8))
        def _(s=s, sl=sl):
            ref[s:s + sl, :] = _scatter_tile(
                ref[s:s + sl, :], tuple(t - s // 8 for t in tiles), patches)


def _bcast_np(arr: np.ndarray) -> np.ndarray:
    """(R,) constant broadcast to (R,128) — passed as a kernel input
    (Pallas forbids captured array constants)."""
    return np.broadcast_to(
        np.asarray(arr, np.int32)[:, None], (len(arr), LANES)
    ).copy()


# Constant tables shipped to the kernel as one (R,128) input each.
_CONST_TABLES = tuple(
    _bcast_np(a)
    for a in (_CLORDER, _FSYM_L_PAD, _FSYM_D_PAD, _LEXT, _LBASE, _DEXT,
              _DBASE)
)


def _store_row(ref, rows, vals, mask):
    """One-hot row store: ref[rows[l], l] = vals[l] where mask[l].
    The mask is folded into the row index (row -1 matches nothing) so
    the predicate keeps the pure ``iota == rows`` one-hot shape."""
    r = ref.shape[0]
    folded = jnp.where(mask, rows, -1)
    cur = ref[...]
    ref[...] = jnp.where(_riota(r) == folded, vals, cur)


def _masked_rows(ref, new, mask):
    """ref[:, l] = new[:, l] where mask[l] (full-column select-merge)."""
    ref[...] = jnp.where(mask, new, ref[...])


def _build_canonical(lens_ref, region_lo, region_hi, sym_bias, maxbits,
                     cnt_ref, first_ref, off_ref, curs_ref, sym_ref, mask):
    """Vectorized canonical table build for the lanes in ``mask``.

    ``lens_ref`` is (R,128) code lengths; the alphabet for each lane is
    rows [region_lo, region_hi) with symbol value row - sym_bias. Writes
    the count/first/offset rows and the (len,sym)-sorted symbol table
    via a counting sort of one-hot stores. Rows are read back through
    the ref (dynamic uniform-row ref reads lower on Mosaic; dynamic
    slices of loaded arrays do not).
    """
    lens = lens_ref[...]
    r = lens.shape[0]
    ri = _riota(r)
    region = (ri >= region_lo) & (ri < region_hi)
    cnts = []
    for l in range(1, maxbits + 1):
        c = jnp.sum(
            jnp.where(region & (lens == l), jnp.ones_like(lens), 0),
            axis=0, keepdims=True,
        ).astype(_U32)
        cnts.append(c)
    first = jnp.zeros((1, LANES), _U32)
    off = jnp.zeros((1, LANES), _U32)
    zero = jnp.zeros((1, LANES), _U32)
    first_rows, off_rows = [zero], [zero]
    for l in range(1, maxbits + 1):
        if l > 1:
            first = (first + cnts[l - 2]) << 1
            off = off + cnts[l - 2]
        first_rows.append(first)
        off_rows.append(off)
    cnt_new = jnp.concatenate([zero] + cnts, axis=0)
    first_new = jnp.concatenate(first_rows, axis=0)
    off_new = jnp.concatenate(off_rows, axis=0)
    _masked_rows(cnt_ref, cnt_new, mask)
    _masked_rows(first_ref, first_new, mask)
    _masked_rows(off_ref, off_new, mask)
    _masked_rows(curs_ref, off_new, mask)

    def body(p, _):
        len_p = lens_ref[pl.ds(p, 1), :].astype(_I32)
        in_reg = (
            mask
            & (p >= region_lo) & (p < region_hi)
            & (len_p > 0)
        )
        rank = _gather(curs_ref[...].astype(_I32), len_p)
        _store_row(
            sym_ref, rank,
            jnp.full((1, LANES), 0, _I32) + (p - sym_bias), in_reg,
        )
        _store_row(curs_ref, len_p, (rank + 1).astype(_U32), in_reg)
        return 0

    lax.fori_loop(0, r, body, 0)


def _decode_canonical(bitbuf, maxbits, cnt, first, off,
                      fcnt=None, ffirst=None, foff=None, fixed=None):
    """Puff-style canonical walk, vectorized over lanes: returns
    (symbol-table index, code length, found). ``cnt``/``first``/``off``
    are (16,128) per-lane arrays; the optional f* numpy arrays are the
    fixed-Huffman constants select-merged in for lanes with ``fixed``."""
    code = jnp.zeros((1, LANES), _U32)
    rem = bitbuf
    idx = jnp.zeros((1, LANES), _I32)
    nbits = jnp.zeros((1, LANES), _I32)
    found = jnp.zeros((1, LANES), jnp.bool_)
    for l in range(1, maxbits + 1):
        bit = (rem & 1).astype(_U32)
        rem = rem >> 1
        code = (code << 1) | bit
        c = cnt[l][None, :]
        f = first[l][None, :]
        o = off[l][None, :]
        if fixed is not None:
            c = jnp.where(fixed, _U32(int(fcnt[l])), c)
            f = jnp.where(fixed, _U32(int(ffirst[l])), f)
            o = jnp.where(fixed, _U32(int(foff[l])), o)
        hit = (~found) & ((code - f) < c)
        idx = jnp.where(hit, (o + (code - f)).astype(_I32), idx)
        nbits = jnp.where(hit, l, nbits)
        found = found | hit
    return idx, nbits, found


def _mask_bits(n):
    """(1 << n) - 1 for per-lane n in [0, 32]. The clamp runs in i32 —
    Mosaic cannot legalize unsigned min."""
    n = n.astype(_I32)
    full = n >= 32
    safe = jnp.minimum(n, 31).astype(_U32)
    return jnp.where(full, _U32(0xFFFFFFFF), (_U32(1) << safe) - 1)


def _inflate_simd_kernel(
    comp_ref, clen_ref,
    clorder_ref, fsyml_ref, fsymd_ref, lext_ref, lbase_ref, dext_ref,
    dbase_ref,
    out_ref, meta_ref,
    lens_ref, cl_lens_ref,
    symlit_ref, symdist_ref, symcl_ref,
    cntl_ref, firstl_ref, offl_ref, cursl_ref,
    cntd_ref, firstd_ref, offd_ref, cursd_ref,
    cntc_ref, firstc_ref, offc_ref, cursc_ref,
    ring_ref,
    *, cw: int, ow: int, max_steps: int, slab: int,
):
    zrow = jnp.zeros((1, LANES), _I32)
    zrow_u = jnp.zeros((1, LANES), _U32)
    # slab-wise init + RMW below keep peak scoped-vmem temps ~1 MB so
    # comp (8192,128) fits alongside out (16384,128)
    for _s in range(0, ow, slab):
        _sl = min(slab, ow - _s)
        out_ref[_s:_s + _sl, :] = jnp.zeros((_sl, LANES), _U32)
    for ref in (symlit_ref, symdist_ref, symcl_ref, lens_ref, cl_lens_ref):
        ref[...] = jnp.zeros(ref.shape, ref.dtype)
    for ref in (cntl_ref, firstl_ref, offl_ref, cursl_ref,
                cntd_ref, firstd_ref, offd_ref, cursd_ref,
                cntc_ref, firstc_ref, offc_ref, cursc_ref, ring_ref):
        ref[...] = jnp.zeros(ref.shape, ref.dtype)

    clen = clen_ref[...].astype(_I32)

    # 64-bit bit buffer as a (lo, hi) u32 pair + total valid-bit count.
    # One *word-aligned* word per refill site. A refill turns any cnt
    # in [0, 32] into cnt + 32, so after it cnt >= 32 and the low word
    # is whole, whatever was consumed before; two refill sites per
    # superstep keep every phase's peek within the low word.
    # Pre-phase-A: 32 valid bits, phase A consumes <= 32 (a
    # word-aligned 4-byte stored copy; Huffman paths <= 30 — two
    # literal codes of <= 15 bits each, or a 15-bit length code + 5
    # extra bits). Pre-phase-B: 32 valid bits again, so a match's
    # distance can follow its length in the same superstep: the dist
    # code (<= 15) is consumed first, which leaves >= 17 >= its 13
    # extra bits. No unaligned double-gather assembly.
    # A site adds at most one word to a lane, so in_w rises by at most
    # 2 a superstep: the words a lane can want in COMP_PERIOD
    # supersteps lie in the tiles ``ctiles`` the carry holds for it
    # (the lane's tiles ``ct`` on: ``comp_sweep`` below), and a site
    # picks its word out of those registers. comp_ref is read nowhere
    # else. A malformed lane that runs past its payload reads row
    # cw - 1 again and again until the overrun guard flags it.
    def refill64(lo, hi, cnt, in_w, ctiles, ct):
        w = _pick_word(ctiles, ct, jnp.minimum(in_w, cw - 1))
        do = cnt <= 32
        cu = jnp.minimum(cnt, 31).astype(_U32)
        lo = jnp.where(do & (cnt < 32), lo | (w << cu), lo)
        hi_add = jnp.where(
            cnt == 32, w,
            jnp.where(cnt > 0, w >> ((_U32(32) - cu) & _U32(31)),
                      zrow_u))
        hi = jnp.where(do, hi | hi_add, hi)
        cnt = cnt + jnp.where(do, 32, 0)
        in_w = in_w + jnp.where(do, 1, 0)
        return lo, hi, cnt, in_w

    def comp_sweep(ct, live):
        """The carry's compressed window, read anew: every live lane's
        ``_COMP_TILES`` tiles from tile ``ct`` on (a tile past the
        buffer, and every tile of a lane that is done or flagged, is
        -1: zeros, and no anchor of the hull), in one windowed sweep
        behind one set of slab gates."""
        tiles = tuple(
            jnp.where(live & (ct + j < cw // 8), ct + j, -1)
            for j in range(_COMP_TILES))
        return _gather_tiles_ref_win(comp_ref, tiles, slab=slab)

    def consume64(lo, hi, cnt, n):
        """Drop n (0..32, per-lane) low bits from the pair. n == 32
        (a word-aligned 4-byte stored copy) is handled explicitly —
        u32 shift-by-32 is implementation-defined on XLA backends."""
        nu = jnp.minimum(n, 31).astype(_U32)
        n0 = n == 0
        full = n >= 32
        lo2 = (lo >> nu) | (hi << ((_U32(32) - nu) & _U32(31)))
        lo2 = jnp.where(full, hi, lo2)
        hi2 = jnp.where(full, zrow_u, hi >> nu)
        return (jnp.where(n0, lo, lo2), jnp.where(n0, hi, hi2), cnt - n)

    def superstep(carry):
        (step, state, lo, hi, cnt, in_w, outpos, bfinal, fixed,
         copy_len, copy_dist, hlit, hdist, hclen, tb_idx, tb_nread,
         rep_val, rep_cnt, prev_len, status, far_steps,
         crossing, ctiles, ct, comp_fetches) = carry

        live = (state != _DONE) & (state != _ERR)
        # the comp sweep runs on the loop counter's schedule, not on a
        # condition reduced across the lanes
        sweep = step & (COMP_PERIOD - 1) == 0
        ct = jnp.where(sweep, jnp.minimum(in_w, cw - 1) >> 3, ct)
        ctiles, comp_fetches = lax.cond(
            sweep,
            lambda: (comp_sweep(ct, live), comp_fetches + 1),
            lambda: (ctiles, comp_fetches))
        lo, hi, cnt, in_w = refill64(lo, hi, cnt, in_w, ctiles, ct)
        bitbuf = lo

        new_state = state
        new_status = status
        # emit: packed = the chunk's LE bytes from its first (a copy
        # chunk's later words follow in the copy phase), emit_k = its
        # byte count; the emit merge places it at the output's byte
        # offset. A stored chunk stops at the word boundary (<= 4
        # bytes: it is consumed from the 32-bit peek).
        emit_k = zrow
        packed = zrow_u
        off = outpos & 3
        kmax = 4 - off       # bytes until the word boundary
        used = zrow          # bits consumed in phase A

        after_block = jnp.where(bfinal != 0, _DONE, _HEADER)

        # ---- HEADER --------------------------------------------------
        m = state == _HEADER
        hdr = (bitbuf & 7).astype(_I32)
        h_bfinal = hdr & 1
        btype = (hdr >> 1) & 3
        # stored: skip to byte boundary right here (3 + pad bits)
        h_pad = (cnt - 3) & 7
        h_used = jnp.where(btype == 0, 3 + h_pad, 3)
        h_state = jnp.where(
            btype == 0, _SLEN,
            jnp.where(btype == 1, _DECODE,
                      jnp.where(btype == 2, _TBHDR, _ERR)))
        new_state = jnp.where(m, h_state, new_state)
        new_status = jnp.where(m & (btype == 3), 1, new_status)
        bfinal = jnp.where(m, h_bfinal, bfinal)
        fixed = jnp.where(m, (btype == 1).astype(_I32), fixed)
        used = jnp.where(m, h_used, used)
        # zero the code-length buffers for lanes starting a dyn block
        # (rare event — gate the (320,128)/(19,128) sweeps off the
        # common superstep)
        mdyn = m & (btype == 2)

        @pl.when(jnp.any(mdyn))
        def _():
            _masked_rows(lens_ref, jnp.zeros(lens_ref.shape, _I32), mdyn)
            _masked_rows(
                cl_lens_ref, jnp.zeros(cl_lens_ref.shape, _I32), mdyn)

        # ---- STORED len/nlen/copy -----------------------------------
        m = state == _SLEN
        s_len = (bitbuf & 0xFFFF).astype(_I32)
        copy_len = jnp.where(m, s_len, copy_len)
        used = jnp.where(m, 16, used)
        new_state = jnp.where(m, _SNLEN, new_state)

        m = state == _SNLEN
        s_nlen = (bitbuf & 0xFFFF).astype(_I32)
        bad = (s_nlen ^ 0xFFFF) != copy_len
        used = jnp.where(m, 16, used)
        new_state = jnp.where(
            m,
            jnp.where(bad, _ERR,
                      jnp.where(copy_len > 0, _SCOPY, after_block)),
            new_state)
        new_status = jnp.where(m & bad, 2, new_status)

        m = state == _SCOPY
        sk = jnp.minimum(kmax, copy_len)
        used = jnp.where(m, sk << 3, used)
        emit_k = jnp.where(m, sk, emit_k)
        packed = jnp.where(m, bitbuf, packed)
        copy_len = jnp.where(m, copy_len - sk, copy_len)
        new_state = jnp.where(
            m & (copy_len == 0), after_block, new_state)

        # ---- TB_HDR: HLIT/HDIST/HCLEN -------------------------------
        m = state == _TBHDR
        v = bitbuf.astype(_U32)
        t_hlit = ((v & 31) + 257).astype(_I32)
        t_hdist = (((v >> 5) & 31) + 1).astype(_I32)
        t_hclen = (((v >> 10) & 15) + 4).astype(_I32)
        hlit = jnp.where(m, t_hlit, hlit)
        hdist = jnp.where(m, t_hdist, hdist)
        hclen = jnp.where(m, t_hclen, hclen)
        tb_idx = jnp.where(m, 0, tb_idx)
        tb_nread = jnp.where(m, 0, tb_nread)
        used = jnp.where(m, 14, used)
        new_state = jnp.where(m, _TBCLLEN, new_state)

        # ---- TB_CLLEN: one 3-bit CL code length per superstep --------
        m = state == _TBCLLEN
        cl_v = (bitbuf & 7).astype(_I32)
        ord_pos = _gather(clorder_ref[...], tb_idx)
        _store_row(cl_lens_ref, ord_pos, cl_v, m)
        tb_idx = jnp.where(m, tb_idx + 1, tb_idx)
        used = jnp.where(m, 3, used)
        cl_done = m & (tb_idx >= hclen)
        new_state = jnp.where(cl_done, _TBCODELEN, new_state)

        def build_cl():
            _build_canonical(
                cl_lens_ref, zrow, zrow + 19, 0, 7,
                cntc_ref, firstc_ref, offc_ref, cursc_ref, symcl_ref,
                cl_done)

        pl.when(jnp.any(cl_done))(build_cl)

        # ---- TB_CODELEN: decode one CL symbol or emit one repeat -----
        m = state == _TBCODELEN
        total = hlit + hdist
        in_rep = m & (rep_cnt > 0)

        # repeat write ((320,128) sweep — table-read phases only)
        @pl.when(jnp.any(in_rep))
        def _():
            _store_row(lens_ref, tb_nread, rep_val,
                       in_rep & (tb_nread < total))
        new_status = jnp.where(in_rep & (tb_nread >= total), 7, new_status)
        new_state = jnp.where(in_rep & (tb_nread >= total), _ERR, new_state)
        tb_nread = jnp.where(in_rep, tb_nread + 1, tb_nread)
        rep_cnt = jnp.where(in_rep, rep_cnt - 1, rep_cnt)
        prev_len = jnp.where(in_rep, rep_val, prev_len)

        mdec = m & ~in_rep

        cidx, cbits, cfound = _decode_canonical(
            bitbuf, 7, cntc_ref[...], firstc_ref[...], offc_ref[...])
        csym = _gather(symcl_ref[...], cidx)
        bad = mdec & ~cfound
        new_status = jnp.where(bad, 3, new_status)
        new_state = jnp.where(bad, _ERR, new_state)
        # literal length 0..15
        ml = mdec & cfound & (csym <= 15)

        @pl.when(jnp.any(ml))
        def _():
            _store_row(lens_ref, tb_nread, csym, ml & (tb_nread < total))
        new_status = jnp.where(ml & (tb_nread >= total), 7, new_status)
        new_state = jnp.where(ml & (tb_nread >= total), _ERR, new_state)
        prev_len = jnp.where(ml, csym, prev_len)
        # repeats: 16 = prev x 3+2bits, 17 = 0 x 3+3bits, 18 = 0 x 11+7bits
        rext = bitbuf >> cbits.astype(_U32)
        m16 = mdec & cfound & (csym == 16)
        m17 = mdec & cfound & (csym == 17)
        m18 = mdec & cfound & (csym == 18)
        new_status = jnp.where(m16 & (tb_nread == 0), 7, new_status)
        new_state = jnp.where(m16 & (tb_nread == 0), _ERR, new_state)
        rep_cnt = jnp.where(m16, 3 + (rext & 3).astype(_I32), rep_cnt)
        rep_cnt = jnp.where(m17, 3 + (rext & 7).astype(_I32), rep_cnt)
        rep_cnt = jnp.where(m18, 11 + (rext & 127).astype(_I32), rep_cnt)
        rep_val = jnp.where(m16, prev_len, jnp.where(m17 | m18, 0, rep_val))
        cl_extra = jnp.where(m16, 2, jnp.where(m17, 3, jnp.where(m18, 7, 0)))
        tb_nread = jnp.where(ml, tb_nread + 1, tb_nread)
        used = jnp.where(mdec, cbits + cl_extra, used)

        # finalize when all code lengths are in
        fin = (m & (tb_nread >= total)
               & (new_state != _ERR)
               & ~(in_rep & (rep_cnt > 0)))

        def build_main():
            _build_canonical(
                lens_ref, zrow, hlit, 0, 15,
                cntl_ref, firstl_ref, offl_ref, cursl_ref, symlit_ref, fin)
            _build_canonical(
                lens_ref, hlit, hlit + hdist, hlit, 15,
                cntd_ref, firstd_ref, offd_ref, cursd_ref, symdist_ref, fin)

        pl.when(jnp.any(fin))(build_main)
        new_state = jnp.where(fin, _DECODE, new_state)
        fixed = jnp.where(fin, 0, fixed)

        # ---- DECODE: one literal/length symbol -----------------------
        m = state == _DECODE
        fixed_b = fixed != 0

        didx, dbits, dfound = _decode_canonical(
            bitbuf, 15, cntl_ref[...], firstl_ref[...], offl_ref[...],
            _FCNT_L, _FFIRST_L, _FOFF_L, fixed_b)
        symdata = jnp.where(fixed_b, fsyml_ref[...], symlit_ref[...])
        sym = _gather(symdata, didx)
        li = jnp.clip(sym - 257, 0, 28)
        lext = _gather(lext_ref[...], li)
        lbase = _gather(lbase_ref[...], li)
        bad = m & ~dfound
        new_status = jnp.where(bad, 3, new_status)
        new_state = jnp.where(bad, _ERR, new_state)
        mok = m & dfound
        # literal
        mlit = mok & (sym < 256)
        emit_k = jnp.where(mlit, 1, emit_k)
        packed = jnp.where(mlit, sym.astype(_U32), packed)
        # second literal: Huffman is prefix-free, so the bits after
        # symbol 1 are always the TRUE next symbol — decode it too and
        # take the pair when both are literals, at every output offset
        # (at off == 3 the second byte lands in the next output word:
        # the emit merge places any chunk). Literal runs dominate the
        # superstep count once long copies emit 16 bytes, so pairs
        # nearly halve them.
        # Bit budget: two codes <= 30 bits of the >= 33 available.
        didx2, dbits2, dfound2 = _decode_canonical(
            bitbuf >> dbits.astype(_U32), 15,
            cntl_ref[...], firstl_ref[...], offl_ref[...],
            _FCNT_L, _FFIRST_L, _FOFF_L, fixed_b)
        sym2 = _gather(symdata, didx2)
        mpair = mlit & dfound2 & (sym2 < 256)
        emit_k = jnp.where(mpair, 2, emit_k)
        packed = jnp.where(
            mpair, sym.astype(_U32) | (sym2.astype(_U32) << 8), packed)
        # end of block
        meob = mok & (sym == 256)
        new_state = jnp.where(meob, after_block, new_state)
        # length code
        mlen = mok & (sym > 256)
        bad_len = mlen & (sym - 257 > 28)
        new_status = jnp.where(bad_len, 3, new_status)
        new_state = jnp.where(bad_len, _ERR, new_state)
        lex_v = ((bitbuf >> dbits.astype(_U32)) &
                 _mask_bits(lext)).astype(_I32)
        copy_len = jnp.where(mlen, lbase + lex_v, copy_len)
        mdist = mlen & ~bad_len
        used = jnp.where(
            m,
            dbits + jnp.where(mlen, lext, 0)
            + jnp.where(mpair, dbits2, 0),
            used)

        # ---- consume phase-A bits, refill for phase B ---------------
        lo, hi, cnt = consume64(lo, hi, cnt, jnp.where(live, used, zrow))
        lo, hi, cnt, in_w = refill64(lo, hi, cnt, in_w, ctiles, ct)
        bitbuf = lo

        # ---- DIST (phase B): the distance of the length symbol phase
        # A has just read, in the same superstep. The refill above left
        # >= 32 valid bits whatever phase A consumed; the code (<= 15)
        # is consumed before the extra bits (<= 13) are read, so both
        # peeks stay inside the low word.
        m = mdist

        xidx, xbits, xfound = _decode_canonical(
            bitbuf, 15, cntd_ref[...], firstd_ref[...], offd_ref[...],
            _FCNT_D, _FFIRST_D, _FOFF_D, fixed_b)
        symdata_d = jnp.where(fixed_b, fsymd_ref[...], symdist_ref[...])
        dsym = _gather(symdata_d, xidx)
        dsym_c = jnp.clip(dsym, 0, 29)
        dext = _gather(dext_ref[...], dsym_c)
        dbase = _gather(dbase_ref[...], dsym_c)
        bad = m & (~xfound | (dsym > 29))
        new_status = jnp.where(bad, 3, new_status)
        new_state = jnp.where(bad, _ERR, new_state)
        mok = m & ~bad
        lo, hi, cnt = consume64(lo, hi, cnt, jnp.where(m, xbits, zrow))
        bitbuf = lo
        dex_v = (bitbuf & _mask_bits(dext)).astype(_I32)
        dist = dbase + dex_v
        bad_d = mok & ((dist > outpos) | (dist > 32768))
        new_status = jnp.where(bad_d, 4, new_status)
        new_state = jnp.where(bad_d, _ERR, new_state)
        copy_dist = jnp.where(mok, dist, copy_dist)
        new_state = jnp.where(mok & ~bad_d, _COPY, new_state)
        lo, hi, cnt = consume64(lo, hi, cnt, jnp.where(mok, dext, zrow))

        # ---- COPY: up to 16 history bytes per superstep --------------
        # Acts on the lanes in _COPY *after* phase B: a match's first
        # chunk goes out in the superstep that read its length and its
        # distance. Such a lane has emitted nothing earlier in this
        # superstep (a length symbol emits no byte), so outpos, off and
        # kmax are still the superstep's own, and the ring and the big
        # out buffer are read before this superstep's emit is merged:
        # only bytes written by earlier supersteps are ever read.
        # Source bytes come from the 4 KiB circular history ring (last
        # 4096 bytes, word rows = w & (RING_W-1)); distances past the
        # ring window read the big out buffer under a gated cond (both
        # as aligned 8-word tiles, below). For
        # d < 4 the 4 fetched bytes start at outpos-d and are replicated
        # modularly (byte j := B[j mod d]). A chunk starts at the
        # output's byte offset, wherever that is, and runs to the end
        # of the FOURTH output word for d >= 16 (16 - off bytes), of
        # the SECOND for d >= 8 (8 - off), of the first below (4 - off):
        # never more than d bytes, so the invariant above holds, and
        # never a fifth output word. Most matches are shorter than that
        # (wgs30x: mean 10 bytes at d >= 16), so a match is one chunk
        # at any offset; a long one is word-aligned after its first.
        m = new_state == _COPY
        d = copy_dist
        wide8 = m & (d >= 8)
        wide16 = m & (d >= 16)
        ck = jnp.minimum(
            jnp.where(wide16, 16, jnp.where(wide8, 8, 4)) - off, copy_len)
        base = outpos - d
        bw = base >> 2
        bo = ((base & 3) << 3).astype(_U32)
        # The five words bw .. bw+4 (a 16-byte unaligned chunk) lie
        # inside the aligned 8-word tiles bw >> 3 and (bw >> 3) + 1 at
        # every alignment: two tile sweeps of the ring (its tile index
        # wraps) and, for the lanes past the ring, two windowed tile
        # sweeps of the big out buffer behind one set of slab gates.
        t0 = bw >> 3
        ring_t = RING_W // 8 - 1
        rt0 = _gather_tile(ring_ref[...], jnp.where(m, t0 & ring_t, -1))
        rt1 = _gather_tile(
            ring_ref[...], jnp.where(m, (t0 + 1) & ring_t, -1))
        far = m & (d > RING_SAFE)
        any_far = jnp.any(far)
        far_steps = far_steps + any_far.astype(_I32)

        def far_fetch():
            last = ow // 8 - 1
            return _gather_tiles_ref_win(
                out_ref,
                (jnp.where(far, jnp.minimum(t0, last), -1),
                 jnp.where(far, jnp.minimum(t0 + 1, last), -1)),
                slab=slab)

        ztile = jnp.zeros((8, LANES), _U32)
        ft0, ft1 = lax.cond(any_far, far_fetch, lambda: (ztile, ztile))
        # the words are picked out of the 16 fetched rows by one-hot
        # (two registers a word)
        hist = jnp.concatenate(
            [jnp.where(far, ft0, rt0), jnp.where(far, ft1, rt1)], axis=0)
        k = bw & 7
        w0, w1, w2, w3, w4 = (
            _gather(hist, jnp.where(live_j, k + j, -1))
            for j, live_j in enumerate((m, m, wide8, wide16, wide16)))
        sh = (_U32(32) - bo) & _U32(31)
        asm = jnp.where(bo == 0, w0, (w0 >> bo) | (w1 << sh))
        asm2 = jnp.where(bo == 0, w1, (w1 >> bo) | (w2 << sh))
        asm3 = jnp.where(bo == 0, w2, (w2 >> bo) | (w3 << sh))
        asm4 = jnp.where(bo == 0, w3, (w3 >> bo) | (w4 << sh))
        b0 = asm & 0xFF
        b1 = (asm >> 8) & 0xFF
        b2 = (asm >> 16) & 0xFF
        b3 = (asm >> 24) & 0xFF
        # modular replication for d in {1,2,3}
        r1 = b0 | (b0 << 8) | (b0 << 16) | (b0 << 24)
        r2 = b0 | (b1 << 8) | (b0 << 16) | (b1 << 24)
        r3 = b0 | (b1 << 8) | (b2 << 16) | (b0 << 24)
        cpk = jnp.where(d == 1, r1,
                        jnp.where(d == 2, r2,
                                  jnp.where(d == 3, r3, asm)))
        emit_k = jnp.where(m, ck, emit_k)
        packed = jnp.where(m, cpk, packed)
        copy_len = jnp.where(m, copy_len - ck, copy_len)
        new_state = jnp.where(m & (copy_len == 0), _DECODE, new_state)

        # ---- emit merge ---------------------------------------------
        # Any chunk at any offset: the chunk's bytes from its first
        # (packed, then asm2 .. asm4: zero outside the copy phase) and
        # its count emit_k are placed at byte off of output word w0r
        # and fill up to 4 output words. Word j holds the chunk's bytes
        # 4j - off .. 4j - off + 3, the low bytes from the chunk word
        # before; byte counts clip(off + emit_k - 4j, 0, 4) mask what
        # lies past the chunk's end (word 0: less its low off bytes,
        # which earlier supersteps wrote).
        emit_k = jnp.where(live & (new_state != _ERR), emit_k, zrow)
        over = (emit_k > 0) & (outpos + emit_k > ow * 4)
        new_status = jnp.where(over, 5, new_status)
        new_state = jnp.where(over, _ERR, new_state)
        emit_k = jnp.where(over, 0, emit_k)
        emitting = emit_k > 0
        crossing = crossing + (
            m & (off != 0) & (emit_k > kmax)).astype(_I32)
        end = off + emit_k
        klo = jnp.minimum(emit_k, kmax)
        k1 = jnp.clip(end - 4, 0, 4)
        k2 = jnp.clip(end - 8, 0, 4)
        k3 = jnp.clip(end - 12, 0, 4)
        kmask = _mask_bits(klo << 3)
        kmask1 = _mask_bits(k1 << 3)
        kmask2 = _mask_bits(k2 << 3)
        kmask3 = _mask_bits(k3 << 3)
        shl = (off << 3).astype(_U32)
        shr = (_U32(32) - shl) & _U32(31)
        aligned = off == 0

        def placed(before, word, mask):
            return jnp.where(
                aligned, word, (before >> shr) | (word << shl)) & mask

        bits = (packed & kmask) << shl
        bits1 = placed(packed, asm2, kmask1)
        bits2 = placed(asm2, asm3, kmask2)
        bits3 = placed(asm3, asm4, kmask3)
        # The four words w0r .. w0r + 3 lie inside the aligned 8-word
        # tiles w0r >> 3 and (w0r >> 3) + 1 at every alignment, as the
        # copy phase's five history words do: the words and their byte
        # masks are placed once, on two registers each, in a 16-row
        # patch at rows (w0r & 7) + j (a word past the chunk's end is
        # zero under a zero mask, so is every word of a lane that emits
        # nothing), and the patch's halves are merged into the big out
        # buffer and into the ring as tiles.
        w0r = outpos >> 2
        wk = w0r & 7
        si = _riota(16)
        patch = pmask = jnp.zeros((16, LANES), _U32)
        for j, (word, mask) in enumerate((
                (bits, kmask << shl), (bits1, kmask1), (bits2, kmask2),
                (bits3, kmask3))):
            hit = si == wk + j
            patch = jnp.where(hit, word, patch)
            pmask = jnp.where(hit, mask, pmask)
        patches = patch[:8], patch[8:]
        wt = w0r >> 3
        # the second tile only where the chunk's last byte lies in it:
        # never past the buffer's last tile (``over`` above)
        second = emitting & (wk + ((end - 1) >> 2) >= 8)
        # big out: bytes land exactly once, buffer starts zeroed -> OR,
        # slab-wise to bound scoped-vmem temps and slab-gated on the
        # hull of the live tiles (lanes advance in rough lockstep, so
        # most supersteps touch one slab, not all sixteen)
        out_tiles = (jnp.where(emitting, wt, -1),
                     jnp.where(second, wt + 1, -1))
        hull = _tile_hull(out_tiles, ow // 8)
        _scatter_tiles_ref_win(out_ref, out_tiles, patches, hull, slab=slab)

        # history ring: same patches, replace-semantics (rows recycle;
        # word 0 keeps its low off bytes, which its mask leaves out),
        # the tile index wrapped, every register, in every superstep
        # in which some lane emits (the hull's max says so: no further
        # reduction)
        @pl.when(hull[1] >= 0)
        def _():
            ring_ref[...] = _scatter_tile(
                ring_ref[...],
                (jnp.where(emitting, wt & ring_t, -1),
                 jnp.where(second, (wt + 1) & ring_t, -1)),
                patches, masks=(pmask[:8], pmask[8:]))
        outpos = outpos + emit_k

        # ---- input-overrun guard ------------------------------------
        consumed = (in_w << 5) - cnt
        overrun = live & (consumed > ((clen + 8) << 3))
        new_status = jnp.where(overrun, 6, new_status)
        new_state = jnp.where(overrun, _ERR, new_state)

        return (step + 1, new_state, lo, hi, cnt, in_w, outpos,
                bfinal, fixed, copy_len, copy_dist, hlit, hdist, hclen,
                tb_idx, tb_nread, rep_val, rep_cnt, prev_len, new_status,
                far_steps, crossing, ctiles, ct, comp_fetches)

    def cond(carry):
        step, state = carry[0], carry[1]
        return (step < max_steps) & jnp.any(
            (state != _DONE) & (state != _ERR))

    init_state = jnp.where(clen > 0, _HEADER, _DONE)
    init = (
        jnp.int32(0), init_state, zrow_u, zrow_u, zrow, zrow, zrow,
        zrow, zrow, zrow, zrow,
        zrow, zrow, zrow, zrow, zrow, zrow, zrow, zrow, zrow,
        jnp.int32(0), zrow,
        # the window: superstep 0 sweeps, so what it starts as is never
        # read. ct starts as a loaded row and not as zrow because this
        # Mosaic refuses the loop with it carried from a splat constant
        # ("Invalid relayout ... replicated in destination but not in
        # source")
        (jnp.zeros((8, LANES), _U32),) * _COMP_TILES, clen, jnp.int32(0),
    )
    final = lax.while_loop(cond, superstep, init)
    step, state, _lo, _hi, _cnt, _iw, outpos = final[:7]
    status, far_steps, crossing = final[19:22]
    comp_fetches = final[24]
    # lanes still live at the step cap ran away
    status = jnp.where(
        (state != _DONE) & (state != _ERR), 6, status)
    meta_ref[...] = jnp.concatenate(
        [outpos, status, jnp.broadcast_to(step[None, None], (1, LANES)),
         jnp.broadcast_to(far_steps[None, None], (1, LANES)),
         crossing,
         jnp.broadcast_to(comp_fetches[None, None], (1, LANES))], axis=0)


@functools.lru_cache(maxsize=16)
def _compiled(cw: int, ow: int, interpret: bool,
              transpose: bool = False, donate: bool = False):
    # emits bound one term; non-emitting supersteps (headers, table
    # builds, dist phases) consume >= 3 input bits each, so cw bounds
    # the other — flush-heavy many-small-block streams stay on device
    max_steps = 2 * ow * 4 + 2 * cw * 4 + 8192
    # big geometries (comp 4 MB + out 8 MB persistent) leave < 4 MB of
    # scoped-vmem stack: halve the slab temps there
    slab = 1024 if cw + ow >= 20480 else _SLAB
    # the wide geometry (comp 8 MB + out 8 MB + ring and tables) is past
    # the 16 MiB scoped-VMEM default (the chip refuses it: "ran out of
    # memory in memory space vmem"): it alone raises the limit (a v5e
    # core has 128 MiB), so every narrower geometry compiles to the
    # program it always did.  Its lanes are long-read blocks of 25-42 KB
    # of payload that drift apart, so a sweep's window spans more slabs:
    # 512-row slabs read 4.86 us a superstep there against 5.12 at 1,024
    # and 5.28 at 256 (PERF.md, PR 48)
    params = {}
    if cw > 8192:
        slab = 512
        if not interpret:
            params["compiler_params"] = pltpu.CompilerParams(
                vmem_limit_bytes=_WIDE_VMEM_LIMIT)
    kernel = functools.partial(
        _inflate_simd_kernel, cw=cw, ow=ow, max_steps=max_steps,
        slab=slab)
    t16 = pltpu.VMEM((16, LANES), _U32)
    t8 = pltpu.VMEM((8, LANES), _U32)
    call = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((ow, LANES), _U32),
            jax.ShapeDtypeStruct((6, LANES), _I32),
        ),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * (2 + len(_CONST_TABLES)),
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((_MAXLENS, LANES), _I32),   # lens
            pltpu.VMEM((19, LANES), _I32),         # cl_lens
            pltpu.VMEM((_MAXLENS, LANES), _I32),   # symlit
            pltpu.VMEM((32, LANES), _I32),         # symdist
            pltpu.VMEM((19, LANES), _I32),         # symcl
            t16, t16, t16, t16,                    # lit cnt/first/off/curs
            t16, t16, t16, t16,                    # dist
            t8, t8, t8, t8,                        # cl
            pltpu.VMEM((RING_W, LANES), _U32),     # history ring
        ],
        interpret=interpret,
        **params,
    )
    if transpose:
        inner = call

        def call(*args):
            # lanes-major words: ONE device-side transpose makes every
            # lane's output bytes host-contiguous, so unpack is a view
            # per lane instead of a strided per-lane gather + tobytes
            words, meta = inner(*args)
            return jnp.transpose(words), meta

    nums: Tuple[int, ...] = ()
    if donate and not interpret:
        # donate the comp upload only when its buffer can actually
        # back the words output (same shape+dtype) — donating args the
        # runtime cannot alias buys nothing and makes jax warn into
        # every importer's process; clen (1,128) never matches meta
        out_words = (LANES, ow) if transpose else (ow, LANES)
        if (cw, LANES) == out_words:
            nums = (0,)
    return jax.jit(call, donate_argnums=nums)


from disq_tpu.util import (  # noqa: E402 — shared policy
    bucket_pow2 as _bucket,
    pallas_interpret as _pallas_interpret,
)


# ---------------------------------------------------------------------------
# Host staging arenas, device-resident constant tables, adaptive window
# ---------------------------------------------------------------------------


class _PackArena:
    """Reusable host staging buffers for one <=128-lane chunk launch.

    ``_pack_chunk`` writes payload bytes in place instead of allocating
    a fresh zeroed (cw,128) buffer per chunk; ``dirty`` tracks each
    lane's written-word high-water mark so reuse zeroes only the stale
    tail, not the whole 4 MB column buffer. ``extras`` carries
    codec-specific lane tables (the rANS freq/cum/state arrays)."""

    def __init__(self, cw: int):
        self.cw = cw
        self.comp = np.zeros((cw, LANES), dtype="<u4")
        self.clen = np.zeros((1, LANES), dtype=np.int32)
        self.dirty = np.zeros(LANES, dtype=np.int64)
        self.extras: Dict[str, np.ndarray] = {}

    @property
    def nbytes(self) -> int:
        return (self.comp.nbytes + self.clen.nbytes + self.dirty.nbytes
                + sum(a.nbytes for a in self.extras.values()))


class _ArenaPool:
    """Process-wide checkout pool of ``_PackArena`` staging buffers,
    keyed by (codec kind, cw bucket).  Thread-safe: concurrent decode
    workers (or the decode service's dispatcher) check an arena out for
    the lifetime of one chunk — pack, upload, launch, materialize — and
    return it afterwards, so a buffer is never repacked while a launch
    might still be reading it.  Pool size self-adjusts to the dispatch
    window; ``device.arena_bytes`` tracks the resident total."""

    def __init__(self, per_key_cap: int = 8) -> None:
        self._lock = threading.Lock()
        self._free: Dict[Any, List[_PackArena]] = {}
        self._bytes = 0
        self._cap = per_key_cap

    def acquire(self, key: Any,
                factory: Callable[[], _PackArena]) -> _PackArena:
        with self._lock:
            free = self._free.get(key)
            if free:
                return free.pop()
        arena = factory()
        with self._lock:
            self._bytes += arena.nbytes
            total = self._bytes
        _gauge("device.arena_bytes").observe(total)
        return arena

    def release(self, key: Any, arena: _PackArena) -> None:
        with self._lock:
            free = self._free.setdefault(key, [])
            if len(free) < self._cap:
                free.append(arena)
                return
            self._bytes -= arena.nbytes
            total = self._bytes
        _gauge("device.arena_bytes").observe(total)


ARENAS = _ArenaPool()

_CONST_CACHE: Dict[Any, tuple] = {}
_CONST_LOCK = threading.Lock()


def _device_const_tables(dev=None) -> tuple:
    """The kernel's constant (R,128) tables as device-resident arrays,
    uploaded ONCE per device per process.  Previously every
    ``inflate_payloads_simd`` call re-ran ``jnp.asarray`` over all
    seven tables — a fresh ~200 KB H2D upload per shard.

    ``dev=None`` resolves to the ambient default device, so a service
    engine running under ``jax.default_device(d)`` (the per-device
    dispatcher lanes, runtime/device_service.py) gets tables resident
    on ITS chip — the cache is device-keyed either way."""
    if dev is None:
        dev = jax.config.jax_default_device or jax.devices()[0]
    with _CONST_LOCK:
        cached = _CONST_CACHE.get(dev)
        if cached is None:
            cached = tuple(jax.device_put(t, dev) for t in _CONST_TABLES)
            _CONST_CACHE[dev] = cached
    return cached


def dispatch_window(n_chunks: int, chunk_bytes: int) -> int:
    """Adaptive dispatch window (replaces the hard-coded ``window = 3``):
    enough chunks in flight to overlap H2D / compute / D2H, bounded by
    a staging-HBM budget so big (cw, ow) geometries don't pin several
    12 MB footprints at once.  ``DISQ_TPU_DISPATCH_WINDOW`` pins the
    width; ``DISQ_TPU_DISPATCH_HBM_MB`` resizes the budget (default
    96 MB)."""
    pinned = os.environ.get("DISQ_TPU_DISPATCH_WINDOW", "").strip()
    if pinned:
        return max(1, min(int(pinned), max(1, n_chunks)))
    budget = int(os.environ.get("DISQ_TPU_DISPATCH_HBM_MB", "96")) << 20
    return max(1, min(4, n_chunks, budget // max(1, chunk_bytes)))


def _pack_chunk(chunk: Sequence, cw: int,
                arena: Optional[_PackArena] = None):
    """Pack <=128 payloads into the kernel's (cw,128) LE word columns +
    (1,128) byte lengths. Single source of truth — the TPU CI lane's
    kernel-only row packs with this too.

    With an ``arena`` the columns are written in place (no fresh 4 MB
    zeroed buffer, no per-payload pad-bytes concat) and only each
    lane's dirty tail from the previous chunk is re-zeroed.  Payloads
    may be ``bytes`` or ``memoryview`` — nothing here copies them."""
    if arena is None:
        comp = np.zeros((cw, LANES), dtype="<u4")
        clen = np.zeros((1, LANES), dtype=np.int32)
        dirty = None
    else:
        comp, clen, dirty = arena.comp, arena.clen, arena.dirty
        clen[:] = 0
    for i, p in enumerate(chunk):
        n = len(p)
        clen[0, i] = n
        nw = n // 4
        if nw:
            comp[:nw, i] = np.frombuffer(p, dtype="<u4", count=nw)
        used = nw
        tail = n - nw * 4
        if tail:
            last = 0
            base = nw * 4
            for j in range(tail):
                last |= p[base + j] << (8 * j)
            comp[nw, i] = last
            used = nw + 1
        if dirty is not None:
            if dirty[i] > used:
                comp[used: int(dirty[i]), i] = 0
            dirty[i] = used
    if dirty is not None:
        for i in range(len(chunk), LANES):
            if dirty[i]:
                comp[: int(dirty[i]), i] = 0
                dirty[i] = 0
    return comp.view(np.uint32), clen


def buckets_for(payloads: Sequence[bytes], max_u: int):
    """The (cw, ow) the production wrapper would compile for."""
    max_c = max(len(p) for p in payloads)
    cw = _bucket((max_c + 8) // 4 + 2)
    ow = min(_bucket(max(1, (max_u + 3) // 4)), 16384)
    return cw, ow


def host_inflate(p, expect: Optional[int] = None) -> bytes:
    """Host-zlib fallback for one raw-DEFLATE payload, with the
    framework's corrupt-input contract: decode failure and genuine
    ISIZE mismatch (error 8) both surface as ``ValueError`` —
    swallowing the latter would break the cumulative-usize slicing in
    bam/source.py."""
    import zlib

    try:
        host = zlib.decompress(p, wbits=-15)
    except zlib.error as e:
        raise ValueError(f"corrupt DEFLATE stream: {e}") from e
    if expect is not None and len(host) != expect:
        raise ValueError(
            f"device inflate failed: error 8 "
            f"(ISIZE {expect} != {len(host)})")
    return host


def _fetch_chunk(handle, lanes: int,
                 labels: Optional[Dict[str, Any]] = None,
                 kernel: str = "inflate_simd", cw: int = 0):
    """Materialize one launched chunk and book the D2H bytes; returns
    the lanes-major uint8 view + the meta rows.  Two spans, so a
    launch's time splits into kernel and transfer: ``device.launch.wait``
    (blocked on the kernel) and then ``device.launch.d2h`` (the copy
    alone).  ``np.asarray`` blocked here before the split, so no fence
    is added.  ``labels`` are the spans' (the decode service passes the
    ones that join a launch's spans: ``kind``, ``lanes``, ``launch``).
    An inflate launch's superstep count (``meta`` row 2) is booked as
    ``device.inflate.supersteps`` and as the d2h span's ``supersteps``
    label: over ``device.kernel_launches`` it is supersteps a launch,
    under the kernel's seconds it is seconds a superstep.  ``meta`` row
    3, the supersteps in which some lane read history past the ring, is
    booked beside it as ``device.inflate.far_supersteps`` and the label
    ``far_supersteps``: over the supersteps it is the share that paid
    the far sweeps of the out buffer.  ``meta`` row 4, each lane's copy
    chunks that ran past the output word they started in, is summed
    over the lanes into ``device.inflate.crossing_chunks`` and the label
    ``crossing_chunks``: how often the one-chunk match engaged (0 for
    a launch with no match in it).  ``meta`` row 5, the supersteps in
    which the kernel swept the compressed buffer for the carry's
    window, is booked as ``device.inflate.comp_fetches`` and the label
    ``comp_fetches``: over the supersteps it is the schedule's share,
    1 / ``COMP_PERIOD``.  ``cw``, the launch's compressed-buffer rows,
    books its lanes under ``device.inflate.lanes{cw}``: the lanes
    decoded, by launch geometry (16384 is the wide one, payloads over
    ``NARROW_CSIZE``)."""
    words, meta = handle
    if labels is None:
        labels = {"kind": "inflate", "lanes": lanes}
    _counter("device.kernel_launches").inc(kernel=kernel)
    with _span("device.launch.wait", **labels):
        jax.block_until_ready((words, meta))
    nbytes = words.nbytes + meta.nbytes
    with _span("device.launch.d2h", bytes=nbytes, **labels) as at_end:
        words = np.asarray(words)
        meta = np.asarray(meta)
        if kernel == "inflate_simd":
            at_end["supersteps"] = supersteps = int(meta[2, 0])
            at_end["far_supersteps"] = far = int(meta[3, 0])
            at_end["crossing_chunks"] = crossing = int(meta[4].sum())
            at_end["comp_fetches"] = fetches = int(meta[5, 0])
            _counter("device.inflate.supersteps").inc(supersteps)
            _counter("device.inflate.far_supersteps").inc(far)
            _counter("device.inflate.crossing_chunks").inc(crossing)
            _counter("device.inflate.comp_fetches").inc(fetches)
            _counter("device.inflate.lanes").inc(lanes, cw=cw)
    _count_transfer("d2h", nbytes)
    return words.view(np.uint8), meta


def _finalize_lane(p, lanes_u8, meta, j: int, expect: Optional[int]):
    """One lane of a materialized chunk: a zero-copy uint8 view of its
    decoded bytes (device path), or host-fallback bytes for a lane the
    kernel flagged; raises ``ValueError`` for truly corrupt input."""
    n, status = int(meta[0, j]), int(meta[1, j])
    if status != 0 or (expect is not None and n != expect):
        last_stats["host_fallback"] += 1
        _counter("device.host_fallback_blocks").inc(reason="flagged")
        return host_inflate(p, expect)
    last_stats["device_lanes"] += 1
    return lanes_u8[j, :n]


class DeviceBlobHandle:
    """The still-resident decoded output of one
    ``inflate_payloads_simd(keep_device=True)`` call: the kernel's
    transposed (LANES, ow) output chunks, kept alive on device, plus
    the per-block lane map and host-fallback patch bytes.

    ``assemble()`` compacts them into one contiguous device word blob
    (``runtime/device_pipeline.assemble_device_words`` — a per-byte
    gather entirely on device), so the fused parse chain reads the
    decoded shard where the inflate kernel left it instead of
    re-uploading the d2h'd host copy. The handle owns the chunks' HBM
    accounting; assemble/release drops them."""

    def __init__(self, n_blocks: int, offsets: np.ndarray) -> None:
        self.chunks: List[Any] = []
        self.lane_of = np.full(n_blocks, -1, np.int64)
        self.offsets = offsets
        self.patches: List[Any] = []
        self._hbm = 0
        self._released = False

    def add_chunk(self, words) -> int:
        """Retain one chunk's device output; returns its index."""
        self.chunks.append(words)
        nbytes = int(words.size) * 4
        self._hbm += nbytes
        _track_hbm(nbytes)
        return len(self.chunks) - 1

    def assemble(self):
        """Device word blob covering every block (host-fallback lanes
        patched from a small upload), or None when nothing stayed on
        device. Releases the retained chunks either way."""
        if self._released:
            return None
        if not self.chunks:
            self.release()
            return None
        from disq_tpu.runtime.device_pipeline import assemble_device_words

        try:
            words, _up = assemble_device_words(
                self.chunks, self.lane_of, self.offsets, self.patches)
        finally:
            self.release()
        return words

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        self.chunks = []
        if self._hbm:
            _track_hbm(-self._hbm)
            self._hbm = 0

    def __del__(self) -> None:  # pragma: no cover - GC timing
        try:
            self.release()
        except Exception:  # noqa: BLE001 — interpreter shutdown
            pass


def assemble_blob(results: Sequence):
    """Compact per-payload results (uint8 views / fallback bytes) into
    one contiguous uint8 blob + (n+1,) int64 offsets with plain
    memcpys — no intermediate ``bytes`` objects, no ``b"".join``."""
    offsets = np.zeros(len(results) + 1, dtype=np.int64)
    for i, r in enumerate(results):
        offsets[i + 1] = offsets[i] + len(r)
    blob = np.empty(int(offsets[-1]), dtype=np.uint8)
    for i, r in enumerate(results):
        if isinstance(r, np.ndarray):
            blob[offsets[i]: offsets[i + 1]] = r
        else:
            blob[offsets[i]: offsets[i + 1]] = np.frombuffer(
                r, dtype=np.uint8)
    return blob, offsets


def inflate_payloads_simd(
    payloads: Sequence,
    usizes: Optional[Sequence[int]] = None,
    interpret: Optional[bool] = None,
    as_array: bool = False,
    keep_device: bool = False,
):
    """Inflate raw-DEFLATE payloads on the 128-lane SIMD kernel.

    Returns the decompressed bytes per payload — or, with
    ``as_array``, one contiguous uint8 blob + (n+1,) offsets assembled
    straight from the kernel's transposed output with zero per-lane
    ``bytes`` round-trips.  Lanes that fail in-kernel (nonzero status)
    are re-inflated with host zlib — corruption is the host's problem
    to adjudicate, surfaced as ``ValueError`` (the framework's
    corrupt-input contract).  Payloads may be ``memoryview`` slices.

    ``keep_device`` (requires ``as_array`` + known usizes) additionally
    returns a ``DeviceBlobHandle`` as a third element: the kernel's
    output chunks stay resident in HBM so the fused resident-decode
    path (``runtime/columnar.ColumnarBatch``) can parse the shard
    without re-uploading the blob; None when no lane stayed on device.

    Dispatch path (this PR's shape): staging arenas from the process
    pool instead of fresh numpy buffers, device-resident constant
    tables (``_device_const_tables``), donated per-chunk uploads, and
    an adaptive launch window (``dispatch_window``).
    """
    if interpret is None:
        interpret = _pallas_interpret()
    keep_device = keep_device and as_array and usizes is not None
    n = len(payloads)
    if n == 0:
        if as_array:
            empty = np.empty(0, np.uint8), np.zeros(1, np.int64)
            return (*empty, None) if keep_device else empty
        return []
    # VMEM budget: comp (8192,128) u32 = 4 MB + out (16384,128) u32 =
    # 8 MB + tables/ring ~1.2 MB fits the scoped default because the
    # out-sized ops run slab-wise; a payload over NARROW_CSIZE makes the
    # call's geometry the wide one (comp (16384,128) = 8 MB), which
    # raises its limit (``_compiled``). Only what is no BGZF block (a
    # payload over MAX_DEVICE_CSIZE, an output over MAX_DEVICE_USIZE)
    # goes to host zlib.
    results: List[Any] = [None] * n
    # With known usizes the output layout is known up front: decoded
    # lanes are written straight into the final blob as each chunk
    # materializes, so no chunk's (LANES, ow*4) buffer outlives its
    # loop iteration (holding per-lane views would pin every chunk of
    # a large call in memory at once).
    blob = offsets = dev_handle = None
    if as_array and usizes is not None:
        offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(np.asarray([int(u) for u in usizes], np.int64),
                  out=offsets[1:])
        blob = np.empty(int(offsets[-1]), dtype=np.uint8)
        if keep_device:
            dev_handle = DeviceBlobHandle(n, offsets)

    def emit(i: int, val) -> None:
        if blob is not None:
            if isinstance(val, np.ndarray):
                blob[offsets[i]: offsets[i + 1]] = val
            else:
                blob[offsets[i]: offsets[i + 1]] = np.frombuffer(
                    val, dtype=np.uint8)
        elif as_array:
            results[i] = val  # usizes unknown: assembled at the end
        else:
            results[i] = (val.tobytes()
                          if isinstance(val, np.ndarray) else val)

    small: List[int] = []
    for i, p in enumerate(payloads):
        if len(p) > MAX_DEVICE_CSIZE or (
                usizes is not None and int(usizes[i]) > MAX_DEVICE_USIZE):
            last_stats["host_big"] += 1
            _counter("device.host_fallback_blocks").inc(reason="oversize")
            val = host_inflate(
                p, None if usizes is None else int(usizes[i]))
            emit(i, val)
            if dev_handle is not None:
                dev_handle.patches.append((i, val))
        else:
            small.append(i)
    if small:
        if usizes is not None:
            max_u = max(int(usizes[i]) for i in small)
        else:
            max_u = 65536
        cw, ow = buckets_for([payloads[i] for i in small], max_u)
        fn = _compiled(cw, ow, bool(interpret), True, True)
        consts = _device_const_tables()
        chunks = [small[lo: lo + LANES]
                  for lo in range(0, len(small), LANES)]
        # Per-chunk device buffers live for the dispatch window; the
        # footprint scope covers all concurrently launched chunks.
        chunk_bytes = (cw + 1) * LANES * 4 + ow * LANES * 4 + 8 * LANES * 4
        window = dispatch_window(len(chunks), chunk_bytes)
        hbm_scope = min(window, len(chunks)) * chunk_bytes
        _track_hbm(hbm_scope)
        launched: List = []

        def launch(ids):
            arena = ARENAS.acquire(
                ("inflate", cw), lambda: _PackArena(cw))
            comp, clen = _pack_chunk([payloads[i] for i in ids], cw,
                                     arena)
            _count_transfer("h2d", comp.nbytes + clen.nbytes)
            out = fn(jnp.asarray(comp), jnp.asarray(clen), *consts)
            return out, arena

        try:
            for ids in chunks[:window]:
                launched.append(launch(ids))
            for ci, ids in enumerate(chunks):
                handle, arena = launched[ci]
                lanes_u8, meta = _fetch_chunk(handle, len(ids), cw=cw)
                launched[ci] = None
                # materialized => the upload was consumed; the arena is
                # safe to repack for a later chunk
                ARENAS.release(("inflate", cw), arena)
                lane_base = -1
                if dev_handle is not None:
                    # retain the chunk's device output: the decoded
                    # bytes stay in HBM for the fused parse chain
                    lane_base = dev_handle.add_chunk(handle[0]) * LANES
                if ci + window < len(chunks):
                    launched.append(launch(chunks[ci + window]))
                for j, i in enumerate(ids):
                    expect = None if usizes is None else int(usizes[i])
                    val = _finalize_lane(
                        payloads[i], lanes_u8, meta, j, expect)
                    emit(i, val)
                    if dev_handle is not None:
                        if isinstance(val, np.ndarray):
                            dev_handle.lane_of[i] = lane_base + j
                        else:  # host re-inflate: patch on assembly
                            dev_handle.patches.append((i, val))
        except BaseException:
            if dev_handle is not None:
                dev_handle.release()
            raise
        finally:
            _track_hbm(-hbm_scope)
            # an abandoned window (corrupt lane raised mid-loop) must
            # still return its staging arenas — the aborted launches'
            # results are discarded, so repacking them is safe
            for entry in launched:
                if entry is not None:
                    ARENAS.release(("inflate", cw), entry[1])
    if blob is not None:
        if keep_device:
            return blob, offsets, dev_handle
        return blob, offsets
    if as_array:
        return assemble_blob(results)
    return results
