"""Pallas raw-DEFLATE inflate — one BGZF block per grid program.

The north-star device codec (SURVEY.md §2.8, §7 step 2; reference
behavior: htsjdk ``BlockCompressedInputStream`` + zlib ``Inflater``):
every BGZF block is an independent ≤64 KiB raw-DEFLATE stream, so a
file decompresses as thousands of independent grid programs over
HBM-resident byte buffers.

Design notes (TPU realities, not a CUDA translation):

- DEFLATE entropy decode is bit-serial; there is no vector parallelism
  *within* a block. The kernel therefore keeps ALL mutable decode state
  imperative — scalar loop carries plus ref stores — and gets its
  parallelism across blocks (grid) and cores (megacore), not lanes.
- Huffman decoding uses canonical per-length counts (the zlib/puff
  "count / offset / first-code" walk) instead of LUTs: per-alphabet
  tables are a few hundred bytes of SMEM scratch, built in-kernel from
  the code-length arrays. All table indexing is scalar SMEM access.
- The RFC 1951 length/distance base+extra tables and fixed-Huffman code
  lengths enter as SMEM inputs (replicated per grid step) so every
  dynamic table lookup is a scalar SMEM read, never a VMEM gather.
- Byte access into the compressed/uncompressed streams is dynamic
  single-element VMEM slices. Bytes are widened to int32 (no value in
  the decoder exceeds 2^24, so int32 is overflow-safe).
- The threaded host codec (``disq_tpu.bgzf.codec`` + ``native/``)
  remains the default production path; this kernel is the device path,
  and its oracle is exact byte equality with zlib.

Error codes in meta[:, 1]: 0 ok · 1 bad btype · 2 stored-LEN mismatch ·
3 bad Huffman code · 4 invalid distance · 5 output overflow · 6 ran past
the compressed payload · 7 code-length repeat overflow · 8 ISIZE
mismatch.
"""

from __future__ import annotations

import functools
from typing import List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

CMAX = 66560          # padded compressed slot: 520 rows x 128 lanes
UMAX = 65536          # BGZF uncompressed bound
_CROWS = CMAX // 128  # 520, multiple of 8
_UROWS = UMAX // 128  # 512
_NLIT = 288           # literal/length alphabet size
_NDIST = 32           # distance alphabet (30 used; 2 reserved)
_NCL = 19             # code-length alphabet
_NLENS = _NLIT + _NDIST

# Length codes 257..285 (RFC 1951 §3.2.5).
_LBASE = np.array(
    [3, 4, 5, 6, 7, 8, 9, 10, 11, 13, 15, 17, 19, 23, 27, 31, 35, 43, 51,
     59, 67, 83, 99, 115, 131, 163, 195, 227, 258], dtype=np.int32)
_LEXT = np.array(
    [0, 0, 0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 2, 2, 2, 2, 3, 3, 3, 3, 4, 4, 4,
     4, 5, 5, 5, 5, 0], dtype=np.int32)
# Distance codes 0..29 (padded to 32).
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
     np.full(_NDIST, 5)]
).astype(np.int32)


def _inflate_kernel(
    csizes_ref, usizes_ref, lbase_ref, lext_ref, dbase_ref, dext_ref,
    clorder_ref, fixedlens_ref, comp_ref,
    out_ref, meta_ref,
    lens_s, cnt_s, first_s, off_s, syms_s,
):
    """One raw-DEFLATE stream → bytes. See module docstring.

    Scratch (SMEM):
      lens_s  (NLENS,)   code lengths being assembled (lit ‖ dist)
      cnt_s / first_s / off_s  (3, 16)  canonical tables per alphabet
                                        (rows: 0=code-length, 1=lit, 2=dist)
      syms_s  (3, NLIT)  per-alphabet symbols sorted by (length, symbol)
    """
    import jax.experimental.pallas as pl

    block_id = pl.program_id(0)
    csize = csizes_ref[block_id]
    bit_limit = csize * 8

    # Mosaic supports dynamic VMEM access only at tile-aligned offsets it
    # can prove: every byte access loads the aligned (8, 128) tile holding
    # byte ``i`` (row base (i >> 10) * 8 is syntactically a multiple of 8)
    # and selects/blends the element with one-hot masks — pure VPU ops.
    _row_iota = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    _lane_iota = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)

    def _mask(i):
        sub = i & 1023
        return (_row_iota == (sub >> 7)) & (_lane_iota == (sub & 127))

    def _tile_get(ref, i):
        tile = ref[pl.ds((i >> 10) * 8, 8), :]
        return jnp.sum(jnp.where(_mask(i), tile, 0))

    def cload(i):
        return _tile_get(comp_ref, i)

    def oload(i):
        return _tile_get(out_ref, i)

    def ostore(i, v):
        base = (i >> 10) * 8
        tile = out_ref[pl.ds(base, 8), :]
        out_ref[pl.ds(base, 8), :] = jnp.where(_mask(i), v, tile)

    def read_bits(bitpos, n):
        """LSB-first bit read, n ≤ 16 (3 bytes cover ≥17 bits post-shift)."""
        i = bitpos >> 3
        v = cload(i) | (cload(i + 1) << 8) | (cload(i + 2) << 16)
        val = (v >> (bitpos & 7)) & ((1 << n) - 1)
        return val, bitpos + n

    # -- canonical Huffman table build for alphabet row ``a`` over
    #    lens_s[base : base + nsym] ----------------------------------------
    def build_table(a, base, nsym):
        for l in range(16):
            cnt_s[a, l] = jnp.int32(0)

        def count_body(s, carry):
            l = lens_s[base + s]

            @pl.when(l > 0)
            def _():
                cnt_s[a, l] = cnt_s[a, l] + 1

            return carry

        jax.lax.fori_loop(0, nsym, count_body, 0)
        # canonical first codes + running offsets (symbols shorter than l)
        code = jnp.int32(0)
        acc = jnp.int32(0)
        first_s[a, 0] = jnp.int32(0)
        off_s[a, 0] = jnp.int32(0)
        for l in range(1, 16):
            code = (code + cnt_s[a, l - 1]) * 2
            acc = acc + cnt_s[a, l - 1]
            first_s[a, l] = code
            off_s[a, l] = acc
        # symbols sorted by (length, symbol): for each length, append the
        # symbols holding it (O(15·nsym) scalar scan; nsym ≤ 288)
        w = jnp.int32(0)
        for l in range(1, 16):

            def scan_sym(s, w):
                hit = lens_s[base + s] == l

                @pl.when(hit)
                def _():
                    syms_s[a, w] = s

                return w + hit.astype(jnp.int32)

            w = jax.lax.fori_loop(0, nsym, scan_sym, w)

    # -- one Huffman symbol (per-bit canonical walk) -----------------------
    def decode_sym(a, bitpos):
        def cond(st):
            code, l, bp, sym, err = st
            return (sym < 0) & (err == 0) & (l < 15)

        def body(st):
            code, l, bp, sym, err = st
            bit, bp = read_bits(bp, 1)
            code = code * 2 + bit
            l = l + 1
            idx = code - first_s[a, l]
            hit = (idx >= 0) & (idx < cnt_s[a, l])
            sym = jnp.where(hit, syms_s[a, off_s[a, l] + idx], sym)
            err = jnp.where(bp > bit_limit, jnp.int32(6), err)
            return code, l, bp, sym, err

        _code, _l, bp, sym, err = jax.lax.while_loop(
            cond, body,
            (jnp.int32(0), jnp.int32(0), bitpos, jnp.int32(-1), jnp.int32(0)),
        )
        err = jnp.where((sym < 0) & (err == 0), jnp.int32(3), err)
        return jnp.maximum(sym, 0), bp, err

    # -- literal/match loop over one block's data section ------------------
    def run_block_data(bitpos, outpos):
        def cond(st):
            bp, op, end, err = st
            return (end == 0) & (err == 0)

        def body(st):
            bp, op, end, err = st
            sym, bp, serr = decode_sym(1, bp)
            err = jnp.where(serr != 0, serr, err)

            is_lit = (sym < 256) & (err == 0)
            is_end = sym == 256
            is_len = (sym > 256) & (err == 0)

            lit_ok = is_lit & (op < UMAX)
            err = jnp.where(is_lit & (op >= UMAX), jnp.int32(5), err)

            @pl.when(lit_ok)
            def _():
                ostore(op, sym)

            op = op + lit_ok.astype(jnp.int32)

            def match(bp, op, err):
                li = sym - 257
                err = jnp.where(li > 28, jnp.int32(3), err)
                li = jnp.minimum(li, 28)
                extra, bp = read_bits(bp, lext_ref[li])
                length = lbase_ref[li] + extra
                dsym, bp, derr = decode_sym(2, bp)
                err = jnp.where((err == 0) & (derr != 0), derr, err)
                err = jnp.where((err == 0) & (dsym > 29), jnp.int32(4), err)
                dsym = jnp.minimum(dsym, 29)
                extra, bp = read_bits(bp, dext_ref[dsym])
                dist = dbase_ref[dsym] + extra
                err = jnp.where((err == 0) & (dist > op), jnp.int32(4), err)
                err = jnp.where((err == 0) & (op + length > UMAX),
                                jnp.int32(5), err)

                def copy_body(k, carry):
                    ostore(op + k, oload(op + k - dist))
                    return carry

                n_copy = jnp.where(err == 0, length, 0)
                jax.lax.fori_loop(0, n_copy, copy_body, 0)
                return bp, op + n_copy, err

            bp, op, err = jax.lax.cond(
                is_len, match, lambda b, o, e: (b, o, e), bp, op, err
            )
            err = jnp.where((err == 0) & (bp > bit_limit), jnp.int32(6), err)
            return bp, op, is_end.astype(jnp.int32), err

        bp, op, _end, err = jax.lax.while_loop(
            cond, body, (bitpos, outpos, jnp.int32(0), jnp.int32(0))
        )
        return bp, op, err

    # -- the three block types ---------------------------------------------
    def stored_block(bitpos, outpos):
        bp = ((bitpos + 7) >> 3) << 3
        blen, bp = read_bits(bp, 16)
        nlen, bp = read_bits(bp, 16)
        err = jnp.where((blen ^ 0xFFFF) != nlen, jnp.int32(2), jnp.int32(0))
        err = jnp.where((err == 0) & (outpos + blen > UMAX), jnp.int32(5), err)
        err = jnp.where((err == 0) & (bp + blen * 8 > bit_limit),
                        jnp.int32(6), err)
        src = bp >> 3

        def copy_body(k, carry):
            ostore(outpos + k, cload(src + k))
            return carry

        n_copy = jnp.where(err == 0, blen, 0)
        jax.lax.fori_loop(0, n_copy, copy_body, 0)
        return bp + n_copy * 8, outpos + n_copy, err

    def fixed_block(bitpos, outpos):
        def fill_body(i, carry):
            lens_s[i] = fixedlens_ref[i]
            return carry

        jax.lax.fori_loop(0, _NLENS, fill_body, 0)
        build_table(1, 0, _NLIT)
        build_table(2, _NLIT, _NDIST)
        return run_block_data(bitpos, outpos)

    def dynamic_block(bitpos, outpos):
        hlit, bp = read_bits(bitpos, 5)
        hlit = hlit + 257
        hdist, bp = read_bits(bp, 5)
        hdist = hdist + 1
        hclen, bp = read_bits(bp, 4)
        hclen = hclen + 4

        def zero_all(i, carry):
            lens_s[i] = jnp.int32(0)
            return carry

        jax.lax.fori_loop(0, _NLENS, zero_all, 0)

        def cl_body(i, bp):
            v, bp = read_bits(bp, 3)
            lens_s[clorder_ref[i]] = v
            return bp

        bp = jax.lax.fori_loop(0, hclen, cl_body, bp)
        build_table(0, 0, _NCL)
        jax.lax.fori_loop(0, _NCL, zero_all, 0)  # reuse region for real lens

        # decode hlit+hdist code lengths with repeat codes 16/17/18
        total = hlit + hdist

        def rb_16(bp):  # repeat previous 3..6 times
            v, bp = read_bits(bp, 2)
            return 3 + v, bp

        def rb_17(bp):  # 3..10 zeros
            v, bp = read_bits(bp, 3)
            return 3 + v, bp

        def rb_18(bp):  # 11..138 zeros
            v, bp = read_bits(bp, 7)
            return 11 + v, bp

        def lens_cond(st):
            n, bp, err = st
            return (n < total) & (err == 0)

        def lens_body(st):
            n, bp, err = st
            sym, bp, serr = decode_sym(0, bp)
            err = jnp.where(serr != 0, serr, err)
            is_plain = sym < 16
            rep, bp = jax.lax.switch(
                jnp.clip(sym - 15, 0, 3),
                [lambda bp: (jnp.int32(1), bp), rb_16, rb_17, rb_18],
                bp,
            )
            prev = lens_s[jnp.maximum(n - 1, 0)]
            err = jnp.where((sym == 16) & (n == 0), jnp.int32(7), err)
            val = jnp.where(is_plain, sym, jnp.where(sym == 16, prev, 0))
            count = jnp.where(is_plain, 1, rep)
            err = jnp.where((err == 0) & (n + count > total), jnp.int32(7), err)
            count = jnp.where(err == 0, count, 0)

            def rep_body(k, carry):
                lens_s[n + k] = val
                return carry

            jax.lax.fori_loop(0, count, rep_body, 0)
            return n + count, bp, err

        _n, bp, err = jax.lax.while_loop(
            lens_cond, lens_body, (jnp.int32(0), bp, jnp.int32(0))
        )

        # Relocate dist lengths from [hlit, hlit+hdist) to the fixed base
        # _NLIT, clearing the gap. Copy BACKWARD: dst = _NLIT + i ≥
        # hlit + i = src, so a descending pass never reads a slot it has
        # already written.
        def move_body(k, carry):
            i = _NDIST - 1 - k
            v = jnp.where(
                i < hdist,
                lens_s[jnp.minimum(hlit + i, _NLENS - 1)],
                jnp.int32(0),
            )
            lens_s[_NLIT + i] = v
            return carry

        jax.lax.fori_loop(0, _NDIST, move_body, 0)

        def clear_tail(i, carry):
            @pl.when(i >= hlit)
            def _():
                lens_s[i] = jnp.int32(0)

            return carry

        jax.lax.fori_loop(0, _NLIT, clear_tail, 0)

        build_table(1, 0, _NLIT)
        build_table(2, _NLIT, _NDIST)
        bp2, op2, derr = run_block_data(bp, outpos)
        err = jnp.where(err == 0, derr, err)
        return bp2, op2, err

    def bad_block(bitpos, outpos):
        return bitpos, outpos, jnp.int32(1)

    # -- outer loop over DEFLATE blocks ------------------------------------
    def outer_cond(st):
        bp, op, fin, err = st
        return (fin == 0) & (err == 0)

    def outer_body(st):
        bp, op, fin, err = st
        hdr, bp = read_bits(bp, 3)
        bfinal = hdr & 1
        btype = hdr >> 1
        bp, op, berr = jax.lax.switch(
            jnp.minimum(btype, 3),
            [stored_block, fixed_block, dynamic_block, bad_block],
            bp, op,
        )
        err = jnp.where(err == 0, berr, err)
        err = jnp.where((err == 0) & (bp > bit_limit), jnp.int32(6), err)
        return bp, op, bfinal, err

    _bp, op, _fin, err = jax.lax.while_loop(
        outer_cond, outer_body,
        (jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0)),
    )
    usize = usizes_ref[block_id]
    err = jnp.where((err == 0) & (usize >= 0) & (op != usize),
                    jnp.int32(8), err)
    meta_ref[:, :] = jnp.where(
        (_row_iota == 0) & (_lane_iota == 0), op,
        jnp.where((_row_iota == 0) & (_lane_iota == 1), err, 0),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def inflate_stacked(
    comp: jax.Array, csizes: jax.Array, usizes: jax.Array,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Inflate B independent raw-DEFLATE streams on device.

    comp: (B, CMAX) int32 byte values (payloads left-aligned, zero pad).
    usizes: expected output lengths for validation, or -1 to skip.
    Returns (out (B, UMAX) int32 bytes, meta (B, 2) int32 [len, err]).
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = comp.shape[0]
    consts = [
        jnp.asarray(_LBASE), jnp.asarray(_LEXT),
        jnp.asarray(_DBASE), jnp.asarray(_DEXT),
        jnp.asarray(_CLORDER), jnp.asarray(_FIXED_LENS),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(b,),
        in_specs=[pl.BlockSpec((_CROWS, 128), lambda i, *_: (i, 0))],
        out_specs=[
            pl.BlockSpec((_UROWS, 128), lambda i, *_: (i, 0)),
            pl.BlockSpec((8, 128), lambda i, *_: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.SMEM((_NLENS,), jnp.int32),
            pltpu.SMEM((3, 16), jnp.int32),
            pltpu.SMEM((3, 16), jnp.int32),
            pltpu.SMEM((3, 16), jnp.int32),
            pltpu.SMEM((3, _NLIT), jnp.int32),
        ],
    )
    out, meta = pl.pallas_call(
        _inflate_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((b * _UROWS, 128), jnp.int32),
            jax.ShapeDtypeStruct((b * 8, 128), jnp.int32),
        ],
        grid_spec=grid_spec,
        interpret=interpret,
    )(
        csizes.astype(jnp.int32), usizes.astype(jnp.int32), *consts,
        comp.reshape(b * _CROWS, 128),
    )
    out = out.reshape(b, UMAX)
    meta = meta.reshape(b, 8 * 128)[:, :2]
    return out, meta


def inflate_payloads(
    payloads: List[bytes], usizes=None, interpret=None
) -> List[bytes]:
    """Host wrapper: raw-DEFLATE payload byte strings → decompressed byte
    strings via the device kernel. ``usizes`` (optional) enables ISIZE
    validation per block."""
    b = len(payloads)
    if b == 0:
        return []
    # Bucket the batch size to a power of two so distinct block counts hit
    # O(log) compile-cache entries instead of one Mosaic compile per count.
    # Padding rows carry a minimal valid stream (fixed-Huffman, immediate
    # end-of-block) and are dropped from the result.
    bb = max(8, 1 << (b - 1).bit_length())
    comp = np.zeros((bb, CMAX), dtype=np.int32)
    cs = np.zeros(bb, dtype=np.int32)
    us = np.full(bb, -1, dtype=np.int32)
    for i, p in enumerate(payloads):
        if len(p) > CMAX - 8:
            raise ValueError(f"payload {i} exceeds BGZF bound: {len(p)}")
        comp[i, : len(p)] = np.frombuffer(p, dtype=np.uint8)
        cs[i] = len(p)
    comp[b:, 0] = 0x03          # empty stream: BFINAL=1, fixed, EOB
    cs[b:] = 2
    us[b:] = 0
    if usizes is not None:
        us[:b] = usizes
    if interpret is None:
        from disq_tpu.util import pallas_interpret

        interpret = pallas_interpret()
    from disq_tpu.runtime.tracing import (
        count_transfer, device_span, hbm_resident)

    count_transfer("h2d", comp.nbytes + cs.nbytes + us.nbytes)
    # Device residency: staged inputs + the (B, UMAX) i32 output slab.
    with hbm_resident(comp.nbytes + cs.nbytes + us.nbytes
                      + bb * UMAX * 4):
        with device_span("device.kernel", kernel="inflate",
                         blocks=b) as fence:
            out, meta = inflate_stacked(
                jnp.asarray(comp), jnp.asarray(cs), jnp.asarray(us),
                interpret=interpret,
            )
            fence.sync(meta)
        out = np.asarray(out)
        meta = np.asarray(meta)
        count_transfer("d2h", out.nbytes + meta.nbytes)
    results = []
    for i in range(b):
        if meta[i, 1] != 0:
            raise ValueError(
                f"device inflate failed for block {i}: "
                f"error {int(meta[i, 1])}"
            )
        results.append(out[i, : meta[i, 0]].astype(np.uint8).tobytes())
    return results
