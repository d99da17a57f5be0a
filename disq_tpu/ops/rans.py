"""Pallas rANS-4x8 order-0 decode — one CRAM external block per grid
program.

The device path promised by SURVEY.md §2.8 ("rANS-order-0/1 decode
kernels") for CRAM's external-block codec (htsjdk's rANS decoder;
CRAM 3.0 §13). Like the DEFLATE kernel (``disq_tpu.ops.inflate``),
entropy decode is bit/byte-serial *within* a stream, so all parallelism
is across blocks (grid) — a CRAM slice carries one external block per
data series, and a container scan yields hundreds of independent
streams.

Kernel design (TPU realities):

- The 4 interleaved rANS states live in SMEM scratch and round-robin
  over output positions (state ``i & 3`` decodes byte ``i``), exactly
  the htslib stream contract.
- The 4096-slot symbol lookup (built host-side from the frequency
  table with one ``np.repeat``) sits in VMEM; per-symbol access uses
  the same tile-aligned one-hot gather idiom as the inflate kernel.
- Per-context frequency/cumulative tables enter via scalar prefetch
  (SMEM), indexed ``[block_id, symbol]``.
- The renormalization loop ("while x < 2^23: consume a byte") needs at
  most two bytes per symbol, so it unrolls into two conditional steps —
  no inner while_loop.
- All arithmetic fits int32: the maximum state is (2^23-1)·256+255 =
  2^31-1 and freq·(x>>12)+m-cum ≤ 2^31-1.

The native C codec (``disq_tpu.native``) remains the production host
path; this kernel is the device alternative, oracle-tested for byte
equality against it.
"""

from __future__ import annotations

import functools
from typing import List

import numpy as np

import jax
import jax.numpy as jnp

RANS_LOW = 1 << 23
TF_SHIFT = 12
TOTFREQ = 1 << TF_SHIFT

_LOOKUP_ROWS = TOTFREQ // 128  # 32


def _rans0_kernel(
    raw_sizes_ref, clens_ref, states0_ref, freqs_ref, cums_ref,
    body_ref, lookup_ref,
    out_ref, meta_ref,
    st_s,
):
    """Decode one stream. st_s (SMEM, 8): [x0..x3, off, err]."""
    import jax.experimental.pallas as pl

    block_id = pl.program_id(0)
    raw_size = raw_sizes_ref[block_id]
    clen = clens_ref[block_id]

    _row_iota = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    _lane_iota = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)

    def _mask(i):
        sub = i & 1023
        return (_row_iota == (sub >> 7)) & (_lane_iota == (sub & 127))

    def _tile_get(ref, i):
        tile = ref[pl.ds((i >> 10) * 8, 8), :]
        return jnp.sum(jnp.where(_mask(i), tile, 0))

    def ostore(i, v):
        base = (i >> 10) * 8
        tile = out_ref[pl.ds(base, 8), :]
        out_ref[pl.ds(base, 8), :] = jnp.where(_mask(i), v, tile)

    for j in range(4):
        st_s[j] = states0_ref[block_id, j]
    st_s[4] = jnp.int32(0)  # off into body (renorm bytes)
    st_s[5] = jnp.int32(0)  # err

    def step(i, carry):
        @pl.when(i < raw_size)
        def _():
            j = i & 3
            x = st_s[j]
            m = x & (TOTFREQ - 1)
            s = _tile_get(lookup_ref, m)
            ostore(i, s)
            x = (
                freqs_ref[block_id, s] * (x >> TF_SHIFT)
                + m
                - cums_ref[block_id, s]
            )
            # ≤ 2 renorm bytes per symbol (byte-wise renorm from ≥ 2^11).
            # The read offset is clamped to clen: a corrupt stream keeps
            # incrementing st_s[4] (tripping the overrun error below)
            # without ever issuing an out-of-block VMEM access.
            for _ in range(2):
                off = st_s[4]
                b = _tile_get(body_ref, jnp.minimum(off, clen))
                need = x < RANS_LOW
                x = jnp.where(need, (x << 8) | b, x)
                st_s[4] = off + need.astype(jnp.int32)
            st_s[j] = x

        return carry

    jax.lax.fori_loop(0, out_ref.shape[0] * 128, step, 0)
    # err: consumed past the announced compressed length
    err = (st_s[4] > clen).astype(jnp.int32)
    meta_ref[:, :] = jnp.where(
        (_row_iota == 0) & (_lane_iota == 0), st_s[4],
        jnp.where((_row_iota == 0) & (_lane_iota == 1), err, 0),
    )


@functools.partial(
    jax.jit, static_argnames=("body_rows", "out_rows", "interpret")
)
def rans0_decode_stacked(
    body, lookup, raw_sizes, clens, states0, freqs, cums,
    body_rows: int, out_rows: int, interpret: bool = False,
):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = raw_sizes.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((body_rows, 128), lambda i, *_: (i, 0)),
            pl.BlockSpec((_LOOKUP_ROWS, 128), lambda i, *_: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((out_rows, 128), lambda i, *_: (i, 0)),
            pl.BlockSpec((8, 128), lambda i, *_: (i, 0)),
        ],
        scratch_shapes=[pltpu.SMEM((8,), jnp.int32)],
    )
    out, meta = pl.pallas_call(
        _rans0_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((b * out_rows, 128), jnp.int32),
            jax.ShapeDtypeStruct((b * 8, 128), jnp.int32),
        ],
        grid_spec=grid_spec,
        interpret=interpret,
    )(
        raw_sizes.astype(jnp.int32), clens.astype(jnp.int32),
        states0.astype(jnp.int32), freqs.astype(jnp.int32),
        cums.astype(jnp.int32),
        body.reshape(b * body_rows, 128),
        lookup.reshape(b * _LOOKUP_ROWS, 128),
    )
    return out.reshape(b, out_rows * 128), meta.reshape(b, 8 * 128)[:, :2]


def rans0_decode_device(streams: List[bytes], interpret=None) -> List[bytes]:
    """Decode a batch of order-0 rANS 4x8 streams (full streams incl.
    the 9-byte header) on device. Tables parse host-side (O(alphabet));
    the per-byte loop runs in the kernel."""
    # shared header/table/state parse + validation (single source of
    # truth with the SIMD kernel — both kernels accept the same streams)
    from disq_tpu.ops.rans_simd import _parse_stream

    b = len(streams)
    if b == 0:
        return []
    metas = []
    for k, s in enumerate(streams):
        p = _parse_stream(k, s)
        if p is None:
            metas.append(None)
            continue
        raw_size, renorm, states, freqs, cum = p
        lookup = np.repeat(np.arange(256, dtype=np.int32), freqs)
        metas.append((raw_size, renorm, states, freqs, cum[:256], lookup))

    live = [m for m in metas if m is not None]
    if not live:
        return [b""] * b
    n = len(live)
    # Bucket padded shapes so distinct batches reuse compiled kernels.
    nb = max(8, 1 << (n - 1).bit_length())
    max_raw = max(m[0] for m in live)
    max_body = max(len(m[1]) for m in live)
    out_rows = max(8, -(-max_raw // 1024) * 8)
    body_rows = max(8, -(-(max_body + 8) // 1024) * 8)
    body_arr = np.zeros((nb, body_rows * 128), dtype=np.int32)
    lookup_arr = np.zeros((nb, TOTFREQ), dtype=np.int32)
    raws = np.zeros(nb, dtype=np.int32)
    clens = np.zeros(nb, dtype=np.int32)
    states0 = np.full((nb, 4), RANS_LOW, dtype=np.int64)
    freqs_arr = np.zeros((nb, 256), dtype=np.int32)
    cums_arr = np.zeros((nb, 256), dtype=np.int32)
    for i, (raw_size, renorm, states, freqs, cum, lookup) in enumerate(live):
        body_arr[i, : len(renorm)] = np.frombuffer(renorm, dtype=np.uint8)
        lookup_arr[i] = lookup
        raws[i] = raw_size
        clens[i] = len(renorm)
        states0[i] = states
        freqs_arr[i] = freqs[:256]
        cums_arr[i] = cum
    if interpret is None:
        from disq_tpu.util import pallas_interpret

        interpret = pallas_interpret()
    from disq_tpu.runtime.tracing import (
        count_transfer, device_span, hbm_resident)

    states32 = states0.astype(np.int32)  # the upload is the i32 cast
    up = (body_arr.nbytes + lookup_arr.nbytes + raws.nbytes
          + clens.nbytes + states32.nbytes + freqs_arr.nbytes
          + cums_arr.nbytes)
    count_transfer("h2d", up)
    with hbm_resident(up + nb * out_rows * 128 * 4):
        with device_span("device.kernel", kernel="rans",
                         streams=n) as fence:
            out, meta = rans0_decode_stacked(
                jnp.asarray(body_arr), jnp.asarray(lookup_arr),
                jnp.asarray(raws),
                jnp.asarray(clens), jnp.asarray(states32),
                jnp.asarray(freqs_arr), jnp.asarray(cums_arr),
                body_rows=int(body_rows), out_rows=int(out_rows),
                interpret=bool(interpret),
            )
            fence.sync(meta)
        out = np.asarray(out)
        meta = np.asarray(meta)
        count_transfer("d2h", out.nbytes + meta.nbytes)
    results = []
    li = 0
    for orig, m in enumerate(metas):
        if m is None:
            results.append(b"")
            continue
        if meta[li, 1] != 0:
            raise ValueError(
                f"device rANS decode overran stream {orig} "
                f"(consumed {int(meta[li, 0])} of {int(clens[li])})"
            )
        results.append(out[li, : m[0]].astype(np.uint8).tobytes())
        li += 1
    return results
