"""Device-side BAM fixed-field parsing.

The north-star op from BASELINE.json: BAM record byte parsing as device
kernels over HBM-resident buffers. The ragged scan (pass 1) lives in the
C++ host runtime; this module is pass 2 for the *fixed* section in
device form: each record's 36-byte fixed prefix is 9 little-endian
words, so a dense ``(N, 9)`` int32 array (one host strided gather)
parses into columns with pure VPU integer ops — shifts and masks, no
gathers, no per-record control flow.

Two implementations with identical semantics:
- ``parse_fixed_words``        — jnp (XLA fuses it into one pass)
- ``parse_fixed_words_pallas`` — explicit Pallas TPU kernel (tiled over
  records; the template the BGZF-inflate and record-scan kernels build
  on). Falls back to interpret mode off-TPU.

Word layout (SAM spec §4.2; the leading block_size word is included so
records are 9 aligned words):
  w0 block_size · w1 refID · w2 pos ·
  w3 = l_read_name | mapq<<8 | bin<<16 · w4 = n_cigar | flag<<16 ·
  w5 l_seq · w6 next_refID · w7 next_pos · w8 tlen
"""

from __future__ import annotations

import functools
from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp

N_WORDS = 9
_TILE = 1024


def record_prefix_words(blob: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """Host staging: gather each record's 36-byte prefix (including the
    leading block_size word) as ``(N, 9)`` int32."""
    starts = offsets[:-1].astype(np.int64)
    fixed = blob[starts[:, None] + np.arange(4 * N_WORDS)]
    return np.ascontiguousarray(fixed).view("<i4").reshape(-1, N_WORDS)


def _split_words(w):
    """Shared field math (works on jnp or np arrays)."""
    return dict(
        block_size=w[:, 0],
        refid=w[:, 1],
        pos=w[:, 2],
        l_read_name=w[:, 3] & 0xFF,
        mapq=(w[:, 3] >> 8) & 0xFF,
        bin=(w[:, 3] >> 16) & 0xFFFF,
        n_cigar=w[:, 4] & 0xFFFF,
        flag=(w[:, 4] >> 16) & 0xFFFF,
        l_seq=w[:, 5],
        next_refid=w[:, 6],
        next_pos=w[:, 7],
        tlen=w[:, 8],
    )


@jax.jit
def parse_fixed_words(words: jax.Array) -> Dict[str, jax.Array]:
    """jnp implementation — one fused elementwise pass on device."""
    return _split_words(words)


def _parse_kernel(w_ref, *out_refs):
    outs = _split_words(w_ref[:])
    for ref, key in zip(out_refs, _FIELD_ORDER):
        ref[:] = outs[key]


_FIELD_ORDER = (
    "block_size", "refid", "pos", "l_read_name", "mapq", "bin",
    "n_cigar", "flag", "l_seq", "next_refid", "next_pos", "tlen",
)


def parse_fixed_words_pallas(
    words: jax.Array, interpret: bool = False
) -> Dict[str, jax.Array]:
    """Instrumented entry for the Pallas fixed-field parse kernel.

    Called with concrete arrays (host entry) it books device telemetry
    — ``device.kernel_launches{kernel=parse}``, transfer bytes for a
    host-side input, and a ``device.kernel`` span fenced with
    ``block_until_ready``.  Called under an enclosing trace
    (the device pipeline's jit) it is a passthrough: the outer caller
    owns the accounting and no host sync is possible mid-trace."""
    from jax.core import Tracer

    if isinstance(words, Tracer):
        return _parse_fixed_words_pallas(words, interpret=interpret)
    from disq_tpu.runtime.tracing import count_transfer, device_span

    nbytes = int(words.size) * words.dtype.itemsize
    if not isinstance(words, jax.Array):
        count_transfer("h2d", nbytes)
    with device_span("device.kernel", kernel="parse",
                     records=int(words.shape[0])) as fence:
        return fence.sync(
            _parse_fixed_words_pallas(words, interpret=interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _parse_fixed_words_pallas(
    words: jax.Array, interpret: bool = False
) -> Dict[str, jax.Array]:
    """Pallas TPU kernel: grid over record tiles, each program parsing
    ``_TILE`` records from VMEM with VPU shifts/masks."""
    from jax.experimental import pallas as pl

    n = words.shape[0]
    padded = ((n + _TILE - 1) // _TILE) * _TILE
    if padded != n:
        words = jnp.pad(words, ((0, padded - n), (0, 0)))
    grid = padded // _TILE
    outs = pl.pallas_call(
        _parse_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((padded,), jnp.int32) for _ in _FIELD_ORDER
        ],
        grid=(grid,),
        in_specs=[pl.BlockSpec((_TILE, N_WORDS), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((_TILE,), lambda i: (i,)) for _ in _FIELD_ORDER],
        interpret=interpret,
    )(words)
    return {k: v[:n] for k, v in zip(_FIELD_ORDER, outs)}
