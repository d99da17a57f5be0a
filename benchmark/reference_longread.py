"""The long-read configuration's copy of the plain reference.

numpy and zlib only: nothing here imports the program or takes anything
the program has made.  What differs from ``reference.py`` is the record
shape: every ragged column is flat with offsets, a record may carry no
SEQ, and the reference bases an alignment covers are worked out here
from the CIGAR op words, not taken from the generator's own count.
Every comparison is exact (limit 0).
"""

from __future__ import annotations

import numpy as np

from benchmark.gen_longread import LongTruth, offsets_of
from benchmark.reference import (  # noqa: F401  (the shared pieces)
    Checks, bgzf_blocks, columns_differing, depth_differs, flagstat)

_ROWS = 1024       # records a step of a ragged copy (bounds its index)
_REF_OPS = (0, 2, 3, 7, 8)      # M D N = X consume the reference


def lens_of(off: np.ndarray) -> np.ndarray:
    return off[1:] - off[:-1]


def reference_lengths(truth: LongTruth) -> np.ndarray:
    """Reference bases each record's CIGAR consumes (SAM spec section
    1.4.6), from the op words themselves."""
    consumed = (truth.cigars >> 4).astype(np.int64) \
        * np.isin(truth.cigars & 0xF, _REF_OPS)
    total = np.concatenate([[0], np.cumsum(consumed)])
    return total[truth.cigar_offsets[1:]] - total[truth.cigar_offsets[:-1]]


def depth(truth: LongTruth, contig_lengths, window: int) -> dict:
    """Windowed depth per contig: +1 at the alignment's first window,
    -1 past its last, cumulative sum (mapped, placed records only)."""
    reflen = reference_lengths(truth)
    out = {}
    for r, length in enumerate(contig_lengths):
        nw = max(1, -(-length // window))
        sel = (truth.refid == r) & ((truth.flag & 0x4) == 0)
        p = truth.pos[sel].astype(np.int64)
        e = p + np.maximum(reflen[sel], 1)
        lo = np.clip(p // window, 0, nw - 1)
        hi = np.clip((e - 1) // window, 0, nw - 1)
        diff = np.bincount(lo, minlength=nw + 1).astype(np.int64)
        diff -= np.bincount(hi + 1, minlength=nw + 1)
        out[r] = np.cumsum(diff)[:nw].astype(np.int32)
    return out


def record_sizes(truth: LongTruth) -> np.ndarray:
    """``block_size`` of each record (SAM spec section 4.2)."""
    l_seq = lens_of(truth.seq_offsets)
    return (32 + lens_of(truth.name_offsets) + 1
            + 4 * lens_of(truth.cigar_offsets) + (l_seq + 1) // 2 + l_seq
            + lens_of(truth.tag_offsets)).astype(np.int64)


def record_bytes(truth: LongTruth) -> int:
    """Decoded bytes of the records, each with its 4-byte length."""
    return int(record_sizes(truth).sum()) + 4 * truth.count


def _place(out: np.ndarray, at: np.ndarray, flat: np.ndarray,
           off: np.ndarray) -> None:
    """``flat``'s row i (``off[i]`` to ``off[i + 1]``) to ``out[at[i]:]``."""
    for lo in range(0, len(at), _ROWS):
        hi = min(lo + _ROWS, len(at))
        dst = np.repeat(at[lo:hi] - off[lo:hi], lens_of(off[lo: hi + 1])) \
            + np.arange(off[lo], off[hi])
        out[dst] = flat[off[lo]: off[hi]]


def packed_sequences(truth: LongTruth):
    """4-bit packing, two bases a byte, the high nibble first and a zero
    nibble after an odd length -> (bytes flat, offsets)."""
    l_seq = lens_of(truth.seq_offsets)
    off = offsets_of((l_seq + 1) // 2)
    padded = np.zeros(2 * int(off[-1]), np.uint8)
    _place(padded, 2 * off[:-1], truth.seqs, truth.seq_offsets)
    return (padded[0::2] << 4) | padded[1::2], off


def encode_records(truth: LongTruth) -> bytes:
    """The BAM record bytes of ``truth``, in its order."""
    n = truth.count
    size = record_sizes(truth)
    core = np.zeros(n, np.dtype([
        ("block_size", "<i4"), ("refid", "<i4"), ("pos", "<i4"),
        ("l_read_name", "u1"), ("mapq", "u1"), ("bin", "<u2"),
        ("n_cigar", "<u2"), ("flag", "<u2"), ("l_seq", "<i4"),
        ("next_refid", "<i4"), ("next_pos", "<i4"), ("tlen", "<i4")]))
    core["block_size"] = size
    for name in ("refid", "pos", "mapq", "bin", "flag", "next_refid",
                 "next_pos", "tlen"):
        core[name] = getattr(truth, name)
    l_name = lens_of(truth.name_offsets)
    core["l_read_name"] = l_name + 1
    core["n_cigar"] = lens_of(truth.cigar_offsets)
    core["l_seq"] = lens_of(truth.seq_offsets)
    packed, packed_off = packed_sequences(truth)
    cigar_bytes = truth.cigars.astype("<u4").view(np.uint8)
    start = offsets_of(size + 4)
    out = np.zeros(int(start[-1]), np.uint8)     # the names' NULs stay 0
    at = start[:-1].copy()
    for flat, off, gap in (
            (core.view(np.uint8), np.arange(0, 36 * n + 1, 36), 0),
            (truth.names, truth.name_offsets, 1),
            (cigar_bytes, 4 * truth.cigar_offsets, 0),
            (packed, packed_off, 0),
            (truth.quals, truth.seq_offsets, 0),
            (truth.tags, truth.tag_offsets, 0)):
        _place(out, at, flat, off)
        at += lens_of(off) + gap
    return out.tobytes()
