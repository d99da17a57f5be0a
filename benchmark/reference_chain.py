"""The plain reference of the operator chain: ``samtools view``
predicate -> stable coordinate order -> duplicate marking, over the
generator's arrays.

numpy only; imports nothing of the program.  It is the array copy of
``tests/reference_markdup.py`` (record by record, whose header states
the marking rule and where it departs from Picard / GATK), and a test
holds the two to each other on the same seeded records.
"""

from __future__ import annotations

import collections

import numpy as np

from benchmark.gen import Truth
from benchmark.reference import coordinate_order

MARKDUP_EXCLUDE = 0x4 | 0x100 | 0x800
DUPLICATE = 0x400
MIN_QUALITY, NO_QUALITY = 15, 0xFF
CLIP_OPS = (4, 5)                   # S H
REFERENCE_OPS = (0, 2, 3, 7, 8)     # M D N = X

# the chain's answer: the kept records in output order with their
# duplicate bits set, which of them the marking made duplicates, and
# the operator's two counts
Chain = collections.namedtuple("Chain", "kept marked examined duplicates")


def view_mask(truth: Truth, spec: str) -> np.ndarray:
    """Records ``samtools view`` passes under ``-f INT -F INT -q INT``."""
    want = {"-f": 0, "-F": 0, "-q": 0}
    words = spec.split()
    if len(words) % 2 or any(w not in want for w in words[0::2]):
        raise ValueError(f"the reference cannot read the filter {spec!r}")
    for opt, value in zip(words[0::2], words[1::2]):
        want[opt] = int(value, 0)
    flag = truth.flag.astype(np.int64)
    return (((flag & want["-f"]) == want["-f"]) & ((flag & want["-F"]) == 0)
            & (truth.mapq >= want["-q"]))


def unclipped_five_prime(truth: Truth) -> np.ndarray:
    """The position of each read's 5' end with its clips undone: the
    position less the clips that lead the CIGAR (forward), the last
    aligned base plus the clips that trail it (reverse)."""
    op = (truth.cigar_mat & 0xF).astype(np.int64)
    length = (truth.cigar_mat >> 4).astype(np.int64)
    n, width = op.shape
    held = np.arange(width)[None, :] < truth.cigar_len[:, None]
    span = np.where(held & np.isin(op, REFERENCE_OPS), length, 0).sum(axis=1)
    clip = held & np.isin(op, CLIP_OPS)
    row = np.arange(n)
    lead, trail = np.zeros(n, np.int64), np.zeros(n, np.int64)
    leading, trailing = np.ones(n, bool), np.ones(n, bool)
    for k in range(width):
        leading &= clip[:, k]
        lead += np.where(leading, length[:, k], 0)
        last = truth.cigar_len - 1 - k
        trailing &= (last >= 0) & clip[row, np.maximum(last, 0)]
        trail += np.where(trailing, length[row, np.maximum(last, 0)], 0)
    pos = truth.pos.astype(np.int64)
    reverse = (truth.flag & 0x10) != 0
    return np.where(reverse, pos + np.maximum(span, 1) - 1 + trail,
                    pos - lead)


def scores(truth: Truth) -> np.ndarray:
    q = truth.qual_mat
    return np.where((q >= MIN_QUALITY) & (q != NO_QUALITY), q, 0).sum(
        axis=1, dtype=np.int64)


def mark_duplicates(truth: Truth):
    """``(is duplicate, examined count)`` of records in coordinate
    order: of the examined records sharing (reference, unclipped 5'
    position, orientation) the best score stays, ties to the earlier."""
    flag = truth.flag.astype(np.int64)
    looked_at = ((flag & MARKDUP_EXCLUDE) == 0) & (truth.refid >= 0)
    idx = np.flatnonzero(looked_at)
    t = truth.take(idx)
    key = (t.refid.astype(np.int64), unclipped_five_prime(t),
           (t.flag & 0x10) != 0)
    # by key, then best score first, then earliest: the first of each
    # run of one key stays
    by = np.lexsort((idx, -scores(t)) + key[::-1])
    loser = np.zeros(len(idx), bool)
    loser[1:] = np.logical_and.reduce(
        [k[by][1:] == k[by][:-1] for k in key])
    marked = np.zeros(truth.count, bool)
    marked[idx[by[loser]]] = True
    return marked, len(idx)


def chain(truth: Truth, view_spec: str) -> Chain:
    """The whole chain on the generator's input-order records."""
    passed = truth.take(np.flatnonzero(view_mask(truth, view_spec)))
    kept = passed.take(coordinate_order(passed))
    marked, examined = mark_duplicates(kept)
    kept.flag = np.where(marked, kept.flag | np.uint16(DUPLICATE),
                         kept.flag).astype(kept.flag.dtype)
    return Chain(kept, marked, examined, int(marked.sum()))
