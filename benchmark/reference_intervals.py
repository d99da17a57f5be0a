"""The plain reference of an interval read, and the capture kit's target
list that the configuration describes.

numpy only; imports nothing of the program.  A record is kept when it
overlaps a target: 0-based half-open, the record from its position to
its position plus the reference bases its CIGAR consumes, and a placed
record whose CIGAR consumes none is one base long (as samtools has it).
The sweep below goes target by target over the records of a contig in
position order; ``tests/benchmark_harness`` holds it to a record-by-
record brute force on the same seeded records.
"""

from __future__ import annotations

import numpy as np

from benchmark.gen import Truth, populated_span
from benchmark.reference_chain import REFERENCE_OPS


def alignment_ends(truth: Truth) -> np.ndarray:
    """Exclusive end of each record on its reference, from the CIGAR op
    words alone."""
    op = truth.cigar_mat & 0xF
    held = np.arange(op.shape[1])[None, :] < truth.cigar_len[:, None]
    span = np.where(held & np.isin(op, REFERENCE_OPS),
                    truth.cigar_mat >> 4, 0).sum(axis=1, dtype=np.int64)
    return truth.pos.astype(np.int64) + np.maximum(span, 1)


def merge(refid: np.ndarray, start0: np.ndarray, end0: np.ndarray):
    """Targets sorted by (contig, start), overlapping and abutting ones
    made one."""
    order = np.lexsort((start0, refid))
    out = []
    for r, s, e in zip(refid[order].tolist(), start0[order].tolist(),
                       end0[order].tolist()):
        if out and out[-1][0] == r and s <= out[-1][2]:
            out[-1][2] = max(out[-1][2], e)
        else:
            out.append([r, s, e])
    merged = np.array(out, np.int64).reshape(-1, 3)
    return merged[:, 0], merged[:, 1], merged[:, 2]


def targets(cfg: dict, n_records: int):
    """The configuration's target list over the span that ``n_records``
    populate: ``(contig index, start, end)`` arrays, 0-based half-open,
    padded and merged, in (contig, start) order.  Drawn from the
    configuration's ``targets_seed`` alone: the kit is the same file
    for every sample."""
    t = cfg["targets"]
    rng = np.random.default_rng(t["targets_seed"])
    span = populated_span(n_records, cfg)
    sigma = np.sqrt(2 * np.log(t["width_mean"] / t["width_median"]))
    lo, hi = t["width_clip"]
    refid, start0, end0 = [], [], []
    for r, contig in enumerate(cfg["contigs"]):
        genes = max(1, round(span / t["bp_per_gene"]))
        for gene_start in np.sort(rng.integers(100, 100 + span, genes)):
            k = int(rng.geometric(1.0 / t["targets_per_gene_mean"]))
            width = np.clip(rng.lognormal(
                np.log(t["width_median"]), sigma, k), lo, hi).astype(np.int64)
            gap = t["gap_min"] + rng.exponential(
                t["gap_mean"] - t["gap_min"], k).astype(np.int64)
            begin = gene_start + np.cumsum(width + gap) - width - gap
            refid.append(np.full(k, r, np.int64))
            start0.append(np.maximum(begin - t["interval_padding"], 0))
            end0.append(np.minimum(begin + width + t["interval_padding"],
                                   contig["length"]))
    return merge(np.concatenate(refid), np.concatenate(start0),
                 np.concatenate(end0))


def kept(sorted_truth: Truth, target_list) -> np.ndarray:
    """Indices, ascending, of the records of a coordinate-ordered
    ``Truth`` that overlap a target: a sweep over the targets, each
    looking at the records whose position lies between the farthest a
    record reaches back and the target's end."""
    refid, start0, end0 = target_list
    ends = alignment_ends(sorted_truth)
    hit = np.zeros(sorted_truth.count, bool)
    for r in np.unique(refid).tolist():
        rows = np.flatnonzero(sorted_truth.refid == r)
        if len(rows) == 0:
            continue
        pos, end = sorted_truth.pos[rows].astype(np.int64), ends[rows]
        if np.any(np.diff(pos) < 0):
            raise ValueError("the records are not in coordinate order")
        reach = int((end - pos).max())
        mine = np.zeros(len(rows), bool)
        for s, e in zip(start0[refid == r].tolist(),
                        end0[refid == r].tolist()):
            a = np.searchsorted(pos, s - reach, "left")
            b = np.searchsorted(pos, e, "left")
            mine[a:b] |= end[a:b] > s
        hit[rows] = mine
    return np.flatnonzero(hit)
