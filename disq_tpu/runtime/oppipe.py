"""OpPipeline — composable resident operator chains (ROADMAP item 4's
sam2bam shape: decode → filter → markdup → sort → stat as ONE
pipeline on the columnar currency).

An ``OpPipeline`` is an ordered list of operators applied shard-wise
between decode and sink/reduce. Every transform speaks
``ColumnarBatch`` in and out, so a chain over resident shards never
materializes host records: ``filter`` gathers the kept records'
columns on device and, of their bytes, copies the kept ones or keeps
its source's under a pending order (``ColumnarBatch._compact_device``
says when), ``sort`` returns a ``permuted()`` resident batch,
``markdup`` patches flag bits in HBM *and* in the record blob bytes
(a copy of its own where the blob is shared), and the reductions
(``pileup`` / ``rgstats``) only move their result rows d2h. Host
``ReadBatch`` shards run the same operators through their host paths
— identical outputs, different residency.

Operators with cross-shard semantics finalize after the per-shard
pass: ``markdup`` runs the driver-side boundary-key merge
(``ops/markdup.merge_boundary_duplicates``) so duplicate clusters
straddling shard seams elect one global representative.

This module imports none of the operator modules at import time and
is itself only imported by ``ReadsDataset.pipeline`` / direct users —
the suite-off zero-work guard (``scripts/check_overhead.py``) holds
``disq_tpu.runtime.oppipe`` out of ``sys.modules`` entirely.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np


class _Op:
    """One pipeline stage: ``apply`` maps a shard batch to a shard
    batch (identity for reductions); ``finalize`` sees every shard
    once and returns the op's merged stats (or None)."""

    name = "op"

    def apply(self, batch, shard: int):
        return batch

    def finalize(self, batches: List) -> Optional[Dict]:
        return None


class FilterOp(_Op):
    """Predicate filter + seeded subsample (``ops/rfilter`` grammar)."""

    name = "filter"

    def __init__(self, spec):
        from disq_tpu.ops.rfilter import ReadFilter, parse_read_filter

        self.rf = spec if isinstance(spec, ReadFilter) \
            else parse_read_filter(spec)

    def apply(self, batch, shard: int):
        from disq_tpu.ops.rfilter import apply_read_filter

        return apply_read_filter(batch, self.rf)


class SortOp(_Op):
    """Coordinate sort, resident when the batch is (``permuted()``
    keeps the device columns + blob for the write path). Within-shard:
    a coordinate-sorted input's shards cover disjoint coordinate
    ranges, so per-shard sorting preserves the global order."""

    name = "sort"

    def apply(self, batch, shard: int):
        from disq_tpu.sort.coordinate import coordinate_sort_batch

        return coordinate_sort_batch(batch, keep_resident=True)


class MarkdupOp(_Op):
    """Duplicate marking + the cross-shard boundary-key merge."""

    name = "markdup"

    def __init__(self, boundary_bp: Optional[int] = None):
        from disq_tpu.ops.markdup import (
            DEFAULT_BOUNDARY_BP, register_counters)

        register_counters()
        self.boundary_bp = (DEFAULT_BOUNDARY_BP if boundary_bp is None
                            else int(boundary_bp))
        self._results: List = []

    def apply(self, batch, shard: int):
        from disq_tpu.ops.markdup import markdup_batch

        batch, res = markdup_batch(batch, boundary_bp=self.boundary_bp)
        self._results.append((batch, res))
        return batch

    def finalize(self, batches: List) -> Dict:
        from disq_tpu.ops.markdup import merge_boundary_duplicates

        merge_boundary_duplicates(self._results)
        out = {"examined": 0, "duplicates": 0, "boundary_flips": 0}
        for _b, res in self._results:
            for k, v in res.stats().items():
                out[k] += v
        self._results = []
        return out


class PileupOp(_Op):
    """Per-base coverage over one region, summed across shards
    (disjoint shards contribute disjoint alignments; integer adds)."""

    name = "pileup"

    def __init__(self, refid: int, start: int, end: int):
        self.refid, self.start, self.end = int(refid), int(start), int(end)
        self._cov: Optional[np.ndarray] = None

    def apply(self, batch, shard: int):
        from disq_tpu.ops.pileup import region_pileup

        cov = region_pileup(batch, self.refid, self.start, self.end)
        self._cov = cov if self._cov is None \
            else (self._cov + cov).astype(np.int32)
        return batch

    def finalize(self, batches: List) -> Dict:
        cov = self._cov if self._cov is not None else np.zeros(
            max(0, self.end - self.start), np.int32)
        self._cov = None
        return {"refid": self.refid, "start": self.start,
                "end": self.end, "coverage": cov}


class RgStatsOp(_Op):
    """Per-read-group reduction, histogram-merged across shards."""

    name = "rgstats"

    def __init__(self):
        self._acc: Dict[str, Dict] = {}

    def apply(self, batch, shard: int):
        from disq_tpu.ops.rgstats import read_group_stats

        for name, st in read_group_stats(batch).items():
            acc = self._acc.setdefault(name, {
                "reads": 0, "duplicates": 0,
                "mapq_hist": np.zeros(256, np.int64)})
            acc["reads"] += st["reads"]
            acc["duplicates"] += st["duplicates"]
            acc["mapq_hist"] += np.asarray(st["mapq_hist"])
        return batch

    def finalize(self, batches: List) -> Dict:
        out: Dict[str, Dict] = {}
        mq = np.arange(256)
        for name, acc in self._acc.items():
            reads, d = int(acc["reads"]), int(acc["duplicates"])
            h = acc["mapq_hist"]
            out[name] = {
                "reads": reads, "duplicates": d,
                "dup_rate": round(d / reads, 6) if reads else 0.0,
                "mean_mapq": round(float((h * mq).sum() / reads), 3)
                if reads else 0.0,
                "mapq_hist": h.astype(int).tolist(),
            }
        self._acc = {}
        return out


_OP_BY_NAME = {
    "filter": FilterOp, "sort": SortOp, "markdup": MarkdupOp,
    "pileup": PileupOp, "rgstats": RgStatsOp,
}


@dataclass
class PipelineResult:
    """Per-shard output batches + each op's merged stats."""

    batches: List
    stats: Dict[str, object] = field(default_factory=dict)

    def concat(self):
        """One batch (consuming — resident shards fold into a resident
        result, see ``ColumnarBatch.concat``)."""
        from disq_tpu.runtime.columnar import concat_batches

        return concat_batches(self.batches)


def make_op(spec) -> _Op:
    """Resolve one op spec: an ``_Op`` instance passes through; a name
    (``"sort"``) or ``(name, *args)`` tuple constructs one."""
    if isinstance(spec, _Op):
        return spec
    if isinstance(spec, str):
        name, args = spec, ()
    elif isinstance(spec, (tuple, list)) and spec:
        name, args = spec[0], tuple(spec[1:])
    else:
        raise TypeError(f"not an operator spec: {spec!r}")
    cls = _OP_BY_NAME.get(name)
    if cls is None:
        raise ValueError(
            f"unknown operator {name!r}; have {sorted(_OP_BY_NAME)}")
    return cls(*args)


class OpPipeline:
    """``OpPipeline(FilterOp("-q 30"), MarkdupOp(), RgStatsOp())`` —
    or by name: ``OpPipeline("filter -q 30" and friends via specs:
    ("filter", "-q 30"), "sort", "markdup", "rgstats")``. ``run``
    takes the decoded shard batches (one concatenated dataset batch
    counts as a single shard) and applies every op in order,
    shard-wise, then finalizes."""

    def __init__(self, *ops):
        self.ops = [make_op(op) for op in ops]

    def run(self, batches: Sequence) -> PipelineResult:
        from disq_tpu.runtime.tracing import span

        batches = list(batches)
        result = PipelineResult(batches=batches)
        with span("ops.pipeline.run",
                  ops=",".join(op.name for op in self.ops),
                  shards=len(batches)):
            for op in self.ops:
                batches = [op.apply(b, i) for i, b in enumerate(batches)]
                st = op.finalize(batches)
                if st is not None:
                    result.stats[op.name] = st
            result.batches = batches
        return result
