"""Public API — mirrors disq's L6 surface (SURVEY.md §2.1).

Reference parity map:
- ``ReadsStorage``      ← ``HtsjdkReadsRddStorage.java`` (builder-style
  config: ``split_size``, ``validation_stringency``,
  ``reference_source_path``; then ``read`` / ``write``)
- ``ReadsDataset``      ← ``HtsjdkReadsRdd.java`` (header + records); here
  the records are sharded **columnar arrays** (a ``ReadBatch``) rather
  than an RDD of objects.
- ``VariantsStorage``   ← ``HtsjdkVariantsRddStorage.java``
- ``VariantsDataset``   ← ``HtsjdkVariantsRdd.java``
- ``TraversalParameters`` ← ``HtsjdkReadsTraversalParameters.java``
- WriteOption hierarchy ← ``WriteOption.java`` + the enums
  (``ReadsFormatWriteOption``, ``VariantsFormatWriteOption``,
  ``FileCardinalityWriteOption``, ``TempPartsDirectoryWriteOption``,
  ``BaiWriteOption``, ``SbiWriteOption``, ``CraiWriteOption``,
  ``TabixIndexWriteOption``).

Two deliberate departures from the reference, per the TPU-first design:
1. **Sorting is first-class.** Upstream disq trusts
   ``header.sort_order`` and leaves sorting to the caller's Spark
   ``sortBy``; here ``ReadsStorage.write(..., sort=True)`` (or
   ``ReadsDataset.coordinate_sorted()``) runs the multi-chip radix sort.
2. Records live as device-sharded columnar arrays, so ``count()`` /
   filters / sorts are array ops, not object iteration.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple, Union

import numpy as np

from disq_tpu.runtime.errors import DisqOptions, ErrorPolicy  # noqa: F401
# (re-exported here: the error-policy knob is part of the public read
# surface — ``ReadsStorage.make_default().error_policy("skip")``.)


def _telemetry_report(counters) -> dict:
    """Dataset-level telemetry bundle: the dataset's reduced per-shard
    counters together with the process registry (labeled counters,
    gauges, phase-latency histograms), the phase/gauge views, and the
    span-log location — one dict answering "what did this read cost
    and where did the wall-clock go"."""
    from disq_tpu.runtime import tracing
    from disq_tpu.runtime.introspect import introspect_address
    from disq_tpu.runtime.multihost import process_id

    snapshot = tracing.telemetry_snapshot()
    # The device-pipeline rollup (transfer bytes, kernel launches,
    # host fallbacks, HBM peak) pulled out of the full snapshot so
    # callers see the accelerator story without walking every metric.
    device = {
        name: series
        for kind in snapshot.values()
        for name, series in kind.items()
        if name.startswith("device.")
    }
    # Resilience rollup, mirroring the device key: the closed-loop
    # fault-handling story (hedge races, breaker state machine, retry
    # budget, deadline escalations) at a glance.
    resilience = {
        name: series
        for kind in snapshot.values()
        for name, series in kind.items()
        if name.split(".", 1)[0] in ("hedge", "breaker", "budget",
                                     "deadline")
    }
    return {
        "run_id": tracing.RUN_ID,
        "process_id": process_id(),
        "counters": counters.as_dict() if counters is not None else {},
        "metrics": snapshot,
        "device": device,
        "resilience": resilience,
        "phases": tracing.phase_report(),
        "gauges": tracing.gauge_report(),
        "span_log": tracing.span_log_path(),
        "introspect": introspect_address(),
    }


class WriteOption:
    """Marker base for varargs write options (ref: ``WriteOption.java``)."""


class ReadsFormatWriteOption(WriteOption, enum.Enum):
    BAM = "bam"
    CRAM = "cram"
    SAM = "sam"


class VariantsFormatWriteOption(WriteOption, enum.Enum):
    VCF = "vcf"
    VCF_GZ = "vcf.gz"
    VCF_BGZ = "vcf.bgz"
    # Extension beyond reference parity: upstream disq has no BCF
    # (SURVEY.md §2.1 note); BASELINE.json's matrix mentions BCF read.
    BCF = "bcf"


class FileCardinalityWriteOption(WriteOption, enum.Enum):
    SINGLE = "single"
    MULTIPLE = "multiple"


@dataclass(frozen=True)
class TempPartsDirectoryWriteOption(WriteOption):
    """Staging dir for headerless part files before the single-file merge
    (ref: ``TempPartsDirectoryWriteOption.java``)."""

    path: str


@dataclass(frozen=True)
class StageManifestWriteOption(WriteOption):
    """Enable the restartable write protocol (SURVEY.md §5): per-shard
    progress is checkpointed to a stage-manifest JSON at ``path``; a
    crashed write re-run with the same manifest re-executes only the
    missing shards, and staged parts survive failures until the merge
    commit point. Beyond reference parity — Spark got this from task
    retry + lineage."""

    path: str


class BaiWriteOption(WriteOption, enum.Enum):
    ENABLE = True
    DISABLE = False


class SbiWriteOption(WriteOption, enum.Enum):
    ENABLE = True
    DISABLE = False


class CraiWriteOption(WriteOption, enum.Enum):
    ENABLE = True
    DISABLE = False


class TabixIndexWriteOption(WriteOption, enum.Enum):
    ENABLE = True
    DISABLE = False


class ValidationStringency(enum.Enum):
    STRICT = "strict"
    LENIENT = "lenient"
    SILENT = "silent"


@dataclass(frozen=True)
class Interval:
    """A 1-based closed genomic interval (htsjdk ``Locatable`` analogue)."""

    contig: str
    start: int  # 1-based inclusive
    end: int    # inclusive

    def overlaps(self, contig: str, start: int, end: int) -> bool:
        return self.contig == contig and self.start <= end and start <= self.end


@dataclass(frozen=True)
class TraversalParameters:
    """Interval + unplaced-unmapped traversal spec for indexed reads
    (ref: ``HtsjdkReadsTraversalParameters.java``)."""

    intervals: Optional[Sequence[Interval]] = None
    traverse_unplaced_unmapped: bool = False


@dataclass
class ReadsDataset:
    """Header + sharded columnar read batch (ref: ``HtsjdkReadsRdd.java``).

    ``counters``, when present, holds the reduced per-shard decode
    counters (records/blocks/bytes/compression ratio; SURVEY.md §5)."""

    header: "SamHeader"
    reads: "ReadBatch"
    counters: object = None

    def count(self) -> int:
        return int(self.reads.count)

    def telemetry_report(self) -> dict:
        """This dataset's reduced shard counters + the process
        telemetry registry (labeled counters, gauges, phase-latency
        histograms) in one dict — see ``runtime/tracing.py``."""
        return _telemetry_report(self.counters)

    def introspect_address(self) -> "str | None":
        """``host:port`` of the live-introspection endpoint
        (``/metrics`` / ``/healthz`` / ``/progress`` / ``/spans``)
        serving the process this dataset was read in, or None when the
        endpoint is disabled — see ``runtime/introspect.py``."""
        from disq_tpu.runtime.introspect import introspect_address

        return introspect_address()

    def coordinate_sorted(self, keep_resident: bool = False) -> "ReadsDataset":
        """Coordinate-sort the dataset.  ``keep_resident`` keeps a
        device-backed ``ColumnarBatch`` device-backed through the sort
        (fixed columns permuted on device, host records never
        materialized) for the operators and the write from bytes that
        follow it."""
        from disq_tpu.sort.coordinate import coordinate_sort_batch

        header = self.header.with_sort_order("coordinate")
        return ReadsDataset(
            header=header,
            reads=coordinate_sort_batch(
                self.reads, keep_resident=keep_resident))

    def device_columns(self, sharding=None) -> dict:
        """The fixed record columns as device-resident jax Arrays (one
        upload each; optionally placed with a ``NamedSharding``) — the
        HBM-resident shard-buffer form the device kernels consume
        (``runtime/device_pipeline``, ``ops/flagstat``, ``ops/depth``).
        A dataset read through the fused resident-decode path already
        IS device-backed (``runtime/columnar.ColumnarBatch``): its
        columns are returned as-is, zero transfers. Ragged byte
        columns stay host-side (their device movement is the sort
        exchange's padded-matrix path)."""
        import jax

        from disq_tpu.runtime.columnar import ColumnarBatch

        if (sharding is None and isinstance(self.reads, ColumnarBatch)
                and self.reads.device_backed):
            return self.reads.device_columns()
        cols = {}
        for name in ("refid", "pos", "mapq", "flag", "bin",
                     "next_refid", "next_pos", "tlen"):
            arr = np.ascontiguousarray(getattr(self.reads, name))
            cols[name] = (jax.device_put(arr, sharding)
                          if sharding is not None else jax.device_put(arr))
        return cols

    # -- device analytics ---------------------------------------------------

    def flagstat(self, mesh=None, axis: str = "shards") -> dict:
        """Per-category read counts (``samtools flagstat`` equivalent),
        computed on device; with a mesh, sharded + psum-reduced. A
        resident-decode dataset consumes its device flag column
        directly — no h2d re-upload, d2h is the 48-byte row."""
        from disq_tpu.ops.flagstat import flagstat_counts
        from disq_tpu.runtime.columnar import ColumnarBatch

        if (mesh is None and isinstance(self.reads, ColumnarBatch)
                and self.reads.device_backed):
            return self.reads.flagstat()
        return flagstat_counts(np.asarray(self.reads.flag), mesh=mesh, axis=axis)

    def depth(self, window: int = 1024) -> dict:
        """Windowed coverage depth per reference (device scatter+cumsum)."""
        from disq_tpu.ops.depth import window_depth

        return window_depth(
            self.reads, [s.length for s in self.header.sequences], window
        )

    def pipeline(self, *ops) -> "Tuple[ReadsDataset, dict]":
        """Run a resident operator chain (``runtime/oppipe.py``) over
        this dataset's batch and return ``(dataset, stats)`` — the
        sam2bam preprocessing shape as one composition on the columnar
        currency::

            ds2, stats = ds.pipeline(("filter", "-F 0x400 -q 20"),
                                     "sort", "markdup", "rgstats")

        Each op is an operator instance (``FilterOp`` and friends), a
        name, or a ``(name, *args)`` tuple. On a resident dataset the
        whole chain stays device-backed — transforms compact/permute/
        patch the HBM columns, reductions move only result rows d2h,
        and no host record is ever materialized; a host dataset runs
        the same operators' host paths with identical outputs.
        ``stats`` maps op name → its merged result (markdup counts,
        per-RG stats, pileup coverage...)."""
        from disq_tpu.runtime.oppipe import OpPipeline

        pipe = ops[0] if len(ops) == 1 and isinstance(ops[0], OpPipeline) \
            else OpPipeline(*ops)
        res = pipe.run([self.reads])
        header = self.header
        if any(op.name == "sort" for op in pipe.ops):
            header = header.with_sort_order("coordinate")
        out = ReadsDataset(header=header, reads=res.batches[0],
                           counters=self.counters)
        return out, res.stats


@dataclass
class VariantsDataset:
    """Header + columnar variants (ref: ``HtsjdkVariantsRdd.java``).

    ``counters``, when present, holds the reduced per-shard counters
    including error-policy observability (skipped / quarantined /
    retried; SURVEY.md §5)."""

    header: "VcfHeader"
    variants: "VariantBatch"
    counters: object = None

    def count(self) -> int:
        return int(self.variants.count)

    def telemetry_report(self) -> dict:
        """See ``ReadsDataset.telemetry_report``."""
        return _telemetry_report(self.counters)

    def introspect_address(self) -> "str | None":
        """See ``ReadsDataset.introspect_address``."""
        from disq_tpu.runtime.introspect import introspect_address

        return introspect_address()


def _opt(options, cls, default):
    found = [o for o in options if isinstance(o, cls)]
    if len(found) > 1:
        raise ValueError(f"duplicate {cls.__name__}")
    return found[0] if found else default


def _infer_cardinality(path: str) -> FileCardinalityWriteOption:
    """Extension ⇒ SINGLE merged file; otherwise a directory of complete
    per-shard files (ref: FileCardinalityWriteOption default inference)."""
    lowered = path.lower()
    for ext in (".bam", ".cram", ".sam", ".vcf", ".vcf.gz", ".vcf.bgz", ".bcf"):
        if lowered.endswith(ext):
            return FileCardinalityWriteOption.SINGLE
    return FileCardinalityWriteOption.MULTIPLE


class ReadsStorage:
    """Entry point for reads (ref: ``HtsjdkReadsRddStorage``).

    Usage::

        storage = ReadsStorage.make_default()
            .split_size(64 << 20)
            .reference_source_path("ref.fa")
        ds = storage.read("sample.bam")
        storage.write(ds, "out.bam", BaiWriteOption.ENABLE)
    """

    def __init__(self) -> None:
        self._split_size: int = 128 * 1024 * 1024
        self._stringency = ValidationStringency.STRICT
        self._reference_source_path: Optional[str] = None
        self._num_shards: Optional[int] = None
        self._options = DisqOptions()

    @classmethod
    def make_default(cls) -> "ReadsStorage":
        return cls()

    def split_size(self, n: int) -> "ReadsStorage":
        self._split_size = n
        return self

    def error_policy(self, policy: "ErrorPolicy | str") -> "ReadsStorage":
        """Corrupt-block policy for reads: ``strict`` (default — raise
        ``CorruptBlockError`` with coordinates), ``skip`` (drop + count)
        or ``quarantine`` (drop + copy to the quarantine sidecar)."""
        self._options = self._options.with_policy(policy)
        return self

    def options(self, opts: DisqOptions) -> "ReadsStorage":
        """Replace the full read-path option set (retry budget, backoff,
        quarantine dir, executor sizing) in one call."""
        self._options = opts
        return self

    def executor_workers(self, n: int,
                         prefetch_shards: Optional[int] = None
                         ) -> "ReadsStorage":
        """Size the shard-pipeline executor (``runtime/executor.py``):
        ``n`` decode workers overlap range-reads, inflate and record
        decode across splits; at most ``prefetch_shards`` splits run
        ahead of the ordered emit (None ⇒ ``2 × n``). ``n=1`` (the
        default) is the sequential-compatible inline path. Output is
        byte-identical for any ``n``."""
        self._options = self._options.with_executor(n, prefetch_shards)
        return self

    def writer_workers(self, n: int,
                       prefetch_shards: Optional[int] = None
                       ) -> "ReadsStorage":
        """Size the shard write pipeline (``runtime/executor.py``):
        ``n`` workers overlap record encode, BGZF deflate and part
        staging across write shards in every sink (BAM/SAM/CRAM single
        and multiple); at most ``prefetch_shards`` shards run ahead of
        the ordered emit (None ⇒ ``2 × n``). ``n=1`` (the default) is
        the sequential-compatible inline path. Written files (and
        merged indexes) are byte-identical for any ``n``."""
        self._options = self._options.with_writer(n, prefetch_shards)
        return self

    def span_log(self, path: str) -> "ReadsStorage":
        """Point the process-wide JSONL span sink at ``path`` when a
        read through this storage starts (the input of
        ``scripts/trace_report.py``).  One sink per process — see
        ``DisqOptions.span_log`` for the exact semantics."""
        from dataclasses import replace

        self._options = replace(self._options, span_log=path)
        return self

    def introspect_port(self, port: int) -> "ReadsStorage":
        """Serve the process-wide live-introspection endpoint
        (``/metrics`` / ``/healthz`` / ``/progress`` / ``/spans``) on
        127.0.0.1:``port`` when a pipeline built from this storage
        runs; ``0`` binds an ephemeral port (read it back with
        ``dataset.introspect_address()``). Equivalent env knob:
        ``DISQ_TPU_INTROSPECT_PORT``."""
        from dataclasses import replace

        self._options = replace(self._options, introspect_port=int(port))
        return self

    def watchdog(self, stall_s: float,
                 policy: str = "warn") -> "ReadsStorage":
        """Arm the heartbeat watchdog: flag any shard whose active
        pipeline stage has been silent ``stall_s`` seconds
        (``watchdog.stalled_shards`` / ``watchdog.stall`` telemetry,
        ``/healthz`` degraded). ``policy="abort"`` additionally cancels
        the run with a ``WatchdogStallError``; ``"warn"`` (default)
        keeps going."""
        self._options = self._options.with_watchdog(stall_s, policy)
        return self

    def progress_log(self, path: str) -> "ReadsStorage":
        """Append a periodic JSONL progress line (shards done / in
        flight / total, records, rolling records/sec, ETA) to ``path``
        while pipelines run — replay with
        ``scripts/trace_report.py --progress``."""
        from dataclasses import replace

        self._options = replace(self._options, progress_log=path)
        return self

    def hedged_fetches(self, quantile: float = 0.95,
                       min_s: float = 0.05) -> "ReadsStorage":
        """Arm hedged shard fetches (``runtime/resilience.py``): a
        fetch outliving the rolling ``quantile`` of this run's fetch
        latencies (never less than ``min_s``) races a duplicate —
        first result wins, the loser is cancelled/discarded
        (``hedge.launched`` / ``hedge.won`` / ``hedge.wasted_bytes``
        telemetry). Decoded output is byte-identical either way."""
        self._options = self._options.with_hedging(quantile, min_s)
        return self

    def shard_deadline(self, deadline_s: float) -> "ReadsStorage":
        """Give every shard a wall-clock budget with escalation:
        normal retry while young, forced hedging past half the budget,
        and ``DeadlineExceededError`` once it is spent — which
        skip/quarantine policies convert into one quarantined empty
        shard instead of an aborted run."""
        self._options = self._options.with_shard_deadline(deadline_s)
        return self

    def retry_budget(self, tokens: int,
                     refill_per_success: float = 0.1) -> "ReadsStorage":
        """Install the process-wide retry token bucket: every
        ``ShardRetrier`` retry spends a token, every success refills
        ``refill_per_success`` — a dry bucket denies retries so a
        fault storm cannot stampede the store (``budget.*`` metrics,
        fill level on ``/healthz``)."""
        self._options = self._options.with_retry_budget(
            tokens, refill_per_success)
        return self

    def circuit_breaker(self, window: int,
                        cooldown_s: float = 1.0) -> "ReadsStorage":
        """Arm the per-filesystem circuit breaker: ``window``
        consecutive transient failures open it, calls then fail fast
        with ``BreakerOpenError`` until a successful half-open probe
        after ``cooldown_s`` recloses it (``breaker.*`` metrics, state
        on ``/healthz``)."""
        self._options = self._options.with_breaker(window, cooldown_s)
        return self

    def read_ledger(self, path: str) -> "ReadsStorage":
        """Make reads crash-resumable: each decoded shard is spilled
        under ``path`` as it emits, and a killed process restarted
        with the same ledger re-runs only unfinished shards
        (``runtime/manifest.py:ReadLedger`` — the read-side
        generalization of the write ``StageManifest``)."""
        self._options = self._options.with_read_ledger(path)
        return self

    def postmortem_dir(self, path: str) -> "ReadsStorage":
        """Arm the flight recorder (``runtime/flightrec.py``): recent
        pipeline events (retries, hedges, breaker transitions,
        watchdog stalls, quarantines) are kept in a bounded ring, and
        any abort — first-error-abort, watchdog abort, breaker storm,
        or an explicit ``flightrec.dump()`` — writes a postmortem
        bundle under ``path`` (thread stacks, metrics snapshot, span
        tail, event ring, ledger tails, resolved options) for
        ``scripts/trace_report.py --postmortem``.  Also wires
        ``faulthandler`` into the dir so native crashes leave
        tracebacks.  Env equivalent: ``DISQ_TPU_POSTMORTEM_DIR``."""
        self._options = self._options.with_postmortem(path)
        return self

    def profile_hz(self, hz: float) -> "ReadsStorage":
        """Start the in-process sampling profiler
        (``runtime/profiler.py``) at ``hz``: folded stacks keyed by
        the canonical ``disq-*`` thread names attribute CPU per
        pipeline stage; export via ``/debug/profile``,
        ``profiler.stop_profiler().collapsed()`` or a postmortem
        bundle.  Env equivalent: ``DISQ_TPU_PROFILE_HZ``."""
        self._options = self._options.with_profile(hz)
        return self

    def scheduler(self, mode: str, lease_n: int = 2,
                  lease_s: float = 10.0,
                  steal: bool = True,
                  run_weight: float = 1.0,
                  failover_dir: Optional[str] = None) -> "ReadsStorage":
        """Join this storage's reads to the cross-host shard scheduler
        (``runtime/scheduler.py``): ``mode="serve"`` hosts the shared
        work-queue coordinator on this process's introspection endpoint
        (and works); ``mode="host:port"`` joins that coordinator;
        ``mode="auto"`` discovers the coordinator through
        ``failover_dir``.  Workers lease ``lease_n`` shards at a time
        (locality-routed to the host whose HTTP block cache holds their
        byte range), a lease unfinished after ``lease_s`` seconds is
        re-queued (the crash-handoff latency), and ``steal`` lets an
        idle worker take stale leases from the most-loaded host.
        ``run_weight`` is this run's share in the coordinator's
        weighted max-min lease fairness (contended coordinators only);
        ``failover_dir`` arms coordinator failover — the coordinator
        journals every transition there and, on its death, the lowest
        live member replays the journal and resumes the pass.  Env
        equivalents: ``DISQ_TPU_SCHED`` / ``DISQ_TPU_SCHED_LEASE_N`` /
        ``DISQ_TPU_SCHED_LEASE_S`` / ``DISQ_TPU_SCHED_STEAL`` /
        ``DISQ_TPU_SCHED_WEIGHT`` / ``DISQ_TPU_SCHED_FAILOVER``."""
        self._options = self._options.with_scheduler(
            mode, lease_n, lease_s, steal, run_weight, failover_dir)
        return self

    def http_cache_blocks(self, n: int) -> "ReadsStorage":
        """Size the HTTP block-LRU (``fsw/http.py``; default 32
        blocks): applied to every registered HTTP wrapper when a
        pipeline built from this storage runs, and the default for
        wrappers built later.  Occupancy is served on the
        ``fsw.http.cache.blocks`` gauge — the signal the scheduler's
        locality scorer (and an operator sizing the cache to the
        workload) reads.  Env equivalent:
        ``DISQ_TPU_HTTP_CACHE_BLOCKS``."""
        self._options = self._options.with_http_cache_blocks(n)
        return self

    def resident_decode(self, enable: bool = True) -> "ReadsStorage":
        """Arm the HBM-resident fused decode path
        (``runtime/columnar.py``): each shard's decoded blob is parsed
        into a device-backed ``ColumnarBatch`` in the same launch
        chain as the device codecs (with ``DISQ_TPU_DEVICE_INFLATE``
        the SIMD kernel's still-resident output is parsed in place —
        no re-upload), fixed columns stay in HBM, and d2h happens
        lazily per column (``device.d2h_avoided_bytes`` books what
        never moved). ``flagstat()`` / coordinate sort / interval
        reads consume the resident columns directly. Env equivalent:
        ``DISQ_TPU_RESIDENT_DECODE``."""
        self._options = self._options.with_resident_decode(enable)
        return self

    def mesh(self, devices: int = 0) -> "ReadsStorage":
        """Arm the mesh-native pipeline (``runtime/mesh.py``): resident
        parse batches shard over a ``batch`` device axis with
        ``NamedSharding``, the coordinate sort runs as the multi-chip
        psum-histogram radix sort, and flagstat/depth reduce with
        ``lax.psum`` — one sharded program across all chips instead of
        N single-device lanes.  ``devices=0`` uses all local devices,
        ``n`` the first n (power-of-two floor).  A host resolved to one
        device keeps the identical single-device dispatch.  Env
        equivalent: ``DISQ_TPU_MESH``."""
        self._options = self._options.with_mesh(devices)
        return self

    def read_filter(self, spec: str) -> "ReadsStorage":
        """Push a ``samtools view``-style predicate + subsample into
        the decode itself (``ops/rfilter.py``): ``"-f INT"`` require
        flag bits, ``"-F INT"`` exclude flag bits, ``"-q INT"``
        minimum MAPQ, ``"-s SEED.FRAC"`` keep FRAC of read names
        (hash-seeded — mates travel together). On the resident path
        the mask builds on device from the HBM flag/mapq columns and
        each shard compacts BEFORE any d2h or host record parse; the
        host path applies the bit-identical numpy mask. The spec is
        validated here, eagerly. Env equivalent:
        ``DISQ_TPU_READ_FILTER``."""
        self._options = self._options.with_read_filter(spec)
        return self

    def num_shards(self, n: int) -> "ReadsStorage":
        """Device-shard count override (defaults to local device count)."""
        self._num_shards = n
        return self

    def validation_stringency(self, s: ValidationStringency) -> "ReadsStorage":
        self._stringency = s
        return self

    def reference_source_path(self, p: str) -> "ReadsStorage":
        self._reference_source_path = p
        return self

    # -- read ---------------------------------------------------------------

    def read(
        self, path: str, traversal: Optional[TraversalParameters] = None
    ) -> ReadsDataset:
        from disq_tpu.formats import sam_format_from_path
        from disq_tpu.runtime import flightrec

        fmt = sam_format_from_path(path)
        source = fmt.make_source(self)
        try:
            return source.get_reads(path, traversal)
        except Exception as e:
            # Postmortem backstop for aborts that never reach the
            # executor (driver-side split planning, header decode) —
            # the flight recorder dedupes errors the pipeline's own
            # abort path already bundled.
            flightrec.note_abort(e, where="read")
            raise

    # -- write --------------------------------------------------------------

    def write(
        self,
        dataset: ReadsDataset,
        path: str,
        *options: WriteOption,
        sort: bool = False,
    ) -> None:
        from disq_tpu.formats import sam_format_from_write_options

        from disq_tpu.runtime import flightrec

        if sort:
            dataset = dataset.coordinate_sorted()
        fmt_opt = _opt(options, ReadsFormatWriteOption, None)
        fmt = sam_format_from_write_options(path, fmt_opt)
        cardinality = _opt(options, FileCardinalityWriteOption, _infer_cardinality(path))
        sink = fmt.make_sink(self, cardinality)
        try:
            sink.save(dataset, path, options)
        except Exception as e:
            flightrec.note_abort(e, where="write")
            raise


class VariantsStorage:
    """Entry point for variants (ref: ``HtsjdkVariantsRddStorage``)."""

    def __init__(self) -> None:
        self._split_size: int = 128 * 1024 * 1024
        self._num_shards: Optional[int] = None
        self._options = DisqOptions()

    @classmethod
    def make_default(cls) -> "VariantsStorage":
        return cls()

    def split_size(self, n: int) -> "VariantsStorage":
        self._split_size = n
        return self

    def error_policy(self, policy: "ErrorPolicy | str") -> "VariantsStorage":
        self._options = self._options.with_policy(policy)
        return self

    def options(self, opts: DisqOptions) -> "VariantsStorage":
        self._options = opts
        return self

    def executor_workers(self, n: int,
                         prefetch_shards: Optional[int] = None
                         ) -> "VariantsStorage":
        """Shard-pipeline executor sizing for variant reads (VCF text,
        BGZF-split VCF, BCF block inflate) — see
        ``ReadsStorage.executor_workers``."""
        self._options = self._options.with_executor(n, prefetch_shards)
        return self

    def writer_workers(self, n: int,
                       prefetch_shards: Optional[int] = None
                       ) -> "VariantsStorage":
        """Shard write-pipeline sizing for variant writes (VCF plain /
        gzip / BGZF, BCF) — see ``ReadsStorage.writer_workers``."""
        self._options = self._options.with_writer(n, prefetch_shards)
        return self

    def span_log(self, path: str) -> "VariantsStorage":
        """See ``ReadsStorage.span_log``."""
        from dataclasses import replace

        self._options = replace(self._options, span_log=path)
        return self

    def introspect_port(self, port: int) -> "VariantsStorage":
        """See ``ReadsStorage.introspect_port``."""
        from dataclasses import replace

        self._options = replace(self._options, introspect_port=int(port))
        return self

    def watchdog(self, stall_s: float,
                 policy: str = "warn") -> "VariantsStorage":
        """See ``ReadsStorage.watchdog``."""
        self._options = self._options.with_watchdog(stall_s, policy)
        return self

    def progress_log(self, path: str) -> "VariantsStorage":
        """See ``ReadsStorage.progress_log``."""
        from dataclasses import replace

        self._options = replace(self._options, progress_log=path)
        return self

    def hedged_fetches(self, quantile: float = 0.95,
                       min_s: float = 0.05) -> "VariantsStorage":
        """See ``ReadsStorage.hedged_fetches``."""
        self._options = self._options.with_hedging(quantile, min_s)
        return self

    def shard_deadline(self, deadline_s: float) -> "VariantsStorage":
        """See ``ReadsStorage.shard_deadline``."""
        self._options = self._options.with_shard_deadline(deadline_s)
        return self

    def retry_budget(self, tokens: int,
                     refill_per_success: float = 0.1
                     ) -> "VariantsStorage":
        """See ``ReadsStorage.retry_budget``."""
        self._options = self._options.with_retry_budget(
            tokens, refill_per_success)
        return self

    def circuit_breaker(self, window: int,
                        cooldown_s: float = 1.0) -> "VariantsStorage":
        """See ``ReadsStorage.circuit_breaker``."""
        self._options = self._options.with_breaker(window, cooldown_s)
        return self

    def read_ledger(self, path: str) -> "VariantsStorage":
        """See ``ReadsStorage.read_ledger``."""
        self._options = self._options.with_read_ledger(path)
        return self

    def postmortem_dir(self, path: str) -> "VariantsStorage":
        """See ``ReadsStorage.postmortem_dir``."""
        self._options = self._options.with_postmortem(path)
        return self

    def profile_hz(self, hz: float) -> "VariantsStorage":
        """See ``ReadsStorage.profile_hz``."""
        self._options = self._options.with_profile(hz)
        return self

    def scheduler(self, mode: str, lease_n: int = 2,
                  lease_s: float = 10.0,
                  steal: bool = True,
                  run_weight: float = 1.0,
                  failover_dir: Optional[str] = None
                  ) -> "VariantsStorage":
        """See ``ReadsStorage.scheduler``.  VCF reads lease their
        splits from the shared queue; BCF keeps the static whole-file
        path (its single BGZF stream cannot be partitioned across
        hosts) exactly as it keeps strict deadline semantics."""
        self._options = self._options.with_scheduler(
            mode, lease_n, lease_s, steal, run_weight, failover_dir)
        return self

    def http_cache_blocks(self, n: int) -> "VariantsStorage":
        """See ``ReadsStorage.http_cache_blocks``."""
        self._options = self._options.with_http_cache_blocks(n)
        return self

    def resident_decode(self, enable: bool = True) -> "VariantsStorage":
        """See ``ReadsStorage.resident_decode``. Today only the BAM
        read path builds resident batches; the knob is accepted here so
        option sets stay interchangeable across storages (the variant
        columnar currency is ROADMAP item 4's port)."""
        self._options = self._options.with_resident_decode(enable)
        return self

    def mesh(self, devices: int = 0) -> "VariantsStorage":
        """See ``ReadsStorage.mesh``.  Today only the BAM resident
        chain shards over the batch axis; the knob is accepted here so
        option sets stay interchangeable across storages."""
        self._options = self._options.with_mesh(devices)
        return self

    def num_shards(self, n: int) -> "VariantsStorage":
        self._num_shards = n
        return self

    def read(
        self, path: str, intervals: Optional[Sequence[Interval]] = None
    ) -> VariantsDataset:
        from disq_tpu.runtime import flightrec

        try:
            if path.lower().endswith(".bcf"):
                from disq_tpu.vcf.bcf import BcfSource

                return BcfSource(self).get_variants(path, intervals)
            from disq_tpu.vcf.source import VcfSource

            return VcfSource(self).get_variants(path, intervals)
        except Exception as e:
            flightrec.note_abort(e, where="read")
            raise

    def write(
        self, dataset: VariantsDataset, path: str, *options: WriteOption
    ) -> None:
        from disq_tpu.runtime import flightrec
        from disq_tpu.vcf.sink import VcfSink, VcfSinkMultiple

        fmt_opt = _opt(options, VariantsFormatWriteOption, None)
        cardinality = _opt(options, FileCardinalityWriteOption, _infer_cardinality(path))
        try:
            if fmt_opt is VariantsFormatWriteOption.BCF or (
                fmt_opt is None and path.lower().endswith(".bcf")
            ):
                from disq_tpu.vcf.bcf import BcfSink, BcfSinkMultiple

                if cardinality is FileCardinalityWriteOption.SINGLE:
                    BcfSink(self).save(dataset, path, options)
                else:
                    BcfSinkMultiple(self).save(dataset, path, options)
                return
            if cardinality is FileCardinalityWriteOption.SINGLE:
                VcfSink(self).save(dataset, path, options)
            else:
                VcfSinkMultiple(self).save(dataset, path, options)
        except Exception as e:
            flightrec.note_abort(e, where="write")
            raise


class ServeHandle:
    """Handle on the serving plane started by :func:`serve`.

    ``address`` is the ``host:port`` of the HTTP plane now answering
    ``POST /query/reads``, ``POST /query/variants``,
    ``POST /query/stats``, the operator-suite queries
    ``POST /query/markdup-stats`` / ``POST /query/pileup`` /
    ``POST /query/filtered-count``, ``POST /serve/register`` and
    ``GET /serve/stats`` alongside the existing introspection
    endpoints. ``close()`` tears the daemon down (and the HTTP server,
    when :func:`serve` started it)."""

    def __init__(self, address: str, daemon, owns_server: bool) -> None:
        self.address = address
        self.daemon = daemon
        self._owns_server = owns_server

    def register(self, name: str, path: str, kind: str = None) -> dict:
        """Register a dataset by path; ``kind`` is sniffed from the
        extension when omitted ('reads' | 'variants')."""
        return self.daemon.register(name, path, kind)

    def stats(self) -> dict:
        return self.daemon.stats()

    def close(self) -> None:
        from disq_tpu.runtime import serve as serve_mod
        from disq_tpu.runtime.introspect import stop_introspect_server

        serve_mod.stop_serve()
        if self._owns_server:
            stop_introspect_server()

    def __enter__(self) -> "ServeHandle":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()


def serve(datasets: dict = None, *, port: int = 0, options=None,
          tenant_slots: int = None, tenant_queue: int = None,
          compressed_cache_mb: int = None,
          decoded_cache_mb: int = None,
          parsed_cache_mb: int = None) -> ServeHandle:
    """Start the long-lived multi-tenant interval-query daemon
    (``runtime/serve.py``) and return a :class:`ServeHandle`.

    ``datasets`` maps name -> path to register up front; more can be
    added later via ``handle.register`` or ``POST /serve/register``.
    Queries are answered over the introspection HTTP plane with
    cross-request device batching, a shared hot cache (compressed
    blocks, decoded payloads, parsed chunk batches), and per-tenant
    admission control (``tenant_slots`` concurrent requests per tenant
    plus a ``tenant_queue``-deep wait queue; beyond that a tenant's
    requests are shed with 429)."""
    from disq_tpu.runtime import serve as serve_mod
    from disq_tpu.runtime.introspect import introspect_address

    kwargs = {"options": options}
    if tenant_slots is not None:
        kwargs["tenant_slots"] = tenant_slots
    if tenant_queue is not None:
        kwargs["tenant_queue"] = tenant_queue
    if compressed_cache_mb is not None:
        kwargs["compressed_cache_mb"] = compressed_cache_mb
    if decoded_cache_mb is not None:
        kwargs["decoded_cache_mb"] = decoded_cache_mb
    if parsed_cache_mb is not None:
        kwargs["parsed_cache_mb"] = parsed_cache_mb
    owns_server = introspect_address() is None
    address = serve_mod.start_serve(port, **kwargs)
    handle = ServeHandle(address, serve_mod.serve_if_running(),
                         owns_server)
    for name, path in (datasets or {}).items():
        handle.register(name, path)
    return handle


class FleetHandle:
    """Handle on the fleet routing tier started by :func:`serve_fleet`.

    ``address`` is the ``host:port`` of the HTTP plane now answering
    ``POST /fleet/query/reads|variants|stats``, ``POST /fleet/register``
    and ``GET /fleet/stats``. ``close()`` tears the router down (and
    the HTTP server, when :func:`serve_fleet` started it)."""

    def __init__(self, address: str, router, owns_server: bool) -> None:
        self.address = address
        self.router = router
        self._owns_server = owns_server

    def register(self, name: str, path: str, kind: str = None) -> dict:
        """Fan a dataset registration out to every live replica (each
        bumps the dataset epoch and drops stale cache entries)."""
        status, doc = self.router.register(name, path, kind)
        if status != 200:
            raise RuntimeError(doc.get("error", f"HTTP {status}"))
        return doc

    def query(self, endpoint: str, doc: dict) -> tuple:
        """Route one query (``endpoint`` in 'reads' | 'variants' |
        'stats') -> ``(status, body)``."""
        return self.router.query(f"/query/{endpoint}", doc)

    def stats(self) -> dict:
        return self.router.stats()

    def close(self) -> None:
        from disq_tpu.runtime import fleet as fleet_mod
        from disq_tpu.runtime.introspect import stop_introspect_server

        fleet_mod.stop_fleet()
        if self._owns_server:
            stop_introspect_server()

    def __enter__(self) -> "FleetHandle":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()


_FLEET_UNSET = object()  # None is meaningful (= hedging off)


def serve_fleet(replicas, *, port: int = 0, datasets: dict = None,
                policy: str = "locality",
                hedge_quantile: float = _FLEET_UNSET,
                hedge_min_s: float = None,
                tenant_slots: int = None, tenant_queue: int = None,
                refresh_s: float = None,
                probe_s: float = None) -> FleetHandle:
    """Start the fleet routing tier (``runtime/fleet.py``) over
    ``replicas`` (a list of ``host:port`` serving endpoints) and
    return a :class:`FleetHandle`.

    Queries sent to ``/fleet/query/*`` are forwarded to the replica
    whose hot-block cache already holds the query's blocks (digest
    overlap scoring off each replica's ``/serve/cachemap``), hedged to
    the runner-up past the rolling latency quantile
    (``hedge_quantile``; None disables hedging), and admitted against
    the fleet-wide aggregate of per-replica tenant capacity."""
    from disq_tpu.runtime import fleet as fleet_mod
    from disq_tpu.runtime.introspect import introspect_address

    kwargs = {"policy": policy}
    if hedge_quantile is not _FLEET_UNSET:
        kwargs["hedge_quantile"] = hedge_quantile
    if hedge_min_s is not None:
        kwargs["hedge_min_s"] = hedge_min_s
    if tenant_slots is not None:
        kwargs["tenant_slots"] = tenant_slots
    if tenant_queue is not None:
        kwargs["tenant_queue"] = tenant_queue
    if refresh_s is not None:
        kwargs["refresh_s"] = refresh_s
    if probe_s is not None:
        kwargs["probe_s"] = probe_s
    owns_server = introspect_address() is None
    address = fleet_mod.start_fleet(list(replicas), port, **kwargs)
    handle = FleetHandle(address, fleet_mod.fleet_if_running(),
                         owns_server)
    for name, path in (datasets or {}).items():
        handle.register(name, path)
    return handle
