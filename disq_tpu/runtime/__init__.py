"""Runtime auxiliary subsystems (SURVEY.md §5).

The reference delegates failure handling to Spark (task retry, lineage
re-execution) and contributes only the idempotent temp-dir write
protocol. JAX has no task retry, so the equivalents here are:

- ``manifest`` — a deterministic, restartable *stage manifest* on disk:
  which shard ranges have been decoded/sorted/written, with shard-level
  re-execution on restart and the same temp-dir commit protocol; plus
  the ``QuarantineManifest`` sidecar ledger for corrupt blocks.
- ``errors`` — the read-path error policy: ``ShardRetrier`` (bounded
  backoff retry of transient faults), ``ErrorPolicy``
  (strict/skip/quarantine dispatch of corrupt blocks), and
  ``CorruptBlockError`` with full (path, shard, block, voffset)
  coordinates.
- ``executor`` — the shard-pipeline executor: a bounded three-stage
  fetch → decode → ordered-emit pipeline shared by every format
  source, overlapping range-reads, inflate and record decode across
  splits (``DisqOptions.executor_workers`` / ``prefetch_shards``);
  plus its write-direction twin ``ShardWritePipeline`` (encode →
  deflate → stage, ``DisqOptions.writer_workers``) shared by every
  format sink.
- ``counters`` — per-shard counters (records, blocks, bytes,
  compression ratio) returned per shard and reduced.
- ``tracing`` — the structured telemetry layer: a labeled
  ``MetricsRegistry`` (counters / gauges / histograms, Prometheus
  ``metrics_text()``), per-shard ``span`` timelines with a bounded
  ring + JSONL sink (``DISQ_TPU_TRACE_JSONL``, Chrome/Perfetto
  export), and the ``jax.profiler`` bridge (``trace_phase``,
  ``DISQ_TPU_TRACE_DIR``).
- ``resilience`` — adaptive, closed-loop fault handling layered on
  ``errors``/``executor``: hedged shard fetches from a rolling latency
  quantile (``DisqOptions.hedge_quantile``), per-shard deadlines with
  a retry → hedge → quarantine escalation ladder
  (``shard_deadline_s``), a process-wide retry token bucket
  (``retry_budget_tokens``) and per-filesystem circuit breakers
  (``breaker_window``) that fail fast during fault storms — all free
  when disabled.
- ``introspect`` — the live half of observability: an opt-in
  in-process HTTP endpoint (``/metrics`` / ``/healthz`` /
  ``/progress`` / ``/spans``; ``DisqOptions.introspect_port`` /
  ``DISQ_TPU_INTROSPECT_PORT``), a heartbeat watchdog flagging shards
  whose active pipeline stage went silent past
  ``DisqOptions.watchdog_stall_s`` (policy ``warn`` | ``abort``), and
  a progress/ETA reporter with an optional periodic JSONL log
  (``DisqOptions.progress_log``).
- ``flightrec`` — the postmortem half of observability: a bounded
  event ring of recent decisions (retries, hedges, breaker
  transitions, watchdog stalls, quarantines) and, on any abort path,
  a postmortem bundle directory (thread stacks, metrics snapshot,
  span tail, event ring, ledger tails, resolved options;
  ``DisqOptions.postmortem_dir`` / ``DISQ_TPU_POSTMORTEM_DIR``) that
  ``scripts/trace_report.py --postmortem`` renders; plus
  ``faulthandler`` wiring for native crashes.
- ``profiler`` — the in-process sampling profiler: folded stacks
  keyed by the canonical ``disq-*`` thread names attribute CPU per
  pipeline stage, exported as collapsed-stack / speedscope
  (``DisqOptions.profile_hz`` / ``DISQ_TPU_PROFILE_HZ``, the
  ``/debug/profile`` endpoint, ``trace_report.py --flame``).
- ``cluster`` — the cross-host half of observability: a
  ``ClusterAggregator`` scraping N processes' introspection endpoints
  and serving a merged ``/metrics`` / ``/progress`` / ``/healthz``
  rollup with per-process labels, plus fleet-wide ``/debug/stacks`` /
  ``/debug/profile`` collection (CLI:
  ``scripts/metrics_aggregate.py``).
- ``multihost`` — multi-process jax scaffold: axis planning, the
  global (dcn, shards) mesh, and the ``process_id()`` identity every
  introspection endpoint labels its output with.
- ``debug`` — a debug mode (``DISQ_TPU_DEBUG=1``) asserting
  shard-boundary invariants (record counts, offset monotonicity)
  after each phase.
- ``device_service`` — the cross-shard device decode service
  (``DISQ_TPU_DEVICE_SERVICE=1``): one dispatcher owning the device
  queue, coalescing concurrently-decoding shards' BGZF/rANS blocks
  into full 128-lane SIMD launches with per-shard error isolation
  and zero-copy array-native unpack; nothing exists when disabled.
"""

from disq_tpu.runtime.counters import (  # noqa: F401
    PipelineCounters,
    ShardCounters,
    reduce_counters,
)
from disq_tpu.runtime.errors import (  # noqa: F401
    BreakerOpenError,
    CoordinatorLostError,
    CorruptBlockError,
    DeadlineExceededError,
    DisqOptions,
    ErrorPolicy,
    ShardErrorContext,
    ShardRetrier,
    TransientIOError,
    TruncatedReadError,
    WatchdogStallError,
    context_for_storage,
    is_transient,
)
from disq_tpu.runtime.executor import (  # noqa: F401
    ExecutorStats,
    ShardPipelineExecutor,
    ShardResult,
    ShardTask,
    ShardWritePipeline,
    WriteShardResult,
    WriteShardTask,
    WriterStats,
    executor_for_storage,
    map_ordered_resumable,
    read_ledger_for_storage,
    run_write_stage,
    write_retrier_for_storage,
    writer_for_storage,
)
from disq_tpu.runtime.resilience import (  # noqa: F401
    CircuitBreaker,
    HedgeController,
    ResilienceManager,
    RetryBudget,
    ShardDeadline,
    resilience_for_options,
    reset_resilience,
)
from disq_tpu.runtime.cluster import (  # noqa: F401
    ClusterAggregator,
    parse_metrics_text,
)
from disq_tpu.runtime.multihost import (  # noqa: F401
    process_count,
    process_id,
)
from disq_tpu.runtime.scheduler import (  # noqa: F401
    SchedulerClient,
    ShardCoordinator,
    client_for_storage,
    scheduled_map_ordered,
    serve_coordinator,
)
from disq_tpu.runtime.introspect import (  # noqa: F401
    HEALTH,
    PipelineHealth,
    introspect_address,
    note_shard_counters,
    start_introspect_server,
    start_progress_log,
    stop_introspect_server,
    stop_progress_log,
)
from disq_tpu.runtime.flightrec import (  # noqa: F401
    FlightRecorder,
    record_event,
    reset_flightrec,
    thread_stacks_text,
)
from disq_tpu.runtime.profiler import (  # noqa: F401
    SamplingProfiler,
    active_profiler,
    profile_for,
    reset_profiler,
    start_profiler,
    stop_profiler,
)
from disq_tpu.runtime.columnar import (  # noqa: F401
    ColumnarBatch,
    as_read_batch,
    concat_batches,
    resident_decode_enabled,
)
from disq_tpu.runtime.manifest import (  # noqa: F401
    QuarantineManifest,
    ReadLedger,
    StageManifest,
)
from disq_tpu.runtime.tracing import (  # noqa: F401
    REGISTRY,
    MetricsRegistry,
    count_transfer,
    counter,
    device_span,
    gauge,
    hbm_resident,
    synced_timer,
    track_hbm,
    gauge_report,
    histogram,
    metrics_text,
    observe_gauge,
    phase_report,
    record_span,
    reset_telemetry,
    span,
    spans,
    start_span_log,
    stop_span_log,
    telemetry_snapshot,
    trace_phase,
    wrap_span,
)
from disq_tpu.runtime.debug import (  # noqa: F401
    debug_enabled,
    check_read_batch,
    check_voffsets,
)
