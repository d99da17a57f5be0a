"""Shard-level error policy — retry / skip / quarantine (SURVEY.md §5).

The reference inherits fault tolerance from Spark: a task that dies on a
flaky range-read is simply re-executed, and a corrupt input kills the
job with a stack trace pointing at nothing. disq_tpu replaces both with
explicit, observable machinery:

- **Transient faults** (network blips, stalled connections, truncated
  range reads) are retried per shard with bounded exponential backoff
  (``ShardRetrier`` — the Spark-task-retry analogue). Every retry is
  counted (``ShardCounters.retried_reads`` plus the labeled
  ``retry.attempts`` telemetry counter) and its backoff sleep traced
  as a ``retry.backoff`` span labeled with what was being retried.
- **Corrupt data** (failed CRC, bad DEFLATE bits, impossible record
  framing) is *not* retried — re-reading corrupt bytes yields the same
  corrupt bytes. It is governed by an ``ErrorPolicy``:

  - ``STRICT`` (default): raise ``CorruptBlockError`` carrying the full
    coordinates (path, shard, compressed block offset, virtual offset).
  - ``SKIP``: drop the corrupt block, count it
    (``ShardCounters.skipped_blocks``), decode everything else.
  - ``QUARANTINE``: as SKIP, but additionally copy the corrupt
    compressed bytes to a sidecar file recorded in a
    ``QuarantineManifest`` (``runtime/manifest.py``) for offline
    forensics / re-processing.

The classification boundary is ``is_transient``: OSError-family errors
(minus the definitive ones like ``FileNotFoundError``) and truncated
reads are transient; ``ValueError``-family codec errors are corrupt.
"""

from __future__ import annotations

import enum
import random
import threading
import time
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Optional, TypeVar

from disq_tpu.runtime import flightrec

T = TypeVar("T")


class ErrorPolicy(enum.Enum):
    """What to do with a shard's corrupt (non-transient) block."""

    STRICT = "strict"
    SKIP = "skip"
    QUARANTINE = "quarantine"

    @classmethod
    def coerce(cls, value: "ErrorPolicy | str") -> "ErrorPolicy":
        if isinstance(value, cls):
            return value
        try:
            return cls(str(value).lower())
        except ValueError:
            raise ValueError(
                f"unknown error policy {value!r}; expected one of "
                f"{[p.value for p in cls]}"
            ) from None


@dataclass(frozen=True)
class DisqOptions:
    """Read-path runtime knobs, attached to the storage builders
    (``ReadsStorage.error_policy(...)`` / ``VariantsStorage``).

    ``quarantine_dir`` defaults to ``<input path> + ".quarantine"`` on
    the local filesystem; remote (read-only) inputs must set it
    explicitly.

    ``executor_workers`` / ``prefetch_shards`` size the shard-pipeline
    executor (``runtime/executor.py``): 1 worker (the default) is the
    sequential-compatible inline path; N>1 overlaps range-reads,
    inflate and record decode across splits with at most
    ``prefetch_shards`` splits in flight past the emit frontier
    (None ⇒ ``2 × executor_workers``).

    ``writer_workers`` / ``writer_prefetch_shards`` are the write-side
    mirror: they size the ``ShardWritePipeline`` every sink runs its
    shards through, overlapping record encode, BGZF deflate and part
    staging across shards. Output is byte-identical at any width; 1
    (the default) is the inline sequential path.

    ``span_log`` points the *process-wide* JSONL span sink at the
    given path when a read through this storage starts (per-shard
    fetch/decode, retries, quarantine writes — the file
    ``scripts/trace_report.py`` replays).  Equivalent to setting
    ``DISQ_TPU_TRACE_JSONL`` at read time: there is one sink per
    process, so the storage that most recently started a read wins,
    and the sink keeps collecting until ``stop_span_log()`` (each
    run's spans carry its ``run_id``, so appended runs stay
    separable).

    Live introspection (``runtime/introspect.py``):

    - ``introspect_port`` starts the process-wide 127.0.0.1 HTTP
      endpoint (``/metrics`` / ``/healthz`` / ``/progress`` /
      ``/spans``) the first time a pipeline built from these options
      runs; 0 binds an ephemeral port (also: env
      ``DISQ_TPU_INTROSPECT_PORT``). None (the default) never creates
      a thread or socket.
    - ``watchdog_stall_s`` arms the heartbeat watchdog: any shard
      whose active pipeline stage has been silent that many seconds is
      flagged (``watchdog.stalled_shards`` counter, ``watchdog.stall``
      span, one rate-limited stderr line, ``/healthz`` degraded).
      ``watchdog_policy`` decides what happens next: ``"warn"`` (the
      default) keeps running; ``"abort"`` cancels the run through the
      pipeline's first-error-abort path with a ``WatchdogStallError``.
    - ``progress_log`` appends a periodic JSONL progress line
      (shards done / in flight / total, records, rolling records/sec,
      ETA) that ``scripts/trace_report.py --progress`` replays.

    Adaptive resilience (``runtime/resilience.py`` — every knob None
    or default keeps the zero-overhead seed behavior):

    - ``hedge_quantile`` arms hedged fetches: a shard fetch outliving
      that rolling quantile of this run's fetch latencies (never less
      than ``hedge_min_s``) races a duplicate, first result wins.
    - ``shard_deadline_s`` gives each shard a wall-clock budget with
      an escalation ladder: retry while young → forced hedge past half
      the budget → ``DeadlineExceededError`` (quarantined under
      skip/quarantine policy) once it is gone.
    - ``retry_budget_tokens`` installs the process-wide retry token
      bucket every ``ShardRetrier`` consults (a retry spends a token,
      a success refills ``retry_budget_refill``); an empty bucket
      denies retries so a fault storm cannot stampede the store.
    - ``breaker_window`` arms the per-filesystem circuit breaker:
      that many consecutive transient failures open it, calls then
      fail fast with ``BreakerOpenError`` until a successful probe
      after ``breaker_cooldown_s`` recloses it.
    - ``read_ledger`` points the crash-resumable *read* ledger at a
      directory: each decoded shard is spilled there as it emits, and
      a killed process re-runs only unfinished shards on restart.

    Postmortem & profiling (``runtime/flightrec.py`` /
    ``runtime/profiler.py`` — both off by default, zero threads and
    zero per-shard work until armed):

    - ``postmortem_dir`` turns the flight recorder on: recent events
      (retries, hedges, breaker transitions, watchdog stalls,
      quarantines) are kept in a bounded ring, and any abort path —
      pipeline first-error-abort, watchdog abort, breaker storm, or an
      explicit ``flightrec.dump()`` — writes a postmortem bundle
      directory (thread stacks, metrics snapshot, span tail, event
      ring, healthz/progress, ledger tails, resolved options) that
      ``scripts/trace_report.py --postmortem`` renders.  Also wires
      ``faulthandler`` into the dir for native crashes.  Env
      equivalent: ``DISQ_TPU_POSTMORTEM_DIR``.
    - ``profile_hz`` starts the in-process sampling profiler at that
      rate: folded stacks keyed by the canonical ``disq-*`` thread
      names attribute CPU per pipeline stage, exported as
      collapsed-stack / speedscope (``profile.samples{thread_role=}``
      / ``profile.dropped``).  Env equivalent:
      ``DISQ_TPU_PROFILE_HZ``.
    """

    error_policy: ErrorPolicy = ErrorPolicy.STRICT
    max_retries: int = 3
    retry_backoff_s: float = 0.05
    quarantine_dir: Optional[str] = None
    executor_workers: int = 1
    prefetch_shards: Optional[int] = None
    writer_workers: int = 1
    writer_prefetch_shards: Optional[int] = None
    span_log: Optional[str] = None
    introspect_port: Optional[int] = None
    watchdog_stall_s: Optional[float] = None
    watchdog_policy: str = "warn"
    progress_log: Optional[str] = None
    hedge_quantile: Optional[float] = None
    hedge_min_s: float = 0.05
    shard_deadline_s: Optional[float] = None
    retry_budget_tokens: Optional[int] = None
    retry_budget_refill: float = 0.1
    breaker_window: Optional[int] = None
    breaker_cooldown_s: float = 1.0
    read_ledger: Optional[str] = None
    postmortem_dir: Optional[str] = None
    profile_hz: Optional[float] = None
    # HBM-resident fused decode (runtime/columnar.py): sources parse
    # each shard's decoded blob into a device-backed ColumnarBatch in
    # the same launch chain as the device codecs — fixed columns stay
    # resident, d2h happens lazily per column. Env equivalent:
    # DISQ_TPU_RESIDENT_DECODE. Off (default) ⇒ plain host ReadBatch
    # and zero device allocations (check_overhead-guarded).
    resident_decode: bool = False
    # Mesh-native device pipeline (runtime/mesh.py): None (default)
    # keeps every device stage on the single-device dispatch and
    # builds no Mesh object (check_overhead-guarded); 0 shards the
    # resident parse/sort/reduce chain over ALL local devices on a
    # batch axis; n >= 1 uses the first n devices (rounded down to a
    # power of two; 1 ⇒ the off path). Env equivalent: DISQ_TPU_MESH
    # (unset/0/off ⇒ off, all/auto ⇒ all devices, integer ⇒ first n).
    mesh: Optional[int] = None
    # Cross-host shard scheduler (runtime/scheduler.py): None (default)
    # keeps the static split loops with zero coordinator threads or
    # sockets; "serve" hosts the coordinator on this process's
    # introspection endpoint and works; "host:port" joins that
    # coordinator as a worker. sched_lease_n shards per lease round,
    # sched_lease_s lease expiry (crash-detection latency), sched_steal
    # arms idle-worker stealing. Env equivalents: DISQ_TPU_SCHED,
    # DISQ_TPU_SCHED_LEASE_N/_LEASE_S/_STEAL (env wins for the tuning
    # knobs so subprocess workers inherit their launcher's settings).
    # sched_run_weight is this run's share weight in the coordinator's
    # weighted max-min lease quota (multi-run fairness — an interactive
    # run outweighing a batch pass cannot be starved by it); env
    # DISQ_TPU_SCHED_WEIGHT. sched_failover_dir arms coordinator
    # failover: the coordinator journals every state transition to
    # <dir>/journal.jsonl and advertises its address in
    # <dir>/coordinator.addr, workers register member files there, and
    # on coordinator death the lowest live process id replays the
    # journal and resumes the pass; env DISQ_TPU_SCHED_FAILOVER. None
    # (default) keeps PR 12's guarantee: no journal file, no standby,
    # no extra state (check_overhead-guarded).
    scheduler: Optional[str] = None
    sched_lease_n: int = 2
    sched_lease_s: float = 10.0
    sched_steal: bool = True
    sched_run_weight: float = 1.0
    sched_failover_dir: Optional[str] = None
    # HTTP block-LRU capacity (fsw/http.py) — None keeps the built-in
    # default (32 blocks, or DISQ_TPU_HTTP_CACHE_BLOCKS); the locality
    # scorer reads occupancy off the fsw.http.cache.blocks gauge.
    http_cache_blocks: Optional[int] = None
    # Per-tenant SLO spec (runtime/slo.py): comma-separated
    # "tenant:latency_ms:target_pct[:availability_pct]" clauses ("*" =
    # wildcard tenant). Arms the multi-window burn-rate evaluator whose
    # snapshot /slo serves and /healthz merges (fast burn ⇒ degraded).
    # Env equivalent: DISQ_TPU_SLO. None (default) starts no evaluator
    # thread and touches nothing (check_overhead-guarded).
    slo: Optional[str] = None
    # Resident read filter (ops/rfilter.py): a ``samtools view``-style
    # spec ("-f INT -F INT -q INT -s SEED.FRAC") pushed into the
    # decode — the mask builds on device from the resident flag/mapq
    # columns and compacts each shard BEFORE any d2h or host record
    # parse. Env equivalent: DISQ_TPU_READ_FILTER. None (default)
    # builds no mask and imports no operator module
    # (check_overhead-guarded).
    read_filter: Optional[str] = None

    def with_policy(self, policy: "ErrorPolicy | str") -> "DisqOptions":
        return replace(self, error_policy=ErrorPolicy.coerce(policy))

    def with_executor(self, workers: int,
                      prefetch_shards: Optional[int] = None) -> "DisqOptions":
        if workers < 1:
            raise ValueError(f"executor_workers must be >= 1, got {workers}")
        return replace(self, executor_workers=int(workers),
                       prefetch_shards=prefetch_shards)

    def with_writer(self, workers: int,
                    prefetch_shards: Optional[int] = None) -> "DisqOptions":
        if workers < 1:
            raise ValueError(f"writer_workers must be >= 1, got {workers}")
        return replace(self, writer_workers=int(workers),
                       writer_prefetch_shards=prefetch_shards)

    def with_watchdog(self, stall_s: float,
                      policy: str = "warn") -> "DisqOptions":
        if stall_s <= 0:
            raise ValueError(
                f"watchdog_stall_s must be > 0, got {stall_s}")
        if policy not in ("warn", "abort"):
            raise ValueError(
                f"watchdog_policy must be 'warn' or 'abort', got {policy!r}")
        return replace(self, watchdog_stall_s=float(stall_s),
                       watchdog_policy=policy)

    def with_hedging(self, quantile: float,
                     min_s: float = 0.05) -> "DisqOptions":
        if not 0.0 < quantile < 1.0:
            raise ValueError(
                f"hedge_quantile must be in (0, 1), got {quantile}")
        if min_s < 0:
            raise ValueError(f"hedge_min_s must be >= 0, got {min_s}")
        return replace(self, hedge_quantile=float(quantile),
                       hedge_min_s=float(min_s))

    def with_shard_deadline(self, deadline_s: float) -> "DisqOptions":
        if deadline_s <= 0:
            raise ValueError(
                f"shard_deadline_s must be > 0, got {deadline_s}")
        return replace(self, shard_deadline_s=float(deadline_s))

    def with_retry_budget(self, tokens: int,
                          refill_per_success: float = 0.1) -> "DisqOptions":
        if tokens < 1:
            raise ValueError(
                f"retry_budget_tokens must be >= 1, got {tokens}")
        return replace(self, retry_budget_tokens=int(tokens),
                       retry_budget_refill=float(refill_per_success))

    def with_breaker(self, window: int,
                     cooldown_s: float = 1.0) -> "DisqOptions":
        if window < 1:
            raise ValueError(f"breaker_window must be >= 1, got {window}")
        if cooldown_s <= 0:
            raise ValueError(
                f"breaker_cooldown_s must be > 0, got {cooldown_s}")
        return replace(self, breaker_window=int(window),
                       breaker_cooldown_s=float(cooldown_s))

    def with_read_ledger(self, path: str) -> "DisqOptions":
        return replace(self, read_ledger=path)

    def with_postmortem(self, path: str) -> "DisqOptions":
        if not path:
            raise ValueError("postmortem_dir must be a non-empty path")
        return replace(self, postmortem_dir=path)

    def with_profile(self, hz: float) -> "DisqOptions":
        if hz <= 0:
            raise ValueError(f"profile_hz must be > 0, got {hz}")
        return replace(self, profile_hz=float(hz))

    def with_scheduler(self, mode: str, lease_n: int = 2,
                       lease_s: float = 10.0,
                       steal: bool = True,
                       run_weight: float = 1.0,
                       failover_dir: Optional[str] = None
                       ) -> "DisqOptions":
        if not mode:
            raise ValueError(
                "scheduler mode must be 'serve', 'auto' or 'host:port'")
        if lease_n < 1:
            raise ValueError(f"sched_lease_n must be >= 1, got {lease_n}")
        if lease_s <= 0:
            raise ValueError(f"sched_lease_s must be > 0, got {lease_s}")
        if run_weight <= 0:
            raise ValueError(
                f"sched_run_weight must be > 0, got {run_weight}")
        return replace(self, scheduler=str(mode),
                       sched_lease_n=int(lease_n),
                       sched_lease_s=float(lease_s),
                       sched_steal=bool(steal),
                       sched_run_weight=float(run_weight),
                       sched_failover_dir=(str(failover_dir)
                                           if failover_dir else None))

    def with_http_cache_blocks(self, n: int) -> "DisqOptions":
        if n < 1:
            raise ValueError(f"http_cache_blocks must be >= 1, got {n}")
        return replace(self, http_cache_blocks=int(n))

    def with_slo(self, spec: str) -> "DisqOptions":
        """Attach a per-tenant SLO spec (validated eagerly so a typo
        fails at options-build time, not mid-serve)."""
        from disq_tpu.runtime.slo import parse_slo_spec

        parse_slo_spec(spec)  # raises ValueError on a malformed spec
        return replace(self, slo=str(spec))

    def with_resident_decode(self, enable: bool = True) -> "DisqOptions":
        return replace(self, resident_decode=bool(enable))

    def with_read_filter(self, spec: str) -> "DisqOptions":
        """Push a ``samtools view``-grammar read filter into the
        decode (validated eagerly so a typo fails at options-build
        time, not per shard)."""
        from disq_tpu.ops.rfilter import parse_read_filter

        parse_read_filter(spec)  # raises ValueError on a malformed spec
        return replace(self, read_filter=str(spec))

    def with_mesh(self, devices: int = 0) -> "DisqOptions":
        """Arm the mesh-native pipeline: 0 = all local devices, n = the
        first n (power-of-two floor; resolving to 1 device keeps the
        plain single-device dispatch)."""
        if devices < 0:
            raise ValueError(f"mesh devices must be >= 0, got {devices}")
        return replace(self, mesh=int(devices))


class CorruptBlockError(ValueError):
    """A compressed block failed decode *with certainty* (CRC mismatch,
    invalid DEFLATE bits, impossible container framing) — carrying the
    coordinates every layer above needs to act on it."""

    def __init__(
        self,
        message: str,
        *,
        path: str = "",
        shard_id: int = -1,
        block_offset: int = -1,
        virtual_offset: Optional[int] = None,
    ) -> None:
        detail = (
            f"{message} [path={path!r} shard={shard_id} "
            f"block_offset={block_offset}"
            + (f" voffset={virtual_offset:#x}" if virtual_offset is not None else "")
            + "]"
        )
        super().__init__(detail)
        self.path = path
        self.shard_id = shard_id
        self.block_offset = block_offset
        self.virtual_offset = virtual_offset


class TransientIOError(IOError):
    """Marker for errors known to be transient (used by the fault
    injector and by wrappers that can prove transience)."""


class CoordinatorLostError(TransientIOError):
    """The shard-scheduler coordinator became unreachable mid-run
    (``runtime/scheduler.py``).  Transient by inheritance: with
    failover armed (``DISQ_TPU_SCHED_FAILOVER`` / a standby replaying
    the ``SchedJournal``) the worker rediscovers the new coordinator
    address and retries; without failover the worker's rediscovery
    budget drains and this error surfaces as the read's failure."""

    def __init__(self, message: str, *, address: str = "",
                 op: str = "") -> None:
        super().__init__(
            f"{message} [address={address or '?'} op={op or '?'}]")
        self.address = address
        self.op = op


class MissingReferenceError(ValueError):
    """Reference FASTA absent/wrong for reference-compressed CRAM — a
    *configuration* error: never retried, and never treated as data
    corruption by skip/quarantine (silently dropping every container
    because the user forgot ``reference_source_path`` would be a
    catastrophe, not fault tolerance)."""


class WatchdogStallError(RuntimeError):
    """The heartbeat watchdog (``runtime/introspect.py``) flagged a
    shard as stalled past ``DisqOptions.watchdog_stall_s`` under
    ``watchdog_policy="abort"``: the pipeline run is cancelled through
    its first-error-abort path. Deliberately NOT transient — retrying
    the very work the watchdog just declared wedged would mask the
    hang it exists to surface."""

    def __init__(self, message: str, *, shard_id: int = -1,
                 stage: str = "", age_s: float = 0.0,
                 direction: str = "") -> None:
        detail = (f"{message} [direction={direction or '?'} "
                  f"shard={shard_id} stage={stage or '?'} "
                  f"silent_for={age_s:.3f}s]")
        super().__init__(detail)
        self.shard_id = shard_id
        self.stage = stage
        self.age_s = age_s
        self.direction = direction


class DeadlineExceededError(RuntimeError):
    """A shard exhausted its ``DisqOptions.shard_deadline_s`` budget —
    the terminal rung of the resilience escalation ladder (retry →
    hedge → this).  A *certain*, non-transient kind: retrying work the
    deadline already declared over-budget would defeat the deadline.
    Under skip/quarantine policy the sources convert it into a
    quarantined empty shard instead of aborting the run."""

    def __init__(self, message: str, *, shard_id: int = -1,
                 elapsed_s: float = 0.0, deadline_s: float = 0.0) -> None:
        detail = (f"{message} [shard={shard_id} "
                  f"elapsed={elapsed_s:.3f}s deadline={deadline_s:.3f}s]")
        super().__init__(detail)
        self.shard_id = shard_id
        self.elapsed_s = elapsed_s
        self.deadline_s = deadline_s


class BreakerOpenError(RuntimeError):
    """The circuit breaker guarding a filesystem is open: the call was
    rejected *before* touching the store (``runtime/resilience.py``).
    Non-transient by classification — the breaker exists precisely to
    stop retry loops from hammering a store it has declared degraded;
    callers should surface the failure (or wait ``retry_after_s``)."""

    def __init__(self, message: str, *, key: str = "",
                 retry_after_s: float = 0.0) -> None:
        super().__init__(
            f"{message} [filesystem={key or '?'} "
            f"retry_after={retry_after_s:.3f}s]")
        self.key = key
        self.retry_after_s = retry_after_s


class TruncatedReadError(OSError, ValueError):
    """A range read returned fewer bytes than the on-disk structure
    requires. Subclasses ``OSError`` (it is an I/O symptom — a flaky
    remote can truncate a body, so it is *retryable*) and ``ValueError``
    (compat: callers of the block walk historically catch ValueError)."""


# OSError subclasses that are definitive, not worth retrying.
_PERMANENT_OS_ERRORS = (
    FileNotFoundError,
    PermissionError,
    IsADirectoryError,
    NotADirectoryError,
    FileExistsError,
)


def is_transient(exc: BaseException) -> bool:
    """Transient (retryable) vs. permanent/corrupt classification."""
    if isinstance(exc, TransientIOError):
        return True
    if isinstance(exc, (CorruptBlockError, WatchdogStallError,
                        DeadlineExceededError, BreakerOpenError)):
        return False
    if isinstance(exc, _PERMANENT_OS_ERRORS):
        return False
    if isinstance(exc, (TimeoutError, ConnectionError, TruncatedReadError)):
        return True
    try:
        import urllib.error

        if isinstance(exc, urllib.error.HTTPError):
            return exc.code >= 500
        if isinstance(exc, urllib.error.URLError):
            return True
    except ImportError:  # pragma: no cover
        pass
    try:
        import http.client

        # IncompleteRead / RemoteDisconnected and friends: wire-level
        # symptoms a re-request can fix.
        if isinstance(exc, http.client.HTTPException):
            return True
    except ImportError:  # pragma: no cover
        pass
    return isinstance(exc, OSError)


# Shared fallback RNG for backoff jitter: module-wide so concurrent
# retriers draw *different* sleeps even when none injects its own.
_JITTER_RNG = random.Random()

_resilience = None  # lazily bound module ref (avoids an import cycle)


def _resilience_mod():
    global _resilience
    if _resilience is None:
        from disq_tpu.runtime import resilience

        _resilience = resilience
    return _resilience


class ShardRetrier:
    """Bounded retry with decorrelated-jitter backoff for transient
    faults — the analogue of Spark task retry, scoped to one shard's
    work.

    ``call(fn, ...)`` runs ``fn`` up to ``1 + max_retries`` times,
    retrying only when ``is_transient`` says the failure is worth it.
    Retries are counted in ``.retried`` and traced as ``retry.<what>``
    phases so a flaky store is visible in ``phase_report()``.

    Backoff uses *decorrelated jitter* (``sleep = uniform(base, 3 ×
    prev)``, capped at ``base × 2^max_retries``) instead of bare
    exponential doubling: N parallel workers that all failed in the
    same instant must not come back in lockstep against the very store
    that just dropped them.  ``rng`` is injectable (seeded) so tests
    stay deterministic; the default draws from a process-shared RNG so
    sibling shards decorrelate.

    The retrier is also the resilience layer's choke point
    (``runtime/resilience.py``; every hook below is a no-op until the
    matching ``DisqOptions`` knob configures it):

    - the process-wide ``RetryBudget`` is consulted before every
      retry — a dry bucket denies it and the original error surfaces;
    - an attached per-filesystem ``CircuitBreaker`` gates each attempt
      (``BreakerOpenError`` while open) and is fed every transient
      outcome;
    - an attached ``ShardDeadline`` ends retrying with a
      ``DeadlineExceededError`` once the shard's budget is spent.
    """

    def __init__(
        self,
        max_retries: int = 3,
        backoff_s: float = 0.05,
        sleep: Callable[[float], None] = time.sleep,
        rng: Optional[random.Random] = None,
        breaker=None,
    ) -> None:
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self._sleep = sleep
        self._rng = rng if rng is not None else _JITTER_RNG
        self.retried = 0
        # Resilience attachments (None = the zero-overhead default).
        self.breaker = breaker
        self.deadline = None

    def _next_backoff(self, prev: float) -> float:
        """Decorrelated jitter: uniform in [base, 3 × prev], capped at
        the old schedule's terminal value so worst-case total sleep
        stays the same order as before."""
        base = self.backoff_s
        if base <= 0:
            return 0.0
        cap = base * (2 ** max(1, self.max_retries))
        return min(cap, self._rng.uniform(base, max(base, prev * 3)))

    def call(self, fn: Callable[..., T], *args: Any,
             what: str = "read", **kwargs: Any) -> T:
        from disq_tpu.runtime.tracing import counter, span

        attempt = 0
        prev_sleep = self.backoff_s
        if self.deadline is not None:
            # The shard's wall-clock budget starts with its first
            # attempt, not with its first failure.
            self.deadline.arm()
        while True:
            if self.breaker is not None:
                self.breaker.before_call()
            try:
                result = fn(*args, **kwargs)
            except Exception as e:  # noqa: BLE001 — classified below
                transient = is_transient(e)
                if self.breaker is not None:
                    if transient:
                        self.breaker.record_failure()
                    else:
                        # Not a store fault (corrupt data, 404, config
                        # error): no state-machine event, but a
                        # half-open probe slot must be released or the
                        # breaker wedges in half_open.
                        self.breaker.release_probe()
                if not transient or attempt >= self.max_retries:
                    raise
                if self.deadline is not None:
                    # Escalation ladder terminal: no more retries once
                    # the shard's wall-clock budget is gone.
                    try:
                        self.deadline.check(what=what)
                    except Exception as deadline_exc:
                        raise deadline_exc from e
                budget = _resilience_mod().active_budget()
                if budget is not None and not budget.try_spend(what=what):
                    raise  # bucket dry: the storm must not stampede
                attempt += 1
                self.retried += 1
                counter("retry.attempts").inc(what=what)
                flightrec.record_event(
                    "retry", what=what, attempt=attempt,
                    error=f"{type(e).__name__}: {e}")
                prev_sleep = self._next_backoff(prev_sleep)
                with span("retry.backoff", what=what, attempt=attempt):
                    self._sleep(prev_sleep)
            else:
                if self.breaker is not None:
                    self.breaker.record_success()
                budget = _resilience_mod().active_budget()
                if budget is not None:
                    budget.on_success()
                return result


@dataclass
class ShardErrorContext:
    """Per-shard bundle: the policy, the retrier, and the corrupt-block
    bookkeeping, threaded through a source's shard loop."""

    policy: ErrorPolicy
    path: str
    shard_id: int = -1
    retrier: ShardRetrier = field(default_factory=ShardRetrier)
    quarantine: Optional["QuarantineManifest"] = None  # noqa: F821
    quarantine_dir: Optional[str] = None
    skipped_blocks: int = 0
    quarantined_blocks: int = 0

    def for_shard(self, shard_id: int) -> "ShardErrorContext":
        """A fresh per-shard view (own retrier + counters) sharing the
        policy and the quarantine sink."""
        ctx = ShardErrorContext(
            policy=self.policy,
            path=self.path,
            shard_id=shard_id,
            retrier=ShardRetrier(
                self.retrier.max_retries, self.retrier.backoff_s,
                self.retrier._sleep, rng=self.retrier._rng,
                breaker=self.retrier.breaker,
            ),
            quarantine=self.quarantine,
            quarantine_dir=self.quarantine_dir,
        )
        ctx._parent = self  # type: ignore[attr-defined]
        return ctx

    # -- corrupt-block dispatch -------------------------------------------

    def handle_corrupt_block(
        self,
        error: BaseException,
        *,
        block_offset: int,
        raw: bytes = b"",
        virtual_offset: Optional[int] = None,
        kind: str = "block",
    ) -> None:
        """Apply the policy to one corrupt block. STRICT raises a
        ``CorruptBlockError`` with full coordinates; SKIP counts;
        QUARANTINE additionally copies ``raw`` to the sidecar.  Counted
        outcomes are also booked as labeled telemetry counters
        (``errors.skipped_blocks`` / ``quarantine.blocks``) unless this
        is a ``silent()`` non-owner view."""
        from disq_tpu.runtime.tracing import counter

        if self.policy is ErrorPolicy.STRICT:
            raise CorruptBlockError(
                f"corrupt {kind}: {error}",
                path=self.path,
                shard_id=self.shard_id,
                block_offset=block_offset,
                virtual_offset=virtual_offset,
            ) from error
        silent = getattr(self, "_is_silent", False)
        if self.policy is ErrorPolicy.QUARANTINE:
            self._quarantine_sink().quarantine(
                self.path,
                block_offset,
                raw,
                shard_id=self.shard_id,
                virtual_offset=virtual_offset,
                error=str(error),
                kind=kind,
            )
            self.quarantined_blocks += 1
            if not silent:
                counter("quarantine.blocks").inc(kind=kind)
                flightrec.record_event(
                    "quarantine", block_kind=kind, path=self.path,
                    shard=self.shard_id, block_offset=block_offset,
                    error=str(error))
        else:
            self.skipped_blocks += 1
            if not silent:
                counter("errors.skipped_blocks").inc(kind=kind)
                flightrec.record_event(
                    "skipped_block", block_kind=kind, path=self.path,
                    shard=self.shard_id, block_offset=block_offset,
                    error=str(error))

    def silent(self) -> "ShardErrorContext":
        """A non-counting view for blocks this shard reads but does NOT
        own (split-boundary straddle blocks, boundary-guess windows,
        straddling-line extensions): the owning shard does the counting
        and quarantining, so handling them here would double-book one
        corrupt block across two shards. STRICT still raises — failing
        at first sight is identical to failing when the owner decodes."""
        if self.policy is ErrorPolicy.STRICT:
            return self
        ctx = ShardErrorContext(
            policy=ErrorPolicy.SKIP, path=self.path, shard_id=self.shard_id
        )
        # Non-owner views never book telemetry counters either — the
        # owning shard's context does (same one-owner rule as the
        # ShardCounters bookkeeping).
        ctx._is_silent = True  # type: ignore[attr-defined]
        return ctx

    # Sink creation races under the parallel shard executor: two shards
    # hitting their first corrupt block concurrently must share ONE
    # manifest (two instances would tear the JSONL ledger header).
    _sink_lock = threading.Lock()

    def _quarantine_sink(self) -> "QuarantineManifest":  # noqa: F821
        if self.quarantine is None:
            from disq_tpu.runtime.manifest import QuarantineManifest

            parent = getattr(self, "_parent", None)
            with ShardErrorContext._sink_lock:
                if parent is not None and parent.quarantine is not None:
                    self.quarantine = parent.quarantine
                    return self.quarantine
                base = self.quarantine_dir
                if base is None:
                    if "://" in self.path:
                        raise ValueError(
                            "ErrorPolicy.QUARANTINE on remote input "
                            f"{self.path!r} requires an explicit "
                            "DisqOptions.quarantine_dir — the default "
                            "sidecar location <input>.quarantine only "
                            "exists for local files"
                        )
                    base = self.path + ".quarantine"
                self.quarantine = QuarantineManifest(base)
                if parent is not None:
                    parent.quarantine = self.quarantine
        return self.quarantine


def context_for_storage(storage, path: str) -> ShardErrorContext:
    """Build the read-path error context from a storage builder's
    ``DisqOptions`` (absent/None ⇒ defaults: STRICT, 3 retries).
    Every source funnels through here, so this is also where the
    ``span_log`` knob turns on the JSONL span sink for the read."""
    opts = getattr(storage, "_options", None) or DisqOptions()
    if getattr(opts, "span_log", None):
        from disq_tpu.runtime.tracing import start_span_log

        start_span_log(opts.span_log)
    # Arm the flight recorder before any shard work starts, so even a
    # fault in split planning happens with the event ring live.
    flightrec.configure_from_options(opts)
    if getattr(opts, "slo", None):
        from disq_tpu.runtime import slo as _slo

        _slo.configure_from_options(opts)
    breaker = None
    if (getattr(opts, "retry_budget_tokens", None) is not None
            or getattr(opts, "breaker_window", None) is not None):
        res = _resilience_mod()
        res.configure_globals_from_options(opts)
        breaker = res.breaker_for(path)
    return ShardErrorContext(
        policy=ErrorPolicy.coerce(opts.error_policy),
        path=path,
        retrier=ShardRetrier(opts.max_retries, opts.retry_backoff_s,
                             breaker=breaker),
        quarantine_dir=opts.quarantine_dir,
    )


def deadline_fallback_for(opts, shard_ctx,
                          make_empty: Callable[[], T]
                          ) -> Optional[Callable[[], T]]:
    """Build a ``ShardTask.deadline_fallback`` for one shard: under
    skip/quarantine policy with ``shard_deadline_s`` armed, a shard
    whose deadline expires is booked through the shard's existing
    corrupt-block machinery (counted, and under QUARANTINE recorded in
    the manifest with ``kind="shard deadline"``) and replaced by
    ``make_empty()``'s stand-in value.  STRICT — or no deadline — gets
    None: the ``DeadlineExceededError`` then aborts the run, which is
    exactly the strict contract."""
    if getattr(opts, "shard_deadline_s", None) is None:
        return None
    if shard_ctx is None or shard_ctx.policy is ErrorPolicy.STRICT:
        return None

    def fallback() -> T:
        shard_ctx.handle_corrupt_block(
            DeadlineExceededError(
                "shard deadline exceeded — shard set aside",
                shard_id=shard_ctx.shard_id,
                deadline_s=float(opts.shard_deadline_s)),
            block_offset=-1,
            kind="shard deadline",
        )
        return make_empty()

    return fallback


# -- BGZF salvage ----------------------------------------------------------


def inflate_blocks_salvage(data, blocks, base: int, ctx: ShardErrorContext,
                           owned_until: Optional[int] = None):
    """Per-block inflate applying ``ctx``'s policy: returns a list of
    per-block payloads with ``None`` holes where a corrupt block was
    skipped/quarantined (STRICT raises on the first corrupt block).

    Blocks at file offset >= ``owned_until`` (the boundary straddle this
    shard reads but its successor owns) are salvaged with the silent,
    non-counting view of ``ctx`` so one corrupt block is never booked by
    two shards.

    This is the slow path behind the batched ``inflate_blocks`` — used
    only once a batch inflate has already failed, so the common fault-free
    decode pays nothing.
    """
    from disq_tpu.bgzf.block import make_virtual_offset
    from disq_tpu.bgzf.codec import inflate_block

    silent = ctx.silent()
    payloads = []
    for b in blocks:
        off = b.pos - base
        try:
            payloads.append(inflate_block(data, off))
        except ValueError as e:
            target = (
                silent if owned_until is not None and b.pos >= owned_until
                else ctx
            )
            target.handle_corrupt_block(
                e,
                block_offset=b.pos,
                raw=bytes(data[off: off + b.csize]),
                virtual_offset=make_virtual_offset(b.pos, 0),
                kind="BGZF block",
            )
            payloads.append(None)
    return payloads
