"""Structured telemetry — labeled metrics registry + per-shard span timelines.

The reference's only observability is the Spark UI plus slf4j loggers
(SURVEY.md §5).  disq_tpu replaces both with a process-local telemetry
layer shared by every subsystem:

- **Metrics registry** (``MetricsRegistry`` / module-level ``REGISTRY``):
  labeled ``Counter`` / ``Gauge`` (min/max/last/mean) / fixed-bucket
  ``Histogram`` handles, thread-safe and resettable.  Exported as
  Prometheus text exposition via ``metrics_text()`` and as a plain dict
  via ``telemetry_snapshot()``.
- **Span timeline**: ``span(name, shard=…)`` context managers emit
  ``{ts, dur, name, labels}`` events with a process-wide ``run_id`` and
  monotonic timestamps into a bounded in-memory ring (default 64k
  events; overflow drops the oldest and counts
  ``telemetry.dropped_spans``) plus an optional JSONL sink
  (``DISQ_TPU_TRACE_JSONL`` or ``start_span_log(path)`` /
  ``DisqOptions.span_log``).  A whole BAM read becomes a replayable
  per-shard timeline (``scripts/trace_report.py``) instead of a sum.
- **Exporter**: Prometheus text (``metrics_text``).
- **jax.profiler bridge**: every context-manager span (``span`` /
  ``device_span`` / ``trace_phase``) also opens a
  ``jax.profiler.TraceAnnotation("disq_tpu.<name>")``, so under a
  capture the program's spans lie in the same ``.xplane.pb``, on the
  same clock, as the ``XLA Ops`` line (Perfetto shows host and device
  on one timeline).  ``record_span`` books a duration after the fact;
  a thread's wait that is booked so (the dispatcher's sleep, the
  ordered emit's stall) opens the bare ``annotate(name)`` round the
  wait itself, so it lies in the capture too.  ``DISQ_TPU_TRACE_DIR``
  (or ``start_trace(dir)``)
  captures everything between the first ``trace_phase`` entered and
  process exit (or ``stop_trace()``).

Metric taxonomy (dotted names, linted by ``scripts/check_metrics.py``
against the README table):

- ``executor.*``  — shard-pipeline executor: per-shard ``executor.fetch``
  / ``executor.decode`` spans + latency histograms, the
  ``executor.emit.stall`` ordered-emit stall histogram, and the
  ``executor.in_flight`` window-depth gauge.
- ``retry.*``     — transient-fault machinery: ``retry.attempts``
  (counter, labeled ``what=``) and ``retry.backoff`` (sleep spans).
- ``errors.*`` / ``quarantine.*`` — corrupt-block policy outcomes:
  ``errors.skipped_blocks``, ``quarantine.blocks`` (counters, labeled
  ``kind=``) and ``quarantine.write`` sidecar-write spans.
- ``fsw.http.*``  — remote I/O: ``fsw.http.range_get`` latency
  spans/histogram and the block-LRU efficacy counters
  ``fsw.http.cache.hits`` / ``fsw.http.cache.misses`` /
  ``fsw.http.cache.evictions``.
- ``codec.*``     — codec batch work: ``codec.inflate.batch`` spans.
- ``bam.*`` / ``vcf.*`` / ``bcf.*`` / ``cram.*`` — format phases
  (``bam.read.header`` …) and per-split ``<fmt>.split.fetch`` /
  ``<fmt>.split.decode`` spans carrying shard id + byte range.
- ``device.*`` — the device-resident pipeline and Pallas kernels:
  ``device.bytes_to_device`` / ``device.bytes_to_host`` transfer
  counters, ``device.kernel_launches{kernel=}``,
  ``device.host_fallback_blocks{reason=}``, the ``device.hbm_bytes``
  live-footprint gauge, and ``device.kernel`` / ``device.transfer``
  spans.  Device spans are timed by ``device_span`` /
  ``synced_timer``, which ``jax.block_until_ready`` the kernel's
  output before closing — dispatch is asynchronous, so an unfenced
  timing measures the enqueue (``chip_smoke.py`` times one long
  kernel both ways on every run and fails if the fence does not hold).
- ``telemetry.*`` — self-observation (``telemetry.dropped_spans``).

Back-compat: ``trace_phase`` / ``phase_report`` /
``observe_gauge`` / ``gauge_report`` are thin views over the registry —
phases are unlabeled duration histograms, so ``phase_report()`` keeps
returning ``{name: {calls, total_s}}``.
"""

from __future__ import annotations

import atexit
import contextlib
import contextvars
import json
import logging
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

logger = logging.getLogger("disq_tpu.tracing")

# Process-wide run id: every span carries it, so timelines from
# different runs/processes appended to one JSONL stay separable.
RUN_ID = f"{os.getpid():x}-{time.time_ns() & 0xFFFFFFFF:08x}"

# Default latency buckets (seconds): spans are I/O + decode phases that
# range from sub-millisecond (cache hit) to tens of seconds (cold
# remote shard).  Fixed buckets keep observe() O(len(buckets)) with no
# allocation.
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)

_LabelKey = Tuple[Tuple[str, Any], ...]


def _label_key(labels: Dict[str, Any]) -> _LabelKey:
    return tuple(sorted(labels.items()))


def _label_str(key: _LabelKey) -> str:
    return ",".join(f"{k}={v}" for k, v in key)


class Counter:
    """Monotonic labeled counter handle: ``inc(n, **labels)``."""

    kind = "counter"

    def __init__(self, name: str, registry: "MetricsRegistry") -> None:
        self.name = name
        self._registry = registry
        self._values: Dict[_LabelKey, float] = {}

    def inc(self, n: float = 1, **labels: Any) -> None:
        key = _label_key(labels)
        with self._registry._lock:
            self._values[key] = self._values.get(key, 0) + n

    def value(self, **labels: Any) -> float:
        """Value for one exact labelset (no labels ⇒ the unlabeled
        series)."""
        with self._registry._lock:
            return self._values.get(_label_key(labels), 0)

    def total(self) -> float:
        """Sum across every labelset."""
        with self._registry._lock:
            return sum(self._values.values())

    def _reset(self) -> None:
        self._values.clear()

    def _snapshot(self) -> Dict[str, float]:
        return {_label_str(k): v for k, v in sorted(self._values.items())}


class Gauge:
    """Level-style labeled quantity (queue depth, in-flight shards):
    keeps min / max / last / mean per labelset — gauges are states, not
    durations."""

    kind = "gauge"

    def __init__(self, name: str, registry: "MetricsRegistry") -> None:
        self.name = name
        self._registry = registry
        self._states: Dict[_LabelKey, Dict[str, float]] = {}

    def observe(self, value: float, **labels: Any) -> None:
        key = _label_key(labels)
        with self._registry._lock:
            g = self._states.get(key)
            if g is None:
                self._states[key] = {
                    "min": value, "max": value, "last": value,
                    "sum": value, "samples": 1,
                }
            else:
                g["min"] = min(g["min"], value)
                g["max"] = max(g["max"], value)
                g["last"] = value
                g["sum"] += value
                g["samples"] += 1

    def state(self, **labels: Any) -> Optional[Dict[str, float]]:
        with self._registry._lock:
            g = self._states.get(_label_key(labels))
            return None if g is None else self._view(g)

    @staticmethod
    def _view(g: Dict[str, float]) -> Dict[str, float]:
        out = {k: g[k] for k in ("min", "max", "last", "samples")}
        out["mean"] = g["sum"] / g["samples"] if g["samples"] else 0.0
        return out

    def _reset(self) -> None:
        self._states.clear()

    def _snapshot(self) -> Dict[str, Dict[str, float]]:
        return {
            _label_str(k): self._view(g)
            for k, g in sorted(self._states.items())
        }


class Histogram:
    """Fixed-bucket labeled histogram with percentile estimation.

    ``observe(seconds)`` is O(len(buckets)); ``percentile(p)`` linearly
    interpolates inside the winning bucket, clamped to the observed
    min/max so a single sample reports itself exactly."""

    kind = "histogram"

    def __init__(self, name: str, registry: "MetricsRegistry",
                 buckets: Tuple[float, ...] = DEFAULT_BUCKETS,
                 unit: str = "seconds") -> None:
        self.name = name
        self.buckets = tuple(sorted(buckets))
        self.unit = unit
        self._registry = registry
        # labelset -> [bucket counts... , +Inf count]
        self._counts: Dict[_LabelKey, List[int]] = {}
        self._stats: Dict[_LabelKey, Dict[str, float]] = {}

    def observe(self, value: float, **labels: Any) -> None:
        key = _label_key(labels)
        with self._registry._lock:
            counts = self._counts.get(key)
            if counts is None:
                counts = self._counts[key] = [0] * (len(self.buckets) + 1)
                self._stats[key] = {"count": 0, "sum": 0.0,
                                    "min": value, "max": value}
            i = 0
            for i, b in enumerate(self.buckets):  # noqa: B007
                if value <= b:
                    break
            else:
                i = len(self.buckets)
            counts[i] += 1
            st = self._stats[key]
            st["count"] += 1
            st["sum"] += value
            st["min"] = min(st["min"], value)
            st["max"] = max(st["max"], value)

    # -- read side ---------------------------------------------------------

    def _merged(self) -> Tuple[List[int], Dict[str, float]]:
        """Aggregate counts+stats across every labelset (caller holds
        the registry lock)."""
        counts = [0] * (len(self.buckets) + 1)
        stats = {"count": 0, "sum": 0.0, "min": float("inf"), "max": 0.0}
        for key, c in self._counts.items():
            for i, n in enumerate(c):
                counts[i] += n
            st = self._stats[key]
            stats["count"] += st["count"]
            stats["sum"] += st["sum"]
            stats["min"] = min(stats["min"], st["min"])
            stats["max"] = max(stats["max"], st["max"])
        if stats["count"] == 0:
            stats["min"] = 0.0
        return counts, stats

    @property
    def count(self) -> int:
        with self._registry._lock:
            return self._merged()[1]["count"]

    @property
    def sum(self) -> float:
        with self._registry._lock:
            return self._merged()[1]["sum"]

    def percentile(self, p: float) -> float:
        """Estimate the p-th percentile (p in [0, 100]) across all
        labelsets from the bucket counts."""
        with self._registry._lock:
            counts, stats = self._merged()
        total = stats["count"]
        if total == 0:
            return 0.0
        rank = p / 100.0 * total
        cum = 0
        lo = stats["min"]
        for i, n in enumerate(counts):
            if n == 0:
                continue
            hi = (self.buckets[i] if i < len(self.buckets)
                  else stats["max"])
            if cum + n >= rank:
                frac = (rank - cum) / n
                est = lo + (hi - lo) * max(0.0, min(1.0, frac))
                return max(stats["min"], min(stats["max"], est))
            cum += n
            lo = hi
        return stats["max"]

    def _reset(self) -> None:
        self._counts.clear()
        self._stats.clear()

    def _snapshot(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for key in sorted(self._counts):
            counts = self._counts[key]
            st = self._stats[key]
            out[_label_str(key)] = {
                "count": st["count"],
                "sum": round(st["sum"], 6),
                "min": round(st["min"], 6),
                "max": round(st["max"], 6),
                "buckets": {
                    ("+Inf" if i == len(self.buckets)
                     else repr(self.buckets[i])): n
                    for i, n in enumerate(counts) if n
                },
            }
        return out


class MetricsRegistry:
    """Thread-safe named-metric registry.  ``counter`` / ``gauge`` /
    ``histogram`` create-or-return handles; registering one name as two
    different kinds raises (the metric-name lint makes that a CI
    failure before it is a runtime one)."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._metrics: Dict[str, Any] = {}
        self._settlers: List[Callable[[], None]] = []

    def on_snapshot(self, fn: Callable[[], None]) -> None:
        """Call ``fn`` before every ``snapshot()`` / ``metrics_text()``
        copies the registry: the owner of a counter that grows with
        time (the dispatcher's sleep) books what has passed so far, so
        two snapshots differ by what lay between them."""
        with self._lock:
            self._settlers.append(fn)

    def off_snapshot(self, fn: Callable[[], None]) -> None:
        with self._lock:
            if fn in self._settlers:
                self._settlers.remove(fn)

    def _settle(self) -> None:
        # outside the registry lock: a settler takes its owner's lock
        # first and the registry's (``inc``) inside it, as its owner does
        with self._lock:
            settlers = list(self._settlers)
        for fn in settlers:
            fn()

    def _get(self, name: str, factory: Callable[[], Any], kind: str):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = factory()
            elif m.kind != kind:
                raise ValueError(
                    f"metric {name!r} already registered as {m.kind}, "
                    f"requested {kind}")
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, lambda: Counter(name, self), "counter")

    def gauge(self, name: str) -> Gauge:
        return self._get(name, lambda: Gauge(name, self), "gauge")

    def histogram(self, name: str,
                  buckets: Tuple[float, ...] = DEFAULT_BUCKETS,
                  unit: str = "seconds") -> Histogram:
        return self._get(
            name, lambda: Histogram(name, self, buckets, unit), "histogram")

    def metrics(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._metrics)

    def reset(self) -> None:
        """Zero every metric (handles stay registered, so references
        held by long-lived objects keep working)."""
        with self._lock:
            for m in self._metrics.values():
                m._reset()

    # -- exporters ---------------------------------------------------------

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        """Full registry state as a JSON-serializable dict:
        ``{"counters": …, "gauges": …, "histograms": …}``, each keyed
        by metric name then labelset string (``""`` = unlabeled)."""
        out: Dict[str, Dict[str, Any]] = {
            "counters": {}, "gauges": {}, "histograms": {},
        }
        self._settle()
        with self._lock:
            for name in sorted(self._metrics):
                m = self._metrics[name]
                snap = m._snapshot()
                if snap:
                    out[m.kind + "s"][name] = snap
        return out

    def metrics_text(self) -> str:
        """Prometheus text exposition.  Dotted names become
        ``disq_tpu_``-prefixed underscore names; histograms get the
        conventional ``_bucket``/``_sum``/``_count`` series with
        cumulative ``le`` labels."""
        def prom_name(name: str) -> str:
            return "disq_tpu_" + name.replace(".", "_")

        def esc(v: Any) -> str:
            return str(v).replace("\\", "\\\\").replace('"', '\\"')

        def fmt_labels(key: _LabelKey, extra: str = "") -> str:
            parts = ['%s="%s"' % (k, esc(v)) for k, v in key]
            if extra:
                parts.append(extra)
            return "{" + ",".join(parts) + "}" if parts else ""

        def fmt_val(v: float) -> str:
            return repr(round(v, 9)) if isinstance(v, float) else str(v)

        lines: List[str] = []
        self._settle()
        with self._lock:
            items = sorted(self._metrics.items())
            for name, m in items:
                pn = prom_name(name)
                if m.kind == "counter":
                    if not m._values:
                        continue
                    lines.append(f"# TYPE {pn} counter")
                    for key, v in sorted(m._values.items()):
                        lines.append(f"{pn}{fmt_labels(key)} {fmt_val(v)}")
                elif m.kind == "gauge":
                    if not m._states:
                        continue
                    lines.append(f"# TYPE {pn} gauge")
                    for key, g in sorted(m._states.items()):
                        lines.append(
                            f"{pn}{fmt_labels(key)} {fmt_val(g['last'])}")
                else:
                    if not m._counts:
                        continue
                    hn = pn + ("_" + m.unit if m.unit else "")
                    lines.append(f"# TYPE {hn} histogram")
                    for key in sorted(m._counts):
                        counts = m._counts[key]
                        st = m._stats[key]
                        cum = 0
                        for i, n in enumerate(counts):
                            cum += n
                            le = ("+Inf" if i == len(m.buckets)
                                  else repr(m.buckets[i]))
                            lines.append(
                                "%s_bucket%s %d" % (
                                    hn, fmt_labels(key, 'le="%s"' % le), cum))
                        lines.append(
                            f"{hn}_sum{fmt_labels(key)} "
                            f"{fmt_val(st['sum'])}")
                        lines.append(
                            f"{hn}_count{fmt_labels(key)} "
                            f"{int(st['count'])}")
        return "\n".join(lines) + ("\n" if lines else "")


REGISTRY = MetricsRegistry()


def counter(name: str) -> Counter:
    return REGISTRY.counter(name)


def gauge(name: str) -> Gauge:
    return REGISTRY.gauge(name)


def histogram(name: str,
              buckets: Tuple[float, ...] = DEFAULT_BUCKETS) -> Histogram:
    return REGISTRY.histogram(name, buckets)


def metrics_text() -> str:
    return REGISTRY.metrics_text()


def telemetry_snapshot() -> Dict[str, Any]:
    return REGISTRY.snapshot()


def on_snapshot(fn: Callable[[], None]) -> None:
    REGISTRY.on_snapshot(fn)


def off_snapshot(fn: Callable[[], None]) -> None:
    REGISTRY.off_snapshot(fn)


# ---------------------------------------------------------------------------
# Request-scoped trace context: causal identity across threads/processes
# ---------------------------------------------------------------------------
#
# A TraceContext is minted ONCE at the serving edge (or adopted from a
# client's X-Disq-Trace-* headers) and carried via contextvars so every
# span and flight-recorder event emitted under it — on this thread, or
# on a hop the caller explicitly propagated to — is stamped with the
# request's trace id.  Propagation is explicit and cheap:
#
# - HTTP hop (scheduler RPCs, fsw ranged GETs, cluster scrapes):
#   ``inject_trace_headers(headers)`` adds the three headers when a
#   context is active, and the receiving introspection handler re-
#   activates it via ``trace_from_headers(self.headers)``.
# - Thread hop (device-service submissions): the submitting thread's
#   context rides on each queued lane and the dispatcher re-activates
#   it per owner via ``trace_scope`` when booking that owner's share.
#
# Zero-overhead contract (scripts/check_overhead.py): with no context
# active and DISQ_TPU_TRACE_REQUESTS unset, ``current_trace()`` is one
# ContextVar read, ``inject_trace_headers`` adds nothing, and no trace
# id is ever minted (``trace_ids_minted()`` stays 0).

TRACE_ID_HEADER = "X-Disq-Trace-Id"
TRACE_PARENT_HEADER = "X-Disq-Trace-Parent"
TRACE_TENANT_HEADER = "X-Disq-Trace-Tenant"


class TraceContext:
    """Immutable causal identity of one request: the trace id shared by
    every hop, the parent span/hop id that reached here, the tenant."""

    __slots__ = ("trace_id", "span_id", "tenant")

    def __init__(self, trace_id: str, span_id: str, tenant: str) -> None:
        self.trace_id = trace_id
        self.span_id = span_id
        self.tenant = tenant

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"TraceContext(trace_id={self.trace_id!r}, "
                f"span_id={self.span_id!r}, tenant={self.tenant!r})")


_trace_var: "contextvars.ContextVar[Optional[TraceContext]]" = (
    contextvars.ContextVar("disq_tpu_trace", default=None))
_trace_mint_lock = threading.Lock()
_trace_ids_minted = 0
_trace_span_seq = 0
_trace_env_resolved = False
_trace_requests = False


def trace_requests_enabled() -> bool:
    """True when ``DISQ_TPU_TRACE_REQUESTS`` is set truthy — the
    serving edge then mints a trace for requests that arrive without
    one.  Resolved once per process (explicit headers always win)."""
    global _trace_env_resolved, _trace_requests
    if not _trace_env_resolved:
        with _trace_mint_lock:
            if not _trace_env_resolved:
                _trace_requests = os.environ.get(
                    "DISQ_TPU_TRACE_REQUESTS", "").lower() not in (
                        "", "0", "false", "off")
                _trace_env_resolved = True
    return _trace_requests


def current_trace() -> Optional[TraceContext]:
    """The active request context, or None (the common, free case)."""
    return _trace_var.get()


def _mint_id(nbytes: int = 8) -> str:
    global _trace_ids_minted
    with _trace_mint_lock:
        _trace_ids_minted += 1
    return os.urandom(nbytes).hex()


def trace_ids_minted() -> int:
    """How many trace/span ids this process has minted — the overhead
    guard asserts this stays 0 on the tracing-off path."""
    with _trace_mint_lock:
        return _trace_ids_minted


def mint_trace(tenant: str) -> TraceContext:
    """Mint a fresh root context at the serving edge."""
    return TraceContext(_mint_id(8), _mint_id(4), str(tenant))


def child_context(ctx: TraceContext) -> TraceContext:
    """A hop-local context under ``ctx``'s trace: same trace id and
    tenant, a fresh span/hop id (cheap sequence, not entropy — hop ids
    only need uniqueness within one process's trace participation)."""
    global _trace_span_seq
    with _trace_mint_lock:
        _trace_span_seq += 1
        seq = _trace_span_seq
    return TraceContext(ctx.trace_id, f"{RUN_ID}-{seq:x}", ctx.tenant)


def activate_trace(ctx: TraceContext) -> "contextvars.Token":
    """Make ``ctx`` the active context on this thread; returns the
    token for ``deactivate_trace``."""
    return _trace_var.set(ctx)


def deactivate_trace(token: "contextvars.Token") -> None:
    _trace_var.reset(token)


@contextlib.contextmanager
def trace_scope(ctx: Optional[TraceContext]) -> Iterator[None]:
    """Scope ``ctx`` (None = no-op) over a block — used by the device
    dispatcher to book each owner's share under its own trace."""
    if ctx is None:
        yield
        return
    token = _trace_var.set(ctx)
    try:
        yield
    finally:
        _trace_var.reset(token)


def inject_trace_headers(headers: Dict[str, str]) -> Dict[str, str]:
    """Add ``X-Disq-Trace-*`` to an outbound header dict when a context
    is active; with none active this is one ContextVar read and the
    dict is returned untouched."""
    ctx = _trace_var.get()
    if ctx is not None:
        headers[TRACE_ID_HEADER] = ctx.trace_id
        headers[TRACE_PARENT_HEADER] = ctx.span_id
        headers[TRACE_TENANT_HEADER] = ctx.tenant
    return headers


def trace_from_headers(headers: Any) -> Optional[TraceContext]:
    """Parse an inbound context from HTTP headers (any mapping with
    ``.get``, including ``http.client.HTTPMessage``); None when the
    trace-id header is absent — one dict lookup on the off path."""
    trace_id = headers.get(TRACE_ID_HEADER)
    if not trace_id:
        return None
    return TraceContext(
        str(trace_id),
        str(headers.get(TRACE_PARENT_HEADER) or ""),
        str(headers.get(TRACE_TENANT_HEADER) or "anon"))


def reset_trace_state() -> None:
    """Test hook: forget the env resolution and zero the mint counter
    (any active context on the calling thread is left alone)."""
    global _trace_env_resolved, _trace_requests, _trace_ids_minted
    global _trace_span_seq
    with _trace_mint_lock:
        _trace_env_resolved = False
        _trace_requests = False
        _trace_ids_minted = 0
        _trace_span_seq = 0


# ---------------------------------------------------------------------------
# Span timeline: bounded ring + optional JSONL sink
# ---------------------------------------------------------------------------

DEFAULT_SPAN_RING = 65536

_span_lock = threading.Lock()
_span_ring: "deque[Dict[str, Any]]" = deque(maxlen=DEFAULT_SPAN_RING)
_span_sink = None            # open file object, or None
_span_sink_path: Optional[str] = None
_span_writes = 0             # lines since the last explicit flush
_sink_dropped_base = 0.0     # telemetry.dropped_spans total when this
                             # sink opened — the stop trailer reports
                             # only drops during the sink's lifetime
_SINK_FLUSH_EVERY = 64       # amortize flushes: a synchronous flush per
                             # span would serialize every worker thread
                             # on trace-disk latency (close() flushes
                             # the tail, so at most this many spans are
                             # lost to a hard crash)
_env_resolved = False        # DISQ_TPU_TRACE_JSONL honored at first use


def _resolve_span_env() -> None:
    global _env_resolved
    if _env_resolved:
        return
    with _span_lock:
        if _env_resolved:
            return
        _env_resolved = True
        path = os.environ.get("DISQ_TPU_TRACE_JSONL")
    if path and _span_sink is None:
        start_span_log(path)


def start_span_log(path: str) -> None:
    """Start (or re-point) the JSONL span sink.  Each emitted span is
    appended as one JSON line; a meta line maps this run's monotonic
    clock to the epoch so timelines from multiple runs stay
    separable."""
    global _span_sink, _span_sink_path, _env_resolved, _sink_dropped_base
    dropped_now = REGISTRY.counter("telemetry.dropped_spans").total()
    with _span_lock:
        _env_resolved = True  # explicit call wins over the env knob
        if _span_sink is not None:
            if _span_sink_path == path:
                return
            _span_sink.close()
        _sink_dropped_base = dropped_now
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        _span_sink = open(path, "a")
        _span_sink_path = path
        _span_sink.write(json.dumps({
            "meta": 1, "run_id": RUN_ID, "pid": os.getpid(),
            "epoch": time.time(), "mono": time.perf_counter(),
        }) + "\n")
        _span_sink.flush()
        atexit.register(stop_span_log)


def stop_span_log() -> None:
    global _span_sink, _span_sink_path, _span_writes
    total = REGISTRY.counter("telemetry.dropped_spans").total()
    with _span_lock:
        if _span_sink is not None:
            dropped = int(total - _sink_dropped_base)
            if dropped > 0:
                # Trailer meta line: the in-memory ring overflowed
                # during this sink's lifetime, so any ring-derived view
                # (/spans) is truncated even though the
                # JSONL itself is complete — trace_report surfaces it
                # as a banner instead of silently rendering a partial
                # waterfall.
                _span_sink.write(json.dumps({
                    "meta": 1, "run_id": RUN_ID,
                    "dropped_spans": dropped,
                }) + "\n")
            _span_sink.close()  # flushes any buffered tail
            _span_sink = None
            _span_sink_path = None
            _span_writes = 0


def span_log_path() -> Optional[str]:
    with _span_lock:
        return _span_sink_path


def set_span_ring_capacity(n: int) -> None:
    """Resize the in-memory span ring (keeps the most recent spans)."""
    global _span_ring
    with _span_lock:
        _span_ring = deque(_span_ring, maxlen=max(1, int(n)))


def spans() -> List[Dict[str, Any]]:
    """Snapshot of the in-memory span ring, oldest first."""
    with _span_lock:
        return list(_span_ring)


def reset_spans() -> None:
    with _span_lock:
        _span_ring.clear()


def _emit_span(name: str, ts: float, dur: float,
               labels: Dict[str, Any]) -> None:
    global _span_writes
    REGISTRY.histogram(name).observe(dur)
    rec = {"ts": round(ts, 6), "dur": round(dur, 6), "name": name,
           "run": RUN_ID, "labels": labels}
    ctx = _trace_var.get()
    if ctx is not None:
        rec["trace"] = ctx.trace_id
        rec["parent"] = ctx.span_id
        rec["tenant"] = ctx.tenant
    # Serialize outside the lock (unlocked sink check is benign: worst
    # case one wasted dumps around a concurrent start/stop).
    line = (json.dumps(rec, default=str) + "\n"
            if _span_sink is not None else None)
    with _span_lock:
        dropped = len(_span_ring) == _span_ring.maxlen
        _span_ring.append(rec)
        if _span_sink is not None:
            if line is None:
                line = json.dumps(rec, default=str) + "\n"
            _span_sink.write(line)
            _span_writes += 1
            if _span_writes >= _SINK_FLUSH_EVERY:
                _span_sink.flush()
                _span_writes = 0
    if dropped:
        REGISTRY.counter("telemetry.dropped_spans").inc()
    logger.debug("span %s: %.4fs %s", name, dur, labels)


_annotation_cls = None  # jax.profiler.TraceAnnotation once jax is loaded
_NO_ANNOTATION = contextlib.nullcontext()


def annotate(name: str):
    """The profiler annotation of a span, bare: ``disq_tpu.<name>`` on
    the capture's clock, no ring entry, no histogram, no lock taken (no
    labels in its name, so a reduction can key on it).  ``span`` opens
    one; a wait that ``record_span`` books after the fact opens one
    round the wait itself.  jax is never imported for it: a process
    that has not loaded jax has no capture running."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        try:
            cls = sys.modules["jax"].profiler.TraceAnnotation
        except (KeyError, AttributeError):  # absent, or mid-import
            return _NO_ANNOTATION
        _annotation_cls = cls
    return cls("disq_tpu." + name)


@contextlib.contextmanager
def span(name: str, **labels: Any) -> Iterator[Dict[str, Any]]:
    """Timeline span: emits a ``{ts, dur, name, labels}`` event into the
    ring/JSONL, books the duration in the ``name`` histogram (so
    ``phase_report()`` and percentiles see it) and lies, as
    ``disq_tpu.<name>``, on the profiler's clock under a capture.
    Yields its labels, so the body can add one it only knows at its
    end."""
    _resolve_span_env()
    t0 = time.perf_counter()
    try:
        with annotate(name):
            yield labels
    finally:
        _emit_span(name, t0, time.perf_counter() - t0, labels)


def record_span(name: str, seconds: float, **labels: Any) -> None:
    """Book an already-measured duration as a span ending now (for
    durations timed inline: the executor's ordered-emit stall, the
    dispatcher's sleep, a launch's queueing delay).  It opens no
    annotation; where the duration is a thread's wait, the waiter opens
    ``annotate(name)`` round it."""
    _resolve_span_env()
    now = time.perf_counter()
    _emit_span(name, now - seconds, seconds, labels)


def wrap_span(name: str, fn: Callable, **labels: Any) -> Callable:
    """``fn`` wrapped in ``span(name, **labels)`` — for handing staged
    callables (executor ``ShardTask.fetch``/``decode``) a per-shard
    span without changing their signatures."""
    def wrapped(*args: Any, **kwargs: Any):
        with span(name, **labels):
            return fn(*args, **kwargs)
    return wrapped


# ---------------------------------------------------------------------------
# Device telemetry: synced kernel spans, transfer counters, HBM gauge
# ---------------------------------------------------------------------------

class _DeviceSync:
    """Handle yielded by ``device_span``: the body registers its device
    outputs with ``sync(...)``; span close blocks until they are ready
    so the recorded duration covers real execution."""

    __slots__ = ("_values",)

    def __init__(self) -> None:
        self._values: List[Any] = []

    def sync(self, *values: Any):
        """Register device arrays (or pytrees of them) to fence on at
        span close.  Returns the single value (or the tuple) so call
        sites can wrap an expression in place."""
        self._values.extend(values)
        return values[0] if len(values) == 1 else values

    def block(self) -> None:
        if self._values:
            import jax

            # tracers and non-array leaves pass through untouched
            jax.block_until_ready(self._values)
            self._values.clear()


@contextlib.contextmanager
def device_span(name: str, **labels: Any) -> Iterator[_DeviceSync]:
    """Span over device work whose close is a true sync point: the body
    hands its output arrays to ``.sync(...)`` and span exit
    ``jax.block_until_ready``s them before taking the end timestamp
    (an unfenced device timing measures the enqueue).  Also books one
    ``device.kernel_launches`` increment when a ``kernel=`` label is
    present, so every synced kernel span is a counted launch."""
    if "kernel" in labels:
        REGISTRY.counter("device.kernel_launches").inc(
            kernel=labels["kernel"])
    handle = _DeviceSync()
    with span(name, **labels):
        try:
            yield handle
        finally:
            handle.block()


def synced_timer(name: str, **labels: Any) -> Callable:
    """Decorator form of ``device_span``: times the wrapped function
    and blocks on its return value before the span closes — for ops
    entry points whose return IS the device output."""
    def deco(fn: Callable) -> Callable:
        def wrapped(*args: Any, **kwargs: Any):
            with device_span(name, **labels) as fence:
                return fence.sync(fn(*args, **kwargs))
        return wrapped
    return deco


def count_transfer(direction: str, nbytes: int) -> None:
    """Book one explicit host↔device transfer (``direction`` ``"h2d"``
    or ``"d2h"``) in the ``device.bytes_*`` counters."""
    if direction == "h2d":
        REGISTRY.counter("device.bytes_to_device").inc(int(nbytes))
    else:
        REGISTRY.counter("device.bytes_to_host").inc(int(nbytes))


_hbm_lock = threading.Lock()
_hbm_live = 0


def track_hbm(nbytes: int) -> int:
    """Adjust the live-HBM-footprint estimate (negative to release) and
    observe the ``device.hbm_bytes`` gauge; returns the new estimate.
    The estimate is array-size arithmetic, not an allocator query — it
    tracks what the framework *put* on device, which is exactly the
    number a shard-sizing decision needs."""
    global _hbm_live
    with _hbm_lock:
        _hbm_live = max(0, _hbm_live + int(nbytes))
        live = _hbm_live
    REGISTRY.gauge("device.hbm_bytes").observe(live)
    return live


def hbm_live_bytes() -> int:
    with _hbm_lock:
        return _hbm_live


@contextlib.contextmanager
def hbm_resident(nbytes: int) -> Iterator[None]:
    """Scope one call's device residency: adds ``nbytes`` to the live
    HBM estimate on entry and releases it on exit, so the gauge's max
    is the peak concurrent footprint across overlapping device calls."""
    track_hbm(nbytes)
    try:
        yield
    finally:
        track_hbm(-nbytes)


# ---------------------------------------------------------------------------
# jax.profiler bridge + phase back-compat views
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_trace_active = False

# DISQ_TPU_TRACE_DIR is resolved ONCE (first trace_phase), not read
# from os.environ on every call.
_phase_env_resolved = False
_trace_dir: Optional[str] = None


def _resolve_phase_env() -> None:
    global _phase_env_resolved, _trace_dir
    if _phase_env_resolved:
        return
    with _lock:
        if _phase_env_resolved:
            return
        _trace_dir = os.environ.get("DISQ_TPU_TRACE_DIR")
        _phase_env_resolved = True


def start_trace(trace_dir: str) -> None:
    """Begin a ``jax.profiler`` capture writing to ``trace_dir``."""
    global _trace_active
    try:
        import jax
    except ImportError:
        logger.warning("DISQ_TPU_TRACE_DIR set but jax unavailable; no trace")
        return

    with _lock:
        if _trace_active:
            return
        jax.profiler.start_trace(trace_dir)
        _trace_active = True
        atexit.register(stop_trace)


def stop_trace() -> None:
    global _trace_active
    with _lock:
        if not _trace_active:
            return
        import jax

        jax.profiler.stop_trace()
        _trace_active = False


@contextlib.contextmanager
def trace_phase(name: str, **labels: Any) -> Iterator[None]:
    """``span`` whose first entry auto-starts a ``DISQ_TPU_TRACE_DIR``
    capture (the top-level phases of a read or a write)."""
    _resolve_phase_env()
    if _trace_dir and not _trace_active:
        start_trace(_trace_dir)
    with span(name, **labels):
        yield


def phase_report() -> Dict[str, Dict[str, float]]:
    """Aggregated ``{phase: {calls, total_s}}`` since process start —
    a thin view over the registry's duration histograms (every span /
    ``trace_phase`` books one)."""
    out: Dict[str, Dict[str, float]] = {}
    with REGISTRY._lock:
        for name, m in sorted(REGISTRY.metrics().items()):
            if m.kind != "histogram":
                continue
            calls = m.count
            if calls:
                out[name] = {"calls": calls, "total_s": round(m.sum, 6)}
    return out


def reset_phase_report() -> None:
    """Zero the duration histograms (and the span ring — a fresh phase
    report implies a fresh timeline)."""
    with REGISTRY._lock:
        for m in REGISTRY.metrics().values():
            if m.kind == "histogram":
                m._reset()
    reset_spans()


def observe_gauge(name: str, value: float, **labels: Any) -> None:
    """Record one sample of a level-style quantity — a thin wrapper
    over ``gauge(name).observe(value)``."""
    REGISTRY.gauge(name).observe(value, **labels)


def gauge_report() -> Dict[str, Dict[str, float]]:
    """Snapshot of every unlabeled gauge series (legacy shape: ``max``
    / ``last`` / ``samples``, now also ``min`` / ``mean``)."""
    out: Dict[str, Dict[str, float]] = {}
    with REGISTRY._lock:
        for name, m in sorted(REGISTRY.metrics().items()):
            if m.kind != "gauge":
                continue
            st = m.state()
            if st is not None:
                out[name] = st
            else:
                snap = m._snapshot()
                if snap:
                    out[name] = next(iter(snap.values()))
    return out


def reset_gauges() -> None:
    with REGISTRY._lock:
        for m in REGISTRY.metrics().values():
            if m.kind == "gauge":
                m._reset()


def reset_telemetry() -> None:
    """Zero everything: registry, span ring, the live-HBM estimate
    (the JSONL sink, if open, is left open — it is an append log)."""
    global _hbm_live
    REGISTRY.reset()
    reset_spans()
    with _hbm_lock:
        _hbm_live = 0
