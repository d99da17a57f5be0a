"""Pipelined parallel shard executor — bounded stage overlap in both
directions.

The reference gets cross-split parallelism for free from Spark: one
task per split, scheduled across executors. disq_tpu's read path walked
splits one at a time in a single host thread (only the C++ inflate
inside a block batch was threaded), so remote/HTTP reads and
stage-serialized formats (CRAM) were latency-bound. This module is the
Spark-scheduler analogue: a bounded staged pipeline shared by every
format source — and, since the write-path generalization, by every
format sink.

Two directions over one core (``_BoundedStagePipeline``):

- **Read** (``ShardPipelineExecutor``): fetch (I/O) → decode (CPU) →
  ordered emit.
- **Write** (``ShardWritePipeline``): encode (batch slice + record
  encode, CPU) → deflate (BGZF/gzip compress + voffset arithmetic,
  native-threaded) → stage (``fs.write_all`` of parts + index
  fragments, I/O) → ordered emit of per-shard part records. Shard
  ``i+1`` encodes while shard ``i`` deflates and shard ``i-1`` stages;
  the driver-side concat/merge consumes results in shard order, so
  output is byte-identical to the sequential loop at any worker count.

- **Stage A — fetch**: ``ShardTask.fetch()`` range-reads the split's
  byte window through the fsw layer (so HTTP prefetch and
  ``FaultInjectingFileSystemWrapper`` compose) and walks/collects its
  compressed structure. Runs on the fetch pool.
- **Stage B — decode**: ``ShardTask.decode(payload)`` inflates and
  parses records. Runs on the decode worker pool.
- **Stage C — emit**: ``map_ordered`` yields results **in shard
  order**, streaming — shard i+1 can be fetching/decoding while shard
  i's result is being consumed.

Guarantees:

- **Order and byte identity.** Results are emitted in task order
  regardless of worker count; the stages run the exact same per-shard
  code the sequential path runs, so output is byte-identical for any
  ``workers``.
- **Sequential-compatible default.** ``workers=1`` runs everything
  inline on the caller's thread in the same call order as the
  pre-executor loop — no threads, no queues.
- **Bounded in-flight window.** At most ``prefetch_shards`` shards past
  the emit frontier are admitted, so a retry storm or a quarantine on
  shard i delays shards ``i+k`` only once they fall inside the window
  (and memory stays bounded by ``window × shard bytes``).
- **ErrorPolicy / ShardRetrier semantics.** Each task carries its own
  per-shard ``ShardRetrier``; transient faults in fetch retry the fetch,
  transient faults escaping decode (salvage re-reads, CRAM reference
  fetch) re-run the shard from fetch under the same retrier. Corrupt
  data follows the shard's ``ErrorPolicy`` exactly as in the sequential
  path; the first raising shard aborts the pipeline.
- **Observability.** Per-stage, per-shard telemetry spans
  (``executor.fetch`` / ``executor.decode`` / ``executor.emit.stall``,
  each labeled with the shard id and feeding the same-named latency
  histogram) plus ``ExecutorStats`` (stage seconds, emit-stall
  seconds, max queue depth) and the ``executor.in_flight`` gauge make
  the overlap measurable, not asserted.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import (
    Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple)

from disq_tpu.runtime import flightrec
from disq_tpu.runtime.errors import (
    DeadlineExceededError,
    DisqOptions,
    ShardRetrier,
    is_transient,
)
from disq_tpu.runtime.tracing import (
    annotate, observe_gauge, record_span, span)

# Sentinel a fetch stage emits when the shard's deadline expired and
# the task carries a fallback: the decode stage then produces the
# fallback value instead of decoding (runtime/resilience.py ladder).
_DEADLINE_MISS = object()


@dataclass
class ShardTask:
    """One split's pipeline work. ``fetch`` does the I/O (stage A) and
    returns an opaque payload; ``decode`` turns that payload into the
    shard's result (stage B). Both close over their shard's
    ``ShardErrorContext`` for policy dispatch; ``retrier`` is that
    context's retrier (None ⇒ no transient retry).

    ``deadline_fallback`` (set by sources when the error policy is
    skip/quarantine and ``DisqOptions.shard_deadline_s`` is armed)
    produces the shard's stand-in value — typically an empty batch,
    booked through the shard's quarantine machinery — when the shard's
    deadline expires; without it a ``DeadlineExceededError`` aborts the
    run (the strict-policy behavior).

    ``byte_range`` is the shard's compressed byte window ``(lo, hi)``
    in the input file — the coordinate the cross-host scheduler's
    locality scorer matches against a worker's HTTP block-cache
    occupancy (``runtime/scheduler.py``; None ⇒ never locality-routed)."""

    shard_id: int
    fetch: Callable[[], Any]
    decode: Callable[[Any], Any]
    retrier: Optional[ShardRetrier] = None
    what: str = "shard"
    deadline_fallback: Optional[Callable[[], Any]] = None
    byte_range: Optional[tuple] = None


@dataclass
class ShardResult:
    """Ordered emission unit: the decoded value plus per-stage wall
    time, so emit-side counter assembly can report real shard cost."""

    shard_id: int
    value: Any
    fetch_seconds: float = 0.0
    decode_seconds: float = 0.0

    @property
    def wall_seconds(self) -> float:
        return self.fetch_seconds + self.decode_seconds


@dataclass
class ExecutorStats:
    """Aggregate pipeline observability for one ``map_ordered`` run
    (cumulative across runs on the same executor instance)."""

    workers: int = 0
    window: int = 0
    shards: int = 0
    fetch_seconds: float = 0.0
    decode_seconds: float = 0.0
    emit_stall_seconds: float = 0.0
    max_in_flight: int = 0

    def as_dict(self) -> Dict[str, float]:
        return {
            "workers": self.workers,
            "window": self.window,
            "shards": self.shards,
            "fetch_seconds": round(self.fetch_seconds, 6),
            "decode_seconds": round(self.decode_seconds, 6),
            "emit_stall_seconds": round(self.emit_stall_seconds, 6),
            "max_in_flight": self.max_in_flight,
        }


class _BoundedStagePipeline:
    """The bounded-window machinery shared by the read executor and the
    write pipeline: N stages, one worker pool per stage, streaming
    ordered emit keyed by task-list index, first-error abort.

    ``stage_fns[i](task, payload)`` runs stage ``i`` (``payload`` is
    None for stage 0; each stage's return feeds the next). The
    ``on_admit(depth)`` / ``on_result(seconds)`` / ``on_stall(seconds,
    task)`` hooks keep stats accounting and metric *names* in the
    direction-specific wrappers, so ``executor.*`` and ``writer.*``
    stay literal at their call sites (the metric-name lint scans
    literals). ``on_result`` and ``on_stall`` run with the pipeline
    condition held — keep them cheap and non-blocking.

    When a run is live-introspected (``health`` is a
    ``PipelineHealth`` board and ``health_token`` its run token), every
    stage worker stamps a per-shard heartbeat as it starts a stage and
    clears it when the stage returns, and the watchdog can cancel the
    run through the existing first-error-abort path: the injected
    ``WatchdogStallError`` is recorded at the emit frontier, so the
    consumer raises it deterministically at its next ``next()``. With
    ``health=None`` (the default) none of this code runs.
    """

    def __init__(
        self,
        workers: int,
        window: int,
        stage_fns: Sequence[Callable[[Any, Any], Any]],
        thread_prefixes: Sequence[str],
        on_admit: Callable[[int], None],
        on_result: Callable[[List[float]], None],
        on_stall: Callable[[float, Any], None],
        stall_name: str,
        drain_on_close: bool = False,
        stage_names: Sequence[str] = (),
        health=None,
        health_token: Optional[int] = None,
    ) -> None:
        self.workers = workers
        self.window = window
        self.stage_fns = list(stage_fns)
        self.thread_prefixes = list(thread_prefixes)
        self.on_admit = on_admit
        self.on_result = on_result
        self.on_stall = on_stall
        # the span name ``on_stall`` books its seconds under: the wait
        # itself lies under that name in a profiler capture
        self.stall_name = stall_name
        # The write direction drains running jobs at close so an
        # aborting sink never races an in-flight part write against its
        # own temp-dir cleanup; the read direction keeps wait=False (a
        # stalled remote fetch must not block the caller's error).
        self.drain_on_close = drain_on_close
        self.stage_names = list(stage_names)
        self.health = health
        self.health_token = health_token

    def run(self, tasks: List[Any]) -> Iterator[tuple]:
        """Admit the first window EAGERLY (stage-0 work is in flight
        before the caller's first ``next()``) and return the
        ordered-emit generator yielding ``(index, value,
        per_stage_seconds)`` in task order."""
        n_stages = len(self.stage_fns)
        cond = threading.Condition()
        results: Dict[int, tuple] = {}
        errors: Dict[int, BaseException] = {}
        state = {"next_admit": 0, "next_emit": 0, "in_flight": 0,
                 "aborted": False}
        pools = [
            ThreadPoolExecutor(max_workers=self.workers,
                               thread_name_prefix=prefix)
            for prefix in self.thread_prefixes
        ]

        health, token = self.health, self.health_token
        if health is not None and token is not None:
            # The watchdog's abort path: record the stall error at the
            # emit frontier so the consumer's next ``next()`` raises it
            # (same mechanics as a stage failure on that shard).
            def inject_abort(exc: BaseException) -> None:
                with cond:
                    if state["aborted"]:
                        return
                    errors.setdefault(state["next_emit"], exc)
                    cond.notify_all()

            health.set_abort(token, inject_abort)

        def stage_name(stage: int) -> str:
            return (self.stage_names[stage]
                    if stage < len(self.stage_names) else str(stage))

        def record_error(idx: int, exc: BaseException) -> None:
            with cond:
                errors[idx] = exc
                state["in_flight"] -= 1
                cond.notify_all()

        def job(stage: int, idx: int, task: Any, payload: Any,
                seconds: List[float]) -> None:
            if stage == 0:
                with cond:
                    if state["aborted"]:
                        state["in_flight"] -= 1
                        cond.notify_all()
                        return
            shard = getattr(task, "shard_id", idx)
            if health is not None:
                health.beat(token, stage_name(stage), shard)
            t0 = time.perf_counter()
            try:
                value = self.stage_fns[stage](task, payload)
            except BaseException as e:  # noqa: BLE001 — re-raised at emit
                if health is not None:
                    health.clear(token, stage_name(stage), shard)
                record_error(idx, e)
                return
            if health is not None:
                health.clear(token, stage_name(stage), shard)
            seconds.append(time.perf_counter() - t0)
            if stage + 1 < n_stages:
                pools[stage + 1].submit(job, stage + 1, idx, task, value,
                                        seconds)
                return
            with cond:
                results[idx] = (value, seconds)
                state["in_flight"] -= 1
                self.on_result(seconds)
                cond.notify_all()

        def admit_locked() -> None:
            # caller holds cond
            while (not state["aborted"]
                   and state["next_admit"] < len(tasks)
                   and state["next_admit"]
                   < state["next_emit"] + self.window):
                idx = state["next_admit"]
                state["next_admit"] += 1
                state["in_flight"] += 1
                self.on_admit(state["in_flight"])
                pools[0].submit(job, 0, idx, tasks[idx], None, [])

        with cond:
            admit_locked()

        def emit() -> Iterator[tuple]:
            try:
                for i in range(len(tasks)):
                    with cond:
                        t0 = time.perf_counter()
                        with annotate(self.stall_name):
                            while i not in results and i not in errors:
                                cond.wait()
                        self.on_stall(time.perf_counter() - t0, tasks[i])
                        if i in errors:
                            state["aborted"] = True
                            # The pipeline's first-error-abort IS the
                            # postmortem moment: every stage worker is
                            # still live, so the bundle's thread stacks
                            # show what each one was doing.
                            flightrec.note_abort(errors[i], where="emit")
                            raise errors[i]
                        value, seconds = results.pop(i)
                        state["next_emit"] = i + 1
                        admit_locked()
                    yield i, value, seconds
            finally:
                with cond:
                    state["aborted"] = True
                for pool in pools:
                    pool.shutdown(wait=self.drain_on_close,
                                  cancel_futures=True)

        return emit()


def _check_abort(health, token: Optional[int]) -> None:
    """Cooperative watchdog-abort pickup for the inline (workers=1)
    paths, which have no pipeline to inject an error into: raise the
    parked WatchdogStallError at the stage boundary where the run's
    own thread next surfaces."""
    if health is not None and token is not None:
        exc = health.take_abort(token)
        if exc is not None:
            raise exc


def _tracked(inner: Iterator, health, token: int) -> Iterator:
    """Wrap an ordered-emit iterator so each yielded shard is marked
    done on the health board and the run is closed out when the
    iterator ends (normally, by error, or abandoned)."""
    try:
        for res in inner:
            health.shard_done(token, res.shard_id)
            yield res
    finally:
        health.finish_run(token)


class ShardPipelineExecutor:
    """Bounded three-stage shard pipeline (see module docstring).

    ``workers`` sizes the decode pool (and the fetch pool — fetches are
    I/O-bound and cheap to oversubscribe, but one pool bound keeps the
    fsw request concurrency predictable). ``prefetch_shards`` bounds
    how many shards past the emit frontier may be in flight; default
    ``2 × workers`` keeps every worker busy while the consumer drains.
    """

    def __init__(self, workers: int = 1,
                 prefetch_shards: Optional[int] = None,
                 health=None,
                 watchdog_stall_s: Optional[float] = None,
                 watchdog_policy: str = "warn",
                 resilience=None) -> None:
        self.workers = max(1, int(workers))
        if prefetch_shards is None:
            prefetch_shards = 2 * self.workers
        self.prefetch_shards = max(1, int(prefetch_shards))
        # prefetch_shards IS the documented in-flight bound: an
        # explicit value below ``workers`` caps memory at the cost of
        # idle workers, exactly as the caller asked.
        self.stats = ExecutorStats(
            workers=self.workers,
            window=self.prefetch_shards,
        )
        # Live introspection (None = disabled, the zero-overhead path):
        # a PipelineHealth board receiving run registration, per-shard
        # heartbeats and completions — see runtime/introspect.py.
        self._health = health
        self._watchdog_stall_s = watchdog_stall_s
        self._watchdog_policy = watchdog_policy
        # Adaptive resilience (None = disabled, zero overhead): a
        # ResilienceManager providing hedged fetches and per-shard
        # deadlines — see runtime/resilience.py.
        self._resilience = resilience

    # -- public -------------------------------------------------------------

    def map_ordered(
        self, tasks: Sequence[ShardTask]
    ) -> Iterator[ShardResult]:
        """Run every task through fetch→decode, yielding results in
        task order as they become ready (streaming — stage C)."""
        tasks = list(tasks)
        self.stats.shards += len(tasks)
        if not tasks:
            return iter(())
        token = None
        if self._health is not None:
            token = self._health.register_run(
                "read", len(tasks), self._watchdog_stall_s,
                self._watchdog_policy)
        if self.workers == 1:
            inner = self._run_sequential(tasks, token)
        else:
            inner = self._run_pipelined(tasks, token)
        if token is None:
            return inner
        return _tracked(inner, self._health, token)

    # -- sequential (workers=1): the exact pre-executor call order ----------

    def _run_sequential(self, tasks: List[ShardTask],
                        token: Optional[int] = None
                        ) -> Iterator[ShardResult]:
        try:
            for task in tasks:
                yield self._run_one_inline(task, token)
        except GeneratorExit:
            # Consumer stopped iterating early — a normal close, not
            # an abort; no postmortem.
            raise
        except BaseException as e:
            # Inline first-error-abort: same postmortem moment as the
            # pipelined emit raise.
            flightrec.note_abort(e, where="inline")
            raise
        finally:
            if self._resilience is not None:
                self._resilience.close()

    def _run_one_inline(self, task: ShardTask,
                        token: Optional[int] = None) -> ShardResult:
        """Whole-shard work under ONE retrier budget — identical
        semantics (and retry accounting) to the historical
        ``retrier.call(decode_range, …)`` per-shard loop."""
        times = [0.0, 0.0]
        health = self._health if token is not None else None
        res = self._resilience
        deadline = (res.new_deadline(task.shard_id)
                    if res is not None else None)
        if deadline is not None and task.retrier is not None:
            task.retrier.deadline = deadline

        def attempt():
            t0 = time.perf_counter()
            _check_abort(health, token)
            if deadline is not None:
                deadline.check(what=task.what)
            if health is not None:
                health.beat(token, "fetch", task.shard_id)
            with span("executor.fetch", shard=task.shard_id):
                if res is not None:
                    payload = res.fetch(task.fetch, task.shard_id, deadline)
                else:
                    payload = task.fetch()
            t1 = time.perf_counter()
            times[0] += t1 - t0
            _check_abort(health, token)
            if deadline is not None:
                deadline.check(what=task.what)
            if health is not None:
                health.beat(token, "decode", task.shard_id)
            with span("executor.decode", shard=task.shard_id):
                value = task.decode(payload)
            times[1] += time.perf_counter() - t1
            if health is not None:
                health.clear(token, "decode", task.shard_id)
            _check_abort(health, token)
            return value

        try:
            if task.retrier is not None:
                value = task.retrier.call(attempt, what=task.what)
            else:
                value = attempt()
        except DeadlineExceededError:
            if task.deadline_fallback is None:
                raise
            value = task.deadline_fallback()
        self.stats.fetch_seconds += times[0]
        self.stats.decode_seconds += times[1]
        return ShardResult(task.shard_id, value, times[0], times[1])

    # -- pipelined (workers>1) ----------------------------------------------

    def _run_pipelined(self, tasks: List[ShardTask],
                       token: Optional[int] = None
                       ) -> Iterator[ShardResult]:
        """Two stages over the shared bounded core: fetch (with the
        per-shard retrier, hedged when resilience is armed) and decode
        (with the transient-escape refetch hatch)."""
        res = self._resilience
        deadlines: Dict[int, Any] = {}
        if res is not None:
            for t in tasks:
                dl = res.new_deadline(t.shard_id)
                if dl is not None:
                    deadlines[t.shard_id] = dl
                    if t.retrier is not None:
                        t.retrier.deadline = dl

        def fetch_once(task: ShardTask) -> Any:
            if res is not None:
                return res.fetch(task.fetch, task.shard_id,
                                 deadlines.get(task.shard_id))
            return task.fetch()

        def fetch_fn(task: ShardTask, _payload: Any) -> Any:
            with span("executor.fetch", shard=task.shard_id):
                dl = deadlines.get(task.shard_id)
                try:
                    if dl is not None:
                        dl.check(what=task.what)
                    if task.retrier is not None:
                        return task.retrier.call(
                            lambda: fetch_once(task),
                            what=f"{task.what}.fetch")
                    return fetch_once(task)
                except DeadlineExceededError:
                    if task.deadline_fallback is None:
                        raise
                    return _DEADLINE_MISS

        def decode_fn(task: ShardTask, payload: Any) -> Any:
            with span("executor.decode", shard=task.shard_id):
                if payload is _DEADLINE_MISS:
                    return task.deadline_fallback()
                dl = deadlines.get(task.shard_id)
                try:
                    if dl is not None:
                        dl.check(what=task.what)
                    return self._decode_with_refetch(task, payload)
                except DeadlineExceededError:
                    if task.deadline_fallback is None:
                        raise
                    return task.deadline_fallback()

        def on_admit(depth: int) -> None:
            if depth > self.stats.max_in_flight:
                self.stats.max_in_flight = depth
            observe_gauge("executor.in_flight", depth)

        def on_result(seconds: List[float]) -> None:
            self.stats.fetch_seconds += seconds[0]
            self.stats.decode_seconds += seconds[1]

        def on_stall(stall: float, task: ShardTask) -> None:
            self.stats.emit_stall_seconds += stall
            if stall > 0.0005:
                # only meaningful waits become trace spans
                record_span("executor.emit.stall", stall,
                            shard=task.shard_id)

        core = _BoundedStagePipeline(
            workers=self.workers,
            window=self.stats.window,
            stage_fns=(fetch_fn, decode_fn),
            thread_prefixes=("disq-fetch", "disq-decode"),
            on_admit=on_admit,
            on_result=on_result,
            on_stall=on_stall,
            stall_name="executor.emit.stall",
            stage_names=("fetch", "decode"),
            health=self._health if token is not None else None,
            health_token=token,
        )
        inner = core.run(tasks)  # admits the first window eagerly

        def adapt() -> Iterator[ShardResult]:
            try:
                for idx, value, secs in inner:
                    yield ShardResult(tasks[idx].shard_id, value,
                                      secs[0], secs[1])
            finally:
                # Same lifecycle as the stage pools (core.run's emit
                # closes them in ITS finally): an abort or exhausted
                # run must not leave hedge duplicates in flight.
                if res is not None:
                    res.close()

        return adapt()

    def _decode_with_refetch(self, task: ShardTask, payload: Any) -> Any:
        """Stage B with the transient-escape hatch: decode is normally
        pure CPU over fetched bytes, but the salvage paths (BGZF
        re-sync, VCF line extension) and CRAM reference fetch can issue
        fresh reads. A transient there re-runs the shard from fetch
        under the task's retrier — the bounded equivalent of the
        sequential path's whole-shard retry."""
        try:
            return task.decode(payload)
        except Exception as e:  # noqa: BLE001 — classified below
            if task.retrier is None or not is_transient(e):
                raise
            task.retrier.retried += 1  # the attempt that just failed

            def rerun():
                return task.decode(task.fetch())

            return task.retrier.call(rerun, what=task.what)


def executor_for_storage(storage) -> ShardPipelineExecutor:
    """Build the shard executor from a storage builder's
    ``DisqOptions`` (absent/None ⇒ sequential-compatible defaults).
    This is also where live introspection and adaptive resilience turn
    on for a read: the options' endpoint / watchdog / progress-log /
    hedging / deadline knobs are resolved once per run, and the
    default (nothing configured) hands the executor ``health=None`` /
    ``resilience=None`` — the no-op path."""
    from disq_tpu.runtime import profiler
    from disq_tpu.runtime.introspect import configure_from_options
    from disq_tpu.runtime.resilience import resilience_for_options

    opts = getattr(storage, "_options", None) or DisqOptions()
    flightrec.configure_from_options(opts)
    profiler.configure_from_options(opts)
    cache_blocks = getattr(opts, "http_cache_blocks", None)
    if cache_blocks:
        from disq_tpu.fsw.http import configure_cache_blocks

        configure_cache_blocks(cache_blocks)
    return ShardPipelineExecutor(
        workers=getattr(opts, "executor_workers", 1),
        prefetch_shards=getattr(opts, "prefetch_shards", None),
        health=configure_from_options(opts),
        watchdog_stall_s=getattr(opts, "watchdog_stall_s", None),
        watchdog_policy=getattr(opts, "watchdog_policy", "warn"),
        resilience=resilience_for_options(opts),
    )


def read_ledger_for_storage(storage, path: str, n_shards: int):
    """The crash-resume read ledger for one read, or None when
    ``DisqOptions.read_ledger`` is unset (the default — no directory,
    no spill I/O).  The params fingerprint ties the ledger to this
    exact input shape AND to every option that changes what a shard
    decodes to (policy, deadline fallback): resuming against a
    different path, split count, or decode-affecting option resets the
    ledger instead of serving stale shards."""
    opts = getattr(storage, "_options", None) or DisqOptions()
    base = getattr(opts, "read_ledger", None)
    if not base:
        return None
    from disq_tpu.runtime.errors import ErrorPolicy
    from disq_tpu.runtime.manifest import ReadLedger

    from disq_tpu.runtime.columnar import resident_decode_enabled

    return ReadLedger(base, params={
        "path": path,
        "shards": int(n_shards),
        "error_policy": ErrorPolicy.coerce(opts.error_policy).value,
        "shard_deadline_s": getattr(opts, "shard_deadline_s", None),
        # resident decode changes the spilled shard *type* (ColumnarBatch
        # spills rebuild device-side on load) — toggling it between a
        # crashed and a resumed run must reset the ledger, not serve
        # stale host-form spills
        "resident_decode": bool(resident_decode_enabled(storage)),
    })


def map_ordered_resumable(executor: ShardPipelineExecutor,
                          tasks: Sequence[ShardTask],
                          ledger=None) -> Iterator[ShardResult]:
    """``executor.map_ordered`` with read-side crash resume: shards the
    ledger already holds are served from their spills (zero fetch /
    decode), fresh shards run through the executor and are spilled as
    they emit, and a fully consumed run hits the ledger's commit point
    (``finish`` — spills dropped, next run starts clean).  Without a
    ledger this is exactly ``map_ordered`` (the zero-overhead path)."""
    tasks = list(tasks)
    if ledger is None:
        return executor.map_ordered(tasks)

    def gen() -> Iterator[ShardResult]:
        cached = {t.shard_id for t in tasks if ledger.is_done(t.shard_id)}
        fresh = executor.map_ordered(
            [t for t in tasks if t.shard_id not in cached])
        for t in tasks:
            if t.shard_id in cached:
                yield ShardResult(t.shard_id, ledger.load(t.shard_id))
            else:
                res = next(fresh)
                ledger.record(res.shard_id, res.value)
                yield res
        ledger.finish()

    return gen()


# ---------------------------------------------------------------------------
# Write direction: encode → deflate → stage
# ---------------------------------------------------------------------------


@dataclass
class WriteShardTask:
    """One shard's write-direction pipeline work. ``encode`` slices the
    batch and encodes records (CPU); ``deflate`` compresses and does
    voffset/index arithmetic (native-threaded CPU; None ⇒ pass-through
    for uncompressed formats); ``stage`` durably writes the part +
    index fragments (I/O; None ⇒ the caller consumes the payload at
    ordered emit — single-stream sinks like BCF). ``retrier`` guards
    only the stage step: encode/deflate are pure CPU, while a staged
    write can hit the same transient faults a read can."""

    shard_id: int
    encode: Callable[[], Any]
    deflate: Optional[Callable[[Any], Any]] = None
    stage: Optional[Callable[[Any], Any]] = None
    retrier: Optional[ShardRetrier] = None
    what: str = "write"
    # estimated output byte range of this shard's part within the
    # merged file (uncompressed record bytes) — the write-lease
    # locality hint: scheduled_write_stage registers it with the
    # coordinator so write leases score contiguity/cache locality the
    # way read leases do, instead of FIFO-only.  None (default) keeps
    # the pure-FIFO write lease.
    byte_range: Optional[Tuple[int, int]] = None


@dataclass
class WriteShardResult:
    """Ordered emission unit of the write pipeline: the stage step's
    return value (the shard's part record) plus per-stage wall time."""

    shard_id: int
    value: Any
    encode_seconds: float = 0.0
    deflate_seconds: float = 0.0
    stage_seconds: float = 0.0

    @property
    def wall_seconds(self) -> float:
        return (self.encode_seconds + self.deflate_seconds
                + self.stage_seconds)


@dataclass
class WriterStats:
    """Aggregate write-pipeline observability (cumulative across runs
    on the same pipeline instance)."""

    workers: int = 0
    window: int = 0
    shards: int = 0
    encode_seconds: float = 0.0
    deflate_seconds: float = 0.0
    stage_seconds: float = 0.0
    emit_stall_seconds: float = 0.0
    max_in_flight: int = 0

    def as_dict(self) -> Dict[str, float]:
        return {
            "workers": self.workers,
            "window": self.window,
            "shards": self.shards,
            "encode_seconds": round(self.encode_seconds, 6),
            "deflate_seconds": round(self.deflate_seconds, 6),
            "stage_seconds": round(self.stage_seconds, 6),
            "emit_stall_seconds": round(self.emit_stall_seconds, 6),
            "max_in_flight": self.max_in_flight,
        }


class ShardWritePipeline:
    """Bounded three-stage write pipeline over the shared core: encode
    → deflate → stage, ordered streaming emit.

    Guarantees mirror the read executor's: results emit in task order,
    per-shard bytes are produced by the exact per-shard code the
    sequential loop runs (⇒ byte-identical merged output at any
    ``workers``), ``workers=1`` runs everything inline on the caller's
    thread in the historical call order, and at most
    ``prefetch_shards`` shards past the emit frontier are in flight
    (default ``2 × workers``), bounding peak memory to ``window ×
    (uncompressed + compressed shard bytes)``."""

    def __init__(self, workers: int = 1,
                 prefetch_shards: Optional[int] = None,
                 health=None,
                 watchdog_stall_s: Optional[float] = None,
                 watchdog_policy: str = "warn") -> None:
        self.workers = max(1, int(workers))
        if prefetch_shards is None:
            prefetch_shards = 2 * self.workers
        self.prefetch_shards = max(1, int(prefetch_shards))
        # As in the read executor, prefetch_shards IS the in-flight
        # bound (the memory cap the docstring promises), even below
        # ``workers``.
        self.stats = WriterStats(
            workers=self.workers,
            window=self.prefetch_shards,
        )
        # Live introspection (see ShardPipelineExecutor / introspect.py).
        self._health = health
        self._watchdog_stall_s = watchdog_stall_s
        self._watchdog_policy = watchdog_policy

    # -- public -------------------------------------------------------------

    def map_ordered(
        self, tasks: Sequence[WriteShardTask]
    ) -> Iterator[WriteShardResult]:
        tasks = list(tasks)
        self.stats.shards += len(tasks)
        if not tasks:
            return iter(())
        token = None
        if self._health is not None:
            token = self._health.register_run(
                "write", len(tasks), self._watchdog_stall_s,
                self._watchdog_policy)
        if self.workers == 1:
            inner = self._run_sequential(tasks, token)
        else:
            inner = self._run_pipelined(tasks, token)
        if token is None:
            return inner
        return _tracked(inner, self._health, token)

    # -- stage bodies (shared by both paths) --------------------------------

    @staticmethod
    def _encode(task: WriteShardTask, _payload: Any) -> Any:
        return task.encode()

    @staticmethod
    def _deflate(task: WriteShardTask, payload: Any) -> Any:
        if task.deflate is None:
            return payload
        return task.deflate(payload)

    @staticmethod
    def _stage(task: WriteShardTask, payload: Any) -> Any:
        if task.stage is None:
            return payload
        if task.retrier is not None:
            return task.retrier.call(
                lambda: task.stage(payload), what=f"{task.what}.stage")
        return task.stage(payload)

    # -- sequential (workers=1): the historical per-shard loop order --------

    def _run_sequential(
        self, tasks: List[WriteShardTask],
        token: Optional[int] = None,
    ) -> Iterator[WriteShardResult]:
        health = self._health if token is not None else None
        try:
            for task in tasks:
                secs = []
                payload = None
                for name, fn in (("encode", self._encode),
                                 ("deflate", self._deflate),
                                 ("stage", self._stage)):
                    _check_abort(health, token)
                    if health is not None:
                        health.beat(token, name, task.shard_id)
                    t0 = time.perf_counter()
                    payload = fn(task, payload)
                    secs.append(time.perf_counter() - t0)
                    if health is not None:
                        health.clear(token, name, task.shard_id)
                self.stats.encode_seconds += secs[0]
                self.stats.deflate_seconds += secs[1]
                self.stats.stage_seconds += secs[2]
                yield WriteShardResult(task.shard_id, payload, *secs)
        except GeneratorExit:
            raise  # early close of the iterator, not an abort
        except BaseException as e:
            flightrec.note_abort(e, where="inline")
            raise

    # -- pipelined (workers>1) ----------------------------------------------

    def _run_pipelined(
        self, tasks: List[WriteShardTask],
        token: Optional[int] = None,
    ) -> Iterator[WriteShardResult]:
        def on_admit(depth: int) -> None:
            if depth > self.stats.max_in_flight:
                self.stats.max_in_flight = depth
            observe_gauge("writer.in_flight", depth)

        # A stage that is None on EVERY task (SAM/CRAM have no deflate,
        # BCF's stream write happens at emit) is dropped from the
        # pipeline entirely — no idle thread pool, no per-shard queue
        # hop for an identity function.
        stage_attrs = [("encode_seconds", self._encode, "disq-encode")]
        if any(t.deflate is not None for t in tasks):
            stage_attrs.append(
                ("deflate_seconds", self._deflate, "disq-deflate"))
        if any(t.stage is not None for t in tasks):
            stage_attrs.append(("stage_seconds", self._stage, "disq-stage"))
        attr_names = [a for a, _f, _p in stage_attrs]

        def on_result(seconds: List[float]) -> None:
            for name, s in zip(attr_names, seconds):
                setattr(self.stats, name, getattr(self.stats, name) + s)

        def on_stall(stall: float, task: WriteShardTask) -> None:
            self.stats.emit_stall_seconds += stall
            if stall > 0.0005:
                record_span("writer.emit.stall", stall,
                            shard=task.shard_id)

        core = _BoundedStagePipeline(
            workers=self.workers,
            window=self.stats.window,
            stage_fns=[f for _a, f, _p in stage_attrs],
            thread_prefixes=[p for _a, _f, p in stage_attrs],
            on_admit=on_admit,
            on_result=on_result,
            on_stall=on_stall,
            stall_name="writer.emit.stall",
            drain_on_close=True,
            # "encode_seconds" -> heartbeat stage name "encode", etc.
            stage_names=[a.split("_", 1)[0] for a in attr_names],
            health=self._health if token is not None else None,
            health_token=token,
        )
        inner = core.run(tasks)  # admits the first window eagerly

        def adapt() -> Iterator[WriteShardResult]:
            for idx, value, secs in inner:
                by_attr = dict(zip(attr_names, secs))
                yield WriteShardResult(
                    tasks[idx].shard_id, value,
                    by_attr.get("encode_seconds", 0.0),
                    by_attr.get("deflate_seconds", 0.0),
                    by_attr.get("stage_seconds", 0.0),
                )

        return adapt()


def writer_for_storage(storage) -> ShardWritePipeline:
    """Build the write pipeline from a storage builder's
    ``DisqOptions`` (absent/None ⇒ sequential-compatible defaults).
    Live-introspection knobs resolve here for writes, mirroring
    ``executor_for_storage`` for reads."""
    from disq_tpu.runtime import profiler
    from disq_tpu.runtime.introspect import configure_from_options

    opts = getattr(storage, "_options", None) or DisqOptions()
    flightrec.configure_from_options(opts)
    profiler.configure_from_options(opts)
    return ShardWritePipeline(
        workers=getattr(opts, "writer_workers", 1),
        prefetch_shards=getattr(opts, "writer_prefetch_shards", None),
        health=configure_from_options(opts),
        watchdog_stall_s=getattr(opts, "watchdog_stall_s", None),
        watchdog_policy=getattr(opts, "watchdog_policy", "warn"),
    )


def write_retrier_for_storage(storage, path: Optional[str] = None
                              ) -> ShardRetrier:
    """A fresh per-shard retrier sized from the storage's retry knobs —
    the write-side analogue of ``context_for_storage().for_shard()``
    (writes carry no corrupt-block policy, only transient retry).
    With ``path`` and an armed ``breaker_window``, the retrier is also
    gated by the per-filesystem circuit breaker guarding the output's
    store, and every write retry draws from the shared retry budget."""
    opts = getattr(storage, "_options", None) or DisqOptions()
    breaker = None
    if (getattr(opts, "retry_budget_tokens", None) is not None
            or getattr(opts, "breaker_window", None) is not None):
        from disq_tpu.runtime.resilience import (
            breaker_for,
            configure_globals_from_options,
        )

        configure_globals_from_options(opts)
        if path is not None:
            breaker = breaker_for(path)
    return ShardRetrier(opts.max_retries, opts.retry_backoff_s,
                        breaker=breaker)


def _retrying(fn: Optional[Callable], retries: int) -> Optional[Callable]:
    """``fn`` re-run up to ``retries`` extra times on ANY exception —
    the per-shard Spark-task-retry analogue ``StageManifest.run_stage``
    applies, preserved for checkpointed pipeline runs (the pipeline's
    own ``ShardRetrier`` only retries transient-classified faults)."""
    if fn is None or retries <= 0:
        return fn

    def wrapped(*args: Any):
        last: Optional[BaseException] = None
        for _attempt in range(retries + 1):
            try:
                return fn(*args)
            except Exception as e:  # noqa: BLE001 — shard-level retry
                last = e
        raise last

    return wrapped


def run_write_stage(
    pipeline: ShardWritePipeline,
    n_shards: int,
    make_task: Callable[[int], WriteShardTask],
    manifest=None,
    stage_name: str = "write.parts",
    retries: int = 1,
    storage=None,
    path: Optional[str] = None,
    fs=None,
) -> List[Any]:
    """Run one write stage's shards through ``pipeline``, shard-level
    resumable. With a manifest, shards already recorded are skipped,
    each stage step keeps ``run_stage``'s any-exception shard retry
    (``retries`` extra attempts), and each fresh shard is recorded the
    moment its stage step durably completes — in *completion* order on
    the stage worker, not emit order, so a crash mid-run preserves
    every staged shard even when a straggler holds up the ordered
    emit. Returns the per-shard info list in shard order, mixing
    cached and fresh results.

    With ``storage`` + ``path`` AND a manifest AND the shard scheduler
    armed, the stage instead leases its shards through the coordinator
    (``scheduler.scheduled_write_stage`` — the write direction of the
    distributed data plane, with the manifest as the durable side);
    ``fs`` (the destination filesystem) feeds the worker's block-cache
    locality hint into those leases.  Otherwise this inline path runs
    unchanged, allocating nothing extra."""
    from dataclasses import replace

    if manifest is not None and storage is not None and path is not None:
        from disq_tpu.runtime import scheduler

        if scheduler.write_leasing_armed(storage):
            return scheduler.scheduled_write_stage(
                storage, path, pipeline, n_shards, make_task, manifest,
                stage_name=stage_name, retries=retries, fs=fs)

    infos: List[Any] = [None] * n_shards
    pending: List[int] = []
    for k in range(n_shards):
        if manifest is not None and manifest.is_done(stage_name, k):
            infos[k] = manifest.shard_info(stage_name, k)
        else:
            pending.append(k)

    tasks = []
    for k in pending:
        task = make_task(k)
        if manifest is not None:
            inner = _retrying(task.stage, retries)

            def marked(payload, _inner=inner, _k=k):
                info = _inner(payload) if _inner is not None else payload
                manifest.mark_done(stage_name, _k, info)
                return info

            task = replace(
                task,
                encode=_retrying(task.encode, retries),
                deflate=_retrying(task.deflate, retries),
                stage=marked,
            )
        tasks.append(task)

    for res in pipeline.map_ordered(tasks):
        infos[res.shard_id] = res.value
    return infos
