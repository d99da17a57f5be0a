#!/usr/bin/env python
"""check_overhead — guard the zero-overhead invariant of the disabled
telemetry/introspection path (tier-1 via ``tests/test_overhead.py``).

Every per-shard observability hook in the pipelines is designed to be
free when nothing is watching: ``health is None`` skips the heartbeat
stamps, ``note_shard_counters`` returns after ONE boolean test, and no
knob configured means no thread and no socket.  This script fails if
that ever regresses:

1. **Structural**: with default ``DisqOptions``,
   ``configure_from_options`` returns None (the pipelines then carry
   ``health=None``); ``HEALTH.live`` is False; no ``disq-watchdog`` /
   ``disq-introspect`` thread exists.
2. **Timing**: per-shard cost of the inline (workers=1) executor over
   trivial tasks, and per-call cost of ``note_shard_counters`` with
   nothing live, measured as a median of several rounds and asserted
   under generous absolute budgets — "no measurable cost" at the
   scale of a real shard (tens of milliseconds of decode), with 10x+
   headroom against CI noise.

Run directly: ``python scripts/check_overhead.py`` (exit 0 ok).
"""

from __future__ import annotations

import os
import statistics
import sys
import threading
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Budgets (generous on purpose: the guard is against O(ms) accidental
# work — a stray scrape, an unconditional heartbeat, a socket — not
# against the ~10 us a span context manager inherently costs).
SHARD_BUDGET_US = 500.0      # per-shard inline-executor overhead
NOTE_BUDGET_US = 5.0         # per-call note_shard_counters, disabled
ROUNDS = 5
SHARDS = 400
NOTE_CALLS = 20000


def _median_per_unit_us(fn, units: int, rounds: int = ROUNDS) -> float:
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) / units * 1e6)
    return statistics.median(times)


def main() -> int:
    errors = []

    from disq_tpu.runtime.counters import ShardCounters
    from disq_tpu.runtime.errors import DisqOptions
    from disq_tpu.runtime.executor import (
        ShardPipelineExecutor, ShardTask, executor_for_storage)
    from disq_tpu.runtime.introspect import (
        HEALTH, configure_from_options, introspect_address,
        note_shard_counters)

    # -- 1. structural: the default path must configure NOTHING --------------
    class _Storage:
        _options = DisqOptions()

    if configure_from_options(DisqOptions()) is not None:
        errors.append(
            "configure_from_options(default DisqOptions) returned a "
            "health board — pipelines would stamp heartbeats on the "
            "default path")
    ex = executor_for_storage(_Storage())
    if ex._health is not None:
        errors.append("executor_for_storage wired a health board with "
                      "no knob configured")
    if HEALTH.live:
        errors.append("HEALTH.live is True with nothing configured")
    if introspect_address() is not None:
        errors.append("introspection endpoint running with no knob set")
    bad_threads = [
        t.name for t in threading.enumerate()
        if t.name.startswith(
            ("disq-watchdog", "disq-introspect", "disq-device",
             "disq-hostwork", "disq-profiler", "disq-serve",
             "disq-slo", "disq-fleet", "disq-hedge"))
    ]
    if bad_threads:
        errors.append(f"stray observability threads: {bad_threads}")

    # -- 1a. flight recorder + profiler: disabled ⇒ nothing exists -----------
    from disq_tpu.runtime import flightrec, profiler

    if flightrec.enabled() or flightrec.recorder() is not None:
        errors.append(
            "flight recorder instantiated with no postmortem knob — "
            "the default path must allocate no event ring")
    if profiler.active_profiler() is not None:
        errors.append(
            "sampling profiler running with no profile_hz knob — the "
            "default path must spawn zero profiler threads")

    # -- 1b. device decode service: disabled ⇒ no thread, no queue -----------
    from disq_tpu.runtime import device_service

    if device_service.enabled():
        errors.append(
            "DISQ_TPU_DEVICE_SERVICE leaked into the guard's env — the "
            "default path must not route decode through the service")
    if device_service.service_if_running() is not None:
        errors.append(
            "device decode service instantiated with no flag set — the "
            "disabled path must spawn zero dispatcher threads")

    # -- 1b3. shard scheduler: disabled ⇒ no coordinator, inline loop --------
    from disq_tpu.runtime import scheduler
    from disq_tpu.runtime.executor import map_ordered_resumable  # noqa: F401
    from disq_tpu.runtime.scheduler import (
        client_for_storage, scheduled_map_ordered)

    if os.environ.get("DISQ_TPU_SCHED"):
        errors.append(
            "DISQ_TPU_SCHED leaked into the guard's env — the default "
            "path must run the static split loops")
    if client_for_storage(_Storage()) is not None:
        errors.append(
            "client_for_storage built a scheduler client with no knob "
            "configured — sources would RPC on the default path")
    if scheduler.active_coordinator() is not None:
        errors.append(
            "a shard coordinator exists with no scheduler knob set — "
            "the scheduler-off path must allocate no queue state")
    sched_gen = scheduled_map_ordered(
        _Storage(), None, "overhead-guard", ShardPipelineExecutor(workers=1),
        [ShardTask(shard_id=0, fetch=lambda: 0,
                   decode=lambda payload: payload)])
    if getattr(sched_gen, "gi_code", None) is None \
            or sched_gen.gi_code.co_name != "_run_sequential":
        errors.append(
            "scheduled_map_ordered(scheduler off) did not return the "
            "inline map_ordered generator — the default split loop "
            "grew a wrapper")
    list(sched_gen)
    if any(t.name.startswith("disq-sched")
           for t in threading.enumerate()):
        errors.append(
            "stray scheduler thread on the disabled path")
    # failover off must keep the PR 12 guarantee exactly: no journal
    # object (⇒ no journal file is ever created), no standby machinery,
    # and the write path never consults the coordinator
    if os.environ.get("DISQ_TPU_SCHED_FAILOVER"):
        errors.append(
            "DISQ_TPU_SCHED_FAILOVER leaked into the guard's env — the "
            "default path must not arm coordinator failover")
    if scheduler.active_journal() is not None:
        errors.append(
            "a scheduler journal exists with failover off — the "
            "default path must write no journal file")
    if scheduler.write_leasing_armed(_Storage()):
        errors.append(
            "write_leasing_armed(default storage) is True — write "
            "stages would RPC on the default path")
    if any(t.name.startswith(("disq-standby", "disq-failover"))
           for t in threading.enumerate()):
        errors.append(
            "stray failover standby thread on the disabled path — "
            "election must be lazy (probe on RPC failure), never a "
            "resident thread")

    # -- 1b4. serving plane: off ⇒ no daemon, caches or admission state ------
    from disq_tpu.runtime import serve as serve_plane

    if serve_plane.serve_if_running() is not None:
        errors.append(
            "a serve daemon exists with no serve() call — the serve-off "
            "path must hold no registry, cache or admission state")
    code, _body = serve_plane.handle_http("POST", "/query/reads", {})
    if code != 503:
        errors.append(
            f"serve.handle_http answered {code} with no daemon running "
            "— the serve-off path must 503 without serving")
    if serve_plane.serve_if_running() is not None:
        errors.append(
            "handle_http on the serve-off path allocated the daemon — "
            "only start_serve() may create caches/admission state")
    if "disq_tpu.runtime.fleet" in sys.modules:
        errors.append(
            "exercising the serve plane imported runtime.fleet — the "
            "/serve/* path must stay byte-identical to the pre-fleet "
            "serving plane and never consult the router module")

    # -- 1b5. fleet tier: off ⇒ no router, thread, socket or fleet state ----
    # Capture the serve-off answers first: importing/exercising the
    # fleet module must leave /serve/* byte-identical.
    import json as _json

    serve_before = [
        serve_plane.handle_http("POST", "/query/reads", {}),
        serve_plane.handle_http("GET", "/serve/stats", {}),
        serve_plane.handle_http("GET", "/serve/cachemap", {}),
    ]
    from disq_tpu.runtime import fleet as fleet_plane

    if fleet_plane.fleet_if_running() is not None:
        errors.append(
            "a fleet router exists with no start_fleet() call — the "
            "fleet-off path must hold no replica or digest state")
    code, _body = fleet_plane.handle_http("POST", "/fleet/query/reads", {})
    if code != 503:
        errors.append(
            f"fleet.handle_http answered {code} with no router running "
            "— the fleet-off path must 503 without routing")
    if fleet_plane.fleet_if_running() is not None:
        errors.append(
            "handle_http on the fleet-off path allocated the router — "
            "only start_fleet() may create clients/digest state")
    if any(t.name.startswith(("disq-fleet", "disq-hedge"))
           for t in threading.enumerate()):
        errors.append(
            "stray fleet/hedge thread on the disabled path — the "
            "router owns no threads and the hedge pool is lazy")
    serve_after = [
        serve_plane.handle_http("POST", "/query/reads", {}),
        serve_plane.handle_http("GET", "/serve/stats", {}),
        serve_plane.handle_http("GET", "/serve/cachemap", {}),
    ]
    if _json.dumps(serve_before) != _json.dumps(serve_after):
        errors.append(
            "/serve/* answers changed after exercising the fleet-off "
            "path — fleet must not perturb the serving plane")

    # -- 1c. resident decode: disabled ⇒ no ColumnarBatch device builds ------
    from disq_tpu.runtime import columnar

    if columnar.resident_decode_enabled(_Storage()):
        errors.append(
            "DISQ_TPU_RESIDENT_DECODE leaked into the guard's env — "
            "the default path must decode to host ReadBatch objects")
    if columnar.device_batches_built() != 0:
        errors.append(
            f"{columnar.device_batches_built()} device-backed "
            "ColumnarBatch builds on the disabled path — resident "
            "decode off must allocate nothing on device")

    # -- 1d. device mesh: off ⇒ no Mesh object, no resharding ----------------
    from disq_tpu.runtime import mesh as mesh_mod
    from disq_tpu.runtime.tracing import REGISTRY

    if os.environ.get("DISQ_TPU_MESH"):
        errors.append(
            "DISQ_TPU_MESH leaked into the guard's env — the default "
            "path must run single-device dispatch")
    if mesh_mod.mesh_devices_requested(_Storage()) is not None:
        errors.append(
            "mesh_devices_requested(default storage) is not None — "
            "resident reads would branch onto mesh code by default")
    if mesh_mod.mesh_for_storage(_Storage()) is not None:
        errors.append(
            "mesh_for_storage(default storage) built a mesh — the "
            "mesh-off path must construct no Mesh object")
    if mesh_mod.mesh_if_built() is not None:
        errors.append(
            "a Mesh object exists with no mesh knob set — some default "
            "code path constructed one")
    if mesh_mod.service_devices() != [None]:
        errors.append(
            f"service_devices() = {mesh_mod.service_devices()} with "
            "mesh off — the decode service must keep single default-"
            "device dispatch (one sub-queue, no per-device state)")
    for name in ("device.mesh.reshard_bytes",
                 "device.mesh.exchange_bytes",
                 "device.mesh.batches"):
        if REGISTRY.counter(name).total() != 0:
            errors.append(
                f"{name} is nonzero on the mesh-off path — no bytes "
                "may move and no batches may shard by default")

    # -- 1e. request tracing + SLOs: unconfigured ⇒ nothing minted -----------
    from disq_tpu.runtime import slo as slo_mod
    from disq_tpu.runtime import tracing as tracing_mod

    if tracing_mod.trace_requests_enabled():
        errors.append(
            "DISQ_TPU_TRACE_REQUESTS leaked into the guard's env — the "
            "serving edge must mint no trace ids by default")
    if tracing_mod.current_trace() is not None:
        errors.append(
            "a trace context is active with nothing configured — the "
            "default path must carry an empty ContextVar")
    probe_headers = {"Range": "bytes=0-1"}
    if tracing_mod.inject_trace_headers(dict(probe_headers)) \
            != probe_headers:
        errors.append(
            "inject_trace_headers added headers with no active trace — "
            "every HTTP hop would grow bytes on the default path")
    if tracing_mod.trace_ids_minted() != 0:
        errors.append(
            f"{tracing_mod.trace_ids_minted()} trace ids minted on the "
            "tracing-off path (the 1b4 serve exercise ran with tracing "
            "unconfigured) — the serving hot path must mint no uuids "
            "by default")
    if slo_mod.evaluator_if_running() is not None:
        errors.append(
            "an SLO evaluator is running with no DISQ_TPU_SLO / "
            "DisqOptions.slo configured — the default path must start "
            "no disq-slo thread")

    # -- 1f. operator suite: off ⇒ no masks, no operator imports -------------
    # The resident operator chain (runtime/oppipe.py + ops/{rfilter,
    # markdup,pileup,rgstats}.py) is pay-for-what-you-chain: with no
    # read_filter configured and no pipeline() call, the decode path
    # must build no mask, import no operator module and count nothing.
    if os.environ.get("DISQ_TPU_READ_FILTER"):
        errors.append(
            "DISQ_TPU_READ_FILTER leaked into the guard's env — the "
            "default decode must compact nothing")
    if DisqOptions().read_filter is not None:
        errors.append(
            "DisqOptions().read_filter is not None by default — every "
            "decode would parse a filter spec")
    from disq_tpu.bam.source import BamSource

    class _FilterlessSource(BamSource):
        def __init__(self):  # probe _read_filter without opening a file
            self._storage = _Storage()

    if _FilterlessSource()._read_filter() is not None:
        errors.append(
            "BamSource._read_filter() built a filter with no spec "
            "configured — the default decode would mask every shard")
    op_mods = [m for m in sys.modules
               if m == "disq_tpu.runtime.oppipe"
               or m in ("disq_tpu.ops.rfilter", "disq_tpu.ops.markdup",
                        "disq_tpu.ops.pileup", "disq_tpu.ops.rgstats")]
    if op_mods:
        errors.append(
            f"operator modules imported on the suite-off path: "
            f"{op_mods} — filter/markdup/pileup/rgstats must load only "
            "behind a spec, a pipeline() call or a /query/* endpoint")
    for name in ("ops.filter.records_in", "ops.markdup.duplicates",
                 "ops.pileup.records"):
        if REGISTRY.counter(name).total() != 0:
            errors.append(
                f"{name} is nonzero on the suite-off path — no operator "
                "may examine records by default")

    # -- 2. timing: per-shard inline-executor overhead -----------------------
    sink = []

    def run_executor():
        tasks = [
            ShardTask(shard_id=i, fetch=lambda: 0,
                      decode=lambda payload: payload)
            for i in range(SHARDS)
        ]
        sink.extend(
            r.value for r in ShardPipelineExecutor(
                workers=1).map_ordered(tasks))
        sink.clear()

    run_executor()  # warm-up
    per_shard_us = _median_per_unit_us(run_executor, SHARDS)
    if per_shard_us > SHARD_BUDGET_US:
        errors.append(
            f"inline executor costs {per_shard_us:.1f} us/shard with "
            f"telemetry disabled (budget {SHARD_BUDGET_US} us) — the "
            "zero-overhead path grew measurable work")

    # -- 3. timing: note_shard_counters with nothing watching ----------------
    counters = ShardCounters(shard_id=0)

    def run_notes():
        for _ in range(NOTE_CALLS):
            note_shard_counters("read", counters)

    run_notes()  # warm-up
    per_note_us = _median_per_unit_us(run_notes, NOTE_CALLS)
    if per_note_us > NOTE_BUDGET_US:
        errors.append(
            f"note_shard_counters costs {per_note_us:.2f} us/call "
            f"disabled (budget {NOTE_BUDGET_US} us) — it must return "
            "after one boolean test")

    # -- 4. timing: record_event with the recorder off -----------------------
    def run_events():
        for _ in range(NOTE_CALLS):
            flightrec.record_event("retry", what="x")

    run_events()  # warm-up
    per_event_us = _median_per_unit_us(run_events, NOTE_CALLS)
    if per_event_us > NOTE_BUDGET_US:
        errors.append(
            f"flightrec.record_event costs {per_event_us:.2f} us/call "
            f"disabled (budget {NOTE_BUDGET_US} us) — it must return "
            "after one global-is-None test")
    if flightrec.recorder() is not None:
        errors.append(
            "record_event on the disabled path allocated a recorder — "
            "the event ring must only exist once a knob configures it")

    if errors:
        print(f"check_overhead: {len(errors)} problem(s)")
        for e in errors:
            print(f"  - {e}")
        return 1
    print(
        "check_overhead: OK "
        f"(executor {per_shard_us:.1f} us/shard, "
        f"note_shard_counters {per_note_us:.3f} us/call, "
        f"record_event {per_event_us:.3f} us/call, "
        "no stray threads)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
