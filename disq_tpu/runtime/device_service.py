"""Cross-shard device decode service — one dispatcher owns the device
queue and feeds the 128-lane SIMD codecs at full lane utilization.

Why: the SIMD kernels decode 128 independent streams per launch, but
the per-shard dispatch in ``bgzf/codec.py`` / ``cram/rans.py`` submits
one shard's blocks at a time — a shard with 40 BGZF blocks launches a
40/128-full chunk, and N executor decode workers each do so
*concurrently*, so the device sees N partial launches instead of the
few full ones the work actually needs (``TPU_KERNELS.json`` carries the
kernel-only and end-to-end rows; the gap between them is host packing,
per-chunk allocation, transfers and partial lanes).

This module inverts the ownership, the way "Extending TensorFlow's
Semantics with Pipelined Execution" overlaps producer/consumer stages:
executor decode stages submit their shard's block batch
(``submit_inflate`` / ``submit_rans``) and get a future back; ONE
dispatcher thread coalesces blocks *across* in-flight shards into full
128-lane chunks (flushing on full, on an oldest-lane timeout, or at
drain), keeps an adaptive window of launches in flight
(``inflate_simd.dispatch_window``), packs into pooled staging arenas,
and writes each decoded lane straight from the kernel's transposed
output into the owning submission's preallocated blob — zero
intermediate ``bytes`` objects on the device path.

An inflate submission's blob is the finished product when its last
lane lands.  Given the blocks' footer CRC32s, the service checks each
launch's lanes against them as it delivers that launch — a few
host-pool tasks a launch, never on the dispatcher thread — and
``result()`` returns only when every block is stored AND checked (a
mismatch fails the owner with ``BGZF CRC mismatch at block i``), so no
whole-split check is left for after the pass's last launch, when the
device has nothing else to do.  Asked for a ``padded`` blob (the caller
will parse it on the device), the service allocates it at the parse's
upload shape (``util.pad_quantum`` words, the tail zeroed at
submission) and keeps that buffer as ``Submission.base``: the parse
uploads it whole instead of copying the blob into a buffer of its own.

Multi-chip (the mesh-native pipeline, ``runtime/mesh.py``): when the
mesh knob is armed at service creation, each codec keeps one sub-queue
PER DEVICE and the single dispatcher feeds them all — a submission's
lanes land on the least-loaded device (lanes queued or in flight;
ties rotate), each launch runs under
``jax.default_device(dev)`` (const tables and staging land on that
chip, ``inflate_simd._device_const_tables`` is device-keyed), the next
chunk is taken for the chip with the fewest launches in flight, and
the in-flight window scales by the device count, so every chip keeps a
full pipeline instead of one chip taking all launches.  Mesh off, the
device list is ``[None]`` and every code path below degenerates to the
exact single-queue behavior it had before.

Error isolation is strict per submission: a lane the kernel flags is
re-inflated on host; if the host also fails (truly corrupt input) only
the OWNER shard's future raises — lanes co-batched from other shards
are delivered regardless.  Oversize payloads (a raw stream over BGZF's
largest payload, or one that decodes to over 64 KiB: no BGZF block)
never enter the queue: they decode on the submitting shard's own
thread, exactly like the per-shard dispatch did.  Every BGZF block goes
to the device; the blocks whose payload is over the narrow launch
geometry's 32,752 bytes queue apart (``inflate.wide``) and launch at
the wide one, so each launch's geometry is its queue's.

Telemetry: ``device.lane_fill`` (lanes per launch / 128),
``device.queue_depth``, ``device.batch.flush{reason=full|timeout|drain}``,
``device.service.wait`` (oldest-lane queue wait per flushed chunk) and
the arena pool's ``device.arena_bytes``.  The dispatcher thread's own
time is tiled by six spans a launch, joined by ``kind``, ``lanes`` and
the per-service sequence number ``launch``: ``device.service.idle``
(asleep since the previous launch; booked as the launch begins, also
when 0, and once more at close for the sleep that close ended),
``device.launch.pack`` (host lane packing), ``.submit`` (upload +
enqueue), ``.wait`` (blocked on the kernel), ``.d2h`` (the copy back)
and ``.deliver`` (lanes handed to their submissions).  The sleep is
also the counter ``device.service.idle_seconds{reason=empty|filling}``
(nothing queued: the producers starve the device; lanes queued that
wait for company under the flush timeout), exact between any two
``telemetry_snapshot()``s: the service books the sleep so far whenever
one is taken (``tracing.on_snapshot``), the dispatcher the rest when it
wakes.  Under a profiler capture the sleep itself is the annotation
``disq_tpu.device.service.idle``, on the device trace's clock.

Enablement: ``DISQ_TPU_DEVICE_SERVICE=1`` — checked by the codec entry
points alongside ``DISQ_TPU_DEVICE_INFLATE`` / ``DISQ_TPU_DEVICE_RANS``.
Disabled (the default), no thread, queue or arena exists and the
per-shard dispatch runs exactly as before — the zero-overhead contract
``scripts/check_overhead.py`` guards.
"""

from __future__ import annotations

import threading
import time
import zlib
from collections import Counter, deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from disq_tpu.runtime import flightrec as _flightrec
from disq_tpu.runtime.tracing import (
    annotate as _annotate,
    counter as _counter,
    current_trace as _current_trace,
    observe_gauge as _observe_gauge,
    off_snapshot as _off_snapshot,
    on_snapshot as _on_snapshot,
    record_span as _record_span,
    span as _span,
    trace_scope as _trace_scope,
)

LANES = 128  # mirrors ops/inflate_simd.LANES (not imported: keep this
#              module importable without pulling jax in)
# blocks a CRC task: a full launch's lanes go to the host pool in four
# tasks of ~2 MB, not one a block (a task's hand-over costs what the
# CRC of a block does) and not one a launch (the pool has four threads)
CHECK_LANES = 32
# the queue of the inflate lanes that launch at the wide geometry, and
# the engine that serves it
WIDE_QUEUE = "inflate.wide"
QUEUE_ENGINE = {WIDE_QUEUE: "inflate"}


class _Lane:
    """One block/stream queued for a kernel lane."""

    __slots__ = ("sub", "index", "payload", "expect", "ts", "trace")

    def __init__(self, sub: "Submission", index: int, payload: Any,
                 expect: int, ts: float, trace: Any = None) -> None:
        self.sub = sub
        self.index = index
        self.payload = payload
        self.expect = expect
        self.ts = ts
        # the submitting request's TraceContext (or None): rides the
        # thread hop into the dispatcher so a coalesced launch can book
        # each owner request's share of queue wait + launch time
        self.trace = trace


class Submission:
    """Future for one shard's submitted batch.

    Inflate submissions carry a preallocated ``blob`` + ``offsets``
    (usizes are always known for BGZF) that lanes are written into as
    they materialize; rANS submissions collect per-stream ``parts``.
    The first failing owner lane records the error and releases the
    waiter — late lanes of a failed submission are dropped.

    With ``crcs`` (the blocks' footer CRC32s) every block is pending
    twice, once until it is stored and once until ``check`` has held
    its stored bytes to its CRC, so ``result()`` hands out checked
    bytes only.  ``base`` is the buffer ``blob`` is a prefix of when
    the submission was made ``padded`` (else None): word-aligned,
    ``util.pad_quantum`` words long, zero past the blob."""

    __slots__ = ("_event", "_lock", "_pending", "_error", "blob",
                 "offsets", "parts", "crcs", "base")

    def __init__(self, blob: Optional[np.ndarray] = None,
                 offsets: Optional[np.ndarray] = None,
                 parts_n: Optional[int] = None,
                 crcs: Optional[np.ndarray] = None,
                 base: Optional[np.ndarray] = None) -> None:
        self._event = threading.Event()
        self._lock = threading.Lock()
        self.blob = blob
        self.offsets = offsets
        self.crcs = crcs
        self.base = base
        self.parts: Optional[List[Optional[bytes]]] = (
            [None] * parts_n if parts_n is not None else None)
        self._pending = (parts_n if parts_n is not None
                         else (len(offsets) - 1)
                         * (1 if crcs is None else 2))
        self._error: Optional[BaseException] = None
        if self._pending == 0:
            self._event.set()

    def _store(self, index: int, value: Any) -> None:
        if self.parts is not None:
            self.parts[index] = (value if isinstance(value, bytes)
                                 else bytes(value))
        else:
            lo = int(self.offsets[index])
            hi = int(self.offsets[index + 1])
            if isinstance(value, np.ndarray):
                self.blob[lo:hi] = value
            else:
                self.blob[lo:hi] = np.frombuffer(value, dtype=np.uint8)

    def deliver_local(self, index: int, value: Any) -> None:
        """Pre-enqueue delivery on the submitting thread (oversize /
        empty lanes) — no lock needed, the dispatcher can't see the
        submission yet."""
        self._store(index, value)
        self._pending -= 1

    def deliver(self, index: int, value: Any) -> None:
        with self._lock:
            if self._error is None:
                self._store(index, value)
            self._pending -= 1
            if self._pending <= 0:
                self._event.set()

    def check(self, indices: Sequence[int]) -> None:
        """Hold the stored blocks ``indices`` to their footer CRC32s
        (any thread, after their ``deliver``); a no-op without
        ``crcs``.  The first mismatch fails the submission with the
        direct route's error; each index settles its second pending
        unit either way."""
        if self.crcs is None:
            return
        try:
            # failed already: its bytes go nowhere
            if self._error is None:
                bad = self._first_bad(indices)
                _counter("codec.inflate.crc_blocks").inc(
                    len(indices) if bad < 0 else bad + 1, at="launch")
                if bad >= 0:
                    self.fail(ValueError(
                        f"BGZF CRC mismatch at block {indices[bad]}"))
        except BaseException as e:  # noqa: BLE001 — never strand a waiter
            self.fail(e)
        with self._lock:
            self._pending -= len(indices)
            if self._pending <= 0:
                self._event.set()

    def _first_bad(self, indices: Sequence[int]) -> int:
        """The position in ``indices`` of the first block whose stored
        bytes do not have its CRC32, or -1.  One native call a task
        when the host library is built: a ``zlib.crc32`` a block takes
        and drops the interpreter lock a block, 128 times a launch, and
        each time the dispatcher's Python (packing the next launch)
        waits to be woken (measured: its ``pack`` 3 -> 8 ms a
        launch)."""
        try:
            from disq_tpu.native import crc32_check_native

            return crc32_check_native(
                self.blob, self.offsets, indices, self.crcs)
        except ImportError:
            pass
        for k, i in enumerate(indices):
            lo, hi = int(self.offsets[i]), int(self.offsets[i + 1])
            if zlib.crc32(self.blob[lo:hi]) != self.crcs[i]:
                return k
        return -1

    def fail(self, exc: BaseException) -> None:
        with self._lock:
            if self._error is None:
                self._error = exc
            self._pending -= 1
            self._event.set()

    def result(self, timeout: Optional[float] = None):
        """Block until every lane landed and, with ``crcs``, was
        checked (or the first owner-lane error); returns ``(blob,
        offsets)`` for inflate submissions, the parts list for rANS
        ones."""
        if not self._event.wait(timeout):
            raise TimeoutError("device decode service result timed out")
        if self._error is not None:
            raise self._error
        if self.parts is not None:
            return list(self.parts)
        return self.blob, self.offsets


class _InflateEngine:
    """Launch/finalize hooks for BGZF raw-DEFLATE lanes, built on the
    refactored ops/inflate_simd dispatch helpers (shared arenas,
    device-resident const tables, transposed+donated compile).

    ``host_map`` (from the owning service) fans multi-lane host-zlib
    fallbacks out over the service's host pool so a degraded shard's
    re-inflates don't serialize on the dispatcher thread and stall
    every co-batched shard's queue; ``host_check`` hands a launch's
    delivered lanes to the same pool for their CRCs."""

    kind = "inflate"

    def __init__(self, interpret: bool, host_map, host_check) -> None:
        self._interpret = bool(interpret)
        self._host_map = host_map
        self._host_check = host_check

    def launch(self, lanes: Sequence[_Lane], labels: Dict[str, Any]):
        import jax.numpy as jnp

        from disq_tpu.ops import inflate_simd as IS

        payloads = [l.payload for l in lanes]
        arena = None
        try:
            with _span("device.launch.pack", **labels):
                cw, ow = IS.buckets_for(
                    payloads, max(l.expect for l in lanes))
                arena = IS.ARENAS.acquire(
                    ("inflate", cw), lambda: IS._PackArena(cw))
                comp, clen = IS._pack_chunk(payloads, cw, arena)
            nbytes = comp.nbytes + clen.nbytes
            with _span("device.launch.submit", bytes=nbytes, **labels):
                IS._count_transfer("h2d", nbytes)
                fn = IS._compiled(cw, ow, self._interpret, True, True)
                out = fn(jnp.asarray(comp), jnp.asarray(clen),
                         *IS._device_const_tables())
        except BaseException:
            if arena is not None:
                IS.ARENAS.release(("inflate", cw), arena)
            raise
        return out, arena, cw

    def finalize(self, handle, lanes: Sequence[_Lane],
                 labels: Dict[str, Any]) -> None:
        from disq_tpu.ops import inflate_simd as IS

        out, arena, cw = handle
        try:
            lanes_u8, meta = IS._fetch_chunk(
                out, len(lanes), labels, cw=cw)
        except BaseException:
            IS.ARENAS.release(("inflate", cw), arena)
            raise
        with _span("device.launch.deliver", **labels):
            IS.ARENAS.release(("inflate", cw), arena)
            flagged: List[_Lane] = []
            stored: Dict[Submission, List[int]] = {}
            for j, lane in enumerate(lanes):
                n, status = int(meta[0, j]), int(meta[1, j])
                if status != 0 or n != lane.expect:
                    IS.last_stats["host_fallback"] += 1
                    _counter("device.host_fallback_blocks").inc(
                        reason="flagged")
                    flagged.append(lane)
                else:
                    IS.last_stats["device_lanes"] += 1
                    lane.sub.deliver(lane.index, lanes_u8[j, :n])
                    if lane.sub.crcs is not None:
                        stored.setdefault(lane.sub, []).append(lane.index)
            self._host_check(stored)
            if flagged:
                self._host_map(
                    flagged,
                    lambda lane: IS.host_inflate(lane.payload, lane.expect))


class _RansEngine:
    """Launch/finalize hooks for CRAM order-0 rANS lanes; a lane's
    payload is ``(stream bytes, parsed meta)`` — the host-side table
    parse already happened on the submitting thread (and raised there
    for a corrupt header: owner-only by construction)."""

    kind = "rans"

    def __init__(self, interpret: bool, host_map) -> None:
        self._interpret = bool(interpret)
        self._host_map = host_map

    def launch(self, lanes: Sequence[_Lane], labels: Dict[str, Any]):
        import jax.numpy as jnp

        from disq_tpu.ops import inflate_simd as IS
        from disq_tpu.ops import rans_simd as RS

        metas = [l.payload[1] for l in lanes]
        arena = None
        try:
            with _span("device.launch.pack", **labels):
                cw, ow = RS.kernel_geometry(metas)
                arena = IS.ARENAS.acquire(("rans", cw),
                                          lambda: RS._rans_arena(cw))
                args = RS.pack_lane_tables(metas, cw, arena)
            nbytes = sum(a.nbytes for a in args)
            with _span("device.launch.submit", bytes=nbytes, **labels):
                IS._count_transfer("h2d", nbytes)
                fn = RS._compiled(cw, ow, self._interpret, True, True)
                out = fn(*(jnp.asarray(a) for a in args))
        except BaseException:
            if arena is not None:
                IS.ARENAS.release(("rans", cw), arena)
            raise
        return out, arena, cw

    def finalize(self, handle, lanes: Sequence[_Lane],
                 labels: Dict[str, Any]) -> None:
        from disq_tpu.ops import inflate_simd as IS
        from disq_tpu.ops import rans_simd as RS

        out, arena, cw = handle
        try:
            lanes_u8, meta = RS._fetch_chunk(out, len(lanes), labels)
        except BaseException:
            IS.ARENAS.release(("rans", cw), arena)
            raise
        with _span("device.launch.deliver", **labels):
            IS.ARENAS.release(("rans", cw), arena)
            flagged: List[_Lane] = []
            for j, lane in enumerate(lanes):
                if int(meta[1, j]) != 0:
                    RS.last_stats["host_fallback"] += 1
                    _counter("device.host_fallback_blocks").inc(
                        reason="flagged")
                    flagged.append(lane)
                else:
                    RS.last_stats["device_lanes"] += 1
                    lane.sub.deliver(lane.index,
                                     lanes_u8[j, : lane.expect])
            if flagged:
                self._host_map(
                    flagged,
                    lambda lane: RS._host_decode0(lane.payload[0]))


class DeviceDecodeService:
    """The dispatcher that owns the device queue (module docstring)."""

    def __init__(self, flush_timeout_s: Optional[float] = None,
                 interpret: Optional[bool] = None) -> None:
        import os

        if flush_timeout_s is None:
            flush_timeout_s = float(
                os.environ.get("DISQ_TPU_SERVICE_FLUSH_MS", "2")) / 1e3
        self.flush_timeout_s = flush_timeout_s
        if interpret is None:
            from disq_tpu.util import pallas_interpret

            interpret = pallas_interpret()
        # outstanding fire-and-forget pool tasks: host-fallback lanes
        # and launches' CRC checks (drained at close so shutdown never
        # strands a waiter); the pool itself is the process-wide
        # disq_tpu.util.shared_host_pool
        self._pool_pending = 0
        self._engines = {
            "inflate": _InflateEngine(
                interpret, self._host_map, self._host_check),
            "rans": _RansEngine(interpret, self._host_map),
        }
        self._cond = threading.Condition()
        # dispatch targets, snapshotted once: [None] (default-device
        # semantics) unless the mesh knob was armed before service
        # start — then one sub-queue per mesh device (module docstring)
        from disq_tpu.runtime.mesh import service_devices

        self._devices = service_devices()
        n_dev = len(self._devices)
        # a queue an engine, and one more for the inflate lanes whose
        # payload is over the narrow geometry's (``inflate.wide``): a
        # launch's geometry follows its widest payload
        # (``inflate_simd.buckets_for``), so lanes queued apart launch
        # at the one geometry of their kind whichever pass, shard or
        # flush brings them together, and a warm-up pass has compiled
        # both
        self._queues: Dict[str, List[Deque[_Lane]]] = {
            k: [deque() for _ in range(n_dev)]
            for k in (*self._engines, WIDE_QUEUE)}
        self._next_queue = 0  # tie-break rotation (see _enqueue)
        # by device, under ``_cond``: lanes queued or in flight (what
        # ``_enqueue`` balances)
        self._outstanding = [0] * n_dev
        self._inflight: Deque[
            Tuple[str, Any, List[_Lane], Dict[str, Any], int]] = deque()
        self._closed = False
        # dispatcher-thread only: launches so far (the ``launch`` label
        # that joins one launch's spans) and the seconds slept in
        # ``_cond.wait`` since the last one (``device.service.idle``)
        self._launch_seq = 0
        self._idle_s = 0.0
        # under ``_cond``: why the dispatcher sleeps now and since when
        # ``device.service.idle_seconds`` has not been told of it (None:
        # awake).  Both reasons at 0 from the start, so a reader tells
        # "did not sleep" from "no such counter"
        self._sleep_reason = "empty"
        self._sleep_t0: Optional[float] = None
        self._book_sleep_locked(0.0)
        _on_snapshot(self._settle_idle)
        # window sized for the widest full-BGZF geometry (8 MiB of
        # compressed words and 8 MiB of output a launch); the env knobs
        # in dispatch_window apply here too.  Scaled by the device
        # count: the window bounds launches IN FLIGHT, and with n chips
        # each wants its own pipeline of them
        from disq_tpu.ops.inflate_simd import dispatch_window

        self._window = dispatch_window(4, 2 * (16384 + 8) * LANES * 4) * n_dev
        self._thread = threading.Thread(
            target=self._run, name="disq-device-dispatch", daemon=True)
        self._thread.start()

    # -- submission ---------------------------------------------------------

    @property
    def alive(self) -> bool:
        return self._thread.is_alive() and not self._closed

    def submit_inflate(self, payloads: Sequence, usizes: Sequence[int],
                       crcs: Optional[Sequence[int]] = None,
                       padded: bool = False) -> Submission:
        """Submit one shard's raw-DEFLATE block batch; the result is
        ``(blob, offsets)`` — decoded bytes of every block, contiguous
        in submission order.  Oversize blocks decode on THIS thread
        (host zlib), exactly like the per-shard dispatch.

        ``crcs``: the blocks' expected CRC32s; every block is then
        checked against its own as its launch is delivered, before
        ``result()`` returns (``Submission.check``).  ``padded``: the
        blob is the head of a buffer of the resident parse's upload
        shape (``Submission.base``), its tail zeroed here."""
        from disq_tpu.ops import inflate_simd as IS

        n = len(payloads)
        offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(np.asarray([int(u) for u in usizes], np.int64),
                  out=offsets[1:])
        total = int(offsets[-1])
        base = None
        if padded:
            from disq_tpu.util import pad_quantum

            base = np.empty(
                pad_quantum(max(1, (total + 3) // 4)) * 4, np.uint8)
            base[total:] = 0
            blob = base[:total]
        else:
            blob = np.empty(total, np.uint8)
        if crcs is not None:
            crcs = np.asarray(crcs, np.uint32)
        sub = Submission(blob=blob, offsets=offsets, crcs=crcs, base=base)
        ctx = _current_trace()
        lanes: Dict[str, List[_Lane]] = {"inflate": [], WIDE_QUEUE: []}
        for i, p in enumerate(payloads):
            if (len(p) > IS.MAX_DEVICE_CSIZE
                    or int(usizes[i]) > IS.MAX_DEVICE_USIZE):
                # no BGZF block: a raw stream past the kernel's buffers
                IS.last_stats["host_big"] += 1
                _counter("device.host_fallback_blocks").inc(
                    reason="oversize")
                sub.deliver_local(i, IS.host_inflate(p, int(usizes[i])))
                sub.check((i,))
            else:
                # ts stamped at enqueue (see _enqueue)
                lanes[WIDE_QUEUE if len(p) > IS.NARROW_CSIZE
                      else "inflate"].append(
                    _Lane(sub, i, p, int(usizes[i]), 0.0, ctx))
        self._enqueue(lanes, sub)
        return sub

    def submit_rans(self, streams: Sequence[bytes]) -> Submission:
        """Submit order-0 rANS streams; the result is the per-stream
        decoded bytes list.  Header parse / oversize fallbacks run on
        THIS thread (owner-only errors by construction)."""
        from disq_tpu.ops import rans_simd as RS

        n = len(streams)
        sub = Submission(parts_n=n)
        ctx = _current_trace()
        lanes: List[_Lane] = []
        for k, s in enumerate(streams):
            meta = RS._parse_stream(k, s)
            if meta is None:
                sub.deliver_local(k, b"")
                continue
            if (len(meta[1]) > RS.MAX_DEVICE_CSIZE
                    or meta[0] > RS.MAX_DEVICE_RAW):
                RS.last_stats["host_big"] += 1
                _counter("device.host_fallback_blocks").inc(
                    reason="oversize")
                sub.deliver_local(k, RS._host_decode0(s))
                continue
            lanes.append(_Lane(sub, k, (s, meta), meta[0], 0.0, ctx))
        self._enqueue({"rans": lanes}, sub)
        return sub

    def _enqueue(self, lanes_by_queue: Dict[str, List[_Lane]],
                 sub: Submission) -> None:
        # stamp the flush clock HERE, not at submission start: oversize
        # host decode / rANS table parsing on the submitting thread can
        # take longer than the flush timeout, and pre-aged lanes would
        # flush immediately at partial fill — defeating the coalescing
        # this queue exists for
        now = time.perf_counter()
        n_lanes = 0
        for lanes in lanes_by_queue.values():
            n_lanes += len(lanes)
            for lane in lanes:
                lane.ts = now
        with self._cond:
            if self._closed:
                raise RuntimeError("device decode service is closed")
            # least-loaded device takes the whole batch (one
            # submission's lanes stay together — they share pack
            # geometry and error scope).  Load is the device's lanes
            # queued or in flight, of every codec: a queue that has
            # drained into launches still running is not a free chip
            # (four splits in flight, four chips).  Ties rotate, or a
            # stream of small submissions that each drain before the
            # next arrives (serve queries, tiny splits) would all land
            # on device 0.  With one device this is the old
            # single-queue append
            n_q = len(self._devices)
            pick = min(range(n_q), key=lambda i: (
                self._outstanding[i], (i - self._next_queue) % n_q))
            self._next_queue = (pick + 1) % n_q
            self._outstanding[pick] += n_lanes
            for queue, lanes in lanes_by_queue.items():
                self._queues[queue][pick].extend(lanes)
            depth = sum(
                len(q) for qs in self._queues.values() for q in qs)
            if sub._pending <= 0:
                sub._event.set()
            self._cond.notify_all()
        _observe_gauge("device.queue_depth", depth)

    def _host_map(self, lanes: List[_Lane], fn) -> None:
        """Deliver host-fallback lanes, fanning multi-lane work over
        the process-wide host pool so a degraded shard's re-decodes
        don't serialize the dispatcher (and stall co-batched shards); a
        host failure fails ONLY the owner submission."""

        def one(lane: _Lane) -> None:
            try:
                val = fn(lane)
            except Exception as e:  # noqa: BLE001 — owner-only
                lane.sub.fail(e)
            else:
                lane.sub.deliver(lane.index, val)
                lane.sub.check((lane.index,))

        if len(lanes) <= 1:
            for lane in lanes:
                one(lane)
            return
        # fire-and-forget: each lane delivers (or fails its owner) from
        # the pool; the dispatcher goes straight back to launching
        self._pool_submit([(one, lane) for lane in lanes])

    def _host_check(self, stored: Dict[Submission, List[int]]) -> None:
        """CRC the blocks one launch just stored, by owner, on the host
        pool: ``CHECK_LANES`` blocks a task.  The dispatcher only hands
        them over (8 MB of CRC a launch would make it the wall of a
        mesh read, where a launch lands every ~19 ms)."""
        self._pool_submit([
            (sub.check, idx[k: k + CHECK_LANES])
            for sub, idx in stored.items()
            for k in range(0, len(idx), CHECK_LANES)])

    def _pool_submit(self, tasks: List[Tuple[Any, Any]]) -> None:
        """Run each ``fn(arg)`` on the process-wide host pool, counted
        in ``_pool_pending`` until it returns (``close`` waits for
        them); neither ``one`` nor ``Submission.check`` raises."""
        if not tasks:
            return
        from disq_tpu.util import shared_host_pool

        def tracked(fn, arg) -> None:
            try:
                fn(arg)
            finally:
                with self._cond:
                    self._pool_pending -= 1
                    self._cond.notify_all()

        with self._cond:
            self._pool_pending += len(tasks)
        pool = shared_host_pool()
        for fn, arg in tasks:
            pool.submit(tracked, fn, arg)

    def close(self, timeout: float = 30.0) -> None:
        """Drain the queue (remaining partial chunks flush with
        ``reason=drain``), wait out any in-flight host-fallback lanes
        and CRC checks, and stop the dispatcher."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout)
        with self._cond:
            self._cond.wait_for(
                lambda: self._pool_pending <= 0, timeout)

    # -- dispatcher ---------------------------------------------------------

    def _run(self) -> None:
        try:
            self._loop()
        except BaseException as e:  # noqa: BLE001 — fail pending, not hang
            self._abort_all(e)
        finally:
            _off_snapshot(self._settle_idle)

    def _book_sleep_locked(self, now: float) -> None:
        """The sleep up to ``now`` into ``device.service.idle_seconds``
        under its reason (the other reason by 0: a telemetry reset must
        not take it out of the snapshot)."""
        slept = 0.0
        if self._sleep_t0 is not None:
            slept, self._sleep_t0 = now - self._sleep_t0, now
        idle = _counter("device.service.idle_seconds")
        for reason in ("empty", "filling"):
            idle.inc(slept if reason == self._sleep_reason else 0,
                     reason=reason)

    def _settle_idle(self) -> None:
        # any thread, before a telemetry snapshot is copied: no sleep
        # is cut short for it, the counter is told how long it has
        # lasted so far
        with self._cond:
            self._book_sleep_locked(time.perf_counter())

    def _loop(self) -> None:
        while True:
            chunk = None
            with self._cond:
                while True:
                    chunk = self._take_chunk_locked()
                    if chunk is not None:
                        break
                    if self._inflight:
                        break  # overlap the wait with a materialize
                    if self._closed:
                        break
                    # ``_idle_s`` is the whole sleep, booked as one
                    # span by the launch that ends it; the counter is
                    # told of it piecewise (``_book_sleep_locked``)
                    wait_s = self._wait_s_locked()
                    self._sleep_reason = (
                        "empty" if wait_s is None else "filling")
                    self._sleep_t0 = t_sleep = time.perf_counter()
                    with _annotate("device.service.idle"):
                        self._cond.wait(wait_s)
                    now = time.perf_counter()
                    self._idle_s += now - t_sleep
                    self._book_sleep_locked(now)
                    self._sleep_t0 = None
            if chunk is None and not self._inflight:
                # closed and drained: the sleep that close ended is
                # followed by no launch, so it is booked here
                if self._idle_s > 0.0:
                    _record_span("device.service.idle", self._idle_s)
                return
            if chunk is not None:
                kind, dev_i, lanes, reason = chunk
                try:
                    entry = self._launch(kind, dev_i, lanes, reason)
                except BaseException as e:
                    # the chunk is already out of the queues, so
                    # _abort_all can't see it — fail its owners here
                    # or they wait forever
                    for lane in lanes:
                        lane.sub.fail(e)
                    raise
                if entry is not None:
                    self._inflight.append(entry)
                else:
                    self._settle(dev_i, len(lanes))
            if self._inflight and (chunk is None
                                   or len(self._inflight) >= self._window):
                self._materialize(self._inflight.popleft())

    def _take_chunk_locked(self):
        now = time.perf_counter()
        # the chip with the fewest launches in flight first, so that
        # every chip keeps its own pipeline of them: a submission's
        # lanes carry one timestamp, and oldest-first alone issues all
        # of one chip's launches before the next chip's first (four
        # splits, four chips: the chips inflated one after the other).
        # Among a chip's queues, and with one device, oldest lane
        # first: a sustained full-chunk burst on one codec must not
        # starve another queue's lanes past their flush deadline
        flying = Counter(e[4] for e in self._inflight)
        ready = sorted(
            ((k, i) for k, qs in self._queues.items()
             for i, q in enumerate(qs) if q),
            key=lambda ki: (flying[ki[1]],
                            self._queues[ki[0]][ki[1]][0].ts))
        for kind, i in ready:
            q = self._queues[kind][i]
            if len(q) >= LANES:
                lanes = [q.popleft() for _ in range(LANES)]
                reason = "full"
            elif self._closed or (now - q[0].ts) >= self.flush_timeout_s:
                lanes = list(q)
                q.clear()
                reason = "drain" if self._closed else "timeout"
            else:
                continue
            return QUEUE_ENGINE.get(kind, kind), i, lanes, reason
        return None

    def _wait_s_locked(self) -> Optional[float]:
        now = time.perf_counter()
        waits = [
            self.flush_timeout_s - (now - q[0].ts)
            for qs in self._queues.values() for q in qs if q
        ]
        if not waits:
            return None  # nothing queued: sleep until a notify
        return max(1e-3, min(waits))

    def _launch(self, kind: str, dev_i: int, lanes: List[_Lane],
                reason: str):
        # first of all, so that the sleep this launch ended is booked
        # as it ends and its span's ts and dur are true.  Booked for
        # every launch, 0.0 included: each launch has all six spans
        # under its ``launch`` number, and a join on it lacks none
        self._launch_seq += 1
        labels = {"kind": kind, "lanes": len(lanes),
                  "launch": self._launch_seq}
        _record_span("device.service.idle", self._idle_s, **labels)
        self._idle_s = 0.0
        dev = self._devices[dev_i]
        _counter("device.batch.flush").inc(reason=reason)
        _flightrec.record_event("device_flush", codec=kind,
                                reason=reason, lanes=len(lanes))
        # mesh-off ([None]) keeps the historic unlabeled gauge; a real
        # device list labels fill per chip so partial lanes on one
        # device are visible, not averaged away.  Likewise the queue
        # wait and the five per-launch spans carry ``device`` (the
        # index in ``service_devices()``) only then: which chip a
        # launch went to says whether a mesh read uses its chips
        on_chip = {} if dev is None else {"device": dev_i}
        labels.update(on_chip)
        if dev is None:
            _observe_gauge("device.lane_fill", len(lanes) / LANES)
        else:
            _observe_gauge("device.lane_fill", len(lanes) / LANES,
                           device=str(dev_i))
        _observe_gauge(
            "device.queue_depth",
            sum(len(q) for qs in self._queues.values() for q in qs))
        _record_span("device.service.wait",
                     time.perf_counter() - min(l.ts for l in lanes),
                     kind=kind, lanes=len(lanes), **on_chip)
        # group lanes by owning request context (None = untraced): a
        # coalesced launch serves n distinct requests, and each owner
        # inherits its share of queue wait + launch time below
        owners: Dict[Tuple[str, str, str], List[_Lane]] = {}
        for lane in lanes:
            if lane.trace is not None:
                owners.setdefault(
                    (lane.trace.trace_id, lane.trace.span_id,
                     lane.trace.tenant), []).append(lane)
        if owners:
            # label is "requests", not "n" — Counter.inc's first
            # positional is the increment amount named n, so a label
            # called n would collide with it
            _counter("device.batch.requests").inc(
                requests=str(len(owners)))
        t_launch = time.perf_counter()
        try:
            if dev is None:
                handle = self._engines[kind].launch(lanes, labels)
            else:
                import jax

                with jax.default_device(dev):
                    handle = self._engines[kind].launch(lanes, labels)
        except BaseException as e:  # noqa: BLE001 — owners, not the loop
            for lane in lanes:
                lane.sub.fail(e)
            return None
        if owners:
            launch_s = time.perf_counter() - t_launch
            for own_lanes in owners.values():
                share = launch_s * len(own_lanes) / len(lanes)
                wait = t_launch - min(l.ts for l in own_lanes)
                with _trace_scope(own_lanes[0].trace):
                    _record_span("device.batch.share",
                                 max(0.0, wait) + share, kind=kind,
                                 lanes=len(own_lanes),
                                 batch_lanes=len(lanes))
        return kind, handle, lanes, labels, dev_i

    def _settle(self, dev_i: int, n_lanes: int) -> None:
        """``n_lanes`` of device ``dev_i`` are delivered (or failed):
        no longer part of its load."""
        with self._cond:
            self._outstanding[dev_i] -= n_lanes

    def _materialize(self, entry) -> None:
        kind, handle, lanes, labels, dev_i = entry
        try:
            self._engines[kind].finalize(handle, lanes, labels)
        except BaseException as e:  # noqa: BLE001 — owners, not the loop
            for lane in lanes:
                lane.sub.fail(e)
        finally:
            self._settle(dev_i, len(lanes))

    def _abort_all(self, exc: BaseException) -> None:
        with self._cond:
            self._closed = True
            pending = [
                l for qs in self._queues.values() for q in qs for l in q]
            for qs in self._queues.values():
                for q in qs:
                    q.clear()
            inflight = list(self._inflight)
            self._inflight.clear()
        for _kind, _handle, lanes, _labels, _dev_i in inflight:
            pending.extend(lanes)
        for lane in pending:
            lane.sub.fail(exc)


# ---------------------------------------------------------------------------
# Process-wide singleton (lazy — the disabled path touches none of this)
# ---------------------------------------------------------------------------

_SERVICE: Optional[DeviceDecodeService] = None
_SERVICE_LOCK = threading.Lock()


def enabled() -> bool:
    """True when ``DISQ_TPU_DEVICE_SERVICE`` is set truthy — the codec
    entry points then route device decode through the shared service."""
    from disq_tpu.runtime.debug import env_flag

    return env_flag("DISQ_TPU_DEVICE_SERVICE")


def get_service() -> DeviceDecodeService:
    """The process-wide service, created on first use."""
    global _SERVICE
    with _SERVICE_LOCK:
        if _SERVICE is None or not _SERVICE.alive:
            _SERVICE = DeviceDecodeService()
        return _SERVICE


def service_if_running() -> Optional[DeviceDecodeService]:
    """The live service or None — NEVER creates one (the overhead
    guard asserts this stays None on the default path)."""
    return _SERVICE


def shutdown_service() -> None:
    global _SERVICE
    with _SERVICE_LOCK:
        service, _SERVICE = _SERVICE, None
    if service is not None:
        service.close()
