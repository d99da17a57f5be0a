"""Device decode service (runtime/device_service.py) + the dispatch
refactor it rides on (ops/inflate_simd.py arenas / const cache /
adaptive window / array-native unpack).

Interpret-mode kernels on CPU — tiny payloads and BGZF blocksizes keep
superstep counts feasible (production 64 KiB shapes run in the TPU CI
lane).  Geometry buckets are deliberately reused across tests so the
compile cache, not the compiler, pays for parametrization.
"""

import threading
import zlib

import numpy as np
import pytest


def deflate(data: bytes, level: int = 6) -> bytes:
    c = zlib.compressobj(level, zlib.DEFLATED, -15, 8)
    return c.compress(data) + c.flush()


def text_like(n: int, seed: int = 0) -> bytes:
    rng = np.random.default_rng(seed)
    words = [b"the", b"quick", b"brown", b"fox", b"!", b"\n"]
    out = b" ".join(words[i % 6] for i in rng.integers(0, 6, max(1, n // 3)))
    return (out + b"x" * n)[:n]


@pytest.fixture()
def service():
    from disq_tpu.runtime.device_service import DeviceDecodeService

    svc = DeviceDecodeService(flush_timeout_s=0.05, interpret=True)
    yield svc
    svc.close()


# ---------------------------------------------------------------------------
# Dispatch-refactor units (no service thread involved)
# ---------------------------------------------------------------------------


class TestArenaPack:
    def test_arena_reuse_matches_fresh_pack(self):
        """Packing into a reused arena — including after a BIGGER
        previous chunk left dirty lanes — must produce exactly the
        arrays a fresh zeroed pack does (the dirty-tail zeroing)."""
        from disq_tpu.ops.inflate_simd import (
            _PackArena, _pack_chunk, buckets_for)

        big = [deflate(text_like(400, i)) for i in range(6)]
        small = [deflate(b"ab")]
        cw, _ = buckets_for(big + small, 400)
        arena = _PackArena(cw)
        for chunk in (big, small, big[:2], []):
            got_c, got_l = _pack_chunk(chunk, cw, arena)
            want_c, want_l = _pack_chunk(chunk, cw)
            np.testing.assert_array_equal(got_c, want_c)
            np.testing.assert_array_equal(got_l, want_l)

    def test_memoryview_payloads_pack_identically(self):
        from disq_tpu.ops.inflate_simd import _pack_chunk, buckets_for

        pls = [deflate(text_like(300, 7)), deflate(b"xyz" * 40)]
        cw, _ = buckets_for(pls, 300)
        blob = b"".join(pls)
        mv = memoryview(blob)
        views = []
        pos = 0
        for p in pls:
            views.append(mv[pos: pos + len(p)])
            pos += len(p)
        got_c, got_l = _pack_chunk(views, cw)
        want_c, want_l = _pack_chunk(pls, cw)
        np.testing.assert_array_equal(got_c, want_c)
        np.testing.assert_array_equal(got_l, want_l)

    def test_arena_pool_checkout_is_exclusive(self):
        from disq_tpu.ops.inflate_simd import ARENAS, _PackArena

        a = ARENAS.acquire(("test", 64), lambda: _PackArena(64))
        b = ARENAS.acquire(("test", 64), lambda: _PackArena(64))
        assert a is not b
        ARENAS.release(("test", 64), a)
        c = ARENAS.acquire(("test", 64), lambda: _PackArena(64))
        assert c is a  # released arenas are reused, not reallocated
        ARENAS.release(("test", 64), b)
        ARENAS.release(("test", 64), c)

    def test_arena_bytes_gauge_booked(self):
        from disq_tpu.ops.inflate_simd import ARENAS, _PackArena
        from disq_tpu.runtime.tracing import REGISTRY

        ARENAS.acquire(("test-gauge", 64), lambda: _PackArena(64))
        state = REGISTRY.gauge("device.arena_bytes").state()
        assert state is not None and state["last"] > 0


class TestConstTableCache:
    def test_uploaded_once_per_device(self):
        from disq_tpu.ops.inflate_simd import _device_const_tables

        first = _device_const_tables()
        second = _device_const_tables()
        assert all(a is b for a, b in zip(first, second))


class TestDispatchWindow:
    def test_env_pin_wins(self, monkeypatch):
        from disq_tpu.ops.inflate_simd import dispatch_window

        monkeypatch.setenv("DISQ_TPU_DISPATCH_WINDOW", "2")
        assert dispatch_window(10, 1 << 20) == 2
        assert dispatch_window(1, 1 << 20) == 1  # never exceeds chunks

    def test_budget_scales_with_chunk_footprint(self, monkeypatch):
        from disq_tpu.ops.inflate_simd import dispatch_window

        monkeypatch.delenv("DISQ_TPU_DISPATCH_WINDOW", raising=False)
        monkeypatch.delenv("DISQ_TPU_DISPATCH_HBM_MB", raising=False)
        assert dispatch_window(10, 1 << 20) == 4    # small chunks: cap
        assert dispatch_window(10, 60 << 20) == 1   # huge chunks: serial
        assert dispatch_window(2, 1 << 20) == 2     # bounded by chunks


class TestArrayNativeUnpack:
    def test_as_array_equals_bytes_path(self):
        from disq_tpu.ops.inflate_simd import inflate_payloads_simd

        raws = [text_like(200 + 17 * i, seed=i) for i in range(5)] + [b""]
        pls = [deflate(r) for r in raws]
        us = [len(r) for r in raws]
        blob, offsets = inflate_payloads_simd(
            pls, usizes=us, interpret=True, as_array=True)
        assert blob.dtype == np.uint8
        assert blob.tobytes() == b"".join(raws)
        assert list(np.diff(offsets)) == us

    def test_blocks_device_as_array_and_threaded_crc(self, monkeypatch):
        """inflate_blocks_device(as_array=True) returns the contiguous
        uint8 blob; >=32 blocks exercises the threaded CRC pool, and a
        flipped CRC is still caught through it."""
        from disq_tpu.bgzf.block import BGZF_FOOTER_SIZE
        from disq_tpu.bgzf.codec import deflate_block, inflate_blocks_device
        from disq_tpu.bgzf.guesser import find_block_table
        from disq_tpu.fsw import MemoryFileSystemWrapper

        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        payloads = [text_like(120 + 3 * i, seed=i) for i in range(40)]
        data = b"".join(deflate_block(p) for p in payloads)
        fs = MemoryFileSystemWrapper()
        fs.write_all("mem://many.bgzf", data)
        blocks = find_block_table(fs, "mem://many.bgzf")
        blob = inflate_blocks_device(data, blocks, as_array=True)
        assert isinstance(blob, np.ndarray)
        assert blob.tobytes() == b"".join(payloads)
        bad = bytearray(data)
        b0 = blocks[5]
        bad[b0.pos + b0.csize - BGZF_FOOTER_SIZE] ^= 0xFF
        with pytest.raises(ValueError, match="CRC mismatch"):
            inflate_blocks_device(bytes(bad), blocks)


# ---------------------------------------------------------------------------
# The service: batching, isolation, accounting
# ---------------------------------------------------------------------------


class TestServiceBatching:
    def test_coalesces_lanes_across_submissions(self, service):
        """Three shards' partial batches (30 lanes each) coalesce into
        ONE 90-lane launch instead of three — the tentpole win.  The
        test holds the service's lock (re-entrant; ``_enqueue`` takes
        it again) over the three submissions, so the dispatcher first
        looks at the queues when all 90 lanes are in them: no flush
        timeout holds them together on a loaded box."""
        from disq_tpu.runtime.tracing import REGISTRY

        launches = REGISTRY.counter("device.kernel_launches")
        shard_raws = [
            [text_like(80 + 5 * i + 60 * s, seed=10 * s + i)
             for i in range(30)]
            for s in range(3)
        ]
        shard_payloads = [[deflate(r) for r in raws] for raws in shard_raws]
        base = launches.total()
        with service._cond:
            subs = [
                service.submit_inflate(pls, [len(r) for r in raws])
                for raws, pls in zip(shard_raws, shard_payloads)
            ]
        for raws, sub in zip(shard_raws, subs):
            blob, offsets = sub.result(timeout=300)
            assert blob.tobytes() == b"".join(raws)
            assert list(np.diff(offsets)) == [len(r) for r in raws]
        assert launches.total() - base == 1
        fill = REGISTRY.gauge("device.lane_fill").state()
        assert fill is not None and abs(fill["last"] - 90 / 128) < 1e-9

    def test_full_chunk_flushes_without_timeout(self, service):
        """>=128 queued lanes flush immediately with reason=full."""
        from disq_tpu.runtime.tracing import REGISTRY

        flush = REGISTRY.counter("device.batch.flush")
        base_full = flush.value(reason="full")
        raws = [text_like(60 + i % 9, seed=i) for i in range(130)]
        sub = service.submit_inflate(
            [deflate(r) for r in raws], [len(r) for r in raws])
        blob, _ = sub.result(timeout=300)
        assert blob.tobytes() == b"".join(raws)
        assert flush.value(reason="full") - base_full == 1

    def test_corrupt_lane_fails_owner_only(self, service):
        """A truly corrupt lane (kernel flags it, host zlib also fails)
        raises on the OWNER submission; the co-batched shard's
        submission is delivered intact."""
        good_raws = [text_like(150 + 4 * i, seed=40 + i) for i in range(8)]
        good = service.submit_inflate(
            [deflate(r) for r in good_raws],
            [len(r) for r in good_raws])
        bad_raw = text_like(400, seed=99)
        truncated = deflate(bad_raw)[: len(deflate(bad_raw)) // 2]
        owner = service.submit_inflate(
            [deflate(good_raws[0]), truncated],
            [len(good_raws[0]), len(bad_raw)])
        with pytest.raises(ValueError, match="corrupt DEFLATE"):
            owner.result(timeout=300)
        blob, _ = good.result(timeout=300)
        assert blob.tobytes() == b"".join(good_raws)

    def test_lane_accounting_invariant(self, service):
        """device_lanes + host_fallback + host_big == submitted, with
        oversize lanes routed to host on the submitting thread."""
        from disq_tpu.ops.inflate_simd import MAX_DEVICE_CSIZE, last_stats

        snap = dict(last_stats)
        raws = [text_like(100 + 7 * i, seed=60 + i) for i in range(12)]
        # incompressible -> compressed size ~ raw size: over the comp cap
        big_raw = np.random.default_rng(3).integers(
            0, 256, MAX_DEVICE_CSIZE + 4096, dtype=np.uint8).tobytes()
        raws.insert(4, big_raw)
        sub = service.submit_inflate(
            [deflate(r) for r in raws], [len(r) for r in raws])
        blob, _ = sub.result(timeout=300)
        assert blob.tobytes() == b"".join(raws)
        delta = {k: last_stats[k] - snap[k] for k in last_stats}
        assert delta["host_big"] >= 1
        assert (delta["device_lanes"] + delta["host_fallback"]
                + delta["host_big"]) == len(raws)

    def test_rans_streams_coalesce_and_roundtrip(self, service):
        from disq_tpu.cram.rans import rans_encode_order0

        shard_raws = [
            [bytes((7 * i + s + j) % 251 for j in range(96 + 8 * i))
             for i in range(6)]
            for s in range(2)
        ]
        subs = [
            service.submit_rans(
                [rans_encode_order0(r) for r in raws])
            for raws in shard_raws
        ]
        for raws, sub in zip(shard_raws, subs):
            assert sub.result(timeout=300) == raws

    def test_service_survives_and_drains_on_close(self):
        from disq_tpu.runtime.device_service import DeviceDecodeService

        svc = DeviceDecodeService(flush_timeout_s=30.0, interpret=True)
        raws = [text_like(90 + i, seed=i) for i in range(5)]
        sub = svc.submit_inflate(
            [deflate(r) for r in raws], [len(r) for r in raws])
        # close() must flush the partial chunk (reason=drain) instead
        # of leaving the waiter hung on the 30 s timeout
        svc.close()
        blob, _ = sub.result(timeout=10)
        assert blob.tobytes() == b"".join(raws)


# ---------------------------------------------------------------------------
# The dispatcher's time, tiled by spans
# ---------------------------------------------------------------------------

LAUNCH_SPANS = ("device.service.idle", "device.launch.pack",
                "device.launch.submit", "device.launch.wait",
                "device.launch.d2h", "device.launch.deliver")


def _launch_spans(since: int):
    """The dispatcher's spans emitted after ``since`` ring entries,
    grouped by their ``launch`` label (``None``: the sleep that close
    ended, which no launch followed)."""
    from disq_tpu.runtime.tracing import spans

    by_launch = {}
    for s in spans()[since:]:
        if s["name"] in LAUNCH_SPANS:
            by_launch.setdefault(s["labels"].get("launch"), []).append(s)
    return by_launch


def _submit(service, kind: str, lanes: int):
    """One submission of ``lanes`` small lanes of a codec, with the
    check of what came back."""
    raws = [text_like(70 + 3 * i, seed=i) for i in range(lanes)]
    if kind == "inflate":
        sub = service.submit_inflate(
            [deflate(r) for r in raws], [len(r) for r in raws])
        return sub, lambda got: got[0].tobytes() == b"".join(raws)
    from disq_tpu.cram.rans import rans_encode_order0

    sub = service.submit_rans([rans_encode_order0(r) for r in raws])
    return sub, lambda got: got == raws


class TestLaunchSpans:
    @pytest.mark.parametrize("kind", ["inflate", "rans"])
    def test_one_launch_emits_the_six_spans_joined_by_launch(self, kind):
        """One launch of each codec: exactly the six spans, one shared
        ``launch`` number, ``kind`` and ``lanes`` on each, the idle
        span there although the dispatcher may not have slept."""
        from disq_tpu.runtime.device_service import DeviceDecodeService
        from disq_tpu.runtime.tracing import spans

        since = len(spans())
        svc = DeviceDecodeService(flush_timeout_s=0.0, interpret=True)
        try:
            sub, sound = _submit(svc, kind, 9)
            assert sound(sub.result(timeout=300))
        finally:
            svc.close()       # joins the dispatcher: deliver has ended
        by_launch = _launch_spans(since)
        by_launch.pop(None, None)
        assert list(by_launch) == [1], by_launch
        got = by_launch[1]
        assert sorted(s["name"] for s in got) == sorted(LAUNCH_SPANS)
        for s in got:
            assert s["labels"]["kind"] == kind, s
            assert s["labels"]["lanes"] == 9, s
            assert s["dur"] >= 0.0
        by_name = {s["name"]: s for s in got}
        assert by_name["device.launch.submit"]["labels"]["bytes"] > 0
        assert by_name["device.launch.d2h"]["labels"]["bytes"] > 0
        # in the order the dispatcher does them
        order = sorted(got, key=lambda s: s["ts"] + s["dur"])
        assert [s["name"] for s in order] == list(LAUNCH_SPANS)

    def test_idle_is_booked_once_a_launch_also_at_zero(self):
        """A full chunk and its remainder flush back to back (no flush
        timeout): the second launch follows the first with no sleep
        between, and still has its idle span, of 0.0 s."""
        from disq_tpu.runtime.device_service import DeviceDecodeService
        from disq_tpu.runtime.tracing import spans

        since = len(spans())
        svc = DeviceDecodeService(flush_timeout_s=0.0, interpret=True)
        try:
            sub, sound = _submit(svc, "inflate", 130)
            assert sound(sub.result(timeout=300))
        finally:
            svc.close()
        by_launch = _launch_spans(since)
        by_launch.pop(None, None)
        assert sorted(by_launch) == [1, 2]
        idle = {n: [s for s in got if s["name"] == "device.service.idle"]
                for n, got in by_launch.items()}
        assert all(len(v) == 1 for v in idle.values()), idle
        assert idle[2][0]["dur"] == 0.0
        assert {n: idle[n][0]["labels"]["lanes"] for n in idle} == {
            1: 128, 2: 2}

    def test_the_sleep_that_close_ends_is_booked_at_close(self):
        """After its last launch the dispatcher sleeps until close:
        that sleep has no launch to be booked with, and is booked as
        the thread ends, with no ``launch`` label."""
        import time

        from disq_tpu.runtime.device_service import DeviceDecodeService
        from disq_tpu.runtime.tracing import spans

        since = len(spans())
        svc = DeviceDecodeService(flush_timeout_s=0.0, interpret=True)
        try:
            sub, sound = _submit(svc, "inflate", 3)
            assert sound(sub.result(timeout=300))
            time.sleep(0.2)
        finally:
            svc.close()
        closed = time.perf_counter()
        by_launch = _launch_spans(since)
        (last,) = by_launch[None]
        assert last["name"] == "device.service.idle"
        assert last["labels"] == {}
        assert last["dur"] >= 0.15
        deliver = next(s for s in by_launch[1]
                       if s["name"] == "device.launch.deliver")
        assert deliver["ts"] + deliver["dur"] <= last["ts"] + 1e-3
        assert last["ts"] + last["dur"] <= closed

    def test_the_six_spans_tile_the_dispatcher_thread(self, service):
        """Several launches with a sleep between them and one before
        close: the six spans' durations add up to the stretch of the
        dispatcher's own clock that they cover, from the first span's
        start to the last one's end.  Sum against sum on one clock, so
        the machine's speed cancels; what is left out is the
        bookkeeping between spans."""
        import time

        from disq_tpu.runtime.tracing import spans

        since = len(spans())
        for lanes in (130, 40):
            sub, sound = _submit(service, "inflate", lanes)
            assert sound(sub.result(timeout=300))
            time.sleep(0.2)
        service.close()
        by_launch = _launch_spans(since)
        assert sorted(by_launch, key=str) == [1, 2, 3, None]
        got = [s for launch in by_launch.values() for s in launch]
        assert len(got) == 6 * 3 + 1
        covered = sum(s["dur"] for s in got)
        lifetime = (max(s["ts"] + s["dur"] for s in got)
                    - min(s["ts"] for s in got))
        assert 0.9 * lifetime <= covered <= 1.02 * lifetime, (
            covered, lifetime)
        slept = sum(s["dur"] for s in got
                    if s["name"] == "device.service.idle")
        assert slept >= 0.3      # before the third launch, before close


class TestServiceDisabled:
    def test_disabled_path_runs_no_service(self, monkeypatch):
        """No flag -> enabled() is False, a device inflate call routes
        per-shard as before, and no dispatcher thread exists."""
        from disq_tpu.runtime import device_service

        monkeypatch.delenv("DISQ_TPU_DEVICE_SERVICE", raising=False)
        assert not device_service.enabled()
        device_service.shutdown_service()
        assert device_service.service_if_running() is None
        assert not [
            t for t in threading.enumerate()
            if t.name.startswith("disq-device")
        ]


# ---------------------------------------------------------------------------
# End to end through the read path
# ---------------------------------------------------------------------------


def _bam_file(tmp_path, n=150, blocksize=1500):
    from bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records

    recs = synth_records(n, seed=21)
    src = tmp_path / "in.bam"
    src.write_bytes(make_bam_bytes(DEFAULT_REFS, recs, blocksize=blocksize))
    return str(src)


class TestEndToEnd:
    # workers=4 (cross-shard coalescing, ~80s of interpret-mode
    # launches) rides the slow tier: the routing contract is the
    # workers=1 leg, and coalescing correctness is covered by
    # TestServiceBatching at a fraction of the wall-clock.
    @pytest.mark.parametrize("workers", [
        1, pytest.param(4, marks=pytest.mark.slow)])
    def test_bam_read_byte_identity(self, tmp_path, monkeypatch, workers):
        """Full ReadsStorage.read with the decode service on: every
        shard's blocks route through the shared dispatcher and the
        result is byte-identical to the sequential host decode.
        workers=1 submits shard batches serially (routing check, fewer
        shards keeps interpret launches down); workers=4 is the
        cross-shard coalescing case."""
        from disq_tpu.api import ReadsStorage
        from disq_tpu.runtime import device_service

        path = _bam_file(tmp_path)
        host = ReadsStorage.make_default().read(path)
        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        monkeypatch.setenv("DISQ_TPU_DEVICE_SERVICE", "1")
        try:
            dev = (ReadsStorage.make_default()
                   .split_size(16000 if workers == 1 else 6000)
                   .executor_workers(workers).read(path))
        finally:
            device_service.shutdown_service()
        assert dev.count() == host.count()
        np.testing.assert_array_equal(dev.reads.pos, host.reads.pos)
        np.testing.assert_array_equal(dev.reads.seqs, host.reads.seqs)
        np.testing.assert_array_equal(dev.reads.quals, host.reads.quals)

    @pytest.mark.parametrize("workers", [1, 4])
    def test_cram_read_via_service_rans(self, tmp_path, monkeypatch,
                                        workers):
        """CRAM read with device rANS routed through the service: the
        order-0 external blocks of concurrently-decoding containers
        coalesce, output identical to the host codec."""
        from disq_tpu.api import ReadsStorage
        from disq_tpu.runtime import device_service

        path = _bam_file(tmp_path, n=110)
        storage = ReadsStorage.make_default()
        ds = storage.read(path)
        cram = str(tmp_path / "out.cram")
        storage.write(ds.coordinate_sorted(), cram)
        host = storage.read(cram)
        monkeypatch.setenv("DISQ_TPU_DEVICE_RANS", "1")
        monkeypatch.setenv("DISQ_TPU_DEVICE_SERVICE", "1")
        try:
            dev = (ReadsStorage.make_default()
                   .executor_workers(workers).read(cram))
        finally:
            device_service.shutdown_service()
        assert dev.count() == host.count()
        np.testing.assert_array_equal(dev.reads.pos, host.reads.pos)
        np.testing.assert_array_equal(dev.reads.seqs, host.reads.seqs)

    def test_a_service_read_lies_in_a_profiler_capture(
            self, tmp_path, monkeypatch):
        """A capture round a small read through the service holds the
        dispatcher's context-manager spans and the executor's, as many
        events of each name as the ring has spans, on one clock: a
        reduction can put the device's idle time down to them.  The
        idle span is booked after the fact, a launch each; in the
        capture it is one event a sleep (``annotate`` round the wait),
        on the same thread and clock as the launch spans."""
        from profiler_capture import captured_events

        from disq_tpu.api import ReadsStorage
        from disq_tpu.runtime import device_service
        from disq_tpu.runtime.tracing import spans

        path = _bam_file(tmp_path, n=60)
        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        monkeypatch.setenv("DISQ_TPU_DEVICE_SERVICE", "1")
        since = len(spans())
        out = {}

        def body():
            try:
                out["n"] = (ReadsStorage.make_default().split_size(16000)
                            .executor_workers(1).read(path).count())
            finally:
                device_service.shutdown_service()

        bridged = [n for n in LAUNCH_SPANS if n != "device.service.idle"]
        bridged += ["executor.fetch", "executor.decode", "bam.read.splits"]
        events = captured_events(
            tmp_path / "trace", body,
            ["disq_tpu." + n for n in LAUNCH_SPANS + tuple(bridged)])
        assert out["n"] == 60
        ring = [s["name"] for s in spans()[since:]]
        assert ring.count("device.service.idle") >= 1
        for name in bridged:
            assert ring.count(name) >= 1, name
            assert ([ev[0] for ev in events].count("disq_tpu." + name)
                    == ring.count(name)), name
        # the dispatcher is asleep or inside a launch span, never both:
        # its sleeps are disjoint from its five launch spans, and one
        # of them ends before the first launch is packed
        idle = [ev for ev in events
                if ev[0] == "disq_tpu.device.service.idle"]
        busy = [ev for ev in events
                if ev[0].startswith("disq_tpu.device.launch.")]
        assert idle
        for _n, start, dur in idle:
            assert not [b for b in busy
                        if b[1] < start + dur and start < b[1] + b[2]]
        assert min(i[1] + i[2] for i in idle) <= min(b[1] for b in busy)
        # one clock: within a launch, pack ends before submit starts
        pack, submit = (next(ev for ev in events
                             if ev[0] == "disq_tpu.device.launch." + n)
                        for n in ("pack", "submit"))
        assert pack[1] + pack[2] <= submit[1]

    # Slow tier (~65s e2e at workers=4): owner-only quarantine
    # semantics stay tier-1 via TestServiceBatching's unit-level
    # corrupt-lane test and test_resident_decode's faultfs bitflip.
    @pytest.mark.slow
    def test_faultfs_corrupt_lane_quarantines_owner_only(
            self, tmp_path, monkeypatch):
        """A bit-flipped BGZF payload under faultfs, read at
        executor_workers=4 through the service with QUARANTINE policy:
        exactly the owner shard's block is quarantined (one booking —
        co-batched shards are untouched) and the rest of the file
        decodes."""
        from disq_tpu.api import ReadsStorage
        from disq_tpu.bgzf.guesser import find_block_table
        from disq_tpu.fsw import (
            FaultInjectingFileSystemWrapper,
            FaultSpec,
            PosixFileSystemWrapper,
            register_filesystem,
        )
        from disq_tpu.runtime import device_service
        from disq_tpu.runtime.errors import DisqOptions, ErrorPolicy

        path = _bam_file(tmp_path)
        fs = PosixFileSystemWrapper()
        blocks = [b for b in find_block_table(fs, path) if b.usize > 0]
        victim = blocks[len(blocks) // 2]
        fsw = FaultInjectingFileSystemWrapper(
            PosixFileSystemWrapper(),
            [FaultSpec(kind="bitflip", path_substr="in.bam",
                       offset=victim.pos + 24, bit=5)],
        )
        register_filesystem("fault", fsw)
        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        monkeypatch.setenv("DISQ_TPU_DEVICE_SERVICE", "1")
        opts = DisqOptions(
            error_policy=ErrorPolicy.QUARANTINE,
            retry_backoff_s=0.0,
            quarantine_dir=str(tmp_path / "q"),
        )
        try:
            ds = (ReadsStorage.make_default().split_size(6000)
                  .options(opts).executor_workers(4)
                  .read("fault://" + path))
        finally:
            device_service.shutdown_service()
        assert ds.counters.quarantined_blocks == 1
        assert 0 < ds.count() < 150
