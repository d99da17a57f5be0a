"""Why the device waits (ISSUE 38): the spans round the hand-over's host
work, ``device.transfer``'s ``site`` and ``bytes``, the dispatcher's
sleep as a counter that is exact at a window's edges, the two thread
waits in a profiler capture, and the analyzer's buckets for them."""

import os
import re
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(REPO, "tests"), os.path.join(REPO, "scripts")):
    if p not in sys.path:
        sys.path.insert(0, p)

import trace_report  # noqa: E402
from test_trace_analyze import _span  # noqa: E402


def _bam_file(tmp_path, n=90, blocksize=300):
    from test_device_service import _bam_file as bam_file

    return bam_file(tmp_path, n=n, blocksize=blocksize)


def _ring_since(since, *names):
    from disq_tpu.runtime.tracing import spans

    return [s for s in spans()[since:] if s["name"] in names]


def _inside(inner, outer, slack=1e-3):
    return (outer["ts"] - slack <= inner["ts"] and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + slack)


def _idle_seconds():
    from disq_tpu.runtime.tracing import telemetry_snapshot

    return telemetry_snapshot()["counters"].get(
        "device.service.idle_seconds", {})


# ---------------------------------------------------------------------------
# Part 1: spans where the hand-over's seconds are
# ---------------------------------------------------------------------------


class TestVerifySpan:
    @pytest.mark.parametrize("route", ["direct", "service"])
    def test_once_a_batch_inside_the_batch_span(self, monkeypatch, route):
        """``codec.inflate.verify`` opens when the device has answered
        and closes with the function: one a batch, inside
        ``codec.inflate.batch``, with the batch's block count and the
        decoded byte count."""
        from disq_tpu.bgzf.codec import deflate_block, inflate_blocks
        from disq_tpu.bgzf.guesser import find_block_table
        from disq_tpu.fsw import MemoryFileSystemWrapper
        from disq_tpu.runtime import device_service
        from disq_tpu.runtime.tracing import spans

        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        if route == "service":
            monkeypatch.setenv("DISQ_TPU_DEVICE_SERVICE", "1")
        payloads = [bytes([65 + i % 7]) * (90 + 3 * i) for i in range(9)]
        data = b"".join(deflate_block(p) for p in payloads)
        fs = MemoryFileSystemWrapper()
        fs.write_all("mem://nine.bgzf", data)
        blocks = find_block_table(fs, "mem://nine.bgzf")
        since = len(spans())
        try:
            out = inflate_blocks(data, blocks)
        finally:
            device_service.shutdown_service()
        assert out == b"".join(payloads)
        (batch,) = _ring_since(since, "codec.inflate.batch")
        (verify,) = _ring_since(since, "codec.inflate.verify")
        assert _inside(verify, batch)
        assert verify["labels"] == {"blocks": 9, "bytes": len(out)}

    def test_the_host_route_has_none(self):
        """The span is the device route's: the host inflater checks its
        blocks as it decodes them."""
        from disq_tpu.bgzf.codec import deflate_block, inflate_blocks
        from disq_tpu.bgzf.guesser import find_block_table
        from disq_tpu.fsw import MemoryFileSystemWrapper
        from disq_tpu.runtime.tracing import spans

        data = deflate_block(b"abc" * 50)
        fs = MemoryFileSystemWrapper()
        fs.write_all("mem://one.bgzf", data)
        blocks = find_block_table(fs, "mem://one.bgzf")
        since = len(spans())
        assert inflate_blocks(data, blocks) == b"abc" * 50
        assert len(_ring_since(since, "codec.inflate.batch")) == 1
        assert not _ring_since(since, "codec.inflate.verify")


class TestStageSpan:
    def _read(self, path):
        from disq_tpu import ReadsStorage

        ds = (ReadsStorage.make_default().split_size(8000)
              .executor_workers(1).resident_decode().read(path))
        assert ds.count() == 90
        return ds

    def test_once_an_upload_before_its_transfer(self, tmp_path):
        """Host inflate, resident parse: every ``columnar.batch.build``
        copies its decoded blob into the padded upload buffer once
        (``bytes`` the padded size), inside the build span and before
        its ``device.transfer{site=parse_blob}``."""
        from disq_tpu.runtime.tracing import spans

        path = _bam_file(tmp_path)
        since = len(spans())
        self._read(path)
        builds = _ring_since(since, "columnar.batch.build")
        stages = _ring_since(since, "columnar.batch.stage")
        ups = [s for s in _ring_since(since, "device.transfer")
               if s["labels"]["site"] == "parse_blob"]
        assert len(builds) >= 2
        assert len(stages) == len(ups) == len(builds)
        for stage, up in zip(stages, ups):
            assert any(_inside(stage, b) for b in builds)
            assert stage["labels"]["bytes"] % 4 == 0
            # the blob's padded bytes and the starts' go up together
            assert up["labels"]["bytes"] > stage["labels"]["bytes"]
            assert stage["ts"] + stage["dur"] <= up["ts"] + 1e-3

    def test_absent_when_the_blob_is_on_the_device(
            self, tmp_path, monkeypatch):
        """The direct device route leaves the decoded blob in HBM: the
        parse uploads the starts alone (``site=parse_starts``) and
        stages nothing."""
        from disq_tpu.runtime.tracing import spans

        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        path = _bam_file(tmp_path)
        since = len(spans())
        self._read(path)
        assert len(_ring_since(since, "columnar.batch.build")) >= 2
        assert not _ring_since(since, "columnar.batch.stage")
        sites = {s["labels"]["site"]
                 for s in _ring_since(since, "device.transfer")}
        assert sites == {"parse_starts"}


class TestTransferSpans:
    def test_every_site_in_the_source_names_itself(self):
        """Four sites share the name ``device.transfer``: each says
        which it is, one word a site, no two alike."""
        sites = []
        for root, _dirs, files in os.walk(os.path.join(REPO, "disq_tpu")):
            for f in files:
                if not f.endswith(".py"):
                    continue
                with open(os.path.join(root, f)) as fh:
                    src = fh.read()
                for m in re.finditer(
                        r'span\(\s*"device\.transfer"([^)]*)\)', src):
                    site = re.search(r'site="([a-z_]+)"', m.group(1))
                    assert site, (f, m.group(0))
                    assert "direction=" in m.group(1)
                    sites.append(site.group(1))
        assert len(sites) == len(set(sites)) == 4, sites

    def test_bytes_are_what_count_transfer_books(
            self, tmp_path, monkeypatch):
        """Over a resident read, every ``device.transfer`` span carries
        ``site`` and ``bytes``, and its bytes are one ``count_transfer``
        booking of the same direction: one computation feeds both."""
        from disq_tpu.runtime import tracing

        booked = []
        real = tracing.count_transfer

        def recording(direction, nbytes):
            booked.append((direction, int(nbytes)))
            real(direction, nbytes)

        monkeypatch.setattr(tracing, "count_transfer", recording)
        path = _bam_file(tmp_path)
        since = len(tracing.spans())
        TestStageSpan()._read(path)
        transfers = _ring_since(since, "device.transfer")
        assert transfers
        for s in transfers:
            assert set(s["labels"]) == {"direction", "site", "bytes"}
            booked.remove((s["labels"]["direction"], s["labels"]["bytes"]))

    def test_by_direction_they_sum_to_the_counters_growth(self):
        """``run_device_pipeline`` moves nothing but its upload and its
        results: the spans' ``bytes`` by direction are the growth of
        ``device.bytes_to_device`` / ``device.bytes_to_host``."""
        from test_device_pipeline import _shard

        from disq_tpu.runtime.device_pipeline import run_device_pipeline
        from disq_tpu.runtime.tracing import REGISTRY, spans

        up = REGISTRY.counter("device.bytes_to_device")
        down = REGISTRY.counter("device.bytes_to_host")
        base = up.total(), down.total()
        since = len(spans())
        blob, offs = _shard(n=120)
        _keys, _order, stats = run_device_pipeline(blob, offs, interpret=True)
        assert stats["total"] == 120
        by = {"h2d": 0, "d2h": 0}
        sites = set()
        for s in _ring_since(since, "device.transfer"):
            by[s["labels"]["direction"]] += s["labels"]["bytes"]
            sites.add(s["labels"]["site"])
        assert sites == {"pipeline_upload", "pipeline_fetch"}
        assert by["h2d"] == up.total() - base[0] > 0
        assert by["d2h"] == down.total() - base[1] > 0


class TestWriteSlice:
    @pytest.mark.parametrize("resident", [False, True],
                             ids=["host_batch", "device_backed"])
    def test_nested_in_encode_a_part(self, tmp_path, resident):
        """One ``bam.write.slice`` a written part, inside that part's
        ``bam.write.encode`` (which keeps its extent); the slices'
        records are the file's.  A device-backed batch holds its
        records' bytes: each slice copies its own out of them
        (``columnar.batch.materialize{how=bytes}``, inside the slice),
        and nothing parses."""
        from disq_tpu import ReadsStorage
        from disq_tpu.runtime.tracing import spans

        path = _bam_file(tmp_path)
        storage = ReadsStorage.make_default().split_size(8000)
        if resident:
            storage = storage.resident_decode()
        ds = storage.read(path)
        assert bool(getattr(ds.reads, "device_backed", False)) == resident
        since = len(spans())
        (ReadsStorage.make_default().writer_workers(2).num_shards(3)
         .write(ds, str(tmp_path / "out.bam")))
        encodes = _ring_since(since, "bam.write.encode")
        slices = _ring_since(since, "bam.write.slice")
        assert len(encodes) == len(slices) == 3
        for k in range(3):
            (enc,) = [s for s in encodes if s["labels"]["shard"] == k]
            (sl,) = [s for s in slices if s["labels"]["shard"] == k]
            assert _inside(sl, enc)
        assert sum(s["labels"]["records"] for s in slices) == 90
        mats = _ring_since(since, "columnar.batch.materialize")
        assert len(mats) == (3 if resident else 0)
        assert {s["labels"]["how"] for s in mats} <= {"bytes"}
        assert sum(s["labels"]["records"] for s in mats) == (
            90 if resident else 0)
        for sl in slices if resident else ():
            assert any(_inside(m, sl) and m["labels"]["records"]
                       == sl["labels"]["records"] for m in mats)


# ---------------------------------------------------------------------------
# Part 2: the dispatcher's sleep as a counter a window can read
# ---------------------------------------------------------------------------


@pytest.fixture()
def idle_service():
    """Services of the test's own, and no other in the counter: the
    counter is the process's, summed over every live service, so the
    shared service is shut down and the hook of any service another
    test of this worker left alive is set aside for the test (left to
    itself such a service sleeps on and books nothing)."""
    from disq_tpu.runtime import device_service
    from disq_tpu.runtime.device_service import DeviceDecodeService
    from disq_tpu.runtime.tracing import REGISTRY

    device_service.shutdown_service()
    with REGISTRY._lock:
        foreign, REGISTRY._settlers[:] = list(REGISTRY._settlers), []
    made = []

    def make(flush_timeout_s):
        made.append(DeviceDecodeService(
            flush_timeout_s=flush_timeout_s, interpret=True))
        return made[-1]

    yield make
    for svc in made:
        svc.close()
    with REGISTRY._lock:
        REGISTRY._settlers[:0] = foreign


def _submit_inflate(service, lanes):
    """One submission of ``lanes`` small lanes; with it, the check of
    what comes back."""
    from test_device_service import _submit

    return _submit(service, "inflate", lanes)


class TestIdleCounter:
    def test_both_reasons_at_zero_on_a_fresh_service(self, idle_service):
        """Registered at 0 for both reasons when a service starts: a
        reader tells "did not sleep" from "no such counter"."""
        from disq_tpu.runtime.tracing import REGISTRY

        REGISTRY.counter("device.service.idle_seconds")._reset()
        idle_service(0.05)
        assert set(_idle_seconds()) == {"reason=empty", "reason=filling"}
        assert _idle_seconds()["reason=filling"] == 0

    def test_exact_at_the_edges_inside_one_sleep(self, idle_service):
        """A service left idle: snapshots 0.3 s and 0.5 s into one
        sleep differ by the 0.2 s between them under ``reason=empty``,
        and the sleep was cut short for neither."""
        from disq_tpu.runtime.tracing import spans

        svc = idle_service(0.05)
        since = len(spans())
        time.sleep(0.3)
        t_a, a = time.perf_counter(), _idle_seconds()
        time.sleep(0.2)
        t_b, b = time.perf_counter(), _idle_seconds()
        grown = b["reason=empty"] - a["reason=empty"]
        assert grown == pytest.approx(t_b - t_a, abs=0.05)
        assert b["reason=filling"] == a["reason=filling"]
        # one unbroken sleep: nothing was launched, nothing booked
        assert not _ring_since(since, "device.service.idle")
        assert svc._sleep_t0 is not None

    def test_lanes_waiting_for_company_grow_filling(self, idle_service):
        """Fewer than 128 lanes queued under a long flush timeout: the
        dispatcher sleeps until the deadline with ``reason=filling``,
        readable halfway through the wait.  Held to the clock's own
        readings round the snapshots, not to the sleeps asked for: from
        the submission on the dispatcher does nothing but sleep, so the
        two reasons together grow by the time that passed, and all but
        its wake-up to find the lanes is ``filling``."""
        svc = idle_service(1.0)
        sub, sound = _submit_inflate(svc, 5)
        t_q, queued = time.perf_counter(), _idle_seconds()
        time.sleep(0.3)
        t_mid, mid = time.perf_counter(), _idle_seconds()
        assert sound(sub.result(timeout=300))
        t_end, after = time.perf_counter(), _idle_seconds()

        def grown(snap, reason):
            return snap["reason=" + reason] - queued["reason=" + reason]

        half, whole = grown(mid, "filling"), grown(after, "filling")
        if t_mid - t_q < 0.9:   # else the box stalled past the deadline
            assert half + grown(mid, "empty") == pytest.approx(
                t_mid - t_q, abs=0.05)
            assert half > 0.5 * (t_mid - t_q)
        # the launch ended the wait: with what was booked as starving
        # before and after it, no more than the clock saw
        assert 0 < half <= whole
        assert whole + grown(after, "empty") <= t_end - t_q + 0.05

    @pytest.mark.parametrize("readers", [0, 6])
    def test_the_counter_and_the_per_launch_spans_agree(
            self, idle_service, readers):
        """``record_span``'s per-launch idle stays as it was (a sleep is
        one span, booked by the launch that ends it, and once at
        close); over a service's life the counter, both reasons, is the
        same seconds, also with six threads taking snapshots all the
        while (a lost or doubled booking would break the sum).  Both
        sides add up differences of the same ``perf_counter`` readings
        (the ring rounds a span to the microsecond): the box's load
        moves neither against the other."""
        import threading

        from disq_tpu.runtime.tracing import spans, telemetry_snapshot

        base = sum(_idle_seconds().values())
        since = len(spans())
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                telemetry_snapshot()

        threads = [threading.Thread(target=hammer) for _ in range(readers)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            svc = idle_service(0.1)
            time.sleep(0.15)
            for lanes in (3, 4):
                sub, sound = _submit_inflate(svc, lanes)
                assert sound(sub.result(timeout=300))
                time.sleep(0.1)
            svc.close()
        finally:
            stop.set()
            sys.setswitchinterval(interval)
            for t in threads:
                t.join(30)
        assert not [t for t in threads if t.is_alive()]
        idle = _ring_since(since, "device.service.idle")
        assert [s["labels"].get("launch") for s in idle] == [1, 2, None]
        counted = sum(_idle_seconds().values()) - base
        assert counted == pytest.approx(
            sum(s["dur"] for s in idle), abs=1e-4)
        assert counted >= 0.4     # five sleeps of 0.1 s or more

    def test_a_closed_service_leaves_no_hook(self, idle_service):
        from disq_tpu.runtime.tracing import REGISTRY

        svc = idle_service(0.05)
        assert svc._settle_idle in REGISTRY._settlers
        svc.close()
        assert svc._settle_idle not in REGISTRY._settlers

    def test_a_reset_does_not_lose_a_reason(self, idle_service):
        """``reset_telemetry`` empties the counter under a running
        service; the next snapshot holds both reasons again."""
        from disq_tpu.runtime.tracing import REGISTRY

        idle_service(0.05)
        REGISTRY.counter("device.service.idle_seconds")._reset()
        time.sleep(0.05)
        got = _idle_seconds()
        assert set(got) == {"reason=empty", "reason=filling"}
        assert 0 < got["reason=empty"] < 5


class TestSnapshotHook:
    def test_called_before_the_copy_and_taken_off_again(self):
        from disq_tpu.runtime import tracing

        c = tracing.counter("telemetry.dropped_spans")
        base = c.total()
        calls = []

        def settle():
            calls.append(1)
            c.inc(0.5)

        tracing.on_snapshot(settle)
        try:
            snap = tracing.telemetry_snapshot()
            assert snap["counters"]["telemetry.dropped_spans"][""] \
                == base + 0.5
            assert "disq_tpu_telemetry_dropped_spans" \
                in tracing.metrics_text()
            assert len(calls) == 2
        finally:
            tracing.off_snapshot(settle)
            c.inc(-c.total() + base)
        tracing.telemetry_snapshot()
        assert len(calls) == 2


# ---------------------------------------------------------------------------
# Part 3: the two thread waits on the profiler's clock
# ---------------------------------------------------------------------------


class TestWaitsInACapture:
    def test_a_service_read_holds_both_waits(self, tmp_path, monkeypatch):
        """A capture round a two-worker read through the service holds
        ``disq_tpu.device.service.idle`` (the dispatcher's sleeps) and
        ``disq_tpu.executor.emit.stall`` (the ordered emit's wait) on
        the clock of the ``disq_tpu.device.launch.*`` events: the
        sleeps never overlap the dispatcher's launch spans, and the
        emit waits while a kernel is waited for."""
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
                out["n"] = (ReadsStorage.make_default().split_size(8000)
                            .executor_workers(2).read(path).count())
            finally:
                device_service.shutdown_service()

        launch = ["disq_tpu.device.launch." + n
                  for n in ("pack", "submit", "wait", "d2h", "deliver")]
        idle_n, stall_n = ("disq_tpu.device.service.idle",
                           "disq_tpu.executor.emit.stall")
        events = captured_events(tmp_path / "trace", body,
                                 launch + [idle_n, stall_n])
        assert out["n"] == 60
        idle = [ev for ev in events if ev[0] == idle_n]
        stall = [ev for ev in events if ev[0] == stall_n]
        busy = [ev for ev in events if ev[0] in launch]
        assert idle and stall and busy
        for _n, start, dur in idle:
            assert not [b for b in busy
                        if b[1] < start + dur and start < b[1] + b[2]]
        waits = [ev for ev in busy if ev[0].endswith(".wait")]
        assert [w for w in waits for s in stall
                if s[1] <= w[1] and w[1] + w[2] <= s[1] + s[2]]
        # the ring's stalls (booked over 0.5 ms) each have their event
        ring = _ring_since(since, "executor.emit.stall")
        assert 1 <= len(ring) <= len(stall)
        assert sum(s[2] for s in stall) / 1e9 == pytest.approx(
            sum(s["dur"] for s in ring), abs=0.05)

    def test_the_write_pipelines_stall_is_bridged_by_its_name(
            self, tmp_path):
        """The pipeline is given the name by its user: the writer's
        ordered emit waits under ``disq_tpu.writer.emit.stall``."""
        from profiler_capture import captured_events

        from disq_tpu import ReadsStorage

        ds = ReadsStorage.make_default().read(_bam_file(tmp_path))

        def body():
            (ReadsStorage.make_default().writer_workers(2).num_shards(4)
             .write(ds, str(tmp_path / "out.bam")))

        events = captured_events(
            tmp_path / "trace", body,
            ["disq_tpu.writer.emit.stall", "disq_tpu.executor.emit.stall"])
        assert {ev[0] for ev in events} == {"disq_tpu.writer.emit.stall"}


# ---------------------------------------------------------------------------
# trace_report --analyze: a bucket for each new span
# ---------------------------------------------------------------------------


class TestAnalyzerBuckets:
    @pytest.mark.parametrize("name,bucket", [
        ("codec.inflate.verify", "verify"),
        ("columnar.batch.stage", "columnar"),
        ("bam.write.slice", "write_slice"),
        ("device.transfer", "transfer"),
        ("device.service.idle", "service_idle"),
    ])
    def test_each_span_has_its_bucket(self, name, bucket):
        assert trace_report.bucket_of(name) == bucket
        assert bucket in trace_report.ADVICE
        assert bucket in trace_report.WORK_PRIORITY

    def test_the_writers_wait_is_not_read_as_encode(self):
        """Four writers inside ``bam.write.encode``, all of them in
        ``bam.write.slice`` for the first second (one materialises,
        three wait), then encoding: the first second is the slice's."""
        spans = []
        for k in range(4):
            spans.append(_span("bam.write.encode", 0.0, 3.0, shard=k))
            spans.append(_span("bam.write.slice", 0.0, 1.0, shard=k,
                               records=10))
        buckets, *_rest, wall = trace_report.attribute_wall(spans)
        assert wall == pytest.approx(3.0)
        assert buckets == {"write_slice": pytest.approx(1.0),
                           "encode": pytest.approx(2.0)}

    def test_analyze_prints_the_hand_over(self):
        """One line for the seconds between the device's answer and
        the next kernel: the check, the staging copy, the uploads by
        site, the writers' slice."""
        spans = [
            _span("codec.inflate.batch", 0.0, 5.0, blocks=40),
            _span("codec.inflate.verify", 4.5, 0.5, blocks=40,
                  bytes=2_000_000),
            _span("columnar.batch.build", 5.0, 1.5, records=9, bytes=1),
            _span("columnar.batch.stage", 5.0, 0.25, bytes=2_100_000),
            _span("device.transfer", 5.25, 0.25, direction="h2d",
                  site="parse_blob", bytes=2_100_400),
            _span("bam.write.encode", 7.0, 1.0, shard=0),
            _span("bam.write.slice", 7.0, 0.75, shard=0, records=9),
        ]
        text = trace_report.analyze(spans, "r1", ["r1"])
        (line,) = [ln for ln in text.splitlines()
                   if ln.startswith("hand_over:")]
        assert line == (
            "hand_over: stage 250.00ms in 1 (2.1 MB); "
            "transfer{site=parse_blob} 250.00ms in 1 (2.1 MB); "
            "verify 500.00ms in 1 (2.0 MB); write_slice 750.00ms in 1")
