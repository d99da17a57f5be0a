"""Device observability (``runtime/tracing.py`` device helpers +
``runtime/device_pipeline.py`` + the ``ops/`` entry points): synced
kernel spans (fenced with ``block_until_ready``), transfer-byte
counters that match what is actually uploaded (alignment pad
included), the live-HBM gauge, the host-fallback counter, and the
device spans on the clock of a profiler capture."""

import gzip
import struct
import zlib

import numpy as np
import pytest

import jax.numpy as jnp

from bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records
from disq_tpu.runtime import tracing
from disq_tpu.runtime.tracing import (
    REGISTRY,
    count_transfer,
    device_span,
    hbm_live_bytes,
    hbm_resident,
    reset_telemetry,
    spans,
    stop_span_log,
    synced_timer,
    track_hbm,
)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    stop_span_log()
    reset_telemetry()
    yield
    stop_span_log()
    reset_telemetry()


def _shard(n=400, seed=3):
    """Decoded BAM payload + record offsets (host walk)."""
    raw = make_bam_bytes(DEFAULT_REFS, synth_records(n, seed=seed))
    payload = gzip.decompress(raw)
    (l_text,) = struct.unpack_from("<i", payload, 4)
    p = 8 + l_text
    (n_ref,) = struct.unpack_from("<i", payload, p)
    p += 4
    for _ in range(n_ref):
        (l_name,) = struct.unpack_from("<i", payload, p)
        p += 4 + l_name + 4
    offs = [p]
    while p < len(payload):
        (bs,) = struct.unpack_from("<i", payload, p)
        p += 4 + bs
        offs.append(p)
    return np.frombuffer(payload, np.uint8), np.asarray(offs, np.int64)


# -- tracing helpers --------------------------------------------------------


class TestDeviceSpanHelpers:
    def test_device_span_emits_and_counts_launch(self):
        with device_span("device.kernel", kernel="unittest") as fence:
            out = fence.sync(jnp.arange(16))
        assert int(np.asarray(out)[3]) == 3
        ev = spans()[-1]
        assert ev["name"] == "device.kernel"
        assert ev["labels"]["kernel"] == "unittest"
        assert REGISTRY.counter("device.kernel_launches").value(
            kernel="unittest") == 1

    def test_device_span_without_kernel_label_books_no_launch(self):
        with device_span("device.transfer", direction="h2d"):
            pass
        assert REGISTRY.counter("device.kernel_launches").total() == 0

    def test_fence_handles_pytrees_and_scalars(self):
        with device_span("device.kernel", kernel="tree") as fence:
            fence.sync({"a": jnp.ones((2, 3)), "b": [jnp.float32(1.5)]})
            fence.sync(np.arange(4))  # non-jax values pass through
        assert spans()[-1]["name"] == "device.kernel"

    def test_synced_timer_decorator(self):
        @synced_timer("device.kernel", kernel="deco")
        def work(n):
            return jnp.arange(n) * 2

        out = work(8)
        assert int(np.asarray(out)[4]) == 8
        assert REGISTRY.counter("device.kernel_launches").value(
            kernel="deco") == 1
        assert spans()[-1]["labels"]["kernel"] == "deco"

    def test_count_transfer_directions(self):
        count_transfer("h2d", 100)
        count_transfer("h2d", 20)
        count_transfer("d2h", 7)
        assert REGISTRY.counter("device.bytes_to_device").total() == 120
        assert REGISTRY.counter("device.bytes_to_host").total() == 7

    def test_hbm_tracking_scopes_and_peaks(self):
        assert hbm_live_bytes() == 0
        with hbm_resident(1000):
            assert hbm_live_bytes() == 1000
            with hbm_resident(500):
                assert hbm_live_bytes() == 1500
            assert hbm_live_bytes() == 1000
        assert hbm_live_bytes() == 0
        st = REGISTRY.gauge("device.hbm_bytes").state()
        assert st["max"] == 1500 and st["last"] == 0

    def test_track_hbm_never_negative(self):
        track_hbm(-999)
        assert hbm_live_bytes() == 0


# -- the profiler bridge: device spans on the capture's clock ---------------


class TestDeviceSpansInAProfilerCapture:
    def test_a_device_span_reads_against_the_host_stage_round_it(
            self, tmp_path):
        """Host stage and fenced device span are events of one capture,
        on one clock: the kernel's lies inside the stage's."""
        from profiler_capture import captured_events

        def body():
            with tracing.span("executor.decode", shard=3):
                with device_span("device.kernel",
                                 kernel="unittest") as fence:
                    fence.sync(jnp.arange(1024) * 2)

        stage, kernel = captured_events(
            tmp_path, body,
            ["disq_tpu.executor.decode", "disq_tpu.device.kernel"])
        assert stage[0] == "disq_tpu.executor.decode"
        assert kernel[0] == "disq_tpu.device.kernel"    # no label in it
        assert stage[1] <= kernel[1]
        assert kernel[1] + kernel[2] <= stage[1] + stage[2]
        assert [s["name"] for s in spans()] == [
            "device.kernel", "executor.decode"]

    def test_a_synced_timer_is_one_event_a_call(self, tmp_path):
        """The decorator form rides the same bridge: one event of the
        kernel span's name for each call, none for its labels."""
        from profiler_capture import captured_events

        @synced_timer("device.kernel", kernel="deco")
        def work(n):
            return jnp.ones((n,)) * n

        events = captured_events(
            tmp_path, lambda: [work(8), work(16)],
            ["disq_tpu.device.kernel", "disq_tpu.device.kernel.deco"])
        assert [ev[0] for ev in events] == ["disq_tpu.device.kernel"] * 2
        assert events[0][1] + events[0][2] <= events[1][1]


# -- run_device_pipeline ----------------------------------------------------


class TestDevicePipelineTelemetry:
    def test_books_transfers_launch_and_kernel_span(self, tmp_path):
        """Acceptance: a CPU run books nonzero bytes_to_device /
        bytes_to_host and emits device.kernel and device.transfer
        spans, in the ring and in a profiler capture round the run."""
        from profiler_capture import captured_events

        from disq_tpu.runtime.device_pipeline import run_device_pipeline

        blob, offs = _shard()
        out = {}

        def body():
            out["keys"], out["order"], out["stats"] = run_device_pipeline(
                blob, offs, interpret=True)

        events = captured_events(
            tmp_path, body,
            ["disq_tpu.device.kernel", "disq_tpu.device.transfer"])
        assert out["stats"]["total"] == len(offs) - 1
        assert sorted(ev[0] for ev in events) == [
            "disq_tpu.device.kernel", "disq_tpu.device.transfer",
            "disq_tpu.device.transfer"]

        h2d = REGISTRY.counter("device.bytes_to_device").total()
        d2h = REGISTRY.counter("device.bytes_to_host").total()
        assert h2d > 0 and d2h > 0
        # upload accounting is exact: word-padded blob + i32 starts
        pad = (-len(blob)) % 4
        assert h2d == (len(blob) + pad) + 4 * (len(offs) - 1)
        # fetched results: hi/lo keys u32 + order i32 + flagstat
        n = len(offs) - 1
        assert d2h >= 3 * 4 * n
        assert REGISTRY.counter("device.kernel_launches").value(
            kernel="device_pipeline") == 1

        names = [s["name"] for s in spans()]
        assert "device.kernel" in names
        assert names.count("device.transfer") == 2

    def test_pad_accounting_counts_uploaded_bytes(self):
        """The word-alignment pad is part of what is uploaded, so it
        is part of what is counted (satellite: the old np.concatenate
        path neither preallocated nor accounted)."""
        from disq_tpu.runtime.device_pipeline import run_device_pipeline

        blob, offs = _shard(n=37, seed=5)
        if len(blob) % 4 == 0:
            # force misalignment with trailing slack past the last
            # record (the pipeline reads [0, offsets[-1]) only)
            blob = np.concatenate([blob, np.zeros(1, np.uint8)])
        assert len(blob) % 4 != 0
        run_device_pipeline(blob, offs, interpret=True)
        pad = (-len(blob)) % 4
        assert REGISTRY.counter("device.bytes_to_device").total() == \
            (len(blob) + pad) + 4 * (len(offs) - 1)

    def test_hbm_gauge_returns_to_zero(self):
        from disq_tpu.runtime.device_pipeline import run_device_pipeline

        blob, offs = _shard(n=50, seed=7)
        run_device_pipeline(blob, offs, interpret=True)
        st = REGISTRY.gauge("device.hbm_bytes").state()
        assert st["max"] > 0 and st["last"] == 0

    def test_empty_shard_books_nothing(self):
        from disq_tpu.runtime.device_pipeline import run_device_pipeline

        run_device_pipeline(np.zeros(0, np.uint8),
                            np.zeros(1, np.int64), interpret=True)
        assert REGISTRY.counter("device.bytes_to_device").total() == 0


# -- ops entry points -------------------------------------------------------


class TestOpsTelemetry:
    def test_inflate_payloads_books_device_metrics(self):
        from disq_tpu.ops.inflate_simd import inflate_payloads_simd

        raw = b"device telemetry " * 8
        comp = zlib.compress(raw, 6)[2:-4]  # raw DEFLATE
        out = inflate_payloads_simd([comp], usizes=[len(raw)],
                                    interpret=True)
        assert out == [raw]
        assert REGISTRY.counter("device.kernel_launches").value(
            kernel="inflate_simd") == 1
        assert REGISTRY.counter("device.bytes_to_device").total() > 0
        assert REGISTRY.counter("device.bytes_to_host").total() > 0
        for name in ("device.launch.wait", "device.launch.d2h"):
            assert any(s["name"] == name
                       and s["labels"].get("kind") == "inflate"
                       for s in spans())

    def test_parse_host_entry_books_in_jit_passthrough_does_not(self):
        from disq_tpu.ops.parse import parse_fixed_words_pallas
        from disq_tpu.runtime.device_pipeline import run_device_pipeline

        words = np.zeros((16, 9), dtype=np.int32)
        words[:, 0] = 36  # block_size
        cols = parse_fixed_words_pallas(words, interpret=True)
        assert int(np.asarray(cols["block_size"])[0]) == 36
        launches = REGISTRY.counter("device.kernel_launches")
        assert launches.value(kernel="parse") == 1
        # numpy input counted as an upload
        assert REGISTRY.counter("device.bytes_to_device").total() >= \
            words.nbytes

        # under the device pipeline's jit the parse call is traced —
        # only the enclosing device_pipeline launch is booked
        blob, offs = _shard(n=20, seed=9)
        run_device_pipeline(blob, offs, interpret=True)
        assert launches.value(kernel="parse") == 1
        assert launches.value(kernel="device_pipeline") == 1

    def test_flagstat_books_device_metrics(self):
        from disq_tpu.ops.flagstat import flagstat_counts

        flag = np.array([0, 4, 1024, 16], dtype=np.int32)
        stats = flagstat_counts(flag)
        assert stats["total"] == 4
        assert REGISTRY.counter("device.kernel_launches").value(
            kernel="flagstat") == 1
        assert REGISTRY.counter("device.bytes_to_device").total() == \
            flag.astype(np.int32).nbytes
        assert REGISTRY.counter("device.bytes_to_host").total() > 0

    def test_rans_books_device_metrics(self):
        from disq_tpu.cram.rans import rans_encode_order0
        from disq_tpu.ops.rans_simd import rans0_decode_simd

        raw = bytes(range(8)) * 40
        stream = rans_encode_order0(raw)
        assert rans0_decode_simd([stream], interpret=True) == [raw]
        assert REGISTRY.counter("device.kernel_launches").value(
            kernel="rans_simd") == 1
        assert REGISTRY.counter("device.bytes_to_device").total() > 0
        assert REGISTRY.counter("device.bytes_to_host").total() > 0
        for name in ("device.launch.wait", "device.launch.d2h"):
            assert any(s["name"] == name
                       and s["labels"].get("kind") == "rans"
                       for s in spans())

    def test_simd_unpack_flagged_lane_counts_host_fallback(self):
        from disq_tpu.ops import inflate_simd

        raw = b"fallback lane payload " * 4
        comp = zlib.compress(raw, 6)[2:-4]
        lanes_u8 = np.zeros((inflate_simd.LANES, 64 * 4), dtype=np.uint8)
        meta = np.zeros((4, inflate_simd.LANES), dtype=np.int32)
        meta[1, 0] = 3  # kernel flagged lane 0 -> host zlib re-inflates
        out = inflate_simd._finalize_lane(
            comp, lanes_u8, meta, 0, len(raw))
        assert out == raw
        assert REGISTRY.counter("device.host_fallback_blocks").value(
            reason="flagged") == 1
