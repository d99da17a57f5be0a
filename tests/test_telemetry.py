"""Structured telemetry layer (``runtime/tracing.py``): registry
thread-safety, histogram bucket correctness, span JSONL round-trip
through a real executor run, exporter golden outputs, and
``phase_report`` back-compat."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records
from disq_tpu.runtime import tracing
from disq_tpu.runtime.executor import ShardPipelineExecutor, ShardTask
from disq_tpu.runtime.tracing import (
    DEFAULT_BUCKETS,
    MetricsRegistry,
    counter,
    gauge,
    histogram,
    metrics_text,
    phase_report,
    gauge_report,
    record_span,
    reset_telemetry,
    span,
    spans,
    start_span_log,
    stop_span_log,
    trace_phase,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    stop_span_log()
    reset_telemetry()
    yield
    stop_span_log()


# -- registry ---------------------------------------------------------------


def test_counter_labels_and_totals():
    c = counter("retry.attempts")
    c.inc(what="header")
    c.inc(2, what="header")
    c.inc(what="shard0")
    assert c.value(what="header") == 3
    assert c.value(what="shard0") == 1
    assert c.value(what="nope") == 0
    assert c.total() == 4


def test_gauge_min_max_last_mean():
    g = gauge("executor.in_flight")
    for v in (3, 7, 2):
        g.observe(v)
    st = g.state()
    assert st["min"] == 2 and st["max"] == 7 and st["last"] == 2
    assert st["samples"] == 3
    assert abs(st["mean"] - 4.0) < 1e-9


def test_kind_conflict_raises():
    counter("retry.attempts")
    with pytest.raises(ValueError, match="already registered"):
        gauge("retry.attempts")
    with pytest.raises(ValueError, match="already registered"):
        histogram("retry.attempts")


def test_registry_thread_safety():
    """Concurrent writers on one counter / gauge / histogram lose no
    increments — the registry is the executor's shared sink."""
    reg = MetricsRegistry()  # private instance: no cross-test state
    c = reg.counter("executor.fetch.calls")
    g = reg.gauge("executor.in_flight")
    h = reg.histogram("executor.fetch")
    N, T = 2000, 8

    def writer(tid):
        for i in range(N):
            c.inc(shard=tid)
            g.observe(i % 7, shard=tid)
            h.observe(0.001 * (i % 50), shard=tid)

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.total() == N * T
    assert h.count == N * T
    for t in range(T):
        assert c.value(shard=t) == N
        assert g.state(shard=t)["samples"] == N


def test_histogram_bucket_correctness():
    h = histogram("executor.fetch")
    # one observation per bucket edge: exactly at an edge lands IN that
    # bucket (le is inclusive, Prometheus-style)
    for edge in DEFAULT_BUCKETS:
        h.observe(edge)
    h.observe(1e9)  # +Inf bucket
    snap = h._snapshot()[""]
    assert snap["count"] == len(DEFAULT_BUCKETS) + 1
    assert snap["buckets"]["+Inf"] == 1
    for edge in DEFAULT_BUCKETS:
        assert snap["buckets"][repr(edge)] == 1
    assert snap["min"] == DEFAULT_BUCKETS[0]
    assert snap["max"] == 1e9


def test_histogram_percentiles_bounded_by_observed_range():
    h = histogram("executor.decode")
    for v in (0.002, 0.003, 0.004, 0.2):
        h.observe(v)
    assert h.percentile(0) >= 0.002
    assert h.percentile(100) == 0.2
    p50 = h.percentile(50)
    assert 0.002 <= p50 <= 0.2
    # single observation reports itself exactly from min/max clamping
    h2 = histogram("executor.emit.stall")
    h2.observe(0.0123)
    assert h2.percentile(50) == pytest.approx(0.0123)
    assert h2.percentile(99) == pytest.approx(0.0123)


def test_reset_zeroes_but_keeps_handles():
    c = counter("retry.attempts")
    c.inc(5)
    reset_telemetry()
    assert c.total() == 0
    c.inc()  # the old handle still writes into the registry
    assert counter("retry.attempts").total() == 1


# -- back-compat views ------------------------------------------------------


def test_phase_report_backcompat():
    with trace_phase("bam.read.header"):
        pass
    with trace_phase("bam.read.header"):
        pass
    rep = phase_report()
    assert rep["bam.read.header"]["calls"] == 2
    assert rep["bam.read.header"]["total_s"] >= 0
    tracing.reset_phase_report()
    assert "bam.read.header" not in phase_report()


def test_gauge_report_legacy_keys():
    tracing.observe_gauge("executor.in_flight", 3)
    tracing.observe_gauge("executor.in_flight", 5)
    rep = gauge_report()
    g = rep["executor.in_flight"]
    # legacy shape preserved...
    assert g["max"] == 5 and g["last"] == 5 and g["samples"] == 2
    # ...plus the new aggregates
    assert g["min"] == 3 and g["mean"] == 4.0


def test_record_span_books_a_wait_that_ends_now():
    record_span("executor.emit.stall", 0.25, shard=2)
    now = time.perf_counter()
    rep = phase_report()
    assert rep["executor.emit.stall"]["calls"] == 1
    assert rep["executor.emit.stall"]["total_s"] == pytest.approx(0.25)
    (s,) = spans()
    assert s["dur"] == 0.25 and s["labels"] == {"shard": 2}
    assert s["ts"] + s["dur"] == pytest.approx(now, abs=0.05)


# -- span ring + sink -------------------------------------------------------


def test_span_ring_caps_and_counts_drops():
    tracing.set_span_ring_capacity(4)
    try:
        for i in range(10):
            record_span("executor.fetch", 0.001, shard=i)
        ring = spans()
        assert len(ring) == 4
        assert [s["labels"]["shard"] for s in ring] == [6, 7, 8, 9]
        assert counter("telemetry.dropped_spans").total() == 6
    finally:
        tracing.set_span_ring_capacity(tracing.DEFAULT_SPAN_RING)


def test_span_records_have_run_id_and_monotonic_ts():
    with span("executor.fetch", shard=1):
        time.sleep(0.002)
    with span("executor.decode", shard=1):
        pass
    a, b = spans()[-2:]
    assert a["run"] == b["run"] == tracing.RUN_ID
    assert a["dur"] >= 0.002
    assert b["ts"] >= a["ts"]  # monotonic ordering
    assert a["labels"] == {"shard": 1}


def test_span_jsonl_roundtrip_through_executor(tmp_path):
    """A real ``ShardPipelineExecutor`` run at w=4 writes a replayable
    JSONL: per-shard fetch/decode spans, shard-id labels, one run id."""
    log = tmp_path / "spans.jsonl"
    start_span_log(str(log))
    ex = ShardPipelineExecutor(workers=4)
    tasks = [
        ShardTask(shard_id=i,
                  fetch=(lambda i=i: (time.sleep(0.002), i)[1]),
                  decode=(lambda v: v * 10))
        for i in range(8)
    ]
    out = [r.value for r in ex.map_ordered(tasks)]
    assert out == [i * 10 for i in range(8)]
    stop_span_log()

    recs = [json.loads(ln) for ln in log.read_text().splitlines()]
    meta = [r for r in recs if r.get("meta")]
    assert meta and meta[0]["run_id"] == tracing.RUN_ID
    evs = [r for r in recs if "name" in r]
    fetch_shards = {r["labels"]["shard"] for r in evs
                    if r["name"] == "executor.fetch"}
    decode_shards = {r["labels"]["shard"] for r in evs
                     if r["name"] == "executor.decode"}
    assert fetch_shards == decode_shards == set(range(8))
    assert all(r["run"] == tracing.RUN_ID for r in evs)
    # the in-memory ring saw the same events
    assert {s["name"] for s in spans()} >= {"executor.fetch",
                                            "executor.decode"}


def test_start_span_log_repoint_and_append(tmp_path):
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    start_span_log(str(a))
    record_span("executor.fetch", 0.001, shard=0)
    start_span_log(str(a))  # same path: no-op, no duplicate meta
    start_span_log(str(b))  # repoint
    record_span("executor.decode", 0.001, shard=0)
    stop_span_log()
    a_recs = [json.loads(ln) for ln in a.read_text().splitlines()]
    b_recs = [json.loads(ln) for ln in b.read_text().splitlines()]
    assert sum(1 for r in a_recs if r.get("meta")) == 1
    assert [r["name"] for r in a_recs if "name" in r] == ["executor.fetch"]
    assert [r["name"] for r in b_recs if "name" in r] == ["executor.decode"]


# -- exporters --------------------------------------------------------------


def test_prometheus_golden():
    counter("retry.attempts").inc(3, what="header")
    gauge("executor.in_flight").observe(4)
    h = histogram("fsw.http.range_get")
    h.observe(0.002)
    h.observe(0.2)
    expected = "\n".join([
        "# TYPE disq_tpu_executor_in_flight gauge",
        "disq_tpu_executor_in_flight 4",
        "# TYPE disq_tpu_fsw_http_range_get_seconds histogram",
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="0.0005"} 0',
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="0.001"} 0',
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="0.0025"} 1',
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="0.005"} 1',
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="0.01"} 1',
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="0.025"} 1',
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="0.05"} 1',
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="0.1"} 1',
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="0.25"} 2',
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="0.5"} 2',
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="1.0"} 2',
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="2.5"} 2',
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="5.0"} 2',
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="10.0"} 2',
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="30.0"} 2',
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="60.0"} 2',
        'disq_tpu_fsw_http_range_get_seconds_bucket{le="+Inf"} 2',
        "disq_tpu_fsw_http_range_get_seconds_sum 0.202",
        "disq_tpu_fsw_http_range_get_seconds_count 2",
        "# TYPE disq_tpu_retry_attempts counter",
        'disq_tpu_retry_attempts{what="header"} 3',
        "",
    ])
    assert metrics_text() == expected


def test_prometheus_label_escaping():
    counter("retry.attempts").inc(what='a"b\\c')
    assert 'what="a\\"b\\\\c"' in metrics_text()


# -- the profiler bridge: spans on the capture's clock ----------------------


@pytest.mark.parametrize("opener", [span, trace_phase],
                         ids=["span", "trace_phase"])
def test_a_span_is_one_event_of_a_profiler_capture(tmp_path, opener):
    """``span("x", ...)`` under a capture is one ``disq_tpu.x`` event
    of the ``.xplane.pb`` (no labels in its name), inside the ring's
    span of the same name; ``trace_phase`` delegates and yields one
    event, not two."""
    from profiler_capture import captured_events

    def body():
        with opener("executor.fetch", shard=3, path="x.bam"):
            time.sleep(0.01)

    events = captured_events(
        tmp_path, body, ["disq_tpu.executor.fetch", "executor.fetch"])
    assert [ev[0] for ev in events] == ["disq_tpu.executor.fetch"]
    (ring,) = [s for s in spans() if s["name"] == "executor.fetch"]
    assert ring["labels"] == {"shard": 3, "path": "x.bam"}
    assert 0.01 <= events[0][2] / 1e9 <= ring["dur"] + 1e-6


def test_spans_of_two_threads_share_the_captures_clock(tmp_path):
    """Spans opened one after another on different threads come out of
    the capture in that order, on one clock; a back-dated
    ``record_span`` is in the ring only."""
    from profiler_capture import captured_events

    def decode():
        with span("executor.decode", shard=0):
            time.sleep(0.005)

    def body():
        with span("executor.fetch", shard=0):
            time.sleep(0.005)
        t = threading.Thread(target=decode)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        record_span("executor.emit.stall", 0.004, shard=0)

    names = ["disq_tpu.executor." + n
             for n in ("fetch", "decode", "emit.stall")]
    events = captured_events(tmp_path, body, names)
    assert [ev[0] for ev in events] == names[:2]
    fetch, decode_ev = events
    assert fetch[1] + fetch[2] <= decode_ev[1]
    assert {s["name"] for s in spans()} >= {
        "executor.fetch", "executor.decode", "executor.emit.stall"}


def test_without_jax_a_span_opens_no_annotation(monkeypatch):
    """No jax in the process, no annotation (and no import of it for a
    span's sake): the span books in the ring as before."""
    monkeypatch.setattr(tracing, "_annotation_cls", None)
    monkeypatch.delitem(sys.modules, "jax")
    assert tracing.annotate("executor.fetch") is tracing._NO_ANNOTATION
    with span("executor.fetch", shard=1):
        pass
    assert "jax" not in sys.modules
    assert [s["name"] for s in spans()] == ["executor.fetch"]


# -- end-to-end: BAM read -> span log -> trace_report -----------------------


def _read_bam_with_span_log(tmp_path, n=3000, workers=4):
    src = tmp_path / "in.bam"
    src.write_bytes(make_bam_bytes(DEFAULT_REFS, synth_records(n, seed=9)))
    log = tmp_path / "spans.jsonl"
    from disq_tpu.api import ReadsStorage

    ds = (ReadsStorage.make_default().split_size(64 * 1024)
          .executor_workers(workers).span_log(str(log)).read(str(src)))
    stop_span_log()
    return ds, log, n


def test_bam_read_span_log_and_telemetry_report(tmp_path):
    ds, log, n = _read_bam_with_span_log(tmp_path)
    assert ds.count() == n
    recs = [json.loads(ln) for ln in log.read_text().splitlines()]
    names = {r["name"] for r in recs if "name" in r}
    assert {"executor.fetch", "executor.decode", "bam.split.fetch",
            "bam.split.decode", "bam.read.header"} <= names
    fetch = [r for r in recs if r.get("name") == "bam.split.fetch"]
    assert len({r["labels"]["shard"] for r in fetch}) > 1
    assert all("lo" in r["labels"] and "hi" in r["labels"] for r in fetch)

    rep = ds.telemetry_report()
    assert rep["run_id"] == tracing.RUN_ID
    assert rep["counters"]["records"] == n
    assert "bam.split.decode" in rep["phases"]
    assert "executor.in_flight" in rep["gauges"]
    assert "bam.split.fetch" in rep["metrics"]["histograms"]


def test_trace_report_cli_waterfall_and_percentiles(tmp_path):
    _, log, _ = _read_bam_with_span_log(tmp_path)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
         str(log), "--width", "48"],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    out = proc.stdout
    assert "per-shard waterfall" in out
    assert "shard 0" in out and "F=fetch D=decode" in out
    assert "phase latency percentiles" in out
    assert "p50" in out and "p99" in out
    assert "executor.fetch" in out and "executor.decode" in out
    assert "stall attribution" in out
    assert "straggler shards" in out


def test_trace_jsonl_env_knob(tmp_path):
    """DISQ_TPU_TRACE_JSONL alone (no API calls) produces the span log
    — run in a subprocess so the once-per-process env resolution is
    actually exercised fresh."""
    src = tmp_path / "in.bam"
    src.write_bytes(make_bam_bytes(DEFAULT_REFS, synth_records(800, seed=3)))
    log = tmp_path / "env_spans.jsonl"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               DISQ_TPU_TRACE_JSONL=str(log))
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from disq_tpu.api import ReadsStorage\n"
        "ds = (ReadsStorage.make_default().split_size(64*1024)"
        ".executor_workers(4).read(%r))\n"
        "assert ds.count() == 800\n" % (REPO, str(src)))
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr
    recs = [json.loads(ln) for ln in log.read_text().splitlines()]
    names = {r["name"] for r in recs if "name" in r}
    assert "executor.fetch" in names and "bam.split.decode" in names


def test_metrics_text_exposes_retry_and_quarantine(tmp_path):
    """Acceptance: retry + quarantine counters from a faulty read show
    in the Prometheus exposition."""
    import numpy as np
    from disq_tpu.api import ReadsStorage
    from disq_tpu.fsw.faultfs import FaultInjectingFileSystemWrapper, FaultSpec
    from disq_tpu.fsw.filesystem import PosixFileSystemWrapper

    src = tmp_path / "in.bam"
    raw = make_bam_bytes(DEFAULT_REFS, synth_records(500, seed=5))
    src.write_bytes(raw)
    fs = FaultInjectingFileSystemWrapper(
        PosixFileSystemWrapper(),
        [FaultSpec(kind="transient", path_substr="in.bam",
                   call_index=2, times=1)])
    from disq_tpu.bam.source import BamSource

    class _Storage:
        _split_size = 64 * 1024
        _options = None

    src_obj = BamSource(_Storage())
    from disq_tpu.bam.source import read_header

    header, first_vo = read_header(fs, str(src))
    batches = src_obj.read_split_batches(fs, str(src), header, first_vo)
    assert sum(b.count for b in batches) == 500
    txt = metrics_text()
    assert "disq_tpu_retry_attempts" in txt

    # quarantine path: corrupt one block payload, read with QUARANTINE
    bad = bytearray(raw)
    # Flip a byte in the LAST data block's payload (past the header
    # block, before the 28-byte EOF marker) — header corruption is
    # never skippable, so a mid-header flip would raise under any
    # policy.
    bad[len(bad) - 200] ^= 0xFF
    bad_path = tmp_path / "bad.bam"
    bad_path.write_bytes(bytes(bad))
    qdir = tmp_path / "q"
    from disq_tpu.runtime.errors import DisqOptions, ErrorPolicy

    ds = (ReadsStorage.make_default().split_size(64 * 1024)
          .options(DisqOptions(error_policy=ErrorPolicy.QUARANTINE,
                               quarantine_dir=str(qdir)))
          .read(str(bad_path)))
    assert ds.counters.quarantined_blocks >= 1
    txt = metrics_text()
    assert "disq_tpu_quarantine_blocks" in txt
