"""Request-scoped distributed tracing + per-tenant SLO layer
(``runtime/tracing.py`` trace contexts, ``runtime/slo.py``): header
roundtrips, span/event trace stamping, device-service owner
attribution, burn-rate math over synthetic latency, ``/healthz``
degradation on a fast burn, the ``/slo`` endpoint, and the end-to-end
acceptance — a multi-tenant request traced across TWO serve replicas
stitched into one waterfall by ``trace_report.py --request`` covering
≥95% of the measured wall-clock."""

import json
import re
import os
import subprocess
import sys
import threading
import urllib.error
import urllib.request
import zlib

import pytest

from disq_tpu.runtime import flightrec, slo
from disq_tpu.runtime import serve as serve_mod
from disq_tpu.runtime.introspect import (
    HEALTH, start_introspect_server, stop_introspect_server)
from disq_tpu.runtime.tracing import (
    TRACE_ID_HEADER,
    TRACE_PARENT_HEADER,
    TRACE_TENANT_HEADER,
    TraceContext,
    activate_trace,
    child_context,
    counter,
    current_trace,
    deactivate_trace,
    histogram,
    inject_trace_headers,
    mint_trace,
    record_span,
    reset_telemetry,
    reset_trace_state,
    spans,
    trace_from_headers,
    trace_requests_enabled,
    trace_scope,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_state():
    reset_trace_state()
    reset_telemetry()
    yield
    slo.reset_slo()
    reset_trace_state()
    reset_telemetry()


# -- trace context plumbing --------------------------------------------------


class TestTraceContext:
    def test_header_roundtrip(self):
        ctx = mint_trace("acme")
        token = activate_trace(ctx)
        try:
            headers = inject_trace_headers(
                {"Content-Type": "application/json"})
        finally:
            deactivate_trace(token)
        assert headers[TRACE_ID_HEADER] == ctx.trace_id
        assert headers[TRACE_PARENT_HEADER] == ctx.span_id
        assert headers[TRACE_TENANT_HEADER] == "acme"
        back = trace_from_headers(headers)
        assert back is not None
        assert back.trace_id == ctx.trace_id
        assert back.tenant == "acme"

    def test_inject_is_noop_without_context(self):
        assert current_trace() is None
        headers = {"Range": "bytes=0-9"}
        assert inject_trace_headers(headers) == {"Range": "bytes=0-9"}
        assert trace_from_headers({}) is None

    def test_trace_requests_env_resolved_once(self, monkeypatch):
        monkeypatch.setenv("DISQ_TPU_TRACE_REQUESTS", "1")
        reset_trace_state()
        assert trace_requests_enabled()
        # resolved once: flipping the env after resolution changes
        # nothing until reset_trace_state
        monkeypatch.delenv("DISQ_TPU_TRACE_REQUESTS")
        assert trace_requests_enabled()
        reset_trace_state()
        assert not trace_requests_enabled()

    def test_child_keeps_trace_and_tenant(self):
        ctx = TraceContext("deadbeef", "01", "t0")
        kid = child_context(ctx)
        assert kid.trace_id == "deadbeef"
        assert kid.tenant == "t0"
        assert kid.span_id != ctx.span_id

    def test_span_stamped_under_active_context(self):
        ctx = TraceContext("feedface", "02", "lab")
        with trace_scope(ctx):
            record_span("serve.admission.wait", 0.001, tenant="lab")
        rec = spans()[-1]
        assert rec["name"] == "serve.admission.wait"
        assert rec["trace"] == "feedface"
        assert rec["parent"] == "02"
        assert rec["tenant"] == "lab"
        # outside the scope nothing is stamped
        record_span("serve.admission.wait", 0.001, tenant="lab")
        assert "trace" not in spans()[-1]

    def test_trace_scope_none_is_noop(self):
        with trace_scope(None):
            assert current_trace() is None

    def test_flightrec_events_stamped(self, tmp_path):
        flightrec.enable(str(tmp_path))
        try:
            ctx = TraceContext("0ddba11", "03", "evicted")
            with trace_scope(ctx):
                cache = serve_mod.HotBlockCache(
                    compressed_bytes=1 << 10, decoded_bytes=1 << 10,
                    parsed_bytes=1 << 10)
                for i in range(4):
                    cache.put("decoded", "p", i, b"x" * 512, 512, "t9")
            evs = [e for e in flightrec.recorder().events()
                   if e["kind"] == "serve_cache_evict"]
            assert evs, "eviction under budget must record an event"
            assert evs[-1]["trace"] == "0ddba11"
            assert evs[-1]["tier"] == "decoded"
            # the event's own tenant field wins over the context's
            assert evs[-1]["tenant"] == "t9"
        finally:
            flightrec.reset_flightrec()


# -- device-service owner attribution ----------------------------------------


class _StubInflateEngine:
    """Host-only engine stub: the dispatcher's batching/attribution is
    what is under test, not the kernel."""

    kind = "inflate"

    def launch(self, lanes, labels):
        return [zlib.decompress(l.payload, -15) for l in lanes]

    def finalize(self, handle, lanes, labels):
        for lane, out in zip(lanes, handle):
            lane.sub.deliver(lane.index, out)


class TestDeviceBatchAttribution:
    def test_owner_share_spans_and_request_count(self):
        from disq_tpu.runtime.device_service import DeviceDecodeService

        svc = DeviceDecodeService(flush_timeout_s=0.005, interpret=True)
        svc._engines["inflate"] = _StubInflateEngine()
        data = [b"a" * 300, b"b" * 200]
        comp = [zlib.compress(d)[2:-4] for d in data]
        ctx = mint_trace("devten")
        token = activate_trace(ctx)
        try:
            sub = svc.submit_inflate(comp, [len(d) for d in data])
            blob, offsets = sub.result(timeout=30)
        finally:
            deactivate_trace(token)
            svc.close()
        assert bytes(blob[:300]) == data[0]
        assert counter("device.batch.requests").value(requests="1") >= 1
        share = [s for s in spans() if s["name"] == "device.batch.share"]
        assert share, "each owning request books its batch share"
        assert share[-1]["trace"] == ctx.trace_id
        assert share[-1]["tenant"] == "devten"
        assert share[-1]["labels"]["lanes"] == 2
        assert share[-1]["labels"]["batch_lanes"] == 2

    def test_untraced_submissions_book_nothing(self):
        from disq_tpu.runtime.device_service import DeviceDecodeService

        svc = DeviceDecodeService(flush_timeout_s=0.005, interpret=True)
        svc._engines["inflate"] = _StubInflateEngine()
        comp = [zlib.compress(b"z" * 100)[2:-4]]
        try:
            assert current_trace() is None
            svc.submit_inflate(comp, [100]).result(timeout=30)
        finally:
            svc.close()
        assert counter("device.batch.requests").total() == 0
        assert not [s for s in spans()
                    if s["name"] == "device.batch.share"]


# -- SLO spec + burn-rate math ----------------------------------------------


class TestSloSpec:
    def test_parse_clauses_and_wildcard(self):
        objs = slo.parse_slo_spec("t0:250:99, *:500:95:99.9")
        assert objs["t0"].latency_s == pytest.approx(0.25)
        assert objs["t0"].target == pytest.approx(0.99)
        assert objs["t0"].availability is None
        assert objs["*"].availability == pytest.approx(0.999)

    @pytest.mark.parametrize("bad", [
        "t0:250",                 # too few fields
        "t0:250:99:99.9:extra",   # too many fields
        ":250:99",                # empty tenant
        "t0:zero:99",             # non-numeric
        "t0:-5:99",               # latency <= 0
        "t0:250:0",               # pct out of (0, 100)
        "t0:250:100",
        "",                       # empty spec
        " , ",
    ])
    def test_parse_rejects_malformed(self, bad):
        with pytest.raises(ValueError):
            slo.parse_slo_spec(bad)


class _Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def _inject_latency(n, seconds, tenant, errors=0):
    h = histogram("serve.request")
    for _ in range(n):
        h.observe(seconds, endpoint="reads", tenant=tenant)
    if errors:
        counter("serve.request.errors").inc(
            errors, endpoint="reads", tenant=tenant)


class TestSloEvaluator:
    def test_burn_rate_over_synthetic_latency(self):
        clock = _Clock()
        ev = slo.SloEvaluator(slo.parse_slo_spec("t0:100:99"),
                              interval_s=3600.0, clock=clock)
        try:
            # 50 requests all at 500 ms against a 100 ms / 99% target:
            # every one is bad, burn = 1.0 / 0.01 = 100 per window
            _inject_latency(50, 0.5, "t0")
            clock.t += 61
            doc = ev.evaluate_now()
            t0 = doc["tenants"]["t0"]
            w60 = t0["windows"]["60"]
            assert w60["total"] == 50 and w60["good"] == 0
            assert w60["burn"] == pytest.approx(100.0)
            assert t0["fast_burn"] is True
            frag = ev.health_fragment()
            assert frag["fast_burn_tenants"] == ["t0"]
            assert frag["worst_burn"]["t0"] == pytest.approx(100.0)
        finally:
            ev.stop()

    def test_within_target_burns_zero(self):
        clock = _Clock()
        ev = slo.SloEvaluator(slo.parse_slo_spec("t0:100:99"),
                              interval_s=3600.0, clock=clock)
        try:
            _inject_latency(50, 0.001, "t0")  # all well under 100 ms
            clock.t += 61
            doc = ev.evaluate_now()
            t0 = doc["tenants"]["t0"]
            assert t0["windows"]["60"]["burn"] == pytest.approx(0.0)
            assert t0["fast_burn"] is False
        finally:
            ev.stop()

    def test_availability_burn_from_error_counter(self):
        clock = _Clock()
        ev = slo.SloEvaluator(slo.parse_slo_spec("*:1000:50:99"),
                              interval_s=3600.0, clock=clock)
        try:
            # fast latency but 10/100 requests 5xx against 99%
            # availability: burn = 0.1 / 0.01 = 10
            _inject_latency(100, 0.001, "tx", errors=10)
            clock.t += 61
            doc = ev.evaluate_now()
            w60 = doc["tenants"]["tx"]["windows"]["60"]
            assert w60["errors"] == 10
            assert w60["availability_burn"] == pytest.approx(10.0)
        finally:
            ev.stop()

    def test_unconfigured_is_structurally_off(self):
        assert slo.evaluator_if_running() is None
        doc = slo.slo_doc()
        assert doc["enabled"] is False and doc["tenants"] == {}
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("disq-slo")]

    def test_fast_burn_degrades_healthz(self):
        clock = _Clock()
        slo.configure("t0:100:99", interval_s=3600.0, clock=clock)
        try:
            _inject_latency(50, 0.5, "t0")
            clock.t += 61
            slo.evaluator_if_running().evaluate_now()
            doc = HEALTH.healthz()
            assert doc["status"] == "degraded"
            assert doc["slo"]["fast_burn_tenants"] == ["t0"]
        finally:
            slo.reset_slo()
        # with the evaluator gone, healthz recovers
        assert "slo" not in HEALTH.healthz()

    def test_slo_endpoint(self):
        clock = _Clock()
        slo.configure("t0:100:99", interval_s=3600.0, clock=clock)
        addr = start_introspect_server(0)
        try:
            _inject_latency(20, 0.5, "t0")
            clock.t += 61
            slo.evaluator_if_running().evaluate_now()
            with urllib.request.urlopen(f"http://{addr}/slo",
                                        timeout=10) as r:
                doc = json.loads(r.read())
            assert doc["enabled"] is True
            assert doc["tenants"]["t0"]["fast_burn"] is True
            assert "process_id" in doc
        finally:
            stop_introspect_server()
            slo.reset_slo()


# -- serving-plane satellites -------------------------------------------------


class TestServeTracing:
    def test_oldest_wait_seconds_in_stats(self):
        adm = serve_mod.TenantAdmission(slots=1, queue_depth=4)
        adm.acquire("t")
        released = threading.Event()

        def waiter():
            adm.acquire("t")
            adm.release("t")
            released.set()

        th = threading.Thread(target=waiter)
        th.start()
        spins = 500
        while spins and adm.stats()["tenants"].get(
                "t", {}).get("queued", 0) < 1:
            spins -= 1
            threading.Event().wait(0.01)
        st = adm.stats()["tenants"]["t"]
        assert st["queued"] == 1
        assert st["oldest_wait_s"] > 0.0
        adm.release("t")
        th.join(timeout=10)
        assert released.is_set()
        assert adm.stats()["tenants"]["t"]["oldest_wait_s"] == 0.0

    def test_shed_records_flightrec_event_and_root_span(self, tmp_path):
        flightrec.enable(str(tmp_path))
        addr = serve_mod.start_serve(port=0, tenant_slots=1,
                                     tenant_queue=0)
        d = serve_mod.serve_if_running()
        d.admission.acquire("pig")
        try:
            req = urllib.request.Request(
                f"http://{addr}/query/reads",
                data=json.dumps({"dataset": "x", "tenant": "pig",
                                 "intervals": []}).encode(),
                headers={"Content-Type": "application/json",
                         TRACE_ID_HEADER: "beefcafe00000001",
                         TRACE_PARENT_HEADER: "00",
                         TRACE_TENANT_HEADER: "pig"},
                method="POST")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 429
            evs = [e for e in flightrec.recorder().events()
                   if e["kind"] == "serve_shed"]
            assert evs and evs[-1]["tenant"] == "pig"
            assert evs[-1]["trace"] == "beefcafe00000001"
            roots = [s for s in spans()
                     if s["name"] == "serve.request.trace"]
            assert roots and roots[-1]["trace"] == "beefcafe00000001"
            assert roots[-1]["labels"]["status"] == 429
        finally:
            d.admission.release("pig")
            serve_mod.stop_serve()
            stop_introspect_server()
            flightrec.reset_flightrec()

    def test_no_trace_minted_without_optin(self):
        from disq_tpu.runtime.tracing import trace_ids_minted

        addr = serve_mod.start_serve(port=0, tenant_slots=2,
                                     tenant_queue=2)
        try:
            minted0 = trace_ids_minted()
            req = urllib.request.Request(
                f"http://{addr}/query/reads",
                data=json.dumps({"dataset": "nope", "tenant": "t",
                                 "intervals": []}).encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 404  # unknown dataset, no shed
            assert trace_ids_minted() == minted0
            assert not [s for s in spans()
                        if s["name"] == "serve.request.trace"]
        finally:
            serve_mod.stop_serve()
            stop_introspect_server()


# -- acceptance: one request stitched across two serve replicas ---------------


REPLICA_CODE = """\
import sys
sys.path.insert(0, {repo!r})
from disq_tpu.runtime import serve as serve_mod
addr = serve_mod.start_serve(port=0, tenant_slots=8, tenant_queue=32)
serve_mod.serve_if_running().register("reads", sys.argv[1])
print("ADDR", addr, flush=True)
sys.stdin.readline()  # hold the replica open until the parent is done
serve_mod.stop_serve()
"""


@pytest.fixture(scope="module")
def stitch_bam(tmp_path_factory):
    from disq_tpu import BaiWriteOption, ReadsStorage, SbiWriteOption
    from tests.bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records

    raw = str(tmp_path_factory.mktemp("stitch") / "raw.bam")
    with open(raw, "wb") as f:
        f.write(make_bam_bytes(DEFAULT_REFS,
                               synth_records(1200, seed=7,
                                             unmapped_tail=0),
                               blocksize=700))
    storage = ReadsStorage.make_default().num_shards(4)
    out = str(tmp_path_factory.mktemp("stitch") / "sorted.bam")
    storage.write(storage.read(raw), out, BaiWriteOption.ENABLE,
                  SbiWriteOption.ENABLE, sort=True)
    return out


class TestStitchedWaterfall:
    def test_two_replica_request_stitches_to_one_waterfall(
            self, stitch_bam, tmp_path):
        """Acceptance: a multi-tenant request fanned to TWO replica
        processes stitches into one waterfall covering ≥95% of the
        measured wall-clock, remainder attributed as gap buckets."""
        procs, addrs, logs = [], [], []
        code = REPLICA_CODE.format(repo=REPO)
        trace_id = "cafe0123deadbeef"
        try:
            for i in range(2):
                log = str(tmp_path / f"replica{i}.jsonl")
                logs.append(log)
                env = dict(os.environ, JAX_PLATFORMS="cpu",
                           DISQ_TPU_TRACE_JSONL=log,
                           DISQ_TPU_TRACE_REQUESTS="1")
                p = subprocess.Popen(
                    [sys.executable, "-c", code, stitch_bam],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    text=True, env=env, cwd=REPO)
                procs.append(p)
            for p in procs:
                line = p.stdout.readline()
                assert line.startswith("ADDR "), line
                addrs.append(line.split()[1])

            # the same trace id hits both replicas concurrently, one
            # tenant per replica — the stitcher must interleave them
            barrier = threading.Barrier(2)
            outcomes = [None, None]

            def client(i):
                barrier.wait()
                req = urllib.request.Request(
                    f"http://{addrs[i]}/query/reads",
                    data=json.dumps({
                        "dataset": "reads", "tenant": f"t{i}",
                        "intervals": [{"contig": "chr1", "start": 1,
                                       "end": 250_000}],
                        "digest": True}).encode(),
                    headers={"Content-Type": "application/json",
                             TRACE_ID_HEADER: trace_id,
                             TRACE_PARENT_HEADER: "00",
                             TRACE_TENANT_HEADER: f"t{i}"},
                    method="POST")
                with urllib.request.urlopen(req, timeout=120) as r:
                    outcomes[i] = (r.status, json.loads(r.read()))

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            assert all(o is not None and o[0] == 200 for o in outcomes), \
                outcomes
        finally:
            for p in procs:
                try:
                    p.stdin.close()
                except OSError:
                    pass
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()

        script = os.path.join(REPO, "scripts", "trace_report.py")
        proc = subprocess.run(
            [sys.executable, script, logs[0], logs[1],
             "--request", trace_id],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        out = proc.stdout
        assert f"trace {trace_id}" in out
        assert "2 processes" in out
        assert "serve.request.trace" in out
        assert "t0" in out and "t1" in out
        m = re.search(r"coverage: ([0-9.]+)% of client wall-clock", out)
        assert m, out
        assert float(m.group(1)) >= 95.0, out
