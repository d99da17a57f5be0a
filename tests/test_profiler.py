"""Sampling profiler (``runtime/profiler.py``): folded-stack golden
under a synthetic busy stage at ``executor_workers=4``, per-role
attribution of a real BAM decode, the zero-thread disabled default,
the continuous-profiler options plumbing, the ``/debug/profile``
endpoint + fleet collection, and the ``--flame`` renderer."""

import json
import os
import re
import subprocess
import sys
import threading
import time

import pytest

from bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records
from disq_tpu import ReadsStorage
from disq_tpu.runtime import profiler
from disq_tpu.runtime.executor import ShardPipelineExecutor, ShardTask
from disq_tpu.runtime.introspect import reset_introspection
from disq_tpu.runtime.profiler import SamplingProfiler, role_of
from disq_tpu.runtime.tracing import counter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_REPORT = os.path.join(REPO, "scripts", "trace_report.py")


@pytest.fixture(autouse=True)
def _clean_profiler():
    profiler.reset_profiler()
    reset_introspection()
    yield
    profiler.reset_profiler()
    reset_introspection()


@pytest.fixture(scope="module")
def bam_file(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("profbam") / "in.bam")
    with open(path, "wb") as f:
        f.write(make_bam_bytes(DEFAULT_REFS,
                               synth_records(3000, seed=13)))
    return path


def _burn(seconds: float) -> int:
    """The synthetic busy stage: a named frame the golden asserts on."""
    t0 = time.perf_counter()
    x = 0
    while time.perf_counter() - t0 < seconds:
        x += 1
    return x


@pytest.fixture()
def quick_gil():
    """A spinning thread holds the GIL for a whole switch interval: at
    the default 5 ms, four burners make every hand-over of the pipeline
    (a pool thread's start, a task's submit) wait its turn for 20 ms,
    and the decode threads idle meanwhile, a third of their samples on
    a loaded box.  At 0.5 ms the hand-overs are a tenth of that."""
    was = sys.getswitchinterval()
    sys.setswitchinterval(0.0005)
    yield
    sys.setswitchinterval(was)


def _bystanders(role: str) -> int:
    """Threads of ``role`` that are there before a profile begins: the
    test runner's, not the profiled run's (under xdist the worker's
    receiver thread, which ``threading`` does not even list; pools
    that earlier tests of this process left blocked).  Each is sampled
    once a tick, as the main thread is, so together they account for
    that many times ``by_role()["main"]`` of the role's samples."""
    names = {t.ident: t.name for t in threading.enumerate()}
    return sum(role_of(names.get(tid, "?")) == role
               for tid in sys._current_frames())


class TestRoles:
    def test_canonical_role_mapping(self):
        assert role_of("disq-fetch_0") == "fetch"
        assert role_of("disq-decode_3") == "decode"
        assert role_of("disq-stage_1") == "stage"
        assert role_of("disq-device-dispatch") == "dispatcher"
        assert role_of("disq-hedge_0") == "hedge"
        assert role_of("disq-hostwork_2") == "hostwork"
        assert role_of("disq-http-prefetch_0") == "prefetch"
        assert role_of("MainThread") == "main"
        assert role_of("Thread-7") == "other"


class TestDisabledDefault:
    def test_zero_profiler_thread_when_off(self):
        tasks = [ShardTask(shard_id=i, fetch=lambda: 0,
                           decode=lambda p: p) for i in range(16)]
        list(ShardPipelineExecutor(workers=4).map_ordered(tasks))
        assert profiler.active_profiler() is None
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("disq-profiler")]


class TestSampling:
    def test_folded_golden_synthetic_busy_stage(self, quick_gil):
        """executor_workers=4 with a decode stage that spins in a
        named function: the folded stacks must attribute the burn to
        the ``decode`` role with the function on the stack."""
        before = counter("profile.samples").value(thread_role="decode")
        bystanders = _bystanders("decode")
        prof = SamplingProfiler(hz=400).start()
        tasks = [
            ShardTask(shard_id=i, fetch=lambda: 0,
                      decode=lambda p: _burn(0.05))
            for i in range(12)
        ]
        list(ShardPipelineExecutor(workers=4).map_ordered(tasks))
        prof.stop()
        folded = prof.folded()
        assert folded, "no samples collected"
        # Golden shape: every folded key is role;frame;...;frame and
        # every collapsed line is "<stack> <count>".
        for key in folded:
            assert re.match(r"^[a-z_]+(;[^;]+)+$", key), key
        for line in prof.collapsed().splitlines():
            assert re.match(r"^\S.* \d+$", line), line
        decode_burn = sum(
            n for key, n in folded.items()
            if key.startswith("decode;")
            and "test_profiler.py:_burn" in key)
        assert decode_burn > 0, sorted(folded)[:10]
        by_role = prof.by_role()
        # the burn dominates this run's decode samples
        own = by_role["decode"] - bystanders * by_role["main"]
        assert decode_burn >= own * 0.5
        assert (counter("profile.samples").value(thread_role="decode")
                - before) >= by_role["decode"]

    def test_real_bam_decode_attributes_to_named_roles(self, bam_file):
        """Acceptance: a ~2 s profile of a real BAM decode at w=4
        attributes >= 90% of samples to named thread roles (the
        canonical ``disq-*`` stage names plus the consuming main
        thread) — not to anonymous ``other`` threads."""
        st = (ReadsStorage.make_default().split_size(16 * 1024)
              .executor_workers(4))
        bystanders = _bystanders("other")
        prof = SamplingProfiler(hz=200).start()
        t0 = time.perf_counter()
        n = None
        while time.perf_counter() - t0 < 2.0:
            n = st.read(bam_file).count()
        prof.stop()
        assert n == 3000
        by_role = prof.by_role()
        ticks = by_role["main"]
        other = max(0, by_role.get("other", 0) - bystanders * ticks)
        named = sum(v for k, v in by_role.items() if k != "other")
        assert named > 100, by_role
        assert named / (named + other) >= 0.9, (by_role, bystanders)
        # and the pipeline stages themselves were seen working
        assert by_role.get("fetch", 0) + by_role.get("decode", 0) > 0

    def test_speedscope_document_shape(self):
        prof = SamplingProfiler(hz=400).start()
        _burn(0.1)
        prof.stop()
        doc = prof.speedscope()
        assert doc["$schema"].endswith("file-format-schema.json")
        assert doc["shared"]["frames"]
        names = {p["name"] for p in doc["profiles"]}
        assert "main" in names
        for p in doc["profiles"]:
            assert p["type"] == "sampled"
            assert len(p["samples"]) == len(p["weights"])
            assert p["endValue"] == sum(p["weights"])
            nframes = len(doc["shared"]["frames"])
            assert all(0 <= i < nframes
                       for s in p["samples"] for i in s)


class TestLifecycles:
    def test_profile_hz_option_starts_continuous_profiler(self,
                                                          bam_file):
        st = (ReadsStorage.make_default().split_size(32 * 1024)
              .profile_hz(200))
        st.read(bam_file)
        active = profiler.active_profiler()
        assert active is not None and active.running
        assert [t for t in threading.enumerate()
                if t.name == "disq-profiler"]
        stopped = profiler.stop_profiler()
        assert stopped is active and stopped.samples > 0
        assert profiler.active_profiler() is None
        assert not [t for t in threading.enumerate()
                    if t.name == "disq-profiler"]

    def test_profile_for_window(self):
        prof = profiler.profile_for(0.2, hz=300)
        assert not prof.running
        assert prof.samples > 0
        assert prof.stopped_at is not None

    def test_option_validation(self):
        from disq_tpu import DisqOptions

        with pytest.raises(ValueError):
            DisqOptions().with_profile(0)
        with pytest.raises(ValueError):
            SamplingProfiler(hz=-1)


class TestEndpoints:
    def test_debug_profile_endpoint_collapsed_and_speedscope(self):
        import urllib.request

        from disq_tpu.runtime.introspect import start_introspect_server

        addr = start_introspect_server(0)
        with urllib.request.urlopen(
                f"http://{addr}/debug/profile?seconds=0.3&hz=300",
                timeout=30) as resp:
            body = resp.read().decode()
        assert body.strip(), "empty collapsed profile"
        for line in body.splitlines():
            assert re.match(r"^\S.* \d+$", line), line
        with urllib.request.urlopen(
                f"http://{addr}/debug/profile?seconds=0.2&hz=300"
                "&format=speedscope", timeout=30) as resp:
            doc = json.loads(resp.read())
        assert doc["profiles"]

    def test_cluster_collects_stacks_and_profiles(self):
        """Fleet-wide debug collection: the aggregator fetches
        /debug/stacks and /debug/profile from every worker and labels
        the merge with process ids."""
        from disq_tpu.runtime.cluster import ClusterAggregator
        from disq_tpu.runtime.introspect import start_introspect_server

        addr = start_introspect_server(0)
        agg = ClusterAggregator([addr])
        stacks = agg.debug_stacks()
        assert stacks["cluster"] is True
        (pid, doc), = stacks["processes"].items()
        assert doc["ok"] and "MainThread" in doc["body"]
        merged = agg.debug_profile(seconds=0.3)
        assert merged.strip()
        for line in merged.splitlines():
            assert line.startswith(f"process={pid};"), line


class TestFlameCli:
    def test_flame_renders_collapsed(self, tmp_path):
        prof = SamplingProfiler(hz=400).start()
        _burn(0.15)
        prof.stop()
        collapsed = tmp_path / "profile.collapsed"
        # this thread's stacks alone: every thread blocked in this
        # process is sampled once a tick too, and three of their leaves
        # take the top-3 table from ``_burn``
        collapsed.write_text("".join(
            line for line in prof.collapsed().splitlines(keepends=True)
            if line.startswith("main;")))
        proc = subprocess.run(
            [sys.executable, TRACE_REPORT, str(collapsed), "--flame",
             "--top", "3"],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr
        out = proc.stdout
        assert "flame:" in out and "samples" in out
        assert "top-3 functions by self samples" in out
        assert "test_profiler.py:_burn" in out
        # the role root tier leads the flame
        assert re.search(r"^  main\b", out, re.M), out

    def test_flame_empty_input(self, tmp_path):
        empty = tmp_path / "empty.collapsed"
        empty.write_text("")
        proc = subprocess.run(
            [sys.executable, TRACE_REPORT, str(empty), "--flame"],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0
        assert "no samples" in proc.stdout
