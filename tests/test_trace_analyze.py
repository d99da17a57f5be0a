"""Run analyzer (``scripts/trace_report.py --analyze``): wall-clock
attribution golden, critical-path extraction, bottleneck verdict, and
the CLI round trip over a recorded JSONL."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

sys.path.insert(0, os.path.join(REPO, "scripts"))
import trace_report  # noqa: E402


def _span(name, ts, dur, **labels):
    return {"name": name, "ts": ts, "dur": dur, "run": "r1",
            "labels": labels}


# A synthetic 10-second run with clean numbers:
#   shard 0: fetch [0,2), decode [2,8)
#   shard 1: emit stall [8,9), then fetch [9.5,10)
# -> fetch 2.5s, decode 6s, stall 1s, idle 0.5s over a 10s wall.
SPANS = [
    _span("executor.fetch", 0.0, 2.0, shard=0),
    _span("executor.decode", 2.0, 6.0, shard=0),
    _span("executor.emit.stall", 8.0, 1.0, shard=1),
    _span("executor.fetch", 9.5, 0.5, shard=1),
]


class TestAttribution:
    def test_bucket_seconds_golden(self):
        buckets, t0, t1, wall = trace_report.attribute_wall(SPANS)
        assert (t0, t1, wall) == (0.0, 10.0, 10.0)
        assert buckets == {
            "fetch": pytest.approx(2.5),
            "decode": pytest.approx(6.0),
            "stall": pytest.approx(1.0),
            "idle": pytest.approx(0.5),
        }

    def test_work_beats_stall_and_overlap_attributes_once(self):
        spans = [
            _span("executor.fetch", 0.0, 4.0, shard=0),
            _span("executor.emit.stall", 1.0, 2.0, shard=1),
            _span("executor.decode", 2.0, 4.0, shard=2),
        ]
        buckets, _t0, _t1, wall = trace_report.attribute_wall(spans)
        assert wall == pytest.approx(6.0)
        # [0,2) fetch alone (stall overlap loses to work), [2,4) tie
        # fetch/decode -> WORK_PRIORITY picks decode, [4,6) decode
        assert buckets == {
            "fetch": pytest.approx(2.0),
            "decode": pytest.approx(4.0),
        }

    def test_device_and_transfer_buckets(self):
        spans = [
            _span("device.transfer", 0.0, 1.0, direction="h2d"),
            _span("device.kernel", 1.0, 3.0, kernel="inflate"),
            _span("device.transfer", 4.0, 0.5, direction="d2h"),
        ]
        buckets, *_rest, wall = trace_report.attribute_wall(spans)
        assert wall == pytest.approx(4.5)
        assert buckets == {
            "transfer": pytest.approx(1.5),
            "device": pytest.approx(3.0),
        }

    def test_a_launch_of_the_decode_service_splits_into_four_buckets(self):
        """The dispatcher's six spans of one launch, end to end on its
        one thread: asleep, packing, uploading, blocked on the kernel,
        copying back, delivering — each second in the bucket that says
        whose it is."""
        names = ["device.service.idle", "device.launch.pack",
                 "device.launch.submit", "device.launch.wait",
                 "device.launch.d2h", "device.launch.deliver"]
        spans = [_span(n, float(i), 1.0, kind="inflate", launch=1)
                 for i, n in enumerate(names)]
        buckets, *_rest, wall = trace_report.attribute_wall(spans)
        assert wall == pytest.approx(6.0)
        assert buckets == {
            "service_idle": pytest.approx(1.0),
            "dispatch": pytest.approx(2.0),
            "transfer": pytest.approx(2.0),
            "device": pytest.approx(1.0),
        }
        for bucket in buckets:
            assert bucket in trace_report.ADVICE
            assert bucket in trace_report.WORK_PRIORITY

    def test_depth_host_side_is_a_columnar_row(self):
        """``ops.depth.prepare`` (windowed depth's host side on a
        resident batch: column fetches, the CIGAR pass, the window
        arithmetic) reads in the ``columnar`` row, with the pass it
        holds; the scatter that follows stays the device's."""
        spans = [
            _span("ops.depth.prepare", 0.0, 2.0, records=10, ends="cigar"),
            _span("columnar.batch.ends", 0.5, 1.0, records=10,
                  source="native"),
            _span("device.kernel", 2.0, 1.0, kernel="depth"),
        ]
        buckets, *_rest, wall = trace_report.attribute_wall(spans)
        assert wall == pytest.approx(3.0)
        assert buckets == {
            "columnar": pytest.approx(2.0),
            "device": pytest.approx(1.0),
        }
        assert "ops.depth.prepare" in trace_report.ADVICE["columnar"]

    def test_empty(self):
        assert trace_report.attribute_wall([]) == ({}, 0.0, 0.0, 0.0)


class TestCriticalPath:
    def test_backward_walk_golden(self):
        path = trace_report.critical_path(SPANS)
        assert [(label, round(dur, 6)) for label, _b, dur in path] == [
            ("fetch[shard 0]", 2.0),
            ("decode[shard 0]", 6.0),
            ("stall[shard 1]", 1.0),
            ("idle", 0.5),
            ("fetch[shard 1]", 0.5),
        ]

    def test_innermost_span_wins(self):
        # a long fetch covering the whole window with a kernel inside:
        # the walk descends into the later-starting (inner) span first
        spans = [
            _span("executor.fetch", 0.0, 10.0, shard=0),
            _span("device.kernel", 4.0, 6.0, kernel="parse"),
        ]
        path = trace_report.critical_path(spans)
        assert [(label, dur) for label, _b, dur in path] == [
            ("fetch[shard 0]", 4.0),
            ("device[parse]", 6.0),
        ]


def _supersteps_line(supersteps, **labelled):
    """The analyzer's ``inflate_supersteps`` line for inflate launches
    of these superstep counts; ``labelled`` maps further d2h labels to
    a value a launch (None: a log from before the label)."""
    spans = []
    for i, steps in enumerate(supersteps):
        labels = {k: v[i] for k, v in labelled.items() if v is not None}
        spans += [
            _span("device.launch.wait", 2.0 * i, 0.2,
                  kind="inflate", launch=i),
            _span("device.launch.d2h", 2.0 * i + 0.2, 0.01, kind="inflate",
                  launch=i, supersteps=steps, **labels),
        ]
    return next(ln for ln in trace_report.analyze(
        spans, "r1", ["r1"]).splitlines()
        if ln.startswith("inflate_supersteps"))


class TestVerdict:
    def test_analyze_report_golden(self):
        out = trace_report.analyze(SPANS, "r1", ["r1"])
        assert "run r1  (4 spans, wall 10.000s)" in out
        assert "wall-clock attribution" in out
        # ordered by share, exact percentages
        lines = [ln.strip() for ln in out.splitlines()]
        assert any(ln.startswith("decode") and "60.0%" in ln
                   for ln in lines)
        assert any(ln.startswith("fetch") and "25.0%" in ln
                   for ln in lines)
        assert any(ln.startswith("stall") and "10.0%" in ln
                   for ln in lines)
        assert any(ln.startswith("idle") and "5.0%" in ln
                   for ln in lines)
        assert "critical path (5 segments)" in out
        assert ("verdict: decode is the bottleneck — 60.0% of "
                "wall-clock") in out
        assert "CPU-bound record decode" in out

    def test_inflate_launches_split_into_supersteps_and_their_cost(self):
        """The inflate kernel's two factors: supersteps a launch from
        the d2h spans' label, seconds a superstep from the waits."""
        spans = []
        for i, steps in enumerate((18000, 17000)):
            spans += [
                _span("device.launch.wait", 2.0 * i, 0.2,
                      kind="inflate", launch=i),
                _span("device.launch.d2h", 2.0 * i + 0.2, 0.01,
                      kind="inflate", launch=i, supersteps=steps),
            ]
        spans.append(_span("device.launch.wait", 5.0, 3.0, kind="rans"))
        out = trace_report.analyze(spans, "r1", ["r1"])
        assert ("inflate_supersteps: 17,500 a launch over 2 launches, "
                "11.43 us a superstep") in out
        assert "inflate_supersteps" not in trace_report.analyze(
            SPANS, "r1", ["r1"])

    @pytest.mark.parametrize("far,want", [
        ((17100, 16150), "95.0% of them read history past the ring"),
        ((0, 0), "0.0% of them read history past the ring"),
        (None, "0.0% of them read history past the ring"),
    ], ids=["far", "all-near", "label-absent"])
    def test_inflate_far_share_beside_the_two_factors(self, far, want):
        """The share of supersteps that paid the far sweeps, from the
        d2h spans' ``far_supersteps`` label (meta row 3); a log from
        before the label reads 0."""
        line = _supersteps_line((18000, 17000), far_supersteps=far)
        assert "17,500 a launch over 2 launches" in line
        assert want in line

    @pytest.mark.parametrize("crossing,want", [
        ((460000, 440000), "450,000 copy chunks a launch crossed"),
        ((0, 0), " 0 copy chunks a launch crossed"),
        (None, " 0 copy chunks a launch crossed"),
    ], ids=["crossing", "no-match", "label-absent"])
    def test_inflate_crossing_chunks_beside_the_far_share(
            self, crossing, want):
        """How often a copy chunk ran past the output word it started
        in, from the d2h spans' ``crossing_chunks`` label (meta row 4
        summed over the lanes); a log from before the label reads 0."""
        line = _supersteps_line((15000, 14000), far_supersteps=(7500, 7000),
                                crossing_chunks=crossing)
        assert "14,500 a launch over 2 launches" in line
        assert "50.0% of them read history past the ring" in line
        assert want in line
        assert line.endswith("an output word's boundary")

    @pytest.mark.parametrize("fetches,want", [
        ((3750, 3500), "boundary, the compressed buffer swept in 0.250 "
                       "of them"),
        ((15000, 14000), "swept in 1.000 of them"),
        (None, "an output word's boundary"),
    ], ids=["every-fourth", "every-superstep", "label-absent"])
    def test_inflate_comp_sweep_share_after_the_crossing_chunks(
            self, fetches, want):
        """The share of supersteps in which the kernel swept the
        compressed buffer, from the d2h spans' ``comp_fetches`` label
        (meta row 5); a log from before the label says nothing."""
        line = _supersteps_line((15000, 14000), comp_fetches=fetches)
        assert line.endswith(want)

    @pytest.mark.parametrize("lanes,want", [
        ((13, 128, 128, 40), "; 2 full launches of 13,900 to 14,100 "
                             "supersteps"),
        ((128, 128, 128, 128), "; 4 full launches of 9,000 to 14,100 "
                               "supersteps"),
        ((13, 40, 40, 40), "an output word's boundary"),
        (None, "an output word's boundary"),
    ], ids=["two-full", "all-full", "none-full", "label-absent"])
    def test_inflate_full_launches_range_at_the_lines_end(self, lanes, want):
        """The least and the most supersteps of a log's full launches
        (the d2h spans' ``lanes`` label at 128): a short header or tail
        launch does not set the range; a log without a full launch, or
        from before the label, ends where the line did."""
        line = _supersteps_line((9000, 14100, 13900, 10000), lanes=lanes)
        assert "11,750 a launch over 4 launches" in line
        assert line.endswith(want)

    def test_no_spans(self):
        assert "no spans" in trace_report.analyze([], None, [])

    def test_dropped_spans_banner(self):
        out = trace_report.analyze(SPANS, "r1", ["r1"], dropped=7)
        assert "WARNING" in out and "7 spans dropped" in out
        assert "truncated timeline" in out
        assert "WARNING" not in trace_report.analyze(SPANS, "r1", ["r1"])


class TestCli:
    def _write_jsonl(self, tmp_path):
        log = tmp_path / "spans.jsonl"
        with open(log, "w") as f:
            f.write(json.dumps({"meta": 1, "run_id": "r1"}) + "\n")
            for s in SPANS:
                f.write(json.dumps(s) + "\n")
        return log

    def test_analyze_cli(self, tmp_path):
        log = self._write_jsonl(tmp_path)
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "trace_report.py"),
             str(log), "--analyze"],
            capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
        assert ("verdict: decode is the bottleneck — 60.0% of "
                "wall-clock") in proc.stdout
        assert "wall-clock attribution" in proc.stdout
        assert "critical path" in proc.stdout

    def test_analyze_cli_surfaces_ring_overflow(self, tmp_path):
        log = self._write_jsonl(tmp_path)
        with open(log, "a") as f:
            f.write(json.dumps(
                {"meta": 1, "run_id": "r1", "dropped_spans": 12}) + "\n")
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "trace_report.py"),
             str(log), "--analyze"],
            capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
        assert "WARNING" in proc.stdout
        assert "12 spans dropped" in proc.stdout

    def test_analyze_real_read_names_a_bottleneck(self, tmp_path):
        """--analyze over a real framework read's span log ends in a
        verdict line naming one bucket."""
        from bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records
        from disq_tpu.api import ReadsStorage
        from disq_tpu.runtime.tracing import stop_span_log

        src = tmp_path / "in.bam"
        src.write_bytes(
            make_bam_bytes(DEFAULT_REFS, synth_records(2000, seed=4)))
        log = tmp_path / "real.jsonl"
        ds = (ReadsStorage.make_default().split_size(64 * 1024)
              .executor_workers(4).span_log(str(log)).read(str(src)))
        stop_span_log()
        assert ds.count() == 2000
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "trace_report.py"),
             str(log), "--analyze"],
            capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
        assert "verdict:" in proc.stdout
        assert "is the bottleneck" in proc.stdout
