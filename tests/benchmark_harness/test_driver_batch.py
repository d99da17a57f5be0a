"""The ``batch_passes`` driver end to end on the CPU at a tiny size, the
look for a chip waived inside the test: a sound run is correct; the
control (a dropped record) and a timed path broken underneath are not."""

import json
import os
import subprocess
import sys

import pytest

from harness_util import REPO, TINY, copy_benchmark, manifest, run_tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return copy_benchmark(tmp_path_factory.mktemp("bench"), TINY)


@pytest.mark.parametrize("workload,metric", [
    ("wgs_read", "records_per_s"), ("wgs_sort_write", "write_records_per_s")])
def test_sound_run_is_correct_and_reports_the_end_to_end_metrics(
        root, workload, metric):
    line = run_tiny(root, workload)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {metric, "setup_s"}
    assert line["metrics"][metric]["value"] > 0
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    json.dumps(line)


@pytest.mark.parametrize("workload,metric", [
    ("wgs_read", "records_per_s"), ("wgs_sort_write", "write_records_per_s")])
def test_the_rate_is_all_the_records_over_all_of_the_window(
        root, workload, metric, monkeypatch, capsys):
    """A stall between passes is time of the window: it lowers the rate,
    though no pass's own rate sees it."""
    import time

    from benchmark.drivers import batch_passes

    sound = batch_passes.Driver.one_pass
    calls, stalled = [], []

    def stalling(self, i):
        if calls:                       # every pass but the warm-up
            time.sleep(0.4)
            stalled.append(i)
        calls.append(i)
        sound(self, i)

    monkeypatch.setattr(batch_passes.Driver, "one_pass", stalling)
    line = run_tiny(root, workload)
    assert line["correct"] is True and stalled
    out = capsys.readouterr().out
    window = next(ln for ln in out.splitlines() if ln.startswith("window:"))
    records, elapsed = int(window.split()[1]), float(window.split()[4])
    rates = [float(r) for r in window.split("rates ")[1].split()]
    assert line["attempted"] == len(rates) == len(stalled)
    value = line["metrics"][metric]["value"]
    assert value == pytest.approx(records / elapsed, rel=1e-3)
    assert elapsed >= 0.4 * len(stalled)
    # every pass ran behind a stall that no pass's own rate holds
    assert value < min(rates)


def test_the_tiny_read_is_cut_into_two_splits_and_the_cell_is_not(root):
    from benchmark import run

    spec = run.load_cell(root, "wgs_read")
    assert spec["config"]["split_size_bytes"] == 128 << 20
    assert "split_size_bytes" not in run.load_cell(REPO, "wgs_read")["traffic"]
    small = spec["traffic"]["split_size_bytes"]
    import benchmark.drivers.program as program
    from benchmark import gen

    truth = gen.generate(spec["traffic"]["records"], 5, spec["config"])
    path = os.path.join(root, "two_splits.bam")
    program.write_input(truth, spec["config"], spec["traffic"], path)
    assert small < os.path.getsize(path) <= 2 * small
    os.remove(path)


@pytest.mark.parametrize("workload", ["wgs_read", "wgs_sort_write"])
def test_the_control_a_dropped_record_is_not_correct(root, workload, capsys):
    line = run_tiny(root, workload, control="drop_record")
    assert line["correct"] is False
    assert "FAILED" in capsys.readouterr().out


def test_traced_read_reports_per_layer_metrics_and_no_end_to_end(root):
    line = run_tiny(root, "wgs_read", trace=True)
    assert line["correct"] is True
    got = set(line["metrics"])
    assert {"emit_stall_s", "lane_fill_pct", "launches_per_pass",
            "h2d_bytes_per_record", "d2h_bytes_per_record",
            "compiles_in_window", "setup_compiles"} <= got
    assert "records_per_s" not in got and "setup_s" not in got
    # no device plane on the CPU: the trace readers find nothing to read
    assert "inflate_kernel_s" not in got
    allowed = {p["name"] for p in manifest()["per_layer"]
               if "wgs_read" in p.get("workloads", ["wgs_read"])}
    assert got <= allowed
    assert line["metrics"]["compiles_in_window"]["value"] >= 0
    assert 0 < line["metrics"]["lane_fill_pct"]["value"] <= 100
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_traced_sort_write_reads_the_write_spans(root):
    line = run_tiny(root, "wgs_sort_write", trace=True)
    assert line["correct"] is True
    assert line["metrics"]["write_deflate_s"]["value"] > 0
    assert line["metrics"]["write_encode_s"]["value"] > 0


def test_read_passes_that_answer_wrongly_are_not_correct(root, monkeypatch):
    """The timed path broken underneath: flagstat alters one count where
    it is produced."""
    from disq_tpu.api import ReadsDataset

    sound = ReadsDataset.flagstat

    def broken(self, *a, **kw):
        out = dict(sound(self, *a, **kw))
        out["mapped"] += 1
        return out

    monkeypatch.setattr(ReadsDataset, "flagstat", broken)
    line = run_tiny(root, "wgs_read")
    assert line["correct"] is False and line["failed"] == line["attempted"]


def test_a_sorted_record_altered_where_it_is_produced_is_not_correct(
        root, monkeypatch):
    import dataclasses

    import numpy as np
    from disq_tpu.api import ReadsDataset

    sound = ReadsDataset.coordinate_sorted

    def broken(self, keep_resident=False):
        out = sound(self, keep_resident)
        mapq = np.array(out.reads.mapq)
        mapq[0] ^= 1
        return ReadsDataset(header=out.header, reads=dataclasses.replace(
            out.reads, mapq=mapq))

    monkeypatch.setattr(ReadsDataset, "coordinate_sorted", broken)
    line = run_tiny(root, "wgs_sort_write")
    assert line["correct"] is False


def test_without_a_chip_the_command_fails_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "wgs_sort_write", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_alone_in_an_empty_directory_the_command_fails(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "wgs_read",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "disq_tpu" in proc.stderr
