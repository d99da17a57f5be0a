"""ISSUE 42's per-layer metric ``inflate_comp_fetches_per_pass``, staged.

It reads the counter ``device.inflate.comp_fetches``, which the inflate
kernel has since PR 42 and its parent has not.  On the chip
``benchmark/run.py`` fails a traced run whose listed reader finds
nothing, and the driver makes the parent's traced runs with the change's
benchmark files, so the metric cannot be listed by the PR that brings
the counter.  As ``tests/benchmark_harness/test_handover_metrics.py``
does for PR 38's, its reader file and its entry are laid into a
temporary copy of the benchmark here and read in tiny traced runs of
both cells, so that the next ``benchmark`` PR adds them as data.  This
file lies outside the benchmark's own paths: nothing the benchmark has
is touched.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark_harness"))

from harness_util import TINY, copy_benchmark, run_tiny  # noqa: E402

TINY_CELLS = dict(TINY, mesh4_chain={
    "records": 120, "bgzf_block_payload": 300, "trace_seconds": 1,
    "split_size_bytes": 8192})

NAME = "inflate_comp_fetches_per_pass"
# the supersteps in which the kernel swept the compressed buffer for the
# window of words its loop carries
READER = {"reader": "counter", "key": "device.inflate.comp_fetches",
          "per": "passes"}
ENTRY = {"name": NAME, "unit": "count/pass", "better": "lower",
         "source": "program_counter", "layer": "SIMD codecs",
         "moves": "records_per_s", "workloads": ["wgs_read", "wgs_mesh4"]}


@pytest.fixture(scope="module")
def bench_root(tmp_path_factory):
    """A copy of the benchmark that also lists the metric (one new file,
    one new entry at the end)."""
    root = copy_benchmark(tmp_path_factory.mktemp("bench"), TINY_CELLS)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           NAME + ".json"), "x") as f:
        json.dump(READER, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    assert NAME not in [p["name"] for p in doc["per_layer"]]
    doc["per_layer"].append(ENTRY)
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


@pytest.mark.parametrize("workload", ENTRY["workloads"])
def test_a_traced_run_reads_the_schedules_count(bench_root, workload):
    """The general ``counter`` reader reads the kernel's own count with
    no new code: a sweep every COMP_PERIOD supersteps of every launch,
    the first included."""
    from disq_tpu.ops.inflate_simd import COMP_PERIOD

    line = run_tiny(bench_root, workload, trace=True)
    assert line["correct"] is True
    m = line["metrics"]
    got = m[NAME]
    assert got["unit"] == "count/pass"
    sweeps = m["inflate_supersteps_per_pass"]["value"] / COMP_PERIOD
    assert 0 < sweeps <= got["value"] < (
        sweeps + m["launches_per_pass"]["value"])
