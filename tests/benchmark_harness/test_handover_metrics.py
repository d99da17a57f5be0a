"""The per-layer metrics of ISSUE 38.

Six enter ``BENCHMARK.json`` with it, as data only: a reader file and an
entry each, reading spans and counters the program has had for four to
eleven PRs (so the parent's traced run, which the driver makes with the
change's benchmark files, reads them too).  Six more read what this PR
adds to the program (the hand-over's spans, the dispatcher's idle
counter) and cannot be listed yet: on
the chip ``benchmark/run.py`` fails a traced run whose listed reader
finds nothing.  As ``test_span_metrics.py`` did for PR 26's spans, they
are laid into a temporary copy of the benchmark here and read in a tiny
traced run, so that the next ``benchmark`` PR adds them as data.
"""

import json
import os
import time

import pytest

from harness_util import REPO, TINY, copy_benchmark, manifest, run_tiny

TINY_CELLS = dict(TINY, mesh4_chain={
    "records": 120, "bgzf_block_payload": 300, "trace_seconds": 1,
    "split_size_bytes": 8192})

# metric: (reader file, unit, better, source, layer, moves, cells)
ENTERED = {
    "depth_prepare_s": (
        {"reader": "span_sum", "key": "ops.depth.prepare", "per": "passes"},
        "s/pass", "lower", "program_span", "resident parse",
        "records_per_s", ["wgs_read", "wgs_mesh4"]),
    "ends_from_cigar_per_pass": (
        {"reader": "counter", "key": "columnar.batch.ends_from_cigar",
         "per": "passes"},
        "records/pass", "higher", "program_counter", "resident parse",
        "records_per_s", ["wgs_read", "wgs_mesh4"]),
    "inflate_far_supersteps_per_pass": (
        {"reader": "counter", "key": "device.inflate.far_supersteps",
         "per": "passes"},
        "count/pass", "lower", "program_counter", "SIMD codecs",
        "records_per_s", ["wgs_read", "wgs_mesh4"]),
    "inflate_crossing_chunks_per_pass": (
        {"reader": "counter", "key": "device.inflate.crossing_chunks",
         "per": "passes"},
        "count/pass", "higher", "program_counter", "SIMD codecs",
        "records_per_s", ["wgs_read", "wgs_mesh4"]),
    "flush_timeout_launches_per_pass": (
        {"reader": "counter", "key": "device.batch.flush",
         "labels": ["reason=timeout"], "per": "passes"},
        "count/pass", "lower", "program_counter", "decode service",
        "records_per_s", ["wgs_read", "wgs_mesh4"]),
    "sort_write_gather_s": (
        {"reader": "span_sum", "key": "sort.gather", "per": "passes"},
        "s/pass", "lower", "program_span", "sort",
        "write_records_per_s", ["wgs_sort_write"]),
}
# a tiny file's matches stay inside the ring, and a launch may fill
ZERO_AT_TINY = {"inflate_far_supersteps_per_pass",
                "flush_timeout_launches_per_pass"}

# what the next `benchmark` PR would list, once this PR is the parent
QUEUED = {
    "inflate_verify_s": (
        {"reader": "span_sum", "key": "codec.inflate.verify",
         "per": "passes"},
        "s/pass", "lower", "program_span", "SIMD codecs",
        "records_per_s", ["wgs_read", "wgs_mesh4"]),
    "parse_stage_s": (
        {"reader": "span_sum", "key": "columnar.batch.stage",
         "per": "passes"},
        "s/pass", "lower", "program_span", "resident parse",
        "records_per_s", ["wgs_read", "wgs_mesh4"]),
    # core-seconds over the writers, as ``write_encode_s`` and
    # ``mesh4_write_encode_s`` beside them: encode proper is those
    # minus these
    "write_slice_s": (
        {"reader": "span_sum", "key": "bam.write.slice", "per": "passes"},
        "s/pass", "lower", "program_span", "host codecs and write",
        "write_records_per_s", ["wgs_sort_write"]),
    "mesh4_write_slice_s": (
        {"reader": "span_sum", "key": "bam.write.slice", "per": "passes"},
        "s/pass", "lower", "program_span", "host codecs and write",
        "records_per_s", ["wgs_mesh4"]),
    "dispatcher_idle_empty_s": (
        {"reader": "counter", "key": "device.service.idle_seconds",
         "labels": ["reason=empty"], "per": "passes"},
        "s/pass", "lower", "program_counter", "decode service",
        "records_per_s", ["wgs_read", "wgs_mesh4"]),
    "dispatcher_idle_filling_s": (
        {"reader": "counter", "key": "device.service.idle_seconds",
         "labels": ["reason=filling"], "per": "passes"},
        "s/pass", "lower", "program_counter", "decode service",
        "records_per_s", ["wgs_read", "wgs_mesh4"]),
}


def entry(name, spec):
    _reader, unit, better, source, layer, moves, cells = spec
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": cells}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced tiny run a cell, in a copy of the benchmark that also
    lists the queued metrics (new files, new entries at the end)."""
    root = copy_benchmark(tmp_path_factory.mktemp("bench"), TINY_CELLS)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    for name, spec in QUEUED.items():
        with open(os.path.join(root, "benchmark", "layer_metrics",
                               name + ".json"), "x") as f:
            json.dump(spec[0], f)
        doc["per_layer"].append(entry(name, spec))
    with open(path, "w") as f:
        json.dump(doc, f)
    lines = {}

    def line_of(workload):
        if workload not in lines:
            t0 = time.perf_counter()
            lines[workload] = run_tiny(root, workload, trace=True)
            line_of.wall[workload] = time.perf_counter() - t0
        return lines[workload]

    line_of.wall = {}
    return line_of


@pytest.mark.parametrize("name", sorted(ENTERED))
def test_the_entry_and_its_reader_file_are_the_issues_table(name):
    """One appended entry and one new reader file a metric, as ISSUE 38
    gives them, each listed once."""
    from benchmark import readers

    listed = [p for p in manifest()["per_layer"] if p["name"] == name]
    assert listed == [entry(name, ENTERED[name])]
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec == ENTERED[name][0]
    assert spec["reader"] in readers.READERS


def test_the_six_are_listed_in_the_issues_order():
    """Names stay unique; a later PR appends after them, so neither the
    list's length nor its tail is held here."""
    names = [p["name"] for p in manifest()["per_layer"]]
    assert len(names) == len(set(names))
    assert [n for n in names if n in ENTERED] == list(ENTERED)


@pytest.mark.parametrize("name,workload", [
    (n, w) for n, spec in ENTERED.items() for w in spec[-1]])
def test_a_traced_run_of_a_listed_cell_reads_the_metric(
        traced, name, workload):
    line = traced(workload)
    assert line["correct"] is True
    got = line["metrics"][name]
    assert got["unit"] == ENTERED[name][1]
    if name in ZERO_AT_TINY:
        assert got["value"] >= 0
    else:
        assert got["value"] > 0
    if name == "ends_from_cigar_per_pass":
        # depth took every record's end from its CIGAR bytes
        assert got["value"] == {"wgs_read": 90, "wgs_mesh4": 120}[workload]


@pytest.mark.parametrize("name,workload", [
    (n, w) for n, spec in QUEUED.items() for w in spec[-1]])
def test_a_traced_run_reads_the_queued_metric_as_it_stands(
        traced, name, workload):
    """The general readers read this PR's own spans, labels and counter
    with no new code: ``span_sum`` and ``counter`` (with ``labels`` for
    the sleep's reason)."""
    line = traced(workload)
    assert line["correct"] is True
    got = line["metrics"][name]
    assert got["unit"] == "s/pass"
    if name == "dispatcher_idle_filling_s":
        assert got["value"] >= 0    # a launch that filled slept for none
    else:
        assert got["value"] > 0


def test_one_mesh_run_reads_every_queued_metric_of_its_cell(traced):
    """The four-chip chain reads, parses and writes: one traced run of
    it holds every span and counter the queued metrics read.  The
    sleep is read between the harness's two counter snapshots, which in
    a traced run lie round the window AND ``jax.profiler.stop_trace``
    (on the CPU the longer of the two): no more than the run lasted."""
    m = traced("wgs_mesh4")["metrics"]
    names = [n for n, spec in QUEUED.items() if "wgs_mesh4" in spec[-1]]
    assert len(names) == 5 and all(n in m for n in names), names
    assert (m["dispatcher_idle_empty_s"]["value"]
            + m["dispatcher_idle_filling_s"]["value"]) \
        <= traced.wall["wgs_mesh4"]
    # the staging copy is a part of the build, the slice of the encode
    assert m["parse_stage_s"]["value"] <= m["parse_build_s"]["value"]
    assert m["mesh4_write_slice_s"]["value"] \
        <= m["mesh4_write_encode_s"]["value"]
