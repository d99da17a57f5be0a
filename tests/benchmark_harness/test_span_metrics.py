"""The per-layer metrics that read the program's spans (ISSUE 26), and
the names the trace readers key on.

Four metrics read spans the program already had (``parse_build_s``,
``fetch_stage_s``, ``write_stage_s``, ``write_merge_s``) and are in
BENCHMARK.json.  The six spans that tile a launch of the decode service
are new with their PR, and a metric of one cannot be listed yet: on the
chip ``benchmark/run.py`` fails a traced run whose listed reader finds
nothing, and the parent's traced run is made with the change's
benchmark files.  That ``span_sum`` reads them as they stand is shown
here, in a temporary copy of the benchmark with one entry a span.
"""

import json
import os
import re
import time

import pytest

from harness_util import REPO, TINY, copy_benchmark, manifest, run_tiny

ACCEPTED = [("wgs_read", "parse_build_s"), ("wgs_read", "fetch_stage_s"),
            ("wgs_sort_write", "write_stage_s"),
            ("wgs_sort_write", "write_merge_s")]
# (metric, span, layer): what a `benchmark` PR whose parent has the
# spans would list, for wgs_read
LAUNCH = [("launch_pack_s", "device.launch.pack", "decode service"),
          ("launch_submit_s", "device.launch.submit", "transfers"),
          ("launch_wait_s", "device.launch.wait", "decode service"),
          ("launch_d2h_s", "device.launch.d2h", "transfers"),
          ("launch_deliver_s", "device.launch.deliver", "decode service"),
          ("dispatcher_idle_s", "device.service.idle", "decode service")]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced tiny run of each cell, the six launch metrics laid
    into the copy of the benchmark as new files and new entries."""
    root = copy_benchmark(tmp_path_factory.mktemp("bench"), TINY)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    for name, key, layer in LAUNCH:
        with open(os.path.join(root, "benchmark", "layer_metrics",
                               name + ".json"), "x") as f:
            json.dump({"reader": "span_sum", "key": key, "per": "passes"}, f)
        doc["per_layer"].append({
            "name": name, "unit": "s/pass", "better": "lower",
            "source": "program_span", "layer": layer,
            "moves": "records_per_s", "workloads": ["wgs_read"]})
    with open(path, "w") as f:
        json.dump(doc, f)
    lines = {}

    def line_of(workload):
        if workload not in lines:
            lines[workload] = run_tiny(root, workload, trace=True)
        return lines[workload]

    line_of.since = time.perf_counter()   # the program's spans' clock
    return line_of


@pytest.mark.parametrize("workload,metric", ACCEPTED + [
    ("wgs_read", m) for m, _key, _layer in LAUNCH])
def test_a_traced_run_reads_the_metric_from_the_programs_spans(
        traced, workload, metric):
    line = traced(workload)
    assert line["correct"] is True
    got = line["metrics"][metric]
    assert got["unit"] == "s/pass"
    # the idle span is booked at every launch, also when it is 0.0 s
    assert got["value"] >= 0 if metric == "dispatcher_idle_s" \
        else got["value"] > 0


def test_the_launch_metrics_sum_as_many_spans_as_there_were_launches(traced):
    """Six spans a launch: ``launches_per_pass`` counts a
    ``device.service.wait`` a launch, and the ring holds as many spans
    of each of the six names, so the six tile the same launches."""
    from benchmark.drivers import program

    launches = traced("wgs_read")["metrics"]["launches_per_pass"]["value"]
    assert launches >= 1
    # (the sleep that a shutdown ended is an idle span with no launch)
    ring = [s["name"] for s in program.spans_between(
        traced.since, float("inf"))
        if s["name"] != "device.service.idle" or "launch" in s["labels"]]
    names = ["device.service.wait"] + [key for _m, key, _layer in LAUNCH]
    counts = {n: ring.count(n) for n in names}
    assert len(set(counts.values())) == 1, counts
    assert counts["device.service.wait"] >= launches


@pytest.mark.parametrize("name", sorted(
    f[:-len(".json")] for f in os.listdir(
        os.path.join(REPO, "benchmark", "layer_metrics"))))
def test_every_reader_file_names_a_reader_and_is_listed(name):
    """A file under ``layer_metrics/`` is one metric of BENCHMARK.json,
    read through one of the general readers."""
    from benchmark import readers

    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] in readers.READERS, spec
    listed = [p for p in manifest()["per_layer"] if p["name"] == name]
    assert len(listed) == 1, name
    if spec["reader"].startswith("span_"):
        assert listed[0]["source"] == "program_span"
        assert re.fullmatch(r"[a-z0-9_]+(\.[a-z0-9_]+)+", spec["key"])


def _jitted_inflate():
    from disq_tpu.ops import inflate_simd

    return inflate_simd._compiled(64, 64, True, True, True)


def _jitted_parse():
    from disq_tpu.runtime import device_pipeline

    return device_pipeline._parse_columns


def _jitted_sort():
    from disq_tpu.runtime import columnar

    return columnar._jax_fns()["coord_perm"]


@pytest.mark.parametrize("jitted,op,metrics", [
    (_jitted_inflate, "/call.1", ["inflate_kernel_s",
                                  "inflate_simd_roofline"]),
    (_jitted_parse, "", ["parse_kernel_s"]),
    (_jitted_sort, "", ["sort_kernel_s"]),
], ids=["inflate", "parse", "sort"])
def test_the_names_the_trace_readers_key_on_are_pinned(jitted, op, metrics):
    """The profiler names a jitted program ``jit_<function name>``, and
    four reader files match on those names; on the chip a reader that
    matches nothing fails the traced run.  So the names hold still."""
    key = "jit_" + jitted().__name__ + op
    for metric in metrics:
        with open(os.path.join(REPO, "benchmark", "layer_metrics",
                               metric + ".json")) as f:
            match = json.load(f)["match"]
        assert re.search(match, key), (
            f"the trace would name this program {key!r}, which {metric} "
            f"({match!r}) no longer matches: keep the function's name, or "
            "repoint benchmark/layer_metrics/{inflate_kernel_s,"
            "inflate_simd_roofline,parse_kernel_s,sort_kernel_s}.json in "
            "a `benchmark` PR")
