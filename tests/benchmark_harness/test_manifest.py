"""BENCHMARK.json against the contract's limits, and the harness as data:
a cell, a configuration and a per-layer metric are each added as new
files and new entries, with no existing file edited."""

import json
import os
import re

import pytest

from harness_util import REPO, copy_benchmark, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_limits():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["paths"]) <= 16 and len(m["command"]) <= 32
    for p in m["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(REPO, p))
    assert m["command"][1].startswith(m["paths"][0] + "/")


def test_every_name_and_unit_uses_the_allowed_characters():
    m = manifest()
    names = []
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        names += [w["name"], w["config"], w["traffic"]]
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for metric in m["end_to_end"] + m["per_layer"]:
        names.append(metric["name"])
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for n in names:
        assert NAME.match(n), n
    for group in ("configs", "workloads"):
        got = [x["name"] for x in m[group]]
        assert len(got) == len(set(got))
    metrics = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_end_to_end_metrics_carry_bounds_and_setup_s():
    m = manifest()
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    by_name = {e["name"]: e for e in m["end_to_end"]}
    assert by_name["setup_s"]["bound"] == 0.25
    assert "workloads" not in by_name["setup_s"]
    cells = {w["name"] for w in m["workloads"]}
    for w in cells:
        reported = [e["name"] for e in m["end_to_end"]
                    if w in e.get("workloads", cells)]
        assert "setup_s" in reported and len(reported) >= 2, w


def test_every_moves_names_an_end_to_end_metric_of_the_same_cells():
    m = manifest()
    cells = {w["name"] for w in m["workloads"]}
    e2e = {e["name"]: set(e.get("workloads", cells))
           for e in m["end_to_end"]}
    assert 1 <= len(m["per_layer"]) <= 128
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert p["moves"] in e2e, p
        assert set(p.get("workloads", cells)) <= e2e[p["moves"]], p
        assert 1 <= len(p["layer"]) <= 200 and "\n" not in p["layer"]
    for w in cells:
        assert any(w in p.get("workloads", cells) for p in m["per_layer"])
    # a roofline share is a percentage named for its kernel
    for p in m["per_layer"]:
        if p["name"].endswith("_roofline"):
            assert p["unit"] == "%" and p["source"] == "device_trace"


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      manifest()["workloads"]])
def test_every_workload_resolves_its_config_driver_and_metrics(workload):
    import importlib

    from benchmark import readers, run

    spec = run.load_cell(REPO, workload)
    assert spec["config"]["name"] == spec["cell"]["config"]
    for key in ("guarantees", "reduced", "assumed", "source"):
        assert spec["config"][key]
    driver = importlib.import_module(
        "benchmark.drivers." + spec["traffic"]["driver"])
    assert all(hasattr(driver.Driver, f)
               for f in ("setup", "window", "check", "close"))
    assert spec["traffic"]["metric"] in {
        e["name"] for e in spec["end_to_end"]}
    assert {"setup_s", spec["traffic"]["metric"]} == {
        e["name"] for e in spec["end_to_end"]}
    assert spec["per_layer"]
    for p in spec["per_layer"]:
        assert spec["readers"][p["name"]]["reader"] in readers.READERS
    m = manifest()
    config = next(c for c in m["configs"] if c["name"] == spec["cell"]["config"])
    assert config["file"].startswith(m["paths"][0] + "/")
    assert config["source"] == spec["config"]["source"]


def test_files_under_paths_are_named_from_the_allowed_characters():
    for path in manifest()["paths"]:
        for d, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                assert re.fullmatch(r"[A-Za-z0-9_.\-]+", f), (d, f)


def test_a_cell_a_configuration_and_a_metric_are_added_as_new_files(tmp_path):
    """What a later PR does: new files and new entries only."""
    from benchmark import readers, run

    root = copy_benchmark(tmp_path)
    before = {}
    for d, _dirs, files in os.walk(root):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "wgs30x.json")) as f:
        config = json.load(f)
    config.update(name="exome", coverage=100)
    with open(os.path.join(bench, "configs", "exome.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", "read.json")) as f:
        traffic = json.load(f)
    traffic.update(records=1000)
    with open(os.path.join(bench, "traffic", "small_read.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "layer_metrics", "flushes_per_pass.json"),
              "w") as f:
        json.dump({"reader": "counter", "key": "device.batch.flush",
                   "per": "passes"}, f)
    manifest_path = os.path.join(root, "BENCHMARK.json")
    del before[manifest_path]
    with open(manifest_path) as f:
        m = json.load(f)
    m["configs"].append({"name": "exome", "source": "a panel",
                         "file": "benchmark/configs/exome.json",
                         "reduced": ["records"], "why": "dummy"})
    m["workloads"].append({"name": "exome_read", "config": "exome",
                           "traffic": "small_read", "chips": 1,
                           "why": "dummy"})
    m["per_layer"].append({
        "name": "flushes_per_pass", "unit": "count/pass", "better": "lower",
        "source": "program_counter", "layer": "decode service",
        "moves": "records_per_s", "workloads": ["exome_read", "wgs_read"]})
    for e in m["end_to_end"]:
        if e["name"] == "records_per_s":
            e["workloads"].append("exome_read")
    with open(manifest_path, "w") as f:
        json.dump(m, f)

    new = run.load_cell(root, "exome_read")
    assert new["config"]["coverage"] == 100
    assert new["traffic"]["records"] == 1000
    assert {p["name"] for p in new["per_layer"]} == {
        "flushes_per_pass", "compiles_in_window", "setup_compiles"}
    assert {e["name"] for e in new["end_to_end"]} == {
        "records_per_s", "setup_s"}
    old = run.load_cell(root, "wgs_read")
    assert "flushes_per_pass" in old["readers"]
    # the new reader file reads through the general reader
    w = {"counters_before": {}, "counters_after": {
        "device.batch.flush": {"reason=full": 18, "reason=timeout": 2}},
        "numbers": {"passes": 2}}
    assert readers.counter(w, new["readers"]["flushes_per_pass"]) == 10
    # and nothing that was there has been touched
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, path


def test_on_the_chip_a_reader_that_finds_nothing_fails_the_run():
    """A kernel renamed under ``inflate_kernel_s`` may not drop out of
    the result line unseen."""
    from benchmark import run

    spec = run.load_cell(REPO, "wgs_read")
    w = {"counters_before": {}, "counters_after": {}, "spans": [],
         "trace": {"ops": {"jit_call/renamed": 1.0}, "busy_s": 1.0,
                   "window_s": 2.0},
         "numbers": {"passes": 1, "records": 10, "pass_rate_median": 5.0,
                     "inflate_bytes": 100},
         "device": {"memory_peak_bytes": 1}, "compiles": {"window": 0,
                                                          "setup": 0},
         "peaks": {"hbm_bytes_per_s": 1e9}}
    got = run.layer_values(spec, w, strict=False)
    assert "inflate_kernel_s" not in got and "read_pass_rate_median" in got
    with pytest.raises(SystemExit, match="found nothing to read"):
        run.layer_values(spec, w, strict=True)
    w["trace"]["ops"] = {"jit_call/call.1": 1.0, "jit__parse_columns": 0.1}
    spec["per_layer"] = [m for m in spec["per_layer"]
                         if m["source"] in ("device_trace", "host_clock")]
    got = run.layer_values(spec, w, strict=True)
    assert got["inflate_kernel_s"]["value"] == 1.0
    assert got["device_idle_pct.read"]["value"] == 50.0
