"""The ``mesh_chain`` driver end to end on the CPU at a tiny size (four
of conftest's eight forced host devices as the mesh, four splits, the
look for a chip waived): a sound run is correct; the control, a sort
forced to its host fallback, a sorted record or a mesh-parsed column
altered where it is produced and an index that is not the host writer's
are not; a traced run reads every metric listed for the cell that has
no device plane to wait for."""

import json
import time

import pytest

from harness_util import REPO, copy_benchmark, manifest, run_tiny

CELL = "wgs_mesh4"
# interpreter-sized: four splits of some 35 blocks of 300 bytes
TINY = {"mesh4_chain": {"records": 120, "bgzf_block_payload": 300,
                        "trace_seconds": 1, "split_size_bytes": 8192}}
# what only a device plane of the profiler's trace gives
DEVICE_TRACE = {"inflate_kernel_s", "inflate_simd_roofline",
                "mesh_parse_kernel_s"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return copy_benchmark(tmp_path_factory.mktemp("bench"), TINY)


def listed(kind="per_layer"):
    return {m["name"] for m in manifest()[kind]
            if CELL in m.get("workloads", [CELL])}


def test_the_cell_is_the_issues_table_letter_for_letter():
    from benchmark import run

    spec = run.load_cell(REPO, CELL)
    assert spec["cell"]["chips"] == 4
    tr, cfg = spec["traffic"], spec["config"]
    assert (tr["records"], tr["mesh_devices"], tr["executor_workers"],
            tr["writer_workers"], tr["trace_seconds"]) == (
                4600001, 4, 4, 4, 25)
    assert tr["env"] == {"DISQ_TPU_DEVICE_INFLATE": "1",
                         "DISQ_TPU_DEVICE_SERVICE": "1",
                         "DISQ_TPU_MESH": "4"}
    # the split size is the configuration's, the source's: not cut
    assert "split_size_bytes" not in tr
    assert cfg["split_size_bytes"] == 134217728 and cfg["mesh_devices"] == 4
    with open(f"{REPO}/benchmark/configs/wgs30x.json") as f:
        one_chip = json.load(f)
    differ = {k for k in one_chip if one_chip[k] != cfg.get(k)}
    assert differ == {"name", "source", "deployment", "guarantees",
                      "reduced"}
    assert cfg["guarantees"][:len(one_chip["guarantees"])] \
        == one_chip["guarantees"]
    assert list(cfg["reduced"]) == ["records"]
    assert listed("end_to_end") == {"records_per_s", "setup_s"}


def test_sound_run_is_correct_and_reports_the_end_to_end_metrics(
        root, capsys):
    line = run_tiny(root, CELL)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"records_per_s", "setup_s"}
    assert line["metrics"]["records_per_s"]["value"] > 0
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    json.dumps(line)
    out = capsys.readouterr().out
    chips = next(ln for ln in out.splitlines()
                 if ln.startswith("inflate launches by chip:"))
    assert [c.split(":")[0] for c in chips.split(": ")[1].split()] \
        == ["0", "1", "2", "3"]


def test_the_rate_is_all_the_records_over_all_of_the_window(
        root, monkeypatch, capsys):
    from benchmark.drivers import mesh_chain

    sound = mesh_chain.Driver.one_pass
    calls, stalled = [], []

    def stalling(self, i):
        if calls:                       # every pass but the warm-up
            time.sleep(0.4)
            stalled.append(i)
        calls.append(i)
        sound(self, i)

    monkeypatch.setattr(mesh_chain.Driver, "one_pass", stalling)
    line = run_tiny(root, CELL)
    assert line["correct"] is True and stalled
    out = capsys.readouterr().out
    window = next(ln for ln in out.splitlines() if ln.startswith("window:"))
    records, elapsed = int(window.split()[1]), float(window.split()[4])
    rates = [float(r) for r in window.split("rates ")[1].split()]
    assert line["attempted"] == len(rates) == len(stalled)
    assert records == 120 * len(rates)
    value = line["metrics"]["records_per_s"]["value"]
    assert value == pytest.approx(records / elapsed, rel=1e-3)
    assert value < min(rates)


def test_the_control_a_dropped_record_is_not_correct(root, capsys):
    line = run_tiny(root, CELL, control="drop_record")
    assert line["correct"] is False
    assert "FAILED" in capsys.readouterr().out


def test_a_sort_forced_to_its_host_fallback_is_not_correct(
        root, monkeypatch, capsys):
    """The answer stays right (the fallback is a stable argsort): what
    fails is the guarantee that the sort ran on the mesh."""
    from disq_tpu.sort import sharded

    sound = sharded.sharded_sort_step

    def overflowing(*args, **kw):
        oh, ol, orows, counts, ok = sound(*args, **kw)
        return oh, ol, orows, counts, ok & False

    monkeypatch.setattr(sharded, "sharded_sort_step", overflowing)
    line = run_tiny(root, CELL)
    assert line["correct"] is False and line["failed"] == 0
    out = capsys.readouterr().out
    assert any(ln.startswith("compared sorts that fell back to the host")
               and ln.endswith("FAILED") for ln in out.splitlines())
    assert "reference order's: worst 0 limit 0 ok" in out


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
    line = run_tiny(root, CELL)
    assert line["correct"] is False


def test_a_mesh_parsed_column_altered_where_it_is_produced_is_not_correct(
        root, monkeypatch, capsys):
    """The sorted file is encoded from the host's blob and the
    permutation, and the answers read flag, refid, pos and the
    reference length: only the comparison of the resident columns sees
    a wrong mapq out of the sharded parse."""
    from disq_tpu.runtime import device_pipeline

    sound = device_pipeline._mesh_parse_compiled

    def broken(mesh, interpret):
        parse = sound(mesh, interpret)

        def altered(words, starts):
            cols = dict(parse(words, starts))
            cols["mapq"] = ~cols["mapq"]    # no constant: the guard is on
            return cols

        return altered

    monkeypatch.setattr(device_pipeline, "_mesh_parse_compiled", broken)
    line = run_tiny(root, CELL)
    assert line["correct"] is False and line["failed"] == 0
    out = capsys.readouterr().out
    bad = [ln.split(":")[0] for ln in out.splitlines()
           if ln.startswith("compared ") and ln.endswith("FAILED")]
    assert bad == ["compared resident column mapq"]


def test_an_index_that_is_not_the_host_writers_is_not_correct(
        root, monkeypatch, capsys):
    """The same wrong byte in every pass's BAI: sizes agree, the record
    bytes are the reference order's, the index is present."""
    from benchmark.drivers import mesh_chain

    sound = mesh_chain.Driver.one_pass

    def spoiling(self, i):
        sound(self, i)
        with open(self.out + ".bai", "r+b") as f:
            f.seek(-1, 2)
            last = f.read(1)
            f.seek(-1, 2)
            f.write(bytes([last[0] ^ 1]))

    monkeypatch.setattr(mesh_chain.Driver, "one_pass", spoiling)
    line = run_tiny(root, CELL)
    assert line["correct"] is False and line["failed"] == 0
    out = capsys.readouterr().out
    bad = [ln for ln in out.splitlines()
           if ln.startswith("compared ") and ln.endswith("FAILED")]
    assert len(bad) == 1 and "host writer's" in bad[0]


def test_a_chip_that_held_no_launch_is_not_correct(root, monkeypatch):
    """Every launch on one chip: the answers are right and the cell's
    guarantee is not kept."""
    from benchmark.drivers import mesh_chain

    monkeypatch.setattr(
        mesh_chain, "launches_by_chip",
        lambda spans: {0: sum(s["name"] == mesh_chain.LAUNCH
                              for s in spans)})
    line = run_tiny(root, CELL)
    assert line["correct"] is False and line["failed"] == 0


def test_a_traced_run_reads_every_metric_listed_for_the_cell(root):
    line = run_tiny(root, CELL, trace=True)
    assert line["correct"] is True
    got = set(line["metrics"])
    assert "records_per_s" not in got and "setup_s" not in got
    # no device plane on the CPU: the trace readers find nothing there
    assert got == listed() - DEVICE_TRACE
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 25.0 <= m["chip_launch_share_max_pct"] < 100.0
    assert 0 < m["lane_fill_pct"] <= 100
    for name in ("mesh_sort_keys_s", "mesh_splitters_s",
                 "mesh_sort_exchange_s", "mesh_gather_s",
                 "mesh4_write_deflate_s", "mesh4_write_encode_s",
                 "mesh_exchange_bytes", "parse_build_s", "emit_stall_s"):
        assert m[name] > 0, name
    assert m["mesh_reshard_bytes_per_record"] >= 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_on_the_chip_the_device_trace_readers_key_on_the_programs_names():
    """``mesh_parse_kernel_s`` reads the shard_map'd parse by the name
    its function gives the XLA module; a rename has to fail here."""
    import jax
    import jax.numpy as jnp

    from benchmark import readers, run
    from disq_tpu.runtime import device_pipeline
    from disq_tpu.runtime.mesh import batch_sharding, get_mesh, replicated

    mesh = get_mesh(4)
    lowered = device_pipeline._mesh_parse_compiled(mesh, True).lower(
        jax.ShapeDtypeStruct((4096,), jnp.uint32, sharding=replicated(mesh)),
        jax.ShapeDtypeStruct((1024,), jnp.int32,
                             sharding=batch_sharding(mesh)))
    module = lowered.as_text().split("module @")[1].split()[0]
    spec = run.load_cell(REPO, CELL)
    # the module's own line holds the chips' waiting for one another;
    # its ops are the work
    w = {"trace": {"ops": {module: 9.0, module + "/fusion": 1.5,
                           module + "/copy.2": 0.5, "jit_body/fusion": 5.0}},
         "numbers": {"passes": 2}}
    assert readers.trace_op(w, spec["readers"]["mesh_parse_kernel_s"]) == 1.0
