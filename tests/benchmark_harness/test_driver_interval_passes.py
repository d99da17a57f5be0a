"""The ``interval_passes`` driver end to end on the CPU at a tiny size
(the look for a chip waived): the cell is ISSUE 35's tables; a sound run
is correct and its rate is the returned records over all the time; the
two controls are not correct, each by checks of its own; a traced run
reads every metric listed for the cell that has no device plane to wait
for; the target list is the kit's (one for every seed, another for
another ``targets_seed``); the reference's sweep agrees with the
record-by-record brute force of ``tests/test_traversal.py``.

``program.write_input`` refuses to re-block an indexed input, so the
tiny file has few blocks: the sparse layouts (chunk runs with gaps, a
chunk that begins inside a block, bin edges) are
``tests/test_traversal.py``'s, which writes its own files.
"""

import json
import os
import re

import numpy as np
import pytest

from harness_util import REPO, copy_benchmark, manifest, run_tiny

CELL = "exome_intervals"
RECORDS = 60
TINY = {"interval_read": {"records": RECORDS, "trace_seconds": 1}}
# of the seeds from run_tiny's on, the first whose 60 records leave a
# target's last base alone holding a record (shift_target's premise)
SHIFT_SEED = 2147484028
DEVICE_TRACE = {"inflate_kernel_s", "inflate_simd_roofline",
                "parse_kernel_s", "overlap_kernel_s",
                "overlap_kernel_roofline"}
READ_SIDE = {
    "emit_stall_s", "read_pass_rate_median", "lane_fill_pct",
    "service_wait_s", "launches_per_pass", "inflate_kernel_s",
    "inflate_simd_roofline", "parse_kernel_s", "h2d_bytes_per_record",
    "d2h_bytes_per_record", "device_idle_pct.read", "hbm_peak_bytes.read",
    "parse_build_s", "fetch_stage_s", "inflate_supersteps_per_pass"}
NEW = {  # metric -> (unit, source)
    "traversal_plan_s": ("s/pass", "program_span"),
    "traversal_overlap_s": ("s/pass", "program_span"),
    "traversal_compact_s": ("s/pass", "program_span"),
    "overlap_kernel_s": ("s/pass", "device_trace"),
    "overlap_kernel_roofline": ("%", "device_trace"),
    "traversal_chunks_per_pass": ("count/pass", "program_counter"),
    "traversal_blocks_per_pass": ("count/pass", "program_counter"),
    "traversal_decoded_per_returned": ("records/record", "program_counter")}
SOURCE = ("disq HtsjdkReadsTraversalParameters read (BASELINE.json "
          "configs[2]) as GATK -L exome_calling_regions.v1.interval_list "
          "-ip 100 (hg38, ~200k targets) on GIAB NA12878 30x WGS BAM+BAI, "
          "arXiv:1806.00788")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return copy_benchmark(tmp_path_factory.mktemp("bench"), TINY)


def listed(kind="per_layer"):
    return {m["name"] for m in manifest()[kind]
            if CELL in m.get("workloads", [CELL])}


def failed_checks(out: str) -> list:
    return [ln.split(":")[0][len("compared "):] for ln in out.splitlines()
            if ln.startswith("compared ") and ln.endswith("FAILED")]


def load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def test_the_cell_is_the_issues_tables_letter_for_letter():
    from benchmark import run

    spec = run.load_cell(REPO, CELL)
    assert spec["cell"]["chips"] == 1
    assert (spec["cell"]["config"], spec["cell"]["traffic"]) == (
        "wgs30x_exome", "interval_read")
    tr, cfg = spec["traffic"], spec["config"]
    # 5,600,001, or the one lever (8,400,001) with its measured reason
    assert tr["records"] in (5600001, 8400001)
    assert (tr["driver"], tr["pass"], tr["metric"], tr["executor_workers"],
            ) == ("interval_passes", "interval_read", "records_per_s", 4)
    read = load("benchmark", "traffic", "read.json")
    assert tr["env"] == read["env"] == {"DISQ_TPU_DEVICE_INFLATE": "1",
                                        "DISQ_TPU_DEVICE_SERVICE": "1"}
    assert tr["records"] == 4 * (read["records"] - 1) + 1 \
        or tr["records"] == 8400001
    assert tr["gap_labels"] == [
        "disq_tpu.traversal.plan", "disq_tpu.traversal.overlap",
        "disq_tpu.columnar.batch.compact", "disq_tpu.columnar.batch.build",
        "disq_tpu.executor.fetch", "interval_read"]
    assert "split_size_bytes" not in tr and "bgzf_block_payload" not in tr
    # every data key of wgs30x unchanged
    data = load("benchmark", "configs", "wgs30x.json")
    differ = {k for k in set(data) | set(cfg) if data.get(k) != cfg.get(k)}
    assert differ == {"name", "source", "deployment", "guarantees",
                      "reduced", "assumed", "targets"}
    assert cfg["targets"] == {
        "targets_seed": cfg["targets"]["targets_seed"],
        "bp_per_target": 15500, "bp_per_gene": 155000,
        "targets_per_gene_mean": 10, "width_median": 130,
        "width_mean": 175, "width_clip": [40, 3000], "gap_mean": 3000,
        "gap_min": 100, "interval_padding": 100,
        "traverse_unplaced_unmapped": False}
    assert list(cfg["reduced"]) == ["records"]
    assert data["assumed"].items() <= cfg["assumed"].items()
    for key in cfg["targets"]:
        assert any(key in names.split(", ") for names in cfg["assumed"]), key
    assert "overlap_rule" in cfg["assumed"]
    assert len(cfg["guarantees"]) == 6
    assert cfg["source"] == SOURCE and len(SOURCE) <= 200
    entry = next(c for c in manifest()["configs"]
                 if c["name"] == "wgs30x_exome")
    assert entry["source"] == SOURCE and entry["reduced"] == ["records"]
    assert entry["file"] == "benchmark/configs/wgs30x_exome.json"
    assert listed("end_to_end") == {"records_per_s", "setup_s"}
    assert listed() == READ_SIDE | set(NEW) | {
        "compiles_in_window", "setup_compiles"}
    for m in manifest()["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["layer"] == "traversal"
            assert (m["unit"], m["source"]) == NEW[m["name"]]
            assert m["moves"] == "records_per_s"
        elif m["name"] in READ_SIDE:
            assert m["workloads"][-1] == CELL
    assert manifest()["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in manifest()["workloads"]) == 1


def test_the_kernels_reader_files_match_the_programs_name():
    from disq_tpu.traversal import bai_query

    name = "jit_" + bai_query._overlap_program().__name__
    assert name == "jit_interval_overlap"
    for metric in ("overlap_kernel_s", "overlap_kernel_roofline"):
        match = load("benchmark", "layer_metrics", metric + ".json")["match"]
        assert re.search(match, name) and not re.search(match, name + "/x")
    roof = load("benchmark", "layer_metrics", "overlap_kernel_roofline.json")
    assert (roof["bytes"], roof["peak"]) == (
        "overlap_bytes", "hbm_bytes_per_s")


def test_the_overlaps_bytes_are_the_records_and_the_table():
    from benchmark.drivers import interval_passes

    # refid, pos, end in and the mask byte out a decoded record; the
    # table's starts and ends a launch
    assert interval_passes.overlap_bytes(1000, 0, 1855) == 13_000
    assert interval_passes.overlap_bytes(0, 20, 1855) == 20 * 1855 * 8


def test_sound_run_is_correct_and_reports_the_end_to_end_metrics(
        root, capsys):
    line = run_tiny(root, CELL)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"records_per_s", "setup_s"}
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    json.dumps(line)
    out = capsys.readouterr().out
    assert not failed_checks(out)
    setup = next(ln for ln in out.splitlines() if ln.startswith("set-up:"))
    for part in ("generate", "order + reference", "write BAM + BAI", "plan",
                 "warm-up pass"):
        assert part in setup
    kept, n = re.search(r"keep (\d+) of (\d+)", setup).groups()
    assert 0 < int(kept) < int(n) == RECORDS
    touched, blocks = re.search(
        r"touch (\d+) blocks", setup).group(1), re.search(
        r"(\d+) blocks\)", setup).group(1)
    assert 0 < int(touched) <= int(blocks)
    # the rate counts what came back, over all of the window
    window = next(ln for ln in out.splitlines() if ln.startswith("window:"))
    returned, elapsed = int(window.split()[1]), float(window.split()[5])
    assert returned == int(kept) * line["attempted"]
    assert line["metrics"]["records_per_s"]["value"] == pytest.approx(
        returned / elapsed, rel=1e-3)


def test_the_control_a_dropped_record_is_not_correct(root, capsys):
    line = run_tiny(root, CELL, control="drop_record")
    assert line["correct"] is False and line["failed"] >= 1
    bad = failed_checks(capsys.readouterr().out)
    assert bad[0] == ("passes whose count, flagstat or depth differ from "
                      "the reference's over its kept set")
    assert not any("inflate" in b or "device-backed" in b or "plan" in b
                   for b in bad)


def test_the_control_a_target_one_base_short_is_not_correct(root, capsys):
    line = run_tiny(root, CELL, control="shift_target", seed=SHIFT_SEED)
    assert line["correct"] is False and line["failed"] >= 1
    bad = failed_checks(capsys.readouterr().out)
    assert bad[0] == ("passes whose count, flagstat or depth differ from "
                      "the reference's over its kept set")
    assert not any("inflate" in b or "device-backed" in b or "plan" in b
                   for b in bad)
    # the same seed, the true list: correct
    assert run_tiny(root, CELL, seed=SHIFT_SEED)["correct"] is True


def test_a_read_left_host_backed_is_not_correct(root, monkeypatch, capsys):
    """The same records: what fails is the guarantee that the returned
    dataset stays on the device."""
    from disq_tpu import ReadsStorage
    from disq_tpu.api import ReadsDataset

    sound = ReadsStorage.read

    def materialised(self, path, traversal=None):
        ds = sound(self, path, traversal)
        if traversal is None or not hasattr(ds.reads, "release"):
            return ds
        host = ds.reads.to_read_batch()
        ds.reads.release()
        return ReadsDataset(header=ds.header, reads=host,
                            counters=ds.counters)

    monkeypatch.setattr(ReadsStorage, "read", materialised)
    line = run_tiny(root, CELL)
    assert line["correct"] is False and line["failed"] == 0
    assert failed_checks(capsys.readouterr().out) == [
        "passes whose dataset was not device-backed"]


def test_a_traced_run_reads_every_metric_listed_for_the_cell(root, capsys):
    line = run_tiny(root, CELL, trace=True)
    assert line["correct"] is True
    got = set(line["metrics"])
    assert "records_per_s" not in got and "setup_s" not in got
    # no device plane on the CPU: the trace readers find nothing there
    assert got == listed() - DEVICE_TRACE
    m = {k: v["value"] for k, v in line["metrics"].items()}
    out = capsys.readouterr().out
    setup = next(ln for ln in out.splitlines() if ln.startswith("set-up:"))
    chunks, touched = re.search(
        r"\((\d+) chunks in \d+ tasks touch (\d+) blocks", setup).groups()
    kept, n = re.search(r"keep (\d+) of (\d+)", setup).groups()
    assert m["traversal_chunks_per_pass"] == int(chunks)
    assert m["traversal_blocks_per_pass"] == int(touched)
    assert m["launches_per_pass"] >= 1
    assert 1 <= m["traversal_decoded_per_returned"] <= int(n) / int(kept)
    for name in ("traversal_plan_s", "traversal_overlap_s",
                 "traversal_compact_s", "parse_build_s", "fetch_stage_s"):
        assert m[name] > 0, name
    assert 0 < m["lane_fill_pct"] <= 100
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_target_list_is_the_kits_not_the_samples():
    from benchmark import reference_intervals

    cfg = load("benchmark", "configs", "wgs30x_exome.json")
    n = load("benchmark", "traffic", "interval_read.json")["records"]
    refid, start0, end0 = reference_intervals.targets(cfg, n)
    again = reference_intervals.targets(cfg, n)
    assert all(np.array_equal(a, b) for a, b in zip(
        (refid, start0, end0), again))
    other = dict(cfg, targets=dict(cfg["targets"],
                                   targets_seed=cfg["targets"][
                                       "targets_seed"] + 1))
    moved = reference_intervals.targets(other, n)
    assert len(moved[1]) != len(start0) or not np.array_equal(
        moved[1], start0)
    # the source's density at the cell's size: some 1,800 targets in
    # 180 genes over 28 Mbp, fewer after the merge; padded, merged, in
    # order, inside their contigs
    assert 1500 < len(refid) < 2100
    assert set(refid.tolist()) == {0, 1, 2}
    same = refid[1:] == refid[:-1]
    assert (start0[1:][same] > end0[:-1][same]).all()
    assert ((end0 - start0) >= 40 + 2 * 100).all() or (start0 == 0).any()
    lengths = np.array([c["length"] for c in cfg["contigs"]])
    assert (end0 <= lengths[refid]).all() and (start0 >= 0).all()
    # clustered: most neighbours lie a gene's gap apart, a few a
    # genome's
    gap = (start0[1:] - end0[:-1])[same]
    assert np.median(gap) < 5000 < 50_000 < gap.max()


def test_the_reference_agrees_with_the_brute_force(tmp_path):
    """``reference_intervals.kept`` (a sweep over sorted targets) against
    ``tests/test_traversal.py:_expect_overlapping`` (record by record,
    on ``bam_oracle.ref_span``) over the same records, read back from
    the bytes the reference's own encoder gives."""
    import sys

    sys.path.insert(0, REPO)
    from benchmark import gen, reference, reference_intervals
    from tests.bam_oracle import decode_all
    from tests.test_traversal import _expect_overlapping

    cfg = load("benchmark", "configs", "wgs30x_exome.json")
    truth = gen.generate(400, 2147483999, cfg)
    truth = truth.take(reference.coordinate_order(truth))
    records = decode_all(reference.encode_records(truth))
    assert len(records) == truth.count
    target_list = reference_intervals.targets(cfg, 400)
    refid, start0, end0 = target_list
    want = []
    for r, s, e in zip(refid.tolist(), start0.tolist(), end0.tolist()):
        want += _expect_overlapping(records, r, s, e)
    keep = reference_intervals.kept(truth, target_list)
    names = [bytes(truth.name_mat[i, : truth.name_len[i]]).decode()
             for i in keep]
    assert 0 < len(keep) < truth.count
    # merged targets do not overlap: a record is named twice only where
    # it reaches two of them
    assert sorted(set(want)) == sorted(set(names))
    # and one base either way changes the answer where a record ends or
    # begins there
    ends = reference_intervals.alignment_ends(truth)
    i = int(keep[0])
    r, p, e = int(truth.refid[i]), int(truth.pos[i]), int(ends[i])
    one = lambda s0, e0: reference_intervals.kept(  # noqa: E731
        truth, (np.array([r]), np.array([s0]), np.array([e0])))
    assert i in one(e - 1, e + 5) and i not in one(e, e + 5)
    assert i in one(p - 5, p + 1) and i not in one(p - 5, p)
