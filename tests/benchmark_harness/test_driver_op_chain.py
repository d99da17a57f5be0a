"""The ``op_chain`` driver end to end on the CPU at a tiny size (the look
for a chip waived): the cell is ISSUE 32's table; a sound run is correct
and its rate is all the records over all the time; the control, and five
faults put where the program produces them, are not correct, each by the
checks that are its own; a traced run reads every metric listed for the
cell that has no device plane to wait for; the programs' names are
pinned; a tree without the cell's counter is refused at once."""

import json
import os
import re
import time

import numpy as np
import pytest

from harness_util import REPO, copy_benchmark, manifest, run_tiny

CELL = "wgs_chain"
# interpreter-sized: two splits of some 70 blocks of 300 bytes
RECORDS = 120
TINY = {"markdup_chain": {"records": RECORDS, "bgzf_block_payload": 300,
                          "trace_seconds": 1, "split_size_bytes": 16384}}
# what only a device plane of the profiler's trace gives
DEVICE_TRACE = {"inflate_kernel_s", "inflate_simd_roofline",
                "parse_kernel_s", "markdup_scan_kernel_s",
                "markdup_scan_roofline", "chain_sort_kernel_s"}
READ_SIDE = {
    "read_pass_rate_median", "emit_stall_s", "fetch_stage_s",
    "lane_fill_pct", "service_wait_s", "launches_per_pass",
    "inflate_kernel_s", "inflate_simd_roofline", "parse_kernel_s",
    "parse_build_s", "h2d_bytes_per_record", "d2h_bytes_per_record",
    "device_idle_pct.read", "hbm_peak_bytes.read"}
NEW = {  # metric -> layer
    "chain_filter_s": "operators", "chain_compact_s": "operators",
    "chain_markdup_s": "operators", "markdup_keys_s": "operators",
    "markdup_scan_kernel_s": "operators",
    "markdup_scan_roofline": "operators",
    "chain_kept_per_pass": "operators",
    "chain_duplicates_per_pass": "operators",
    "chain_sort_kernel_s": "sort", "chain_sort_gather_s": "sort",
    "chain_materialize_s": "host codecs and write",
    "chain_write_encode_s": "host codecs and write",
    "chain_write_deflate_s": "host codecs and write"}
SOURCE = ("GATK4 ReadsPipelineSpark: MarkDuplicatesSpark -> SortSamSpark "
          "over disq on GIAB NA12878 30x 2x150 bp WGS (arXiv:1806.00788); "
          "sam2bam chain (arXiv:1608.01753); BASELINE.json configs[3]")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return copy_benchmark(tmp_path_factory.mktemp("bench"), TINY)


def listed(kind="per_layer"):
    return {m["name"] for m in manifest()[kind]
            if CELL in m.get("workloads", [CELL])}


def failed_checks(out: str) -> list:
    return [ln.split(":")[0][len("compared "):] for ln in out.splitlines()
            if ln.startswith("compared ") and ln.endswith("FAILED")]


def test_the_cell_is_the_issues_table_letter_for_letter():
    from benchmark import run

    spec = run.load_cell(REPO, CELL)
    assert spec["cell"]["chips"] == 1
    assert (spec["cell"]["config"], spec["cell"]["traffic"]) == (
        "wgs30x_markdup", "markdup_chain")
    tr, cfg = spec["traffic"], spec["config"]
    assert (tr["driver"], tr["records"], tr["executor_workers"],
            tr["writer_workers"], tr["metric"]) == (
                "op_chain", 1400001, 4, 4, "records_per_s")
    assert tr["env"] == {"DISQ_TPU_DEVICE_INFLATE": "1",
                         "DISQ_TPU_DEVICE_SERVICE": "1"}
    assert tr["gap_labels"] == [
        "disq_tpu.ops.markdup.apply", "disq_tpu.ops.filter.apply",
        "disq_tpu.sort.gather", "disq_tpu.columnar.batch.materialize",
        "disq_tpu.bam.write.encode", "disq_tpu.bam.write.deflate",
        "disq_tpu.columnar.batch.build", "disq_tpu.executor.fetch", "chain"]
    # one whole pass and no second: a pass takes 13.4 s on the chip
    assert tr["trace_seconds"] == 12
    # the file is wgs_read's, at the configuration's split size: not cut
    with open(f"{REPO}/benchmark/traffic/read.json") as f:
        read = json.load(f)
    assert "split_size_bytes" not in tr
    assert (tr["records"], tr["env"], tr["executor_workers"]) == (
        read["records"], read["env"], read["executor_workers"])
    assert cfg["chain"] == [["filter", "-q 20"], "sort", "markdup"]
    with open(f"{REPO}/benchmark/configs/wgs30x.json") as f:
        data = json.load(f)
    differ = {k for k in set(data) | set(cfg) if data.get(k) != cfg.get(k)}
    assert differ == {"name", "source", "deployment", "guarantees",
                      "reduced", "assumed", "chain"}
    assert cfg["split_size_bytes"] == 134217728
    assert cfg["guarantees"][0] == data["guarantees"][3]
    assert list(cfg["reduced"]) == ["records"]
    assert data["assumed"].items() <= cfg["assumed"].items()
    assert set(cfg["assumed"]) - set(data["assumed"]) == {
        "chain", "marking_rule"}
    assert cfg["source"] == SOURCE and len(SOURCE) <= 200
    entry = next(c for c in manifest()["configs"]
                 if c["name"] == "wgs30x_markdup")
    assert entry["source"] == SOURCE and entry["reduced"] == ["records"]
    assert listed("end_to_end") == {"records_per_s", "setup_s"}
    assert listed() == READ_SIDE | set(NEW) | {
        "compiles_in_window", "setup_compiles"}
    for m in manifest()["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["layer"] == NEW[m["name"]]
            assert m["moves"] == "records_per_s"
    assert sum(w["chips"] == 4 for w in manifest()["workloads"]) == 1


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
    setup = next(ln for ln in out.splitlines() if ln.startswith("set-up:"))
    kept, _of, n = re.search(r"keeps (\d+) (of) (\d+)", setup).groups()
    assert 0 < int(kept) < int(n) == RECORDS
    assert not failed_checks(out)


def test_the_rate_is_all_the_records_over_all_of_the_window(
        root, monkeypatch, capsys):
    from benchmark.drivers import op_chain

    sound = op_chain.Driver.one_pass
    calls, stalled = [], []

    def stalling(self, i):
        if calls:                       # every pass but the warm-up
            time.sleep(0.4)
            stalled.append(i)
        calls.append(self)
        sound(self, i)

    monkeypatch.setattr(op_chain.Driver, "one_pass", stalling)
    line = run_tiny(root, CELL)
    assert line["correct"] is True and stalled
    out = capsys.readouterr().out
    window = next(ln for ln in out.splitlines() if ln.startswith("window:"))
    records, elapsed = int(window.split()[1]), float(window.split()[4])
    # the passes' own seconds (the printed rates are rounded)
    rates = [RECORDS / p.seconds for p in calls[0].passes]
    assert line["attempted"] == len(rates) == len(stalled)
    assert records == RECORDS * len(rates)  # the input's, not the kept
    value = line["metrics"]["records_per_s"]["value"]
    assert value == pytest.approx(records / elapsed, rel=1e-3)
    assert value < min(rates)


def test_the_control_a_dropped_record_is_not_correct(root, capsys):
    line = run_tiny(root, CELL, control="drop_record")
    assert line["correct"] is False and line["failed"] >= 1
    bad = failed_checks(capsys.readouterr().out)
    assert "passes whose kept count differs from the reference's" in bad


def test_a_duplicate_bit_in_the_device_column_but_not_the_blob_is_not_correct(
        root, monkeypatch, capsys):
    """The counts and the resident flag column are right; the written
    records are encoded from the blob, and say so."""
    from disq_tpu.runtime.columnar import ColumnarBatch

    sound = ColumnarBatch.or_flags

    def device_only(self, mask, bits=0x400):
        idx = np.nonzero(np.asarray(mask))[0]
        src = self._order[idx] if self._order is not None else idx
        at = self._offsets[src] + 19
        before = self._host_blob()[at].copy()
        sound(self, mask, bits)
        self._blob[at] = before

    monkeypatch.setattr(ColumnarBatch, "or_flags", device_only)
    line = run_tiny(root, CELL)
    assert line["correct"] is False and line["failed"] == 0
    assert failed_checks(capsys.readouterr().out) == [
        "sorted BAM record bytes differing from the reference order's",
        "files of the compared pass (BAM, BAI, SBI) differing from the "
        "host writer's of the reference's records"]


def test_a_filter_that_keeps_one_record_more_is_not_correct(
        root, monkeypatch, capsys):
    from disq_tpu.ops import rfilter

    sound = rfilter.resident_mask

    def lenient(rf, batch):
        mask = sound(rf, batch).copy()
        mask[np.flatnonzero(~mask)[0]] = True
        return mask

    monkeypatch.setattr(rfilter, "resident_mask", lenient)
    line = run_tiny(root, CELL)
    assert line["correct"] is False and line["failed"] >= 1
    bad = failed_checks(capsys.readouterr().out)
    assert bad[0] == "passes whose kept count differs from the reference's"
    assert not any("inflate" in b or "device-backed" in b for b in bad)


def test_a_tie_given_to_the_later_record_is_not_correct(
        root, monkeypatch, capsys):
    """Every quality alike, so every duplicate group is a tie: the
    sound program is correct on that; one that lets the later record
    stay marks as many, and other ones."""
    import jax
    import jax.numpy as jnp

    from benchmark import gen
    from disq_tpu.ops import markdup

    sound_generate = gen.generate

    def all_ties(n, seed, cfg):
        truth = sound_generate(n, seed, cfg)
        truth.qual_mat[:] = 30
        return truth

    monkeypatch.setattr(gen, "generate", all_ties)
    line = run_tiny(root, CELL)
    assert line["correct"] is True
    capsys.readouterr()
    sound_kernel = markdup._markdup_kernel()

    @jax.jit
    def later_stays(refid, upos, orient, negscore, valid, n):
        m = refid.shape[0]
        flip = lambda a: a[::-1]  # noqa: E731
        dup, examined, dups = sound_kernel(
            flip(refid), flip(upos), flip(orient), flip(negscore),
            flip(valid) & (jnp.arange(m)[::-1] < n), jnp.int32(m))
        return flip(dup), examined, dups

    monkeypatch.setattr(markdup, "_markdup_kernel", lambda: later_stays)
    line = run_tiny(root, CELL)
    assert line["correct"] is False and line["failed"] == 0
    assert failed_checks(capsys.readouterr().out) == [
        "chain output column flag",
        "sorted BAM record bytes differing from the reference order's",
        "files of the compared pass (BAM, BAI, SBI) differing from the "
        "host writer's of the reference's records"]


def test_an_output_left_host_backed_is_not_correct(
        root, monkeypatch, capsys):
    """The same records and the same files: what fails is the
    guarantee that the chain's output stays on the device."""
    from disq_tpu.api import ReadsDataset

    sound = ReadsDataset.pipeline

    def materialised(self, *ops):
        out, stats = sound(self, *ops)
        host = out.reads.to_read_batch()
        out.reads.release()
        return ReadsDataset(header=out.header, reads=host), stats

    monkeypatch.setattr(ReadsDataset, "pipeline", materialised)
    line = run_tiny(root, CELL)
    assert line["correct"] is False and line["failed"] == 0
    assert failed_checks(capsys.readouterr().out) == [
        "passes whose output was not device-backed"]


def test_an_index_that_is_not_the_host_writers_is_not_correct(
        root, monkeypatch, capsys):
    """The same wrong byte in every pass's BAI: sizes agree, the record
    bytes are the reference's, the index is present."""
    from benchmark.drivers import op_chain

    sound = op_chain.Driver.one_pass

    def spoiling(self, i):
        sound(self, i)
        with open(self.out + ".bai", "r+b") as f:
            f.seek(-1, 2)
            last = f.read(1)
            f.seek(-1, 2)
            f.write(bytes([last[0] ^ 1]))

    monkeypatch.setattr(op_chain.Driver, "one_pass", spoiling)
    line = run_tiny(root, CELL)
    assert line["correct"] is False and line["failed"] == 0
    bad = failed_checks(capsys.readouterr().out)
    assert len(bad) == 1 and "host writer's" in bad[0]


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
    kept, marks = re.search(r"keeps (\d+) of .* marks (\d+)", setup).groups()
    assert m["chain_kept_per_pass"] == int(kept)
    assert m["chain_duplicates_per_pass"] == int(marks) > 0
    for name in sorted(set(NEW) - DEVICE_TRACE) + [
            "parse_build_s", "fetch_stage_s", "launches_per_pass"]:
        assert m[name] > 0, name
    assert m["markdup_keys_s"] < m["chain_markdup_s"]
    assert m["chain_compact_s"] < m["chain_filter_s"]
    assert 0 < m["lane_fill_pct"] <= 100
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def _scan():
    from disq_tpu.ops import markdup

    return markdup._markdup_kernel()


def _mask():
    from disq_tpu.ops import rfilter

    return rfilter._mask_kernel()


def _rgstats():
    from disq_tpu.ops import rgstats

    return rgstats._rg_kernel(2)


@pytest.mark.parametrize("jitted,name,metrics", [
    (_scan, "markdup_group_scan", ["markdup_scan_kernel_s",
                                   "markdup_scan_roofline"]),
    (_mask, "read_filter_mask", []),
    (_rgstats, "rgstats_reduce", []),
], ids=["markdup", "filter", "rgstats"])
def test_the_operators_programs_have_names_of_their_own(
        jitted, name, metrics):
    """The profiler names a jitted program ``jit_<function name>``;
    two of the operators' were both ``jit_run``, which no reader could
    key on.  The scan's name is what two reader files match."""
    assert jitted().__name__ == name
    for metric in metrics:
        with open(os.path.join(REPO, "benchmark", "layer_metrics",
                               metric + ".json")) as f:
            match = json.load(f)["match"]
        assert re.search(match, "jit_" + name), (metric, match)
        assert not re.search(match, "jit_run")


def test_the_scans_bytes_follow_the_programs_padding():
    from benchmark.drivers import op_chain
    from disq_tpu.util import bucket_pow2

    for kept in (1, 64, 65, 169, 938_412, 1 << 20, (1 << 20) + 1):
        assert op_chain.scan_bytes(kept) == 18 * bucket_pow2(kept)
    assert op_chain.scan_bytes(938_412) == 18 << 20


def test_a_tree_without_the_cells_counter_is_refused_at_once(
        root, monkeypatch):
    """What the parent commit does with this cell: it can build the
    chain and registers no ``ops.markdup.examined``; the driver ends
    before it generates a record (``SystemExit`` with a message is
    exit code 1)."""
    from disq_tpu.ops import markdup
    from disq_tpu.runtime.tracing import reset_telemetry

    reset_telemetry()
    monkeypatch.setattr(markdup, "register_counters", lambda: None)
    t0 = time.perf_counter()
    with pytest.raises(SystemExit) as stop:
        run_tiny(root, CELL)
    assert time.perf_counter() - t0 < 5
    assert isinstance(stop.value.code, str)
    assert "ops.markdup.examined" in stop.value.code
