"""The ``longread_passes`` driver end to end on the CPU at a tiny size,
the look for a chip waived inside the test: a sound run is correct; the
control (a dropped record), a block sent to the host as oversize and a
timed path broken underneath are not."""

import json

import pytest

from harness_util import REPO, copy_benchmark, manifest, run_tiny

# interpreter-sized: a dozen records of 0.6-25 kb in 1,000-byte BGZF
# blocks (every record spans blocks), three splits
TINY = {"longread_read": {"records": 12, "bgzf_block_payload": 1000,
                          "trace_seconds": 1, "split_size_bytes": 30000}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return copy_benchmark(tmp_path_factory.mktemp("bench"), TINY)


def test_sound_run_is_correct_and_reports_the_end_to_end_metrics(
        root, capsys):
    line = run_tiny(root, "longread_read")
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"records_per_s", "setup_s"}
    assert line["metrics"]["records_per_s"]["value"] > 0
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    json.dumps(line)
    out = capsys.readouterr().out
    # the driver states what came out of the generator and the writer
    stated = next(ln for ln in out.splitlines() if ln.startswith("input:"))
    assert "decoded bytes a record" in stated and "zlib ratio" in stated
    assert "FAILED" not in out


def test_the_cell_is_the_issue_s(root):
    from benchmark import run

    spec = run.load_cell(REPO, "longread_read")
    assert spec["cell"] == {
        "name": "longread_read", "config": "ont30x",
        "traffic": "longread_read", "chips": 1, "why": spec["cell"]["why"]}
    tr = spec["traffic"]
    assert (tr["driver"], tr["records"]) == ("longread_passes", 32001)
    assert (tr["executor_workers"], tr["writer_workers"]) == (4, 4)
    assert tr["env"] == run.load_cell(REPO, "wgs_read")["traffic"]["env"]
    assert "split_size_bytes" not in tr and "bgzf_block_payload" not in tr
    # in order of precedence: the pass's own annotation covers a whole
    # pass, so it comes last and takes what the others leave
    assert tr["gap_labels"][-1] == "longread_read"
    assert len(tr["gap_labels"]) == 5
    assert spec["config"]["split_size_bytes"] == 128 << 20
    assert spec["config"]["reduced"].keys() == {"records"}
    new = {"inflate_wide_lanes_per_pass", "cigar_ops_per_record",
           "split_guess_s"}
    assert new <= {p["name"] for p in spec["per_layer"]}
    for p in manifest()["per_layer"]:
        if p["name"] in new:
            assert p["workloads"] == ["longread_read"]


def test_without_this_cell_exome_intervals_is_still_its_issue_s_tables(
        monkeypatch):
    """``test_driver_interval_passes.py`` asks that its cell be the last
    one, which a later cell undoes (``conftest.py`` expects that test to
    fail).  Its body, run on the manifest with this PR's cell and
    configuration taken off, still holds every line."""
    import test_driver_interval_passes as earlier

    doc = manifest()
    assert doc["workloads"][-1]["name"] == "longread_read"
    assert doc["configs"][-1]["name"] == "ont30x"
    doc["workloads"].pop()
    doc["configs"].pop()
    for m in doc["end_to_end"] + doc["per_layer"]:
        if m.get("workloads", [])[-1:] == ["longread_read"]:
            m["workloads"].pop()
    doc["per_layer"] = [m for m in doc["per_layer"] if m.get("workloads")
                        or "workloads" not in m]
    monkeypatch.setattr(earlier, "manifest", lambda: doc)
    earlier.test_the_cell_is_the_issues_tables_letter_for_letter()


def test_the_control_a_dropped_record_is_not_correct(root, capsys):
    line = run_tiny(root, "longread_read", control="drop_record")
    assert line["correct"] is False
    assert "FAILED" in capsys.readouterr().out


def test_a_block_sent_to_the_host_as_oversize_is_not_correct(
        root, monkeypatch, capsys):
    """The oversize route forced: the kernel's cap set under the tiny
    file's payloads after the driver has looked at it, so every block
    inflates on the host."""
    from benchmark.drivers import longread_passes, program
    from disq_tpu.ops import inflate_simd

    sound = program.storage

    def capped(cfg, params):
        monkeypatch.setattr(inflate_simd, "MAX_DEVICE_CSIZE", 64)
        return sound(cfg, params)

    monkeypatch.setattr(longread_passes.program, "storage", capped)
    line = run_tiny(root, "longread_read")
    assert line["correct"] is False
    out = capsys.readouterr().out
    failed = [ln for ln in out.splitlines() if ln.endswith("FAILED")]
    assert any("inflated on the host" in ln for ln in failed)
    assert any("did not inflate" in ln for ln in failed)


def test_a_tree_whose_kernel_stops_at_32_kib_ends_before_it_generates(
        root, monkeypatch):
    from disq_tpu.ops import inflate_simd

    monkeypatch.setattr(inflate_simd, "MAX_DEVICE_CSIZE", 8192 * 4 - 16)
    with pytest.raises(SystemExit, match="would inflate on the host"):
        run_tiny(root, "longread_read")


def test_traced_run_reads_the_new_counters_and_the_split_search(root):
    line = run_tiny(root, "longread_read", trace=True)
    assert line["correct"] is True
    got = set(line["metrics"])
    assert {"cigar_ops_per_record", "split_guess_s", "launches_per_pass",
            "lane_fill_pct", "inflate_supersteps_per_pass",
            "compiles_in_window"} <= got
    assert "records_per_s" not in got and "setup_s" not in got
    # every mapped record's ops, and the unmapped one's none
    assert line["metrics"]["cigar_ops_per_record"]["value"] > 100
    assert line["metrics"]["split_guess_s"]["value"] > 0
    # no block of the tiny file is over the narrow payload: the lanes'
    # counter moved, and not under the wide geometry's label
    assert line["metrics"]["inflate_wide_lanes_per_pass"]["value"] == 0
    allowed = {p["name"] for p in manifest()["per_layer"]
               if "longread_read" in p.get("workloads", ["longread_read"])}
    assert got <= allowed


def test_passes_that_answer_wrongly_are_not_correct(root, monkeypatch):
    """The timed path broken underneath: depth loses an alignment's last
    window where it is produced."""
    from disq_tpu.api import ReadsDataset

    sound = ReadsDataset.depth

    def broken(self, *a, **kw):
        out = {k: v.copy() for k, v in sound(self, *a, **kw).items()}
        out[0][0] += 1
        return out

    monkeypatch.setattr(ReadsDataset, "depth", broken)
    line = run_tiny(root, "longread_read")
    assert line["correct"] is False and line["failed"] == line["attempted"]
