"""Mesh-native device pipeline parity (ISSUE 17).

conftest forces 8 virtual CPU devices
(``--xla_force_host_platform_device_count=8``), so the whole
sharded decode→sort→reduce program runs here exactly as on a multi-chip
host.  The contracts under test:

- byte-identity: a sorted BAM + BAI written through the mesh pipeline
  is byte-for-byte the single-device (and host) output at 2, 4 and 8
  devices, at executor widths 1 and 4 — duplicate coordinate keys keep
  original-index order because rows ride as the least-significant
  lexsort component at any device count;
- psum reductions: flagstat and windowed depth over the sharded
  columnar batch equal the host truth exactly (integer adds);
- knob semantics: ``DisqOptions.mesh`` / ``DISQ_TPU_MESH`` resolution,
  pow2 rounding, and the off path building no mesh.
"""

import numpy as np
import pytest

from bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records
from disq_tpu.runtime.tracing import (
    REGISTRY, reset_telemetry, stop_span_log)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    stop_span_log()
    reset_telemetry()
    yield
    stop_span_log()
    reset_telemetry()


def _bam_file(tmp_path, n=220, blocksize=900, seed=29, tail=7):
    recs = synth_records(n, seed=seed, unmapped_tail=tail)
    src = tmp_path / "in.bam"
    src.write_bytes(make_bam_bytes(DEFAULT_REFS, recs,
                                   blocksize=blocksize))
    return str(src)


def _mesh_storage(n_dev, workers=1):
    from disq_tpu.api import ReadsStorage

    return (ReadsStorage.make_default().resident_decode()
            .executor_workers(workers).mesh(n_dev))


class TestKnobResolution:
    def test_pow2_floor_and_clamp(self):
        from disq_tpu.runtime.mesh import get_mesh, shard_count

        assert shard_count(get_mesh(0)) == 8
        assert shard_count(get_mesh(8)) == 8
        assert shard_count(get_mesh(6)) == 4  # pow2 floor
        assert shard_count(get_mesh(3)) == 2
        assert shard_count(get_mesh(100)) == 8  # clamps to present
        assert get_mesh(1) is None  # the off path

    def test_env_knob(self, monkeypatch):
        from disq_tpu.runtime.mesh import mesh_devices_requested

        class _S:
            _options = None

        for raw, want in (("", None), ("0", None), ("off", None),
                          ("no", None), ("all", 0), ("auto", 0),
                          ("4", 4)):
            monkeypatch.setenv("DISQ_TPU_MESH", raw)
            assert mesh_devices_requested(_S()) == want, raw

    def test_options_knob_wins_over_env(self, monkeypatch):
        from disq_tpu.api import ReadsStorage
        from disq_tpu.runtime.mesh import mesh_devices_requested

        monkeypatch.setenv("DISQ_TPU_MESH", "2")
        st = ReadsStorage.make_default().mesh(4)
        assert mesh_devices_requested(st) == 4
        assert ReadsStorage.make_default().mesh(0) \
            ._options.mesh == 0

    def test_off_by_default(self):
        from disq_tpu.api import ReadsStorage
        from disq_tpu.runtime.mesh import mesh_devices_requested

        assert mesh_devices_requested(
            ReadsStorage.make_default()) is None


class TestMeshReadParity:
    @pytest.mark.parametrize("n_dev", [2, 4, 8])
    def test_resident_read_carries_mesh_and_matches_host(
            self, tmp_path, n_dev):
        from disq_tpu.api import ReadsStorage
        from disq_tpu.runtime.columnar import ColumnarBatch
        from disq_tpu.runtime.mesh import shard_count

        path = _bam_file(tmp_path)
        host = ReadsStorage.make_default().read(path)
        ds = _mesh_storage(n_dev).read(path)
        cb = ds.reads
        assert isinstance(cb, ColumnarBatch) and cb.device_backed
        assert cb.mesh is not None
        assert shard_count(cb.mesh) == n_dev
        for f in ("refid", "pos", "mapq", "bin", "flag",
                  "next_refid", "next_pos", "tlen"):
            np.testing.assert_array_equal(
                getattr(cb, f), getattr(host.reads, f), err_msg=f)
        assert REGISTRY.counter("device.mesh.batches").total() > 0
        cb.release()

    def test_flagstat_psum_equals_host(self, tmp_path):
        from disq_tpu.api import ReadsStorage

        path = _bam_file(tmp_path, n=260, seed=31, tail=9)
        host = ReadsStorage.make_default().read(path).flagstat()
        got = _mesh_storage(8).read(path).flagstat()
        assert got == host

    def test_depth_psum_equals_host(self, tmp_path):
        from disq_tpu.api import ReadsStorage

        path = _bam_file(tmp_path, n=240, seed=37)
        host = ReadsStorage.make_default().read(path).depth(window=1024)
        got = _mesh_storage(4).read(path).depth(window=1024)
        assert host.keys() == got.keys()
        for k in host:
            np.testing.assert_array_equal(got[k], host[k], err_msg=str(k))

    def test_sort_permutation_byte_identical(self, tmp_path):
        """The multi-chip psum-histogram sort returns the host stable
        argsort EXACTLY — including among duplicate coordinate keys
        (synth records repeat positions)."""
        from disq_tpu.api import ReadsStorage
        from disq_tpu.sort.coordinate import coordinate_keys

        path = _bam_file(tmp_path, n=300, seed=41, tail=11)
        host = ReadsStorage.make_default().read(path).reads
        want = np.argsort(coordinate_keys(host.refid, host.pos),
                          kind="stable")
        cb = _mesh_storage(8).read(path).reads
        got = cb.sort_permutation()
        np.testing.assert_array_equal(got, want)
        assert REGISTRY.counter(
            "device.mesh.exchange_bytes").total() > 0
        cb.release()


class TestMeshWriteByteIdentity:
    @pytest.mark.parametrize("n_dev,workers", [
        (2, 1), (4, 4), (8, 1), (8, 4)])
    def test_sorted_bam_and_bai_byte_identical(
            self, tmp_path, n_dev, workers):
        from disq_tpu.api import BaiWriteOption, ReadsStorage

        path = _bam_file(tmp_path, n=280, seed=43, tail=8)
        ref = ReadsStorage.make_default()
        ref_out = str(tmp_path / "host.bam")
        ref.write(ref.read(path), ref_out, BaiWriteOption.ENABLE,
                  sort=True)

        st = _mesh_storage(n_dev, workers=workers)
        out = str(tmp_path / f"mesh{n_dev}w{workers}.bam")
        st.write(st.read(path), out, BaiWriteOption.ENABLE, sort=True)

        with open(ref_out, "rb") as f:
            want = f.read()
        with open(out, "rb") as f:
            assert f.read() == want
        with open(ref_out + ".bai", "rb") as f:
            want_bai = f.read()
        with open(out + ".bai", "rb") as f:
            assert f.read() == want_bai


class TestMeshOff:
    def test_default_builds_no_mesh(self, tmp_path):
        """Fresh subprocess (this test module already built meshes):
        the default path must never construct a Mesh, reshard a byte,
        or deviate from single-device dispatch — the
        scripts/check_overhead.py section 1d contract, asserted here
        in-process for the read path."""
        import subprocess
        import sys

        code = """
import numpy as np, sys
sys.path.insert(0, "tests")
from bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records
open("%(bam)s", "wb").write(
    make_bam_bytes(DEFAULT_REFS, synth_records(80, seed=3)))
from disq_tpu.api import ReadsStorage
from disq_tpu.runtime import mesh
from disq_tpu.runtime.tracing import REGISTRY
ds = ReadsStorage.make_default().resident_decode().read("%(bam)s")
assert ds.reads.mesh is None
ds.flagstat()
assert mesh.mesh_if_built() is None
assert mesh.service_devices() == [None]
assert REGISTRY.counter("device.mesh.reshard_bytes").total() == 0
assert REGISTRY.counter("device.mesh.exchange_bytes").total() == 0
print("OK")
"""
        bam = str(tmp_path / "off.bam")
        r = subprocess.run(
            [sys.executable, "-c", code % {"bam": bam}],
            capture_output=True, text=True, cwd="/root/repo",
            env={"PATH": "/usr/local/bin:/usr/bin:/bin",
                 "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stderr
        assert "OK" in r.stdout


class TestLaunchSpansNameTheirChip:
    """With the mesh knob armed when the decode service starts, the
    queue wait and the five per-launch spans carry ``device`` (the
    index in ``service_devices()``), so which chip a launch went to can
    be read; mesh off they stay unlabelled, as ``device.lane_fill``."""

    LAUNCH = ["device.launch." + s
              for s in ("pack", "submit", "wait", "d2h", "deliver")]

    def _read_through_the_service(self, tmp_path, monkeypatch, mesh):
        from disq_tpu.api import ReadsStorage
        from disq_tpu.runtime import device_service
        from disq_tpu.runtime.tracing import spans

        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        monkeypatch.setenv("DISQ_TPU_DEVICE_SERVICE", "1")
        if mesh:
            monkeypatch.setenv("DISQ_TPU_MESH", str(mesh))
        # interpreter-sized blocks; eight splits, so that every one of
        # four sub-queues is handed a submission
        path = _bam_file(tmp_path, n=160, blocksize=300)
        import os

        device_service.shutdown_service()
        try:
            st = (ReadsStorage.make_default().resident_decode()
                  .executor_workers(4)
                  .split_size(max(2048, os.path.getsize(path) // 8)))
            ds = st.read(path)
            count = ds.count()
            ds.reads.release()
        finally:
            device_service.shutdown_service()
        assert count == 160 + 7
        return [s for s in spans()
                if s["name"] in self.LAUNCH + ["device.service.wait"]]

    def test_one_value_a_mesh_device(self, tmp_path, monkeypatch):
        got = self._read_through_the_service(tmp_path, monkeypatch, 4)
        for name in self.LAUNCH + ["device.service.wait"]:
            mine = [s for s in got if s["name"] == name]
            assert mine, name
            assert all("device" in s["labels"] for s in mine), name
            assert {s["labels"]["device"] for s in mine} == {0, 1, 2, 3}, name
        # a launch's spans agree on its chip
        by_launch = {}
        for s in got:
            if s["name"] in self.LAUNCH:
                by_launch.setdefault(s["labels"]["launch"], set()).add(
                    s["labels"]["device"])
        assert by_launch and all(len(v) == 1 for v in by_launch.values())

    def test_absent_with_the_mesh_off(self, tmp_path, monkeypatch):
        from disq_tpu.runtime import mesh as mesh_mod

        monkeypatch.delenv("DISQ_TPU_MESH", raising=False)
        # a service started with no knob takes the mesh the process
        # has built, and this module's tests have built some
        monkeypatch.setattr(mesh_mod, "_MESH_CACHE", {})
        got = self._read_through_the_service(tmp_path, monkeypatch, 0)
        assert {s["name"] for s in got} == set(
            self.LAUNCH + ["device.service.wait"])
        assert not any("device" in s["labels"] for s in got)

    def test_the_mesh_counters_are_registered_with_the_mesh(
            self, monkeypatch):
        from disq_tpu.runtime.mesh import MESH_COUNTERS, get_mesh
        from disq_tpu.runtime.tracing import telemetry_snapshot
        from disq_tpu.runtime import mesh as mesh_mod

        monkeypatch.setattr(mesh_mod, "_MESH_CACHE", {})
        assert get_mesh(2) is not None
        counters = telemetry_snapshot()["counters"]
        for name in MESH_COUNTERS:
            assert counters[name] == {"": 0}, name
        # and with the cached mesh, after telemetry was reset
        reset_telemetry()
        assert get_mesh(2) is mesh_mod._MESH_CACHE[2]
        counters = telemetry_snapshot()["counters"]
        for name in MESH_COUNTERS:
            assert counters[name] == {"": 0}, name


class TestEveryChipKeepsItsOwnPipeline:
    """The decode service on a mesh: a split a chip, and the chips fed
    side by side.  The dispatcher thread is never started here, so the
    two choices (``_enqueue``: which chip takes a submission;
    ``_take_chunk_locked``: which chip's chunk is launched next) are
    stepped by hand, as ``_loop`` steps them."""

    @pytest.fixture()
    def parked(self, monkeypatch):
        import threading

        from disq_tpu.runtime import device_service

        monkeypatch.setenv("DISQ_TPU_MESH", "4")
        monkeypatch.setattr(threading.Thread, "start", lambda self: None)
        svc = device_service.DeviceDecodeService(
            flush_timeout_s=60.0, interpret=True)
        assert len(svc._devices) == 4
        return svc

    @staticmethod
    def _split(svc, n_lanes, kind="inflate"):
        from disq_tpu.runtime.device_service import Submission, _Lane

        sub = Submission(parts_n=n_lanes)
        lanes = [_Lane(sub, i, b"", 0, 0.0, None) for i in range(n_lanes)]
        svc._enqueue({kind: lanes}, sub)
        return next(i for i, q in enumerate(svc._queues[kind])
                    if q and q[-1] is lanes[-1])

    def _launch_one(self, svc):
        kind, dev_i, lanes, _reason = svc._take_chunk_locked()
        svc._inflight.append((kind, None, lanes, {}, dev_i))
        return kind, dev_i, len(lanes)

    def _finish_one(self, svc, dev_i, n_lanes):
        entry = next(e for e in svc._inflight if e[4] == dev_i)
        svc._inflight.remove(entry)
        svc._settle(dev_i, n_lanes)

    def test_four_splits_go_to_four_chips(self, parked):
        assert sorted(self._split(parked, 300) for _ in range(4)) \
            == [0, 1, 2, 3]

    def test_a_late_split_goes_to_the_chip_that_has_had_none(self, parked):
        svc = parked
        first = [self._split(svc, 256) for _ in range(3)]
        assert len(set(first)) == 3
        # the first chip finishes its whole split before the fourth
        # split arrives: both it and the untouched chip hold no lane,
        # and the rotation goes on to the untouched one
        done = []
        while svc._queues["inflate"][first[0]]:
            kind, dev_i, n = self._launch_one(svc)
            done.append((dev_i, n))
        for dev_i, n in done:
            self._finish_one(svc, dev_i, n)
        assert not svc._queues["inflate"][first[0]]
        assert self._split(svc, 256) == ({0, 1, 2, 3} - set(first)).pop()

    def test_a_drained_queue_still_in_flight_is_not_a_free_chip(
            self, parked):
        svc = parked
        a = self._split(svc, 128)
        kind, dev_i, n = self._launch_one(svc)      # queued -> in flight
        assert dev_i == a and not svc._queues["inflate"][a]
        others = [self._split(svc, 128) for _ in range(3)]
        assert a not in others and len(set(others)) == 3

    def test_launches_go_round_the_chips(self, parked):
        svc = parked
        for _ in range(4):
            self._split(svc, 3 * 128)
        order = [self._launch_one(svc)[1] for _ in range(12)]
        # oldest-first alone gave 0,0,0,1,1,1,...: one chip's launches
        # all issued before the next chip's first
        for k in range(0, 12, 4):
            assert sorted(order[k: k + 4]) == [0, 1, 2, 3], order
        assert svc._take_chunk_locked() is None

    def test_on_one_chip_the_oldest_lane_goes_first(self, parked):
        svc = parked
        a = self._split(svc, 128, "inflate")
        for i in range(4):                 # load the other chips
            if i != a:
                svc._outstanding[i] += 10_000
        assert self._split(svc, 128, "rans") == a
        svc._queues["rans"][a][0].ts -= 1.0        # the older lane
        assert self._launch_one(svc)[0] == "rans"
        assert self._launch_one(svc)[0] == "inflate"
