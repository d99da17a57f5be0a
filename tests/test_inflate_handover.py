"""A split is finished when its last launch lands (ISSUE 39): the
decode service holds each launch's blocks to their footer CRC32s as it
delivers them, and decodes into the buffer the resident parse uploads.

Interpret-mode kernels on the CPU: tiny payloads and BGZF block sizes,
the geometry buckets ``tests/test_device_service.py`` already
compiles."""

import json
import os
import sys
import zlib

import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
if TESTS not in sys.path:
    sys.path.insert(0, TESTS)

from test_device_service import _bam_file, deflate, text_like  # noqa: E402


@pytest.fixture()
def service():
    from disq_tpu.runtime.device_service import DeviceDecodeService

    # 0.5 s: two submissions made back to back share their first launch
    svc = DeviceDecodeService(flush_timeout_s=0.5, interpret=True)
    yield svc
    svc.close()


def _crc_blocks():
    from disq_tpu.runtime.tracing import REGISTRY

    c = REGISTRY.counter("codec.inflate.crc_blocks")
    return {at: c.value(at=at) for at in ("launch", "tail")}


def _stage_bytes():
    from disq_tpu.runtime.tracing import REGISTRY

    c = REGISTRY.counter("columnar.batch.stage_bytes")
    return {how: c.value(how=how) for how in ("in_place", "copied")}


def _grew(before, after):
    return {k: after[k] - before[k] for k in before}


def _bgzf(payloads):
    """(file bytes, block table) of one BGZF block a payload."""
    from disq_tpu.bgzf.codec import deflate_block
    from disq_tpu.bgzf.guesser import find_block_table
    from disq_tpu.fsw import MemoryFileSystemWrapper

    data = b"".join(deflate_block(p) for p in payloads)
    fs = MemoryFileSystemWrapper()
    fs.write_all("mem://handover.bgzf", data)
    return data, find_block_table(fs, "mem://handover.bgzf")


def _footer_flipped(data, block):
    from disq_tpu.bgzf.block import BGZF_FOOTER_SIZE

    bad = bytearray(data)
    bad[block.pos + block.csize - BGZF_FOOTER_SIZE] ^= 0x10
    return bytes(bad)


# ---------------------------------------------------------------------------
# (a) a wrong footer CRC fails result(), whichever launch holds it
# ---------------------------------------------------------------------------


class TestCrcAsTheLaunchesLand:
    # a sound submission of 4 lanes is queued first, so the owner's
    # 130 blocks go out as 124 lanes of the first launch (full, 128)
    # and 6 of the second: 3 is in the first, 127 and 129 in the second
    @pytest.mark.parametrize("bad", [3, 127, 129])
    def test_a_wrong_crc_fails_its_owner_alone(self, service, bad):
        sound_raws = [text_like(90 + 5 * i, seed=200 + i) for i in range(4)]
        raws = [text_like(60 + i % 9, seed=i) for i in range(130)]
        crcs = [zlib.crc32(r) for r in raws]
        crcs[bad] ^= 1
        before = _crc_blocks()
        sound = service.submit_inflate(
            [deflate(r) for r in sound_raws], [len(r) for r in sound_raws],
            crcs=[zlib.crc32(r) for r in sound_raws])
        owner = service.submit_inflate(
            [deflate(r) for r in raws], [len(r) for r in raws], crcs=crcs)
        with pytest.raises(ValueError) as err:
            owner.result(timeout=300)
        assert str(err.value) == f"BGZF CRC mismatch at block {bad}"
        blob, _ = sound.result(timeout=300)
        assert blob.tobytes() == b"".join(sound_raws)
        assert _grew(before, _crc_blocks())["launch"] >= 4 + 1
        assert _grew(before, _crc_blocks())["tail"] == 0

    def test_result_waits_for_the_checks(self, service, monkeypatch):
        """Every lane stored is not yet a result: a check that has not
        run holds ``result()`` back."""
        import threading

        from disq_tpu.runtime import device_service

        gate = threading.Event()
        real = device_service.Submission.check

        def held(self, indices):
            gate.wait(60)
            real(self, indices)

        monkeypatch.setattr(device_service.Submission, "check", held)
        raws = [text_like(70 + i, seed=i) for i in range(5)]
        sub = service.submit_inflate(
            [deflate(r) for r in raws], [len(r) for r in raws],
            crcs=[zlib.crc32(r) for r in raws])
        with pytest.raises(TimeoutError):
            sub.result(timeout=3)
        gate.set()
        blob, _ = sub.result(timeout=300)
        assert blob.tobytes() == b"".join(raws)

    @pytest.mark.parametrize("native", [True, False])
    @pytest.mark.parametrize("bad", [None, 0, 5])
    def test_check_names_the_first_wrong_block(self, monkeypatch, native,
                                               bad):
        """``Submission.check`` by itself, with the host library's one
        call a task and with ``zlib.crc32`` a block where the library
        cannot be built: the same verdict, the same count."""
        import disq_tpu.native as native_lib
        from disq_tpu.runtime.device_service import Submission

        if not native:
            def unbuilt(*_a, **_k):
                raise ImportError("no toolchain")

            monkeypatch.setattr(native_lib, "crc32_check_native", unbuilt)
        raws = [text_like(50 + 11 * i, seed=i) for i in range(7)] + [b""]
        offsets = np.zeros(len(raws) + 1, np.int64)
        np.cumsum([len(r) for r in raws], out=offsets[1:])
        crcs = np.array([zlib.crc32(r) for r in raws], np.uint32)
        if bad is not None:
            crcs[bad] ^= 1
            crcs[6] ^= 1  # a later one too: the first is named
        sub = Submission(blob=np.empty(int(offsets[-1]), np.uint8),
                         offsets=offsets, crcs=crcs)
        before = _crc_blocks()
        for i, r in enumerate(raws):
            sub.deliver_local(i, r)
        assert not sub._event.is_set()
        sub.check(list(range(len(raws))))
        assert sub._event.is_set()
        grew = _grew(before, _crc_blocks())
        if bad is None:
            blob, _ = sub.result(timeout=1)
            assert blob.tobytes() == b"".join(raws)
            assert grew == {"launch": len(raws), "tail": 0}
        else:
            with pytest.raises(ValueError) as err:
                sub.result(timeout=1)
            assert str(err.value) == f"BGZF CRC mismatch at block {bad}"
            assert grew == {"launch": bad + 1, "tail": 0}

    def test_no_crcs_no_check(self, service):
        """``serve.py`` and ``tpu_ci`` submit without CRCs and see what
        they saw: bytes, and no CRC taken."""
        raws = [text_like(70 + i, seed=i) for i in range(5)]
        before = _crc_blocks()
        blob, _ = service.submit_inflate(
            [deflate(r) for r in raws],
            [len(r) for r in raws]).result(timeout=300)
        assert blob.tobytes() == b"".join(raws)
        assert _grew(before, _crc_blocks()) == {"launch": 0, "tail": 0}

    @pytest.mark.parametrize("route", ["direct", "service"])
    def test_both_routes_name_the_block_alike(self, monkeypatch, route):
        """Through ``inflate_blocks_device``: the same ``ValueError``
        text on either route, and a sound batch counts its blocks under
        the route's ``at``."""
        from disq_tpu.bgzf.codec import inflate_blocks_device
        from disq_tpu.runtime import device_service

        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        if route == "service":
            monkeypatch.setenv("DISQ_TPU_DEVICE_SERVICE", "1")
        payloads = [text_like(120 + 3 * i, seed=i) for i in range(9)]
        data, blocks = _bgzf(payloads)
        before = _crc_blocks()
        try:
            assert inflate_blocks_device(data, blocks) == b"".join(payloads)
            grew = _grew(before, _crc_blocks())
            with pytest.raises(ValueError) as err:
                inflate_blocks_device(
                    _footer_flipped(data, blocks[5]), blocks)
        finally:
            device_service.shutdown_service()
        assert str(err.value) == "BGZF CRC mismatch at block 5"
        at = "launch" if route == "service" else "tail"
        assert grew == {"launch": 0, "tail": 0, at: 9}


# ---------------------------------------------------------------------------
# (c) host-fallback lanes (flagged, oversize) are checked as well
# ---------------------------------------------------------------------------


def _flag_lanes(monkeypatch, which):
    """Make the kernel's answer flag the launch's lanes ``which``: the
    service then re-inflates them on the host."""
    from disq_tpu.ops import inflate_simd as IS

    real = IS._fetch_chunk

    def flagging(handle, lanes, labels=None, kernel="inflate_simd", **kw):
        words, meta = real(handle, lanes, labels, kernel, **kw)
        meta = np.array(meta)
        meta[1, list(which)] = 7
        return words, meta

    monkeypatch.setattr(IS, "_fetch_chunk", flagging)


class TestHostFallbackLanesAreChecked:
    # one flagged lane is re-inflated on the dispatcher's thread, more
    # than one on the host pool
    @pytest.mark.parametrize("flagged", [(2,), (1, 2, 4)])
    def test_flagged_lanes(self, service, monkeypatch, flagged):
        from disq_tpu.ops.inflate_simd import last_stats

        _flag_lanes(monkeypatch, flagged)
        raws = [text_like(80 + 7 * i, seed=30 + i) for i in range(6)]
        payloads = [deflate(r) for r in raws]
        crcs = [zlib.crc32(r) for r in raws]
        fell, before = last_stats["host_fallback"], _crc_blocks()
        blob, _ = service.submit_inflate(
            payloads, [len(r) for r in raws], crcs=crcs).result(300)
        assert blob.tobytes() == b"".join(raws)
        assert last_stats["host_fallback"] - fell == len(flagged)
        assert _grew(before, _crc_blocks()) == {"launch": 6, "tail": 0}
        wrong = list(crcs)
        wrong[flagged[-1]] ^= 1
        with pytest.raises(ValueError) as err:
            service.submit_inflate(
                payloads, [len(r) for r in raws], crcs=wrong).result(300)
        assert str(err.value) == (
            f"BGZF CRC mismatch at block {flagged[-1]}")

    def test_an_oversize_block(self, service):
        from disq_tpu.ops.inflate_simd import MAX_DEVICE_CSIZE, last_stats

        raws = [text_like(100 + 7 * i, seed=60 + i) for i in range(5)]
        raws.insert(2, np.random.default_rng(3).integers(
            0, 256, MAX_DEVICE_CSIZE + 4096, dtype=np.uint8).tobytes())
        payloads = [deflate(r) for r in raws]
        crcs = [zlib.crc32(r) for r in raws]
        big, before = last_stats["host_big"], _crc_blocks()
        blob, _ = service.submit_inflate(
            payloads, [len(r) for r in raws], crcs=crcs).result(300)
        assert blob.tobytes() == b"".join(raws)
        assert last_stats["host_big"] - big == 1
        assert _grew(before, _crc_blocks()) == {"launch": 6, "tail": 0}
        crcs[2] ^= 1
        with pytest.raises(ValueError) as err:
            service.submit_inflate(
                payloads, [len(r) for r in raws], crcs=crcs).result(300)
        assert str(err.value) == "BGZF CRC mismatch at block 2"


# ---------------------------------------------------------------------------
# (d) verify_crc=False takes no CRC
# ---------------------------------------------------------------------------


class TestVerifyOff:
    @pytest.mark.parametrize("route", ["direct", "service"])
    def test_no_crc_is_computed(self, monkeypatch, route):
        from disq_tpu.bgzf.codec import inflate_blocks
        from disq_tpu.runtime import device_service

        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        if route == "service":
            monkeypatch.setenv("DISQ_TPU_DEVICE_SERVICE", "1")
        payloads = [text_like(120 + 3 * i, seed=i) for i in range(9)]
        data, blocks = _bgzf(payloads)
        before = _crc_blocks()
        try:
            # a wrong footer goes unseen: nothing looked at it
            out = inflate_blocks(_footer_flipped(data, blocks[4]), blocks,
                                 verify_crc=False)
        finally:
            device_service.shutdown_service()
        assert out == b"".join(payloads)
        assert _grew(before, _crc_blocks()) == {"launch": 0, "tail": 0}


# ---------------------------------------------------------------------------
# (e) the service's buffer is the upload buffer
# ---------------------------------------------------------------------------


def _record_blob(n, seed, lead):
    """``lead`` bytes that are no record, then ``n`` encoded records;
    returns (bytes, the records' offsets from their own start)."""
    from bam_oracle import encode_record, synth_records

    recs = [encode_record(r) for r in synth_records(n, seed=seed)]
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum([len(r) for r in recs], out=offsets[1:])
    return bytes(range(1, lead + 1)) + b"".join(recs), offsets


def _padded_submission(service, raw, blocksize=300):
    parts = [raw[i: i + blocksize] for i in range(0, len(raw), blocksize)]
    sub = service.submit_inflate(
        [deflate(p) for p in parts], [len(p) for p in parts],
        crcs=[zlib.crc32(p) for p in parts], padded=True)
    blob, _ = sub.result(timeout=300)
    assert blob.tobytes() == raw
    return sub


class TestTheBlobIsTheUploadBuffer:
    def test_a_padded_blob_heads_a_quantised_zero_tailed_buffer(
            self, service):
        from disq_tpu.util import pad_quantum

        raw = text_like(1234, seed=5)  # 1234 = 2 mod 4
        sub = _padded_submission(service, raw)
        assert sub.base.dtype == np.uint8
        assert sub.base.nbytes == 4 * pad_quantum((len(raw) + 3) // 4)
        assert np.shares_memory(sub.blob, sub.base)
        assert sub.base[: len(raw)].tobytes() == raw
        assert not sub.base[len(raw):].any()
        # and asked for nothing, a submission has no base
        plain = service.submit_inflate([deflate(raw[:200])], [200])
        plain.result(timeout=300)
        assert plain.base is None and plain.blob.nbytes == 200

    # lo_u: where the records begin in the blob; tail: bytes after the
    # last record, chosen so that the total is and is not a multiple
    # of 4 (the oracle's records sum to a multiple of 4 or do not, so
    # the case asserts which it got)
    @pytest.mark.parametrize("lo_u", [0, 37])
    @pytest.mark.parametrize("aligned", [True, False])
    @pytest.mark.parametrize("mesh_devices", [0, 8])
    def test_in_place_equals_the_copy(self, service, lo_u, aligned,
                                      mesh_devices):
        from disq_tpu.runtime.device_pipeline import parse_columns_resident
        from disq_tpu.runtime.mesh import get_mesh

        raw, offsets = _record_blob(40, seed=11, lead=lo_u)
        raw += b"\x00" * ((-len(raw)) % 4 + (0 if aligned else 3))
        assert (len(raw) % 4 == 0) == aligned
        sub = _padded_submission(service, raw)
        record_bytes = sub.blob[lo_u: lo_u + int(offsets[-1])]
        mesh = get_mesh(mesh_devices) if mesh_devices else None
        before = _stage_bytes()
        copied, up_c, n_c = parse_columns_resident(
            record_bytes, offsets, interpret=True, mesh=mesh)
        mid = _stage_bytes()
        in_place, up_p, n_p = parse_columns_resident(
            record_bytes, offsets, origin=lo_u, interpret=True, mesh=mesh,
            staged=sub.base)
        assert n_c == n_p == 40
        assert set(copied) == set(in_place)
        for name in copied:
            np.testing.assert_array_equal(
                np.asarray(in_place[name])[:40],
                np.asarray(copied[name])[:40], err_msg=name)
        n_dev = mesh_devices or 1
        assert _grew(before, mid) == {
            "in_place": 0, "copied": (up_c - 64 * 4) // n_dev}
        assert _grew(mid, _stage_bytes()) == {
            "in_place": sub.base.nbytes, "copied": 0}

    def test_a_service_read_stages_nothing(self, tmp_path, monkeypatch):
        """A resident BAM read through the service, in several splits
        (every split after the first begins inside a block: ``lo_u`` >
        0): records equal to the host read's, every build uploaded the
        service's buffer and none copied; the stage span is still one a
        build, before its transfer."""
        from disq_tpu import ReadsStorage
        from disq_tpu.runtime import device_service
        from disq_tpu.runtime.tracing import spans

        path = _bam_file(tmp_path, n=90, blocksize=300)
        host = ReadsStorage.make_default().read(path)
        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        monkeypatch.setenv("DISQ_TPU_DEVICE_SERVICE", "1")
        since, before = len(spans()), _stage_bytes()
        try:
            ds = (ReadsStorage.make_default().split_size(8000)
                  .executor_workers(1).resident_decode().read(path))
        finally:
            device_service.shutdown_service()
        assert ds.count() == host.count() == 90
        for col in ("pos", "flag", "mapq", "seqs", "quals", "names"):
            np.testing.assert_array_equal(
                getattr(ds.reads, col), getattr(host.reads, col), col)
        ring = spans()[since:]
        builds = [s for s in ring if s["name"] == "columnar.batch.build"]
        stages = [s for s in ring if s["name"] == "columnar.batch.stage"]
        assert len(builds) >= 2 and len(stages) == len(builds)
        grew = _grew(before, _stage_bytes())
        assert grew == {
            "in_place": sum(s["labels"]["bytes"] for s in stages),
            "copied": 0}
        ds.reads.release()


# ---------------------------------------------------------------------------
# (f) the counters of a two-launch read
# ---------------------------------------------------------------------------


class TestCountersOfATwoLaunchRead:
    def test_what_a_two_launch_read_gives(self, tmp_path, monkeypatch):
        """One split of more than 128 blocks: two launches, every block
        of the split checked as its launch landed and none at the tail,
        and one padded buffer uploaded in place."""
        from disq_tpu import ReadsStorage
        from disq_tpu.bgzf.guesser import find_block_table
        from disq_tpu.fsw import PosixFileSystemWrapper
        from disq_tpu.runtime import device_service
        from disq_tpu.runtime.tracing import REGISTRY, spans
        from disq_tpu.util import pad_quantum

        path = _bam_file(tmp_path, n=150, blocksize=150)
        blocks = find_block_table(PosixFileSystemWrapper(), path)
        assert 128 < len(blocks) <= 256
        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        monkeypatch.setenv("DISQ_TPU_DEVICE_SERVICE", "1")
        launches = REGISTRY.counter("device.kernel_launches")
        base = launches.value(kernel="inflate_simd")
        since = len(spans())
        crc, staged = _crc_blocks(), _stage_bytes()
        try:
            ds = (ReadsStorage.make_default().executor_workers(1)
                  .resident_decode().read(path))
        finally:
            device_service.shutdown_service()
        assert ds.count() == 150
        ds.reads.release()
        (batch,) = [s for s in spans()[since:]
                    if s["name"] == "codec.inflate.batch"]
        n = batch["labels"]["blocks"]
        assert n > 128
        assert launches.value(kernel="inflate_simd") - base == 2
        assert _grew(crc, _crc_blocks()) == {"launch": n, "tail": 0}
        decoded = sum(b.usize for b in blocks[-n:])
        assert _grew(staged, _stage_bytes()) == {
            "in_place": 4 * pad_quantum((decoded + 3) // 4), "copied": 0}


# ---------------------------------------------------------------------------
# (b) a corrupt block is reported as with the service off
# ---------------------------------------------------------------------------


def _read_corrupt(path, victim, policy, qdir, service_on, monkeypatch):
    """The outcome of a device-inflate read of ``path`` with the footer
    CRC of the block at ``victim`` flipped."""
    from disq_tpu import CorruptBlockError, DisqOptions, ErrorPolicy
    from disq_tpu import ReadsStorage
    from disq_tpu.bgzf.block import BGZF_FOOTER_SIZE
    from disq_tpu.fsw import (
        FaultInjectingFileSystemWrapper,
        FaultSpec,
        PosixFileSystemWrapper,
        register_filesystem,
    )
    from disq_tpu.runtime import device_service

    register_filesystem("fault", FaultInjectingFileSystemWrapper(
        PosixFileSystemWrapper(),
        [FaultSpec(kind="bitflip", path_substr="in.bam",
                   offset=victim.pos + victim.csize - BGZF_FOOTER_SIZE,
                   bit=2)]))
    monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
    monkeypatch.setenv("DISQ_TPU_DEVICE_SERVICE", "1" if service_on else "0")
    opts = DisqOptions(error_policy=ErrorPolicy.coerce(policy),
                       retry_backoff_s=0.0, quarantine_dir=qdir)
    try:
        ds = (ReadsStorage.make_default().split_size(8000).options(opts)
              .executor_workers(1).read("fault://" + path))
    except CorruptBlockError as e:
        return {"raised": (e.block_offset, e.shard_id, str(e))}
    finally:
        device_service.shutdown_service()
    out = {"names": [ds.reads.name(i) for i in range(int(ds.reads.count))],
           "pos": ds.reads.pos.tolist(),
           "skipped": ds.counters.skipped_blocks,
           "quarantined": ds.counters.quarantined_blocks}
    manifest = os.path.join(qdir, "MANIFEST.jsonl")
    if os.path.exists(manifest):
        with open(manifest) as f:
            entries = [json.loads(ln) for ln in f.read().splitlines()][1:]
        for e in entries:
            with open(e.pop("sidecar"), "rb") as f:
                e["sidecar_bytes"] = f.read().hex()
        out["entries"] = entries
    return out


class TestACorruptBlockUnderThePolicy:
    @pytest.mark.parametrize("policy", ["strict", "skip", "quarantine"])
    def test_the_service_reports_it_as_the_direct_route_does(
            self, tmp_path, monkeypatch, policy):
        from disq_tpu.bgzf.guesser import find_block_table
        from disq_tpu.fsw import PosixFileSystemWrapper

        path = _bam_file(tmp_path, n=90, blocksize=300)
        blocks = [b for b in find_block_table(PosixFileSystemWrapper(), path)
                  if b.usize > 0]
        victim = blocks[len(blocks) // 2]
        before = _crc_blocks()
        on = _read_corrupt(path, victim, policy, str(tmp_path / "q_on"),
                           True, monkeypatch)
        grew = _grew(before, _crc_blocks())
        off = _read_corrupt(path, victim, policy, str(tmp_path / "q_off"),
                            False, monkeypatch)
        assert grew["launch"] > 0 and grew["tail"] == 0
        for e in on.get("entries", []) + off.get("entries", []):
            e.pop("ts", None)
        assert on == off
        if policy == "strict":
            assert on["raised"][0] == victim.pos
        else:
            assert 0 < len(on["names"]) < 90
            assert (on["skipped"], on["quarantined"]) == (
                (1, 0) if policy == "skip" else (0, 1))
            assert ("entries" in on) == (policy == "quarantine")
