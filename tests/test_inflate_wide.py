"""BGZF payloads over 32,752 bytes on the device, and the records that
make them: the SIMD inflate kernel's wide launch geometry (cw 16384) in
interpret mode against zlib, the decode service's two inflate queues,
and kilobase records across blocks and split boundaries through
``BamSource``.

The interpreter pays ~2 ms a superstep at the wide geometry, so the
wide lanes share ONE launch whose slowest lane is a stored 65,505-byte
block (16.4 k supersteps, half a minute); a block of near-incompressible
bytes is what zlib stores."""

import zlib

import numpy as np
import pytest

from disq_tpu.ops.inflate_simd import (
    MAX_DEVICE_CSIZE, MAX_DEVICE_USIZE, NARROW_CSIZE, buckets_for,
    inflate_payloads_simd, last_stats,
)

RNG = np.random.default_rng(48)


def deflate(data: bytes, level: int = 6,
            strategy: int = zlib.Z_DEFAULT_STRATEGY) -> bytes:
    c = zlib.compressobj(level, zlib.DEFLATED, -15, 8, strategy)
    return c.compress(data) + c.flush()


def stored(data: bytes) -> bytes:
    """One final stored block (RFC 1951 section 3.2.4)."""
    assert len(data) <= 0xFFFF
    n = len(data)
    return bytes([1, n & 0xFF, n >> 8, ~n & 0xFF, (~n >> 8) & 0xFF]) + data


def lanes_by_cw() -> dict:
    from disq_tpu.runtime.tracing import telemetry_snapshot

    return dict(telemetry_snapshot().get("counters", {}).get(
        "device.inflate.lanes", {}))


def grew(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def uniform(n: int, hi: int) -> bytes:
    return RNG.integers(0, hi, n, dtype=np.uint8).tobytes()


# (name, raw bytes, payload).  Wide: payload over NARROW_CSIZE.
_FULL_BLOCK = uniform(65280, 256)       # what a BGZF writer's block holds
_AT_CAP = uniform(MAX_DEVICE_CSIZE - 5, 256)
_DYNAMIC = uniform(33500, 232)          # 7.86 bits a byte: dynamic codes
# 28 literals of 8-bit codes and a 6-byte match, over and over: fixed
# codes beat a stored block, and the payload is still over the cap
_FIXED = b"".join(row.tobytes() + row.tobytes()[-6:] for row in
                  RNG.integers(0, 144, (1125, 28), dtype=np.uint8))
WIDE = [
    ("near-incompressible-65280-byte-block", _FULL_BLOCK,
     deflate(_FULL_BLOCK)),
    ("stored-at-the-comp-cap", _AT_CAP, stored(_AT_CAP)),
    ("dynamic-over-the-narrow-cap", _DYNAMIC, deflate(_DYNAMIC)),
    ("fixed-over-the-narrow-cap", _FIXED, deflate(_FIXED, 6, zlib.Z_FIXED)),
]
_TEXT = b"the lane that stays at the narrow geometry " * 30
NARROW = [
    ("dynamic-narrow", _TEXT + uniform(600, 7), None),
    ("fixed-narrow", _TEXT[:500], deflate(_TEXT[:500], 6, zlib.Z_FIXED)),
    ("stored-narrow", uniform(700, 256), None),
    ("empty", b"", deflate(b"")),
]
NARROW = [(n, r, deflate(r) if p is None else p) for n, r, p in NARROW]


def test_the_cases_lie_on_both_sides_of_the_narrow_cap():
    assert NARROW_CSIZE == 32752 and MAX_DEVICE_CSIZE == 65510
    assert MAX_DEVICE_USIZE == 65536
    for name, raw, payload in WIDE:
        assert NARROW_CSIZE < len(payload) <= MAX_DEVICE_CSIZE, name
        assert len(raw) <= MAX_DEVICE_USIZE
        assert zlib.decompress(payload, -15) == raw
        assert buckets_for([payload], len(raw))[0] == 16384, name
    for name, raw, payload in NARROW:
        assert len(payload) <= 2048, name
    # zlib stores what it cannot shrink; the others are the block types
    # their names say
    btype = [(p[0] >> 1) & 3 for _n, _r, p in WIDE]
    assert btype == [0, 0, 2, 1]
    assert 65285 <= len(WIDE[0][2]) <= 65285 + 5 * 8    # a few stored blocks
    assert len(WIDE[1][2]) == MAX_DEVICE_CSIZE
    assert buckets_for([p for _n, _r, p in WIDE], 65536) == (16384, 16384)


@pytest.fixture(scope="module")
def served():
    """Every case through ONE submission to the decode service: what
    came back, how ``last_stats`` and the lanes' counter moved."""
    from disq_tpu.runtime.device_service import DeviceDecodeService
    from disq_tpu.runtime.tracing import spans

    cases = WIDE[:2] + NARROW[:2] + WIDE[2:] + NARROW[2:]
    stats, lanes, n_spans = dict(last_stats), lanes_by_cw(), len(spans())
    svc = DeviceDecodeService(flush_timeout_s=0.05, interpret=True)
    try:
        sub = svc.submit_inflate(
            [p for _n, _r, p in cases], [len(r) for _n, r, _p in cases],
            crcs=[zlib.crc32(r) for _n, r, _p in cases])
        blob, offs = sub.result(600)
    finally:
        svc.close()
    waits = [s["labels"]["lanes"] for s in spans()[n_spans:]
             if s["name"] == "device.launch.wait"]
    return {
        "cases": cases,
        "out": [blob[offs[i]: offs[i + 1]].tobytes()
                for i in range(len(cases))],
        "stats": {k: last_stats[k] - stats[k] for k in stats},
        "lanes": grew(lanes, lanes_by_cw()), "launch_lanes": waits}


@pytest.mark.parametrize("i", range(len(WIDE) + len(NARROW)))
def test_every_lane_equals_zlib(served, i):
    name, raw, payload = served["cases"][i]
    assert served["out"][i] == raw, name


def test_no_block_went_to_the_host(served):
    n = len(served["cases"])
    assert served["stats"] == {"device_lanes": n, "host_big": 0,
                               "host_fallback": 0}


def test_the_wide_lanes_launched_apart_at_cw_16384(served):
    assert served["lanes"]["cw=16384"] == len(WIDE)
    narrow = {k: v for k, v in served["lanes"].items() if k != "cw=16384"}
    assert sum(narrow.values()) == len(NARROW)
    assert all(int(k.split("=")[1]) <= 8192 for k in narrow)
    assert sorted(served["launch_lanes"]) == sorted(
        [len(WIDE), len(NARROW)])


def test_the_direct_loop_takes_a_wide_payload_too():
    """One geometry a call there: the widest payload's."""
    raw = uniform(NARROW_CSIZE + 3, 256)
    small = _TEXT[:900]
    payloads = [stored(raw), deflate(small)]
    assert len(payloads[0]) == NARROW_CSIZE + 8
    stats, lanes = dict(last_stats), lanes_by_cw()
    out = inflate_payloads_simd(payloads, usizes=[len(raw), len(small)],
                                interpret=True)
    assert out == [raw, small]
    assert {k: last_stats[k] - stats[k] for k in stats} == {
        "device_lanes": 2, "host_big": 0, "host_fallback": 0}
    assert grew(lanes, lanes_by_cw()) == {"cw=16384": 2}


def test_what_is_no_bgzf_block_keeps_the_host_route_and_its_counter():
    from disq_tpu.runtime.device_service import DeviceDecodeService
    from disq_tpu.runtime.tracing import REGISTRY

    long = b"decodes to more than the kernel's output " * 2000
    big = uniform(MAX_DEVICE_CSIZE + 90, 256)
    cases = [(long, deflate(long)), (big, deflate(big)),
             (_TEXT, deflate(_TEXT))]
    assert len(long) > MAX_DEVICE_USIZE and len(cases[0][1]) < 1024
    assert len(cases[1][1]) > MAX_DEVICE_CSIZE
    oversize = REGISTRY.counter("device.host_fallback_blocks")
    before, stats = oversize.total(), dict(last_stats)
    svc = DeviceDecodeService(flush_timeout_s=0.05, interpret=True)
    try:
        blob, offs = svc.submit_inflate(
            [p for _r, p in cases], [len(r) for r, _p in cases]).result(300)
    finally:
        svc.close()
    assert blob.tobytes() == b"".join(r for r, _p in cases)
    assert {k: last_stats[k] - stats[k] for k in stats} == {
        "device_lanes": 1, "host_big": 2, "host_fallback": 0}
    assert oversize.total() - before == 2


# -- kilobase records across blocks and split boundaries -------------------


def _long_records(seed=5):
    """Records a long-read aligner writes, by the sequential oracle:
    kilobase reads with an indel every 17 bases, a secondary one with a
    1,000-op CIGAR and no SEQ, an unmapped one."""
    from bam_oracle import ORecord, ref_span, reg2bin

    rng = np.random.default_rng(seed)
    recs = []
    for i, length in enumerate((2500, 900, 4100, 0, 3300, 1500, 2600, 700)):
        runs = max(1, (length or 8500) // 17)
        cigar = []
        for k in range(runs):
            cigar += [(15, "M"), (1, "I") if k % 5 < 2 else (2, "D")]
        query = sum(n for n, op in cigar if op in "MI")
        tail = (length or query) - query
        if tail > 0:
            cigar.append((tail, "M"))
        seq = "".join(rng.choice(list("ACGT"), length)) if length else ""
        rec = ORecord(
            name=f"0a1b2c3d-{i:04d}-4e5f-8a9b-0c1d2e3f4a5b", refid=i % 3,
            pos=100 + 37 * i, mapq=60 if length else 0,
            flag=0 if length else 0x100, cigar=cigar, seq=seq,
            qual=bytes(rng.integers(2, 36, length, dtype=np.uint8).tolist()),
            tags=b"NMI" + int(runs).to_bytes(4, "little") + b"tpAP")
        rec.bin = reg2bin(rec.pos, rec.pos + ref_span(rec))
        recs.append(rec)
    assert len(recs[3].cigar) >= 1000 and recs[3].seq == ""
    recs.append(ORecord(name="unmapped-read", refid=-1, pos=-1, flag=4,
                        seq="ACGTACGTAC", qual=b"\x07" * 10, bin=4680))
    return recs


def _same(ds, recs):
    from bam_oracle import CIG

    got = ds.reads
    assert ds.count() == len(recs)
    for i, r in enumerate(recs):
        assert (got.refid[i], got.pos[i], got.flag[i], got.mapq[i]) == (
            r.refid, r.pos, r.flag, r.mapq), i
        lo, hi = got.cigar_offsets[i: i + 2]
        assert [(int(w) >> 4, CIG[int(w) & 0xF])
                for w in got.cigars[lo:hi]] == list(r.cigar), i
        lo, hi = got.seq_offsets[i: i + 2]
        assert "".join("=ACMGRSVTWYHKDBN"[c] for c in got.seqs[lo:hi]) \
            == r.seq, i
        assert got.quals[lo:hi].tobytes() == (r.qual or b""), i
        lo, hi = got.name_offsets[i: i + 2]
        assert got.names[lo:hi].tobytes().decode() == r.name, i


@pytest.mark.parametrize("split_size", [1 << 20, 4000, 2500, 1111])
def test_records_across_blocks_and_split_boundaries_read_back_whole(
        tmp_path, split_size):
    """1,000-byte blocks: a 4,100-base record spans seven of them, and a
    split size under a record's compressed size puts whole splits
    inside one record."""
    from bam_oracle import DEFAULT_REFS, make_bam_bytes

    from disq_tpu import ReadsStorage

    recs = _long_records()
    path = tmp_path / "long.bam"
    path.write_bytes(make_bam_bytes(DEFAULT_REFS, recs, blocksize=1000))
    ds = (ReadsStorage.make_default().executor_workers(2)
          .split_size(split_size).read(str(path)))
    _same(ds, recs)


def test_a_split_boundary_inside_a_record_longer_than_the_search_window(
        tmp_path):
    """A 400,000-base record is ~370 KB of BGZF: the split-start search
    that begins inside it grows its 256 KiB window until it holds a
    record start, says how far in ``bam.split.guess``, and every record
    is read once."""
    from bam_oracle import DEFAULT_REFS, ORecord, make_bam_bytes, reg2bin

    from disq_tpu import ReadsStorage
    from disq_tpu.runtime.tracing import spans

    rng = np.random.default_rng(9)
    n = 400_000
    big = ORecord(
        name="one-very-long-read", refid=0, pos=500, mapq=60, flag=0,
        cigar=[(n, "M")],
        seq=rng.choice(np.frombuffer(b"ACGT", "S1"), n).tobytes().decode(),
        qual=rng.integers(2, 36, n, dtype=np.uint8).tobytes(),
        bin=reg2bin(500, 500 + n))
    recs = _long_records(6)[:3] + [big] + _long_records(7)
    refs = [("chr1", 1_000_000)] + DEFAULT_REFS[1:]
    path = tmp_path / "huge.bam"
    path.write_bytes(make_bam_bytes(refs, recs))
    assert path.stat().st_size > 350_000
    since = len(spans())
    ds = (ReadsStorage.make_default().executor_workers(2)
          .split_size(50_000).read(str(path)))
    _same(ds, recs)
    guesses = [s["labels"] for s in spans()[since:]
               if s["name"] == "bam.split.guess"]
    assert [g["shard"] for g in guesses] == list(range(1, len(guesses) + 1))
    assert len(guesses) >= 6
    assert max(g["window_bytes"] for g in guesses) > 4 * 0x10000
    assert min(g["window_bytes"] for g in guesses) == 4 * 0x10000
