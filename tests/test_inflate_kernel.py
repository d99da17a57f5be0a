"""The device inflate contract (payload in, zlib's bytes out, or
``ValueError``) against the SIMD kernel's direct loop in interpret mode.
The shape is the case, not the size: full size is ``ops/tpu_ci``'s."""

import zlib

import numpy as np
import pytest

from disq_tpu.ops.inflate_simd import (
    MAX_DEVICE_CSIZE,
    MAX_DEVICE_USIZE,
    inflate_payloads_simd,
    last_stats,
)


def raw_deflate(data: bytes, level: int = 6,
                strategy: int = zlib.Z_DEFAULT_STRATEGY) -> bytes:
    c = zlib.compressobj(level, zlib.DEFLATED, -15, 8, strategy)
    return c.compress(data) + c.flush()


def roundtrip(datas, payloads=None, level=6):
    """One launch: every lane equal to its input."""
    if payloads is None:
        payloads = [raw_deflate(d, level) for d in datas]
    out = inflate_payloads_simd(
        payloads, usizes=[len(d) for d in datas], interpret=True
    )
    assert out == list(datas)


def test_simple_text():
    roundtrip([b"hello hello hello world, here is a deflate stream"])


def test_single_byte():
    roundtrip([b"x"])


def test_every_level_1_to_9():
    # a writer's level: chain length, lazy matching, deflate_fast at 1-3
    data = (b"@read/1 ACGTTGCAAGGCTTAACCGGTTA + IIIIHHHHGGGGFFFF\n" * 24
            + bytes(range(64)))
    roundtrip([data] * 9, [raw_deflate(data, lv) for lv in range(1, 10)])


def test_stored_blocks_level0():
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, 1500, dtype=np.uint8).tobytes()
    roundtrip([data], level=0)     # incompressible + level 0 → stored


def test_random_bytes_all_levels():
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, 1500, dtype=np.uint8).tobytes()
    roundtrip([data] * 3, [raw_deflate(data, lv) for lv in (1, 6, 9)])


def test_overlapping_matches():
    # dist=1 run-length copies and short periodic patterns
    roundtrip([b"a" * 2000, b"ab" * 1000, b"abc" * 680])


def test_compressible_structured():
    rng = np.random.default_rng(2)
    # low-entropy bytes → dynamic Huffman with skewed code lengths, and
    # an encoder's other strategies: distance-1 matches only, none at all
    data = rng.choice([65, 67, 71, 84], size=2000,
                      p=[0.7, 0.1, 0.1, 0.1]).astype(np.uint8).tobytes()
    strategies = (zlib.Z_DEFAULT_STRATEGY, zlib.Z_RLE, zlib.Z_HUFFMAN_ONLY)
    roundtrip([data] * 3, [raw_deflate(data, 6, s) for s in strategies])


def test_a_stream_over_the_comp_cap_or_the_output_goes_to_host():
    # what is no BGZF block goes to the host alone: a payload past the
    # kernel's comp cap (BGZF's largest), a raw size past its output
    rng = np.random.default_rng(3)
    big = rng.integers(0, 256, MAX_DEVICE_CSIZE + 4096,
                       dtype=np.uint8).tobytes()
    long = b"a raw size over the output buffer " * 2000
    small = b"the lane that stays on the device " * 40
    payloads = [raw_deflate(big, 9), raw_deflate(long), raw_deflate(small)]
    assert len(payloads[0]) > MAX_DEVICE_CSIZE >= len(payloads[2])
    assert len(long) > MAX_DEVICE_USIZE and len(payloads[1]) < 1024
    before = dict(last_stats)
    roundtrip([big, long, small], payloads)
    delta = {k: last_stats[k] - before[k] for k in before}
    assert delta == {"device_lanes": 1, "host_big": 2, "host_fallback": 0}


def test_batch_of_mixed_blocks():
    rng = np.random.default_rng(4)
    datas = [
        b"",
        b"q",
        b"the quick brown fox " * 60,
        rng.integers(0, 256, 1500, dtype=np.uint8).tobytes(),
        bytes(range(256)) * 5,
        b"\x00" * 2000,
    ]
    roundtrip(datas)


def test_matches_far_distances():
    # distances past the 4 KiB history ring: the copy reads the out buffer
    rng = np.random.default_rng(5)
    chunk = rng.integers(0, 256, 200, dtype=np.uint8).tobytes()
    roundtrip([chunk + bytes(fill) + chunk for fill in (4200, 7700)],
              level=9)


def test_real_bgzf_payload():
    """Payloads exactly as the BAM source stages them."""
    from bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records
    from disq_tpu.bgzf.guesser import find_block_table
    from disq_tpu.fsw import MemoryFileSystemWrapper

    data = make_bam_bytes(DEFAULT_REFS, synth_records(150, seed=7),
                          blocksize=1500)
    fs = MemoryFileSystemWrapper()
    fs.write_all("mem://in.bam", data)
    blocks = find_block_table(fs, "mem://in.bam")
    payloads, usizes, expect = [], [], []
    for blk in blocks:
        if blk.usize == 0:
            continue
        raw = data[blk.pos: blk.pos + blk.csize]
        xlen = int.from_bytes(raw[10:12], "little")
        payloads.append(raw[12 + xlen: blk.csize - 8])
        usizes.append(blk.usize)
        expect.append(zlib.decompress(payloads[-1], -15))
    assert len(payloads) > 8
    got = inflate_payloads_simd(payloads, usizes=usizes, interpret=True)
    assert got == expect


def test_corrupt_stream_reports_error():
    raw = b"hello world, this will be corrupted " * 50
    payload = bytearray(raw_deflate(raw))
    payload[len(payload) // 2] ^= 0xFF
    with pytest.raises(ValueError, match="corrupt DEFLATE stream"):
        inflate_payloads_simd([bytes(payload)], usizes=[len(raw)],
                              interpret=True)


def test_truncated_stream_reports_error():
    raw = b"some data that will be truncated " * 60
    payload = raw_deflate(raw)
    with pytest.raises(ValueError, match="corrupt DEFLATE stream"):
        inflate_payloads_simd([payload[: len(payload) // 2]],
                              usizes=[len(raw)], interpret=True)


def test_isize_mismatch_detected():
    # the footer declares fewer bytes than the stream holds
    raw = b"a stream longer than its footer says " * 50
    with pytest.raises(ValueError, match="error 8"):
        inflate_payloads_simd([raw_deflate(raw)], usizes=[len(raw) - 5],
                              interpret=True)


def test_end_to_end_bam_read_via_device_inflate(tmp_path, monkeypatch):
    """ReadsStorage.read with DISQ_TPU_DEVICE_INFLATE=1 of the benchmark's
    own records (``wgs30x``), every column equal to the host read's."""
    from bam_oracle import make_header_bytes, o_bgzf_compress
    from disq_tpu.api import ReadsStorage
    from test_resident_decode import _assert_identical
    from test_tpu_kernels import wgs30x_config, wgs30x_record_bytes

    refs = [(c["name"], c["length"]) for c in wgs30x_config()["contigs"]]
    src = tmp_path / "in.bam"
    src.write_bytes(o_bgzf_compress(
        make_header_bytes(refs) + wgs30x_record_bytes(61, 30), 2000))
    host = ReadsStorage.make_default().read(str(src))
    monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
    dev = ReadsStorage.make_default().read(str(src))
    assert dev.count() == host.count() == 61
    _assert_identical(dev.reads, host.reads)


def test_device_inflate_crc_mismatch(monkeypatch):
    """A batch big enough for the threaded CRC check names the block."""
    from bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records
    from disq_tpu.bgzf.codec import inflate_blocks_device
    from disq_tpu.bgzf.guesser import find_block_table
    from disq_tpu.fsw import MemoryFileSystemWrapper

    monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
    data = bytearray(make_bam_bytes(DEFAULT_REFS, synth_records(400, seed=9),
                                    blocksize=2000))
    fs = MemoryFileSystemWrapper()
    fs.write_all("mem://x.bam", bytes(data))
    blocks = [b for b in find_block_table(fs, "mem://x.bam") if b.usize > 0]
    assert len(blocks) >= 32
    last = blocks[-1]
    data[last.pos + last.csize - 8] ^= 0xFF
    with pytest.raises(ValueError,
                       match=f"CRC mismatch at block {len(blocks) - 1}"):
        inflate_blocks_device(bytes(data), blocks)
