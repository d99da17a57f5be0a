"""128-lane SIMD inflate kernel vs zlib (byte equality).

Milestone ladder: (a) fixed-Huffman +
stored blocks, (b) dynamic-Huffman table build. The oracle is zlib
itself: every payload here is produced by ``zlib.compressobj`` with a
controlled strategy/level and must round-trip byte-identically.

Reference behavior: htsjdk BlockCompressedInputStream + zlib Inflater
(SURVEY.md §2.8 row 1).
"""

import os
import zlib

import numpy as np
import pytest

from disq_tpu.ops.inflate_simd import inflate_payloads_simd


def deflate(data: bytes, level: int = 6, strategy: int = zlib.Z_DEFAULT_STRATEGY) -> bytes:
    c = zlib.compressobj(level, zlib.DEFLATED, -15, 8, strategy)
    return c.compress(data) + c.flush()


def deflate_fixed(data: bytes, level: int = 6) -> bytes:
    return deflate(data, level, zlib.Z_FIXED)


def deflate_stored(data: bytes) -> bytes:
    return deflate(data, 0)


def check(payloads, raws):
    got = inflate_payloads_simd(payloads, usizes=[len(r) for r in raws],
                                interpret=True)
    for i, (g, r) in enumerate(zip(got, raws)):
        assert g == r, (
            f"lane {i}: {len(g)} vs {len(r)} bytes; "
            f"first diff at {next((j for j in range(min(len(g), len(r))) if g[j] != r[j]), 'len')}"
        )


RNG = np.random.default_rng(42)


def text_like(n: int) -> bytes:
    # repetitive, LZ77-friendly
    words = [b"the", b"quick", b"brown", b"fox", b"jumps", b"!", b"\n"]
    out = b" ".join(words[i % 7] for i in RNG.integers(0, 7, max(1, n // 4)))
    return out[:n] if len(out) >= n else out + b"x" * (n - len(out))


def random_bytes(n: int) -> bytes:
    return RNG.integers(0, 256, n, dtype=np.uint8).tobytes()


class TestFixedHuffman:
    def test_single_literal_stream(self):
        raw = b"hello, bgzf world"
        check([deflate_fixed(raw)], [raw])

    def test_empty_stream(self):
        # the BGZF EOF block's payload is exactly this shape
        check([deflate_fixed(b"")], [b""])

    def test_matches_and_overlaps(self):
        raws = [
            b"abcabcabcabcabcabcabcabc",        # dist 3 overlapping copies
            b"a" * 300,                          # dist 1, len 258 chains
            b"xyxyxyxyxyxyxyxyxyxyxyxyxy" * 4,   # dist 2
            text_like(900),
        ]
        check([deflate_fixed(r) for r in raws], raws)

    def test_lane_mix_and_lengths(self):
        raws = [text_like(1 + 37 * i) for i in range(20)] + [b"", b"Z"]
        check([deflate_fixed(r) for r in raws], raws)

    def test_all_258_len_match(self):
        raw = b"Q" * (258 * 4 + 3)
        check([deflate_fixed(raw)], [raw])


class TestStored:
    def test_incompressible(self):
        raws = [random_bytes(n) for n in (1, 7, 63, 500, 1200)]
        check([deflate_stored(r) for r in raws], raws)

    def test_empty(self):
        check([deflate_stored(b"")], [b""])

    def test_multi_stored_blocks(self):
        # stored blocks cap at 65535; force several via flushes
        c = zlib.compressobj(0, zlib.DEFLATED, -15)
        raw = random_bytes(600)
        payload = (c.compress(raw[:200]) + c.flush(zlib.Z_FULL_FLUSH)
                   + c.compress(raw[200:]) + c.flush())
        check([payload], [raw])


class TestMixedLanes:
    def test_fixed_and_stored_lanes_together(self):
        raws, payloads = [], []
        for i in range(40):
            if i % 3 == 0:
                r = random_bytes(1 + 13 * i)
                payloads.append(deflate_stored(r))
            else:
                r = text_like(1 + 29 * i)
                payloads.append(deflate_fixed(r))
            raws.append(r)
        check(payloads, raws)

    def test_more_than_128_lanes(self):
        raws = [text_like(50 + i) for i in range(150)]
        check([deflate_fixed(r) for r in raws], raws)

    def test_isize_mismatch_raises(self):
        # wrong expected size must raise (error 8), not silently return
        # host-inflated bytes — bam/source.py slices by cumulative usize
        payload = deflate_fixed(b"abcdefgh")
        with pytest.raises(ValueError, match="error 8"):
            inflate_payloads_simd([payload], usizes=[9999], interpret=True)

    def test_truncated_lane_falls_back_to_host(self):
        # A structurally broken stream must error in-kernel (overrun /
        # bad code), and the host zlib fallback then raises. Bit-flips
        # that decode to plausible garbage are the CRC layer's job
        # (bgzf.codec verifies CRC32 on host).
        good = text_like(400)
        payload = deflate_fixed(good)
        bad = payload[: len(payload) // 2]
        with pytest.raises(ValueError, match="corrupt DEFLATE"):
            inflate_payloads_simd(
                [payload, bad], usizes=[len(good), len(good)],
                interpret=True)


class TestDynamicHuffman:
    def test_default_level(self):
        raws = [text_like(n) for n in (64, 300, 1000, 2000)]
        check([deflate(r) for r in raws], raws)

    def test_level9_and_repeats(self):
        # long runs exercise CL codes 16/17/18 in the length tables
        raws = [
            b"\x00" * 800 + text_like(200),
            bytes(range(256)) * 6,
            text_like(1500),
        ]
        check([deflate(r, 9) for r in raws], raws)

    # Slow tier (~70s: a 16.5K-byte window in interpret mode); the
    # other dynamic-Huffman legs keep the code-path tier-1.
    @pytest.mark.slow
    def test_far_distance_28bit_path(self):
        # A match at distance ~16.5K uses dist symbol 29 (13 extra
        # bits); used once, it gets a long Huffman code, so code+extra
        # can exceed the 25-bit refill floor — the DIST phase must
        # consume the code and refill before reading the extra bits.
        rng = np.random.default_rng(3)
        head = rng.integers(0, 256, 16500, dtype=np.uint8).tobytes()
        raw = head + head[:300] + text_like(600)
        check([deflate(raw, 9)], [raw])

    def test_multi_block_full_flush(self):
        c = zlib.compressobj(6, zlib.DEFLATED, -15)
        raw = text_like(1200)
        payload = (c.compress(raw[:500]) + c.flush(zlib.Z_FULL_FLUSH)
                   + c.compress(raw[500:]) + c.flush())
        check([payload], [raw])

    def test_filtered_strategy(self):
        data = (np.arange(1200, dtype=np.uint8) % 250).tobytes()
        check([deflate(data, 6, zlib.Z_FILTERED)], [data])

    def test_dynamic_across_128_lanes(self):
        raws = [text_like(100 + 11 * i) for i in range(130)]
        check([deflate(r) for r in raws], raws)


class TestEndToEnd:
    def test_bam_read_via_simd_inflate(self, tmp_path, monkeypatch):
        """Full ReadsStorage.read with DISQ_TPU_DEVICE_INFLATE=1: the
        SIMD kernel decodes every BGZF block on the read path. Small
        blocksize keeps interpret-mode superstep counts CPU-feasible;
        production 64 KiB shapes run in the TPU CI lane."""
        from bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records
        from disq_tpu.api import ReadsStorage

        recs = synth_records(400, seed=8)
        src = tmp_path / "in.bam"
        src.write_bytes(make_bam_bytes(DEFAULT_REFS, recs, blocksize=2000))
        host = ReadsStorage.make_default().read(str(src))
        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        dev = ReadsStorage.make_default().read(str(src))
        assert dev.count() == host.count() == 400
        np.testing.assert_array_equal(dev.reads.pos, host.reads.pos)
        np.testing.assert_array_equal(dev.reads.seqs, host.reads.seqs)
        np.testing.assert_array_equal(dev.reads.quals, host.reads.quals)

    def test_simd_crc_mismatch_detected(self, monkeypatch):
        from bam_oracle import DEFAULT_REFS, make_bam_bytes, synth_records
        from disq_tpu.bgzf.codec import inflate_blocks_device
        from disq_tpu.bgzf.guesser import find_block_table
        from disq_tpu.fsw import MemoryFileSystemWrapper

        monkeypatch.setenv("DISQ_TPU_DEVICE_INFLATE", "1")
        data = bytearray(
            make_bam_bytes(DEFAULT_REFS, synth_records(60, seed=9),
                           blocksize=2000))
        fs = MemoryFileSystemWrapper()
        fs.write_all("mem://x.bam", bytes(data))
        blocks = [b for b in find_block_table(fs, "mem://x.bam")
                  if b.usize > 0]
        data[blocks[0].pos + blocks[0].csize - 8] ^= 0xFF
        with pytest.raises(ValueError, match="CRC mismatch"):
            inflate_blocks_device(bytes(data), blocks)


class TestCopyWidthBoundaries:
    def test_every_match_distance_1_to_24(self):
        # periodic data with period d makes zlib emit distance-d copies,
        # sweeping the 4-byte / 8-byte (d >= 8) / 16-byte (d >= 16)
        # emit-width eligibility boundaries and the d < 4 modular
        # replication, at every alignment the partial first steps create
        raws, payloads = [], []
        for d in range(1, 25):
            unit = bytes((7 * i + d) % 251 for i in range(d))
            raw = (unit * (3000 // d + 2))[:3000]
            raws.append(raw)
            payloads.append(deflate(raw))
        check(payloads, raws)

    def test_copy_tails_5_to_16_bytes(self):
        # matches whose final step emits 5..16 bytes: literal prefix
        # breaks alignment, then a long match ends mid-word
        raws, payloads = [], []
        for pre in range(1, 5):
            for tail in range(5, 17):
                unit = bytes((3 * i + pre) % 256 for i in range(32))
                raw = bytes(range(pre)) + (unit * 8)[: 32 * 4 + tail]
                raws.append(raw)
                payloads.append(deflate(raw, 9))
        check(payloads, raws)
