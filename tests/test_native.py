"""Native C++ host-runtime tests: byte-identity with the Python codec
paths and differential correctness. Skipped cleanly when no toolchain."""

import numpy as np
import pytest

native = pytest.importorskip("disq_tpu.native")

from disq_tpu.bgzf.block import parse_block_header
from disq_tpu.bgzf.codec import CANONICAL_LEVEL, deflate_block

from tests.bam_oracle import DEFAULT_REFS, encode_record, synth_records


class TestScan:
    def test_matches_python(self, monkeypatch):
        from disq_tpu.bam.codec import scan_record_offsets

        blob = b"".join(encode_record(r) for r in synth_records(300, seed=2))
        got = native.scan_bam_offsets_native(np.frombuffer(blob, np.uint8))
        assert got[0] == 0 and got[-1] == len(blob)
        assert len(got) == 301
        # The pure-Python fallback must agree: block the native import so
        # scan_record_offsets takes the loop path.
        import sys

        monkeypatch.setitem(sys.modules, "disq_tpu.native", None)
        offs2 = scan_record_offsets(blob)
        np.testing.assert_array_equal(got, offs2)

    def test_corrupt(self):
        with pytest.raises(ValueError, match="corrupt"):
            native.scan_bam_offsets_native(np.zeros(10, np.uint8))

    def test_short_record_bounds_checked(self):
        # Caller-supplied offsets with a record shorter than the 36-byte
        # prefix must error, not read out of bounds.
        with pytest.raises(ValueError):
            native.decode_records_native(
                np.zeros(20, np.uint8), np.array([0, 20], np.int64)
            )

    def test_base_shift(self):
        blob = b"".join(encode_record(r) for r in synth_records(5, with_edge_cases=False))
        got = native.scan_bam_offsets_native(np.frombuffer(blob, np.uint8), base=100)
        assert got[0] == 100 and got[-1] == 100 + len(blob)


class TestDeflateInflate:
    def test_deflate_byte_identical_to_python_pin(self):
        rng = np.random.default_rng(0)
        payload = (b"readdata" * 5000 + rng.integers(0, 256, 5000, np.uint8).tobytes())
        pay_off = np.array([0, 30000, len(payload)], dtype=np.int64)
        rows, sizes = native.deflate_blocks_native(payload, pay_off, CANONICAL_LEVEL)
        for i, (s, e) in enumerate(zip(pay_off[:-1], pay_off[1:])):
            expect = deflate_block(payload[int(s):int(e)])
            got = rows[i, : sizes[i]].tobytes()
            assert got == expect, f"block {i} differs from Python pin"

    def test_inflate_roundtrip(self):
        rng = np.random.default_rng(1)
        payload = rng.integers(65, 91, 200_000, np.uint8).tobytes()
        from disq_tpu.bgzf.codec import compress_to_bgzf, inflate_blocks
        from disq_tpu.bgzf.guesser import find_block_table
        from disq_tpu.fsw import MemoryFileSystemWrapper

        comp = compress_to_bgzf(payload)
        fs = MemoryFileSystemWrapper()
        fs.write_all("x", comp)
        blocks = find_block_table(fs, "x")
        out = inflate_blocks(comp, blocks)
        assert out == payload

    def test_inflate_crc_detection(self):
        from disq_tpu.bgzf.codec import compress_to_bgzf, inflate_blocks
        from disq_tpu.bgzf.guesser import find_block_table
        from disq_tpu.fsw import MemoryFileSystemWrapper

        comp = bytearray(compress_to_bgzf(b"a" * 100_000))
        fs = MemoryFileSystemWrapper()
        fs.write_all("x", bytes(comp))
        blocks = find_block_table(fs, "x")
        # corrupt a payload byte of the second block
        comp[blocks[1].pos + 20] ^= 0xFF
        with pytest.raises(ValueError):
            inflate_blocks(bytes(comp), blocks)


class TestCrc32CheckNative:
    def _blob(self):
        import zlib

        rng = np.random.default_rng(4)
        sizes = [0, 1, 65280, 7, 4096, 0, 333]
        offsets = np.zeros(len(sizes) + 1, np.int64)
        np.cumsum(sizes, out=offsets[1:])
        blob = rng.integers(0, 256, int(offsets[-1]), np.uint8)
        expect = np.array(
            [zlib.crc32(blob[offsets[i]: offsets[i + 1]])
             for i in range(len(sizes))], np.uint32)
        return blob, offsets, expect

    def test_sound_blocks_in_any_order_of_indices(self):
        """Every block against zlib's CRC32 of the same bytes (empty
        blocks too), whichever blocks are asked for and in whichever
        order: -1."""
        from disq_tpu.native import crc32_check_native

        blob, offsets, expect = self._blob()
        for idx in ([0, 1, 2, 3, 4, 5, 6], [6, 2, 0], [], [5]):
            assert crc32_check_native(blob, offsets, idx, expect) == -1

    @pytest.mark.parametrize("wrong", [[2], [0, 4], [6]])
    def test_names_the_position_of_the_first_wrong_block(self, wrong):
        """The answer is a position in ``idx``, not a block number; a
        wrong block that is not asked for is not seen."""
        from disq_tpu.native import crc32_check_native

        blob, offsets, expect = self._blob()
        expect[wrong] ^= 0x80000000
        idx = [6, 5, 4, 3, 2, 1, 0]
        assert crc32_check_native(blob, offsets, idx, expect) == min(
            idx.index(w) for w in wrong)
        rest = [i for i in idx if i not in wrong]
        assert crc32_check_native(blob, offsets, rest, expect) == -1
        # a view into a larger buffer (the service's padded base)
        base = np.concatenate([blob, np.zeros(64, np.uint8)])
        assert crc32_check_native(
            base[: len(blob)], offsets, rest, expect) == -1


class TestSegmentGatherNative:
    def test_matches_numpy_reference(self):
        segment_gather_native = native.segment_gather_native

        rng = np.random.default_rng(0)
        for t in range(30):
            n = int(rng.integers(0, 200))
            lens = rng.integers(0, 12, n)
            off = np.zeros(n + 1, np.int64)
            np.cumsum(lens, out=off[1:])
            for dt in (np.uint8, np.uint32):
                flat = rng.integers(0, 250, int(off[-1])).astype(dt)
                idx = (rng.permutation(n)[: int(rng.integers(0, n + 1))]
                       if n else np.zeros(0, np.int64))
                got_f, got_o = segment_gather_native(flat, off, idx)
                # independent numpy reference (the pure fallback path)
                l2 = np.diff(off)[idx]
                ref_o = np.zeros(len(idx) + 1, np.int64)
                np.cumsum(l2, out=ref_o[1:])
                if int(ref_o[-1]):
                    seg = np.repeat(np.arange(len(idx)), l2)
                    within = (np.arange(int(ref_o[-1]), dtype=np.int64)
                              - ref_o[seg])
                    ref_f = flat[off[idx][seg] + within]
                else:
                    ref_f = flat[:0].copy()
                assert got_f.dtype == flat.dtype
                assert np.array_equal(got_f, ref_f), t
                assert np.array_equal(got_o, ref_o), t

    def test_negative_and_out_of_range_indices(self):
        segment_gather_native = native.segment_gather_native

        off = np.array([0, 2, 5, 9], np.int64)
        flat = np.arange(9, dtype=np.uint8)
        got_f, got_o = segment_gather_native(flat, off, np.array([-1, 0]))
        assert got_f.tolist() == [5, 6, 7, 8, 0, 1]
        assert got_o.tolist() == [0, 4, 6]
        with pytest.raises(IndexError):
            segment_gather_native(flat, off, np.array([3]))
        with pytest.raises(IndexError):
            segment_gather_native(flat, off, np.array([-4]))

    def test_malformed_offsets_rejected(self):
        """ADVICE r5 #1: a non-monotone offsets table used to compute a
        negative segment length that cast to a huge size_t memcpy; an
        offsets[-1] past the flat buffer read beyond it. Both must fail
        validation BEFORE any copy."""
        segment_gather_native = native.segment_gather_native

        flat = np.arange(9, dtype=np.uint8)
        with pytest.raises(ValueError, match="monotone"):
            segment_gather_native(
                flat, np.array([0, 5, 2, 9], np.int64), np.array([1]))
        with pytest.raises(ValueError, match="exceeds"):
            segment_gather_native(
                flat, np.array([0, 2, 5, 50], np.int64), np.array([2]))
        with pytest.raises(ValueError, match="non-negative"):
            segment_gather_native(
                flat, np.array([-3, 2, 5, 9], np.int64), np.array([0]))
        # a valid table still round-trips
        got_f, _ = segment_gather_native(
            flat, np.array([0, 2, 5, 9], np.int64), np.array([1]))
        assert got_f.tolist() == [2, 3, 4]


def test_build_stamp_matches_source_and_names_variant():
    """Staleness is keyed on the source hash baked into the library (a
    copied tree keeps no mtimes), and the build says which inflate it
    linked — the zlib-only retry must never pass for libdeflate."""
    assert native.build_variant() in ("libdeflate", "zlib")
    assert native._so_build_info() == (
        native._src_hash(), native.build_variant())
