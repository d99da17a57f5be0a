"""128-lane SIMD rANS order-0 decode tests (disq_tpu/ops/rans_simd.py).

Oracle: the host codec (native C / pure Python, cross-validated against
each other and the order-1 encoder in test_cram.py). Runs in interpret
mode on the CPU mesh; the on-chip lane is ops/tpu_ci.py's
``rans_order0_simd`` rows.
"""

import struct

import numpy as np
import pytest

from disq_tpu.cram.rans import rans_decode, rans_encode_order0
from disq_tpu.ops.rans_simd import (
    MAX_DEVICE_CSIZE,
    rans0_decode_simd,
)


def _markov(n, seed, alpha=29):
    rng = np.random.default_rng(seed)
    steps = rng.integers(0, 5, n)
    return ((np.cumsum(steps) % alpha).astype(np.uint8)).tobytes()


class TestRans0Simd:
    def test_batch_matches_host(self):
        rng = np.random.default_rng(0)
        raws = []
        for _ in range(6):
            n = int(rng.integers(1, 30_000))
            a = int(rng.integers(2, 120))
            raws.append(rng.integers(0, a, n, dtype=np.uint8).tobytes())
        streams = [rans_encode_order0(r) for r in raws]
        assert rans0_decode_simd(streams, interpret=True) == raws

    def test_single_byte_and_tiny(self):
        raws = [b"\x00", b"ab", b"zzzz", bytes(range(5))]
        streams = [rans_encode_order0(r) for r in raws]
        assert rans0_decode_simd(streams, interpret=True) == raws

    def test_empty_stream(self):
        enc = rans_encode_order0(b"")
        assert rans0_decode_simd([enc], interpret=True) == [b""]

    def test_single_symbol_alphabet(self):
        raw = b"\x41" * 10_000
        enc = rans_encode_order0(raw)
        assert rans0_decode_simd([enc], interpret=True) == [raw]

    def test_mixed_sizes_and_empties_in_one_batch(self):
        raws = [b"x", _markov(999, 1), b"", _markov(20_000, 2),
                b"\x00\x01" * 7]
        streams = [rans_encode_order0(r) for r in raws]
        assert rans0_decode_simd(streams, interpret=True) == raws

    def test_batch_larger_than_lane_count(self):
        # 130 streams -> two kernel launches through the chunk window
        rng = np.random.default_rng(3)
        raws = [rng.integers(0, 50, int(rng.integers(1, 500)),
                             dtype=np.uint8).tobytes() for _ in range(130)]
        streams = [rans_encode_order0(r) for r in raws]
        assert rans0_decode_simd(streams, interpret=True) == raws

    def test_oversize_stream_falls_back_to_host(self):
        # incompressible payload: renorm bytes ~= raw size, over the cap
        rng = np.random.default_rng(4)
        big = rng.integers(0, 256, MAX_DEVICE_CSIZE + 20_000,
                           dtype=np.uint8).tobytes()
        small = _markov(100, 5)
        streams = [rans_encode_order0(r) for r in (big, small)]
        assert rans0_decode_simd(streams, interpret=True) == [big, small]

    def test_order1_rejected(self):
        enc = bytearray(rans_encode_order0(b"abcabc"))
        enc[0] = 1
        with pytest.raises(ValueError, match="order-0 only"):
            rans0_decode_simd([bytes(enc)], interpret=True)

    def test_truncated_renorm_stream_raises(self):
        # chop renorm bytes: kernel overruns clen (status 6), the host
        # re-decode then reports it the way the host path always has
        raw = _markov(4000, 6)
        enc = bytearray(rans_encode_order0(raw))
        _, comp_size, _ = struct.unpack_from("<BII", enc, 0)
        cut = bytes(enc[: 9 + comp_size - 60])
        cut = cut[:1] + struct.pack("<I", comp_size - 60) + cut[5:]
        # contract: whatever the host codec does on this stream (native
        # raises; pure Python clamps and returns garbage), the SIMD
        # path's host re-decode does the same
        try:
            want = rans_decode(cut)
        except ValueError:
            with pytest.raises(ValueError):
                rans0_decode_simd([cut], interpret=True)
        else:
            got = rans0_decode_simd([cut], interpret=True)
            assert got == [want] and want != raw

    def test_corrupt_state_rejected(self):
        raw = b"abcd" * 50
        enc = bytearray(rans_encode_order0(raw))
        # locate the 4 state words: after the 9-byte header + freq table
        from disq_tpu.cram.rans import _read_freq_table0

        _, off = _read_freq_table0(memoryview(enc)[9:], 0)
        struct.pack_into("<I", enc, 9 + off, 0xFFFFFFFF)
        with pytest.raises(ValueError, match="state word"):
            rans0_decode_simd([bytes(enc)], interpret=True)
        # below RANS_LOW: host renorm would take >2 bytes/symbol and the
        # kernel's 2-step unroll would silently diverge — must reject
        struct.pack_into("<I", enc, 9 + off, 100)
        with pytest.raises(ValueError, match="state word < 2"):
            rans0_decode_simd([bytes(enc)], interpret=True)

    def test_decode_dispatch_flag(self, monkeypatch):
        # spy on the kernel's entry point so mis-routing can't hide
        # behind the fact that the host decodes correctly too
        import disq_tpu.ops.rans_simd as simd_mod

        calls = []
        real = simd_mod.rans0_decode_simd

        def wrapper(streams, interpret=None):
            calls.append("rans0_decode_simd")
            return real(streams, interpret=interpret)

        monkeypatch.setattr(simd_mod, "rans0_decode_simd", wrapper)
        raw = _markov(2000, 7)
        monkeypatch.setenv("DISQ_TPU_DEVICE_RANS", "1")
        assert rans_decode(rans_encode_order0(raw)) == raw
        assert calls == ["rans0_decode_simd"]
