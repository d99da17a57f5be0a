"""The device rANS-4x8 order-0 contract (stream in, the host codec's
bytes out, or ``ValueError``) against the SIMD kernel in interpret
mode, on inputs ``test_rans_simd_kernel.py`` does not have.

Oracle: the host codec (native C / pure Python, themselves
cross-validated against each other and an independent order-1 encoder
in test_cram.py).
"""

import struct

import numpy as np
import pytest

from disq_tpu.cram.rans import rans_decode, rans_encode_order0
from disq_tpu.ops.rans_simd import rans0_decode_simd


def _markov(n, seed, alpha=29):
    rng = np.random.default_rng(seed)
    steps = rng.integers(0, 5, n)
    return ((np.cumsum(steps) % alpha).astype(np.uint8)).tobytes()


def roundtrip(raws):
    streams = [rans_encode_order0(r) for r in raws]
    assert rans0_decode_simd(streams, interpret=True) == list(raws)
    return streams


class TestRans0Kernel:
    def test_batch_matches_host(self):
        # the whole byte alphabet, flat and geometric
        rng = np.random.default_rng(0)
        roundtrip([rng.integers(0, 256, 9000, dtype=np.uint8).tobytes(),
                   np.minimum(rng.geometric(0.08, 12_000) - 1, 255)
                   .astype(np.uint8).tobytes(),
                   bytes(range(256)) * 3])

    def test_single_byte_and_tiny(self):
        # every length 1..9: each residue of the 4-way interleave
        roundtrip([bytes(range(65, 65 + n)) for n in range(1, 10)])

    def test_empty_stream(self):
        # nothing but empties: no lane is live, nothing is launched
        roundtrip([b""] * 3)

    def test_single_symbol_alphabet(self):
        # one rare symbol in a run of another: frequencies 4095 and 1
        roundtrip([b"\x41" * 6000 + b"\x42" + b"\x41" * 3000])

    def test_mixed_sizes_in_one_batch(self):
        # lengths either side of a multiple of four, one lane each
        roundtrip([_markov(n, n) for n in (4093, 4094, 4095, 4096, 4097)])

    def test_order1_rejected(self):
        # among order-0 streams: the error names the stream's index
        good = rans_encode_order0(b"abcabc")
        with pytest.raises(ValueError, match="stream 2: .*order-0 only"):
            rans0_decode_simd([good, good, b"\x01" + good[1:]],
                              interpret=True)

    def test_truncated_renorm_detected(self):
        # a lane out of renorm bytes between healthy ones does what the
        # host codec does on it (native raises; pure Python returns other
        # bytes), and the call after the abandoned launch is none the worse
        raws = [_markov(3000, 1), _markov(5000, 3), _markov(3000, 2)]
        streams = roundtrip(raws)
        comp_size = struct.unpack_from("<I", streams[1], 1)[0] - 40
        cut = (streams[1][:1] + struct.pack("<I", comp_size)
               + streams[1][5: 9 + comp_size])
        batch = [streams[0], cut, streams[2]]
        try:
            want = rans_decode(cut)
        except ValueError:
            with pytest.raises(ValueError):
                rans0_decode_simd(batch, interpret=True)
        else:
            assert want != raws[1] and rans0_decode_simd(
                batch, interpret=True) == [raws[0], want, raws[2]]
        roundtrip(raws)

    def test_env_flag_routes_decode(self, monkeypatch):
        # =1 books a launch of the SIMD kernel; unset, the host decodes
        from disq_tpu.runtime.tracing import REGISTRY

        def launches():
            return REGISTRY.counter("device.kernel_launches").value(
                kernel="rans_simd")

        raw = _markov(4000, 4)
        enc = rans_encode_order0(raw)
        monkeypatch.delenv("DISQ_TPU_DEVICE_RANS", raising=False)
        before = launches()
        assert rans_decode(enc) == raw and launches() == before
        monkeypatch.setenv("DISQ_TPU_DEVICE_RANS", "1")
        assert rans_decode(enc) == raw and launches() == before + 1

    def test_empty_before_corrupt_reports_original_index(self):
        # an empty stream takes no lane: the error still counts it
        from disq_tpu.cram.rans import _read_freq_table0

        enc = bytearray(rans_encode_order0(_markov(5000, 7)))
        _, off = _read_freq_table0(memoryview(enc)[9:], 0)
        struct.pack_into("<I", enc, 9 + off, 0xFFFFFFFF)
        with pytest.raises(ValueError, match="stream 1: .*state word"):
            rans0_decode_simd([rans_encode_order0(b""), bytes(enc)],
                              interpret=True)


class TestNativePythonByteIdentity:
    """The native C++ encoder must emit byte-identical streams to the
    pure-Python codec (the stable-sort normalize contract)."""

    def test_encode_bytes_identical(self):
        pytest.importorskip("disq_tpu.native")
        import disq_tpu.native as N
        from disq_tpu.cram import rans as R

        if not hasattr(N, "rans_encode0_native"):
            pytest.skip("native lib too old")
        rng = np.random.default_rng(21)
        real = N.rans_encode0_native
        for _ in range(6):
            n = int(rng.integers(1, 100_000))
            a = int(rng.integers(2, 200))
            raw = rng.integers(0, a, n, dtype=np.uint8).tobytes()
            native = N.rans_encode0_native(raw)
            del N.rans_encode0_native  # force the pure-Python body
            try:
                py = R.rans_encode_order0(raw)
            finally:
                N.rans_encode0_native = real
            assert native == py
