"""128-lane SIMD inflate kernel vs zlib (byte equality).

Milestone ladder: (a) fixed-Huffman +
stored blocks, (b) dynamic-Huffman table build. The oracle is zlib
itself: every payload here is produced by ``zlib.compressobj`` with a
controlled strategy/level and must round-trip byte-identically.

Reference behavior: htsjdk BlockCompressedInputStream + zlib Inflater
(SURVEY.md §2.8 row 1).
"""

import functools
import os
import zlib

import numpy as np
import pytest

from disq_tpu.ops import inflate_simd as tables
from disq_tpu.ops.inflate_simd import (
    _COMP_TILES, COMP_PERIOD, MAX_DEVICE_CSIZE, NARROW_CSIZE,
    inflate_payloads_simd,
)


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


# ---------------------------------------------------------------------------
# The fused token schedule: a match's length, its distance and its first
# copy chunk in ONE superstep. Hand-built streams (zlib would never emit
# them) held to zlib's own inflate; raw launches, so each lane's status
# and the launch's superstep count (meta row 2) can be read.
# ---------------------------------------------------------------------------

# RFC 1951's tables, as plain ints (the bit writer shifts Python ints)
_LBASE, _LEXT, _DBASE, _DEXT, _CLORDER, _FIXED_LENS = (
    t.tolist() for t in (tables._LBASE, tables._LEXT, tables._DBASE,
                         tables._DEXT, tables._CLORDER, tables._FIXED_LENS))


def test_tables_equal_rfc1951():
    """The constants every device read decodes by, against RFC 1951
    written out: §3.2.5 (length codes 257..285, distance codes 0..29,
    the distance alphabet padded to 32 with zeros), §3.2.7 (the order
    of the code-length code lengths), §3.2.6 (the fixed code)."""
    lext = [0] * 8 + [1] * 4 + [2] * 4 + [3] * 4 + [4] * 4 + [5] * 4 + [0]
    lbase = [3, 4, 5, 6, 7, 8, 9, 10, 11, 13, 15, 17, 19, 23, 27, 31, 35,
             43, 51, 59, 67, 83, 99, 115, 131, 163, 195, 227, 258]
    dext = [0, 0, 0, 0] + [e for e in range(1, 14) for _ in range(2)]
    dbase = [1, 2, 3, 4, 5, 7, 9, 13, 17, 25, 33, 49, 65, 97, 129, 193,
             257, 385, 513, 769, 1025, 1537, 2049, 3073, 4097, 6145, 8193,
             12289, 16385, 24577]
    # the codes tile their ranges: 3..257 (285 alone is 258), 1..32768
    assert all(lbase[i] + (1 << lext[i]) == lbase[i + 1] for i in range(27))
    assert lbase[27] + (1 << lext[27]) == 259
    assert all(dbase[i] + (1 << dext[i]) == dbase[i + 1] for i in range(29))
    assert dbase[29] + (1 << dext[29]) == 32769
    assert (_LBASE, _LEXT) == (lbase, lext)
    assert (_DBASE, _DEXT) == (dbase + [0, 0], dext + [0, 0])
    assert _CLORDER == [16, 17, 18, 0, 8, 7, 9, 6, 10, 5, 11, 4, 12, 3, 13,
                        2, 14, 1, 15]
    assert tables._NLIT == 288
    assert _FIXED_LENS == ([8] * 144 + [9] * 112 + [7] * 24 + [8] * 8
                           + [5] * 32)
    for t in (tables._LBASE, tables._LEXT, tables._DBASE, tables._DEXT,
              tables._CLORDER, tables._FIXED_LENS):
        assert t.dtype == np.int32


class Bits:
    """DEFLATE bit writer: header fields and extra bits LSB first,
    Huffman codes MSB first."""

    def __init__(self):
        self.acc = self.n = 0

    def put(self, value, nbits):
        self.acc |= (value & ((1 << nbits) - 1)) << self.n
        self.n += nbits

    def code(self, code, nbits):
        for i in range(nbits - 1, -1, -1):
            self.put((code >> i) & 1, 1)

    def full_flush(self):
        # what Z_FULL_FLUSH writes: an empty stored block, byte-aligned
        self.put(0, 3)
        self.n = (self.n + 7) & ~7
        self.put(0, 16)
        self.put(0xFFFF, 16)

    def bytes(self):
        return self.acc.to_bytes((self.n + 7) // 8, "little")


def canonical(lens):
    """{symbol: (code, nbits)} of the canonical Huffman code."""
    code, out = 0, {}
    for nbits in range(1, 16):
        for sym, l in enumerate(lens):
            if l == nbits:
                out[sym] = (code, nbits)
                code += 1
        code <<= 1
    return out


_FIXED_L = canonical(_FIXED_LENS[:288])
_FIXED_D = canonical(_FIXED_LENS[288:])


def match_symbols(length, dist):
    """(length symbol, its extra bits' value, distance symbol, its)."""
    ls = max(i for i in range(29) if _LBASE[i] <= length)
    ds = max(i for i in range(30) if _DBASE[i] <= dist)
    return 257 + ls, length - _LBASE[ls], ds, dist - _DBASE[ds]


def put_tokens(b, tokens, lcodes, dcodes):
    """A block's tokens and its end-of-block. A token is a literal
    (int), a match ``(length, dist)``, or ``("sym", length symbol,
    extra, distance symbol, extra)`` for symbols no encoder would
    write; a distance symbol of None cuts the stream right there."""
    for t in tokens:
        if isinstance(t, int):
            b.code(*lcodes[t])
            continue
        ls, lx, ds, dx = t[1:] if t[0] == "sym" else match_symbols(*t)
        b.code(*lcodes[ls])
        b.put(lx, _LEXT[ls - 257] if ls - 257 < 29 else 0)
        if ds is None:
            return
        b.code(*dcodes[ds])
        b.put(dx, _DEXT[ds])
    b.code(*lcodes[256])


def fixed_block(b, tokens, final=True):
    b.put(int(final), 1)
    b.put(1, 2)
    put_tokens(b, tokens, _FIXED_L, _FIXED_D)


def dynamic_block(b, tokens, lit_lens, dist_lens, final=True):
    """A dynamic-Huffman block with the given code lengths ({symbol:
    bits}; both codes complete, as zlib demands); each length goes out
    as its own 4-bit code-length symbol, no repeats."""
    hlit = max(max(lit_lens) + 1, 257)
    hdist = max(dist_lens) + 1
    ll = [lit_lens.get(s, 0) for s in range(hlit)]
    dl = [dist_lens.get(s, 0) for s in range(hdist)]
    b.put(int(final), 1)
    b.put(2, 2)
    b.put(hlit - 257, 5)
    b.put(hdist - 1, 5)
    b.put(19 - 4, 4)
    cl_lens = [4] * 16 + [0] * 3
    for s in _CLORDER:
        b.put(cl_lens[s], 3)
    cl = canonical(cl_lens)
    for l in ll + dl:
        b.code(*cl[l])
    put_tokens(b, tokens, canonical(ll), canonical(dl))


def flat_lens(symbols, alphabet):
    """Equal code lengths over ``symbols``, padded with unused symbols
    of the alphabet to a power of two so that the code is complete."""
    syms = sorted(set(symbols))
    n = 2
    while n < len(syms):
        n *= 2
    syms += [s for s in range(alphabet) if s not in syms][: n - len(syms)]
    return {s: n.bit_length() - 1 for s in syms}


def raw_launch(payloads, cw=128, ow=64, whole=False):
    """One launch of the kernel itself: (each lane's output bytes, the
    (6, 128) meta rows: outpos, status, supersteps, far supersteps,
    each lane's crossing chunks, comp sweeps). One geometry (512 compressed bytes
    in, 256 out) for all the small streams, so the interpreter traces
    the kernel for them once. ``whole``: a lane's output is all of its
    ``ow * 4`` bytes, not cut at its outpos."""
    import jax.numpy as jnp

    from disq_tpu.ops import inflate_simd as S

    fn = S._compiled(cw, ow, True)
    comp, clen = S._pack_chunk(payloads, cw)
    words, meta = fn(jnp.asarray(comp), jnp.asarray(clen),
                     *(jnp.asarray(t) for t in S._CONST_TABLES))
    words, meta = np.asarray(words), np.asarray(meta)
    outs = [np.ascontiguousarray(words[:, i]).tobytes()[
                : ow * 4 if whole else meta[0, i]]
            for i in range(len(payloads))]
    return outs, meta


# staircase code lengths 1, 2, ... 14, 15, 15: complete, and its last
# two symbols get the longest codes the format allows
_LONG_LIT = dict(zip(b"abcdefghijklm", range(1, 14)))
_LONG_LIT.update({256: 14, 284: 15, ord("z"): 15})
_LONG_DIST = dict(zip(range(14), range(1, 15)))
_LONG_DIST.update({14: 15, 28: 15})
_FILLER_LEN = 16640


def _filler():
    """16,640 bytes that zlib turns into long matches (16 output bytes
    a superstep), yet no two 512-byte stretches alike, so a copy from
    the wrong place shows."""
    rng = np.random.default_rng(11)
    return b"".join(
        rng.integers(0, 256, 32, dtype=np.uint8).tobytes() * 16
        for _ in range(_FILLER_LEN // 512 + 1))[:_FILLER_LEN]


def longest_codes_stream(align):
    """Filler, a full flush, then a dynamic block whose one match is a
    15-bit length code + 5 extra bits and a 15-bit distance code + 13
    extra bits (distance 16,585: past the ring, so the far-history
    fetch runs inside the fused step), at output alignment ``align``."""
    c = zlib.compressobj(9, zlib.DEFLATED, -15)
    head = c.compress(_filler()) + c.flush(zlib.Z_FULL_FLUSH)
    b = Bits()
    dynamic_block(
        b, list(b"abc"[:align]) + [("sym", 284, 21, 28, 200), ord("z")],
        _LONG_LIT, _LONG_DIST)
    return head + b.bytes()


def boundary_stream(kind, lead):
    """A match as the last token before end-of-block, a full flush,
    then a match as the very first token after the next block's header
    (it reaches back across the flush) and another as its last."""
    first = list(b"abcdefgh"[:lead]) + [(5, 3)]
    second = [(6, 8), ord("q"), (4, 2)]
    b = Bits()
    for tokens, final in ((first, False), (second, True)):
        if kind == "fixed":
            fixed_block(b, tokens, final)
        else:
            lits = [t for t in tokens if isinstance(t, int)] + [256]
            syms = [match_symbols(*t) for t in tokens
                    if not isinstance(t, int)]
            dynamic_block(
                b, tokens, flat_lens(lits + [m[0] for m in syms], 286),
                flat_lens([m[2] for m in syms], 30), final)
        if not final:
            b.full_flush()
    return b.bytes()


_HEALTHY = (
    [("longest", a) for a in range(4)]
    + [(kind, lead) for kind in ("fixed", "dynamic") for lead in (5, 6, 7, 8)]
)


@pytest.fixture(scope="module")
def healthy_launch():
    payloads = [longest_codes_stream(a) if kind == "longest"
                else boundary_stream(kind, a) for kind, a in _HEALTHY]
    from disq_tpu.ops.inflate_simd import buckets_for

    outs, meta = raw_launch(payloads,
                            *buckets_for(payloads, _FILLER_LEN + 256))
    return payloads, outs, meta


class TestFusedMatch:
    @pytest.mark.parametrize("lane", range(len(_HEALTHY)),
                             ids=[f"{k}-{a}" for k, a in _HEALTHY])
    def test_hand_built_lane_equals_zlib(self, healthy_launch, lane):
        payloads, outs, meta = healthy_launch
        want = zlib.decompress(payloads[lane], -15)
        if _HEALTHY[lane][0] == "longest":
            # the match really is the far one: 248 bytes from 16,585 back
            at = _FILLER_LEN + _HEALTHY[lane][1]
            assert want[at: at + 248] == want[at - 16585: at - 16585 + 248]
        assert meta[1, lane] == 0
        assert outs[lane] == want

    def test_longest_codes_are_the_formats_longest(self):
        lcodes = canonical([_LONG_LIT.get(s, 0) for s in range(285)])
        dcodes = canonical([_LONG_DIST.get(s, 0) for s in range(29)])
        assert lcodes[284][1] == 15 and _LEXT[284 - 257] == 5
        assert dcodes[28][1] == 15 and _DEXT[28] == 13
        assert _DBASE[28] + 200 > 4088  # RING_SAFE: the far path


def _fixed(tokens, final=True):
    b = Bits()
    fixed_block(b, tokens, final)
    return b.bytes()


_ABCD = list(b"abcd")
# (name, payload, status, outpos): every fault lies inside the one
# superstep that reads the match; the lane emits nothing in it. The
# launch's output is 256 bytes, so a 257th overflows.
_FAULTS = [
    ("ok-first", _fixed(_ABCD + [(4, 4), (10, 4)]), 0, 18),
    ("dist-symbol-30", _fixed(_ABCD + [("sym", 257, 0, 30, 0)]), 3, 4),
    ("dist-symbol-31", _fixed(_ABCD + [("sym", 260, 0, 31, 0)]), 3, 4),
    ("length-symbol-286", _fixed(_ABCD + [("sym", 286, 0, 0, 0)]), 3, 4),
    ("dist-beyond-output", _fixed(list(b"ab") + [(3, 5)]), 4, 2),
    ("ok-middle", _fixed(list(b"xyzw") * 2 + [(20, 8), ord("!")]), 0, 29),
    ("copy-overflows-ow", _fixed(_ABCD + [(252, 4), (4, 4)]), 5, 256),
    ("ok-last", _fixed(_ABCD + [(248, 4), (4, 28)]), 0, 256),
]


@pytest.fixture(scope="module")
def fault_launch():
    return raw_launch([p for _n, p, _s, _o in _FAULTS])


class TestFusedMatchFaults:
    @pytest.mark.parametrize("lane", range(len(_FAULTS)),
                             ids=[f[0] for f in _FAULTS])
    def test_fault_lands_on_its_own_lane(self, fault_launch, lane):
        outs, meta = fault_launch
        _name, payload, status, outpos = _FAULTS[lane]
        assert (meta[1, lane], meta[0, lane]) == (status, outpos)
        if status == 0:
            assert outs[lane] == zlib.decompress(payload, -15)

    def test_stream_cut_between_length_and_distance(self):
        # the kernel reads zero bits for the distance: it flags the lane
        # (overrun, or a length that is not the expected one) and host
        # zlib then refuses the stream; the neighbour is untouched
        good = _ABCD + [(4, 4), (10, 4)]
        cut = _fixed(_ABCD * 3 + [("sym", 265, 1, None, 0)], final=False)
        outs, meta = raw_launch([_fixed(good), cut])
        assert meta[1, 0] == 0 and outs[0] == b"abcd" * 4 + b"ab"
        assert meta[1, 1] != 0 or meta[0, 1] != 12 + 12
        with pytest.raises(ValueError, match="corrupt DEFLATE"):
            inflate_payloads_simd([_fixed(good), cut], usizes=[18, 24],
                                  interpret=True)


def chunk_bytes(off, dist):
    """The most a copy chunk takes from byte ``off`` of an output word:
    to the end of the fourth output word for a distance of 16 or more,
    of the second from 8, of the first below."""
    return (16 if dist >= 16 else 8 if dist >= 8 else 4) - off


def fused_emits(tokens):
    """The emits of the schedule for one fixed-Huffman block, one an
    emitting superstep: (outpos before it, its bytes, whether a copy
    chunk). A step a literal (one for two, at every output offset), a
    step a copy chunk (the match's length and distance ride on its
    first); a chunk starts at the output's byte offset and takes
    ``chunk_bytes``."""
    emits, outpos, i = [], 0, 0
    while i < len(tokens):
        t = tokens[i]
        if isinstance(t, int):
            pair = i + 1 < len(tokens) and isinstance(tokens[i + 1], int)
            i += 2 if pair else 1
            emits.append((outpos, 2 if pair else 1, False))
            outpos += 2 if pair else 1
            continue
        length, dist = t
        while length:
            k = min(chunk_bytes(outpos & 3, dist), length)
            emits.append((outpos, k, True))
            length -= k
            outpos += k
        i += 1
    return emits


def fused_schedule(tokens):
    """(supersteps, crossing chunks) of that schedule: the header, a
    step an emit, the end-of-block. A chunk crosses when it starts
    inside a word and ends in a later one."""
    emits = fused_emits(tokens)
    crossing = sum(copy and at & 3 != 0 and k > 4 - (at & 3)
                   for at, k, copy in emits)
    return 1 + len(emits) + 1, crossing


def fused_supersteps(tokens):
    return fused_schedule(tokens)[0]


class TestSchedulePin:
    @pytest.mark.parametrize("length,dist,n", [(4, 4, 40), (10, 4, 25),
                                               (3, 8, 40), (37, 16, 6)])
    def test_a_match_costs_no_step_of_its_own(self, length, dist, n):
        # n short matches: with the length and the distance each on a
        # superstep of their own (the schedule before the fusion) the
        # launch would take 2 n more
        tokens = list(bytes(range(65, 65 + dist))) + [(length, dist)] * n
        payload = _fixed(tokens)
        outs, meta = raw_launch([payload])
        assert meta[1, 0] == 0
        assert outs[0] == zlib.decompress(payload, -15)
        assert meta[2, 0] == fused_supersteps(tokens)

    @pytest.mark.parametrize("length,dist,n,chunks", [
        (13, 16, 18, 1), (10, 40, 24, 1), (7, 8, 30, 1), (5, 9, 40, 1),
        (16, 16, 15, 2), (3, 4, 60, 2), (33, 17, 7, 3)])
    def test_a_match_is_one_chunk_wherever_it_starts(
            self, length, dist, n, chunks):
        # a literal between the matches walks their start over every
        # offset of the output word; clipped at the word's boundary (the
        # schedule before) each match that starts inside a word would
        # take a step more. ``chunks``: the most a match may take.
        tokens = list(bytes(range(65, 65 + dist)))
        for i in range(n):
            tokens += [97 + i % 26, (length, dist)]
        payload = _fixed(tokens)
        outs, meta = raw_launch([payload], ow=256)
        steps, crossing = fused_schedule(tokens)
        assert meta[1, 0] == 0
        assert outs[0] == zlib.decompress(payload, -15)
        assert (meta[2, 0], meta[4, 0]) == (steps, crossing)
        # header, the lead's pairs, end-of-block; then a literal and
        # the match's chunks a round
        assert steps <= 2 + (dist + 1) // 2 + n * (1 + chunks)
        assert (crossing > 0) == (dist >= 8)

    @pytest.mark.parametrize("off", range(4))
    def test_a_literal_pair_at_every_offset(self, off):
        # 'a' and a run of it leave the output at byte ``off`` of a
        # word; the 24 literals then go out as 12 pairs, the pairs at
        # off 3 with their second byte in the next word
        tokens = [97, (3 + off, 1)] + list(range(100, 124)) + [(5, 8)]
        payload = _fixed(tokens)
        outs, meta = raw_launch([payload])
        assert meta[1, 0] == 0
        assert outs[0] == zlib.decompress(payload, -15)
        # header, 'a', the run (d 1: to the word's end, then the rest),
        # 12 pairs, the match, the end-of-block
        run = 1 if off == 0 else 2
        assert meta[2, 0] == fused_supersteps(tokens) == 16 + run


# ---- a chunk at any offset: the emit merge places it ----------------

_ANY_HEAD = 20480


@functools.lru_cache(maxsize=None)
def any_head_tokens(nbytes):
    """``nbytes`` of output as fixed-Huffman tokens: 64 literals, then
    rounds of 4 fresh literals and a 249-byte match from 64 back. A
    round is 253 bytes, so the fresh bytes fall on a new phase of the
    64-byte pattern every round and no two stretches of the output stay
    alike for long: a copy from the wrong place shows. Cheap to decode
    (some 1,500 supersteps for 20 KiB) and a few hundred bytes long.
    A tuple: the cases share it."""
    assert nbytes >= 64
    lits = iter(np.random.default_rng(33).integers(
        0, 256, 64 + 4 * (nbytes // 253 + 1)).tolist())
    tokens = [next(lits) for _ in range(64)]
    left = nbytes - 64
    while left >= 4 + 3:
        take = min(249, left - 4)
        tokens += [next(lits) for _ in range(4)] + [(take, 64)]
        left -= 4 + take
    return tuple(tokens + [next(lits) for _ in range(left)])


def any_offset_tokens(head, off, length, dist):
    """The head, ``off`` literals (the head ends on a word's boundary),
    the match, a literal."""
    assert head % 4 == 0 and dist <= head + off
    return (list(any_head_tokens(head)) + list(range(200, 200 + off))
            + [(length, dist), ord("z")])


_ANY_DISTS = (4, 7, 8, 15, 16, 17, 4095, 4097, 20000)


def _any_lengths(off):
    return sorted({3, 4 - off, 5, 13 - off, 14 - off, 15 - off, 16 - off,
                   17 - off, 17, 258} - {1, 2})


# (head bytes, off, length, distance): every offset inside a word x the
# distances round the chunk rule's steps (4 | 8 | 16), round the ring's
# reach (RING_SAFE 4,088) and far past it x lengths round each chunk's
# end; then chunks that cross from the ring's last row into its first
# (output word 1,023 -> 1,024) and from an out slab into the next (word
# 2,047 -> 2,048: the slab of this geometry is 2,048 rows), near and far
_ANY_CASES = (
    [(_ANY_HEAD, off, length, d) for off in (1, 2, 3) for d in _ANY_DISTS
     for length in _any_lengths(off)]
    + [(4092, off, 16 - off, d) for off in (1, 2, 3) for d in (16, 4090)]
    + [(8188, off, 16 - off, d) for off in (1, 2, 3) for d in (16, 8000)]
    + [(8188, 2, 6, 9), (4092, 3, 5, 8)]
)
_ANY_GEOMETRY = (256, 8192)     # cw, ow: 1 KiB in, 32 KiB out a lane


@pytest.fixture(scope="module")
def any_offset_launches():
    """Every case's (tokens, payload, output, meta column, the model's
    supersteps of its launch, the model's crossing chunks of its lane):
    128 lanes a launch, one geometry."""
    done = []
    for lo in range(0, len(_ANY_CASES), 128):
        tokens = [any_offset_tokens(*c) for c in _ANY_CASES[lo: lo + 128]]
        payloads = [_fixed(t) for t in tokens]
        outs, meta = raw_launch(payloads, *_ANY_GEOMETRY)
        model = [fused_schedule(t) for t in tokens]
        steps = max(s for s, _c in model)
        done += [(t, p, o, meta[:, i], steps, model[i][1])
                 for i, (t, p, o) in enumerate(zip(tokens, payloads, outs))]
    return done


class TestChunkAtAnyOffset:
    def test_the_cases_cover_the_rule(self):
        from disq_tpu.ops.inflate_simd import RING_SAFE, RING_W, _SLAB

        cw, ow = _ANY_GEOMETRY
        assert cw + ow < 20480 and _SLAB == 2048 < ow  # _compiled's slab
        assert RING_W == 1024 and 4095 > RING_SAFE
        assert max(len(_fixed(any_offset_tokens(*c))) for c in _ANY_CASES) \
            <= cw * 4 - 16
        crossing = [c for c in _ANY_CASES
                    if c[2] > 4 - c[1] and c[3] >= 8]
        assert {(c[0] + c[1]) >> 2 for c in crossing} >= {1023, 2047, 5120}
        for off in (1, 2, 3):
            assert {16 - off, 17 - off} <= set(_any_lengths(off))

    @pytest.mark.parametrize(
        "lane", range(len(_ANY_CASES)),
        ids=[f"at{h + o}-len{n}-d{d}" for h, o, n, d in _ANY_CASES])
    def test_lane_equals_zlib_in_the_models_steps(
            self, any_offset_launches, lane):
        tokens, payload, out, meta, steps, crossing = \
            any_offset_launches[lane]
        head, off, length, dist = _ANY_CASES[lane]
        want = zlib.decompress(payload, -15)
        at = head + off
        assert len(want) == at + length + 1
        assert want[at: at + min(length, dist)] == \
            want[at - dist: at - dist + min(length, dist)]
        assert meta[1] == 0 and meta[0] == len(want)
        assert out == want
        # the launch lasts as long as its slowest lane; each lane's
        # crossing chunks are its own
        assert (meta[2], meta[4]) == (steps, crossing)
        # the case's own match: its first chunk crosses whenever it
        # outruns the word it starts in and the distance allows
        crossed = min(chunk_bytes(off, dist), length) > 4 - off
        assert crossing - fused_schedule(tokens[:-2])[1] == crossed


def _crossing_payloads():
    """Two lanes with crossing chunks in them and one of literals."""
    tokens = [any_offset_tokens(64, 1, 13, 20),
              any_offset_tokens(600, 3, 40, 64),
              list(range(60, 120))]
    return tokens, [_fixed(t) for t in tokens]


class TestCrossingChunkCounter:
    @pytest.mark.parametrize("route", ["direct", "service"])
    def test_crossing_chunks_booked_once_a_launch(self, route):
        from disq_tpu.runtime.tracing import REGISTRY, telemetry_snapshot

        tokens, payloads = _crossing_payloads()
        raws = [zlib.decompress(p, -15) for p in payloads]
        want = sum(fused_schedule(t)[1] for t in tokens)
        assert want > 2 and fused_schedule(tokens[2])[1] == 0
        crossing = REGISTRY.counter("device.inflate.crossing_chunks")
        launches = REGISTRY.counter("device.kernel_launches")
        base = crossing.total(), launches.value(kernel="inflate_simd")
        got = inflate_by(route, payloads, [len(r) for r in raws])
        assert got == raws
        assert launches.value(kernel="inflate_simd") - base[1] == 1
        assert crossing.total() - base[0] == want
        assert last_inflate_d2h_labels()["crossing_chunks"] == want
        assert ("device.inflate.crossing_chunks"
                in telemetry_snapshot()["counters"])

    def test_a_launch_without_a_match_books_none(self):
        from disq_tpu.runtime.tracing import REGISTRY

        raws = [random_bytes(200), b"only literals here", b""]
        payloads = [deflate_fixed(raws[0]), _fixed(list(raws[1])),
                    deflate_stored(raws[2])]
        crossing = REGISTRY.counter("device.inflate.crossing_chunks")
        base = crossing.total()
        assert inflate_by("direct", payloads, [len(r) for r in raws]) == raws
        assert crossing.total() == base
        assert last_inflate_d2h_labels()["crossing_chunks"] == 0

    def test_meta_row_4_is_a_count_a_lane(self):
        tokens, payloads = _crossing_payloads()
        _outs, meta = raw_launch(payloads, cw=256, ow=256)
        assert meta.shape == (6, 128)
        assert meta[4, :3].tolist() == [fused_schedule(t)[1] for t in tokens]
        assert not meta[4, 3:].any()


# ---- the copy phase's history reads: aligned 8-word tiles ----------


def _tile_buffer(rows):
    """(rows, 128) u32, every word its own value, the high bit used."""
    return (np.arange(rows * 128, dtype=np.uint32).reshape(rows, 128)
            * np.uint32(2654435761))


def _want_tiles(buf, tiles):
    want = np.zeros((8, 128), buf.dtype)
    for lane, t in enumerate(tiles):
        if t >= 0:
            want[:, lane] = buf[8 * t: 8 * t + 8, lane]
    return want


def _tile_indices(n_tiles, seed):
    """A tile a lane: random ones, -1 (nothing), the first, the last."""
    tiles = np.random.default_rng(seed).integers(0, n_tiles, 128)
    tiles[[3, 77]] = -1
    tiles[5], tiles[6] = 0, n_tiles - 1
    return tiles.astype(np.int32)


class TestTileGather:
    @pytest.mark.parametrize("rows", [8, 1024])
    def test_tile_gather_equals_numpy_indexing(self, rows):
        import jax.numpy as jnp

        from disq_tpu.ops.inflate_simd import _gather_tile

        buf = _tile_buffer(rows)
        tiles = _tile_indices(rows // 8, rows)
        got = _gather_tile(jnp.asarray(buf), jnp.asarray(tiles[None]))
        assert got.dtype == jnp.uint32 and got.shape == (8, 128)
        assert (np.asarray(got) == _want_tiles(buf, tiles)).all()
        signed = buf.view(np.int32)
        got = _gather_tile(jnp.asarray(signed), jnp.asarray(tiles[None]))
        assert (np.asarray(got) == _want_tiles(signed, tiles)).all()

    def test_the_rings_wrap(self):
        # a lane whose first tile is the ring's last reads tile 0 next
        import jax.numpy as jnp

        from disq_tpu.ops.inflate_simd import RING_W, _gather_tile

        ring = _tile_buffer(RING_W)
        last = RING_W // 8 - 1
        t0 = np.full(128, last, np.int32)
        t0[::2] = np.arange(64) * 2
        t1 = (t0 + 1) & last
        assert t1[1] == 0 and t1[0] == 1
        for tiles in (t0, t1):
            got = _gather_tile(jnp.asarray(ring), jnp.asarray(tiles[None]))
            assert (np.asarray(got) == _want_tiles(ring, tiles)).all()

    @pytest.mark.parametrize("case", ["spread", "one-slab", "none-live",
                                      "pair-across-a-slab-edge"])
    def test_windowed_tile_gather_over_slabs(self, case):
        import jax.numpy as jnp

        from disq_tpu.ops.inflate_simd import _gather_tiles_ref_win

        rows, slab = 4096, 1024           # four slabs of 128 tiles
        buf = _tile_buffer(rows)
        if case == "spread":
            t0 = _tile_indices(rows // 8, 4)
        elif case == "one-slab":
            t0 = 128 + _tile_indices(128, 5)
            t0[[3, 77]] = -1
        elif case == "none-live":
            t0 = np.full(128, -1, np.int32)
        else:
            t0 = np.full(128, 255, np.int32)   # its second tile: slab 2
            t0[9] = -1
        # the copy phase's pair: a tile and the next, clamped at the last
        t1 = np.where(t0 < 0, -1, np.minimum(t0 + 1, rows // 8 - 1))
        got = _gather_tiles_ref_win(
            jnp.asarray(buf),
            (jnp.asarray(t0[None]), jnp.asarray(t1[None].astype(np.int32))),
            slab=slab)
        assert len(got) == 2
        for g, tiles in zip(got, (t0, t1)):
            assert g.dtype == jnp.uint32
            assert (np.asarray(g) == _want_tiles(buf, tiles)).all()
        # one vector alone, and a buffer no larger than a slab
        (alone,) = _gather_tiles_ref_win(
            jnp.asarray(buf), (jnp.asarray(t0[None]),), slab=slab)
        assert (np.asarray(alone) == _want_tiles(buf, t0)).all()
        (small,) = _gather_tiles_ref_win(
            jnp.asarray(buf[:slab]), (jnp.asarray(t0[None] % 128),),
            slab=slab)
        assert (np.asarray(small)
                == _want_tiles(buf[:slab], t0 % 128)).all()


_TILE_HEAD = 32800          # bytes before the hand-built block: 1,025 tiles
_TILE_DISTS = (16, 18, 1001, 4087, 4088, 4089, 16585, 32768)
# a 258-byte match at output word 8,200 + lead / 4: its sixteen 16-byte
# chunks read five words from word-in-tile offset k, then k + 4, ...
_TILE_CASES = (
    [(d, lead) for d in _TILE_DISTS for lead in range(0, 32, 4)]
    # d < 4: four fetched bytes replicated modularly, at every offset
    + [(d, lead) for d in (1, 2, 3) for lead in range(4)]
)


def _tile_head():
    """32,800 bytes, no two 16-byte stretches alike (a copy from the
    wrong word shows), that zlib still turns into long matches."""
    rng = np.random.default_rng(29)
    cell = rng.integers(0, 256, (_TILE_HEAD // 64, 64), dtype=np.uint8)
    return np.concatenate([cell, cell], axis=1).tobytes()[:_TILE_HEAD]


def tile_stream(d, lead):
    """The head, a full flush, then a fixed block: ``lead`` literals, a
    258-byte match from ``d`` back, a literal."""
    c = zlib.compressobj(9, zlib.DEFLATED, -15)
    head = c.compress(_tile_head()) + c.flush(zlib.Z_FULL_FLUSH)
    b = Bits()
    fixed_block(b, list(range(97, 97 + lead)) + [(258, d), ord("z")])
    return head + b.bytes()


@pytest.fixture(scope="module")
def tile_launch():
    payloads = [tile_stream(d, lead) for d, lead in _TILE_CASES]
    from disq_tpu.ops.inflate_simd import buckets_for

    outs, meta = raw_launch(
        payloads, *buckets_for(payloads, _TILE_HEAD + 32 + 258 + 1))
    return payloads, outs, meta


class TestTileReads:
    def test_the_cases_start_at_every_word_of_a_tile(self):
        for d in _TILE_DISTS:
            ks = {((_TILE_HEAD + lead - d) >> 2) & 7
                  for dd, lead in _TILE_CASES if dd == d}
            assert ks == set(range(8)), d
        assert max(_TILE_DISTS[:5]) == 4088 < min(_TILE_DISTS[5:])

    @pytest.mark.parametrize("lane", range(len(_TILE_CASES)),
                             ids=[f"d{d}-lead{a}" for d, a in _TILE_CASES])
    def test_match_equals_zlib(self, tile_launch, lane):
        payloads, outs, meta = tile_launch
        want = zlib.decompress(payloads[lane], -15)
        d, lead = _TILE_CASES[lane]
        at = _TILE_HEAD + lead
        assert len(want) == at + 259
        if d >= 258:
            assert want[at: at + 258] == want[at - d: at - d + 258]
        assert meta[1, lane] == 0
        assert meta[0, lane] == len(want)
        assert outs[lane] == want

    def test_the_launch_read_past_the_ring(self, tile_launch):
        meta = tile_launch[2]
        assert 0 < meta[3, 0] <= meta[2, 0]
        assert (meta[3] == meta[3, 0]).all()


# ---- the emit merge: the superstep's words as two tile patches ------


def _want_scatter(buf, tiles, patches, masks=None):
    want = buf.copy()
    for i, (t, patch) in enumerate(zip(tiles, patches)):
        for lane in np.nonzero(t >= 0)[0]:
            rows = slice(8 * t[lane], 8 * t[lane] + 8)
            if masks is not None:
                want[rows, lane] &= ~masks[i][:, lane]
            want[rows, lane] |= patch[:, lane]
    return want


def _patches(touch, seed):
    """((P0, P1), (M0, M1)) as the merge builds them: up to four
    consecutive words a lane from row ``k`` of a 16-row patch, byte
    masks with them, zero elsewhere. ``touch``: the rows stay inside
    the first tile, run on into the second, or there are none."""
    rng = np.random.default_rng(seed)
    patch = np.zeros((16, 128), np.uint32)
    mask = np.zeros((16, 128), np.uint32)
    byte_masks = np.array([0xFF, 0xFFFF, 0xFFFFFF, 0xFFFFFFFF, 0xFFFFFF00,
                           0xFF000000], np.uint32)
    for lane in range(128):
        if touch == "one-tile":       # 1 .. 4 words, all below row 8
            n = 1 + lane % 4
            k = lane % (9 - n)
        elif touch == "two-tiles":    # 2 .. 4 words across row 8
            n = 2 + lane % 3
            k = 7 - lane % (n - 1)
        else:
            n = k = 0
        for j in range(n):
            m = byte_masks[rng.integers(len(byte_masks))]
            mask[k + j, lane] = m
            patch[k + j, lane] = (rng.integers(
                0, 2**32, dtype=np.uint64).astype(np.uint32) | 0x01010101) & m
    if touch == "two-tiles":
        assert patch[:8].any(axis=0).all() and patch[8:].any(axis=0).all()
    if touch == "one-tile":
        assert not patch[8:].any() and patch[:8].any(axis=0).all()
    return (patch[:8], patch[8:]), (mask[:8], mask[8:])


def _scatter_win(buf, tiles, patches, slab):
    """``_scatter_tiles_ref_win`` on a ref holding ``buf``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from disq_tpu.ops.inflate_simd import (
        _scatter_tiles_ref_win, _tile_hull,
    )

    def body(buf_ref, t0_ref, t1_ref, p0_ref, p1_ref, out_ref):
        out_ref[...] = buf_ref[...]
        tiles = t0_ref[...], t1_ref[...]
        _scatter_tiles_ref_win(
            out_ref, tiles, (p0_ref[...], p1_ref[...]),
            _tile_hull(tiles, buf.shape[0] // 8), slab=slab)

    return np.asarray(pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct(buf.shape, jnp.uint32),
        interpret=True,
    )(jnp.asarray(buf), *(jnp.asarray(t[None]) for t in tiles),
      *(jnp.asarray(p) for p in patches)))


class TestTileScatter:
    @pytest.mark.parametrize("touch", ["one-tile", "two-tiles", "none"])
    @pytest.mark.parametrize("rows", [8, 1024])
    def test_tile_scatter_equals_numpy_indexing(self, rows, touch):
        import jax.numpy as jnp

        from disq_tpu.ops.inflate_simd import _scatter_tile

        buf = _tile_buffer(rows)
        n_tiles = rows // 8
        t0 = _tile_indices(n_tiles, rows)
        # the merge's pair: a tile and the next (the ring's wraps: with
        # one tile in all, the next is the same one and must be dead)
        t1 = np.where(t0 < 0, -1, (t0 + 1) % n_tiles).astype(np.int32)
        if touch != "two-tiles" or n_tiles == 1:
            t1[:] = -1
        if touch == "none":
            t0[::2] = -1
        patches, masks = _patches(touch, rows)
        tiles = tuple(jnp.asarray(t[None]) for t in (t0, t1))
        for mk in (None, masks):
            got = _scatter_tile(
                jnp.asarray(buf), tiles,
                tuple(jnp.asarray(p) for p in patches),
                None if mk is None else tuple(jnp.asarray(m) for m in mk))
            assert got.dtype == jnp.uint32 and got.shape == buf.shape
            want = _want_scatter(buf, (t0, t1), patches, mk)
            assert (np.asarray(got) == want).all()
            assert (want == buf).all() == (touch == "none")

    def test_the_rings_wrap_on_the_write_side(self):
        # a lane whose first tile is the ring's last writes tile 0 next,
        # bytes outside the masks kept (rows recycle: replace, not OR)
        import jax.numpy as jnp

        from disq_tpu.ops.inflate_simd import RING_W, _scatter_tile

        ring = _tile_buffer(RING_W)
        last = RING_W // 8 - 1
        t0 = np.full(128, last, np.int32)
        t0[::2] = np.arange(64) * 2
        t1 = (t0 + 1) & last
        assert t1[1] == 0 and t1[0] == 1
        patches, masks = _patches("two-tiles", 3)
        got = _scatter_tile(
            jnp.asarray(ring), tuple(jnp.asarray(t[None]) for t in (t0, t1)),
            tuple(jnp.asarray(p) for p in patches),
            tuple(jnp.asarray(m) for m in masks))
        want = _want_scatter(ring, (t0, t1), patches, masks)
        assert (np.asarray(got) == want).all()
        assert (want[:8, 1] != ring[:8, 1]).any()
        assert (want[-8:, 1] != ring[-8:, 1]).any()

    @pytest.mark.parametrize("touch", ["one-tile", "two-tiles", "none"])
    @pytest.mark.parametrize("case", ["spread", "one-slab", "none-live",
                                      "pair-across-a-slab-edge"])
    def test_windowed_tile_scatter_over_slabs(self, case, touch):
        rows, slab = 4096, 1024           # four slabs of 128 tiles
        buf = _tile_buffer(rows)
        if case == "spread":
            t0 = _tile_indices(rows // 8, 4)
        elif case == "one-slab":
            t0 = 128 + _tile_indices(128, 5)
            t0[[3, 77]] = -1
        elif case == "none-live":
            t0 = np.full(128, -1, np.int32)
        else:
            t0 = np.full(128, 255, np.int32)   # its second tile: slab 2
            t0[9] = -1
        # the merge's pair: a tile and the next; past the buffer's last
        # tile the second is dead
        t1 = np.where((t0 < 0) | (t0 + 1 >= rows // 8), -1,
                      t0 + 1).astype(np.int32)
        if touch != "two-tiles":
            t1[:] = -1
        patches, _masks = _patches(touch, 7)
        got = _scatter_win(buf, (t0, t1), patches, slab)
        assert (got == _want_scatter(buf, (t0, t1), patches)).all()
        if case == "none-live" or touch == "none":
            assert (got == buf).all()
        # a buffer no larger than a slab: one slab, one gate
        s0 = np.where(t0 < 0, -1, t0 % 128).astype(np.int32)
        s1 = np.where((t1 < 0) | (s0 == 127), -1, s0 + 1).astype(np.int32)
        small = _scatter_win(buf[:slab], (s0, s1), patches, slab)
        assert (small == _want_scatter(buf[:slab], (s0, s1), patches)).all()


# (start word in its tile, byte offset, the emit's bytes, distance):
# every emit the rules allow at every start word of a tile and every
# byte offset, 1 .. 16 - off bytes (one and two bytes as literals, the
# rest the first chunk of a match from 19 back), so every straddle into
# the next tile; then the short distances: d 1, 2, 3 (four fetched
# bytes replicated modularly, a chunk to the word's end) and 8 <= d <
# 16 (to the end of the second word), a second chunk following
_EMIT_CASES = (
    [(wk, off, n, 19) for wk in range(8) for off in range(4)
     for n in range(1, 17 - off)]
    + [(wk, off, 5, d) for wk in range(8) for off in range(4)
       for d in (1, 2, 3)]
    + [(wk, off, 10 - off, d) for wk in range(8) for off in range(4)
       for d in (8, 15)]
)


def lead_tokens(at, rng):
    """Tokens for ``at`` >= 32 bytes of output: an even count of random
    literals (whole pairs), then a match of 3 or 4 bytes from 16 back
    that ends at byte ``at`` exactly."""
    lits = at - 3 - (at - 3) % 2
    return rng.integers(0, 256, lits).tolist() + [(at - lits, 16)]


def emit_tokens(wk, off, n, d, lane):
    """A lead (no two lanes' alike) up to output byte ``32 + 4 wk +
    off``, then the emit under test: one literal or two before a match,
    or an ``n``-byte match from ``d`` back; a literal after it."""
    rng = np.random.default_rng(1000 + lane)
    tokens = lead_tokens(32 + 4 * wk + off, rng)
    if n <= 2 and d == 19:
        return tokens + rng.integers(0, 256, n).tolist() + [(3, 19), 122]
    return tokens + [(n, d), 122]


@pytest.fixture(scope="module")
def emit_launches():
    """Every case's (tokens, payload, output, meta column, the model's
    supersteps of its launch), 128 lanes a launch."""
    done = []
    for lo in range(0, len(_EMIT_CASES), 128):
        tokens = [emit_tokens(*c, lane=lo + i)
                  for i, c in enumerate(_EMIT_CASES[lo: lo + 128])]
        payloads = [_fixed(t) for t in tokens]
        outs, meta = raw_launch(payloads)
        steps = max(fused_supersteps(t) for t in tokens)
        done += [(t, p, o, meta[:, i], steps)
                 for i, (t, p, o) in enumerate(zip(tokens, payloads, outs))]
    return done


# the buffer's end (256 bytes a lane): (start word of the last tile,
# byte offset, bytes past the end). The last emit runs from there to
# the end exactly (status 0: its second tile would be past the
# buffer's last and must match nothing) or ``past`` bytes over it
# (status 5, nothing of that emit written)
_END_CASES = [(wk, off, past) for wk in (4, 5, 6, 7) for off in range(4)
              for past in (0, 1, 7)]


def end_tokens(wk, off, past, lane):
    at = 224 + 4 * wk + off
    n = 256 - at + past
    rng = np.random.default_rng(2000 + lane)
    tokens = lead_tokens(at, rng)
    if n <= 2:
        return tokens + rng.integers(0, 256, n).tolist()
    return tokens + [(n, 19)]


@pytest.fixture(scope="module")
def end_launch():
    tokens = [end_tokens(*c, lane=i) for i, c in enumerate(_END_CASES)]
    payloads = [_fixed(t) for t in tokens]
    outs, meta = raw_launch(payloads, cw=128, ow=64, whole=True)
    return tokens, payloads, outs, meta


def _ring_wrap_tokens(start_word, off, back):
    """A chunk of ``16 - off`` bytes from output word 1,020 +
    ``start_word`` (1, 2 or 3): with the ring's 1,024 rows its words
    sit in ring tile 127 and, from word 1,024, in tile 0; then a match
    that reads the chunk back through the ring (``back``: how far behind
    its own start it begins), and one that reads that match's own end
    back: bytes written past the wrap, in ring tile 0 alone."""
    n = 16 - off
    return (list(any_head_tokens(4080 + 4 * start_word))
            + list(range(200, 200 + off)) + [(n, 23), (n + back, n + back),
                                             (9, 9), ord("z")])


_WRAP_CASES = [(w, off, back) for w in (1, 2, 3) for off in range(4)
               for back in (0, 5)]


@pytest.fixture(scope="module")
def wrap_launch():
    tokens = [_ring_wrap_tokens(*c) for c in _WRAP_CASES]
    payloads = [_fixed(t) for t in tokens]
    outs, meta = raw_launch(payloads, *_ANY_GEOMETRY)
    return tokens, payloads, outs, meta


class TestEmitMerge:
    def test_the_cases_cover_every_emit_the_rules_allow(self, emit_launches):
        seen = set()
        for tokens, *_rest in emit_launches:
            seen |= {((at >> 2) & 7, at & 3, k, copy)
                     for at, k, copy in fused_emits(tokens)}
        placed = {c[:3] for c in seen}
        assert placed >= {(wk, off, n) for wk in range(8) for off in range(4)
                          for n in range(1, 17 - off)}
        # no emit is longer: never a fifth output word
        assert max(off + n for _wk, off, n in placed) == 16
        # every straddle into the next tile: from start words 5, 6 and
        # 7, with two to four live words
        straddles = {(wk, (off + n + 3) // 4) for wk, off, n in placed
                     if wk + (off + n + 3) // 4 > 8}
        assert straddles == {(5, 4), (6, 3), (6, 4), (7, 2), (7, 3), (7, 4)}
        # a literal pair whose second byte lies in the next word, and
        # in the next tile
        assert {(wk, 3, 2, False) for wk in range(8)} <= seen
        ds = {c[3] for c in _EMIT_CASES}
        assert ds >= {1, 2, 3} and any(8 <= d < 16 for d in ds)

    @pytest.mark.parametrize(
        "lane", range(len(_EMIT_CASES)),
        ids=[f"word{wk}-off{off}-{n}bytes-d{d}"
             for wk, off, n, d in _EMIT_CASES])
    def test_lane_equals_zlib(self, emit_launches, lane):
        tokens, payload, out, meta, steps = emit_launches[lane]
        wk, off, n, d = _EMIT_CASES[lane]
        want = zlib.decompress(payload, -15)
        at = 32 + 4 * wk + off
        # the emit under test is the model's, where the case says
        emits = {e[0]: e for e in fused_emits(tokens)}
        match = n > 2 or d != 19
        assert emits[at] == (
            at, min(n, chunk_bytes(off, d)) if match else n, match)
        if match:
            assert want[at: at + min(n, d)] == want[at - d: at - d + min(n, d)]
        assert meta[1] == 0 and meta[0] == len(want)
        assert out == want
        assert meta[2] == steps

    @pytest.mark.parametrize(
        "lane", range(len(_WRAP_CASES)),
        ids=[f"word{1020 + w}-off{off}-back{b}" for w, off, b in _WRAP_CASES])
    def test_the_ring_wraps_under_a_chunk_and_reads_back(
            self, wrap_launch, lane):
        from disq_tpu.ops.inflate_simd import RING_W

        tokens, payloads, outs, meta = wrap_launch
        w, off, _back = _WRAP_CASES[lane]
        at = 4080 + 4 * w + off
        chunk = {e[0]: e for e in fused_emits(tokens[lane])}[at]
        assert chunk == (at, 16 - off, True)
        # its four words: ring tile 127, then tile 0
        rows = [(r & (RING_W - 1)) >> 3
                for r in range(at >> 2, (at + 16 - off + 3) >> 2)]
        assert rows[0] == 127 and rows[-1] == 0
        assert meta[1, lane] == 0
        assert meta[3, lane] == 0          # read back through the ring
        assert outs[lane] == zlib.decompress(payloads[lane], -15)

    @pytest.mark.parametrize(
        "lane", range(len(_END_CASES)),
        ids=[f"word{56 + wk}-off{off}-past{past}"
             for wk, off, past in _END_CASES])
    def test_the_buffers_end(self, end_launch, lane):
        tokens, payloads, outs, meta = end_launch
        wk, off, past = _END_CASES[lane]
        want = zlib.decompress(payloads[lane], -15)
        assert len(want) == 256 + past
        # the model: the first emit that would pass the end writes
        # nothing and flags the lane
        at, status = 0, 0
        for at, k, _copy in fused_emits(tokens[lane]):
            if at + k > 256:
                status = 5
                break
            at += k
        assert (status == 5) == (past > 0)
        assert (meta[1, lane], meta[0, lane]) == (status, at)
        assert outs[lane][:at] == want[:at]
        assert outs[lane][at:] == bytes(256 - at)

    def test_a_lane_over_the_end_harms_no_other(self, end_launch):
        _tokens, payloads, outs, meta = end_launch
        flagged = [past > 0 for _wk, _off, past in _END_CASES]
        assert meta[1, :len(flagged)].tolist() == [5 * f for f in flagged]
        # the lanes between the flagged ones: whole and right
        assert [o for o, f in zip(outs, flagged) if not f] == [
            zlib.decompress(p, -15)
            for p, f in zip(payloads, flagged) if not f]
        assert not meta[:2, len(flagged):].any()


def inflate_by(route, payloads, usizes):
    """One launch's decoded lanes, straight through the wrapper
    (``direct``) or through the decode service (``service``)."""
    if route == "direct":
        return inflate_payloads_simd(payloads, usizes=usizes,
                                     interpret=True)
    from disq_tpu.runtime.device_service import DeviceDecodeService

    svc = DeviceDecodeService(flush_timeout_s=0.05, interpret=True)
    try:
        blob, offs = svc.submit_inflate(payloads, usizes).result(300)
    finally:
        svc.close()
    return [blob[offs[i]: offs[i + 1]].tobytes()
            for i in range(len(payloads))]


def last_inflate_d2h_labels():
    """The labels of the newest ``device.launch.d2h`` span of an
    inflate launch: where ``_fetch_chunk`` books a launch's counts."""
    from disq_tpu.runtime.tracing import spans

    return [s for s in spans() if s["name"] == "device.launch.d2h"
            and s["labels"].get("kind") == "inflate"][-1]["labels"]


def _far_tokens(dist):
    """Literals, then one match from ``dist`` back: past the ring for
    ``dist`` > 4,088."""
    lits = [65 + (i * 7 + i // 26) % 26 for i in range(4200)]
    return lits + [(40, dist), ord("z")]


class TestFarSuperstepCount:
    @pytest.mark.parametrize("dist,far", [(4088, False), (4089, True)],
                             ids=["all-near", "far"])
    def test_meta_row_3_counts_the_far_supersteps(self, dist, far):
        tokens = _far_tokens(dist)
        payload = _fixed(tokens)
        outs, meta = raw_launch([payload], cw=2048, ow=2048)
        assert meta[1, 0] == 0
        assert outs[0] == zlib.decompress(payload, -15)
        # the schedule is what it was: the tile reads cost no step
        assert meta[2, 0] <= fused_supersteps(tokens)
        # 40 bytes from word-aligned output at d >= 16: 16 + 16 + 8
        assert meta[3, 0] == (3 if far else 0)

    @pytest.mark.parametrize("route", ["direct", "service"])
    def test_far_supersteps_booked_once_a_launch(self, route):
        from disq_tpu.runtime.tracing import REGISTRY, telemetry_snapshot

        payloads = [_fixed(_far_tokens(4089)), _fixed(_far_tokens(64))]
        raws = [zlib.decompress(p, -15) for p in payloads]
        usizes = [len(r) for r in raws]
        far = REGISTRY.counter("device.inflate.far_supersteps")
        steps = REGISTRY.counter("device.inflate.supersteps")
        base = far.total(), steps.total()
        got = inflate_by(route, payloads, usizes)
        assert got == raws
        assert far.total() - base[0] == 3
        assert last_inflate_d2h_labels()["far_supersteps"] == 3
        assert (last_inflate_d2h_labels()["supersteps"]
                == steps.total() - base[1])
        assert ("device.inflate.far_supersteps"
                in telemetry_snapshot()["counters"])


class TestSuperstepCounter:
    @pytest.mark.parametrize("route", ["direct", "service"])
    def test_supersteps_booked_once_a_launch(self, route):
        from disq_tpu.runtime.tracing import REGISTRY, telemetry_snapshot

        raws = [text_like(150 + 17 * i) for i in range(5)]
        payloads = [deflate(r) for r in raws]
        usizes = [len(r) for r in raws]
        _outs, meta = raw_launch(payloads)
        steps = REGISTRY.counter("device.inflate.supersteps")
        launches = REGISTRY.counter("device.kernel_launches")
        base = steps.total(), launches.value(kernel="inflate_simd")
        got = inflate_by(route, payloads, usizes)
        assert got == raws
        assert launches.value(kernel="inflate_simd") - base[1] == 1
        assert steps.total() - base[0] == meta[2, 0] > 0
        assert last_inflate_d2h_labels()["supersteps"] == meta[2, 0]
        assert "device.inflate.supersteps" in telemetry_snapshot()["counters"]


# ---- the compressed window in the carry --------------------------------
# A refill site takes at most one word, so a lane's in_w rises by at
# most two a superstep; the kernel sweeps comp_ref once in COMP_PERIOD
# supersteps for the tiles from in_w >> 3 on, and both sites pick
# their word out of the carried tiles.

def window_picks(bits):
    """The refill rule over one lane's supersteps, ``bits`` a superstep
    being (phase A's, phase B's): for every word a site takes,
    (superstep, site, word, the word's position in the tiles the last
    sweep carried: word - 8 * (in_w at the sweep >> 3))."""
    cnt = in_w = tile = 0
    picks = []
    for step, used in enumerate(bits):
        if step % COMP_PERIOD == 0:
            tile = in_w >> 3
        for site in (0, 1):
            if cnt <= 32:
                picks.append((step, site, in_w, in_w - 8 * tile))
                cnt, in_w = cnt + 32, in_w + 1
            cnt -= used[site]
    return picks


def stored_then_literals(n, lits):
    """A stored block of ``n`` bytes, then a fixed block of the
    literals ``lits``: (payload, its bytes, the bits each superstep
    consumes: the headers, LEN, NLEN, a stored chunk to the output
    word's end, a literal pair, the end-of-block)."""
    data = bytes((7 * i + 3) & 0xFF for i in range(n))
    b = Bits()
    b.put(0, 3)
    b.n = 8
    b.put(n, 16)
    b.put(n ^ 0xFFFF, 16)
    for byte in data:
        b.put(byte, 8)
    fixed_block(b, lits)
    bits = [8, 16, 16] + [32] * (n // 4) + [8 * (n % 4)] * (n % 4 > 0) + [3]
    width = [8 if t < 144 else 9 for t in lits]
    bits += [sum(width[i: i + 2]) for i in range(0, len(lits), 2)] + [7]
    return b.bytes(), data + bytes(lits), [(a, 0) for a in bits]


# the fixed block's first word is word r + 2: every residue of in_w
# mod 8 under a sweep, with 9-bit pairs drifting across the words after
_RESIDUE_LITS = [144 + (5 * i) % 100 for i in range(150)]
_RESIDUE_CASES = [stored_then_literals(4 * r + 3, _RESIDUE_LITS)
                  for r in range(8)]

# 15-bit codes on a length symbol and a distance symbol with few extra
# bits: a one-chunk match of 34 bits a superstep, both phases peeking
_DENSE_LIT = dict(zip(b"abcdefghijklm", range(1, 14)))
_DENSE_LIT.update({256: 14, 267: 15, ord("z"): 15})
_DENSE_DIST = {**{s: s + 1 for s in range(9)},
               **{s: s for s in range(10, 15)}, 9: 15, 15: 15}


def _dynamic(tokens, lit_lens, dist_lens):
    b = Bits()
    dynamic_block(b, tokens, lit_lens, dist_lens)
    return b.bytes()


_WINDOW_RNG = np.random.default_rng(4242)


def _window_bytes(n):
    return _WINDOW_RNG.integers(0, 256, n, dtype=np.uint8).tobytes()


def _truncated_stored():
    """A stored block that says 300 bytes and holds 150."""
    return deflate_stored(_window_bytes(300))[:155]


_WINDOW_SMALL = (
    [(f"first-word-{(r + 2) % 8}-mod-8", p) for r, (p, _raw, _bits)
     in enumerate(_RESIDUE_CASES)]
    + [("stored-32-bits-a-step", deflate_stored(_window_bytes(900))),
       ("literal-pairs-of-15-bit-codes",
        _dynamic([ord("z")] * 380 + list(b"abc"), _LONG_LIT, _LONG_DIST)),
       ("matches-of-15-bit-codes",
        _dynamic(list(b"abcdefghijklmabcdefghijklmabcdef")
                 + [(15 + i % 2, 25 + i % 8) for i in range(120)]
                 + [ord("z")], _DENSE_LIT, _DENSE_DIST)),
       ("empty-lane", b""),
       ("truncated", _truncated_stored())]
)
# one launch at the narrow geometry's largest: a payload at its comp cap
# (its last words lie in the buffer's last tile, the tile after it is
# past the buffer; the wide geometry's twin, a payload at
# MAX_DEVICE_CSIZE, is tests/test_inflate_wide.py's) beside a 51-byte
# payload that stays live as long (so the sweep's hull spans slabs 0 to
# 7), empty lanes among them
_WINDOW_BIG = [
    ("tiny-payload-long-life", deflate(b"abcd" * 8000, 9)),
    ("empty-lane", b""),
    ("payload-at-the-comp-cap",
     deflate_stored(_window_bytes(NARROW_CSIZE - 5))),
    ("empty-lane-2", b""),
    ("forty-bytes-and-done", _fixed(list(range(70, 105)))),
]
_WINDOW_CASES = ([("small", i) for i in range(len(_WINDOW_SMALL))]
                 + [("big", i) for i in range(len(_WINDOW_BIG))])


@pytest.fixture(scope="module")
def window_launches():
    from disq_tpu.ops.inflate_simd import buckets_for

    big = [p for _n, p in _WINDOW_BIG]
    assert len(big[2]) == NARROW_CSIZE < MAX_DEVICE_CSIZE
    assert len(big[0]) < 64
    geometry = buckets_for(big, NARROW_CSIZE)
    assert geometry[0] == 8192
    return {"small": raw_launch([p for _n, p in _WINDOW_SMALL], 256, 1024),
            "big": raw_launch(big, *geometry)}


class TestCompWindow:
    def test_the_pick_equals_numpy_at_all_sixteen_positions(self):
        import jax.numpy as jnp

        from disq_tpu.ops.inflate_simd import _pick_word

        rng = np.random.default_rng(42)
        tiles = rng.integers(0, 2 ** 32, (2, 8, 128), dtype=np.uint32)
        first = rng.integers(0, 1024, 128).astype(np.int32)
        both = np.concatenate(list(tiles), axis=0)
        for shift in range(16):
            pos = (np.arange(128) + shift) % 16
            got = _pick_word(
                tuple(jnp.asarray(t) for t in tiles),
                jnp.asarray(first[None]),
                jnp.asarray((8 * first + pos).astype(np.int32)[None]))
            assert got.dtype == jnp.uint32 and got.shape == (1, 128)
            assert (np.asarray(got)[0] == both[pos, np.arange(128)]).all()

    def test_the_cases_cover_the_rule(self):
        sweeps, picked = set(), set()
        for payload, _raw, bits in _RESIDUE_CASES:
            # the model accounts for every bit of the payload
            assert 0 <= 8 * len(payload) - sum(a for a, _b in bits) < 8
            picks = window_picks(bits)
            words = {step: w for step, _site, w, _pos in reversed(picks)}
            sweeps |= {w & 7 for step, w in words.items()
                       if step % COMP_PERIOD == 0}
            picked |= {(site, pos) for _step, site, _w, pos in picks}
        # a sweep meets in_w at every residue; these lanes take a word
        # a superstep at most, so their picks reach position 7 + 2
        assert sweeps == set(range(8))
        assert {pos for _site, pos in picked} >= set(range(10))
        assert {site for site, _pos in picked} == {0, 1}
        # a word at every site (more than any stream can take: phase B
        # consumes 28 bits at most), from every residue: the last
        # position of the bound, inside the carried tiles
        worst = max(pos for lead in range(8) for *_rest, pos in
                    window_picks([(32, 0)] * lead + [(32, 32)] * 64))
        assert worst == 7 + 2 * COMP_PERIOD - 1 < 8 * _COMP_TILES

    @pytest.mark.parametrize(
        "launch,lane", _WINDOW_CASES,
        ids=[(_WINDOW_SMALL if k == "small" else _WINDOW_BIG)[i][0]
             for k, i in _WINDOW_CASES])
    def test_lane_equals_zlib(self, window_launches, launch, lane):
        name, payload = (_WINDOW_SMALL if launch == "small"
                         else _WINDOW_BIG)[lane]
        outs, meta = window_launches[launch]
        if name == "truncated":
            # flagged as it was before the window was carried (status
            # 6 at 160 bytes: the parent kernel's own), so the host
            # adjudicates as before
            assert (meta[1, lane], meta[0, lane]) == (6, 160)
            with pytest.raises(ValueError, match="corrupt DEFLATE"):
                inflate_payloads_simd([payload], usizes=[300],
                                      interpret=True)
            return
        want = zlib.decompress(payload, -15) if payload else b""
        assert meta[1, lane] == 0 and meta[0, lane] == len(want)
        assert outs[lane] == want
        # lanes past the payloads never started
        assert not meta[:2, len(outs):].any()

    def test_meta_row_5_counts_the_sweeps(self, window_launches):
        for _outs, meta in window_launches.values():
            steps = int(meta[2, 0])
            assert steps > 8 * COMP_PERIOD
            assert (meta[5] == -(-steps // COMP_PERIOD)).all()

    @pytest.mark.parametrize("route", ["direct", "service"])
    def test_comp_fetches_booked_once_a_launch(self, route):
        from disq_tpu.runtime.tracing import REGISTRY, telemetry_snapshot

        raws = [text_like(150 + 17 * i) for i in range(5)]
        payloads = [deflate(r) for r in raws]
        fetches = REGISTRY.counter("device.inflate.comp_fetches")
        steps = REGISTRY.counter("device.inflate.supersteps")
        base = fetches.total(), steps.total()
        assert inflate_by(route, payloads, [len(r) for r in raws]) == raws
        took = steps.total() - base[1]
        assert fetches.total() - base[0] == -(-took // COMP_PERIOD)
        assert (last_inflate_d2h_labels()["comp_fetches"]
                == fetches.total() - base[0])
        assert ("device.inflate.comp_fetches"
                in telemetry_snapshot()["counters"])
