"""BGZF layer tests: block framing, round-trip, guesser at hostile offsets.

Test strategy follows SURVEY.md §4: differential against an independent
oracle (Python's gzip module reads BGZF since it is valid multi-member
gzip) and adversarial split offsets that land mid-block on purpose.
"""

import gzip
import io
import os
import struct
import zlib

import numpy as np
import pytest

from disq_tpu.bgzf import (
    BGZF_EOF_MARKER,
    BgzfBlockGuesser,
    BgzfReader,
    BgzfWriter,
    compress_to_bgzf,
    decompress_bgzf,
    find_block_table,
    make_virtual_offset,
    split_virtual_offset,
)
from disq_tpu.bgzf.block import BGZF_MAX_PAYLOAD as BLOCK
from disq_tpu.bgzf.block import parse_block_header
from disq_tpu.fsw import MemoryFileSystemWrapper, compute_path_splits
from disq_tpu.ops import tpu_ci


def _payload(n: int, seed: int = 0) -> bytes:
    rng = np.random.default_rng(seed)
    # Compressible-ish mix: text-like runs + random bytes
    parts = []
    while sum(map(len, parts)) < n:
        parts.append(b"read_" + rng.integers(0, 10, 20).astype(np.uint8).tobytes())
    return b"".join(parts)[:n]


class TestRoundTrip:
    def test_empty(self):
        data = compress_to_bgzf(b"")
        assert data == BGZF_EOF_MARKER
        assert decompress_bgzf(data) == b""

    @pytest.mark.parametrize("n", [1, 100, 65280, 65281, 300_000])
    def test_sizes(self, n):
        payload = _payload(n)
        comp = compress_to_bgzf(payload)
        assert decompress_bgzf(comp) == payload
        # gzip stdlib is the independent oracle: BGZF is valid multi-member gzip
        assert gzip.decompress(comp) == payload

    def test_terminator_present(self):
        comp = compress_to_bgzf(b"hello")
        assert comp.endswith(BGZF_EOF_MARKER)

    def test_canonical_determinism(self):
        p = _payload(200_000, seed=3)
        assert compress_to_bgzf(p) == compress_to_bgzf(p)

    def test_incompressible_payload_fits(self):
        rng = np.random.default_rng(7)
        p = rng.integers(0, 256, 65280, dtype=np.uint8).tobytes()
        comp = compress_to_bgzf(p)
        assert decompress_bgzf(comp) == p


# by id: the payload ``deflate_blob`` is handed
DEFLATE_PAYLOADS = {
    "1": lambda: _payload(1),
    "2": lambda: _payload(2),
    "255": lambda: _payload(255),
    "one_block": lambda: _payload(BLOCK),
    "one_block_and_a_byte": lambda: _payload(BLOCK + 1),
    "three_blocks": lambda: _payload(3 * BLOCK),
    # past the compaction's 256-row chunk
    "257_blocks_and_a_byte": lambda: (
        _payload(100_003) * 168)[: 257 * BLOCK] + b"x",
    "incompressible": lambda: np.random.default_rng(7).integers(
        0, 256, 2 * BLOCK + 9, dtype=np.uint8).tobytes(),
    "repetitive": lambda: b"\0" * (2 * BLOCK - 1),
    "bam_like": lambda: tpu_ci._bam_like(200_000, np.random.default_rng(5)),
    "ndarray": lambda: np.frombuffer(_payload(BLOCK + 300), np.uint8),
}


class TestDeflateBlob:
    """The one BGZF deflate every sink writes through, held to the
    block coder (``deflate_block``, the canonical pin) cut by cut, on
    the native route and on the Python one."""

    @pytest.mark.parametrize("route", ["native", "python"])
    @pytest.mark.parametrize("name", DEFLATE_PAYLOADS)
    def test_blocks_are_the_canonical_ones(self, name, route, monkeypatch):
        import sys

        from disq_tpu.bgzf.codec import deflate_block, deflate_blob

        if route == "native":
            pytest.importorskip("disq_tpu.native")
        else:
            monkeypatch.setitem(sys.modules, "disq_tpu.native", None)
        payload = DEFLATE_PAYLOADS[name]()
        raw = bytes(payload)
        n = len(raw)
        comp, sizes = deflate_blob(payload)
        assert isinstance(comp, bytes) and sizes.dtype == np.int64
        assert int(sizes.sum()) == len(comp)
        assert len(sizes) == -(-n // BLOCK)
        pos = 0
        for i, size in enumerate(sizes):
            assert parse_block_header(comp, pos) == size <= 0x10000
            crc, isize = struct.unpack_from("<II", comp, pos + size - 8)
            cut = raw[i * BLOCK: (i + 1) * BLOCK]
            assert isize == len(cut) and crc == zlib.crc32(cut)
            pos += int(size)
        assert decompress_bgzf(comp) == raw
        assert comp == b"".join(
            deflate_block(raw[o: o + BLOCK]) for o in range(0, n, BLOCK))


class TestWriterReader:
    def test_virtual_offsets_track(self):
        buf = io.BytesIO()
        w = BgzfWriter(buf)
        assert w.tell_virtual() == 0
        w.write(b"a" * 100)
        c, u = split_virtual_offset(w.tell_virtual())
        assert (c, u) == (0, 100)
        w.write(b"b" * 65280)  # forces first block flush at 65280 boundary
        c2, u2 = split_virtual_offset(w.tell_virtual())
        assert c2 > 0 and u2 == 100
        w.close()
        assert decompress_bgzf(buf.getvalue()) == b"a" * 100 + b"b" * 65280

    def test_reader_seek_virtual(self):
        payload = _payload(200_000, seed=1)
        comp = compress_to_bgzf(payload)
        r = BgzfReader(io.BytesIO(comp))
        assert r.read(10) == payload[:10]
        # Find the second block's file offset and seek into it
        first_total = parse_block_header(comp, 0)
        vo = make_virtual_offset(first_total, 1234)
        r.seek_virtual(vo)
        assert r.read(16) == payload[65280 + 1234: 65280 + 1234 + 16]
        assert r.read(-1) == payload[65280 + 1234 + 16:]

    def test_headerless_part_no_terminator(self):
        buf = io.BytesIO()
        with BgzfWriter(buf, write_terminator=False) as w:
            w.write(b"part-data")
        assert not buf.getvalue().endswith(BGZF_EOF_MARKER)
        # Merge protocol: parts + terminator == valid BGZF
        merged = buf.getvalue() + BGZF_EOF_MARKER
        assert decompress_bgzf(merged) == b"part-data"


class TestGuesser:
    @pytest.fixture()
    def bgzf_file(self, mem_fs):
        payload = _payload(500_000, seed=2)
        comp = compress_to_bgzf(payload)
        mem_fs.write_all("f.bgz", comp)
        blocks = find_block_table(mem_fs, "f.bgz")
        return mem_fs, comp, payload, blocks

    def test_block_table_covers_file(self, bgzf_file):
        fs, comp, payload, blocks = bgzf_file
        assert blocks[0].pos == 0
        assert blocks[-1].end == len(comp) - len(BGZF_EOF_MARKER) or blocks[-1].end == len(comp)
        assert sum(b.usize for b in blocks) >= len(payload)

    def test_guess_from_every_block_interior(self, bgzf_file):
        fs, comp, payload, blocks = bgzf_file
        g = BgzfBlockGuesser(fs, "f.bgz")
        starts = [b.pos for b in blocks]
        # From 1 byte into each block, the guesser must find the NEXT block
        for i, b in enumerate(blocks[:-1]):
            got = g.guess_block_start(b.pos + 1)
            assert got == starts[i + 1], f"block {i}"

    def test_guess_at_exact_boundaries(self, bgzf_file):
        fs, comp, payload, blocks = bgzf_file
        g = BgzfBlockGuesser(fs, "f.bgz")
        for b in blocks:
            assert g.guess_block_start(b.pos) == b.pos

    def test_adversarial_embedded_magic(self, mem_fs):
        # Payload containing many fake BGZF headers must not fool the
        # chain validation once compressed data is scanned.
        fake = (bytes([0x1F, 0x8B, 0x08, 0x04]) + b"\x00" * 20) * 50
        comp = compress_to_bgzf(fake + _payload(100_000))
        mem_fs.write_all("t.bgz", comp)
        blocks_true = find_block_table(mem_fs, "t.bgz")
        g = BgzfBlockGuesser(mem_fs, "t.bgz")
        for off in range(0, len(comp) - 1, 997):
            got = g.guess_block_start(off)
            expect = next((b.pos for b in blocks_true if b.pos >= off), None)
            # Guesses must be real block starts (or the EOF terminator pos)
            if got is not None and expect is not None:
                assert got == expect or got == len(comp) - len(BGZF_EOF_MARKER)

    def test_splits_partition_blocks_exactly(self, bgzf_file):
        # "First owner" rule: every block owned by exactly one split.
        fs, comp, payload, blocks = bgzf_file
        g = BgzfBlockGuesser(fs, "f.bgz")
        for split_size in [1000, 7777, 65536, len(comp)]:
            splits = compute_path_splits(fs, "f.bgz", split_size)
            owned = []
            for s in splits:
                owned += [b.pos for b in g.blocks_in_split(s.start, s.end)]
            assert owned == [b.pos for b in blocks]
