"""The long-read configuration's plain reference against independent
code: the program's host reader, the repo's sequential BAM oracle and a
per-base brute force."""

import gzip
import io
import json
import os
import struct

import numpy as np
import pytest

from harness_util import REPO

from benchmark import gen, gen_longread, reference, reference_longread


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark", "configs", "ont30x.json")) as f:
        return json.load(f)


def _bam_bytes(cfg, truth):
    """An uncompressed BAM around ``reference_longread.encode_records``."""
    text = b"@HD\tVN:1.6\tSO:unsorted\n"
    out = io.BytesIO()
    out.write(b"BAM\x01" + struct.pack("<i", len(text)) + text)
    out.write(struct.pack("<i", len(cfg["contigs"])))
    for c in cfg["contigs"]:
        name = c["name"].encode() + b"\x00"
        out.write(struct.pack("<i", len(name)) + name
                  + struct.pack("<i", c["length"]))
    out.write(reference_longread.encode_records(truth))
    return out.getvalue()


def _bgzf(raw: bytes, payload: int = 60000) -> bytes:
    """BGZF blocks of ``payload`` bytes by the standard library's zlib."""
    import zlib

    out = []
    for o in range(0, len(raw), payload):
        chunk = raw[o: o + payload]
        c = zlib.compressobj(6, zlib.DEFLATED, -15)
        comp = c.compress(chunk) + c.flush()
        out.append(struct.pack("<4BI2BH2BHH", 31, 139, 8, 4, 0, 0, 255, 6,
                               66, 67, 2, len(comp) + 25) + comp
                   + struct.pack("<II", zlib.crc32(chunk), len(chunk)))
    out.append(bytes.fromhex(
        "1f8b08040000000000ff0600424302001b0003000000000000000000"))
    return b"".join(out)


def test_the_record_bytes_reread_by_the_host_reader_give_the_columns(
        cfg, tmp_path):
    """The reference's own encoding, in BGZF blocks made by the standard
    library (kilobase records across 60,000-byte blocks), through the
    program's host read: every column is the generator's."""
    from disq_tpu import ReadsStorage

    truth = gen_longread.generate(150, 21, cfg)
    path = tmp_path / "reference.bam"
    path.write_bytes(_bgzf(_bam_bytes(cfg, truth)))
    ds = ReadsStorage.make_default().read(str(path))
    assert ds.count() == truth.count
    want = truth.columns()
    for name in gen.ALL_COLUMNS:
        got = np.asarray(getattr(ds.reads, name))
        assert got.dtype == want[name].dtype, name
        assert np.array_equal(got, want[name]), name


def test_the_encoding_parses_back_through_the_sequential_oracle(cfg):
    from tests.bam_oracle import parse_bam

    truth = gen_longread.generate(120, 11, cfg)
    encoded = reference_longread.encode_records(truth)
    assert len(encoded) == reference_longread.record_bytes(truth)
    _hdr, _refs, records = parse_bam(gzip.compress(_bam_bytes(cfg, truth)))
    assert len(records) == truth.count
    for i in (0, 1, 17, 64, 119):
        r = records[i]
        assert (r.refid, r.pos, r.flag, r.mapq) == (
            truth.refid[i], truth.pos[i], truth.flag[i], truth.mapq[i])
        lo, hi = truth.name_offsets[i: i + 2]
        assert r.name.encode() == truth.names[lo:hi].tobytes()
        lo, hi = truth.cigar_offsets[i: i + 2]
        assert len(r.cigar) == hi - lo
        lo, hi = truth.seq_offsets[i: i + 2]
        assert len(r.seq) == hi - lo


def test_reference_lengths_come_from_the_op_words(cfg):
    truth = gen_longread.generate(400, 31, cfg)
    got = reference_longread.reference_lengths(truth)
    assert np.array_equal(got, truth.reflen)
    for i in (0, 5, 7, 399):
        ops = truth.cigars[truth.cigar_offsets[i]: truth.cigar_offsets[i + 1]]
        want = sum(int(w >> 4) for w in ops if int(w & 0xF) in (0, 2, 3, 7, 8))
        assert got[i] == want


def test_depth_against_a_per_base_brute_force(cfg):
    """A few dozen records on a short contig table: every base each
    alignment covers marks its window once."""
    truth = gen_longread.generate(48, 41, cfg)
    window, lengths = 1024, [c["length"] for c in cfg["contigs"]]
    got = reference_longread.depth(truth, lengths, window)
    assert sorted(got) == [0, 1, 2]
    want = {r: np.zeros(max(1, -(-n // window)), np.int32)
            for r, n in enumerate(lengths)}
    for i in range(truth.count):
        if truth.flag[i] & 0x4:
            continue
        ops = truth.cigars[truth.cigar_offsets[i]: truth.cigar_offsets[i + 1]]
        at, touched = int(truth.pos[i]), set()
        for w in ops:
            if int(w & 0xF) in (0, 2, 3, 7, 8):
                touched.update((at + b) // window for b in range(int(w >> 4)))
                at += int(w >> 4)
        lo, hi = min(touched), max(touched)
        assert touched == set(range(lo, hi + 1))
        want[int(truth.refid[i])][lo: hi + 1] += 1
    assert not reference.depth_differs(got, want)
    assert sum(int(v.sum()) for v in got.values()) > truth.count
    worse = {k: v.copy() for k, v in got.items()}
    worse[2][0] += 1
    assert reference.depth_differs(worse, got)


def test_packed_sequences_pad_an_odd_length_with_a_zero_nibble(cfg):
    truth = gen_longread.generate(60, 51, cfg)
    packed, off = reference_longread.packed_sequences(truth)
    l_seq = np.diff(truth.seq_offsets)
    assert (np.diff(off) == (l_seq + 1) // 2).all()
    assert (l_seq % 2 == 1).any() and (l_seq == 0).any()
    for i in np.flatnonzero(l_seq > 0)[:20]:
        seq = truth.seqs[truth.seq_offsets[i]: truth.seq_offsets[i + 1]]
        got = packed[off[i]: off[i + 1]]
        codes = np.stack([got >> 4, got & 0xF], axis=1).reshape(-1)
        assert np.array_equal(codes[: len(seq)], seq)
        assert not codes[len(seq):].any()
