"""The generator and the plain references, against independent code: the
repo's sequential BAM oracle (``tests/bam_oracle.py``) and brute force."""

import gzip
import io
import json
import os
import struct

import numpy as np
import pytest

from harness_util import REPO

from benchmark import gen, reference


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark", "configs", "wgs30x.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("n", [90, 1000, 23_501])
def test_every_seed_gives_the_same_count_of_each_kind(cfg, n):
    a, b = gen.generate(n, 3, cfg), gen.generate(n, 2147483999, cfg)
    assert a.count == b.count == n
    for t in (a, b):
        assert set(gen.ALL_COLUMNS) == set(t.columns())
    assert reference.flagstat(a.flag) == reference.flagstat(b.flag)
    assert sorted(a.cigar_len.tolist()) == sorted(b.cigar_len.tolist())
    assert reference.record_bytes(a) == reference.record_bytes(b)
    assert (a.pos != b.pos).any()
    same = gen.generate(n, 3, cfg)
    assert all((getattr(a, f) == getattr(same, f)).all()
               for f in ("pos", "flag", "seq_mat", "tag_mat"))


def test_the_populated_span_gives_the_configuration_s_coverage(cfg):
    n = 23_501
    t = gen.generate(n, 5, cfg)
    span = gen.populated_span(n, cfg)
    placed = t.refid >= 0
    assert t.pos[placed].max() < 100 + span + 700
    bases = t.reflen[placed].sum()
    assert bases / (span * len(cfg["contigs"])) == pytest.approx(
        cfg["coverage"], rel=0.1)
    # the record shape is the source's: 150 bp, ~350 decoded bytes
    assert t.seq_mat.shape[1] == 150
    assert 340 < reference.record_bytes(t) / n < 390
    fs = reference.flagstat(t.flag)
    for kind in ("secondary", "supplementary", "duplicates", "read2"):
        assert fs[kind] > 0, kind
    assert fs["mapped"] < fs["total"]
    assert {1, 2, 3} <= set(t.cigar_len.tolist())


def _bam_bytes(cfg, truth):
    """An uncompressed BAM around ``reference.encode_records``."""
    text = b"@HD\tVN:1.6\tSO:unsorted\n"
    out = io.BytesIO()
    out.write(b"BAM\x01" + struct.pack("<i", len(text)) + text)
    out.write(struct.pack("<i", len(cfg["contigs"])))
    for c in cfg["contigs"]:
        name = c["name"].encode() + b"\x00"
        out.write(struct.pack("<i", len(name)) + name
                  + struct.pack("<i", c["length"]))
    out.write(reference.encode_records(truth))
    return out.getvalue()


def test_encoded_records_parse_back_through_the_sequential_oracle(cfg, tmp_path):
    from tests.bam_oracle import parse_bam

    truth = gen.generate(500, 11, cfg)
    _hdr, _refs, records = parse_bam(gzip.compress(_bam_bytes(cfg, truth)))
    assert len(records) == truth.count
    cols = truth.columns()
    for i in (0, 1, 17, 250, 499):
        r = records[i]
        assert (r.refid, r.pos, r.flag, r.mapq) == (
            truth.refid[i], truth.pos[i], truth.flag[i], truth.mapq[i])
        lo, hi = cols["name_offsets"][i: i + 2]
        assert r.name.encode() == cols["names"][lo:hi].tobytes()
        assert len(r.cigar) == truth.cigar_len[i]
    # the records' own length fields add up to the whole
    assert len(reference.encode_records(truth)) == reference.record_bytes(truth)


def test_bam_payload_and_block_walk_read_a_bgzf_file(cfg, tmp_path):
    truth = gen.generate(300, 13, cfg)
    raw = _bam_bytes(cfg, truth)
    path = tmp_path / "x.bam"
    eof = bytes.fromhex(
        "1f8b08040000000000ff0600424302001b0003000000000000000000")
    blocks = []
    for o in range(0, len(raw), 4000):
        chunk = raw[o: o + 4000]
        import zlib

        c = zlib.compressobj(6, zlib.DEFLATED, -15)
        comp = c.compress(chunk) + c.flush()
        blocks.append(struct.pack("<4BI2BH2BHH", 31, 139, 8, 4, 0, 0, 255,
                                  6, 66, 67, 2, len(comp) + 25) + comp
                      + struct.pack("<II", zlib.crc32(chunk), len(chunk)))
    path.write_bytes(b"".join(blocks) + eof)
    text, records = reference.bam_payload(str(path))
    assert "SO:unsorted" in text
    assert records == reference.encode_records(truth)
    assert reference.bgzf_blocks(str(path)) == len(blocks)


def test_coordinate_order_is_stable_with_unplaced_records_last(cfg):
    truth = gen.generate(2000, 17, cfg)
    order = reference.coordinate_order(truth)
    s = truth.take(order)
    placed = s.refid >= 0
    assert not placed[np.argmin(placed):].any()
    key = s.refid[placed].astype(np.int64) << 32 | s.pos[placed]
    assert (np.diff(key) >= 0).all()
    ties = np.flatnonzero(np.diff(key) == 0)
    assert len(ties) and (order[ties] < order[ties + 1]).all()


def test_depth_and_flagstat_on_a_hand_made_batch(cfg):
    t = gen.generate(90, 23, cfg)
    d = reference.depth(t, [c["length"] for c in cfg["contigs"]], 1024)
    assert sorted(d) == [0, 1, 2]
    mapped = (t.refid >= 0) & ((t.flag & 4) == 0)
    windows = sum(int((t.pos[i] + t.reflen[i] - 1) // 1024
                      - t.pos[i] // 1024 + 1) for i in np.flatnonzero(mapped))
    assert sum(int(v.sum()) for v in d.values()) == windows
    assert not reference.depth_differs(d, d)
    worse = {k: v.copy() for k, v in d.items()}
    worse[1][0] += 1
    assert reference.depth_differs(worse, d)
    checks = reference.Checks()
    assert not checks.ok            # nothing compared is not correct
    checks.add("x", 0)
    assert checks.ok
    checks.add("y", 1)
    assert not checks.ok
