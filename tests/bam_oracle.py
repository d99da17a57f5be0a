"""Independent BAM oracle for differential testing.

Plays the role pysam/htsjdk play in the reference's test strategy
(SURVEY.md §4.2): a deliberately *separate* implementation — sequential,
struct-based, record-at-a-time — against which the library's vectorized
columnar codec is compared. Shares no code with disq_tpu.

Also the fixture generator (the analogue of disq's ``AnySamTestUtil`` /
htsjdk ``SAMRecordSetBuilder``): synthesizes BAMs with controlled record
counts, sort orders, unmapped tails, and edge cases (no cigar, no seq,
odd-length seq, missing quals, tags).
"""

from __future__ import annotations

import struct
import zlib
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np

NT16 = "=ACMGRSVTWYHKDBN"
NT16_IDX = {c: i for i, c in enumerate(NT16)}
CIG = "MIDNSHP=X"
CIG_IDX = {c: i for i, c in enumerate(CIG)}


@dataclass
class ORecord:
    name: str = "r"
    refid: int = -1
    pos: int = -1  # 0-based
    mapq: int = 0
    flag: int = 4
    cigar: List[Tuple[int, str]] = field(default_factory=list)  # [(len, op)]
    seq: str = ""
    qual: Optional[bytes] = None  # None => 0xFF fill
    next_refid: int = -1
    next_pos: int = -1
    tlen: int = 0
    tags: bytes = b""
    bin: int = 0


def reg2bin(beg: int, end: int) -> int:
    end -= 1
    if beg >> 14 == end >> 14:
        return ((1 << 15) - 1) // 7 + (beg >> 14)
    if beg >> 17 == end >> 17:
        return ((1 << 12) - 1) // 7 + (beg >> 17)
    if beg >> 20 == end >> 20:
        return ((1 << 9) - 1) // 7 + (beg >> 20)
    if beg >> 23 == end >> 23:
        return ((1 << 6) - 1) // 7 + (beg >> 23)
    if beg >> 26 == end >> 26:
        return ((1 << 3) - 1) // 7 + (beg >> 26)
    return 0


def ref_span(rec: ORecord) -> int:
    return sum(n for n, op in rec.cigar if op in "MDN=X")


def encode_record(rec: ORecord) -> bytes:
    name_b = rec.name.encode() + b"\x00"
    cigar_b = b"".join(
        struct.pack("<I", (n << 4) | CIG_IDX[op]) for n, op in rec.cigar
    )
    l_seq = len(rec.seq)
    seq_b = bytearray((l_seq + 1) // 2)
    for i, base in enumerate(rec.seq):
        v = NT16_IDX[base]
        if i % 2 == 0:
            seq_b[i // 2] |= v << 4
        else:
            seq_b[i // 2] |= v
    qual_b = rec.qual if rec.qual is not None else b"\xff" * l_seq
    assert len(qual_b) == l_seq
    body = (
        struct.pack(
            "<iiBBHHHiiii",
            rec.refid, rec.pos, len(name_b), rec.mapq, rec.bin,
            len(rec.cigar), rec.flag, l_seq, rec.next_refid, rec.next_pos,
            rec.tlen,
        )
        + name_b + cigar_b + bytes(seq_b) + qual_b + rec.tags
    )
    return struct.pack("<i", len(body)) + body


def decode_one(data: bytes, off: int) -> Tuple[ORecord, int]:
    (block_size,) = struct.unpack_from("<i", data, off)
    (refid, pos, l_name, mapq, bin_, n_cig, flag, l_seq, nref, npos, tlen) = (
        struct.unpack_from("<iiBBHHHiiii", data, off + 4)
    )
    p = off + 36
    name = data[p: p + l_name - 1].decode()
    p += l_name
    cigar = []
    for _ in range(n_cig):
        (w,) = struct.unpack_from("<I", data, p)
        cigar.append((w >> 4, CIG[w & 0xF]))
        p += 4
    seq_chars = []
    for i in range(l_seq):
        b = data[p + i // 2]
        seq_chars.append(NT16[(b >> 4) if i % 2 == 0 else (b & 0xF)])
    p += (l_seq + 1) // 2
    qual = data[p: p + l_seq]
    p += l_seq
    tags = data[p: off + 4 + block_size]
    rec = ORecord(
        name=name, refid=refid, pos=pos, mapq=mapq, flag=flag, cigar=cigar,
        seq="".join(seq_chars), qual=qual, next_refid=nref, next_pos=npos,
        tlen=tlen, tags=tags, bin=bin_,
    )
    return rec, off + 4 + block_size


def decode_all(data: bytes, off: int = 0) -> List[ORecord]:
    """Every record of ``data`` from ``off`` to its end."""
    records = []
    while off < len(data):
        rec, off = decode_one(data, off)
        records.append(rec)
    return records


# -- oracle-side BGZF + BAM file framing (independent of disq_tpu.bgzf) ----

def _o_bgzf_block(payload: bytes) -> bytes:
    co = zlib.compressobj(5, zlib.DEFLATED, -15)
    comp = co.compress(payload) + co.flush()
    bsize = len(comp) + 25
    return (
        b"\x1f\x8b\x08\x04" + b"\x00" * 6 + b"\x06\x00BC\x02\x00"
        + struct.pack("<H", bsize)
        + comp
        + struct.pack("<II", zlib.crc32(payload), len(payload))
    )


O_EOF = bytes.fromhex(
    "1f8b08040000000000ff0600424302001b0003000000000000000000"
)


def o_bgzf_compress(data: bytes, blocksize: int = 60000) -> bytes:
    out = b"".join(
        _o_bgzf_block(data[i: i + blocksize]) for i in range(0, len(data), blocksize)
    )
    return out + O_EOF


def make_header_bytes(refs: List[Tuple[str, int]], sort_order: str = "unsorted") -> bytes:
    text = "@HD\tVN:1.6\tSO:%s\n" % sort_order
    text += "".join(f"@SQ\tSN:{n}\tLN:{l}\n" for n, l in refs)
    tb = text.encode()
    out = b"BAM\x01" + struct.pack("<i", len(tb)) + tb + struct.pack("<i", len(refs))
    for n, l in refs:
        nb = n.encode() + b"\x00"
        out += struct.pack("<i", len(nb)) + nb + struct.pack("<i", l)
    return out


def make_bam_bytes(
    refs: List[Tuple[str, int]],
    records: List[ORecord],
    sort_order: str = "unsorted",
    blocksize: int = 60000,
) -> bytes:
    payload = make_header_bytes(refs, sort_order) + b"".join(
        encode_record(r) for r in records
    )
    return o_bgzf_compress(payload, blocksize)


def parse_bam(data: bytes) -> Tuple[str, List[Tuple[str, int]], List[ORecord]]:
    """Sequential whole-file oracle parser (gzip module inflates BGZF)."""
    import gzip

    raw = gzip.decompress(data)
    assert raw[:4] == b"BAM\x01"
    (l_text,) = struct.unpack_from("<i", raw, 4)
    text = raw[8: 8 + l_text].decode()
    p = 8 + l_text
    (n_ref,) = struct.unpack_from("<i", raw, p)
    p += 4
    refs = []
    for _ in range(n_ref):
        (l_name,) = struct.unpack_from("<i", raw, p)
        p += 4
        name = raw[p: p + l_name - 1].decode()
        p += l_name
        (l_ref,) = struct.unpack_from("<i", raw, p)
        p += 4
        refs.append((name, l_ref))
    return text, refs, decode_all(raw, p)


# -- fixture synthesis ------------------------------------------------------

DEFAULT_REFS = [("chr1", 100_000), ("chr2", 50_000), ("chrM", 16_569)]


def synth_records(
    n: int,
    refs: List[Tuple[str, int]] = None,
    seed: int = 0,
    sorted_coord: bool = False,
    unmapped_tail: int = 0,
    with_edge_cases: bool = True,
) -> List[ORecord]:
    refs = refs or DEFAULT_REFS
    rng = np.random.default_rng(seed)
    recs: List[ORecord] = []
    for i in range(n):
        refid = int(rng.integers(0, len(refs)))
        readlen = int(rng.integers(20, 150))
        pos = int(rng.integers(0, max(1, refs[refid][1] - readlen - 1)))
        seq = "".join(rng.choice(list("ACGT"), readlen))
        cigar = [(readlen, "M")]
        if rng.random() < 0.3 and readlen > 10:
            s = int(rng.integers(1, 10))
            cigar = [(s, "S"), (readlen - s, "M")]
        tags = b"NMC\x01" if rng.random() < 0.5 else b""
        rec = ORecord(
            name=f"read{i:06d}", refid=refid, pos=pos,
            mapq=int(rng.integers(0, 61)), flag=0, cigar=cigar, seq=seq,
            qual=bytes(rng.integers(0, 42, readlen, dtype=np.uint8).tolist()),
            tlen=int(rng.integers(-500, 500)), tags=tags,
        )
        rec.bin = reg2bin(rec.pos, rec.pos + ref_span(rec))
        recs.append(rec)
    if with_edge_cases and n >= 4:
        # no-cigar+no-seq record, odd-length seq, missing quals, long CIGAR
        recs[0] = ORecord(name="nocigar", refid=0, pos=5, flag=0, cigar=[],
                          seq="", qual=b"", mapq=0,
                          bin=reg2bin(5, 6))
        odd = "ACGTA"
        recs[1] = ORecord(name="odd", refid=0, pos=10, flag=0,
                          cigar=[(5, "M")], seq=odd, qual=None, mapq=7,
                          bin=reg2bin(10, 15))
        many = [(1, "M"), (1, "I")] * 40 + [(10, "M")]
        mlen = sum(l for l, op in many if op in "MIS=X")
        recs[2] = ORecord(name="longcigar", refid=1, pos=100, flag=0,
                          cigar=many, seq="A" * mlen, qual=b"\x20" * mlen,
                          bin=reg2bin(100, 100 + sum(l for l, o in many if o in "MDN=X")))
    if sorted_coord:
        recs.sort(key=lambda r: (r.refid if r.refid >= 0 else 1 << 30, r.pos))
    for i in range(unmapped_tail):
        recs.append(ORecord(name=f"unm{i}", refid=-1, pos=-1, flag=4,
                            seq="ACGT", qual=b"\x10\x10\x10\x10", bin=4680))
    return recs


def synth_paired_records(
    n_pairs: int,
    refs: List[Tuple[str, int]] = None,
    seed: int = 0,
    dup_every: int = 5,
    rg_names: Tuple[str, ...] = ("rg1", "rg2"),
) -> List[ORecord]:
    """Coordinate-sorted paired reads with controlled duplicate
    clusters for the operator-suite golden tests: every ``dup_every``-th
    pair gets 1-2 extra copies at the same *unclipped* 5' position
    (some with a leading soft-clip, so pos differs but the key
    matches), plus excluded-category members (unmapped / secondary /
    supplementary) sitting inside clusters, and round-robin ``RG:Z``
    tags."""
    refs = refs or DEFAULT_REFS
    rng = np.random.default_rng(seed)
    recs: List[ORecord] = []

    def one(name, refid, pos, flag, clip=0, rl=60, q_base=25, rg=None):
        cigar = ([(clip, "S")] if clip else []) + [(rl - clip, "M")]
        if flag & 4:
            cigar = []  # placed-unmapped: coordinates but no alignment
        r = ORecord(
            name=name, refid=refid, pos=pos + clip if clip else pos,
            mapq=int(rng.integers(10, 60)), flag=flag, cigar=cigar,
            seq="".join(rng.choice(list("ACGT"), rl)),
            qual=bytes(rng.integers(q_base, q_base + 15, rl,
                                    dtype=np.uint8).tolist()),
            tags=(b"RGZ" + rg.encode() + b"\x00") if rg else b"",
        )
        r.bin = reg2bin(max(r.pos, 0), max(r.pos, 0) + max(ref_span(r), 1))
        return r

    for p in range(n_pairs):
        refid = int(rng.integers(0, len(refs)))
        rl = 60
        pos1 = int(rng.integers(100, refs[refid][1] - 1000))
        pos2 = pos1 + int(rng.integers(80, 400))
        rg = rg_names[p % len(rg_names)] if rg_names else None
        # proper pair: R1 forward, R2 reverse
        recs.append(one(f"p{p:05d}", refid, pos1,
                        0x1 | 0x2 | 0x20 | 0x40, rg=rg))
        recs.append(one(f"p{p:05d}", refid, pos2,
                        0x1 | 0x2 | 0x10 | 0x80, rg=rg))
        if p % dup_every == 0:
            # duplicate copies of R1's 5' site: one plain, one whose
            # leading soft-clip shifts pos but not the unclipped key
            recs.append(one(f"d{p:05d}a", refid, pos1,
                            0x1 | 0x2 | 0x20 | 0x40, q_base=32, rg=rg))
            recs.append(one(f"d{p:05d}b", refid, pos1, 0x1 | 0x40,
                            clip=7, q_base=18, rg=rg))
        if p % 11 == 0:
            # excluded categories inside the cluster: none may mark or
            # be marked (unmapped-at-pos, secondary, supplementary)
            recs.append(one(f"x{p:05d}u", refid, pos1, 0x4 | 0x1 | 0x40))
            recs.append(one(f"x{p:05d}s", refid, pos1, 0x100, rg=rg))
            recs.append(one(f"x{p:05d}v", refid, pos1, 0x800, rg=rg))
    recs.sort(key=lambda r: (r.refid if r.refid >= 0 else 1 << 30, r.pos))
    return recs


# -- operator-suite oracles (sequential, record-at-a-time) ------------------

MARKDUP_EXCLUDE_O = 0x4 | 0x100 | 0x800


def _o_clips(rec: ORecord) -> Tuple[int, int]:
    """(leading, trailing) clipped bases — H then S at the start,
    S then H at the end, per the SAM spec's legal clip placement."""
    lead = trail = 0
    cig = list(rec.cigar)
    for _ in range(2):
        if cig and cig[0][1] in "HS":
            lead += cig[0][0]
            cig = cig[1:]
    for _ in range(2):
        if cig and cig[-1][1] in "HS":
            trail += cig[-1][0]
            cig = cig[:-1]
    return lead, trail


def o_markdup_key(rec: ORecord):
    """(refid, unclipped 5' pos, orientation) or None if excluded."""
    if rec.flag & MARKDUP_EXCLUDE_O or rec.refid < 0:
        return None
    lead, trail = _o_clips(rec)
    span = max(ref_span(rec), 1)
    if rec.flag & 0x10:
        return (rec.refid, rec.pos + span - 1 + trail, 1)
    return (rec.refid, rec.pos - lead, 0)


def o_markdup_score(rec: ORecord) -> int:
    q = rec.qual if rec.qual is not None else b""
    return sum(v for v in q if 15 <= v != 0xFF)


def oracle_markdup(records: List[ORecord]) -> List[bool]:
    """Duplicate flags over the WHOLE record list (global truth — what
    the per-shard device pass plus the boundary merge must equal):
    group by key, keep the best score (ties: earliest record), mark
    the rest."""
    groups = {}
    for i, rec in enumerate(records):
        k = o_markdup_key(rec)
        if k is not None:
            groups.setdefault(k, []).append(i)
    dup = [False] * len(records)
    for idxs in groups.values():
        best = max(idxs, key=lambda i: (o_markdup_score(records[i]), -i))
        for i in idxs:
            dup[i] = i != best
    return dup


def oracle_pileup(records: List[ORecord], refid: int, start: int,
                  end: int) -> np.ndarray:
    """Per-base coverage of [start, end): mapped records only, one
    count per reference base the alignment spans."""
    cov = np.zeros(max(0, end - start), np.int64)
    for rec in records:
        if rec.flag & 0x4 or rec.refid != refid:
            continue
        span = max(ref_span(rec), 1)
        lo, hi = max(rec.pos, start), min(rec.pos + span, end)
        if lo < hi:
            cov[lo - start: hi - start] += 1
    return cov


def o_read_group(rec: ORecord):
    """The RG:Z value via a sequential struct tag walk, or None."""
    buf, s, e = rec.tags, 0, len(rec.tags)
    sizes = {"A": 1, "c": 1, "C": 1, "s": 2, "S": 2, "i": 4, "I": 4,
             "f": 4}
    while s + 3 <= e:
        tag, tp = buf[s:s + 2], chr(buf[s + 2])
        s += 3
        if tp in "ZH":
            z = buf.index(b"\x00", s)
            if tag == b"RG" and tp == "Z":
                return buf[s:z].decode()
            s = z + 1
        elif tp == "B":
            sub = chr(buf[s])
            (cnt,) = struct.unpack_from("<i", buf, s + 1)
            s += 5 + sizes.get(sub, 1) * cnt
        else:
            s += sizes.get(tp, 1)
    return None


def oracle_rgstats(records: List[ORecord]) -> dict:
    """{rg: {reads, duplicates, dup_rate, mean_mapq, mapq_hist}} with
    untagged reads in a trailing "(none)" group — the shape
    ``ops/rgstats.read_group_stats`` returns."""
    order: List[str] = []
    hist = {}
    dups = {}
    saw_none = False
    for rec in records:
        rg = o_read_group(rec)
        if rg is None:
            rg = "(none)"
            saw_none = True
        if rg not in hist:
            if rg != "(none)":
                order.append(rg)
            hist[rg] = np.zeros(256, np.int64)
            dups[rg] = 0
        hist[rg][rec.mapq] += 1
        dups[rg] += (rec.flag >> 10) & 1
    if saw_none or not order:
        order.append("(none)")
        hist.setdefault("(none)", np.zeros(256, np.int64))
        dups.setdefault("(none)", 0)
    out = {}
    mq = np.arange(256)
    for rg in order:
        h = hist[rg]
        reads, d = int(h.sum()), int(dups[rg])
        out[rg] = {
            "reads": reads, "duplicates": d,
            "dup_rate": round(d / reads, 6) if reads else 0.0,
            "mean_mapq": round(float((h * mq).sum() / reads), 3)
            if reads else 0.0,
            "mapq_hist": h.astype(int).tolist(),
        }
    return out
