"""Seeded paired-read generator in the record shape a configuration states.

Host numpy only; imports nothing of the program.  The same
``(n_records, seed, config)`` gives the same records; every seed gives the
same number of records of each kind, of the same lengths, so the work a
seed offers is the same and only the values differ.

Started as a copy of ``chip_smoke.py``'s ``generate_reads`` (PR 21): kept
are the pair / duplicate-cluster / secondary / supplementary / unmapped
structure and the ``RG:Z`` + ``NM:C`` tags; changed are the read length
(the source's 150 bp), the coverage (the populated span follows from the
record count so a region sees the source's reads per bp), indel CIGARs,
the duplicate flag on the marked copy, and the exact record count.
"""

from __future__ import annotations

import dataclasses

import numpy as np

FIXED = ("refid", "pos", "mapq", "bin", "flag", "next_refid", "next_pos",
         "tlen")
ALL_COLUMNS = FIXED + (
    "name_offsets", "names", "cigar_offsets", "cigars", "seq_offsets",
    "seqs", "quals", "tag_offsets", "tags")
UNPLACED_BIN = 4680


def reg2bin(beg: np.ndarray, end: np.ndarray) -> np.ndarray:
    """SAM spec section 5.3 ``reg2bin`` over arrays (end exclusive)."""
    end = end - 1
    out = np.zeros(len(beg), np.int64)
    done = np.zeros(len(beg), bool)
    for shift, base in ((14, 4681), (17, 585), (20, 73), (23, 9), (26, 1)):
        hit = ~done & ((beg >> shift) == (end >> shift))
        out[hit] = base + (beg[hit] >> shift)
        done |= hit
    return out.astype(np.uint16)


def ragged(parts, lens):
    """Row-wise concatenation of fixed-width matrices ``parts`` (each
    (n, w_k), one dtype), keeping the first ``lens[k]`` items of part k
    in each row -> (flat, (n+1,) i64 offsets)."""
    n = len(lens[0])
    mat = np.concatenate(parts, axis=1)
    keep = np.concatenate(
        [np.arange(p.shape[1])[None, :] < np.asarray(l)[:, None]
         for p, l in zip(parts, lens)], axis=1)
    off = np.zeros(n + 1, np.int64)
    np.cumsum(keep.sum(axis=1), out=off[1:])
    return mat[keep], off


@dataclasses.dataclass
class Truth:
    """The generator's records: fixed columns, and each ragged column as
    a fixed-width matrix with a length per row, so that a reorder or a
    filter is one fancy index per field."""

    refid: np.ndarray
    pos: np.ndarray
    mapq: np.ndarray
    bin: np.ndarray
    flag: np.ndarray
    next_refid: np.ndarray
    next_pos: np.ndarray
    tlen: np.ndarray
    reflen: np.ndarray      # reference bases consumed (0 when unmapped)
    name_mat: np.ndarray    # (n, widest name) u8, no NUL
    name_len: np.ndarray
    cigar_mat: np.ndarray   # (n, 3) u32 op words
    cigar_len: np.ndarray
    seq_mat: np.ndarray     # (n, L) u8 nibble codes, one a base
    qual_mat: np.ndarray    # (n, L) u8
    tag_mat: np.ndarray     # (n, all tags) u8
    tag_len: np.ndarray

    @property
    def count(self) -> int:
        return len(self.refid)

    def take(self, idx: np.ndarray) -> "Truth":
        return Truth(**{f.name: getattr(self, f.name)[idx]
                        for f in dataclasses.fields(self)})

    def columns(self) -> dict:
        """The 17 columns in the layout of a BAM columnar batch: ragged
        columns flat with (n+1,) i64 offsets; quals share seq_offsets."""
        n, length = self.seq_mat.shape
        out = {name: getattr(self, name) for name in FIXED}
        out["names"], out["name_offsets"] = ragged(
            [self.name_mat], [self.name_len])
        out["cigars"], out["cigar_offsets"] = ragged(
            [self.cigar_mat], [self.cigar_len])
        out["seqs"] = self.seq_mat.reshape(-1)
        out["quals"] = self.qual_mat.reshape(-1)
        out["seq_offsets"] = np.arange(
            0, n * length + 1, length, dtype=np.int64)
        out["tags"], out["tag_offsets"] = ragged(
            [self.tag_mat], [self.tag_len])
        return out


def populated_span(n_records: int, cfg: dict) -> int:
    """Base pairs of each contig that hold reads, so that the records
    give the configuration's coverage."""
    per_contig = n_records * cfg["read_length"] / cfg["coverage"] \
        / len(cfg["contigs"])
    return max(4 * cfg["read_length"], int(per_contig))


def generate(n_records: int, seed: int, cfg: dict) -> Truth:
    """Exactly ``n_records`` UNSORTED paired reads: proper pairs (R1
    forward, R2 reverse); every ``dup_pair_every``-th pair with two extra
    copies of R1's 5' site (one flagged duplicate, one soft-clipped, so
    pos differs but the duplicate key matches); every
    ``odd_pair_every``-th with unmapped / secondary / supplementary
    members; an insertion in every ``ins_pair_every``-th R2 and a
    deletion in every ``del_pair_every``-th R1; a tail of unplaced
    unmapped reads that makes the count exact."""
    rng = np.random.default_rng(seed)
    length = int(cfg["read_length"])
    clip = int(cfg["soft_clip"])
    dup_every, odd_every = cfg["dup_pair_every"], cfg["odd_pair_every"]

    def members(k):     # records of k pairs with their clusters
        return 2 * k + 2 * -(-k // dup_every) + 3 * -(-k // odd_every)

    n_pairs = int(n_records / (2 + 2 / dup_every + 3 / odd_every
                               + cfg["unplaced_per_pair"]))
    while n_pairs > 1 and members(n_pairs) >= n_records:
        n_pairs -= 1
    n_tail = n_records - members(n_pairs)
    if n_pairs < 1 or n_tail < 1:
        raise ValueError(f"{n_records} records are too few")
    pair = np.arange(n_pairs)
    dup = pair % dup_every == 0
    odd = pair % odd_every == 0
    span = populated_span(n_records, cfg)
    lo, hi = cfg["pair_insert"]
    refid_p = rng.integers(0, len(cfg["contigs"]), n_pairs).astype(np.int32)
    pos1 = rng.integers(100, 100 + span, n_pairs).astype(np.int32)
    pos2 = pos1 + rng.integers(lo - length, hi - length,
                               n_pairs).astype(np.int32)
    has_ins = pair % cfg["ins_pair_every"] == 0     # on R2
    has_del = pair % cfg["del_pair_every"] == 0     # on R1
    m = lambda k: (k << 4) | 0  # noqa: E731
    full = np.array([m(length), 0, 0], np.uint32)
    clipped = np.array([(clip << 4) | 4, m(length - clip), 0], np.uint32)
    ins = np.array([m(70), (2 << 4) | 1, m(length - 72)], np.uint32)
    dele = np.array([m(60), (3 << 4) | 2, m(length - 60)], np.uint32)

    # one class a row: (pair index, pos, flag, name letter, cigar, tagged)
    def cls(sel, pos, flag, letter, cigar=full, n_cigar=1, reflen=length,
            tagged=True):
        idx = pair[sel] if sel is not None else np.zeros(n_tail, np.int64)
        k = len(idx)
        return dict(
            idx=idx, pos=np.broadcast_to(pos, (k,)).astype(np.int32),
            flag=np.full(k, flag, np.uint16),
            letter=np.full(k, letter[0], np.uint8),
            cigar=np.broadcast_to(cigar, (k, 3)).copy(),
            n_cigar=np.full(k, n_cigar, np.int64),
            reflen=np.full(k, reflen, np.int64),
            tagged=np.full(k, tagged, bool))

    r1 = cls(slice(None), pos1, 0x1 | 0x2 | 0x20 | 0x40, b"p")
    r1["cigar"][has_del] = dele
    r1["n_cigar"][has_del] = 3
    r1["reflen"][has_del] = length + 3
    r2 = cls(slice(None), pos2, 0x1 | 0x2 | 0x10 | 0x80, b"p")
    r2["cigar"][has_ins] = ins
    r2["n_cigar"][has_ins] = 3
    r2["reflen"][has_ins] = length - 2
    classes = [
        r1, r2,
        cls(dup, pos1[dup], 0x1 | 0x2 | 0x20 | 0x40 | 0x400, b"a"),
        cls(dup, pos1[dup] + clip, 0x1 | 0x40, b"b", clipped, 2,
            length - clip),
        cls(odd, pos1[odd], 0x4 | 0x1 | 0x40, b"u", full, 0, 0, False),
        cls(odd, pos1[odd], 0x100, b"s"),
        cls(odd, pos1[odd], 0x800, b"v"),
        cls(None, -1, 0x4, b"t", full, 0, 0, False),
    ]
    cat = lambda key: np.concatenate([c[key] for c in classes])  # noqa: E731
    idx, pos, flag, letter = cat("idx"), cat("pos"), cat("flag"), cat("letter")
    n = len(idx)
    unplaced = letter == ord("t")
    refid = np.where(unplaced, -1, refid_p[idx]).astype(np.int32)
    paired = np.arange(n) < 2 * n_pairs
    next_pos = np.concatenate(
        [pos2, pos1, np.full(n - 2 * n_pairs, -1, np.int32)]).astype(np.int32)
    tlen = np.zeros(n, np.int32)
    insert = (pos2 + r2["reflen"] - pos1).astype(np.int32)
    tlen[:n_pairs] = insert
    tlen[n_pairs: 2 * n_pairs] = -insert
    tail_no = np.where(unplaced, np.arange(n) - (n - n_tail), idx)

    # names in the Illumina shape: instrument:run:flowcell:lane:tile: +
    # kind letter + 7 digits (+ the copy letter for non-pair kinds)
    prefix = np.frombuffer(cfg["name_prefix"].encode(), np.uint8)
    tile = 1101 + tail_no % 78
    digits = lambda v, w: ((v[:, None] // 10 ** np.arange(  # noqa: E731
        w - 1, -1, -1)[None, :]) % 10 + ord("0")).astype(np.uint8)
    lead = np.where(np.isin(letter, (ord("a"), ord("b"))), ord("d"),
                    np.where(np.isin(letter, (ord("p"), ord("t"))), letter,
                             ord("x"))).astype(np.uint8)
    name_mat = np.concatenate(
        [np.broadcast_to(prefix, (n, len(prefix))), digits(tile, 4),
         np.full((n, 1), ord(":"), np.uint8), lead[:, None],
         digits(tail_no, 7), letter[:, None]], axis=1)
    name_len = name_mat.shape[1] - np.isin(letter, (ord("p"), ord("t")))

    # random ACGT bases (2 bits of entropy in each 4-bit code) and
    # run-structured quals: zlib sees about 3x, like genomic BAM, so full
    # BGZF blocks stay under the device kernel's compressed cap
    seq_mat = (1 << rng.integers(0, 4, (n, length), dtype=np.uint8)
               ).astype(np.uint8)
    qual_mat = np.repeat(
        rng.integers(28, 42, (n, length // 10), dtype=np.uint8), 10, axis=1)

    # tags as an aligner and a duplicate marker leave them: RG:Z, NM:C,
    # MD:Z, AS:C, XS:C, MC:Z, MQ:C, ms:I, mc:I; the unmapped kinds carry none
    u8 = lambda lo, hi: rng.integers(lo, hi, n).astype(np.uint8)  # noqa: E731
    u32 = lambda hi: rng.integers(0, hi, n).astype("<u4").view(  # noqa: E731
        np.uint8).reshape(n, 4)
    lit = lambda b: np.broadcast_to(  # noqa: E731
        np.frombuffer(b, np.uint8), (n, len(b)))
    tag_mat = np.concatenate([
        lit(b"RGZrg"), (ord("0") + idx % 2).astype(np.uint8)[:, None],
        lit(b"\0NMC"), u8(0, 5)[:, None], lit(b"MDZ150\0ASC"),
        u8(100, 151)[:, None], lit(b"XSC"), u8(0, 100)[:, None],
        lit(b"MCZ150M\0MQC"), u8(0, 61)[:, None], lit(b"msI"), u32(6000),
        lit(b"mcI"), u32(1 << 28)], axis=1)
    n_tag = tag_mat.shape[1]

    reflen = cat("reflen")
    beg = np.maximum(pos, 0).astype(np.int64)
    truth = Truth(
        refid=refid, pos=pos,
        mapq=rng.integers(0, 61, n).astype(np.uint8),
        bin=np.where(unplaced, UNPLACED_BIN,
                     reg2bin(beg, beg + np.maximum(reflen, 1))
                     ).astype(np.uint16),
        flag=flag, next_refid=np.where(paired, refid, -1).astype(np.int32),
        next_pos=next_pos, tlen=tlen, reflen=reflen,
        name_mat=name_mat, name_len=name_len,
        cigar_mat=cat("cigar"), cigar_len=cat("n_cigar"),
        seq_mat=seq_mat, qual_mat=qual_mat,
        tag_mat=tag_mat, tag_len=np.where(cat("tagged"), n_tag, 0))
    return truth.take(rng.permutation(n))
