"""Seeded long-read generator in the record shape a configuration states.

Host numpy (and the standard library's normal quantile) only; imports
nothing of the program.  The same ``(n_records, seed, config)`` gives the
same records, and every seed offers the same work: the multiset of read
lengths is the log-normal's ``n_records`` quantiles (no draw), a read's
kind, strand, alignment length and CIGAR op count are fixed functions of
its place among those quantiles, and every text field has a fixed width,
so the decoded bytes of two seeds' files are equal to the byte.  The seed
permutes which record of the file gets which read, and draws positions,
bases, qualities, clip lengths, indel sites and lengths, names and tag
values.

The records are single-end alignments as minimap2 ``-ax map-ont`` writes
them: a primary has a soft clip at both ends and an indel every
``indel_every`` aligned bases between ``M`` runs; a supplementary one is
a hard-clipped third of its read with an ``SA:Z``; a secondary one keeps
its CIGAR and carries no SEQ (``l_seq`` 0); an unmapped one has neither
position nor CIGAR nor tags.  Ragged columns are flat arrays with
offsets (a 200 kb read beside a 200 b one makes a matrix useless).
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

from benchmark.gen import ALL_COLUMNS, FIXED, UNPLACED_BIN, ragged, reg2bin

PRIMARY, SUPPLEMENTARY, SECONDARY, UNMAPPED = 0, 1, 2, 3
KIND_FLAG = {PRIMARY: 0, SUPPLEMENTARY: 0x800, SECONDARY: 0x100,
             UNMAPPED: 0x4}
OP_M, OP_I, OP_D, OP_S, OP_H = 0, 1, 2, 4, 5
RAGGED = (("names", "name_offsets"), ("cigars", "cigar_offsets"),
          ("seqs", "seq_offsets"), ("quals", "seq_offsets"),
          ("tags", "tag_offsets"))
_TAKE_ROWS = 1024        # rows a step of a ragged take (bounds its index)
_QUAL_CHUNK = 1 << 24    # bases a step of the quality walk


def offsets_of(lens: np.ndarray) -> np.ndarray:
    off = np.zeros(len(lens) + 1, np.int64)
    np.cumsum(lens, out=off[1:])
    return off


def take_ragged(flat: np.ndarray, off: np.ndarray, idx: np.ndarray):
    """Rows ``idx`` of a flat ragged column -> (flat, offsets)."""
    idx = np.asarray(idx, np.int64)
    lens = (off[1:] - off[:-1])[idx]
    new = offsets_of(lens)
    out = np.empty(int(new[-1]), flat.dtype)
    for lo in range(0, len(idx), _TAKE_ROWS):
        hi = min(lo + _TAKE_ROWS, len(idx))
        shift = off[idx[lo:hi]] - new[lo:hi]
        src = np.repeat(shift, lens[lo:hi]) + np.arange(new[lo], new[hi])
        out[new[lo]: new[hi]] = flat[src]
    return out, new


@dataclasses.dataclass
class LongTruth:
    """The generator's records: the 8 fixed columns, ``reflen`` (the
    generator's own count of reference bases, which the reference works
    out again from the op lists) and the ragged columns flat."""

    refid: np.ndarray
    pos: np.ndarray
    mapq: np.ndarray
    bin: np.ndarray
    flag: np.ndarray
    next_refid: np.ndarray
    next_pos: np.ndarray
    tlen: np.ndarray
    reflen: np.ndarray
    names: np.ndarray           # u8, no NUL
    name_offsets: np.ndarray
    cigars: np.ndarray          # u32 op words
    cigar_offsets: np.ndarray
    seqs: np.ndarray            # u8 nibble codes, one a base
    quals: np.ndarray           # u8, shares seq_offsets
    seq_offsets: np.ndarray
    tags: np.ndarray
    tag_offsets: np.ndarray

    @property
    def count(self) -> int:
        return len(self.refid)

    def take(self, idx: np.ndarray) -> "LongTruth":
        idx = np.asarray(idx, np.int64)
        out = {name: getattr(self, name)[idx] for name in FIXED + ("reflen",)}
        for flat, off in RAGGED:
            out[flat], out[off] = take_ragged(
                getattr(self, flat), getattr(self, off), idx)
        return LongTruth(**out)

    def columns(self) -> dict:
        """The 17 columns in the layout of a BAM columnar batch."""
        return {name: getattr(self, name) for name in ALL_COLUMNS}


def read_lengths(n_records: int, cfg: dict) -> np.ndarray:
    """The log-normal's ``n_records`` quantiles, ascending: mean and N50
    as the source states them (sigma^2 = 2 ln(N50 / mean), the N50 of a
    log-normal being exp(mu + 1.5 sigma^2)), clipped."""
    mean, n50 = cfg["read_length_mean"], cfg["read_length_n50"]
    s2 = 2.0 * math.log(n50 / mean)
    mu, sigma = math.log(mean) - s2 / 2.0, math.sqrt(s2)
    inv = statistics.NormalDist().inv_cdf
    z = np.array([inv((i + 0.5) / n_records) for i in range(n_records)])
    lo, hi = cfg["read_length_clip"]
    return np.clip(np.exp(mu + sigma * z), lo, hi).astype(np.int64)


def read_kinds(n_records: int, cfg: dict) -> np.ndarray:
    """Kind of the i-th shortest read: one in ``unmapped_every``
    unmapped, one in ``supplementary_every`` supplementary, one in
    ``secondary_every`` secondary, at residues that never meet."""
    i = np.arange(n_records)
    kind = np.full(n_records, PRIMARY, np.int64)
    kind[i % cfg["secondary_every"] == 3] = SECONDARY
    kind[i % cfg["supplementary_every"] == 5] = SUPPLEMENTARY
    kind[i % cfg["unmapped_every"] == 7] = UNMAPPED
    return kind


def shape(n_records: int, cfg: dict) -> dict:
    """What every seed shares, by place among the length quantiles:
    read length, kind, strand, SEQ length, aligned query length, indel
    events, CIGAR ops, whether the record carries ``SA:Z`` and the
    contig it names there."""
    length = read_lengths(n_records, cfg)
    kind = read_kinds(n_records, cfg)
    i = np.arange(n_records)
    mapped = kind != UNMAPPED
    aligned = np.where(
        kind == SUPPLEMENTARY, length // cfg["supplementary_part"],
        length - cfg["soft_clip_total"]) * mapped
    events = aligned // cfg["indel_every"]
    return {
        "length": length, "kind": kind,
        "reverse": mapped & ((i // 3) % 2 == 1),
        "l_seq": np.where(kind == SECONDARY, 0,
                          np.where(kind == SUPPLEMENTARY, aligned, length)),
        "aligned": aligned, "events": events,
        "n_ops": np.where(mapped, 2 * events + 3, 0),
        "split": mapped & ((kind == SUPPLEMENTARY)
                           | (i % cfg["supplementary_every"] == 6)),
        "sa_contig": i % len(cfg["contigs"]),
    }


def populated_span(shp: dict, cfg: dict) -> int:
    """Base pairs of each contig that hold reads, so that the aligned
    bases give the configuration's coverage."""
    per_contig = int(shp["aligned"].sum()) / cfg["coverage"] \
        / len(cfg["contigs"])
    return max(4 * int(shp["length"].max()), int(per_contig))


def _digits(v: np.ndarray, width: int) -> np.ndarray:
    return ((v[:, None] // 10 ** np.arange(width - 1, -1, -1)[None, :])
            % 10 + ord("0")).astype(np.uint8)


def _cigars(rng, shp: dict, cfg: dict):
    """(op words flat, offsets, reflen, indel bases): clip, then ``M``
    runs with an insertion or a deletion between each two, then clip."""
    n = len(shp["kind"])
    events, aligned = shp["events"], shp["aligned"]
    mapped = shp["n_ops"] > 0
    off = offsets_of(shp["n_ops"])
    ev_off = offsets_of(events)
    total = int(ev_off[-1])
    ev_rec = np.repeat(np.arange(n), events)
    ev_k = np.arange(total) - ev_off[ev_rec]
    is_ins = ev_k % 5 < cfg["insertions_of_5"]
    ev_len = np.minimum(
        rng.geometric(1.0 / cfg["indel_length_mean"], total), 8)
    ins = np.bincount(ev_rec, ev_len * is_ins, n).astype(np.int64)
    dele = np.bincount(ev_rec, ev_len * ~is_ins, n).astype(np.int64)
    m_total = aligned - ins
    base = m_total // (events + 1)
    # run k is base + j[k] - j[k-1]: the jitter moves the indel sites and
    # leaves the runs' sum alone; the last run takes the remainder
    jitter = (rng.integers(0, 6, total)
              % np.maximum(base[ev_rec], 1)).astype(np.int64)
    before = np.concatenate([[0], jitter[:-1]]) * (ev_k > 0)
    runs = base[ev_rec] + jitter - before
    last = np.zeros(n, np.int64)
    has = events > 0
    last[has] = jitter[ev_off[1:][has] - 1]
    tail = base + (m_total - base * (events + 1)) - last

    words = np.zeros(int(off[-1]), np.uint32)
    first = off[:-1][mapped]
    ev_at = off[ev_rec] + 1 + 2 * ev_k
    words[ev_at] = (runs << 4) | OP_M
    words[ev_at + 1] = (ev_len << 4) | np.where(is_ins, OP_I, OP_D)
    words[first + 1 + 2 * events[mapped]] = (tail[mapped] << 4) | OP_M
    # the clips: soft, summing to soft_clip_total, on a primary and a
    # secondary; hard, summing to the rest of the read, on a
    # supplementary one
    lo, hi = cfg["soft_clip"]
    supp = shp["kind"] == SUPPLEMENTARY
    clipped = np.where(supp, shp["length"] - aligned,
                       cfg["soft_clip_total"])
    left = np.where(supp, 1 + rng.integers(0, 1 << 30, n)
                    % np.maximum(clipped - 1, 1),
                    rng.integers(max(lo, cfg["soft_clip_total"] - hi),
                                 min(hi, cfg["soft_clip_total"] - lo) + 1, n))
    op = np.where(supp, OP_H, OP_S)
    words[first] = ((left << 4) | op)[mapped]
    words[off[1:][mapped] - 1] = (((clipped - left) << 4) | op)[mapped]
    return words, off, (m_total + dele) * mapped, (ins + dele) * mapped


def _quals(rng, total: int, cfg: dict) -> np.ndarray:
    """A random walk of steps up to ``qual_step`` folded into the Phred
    range: neighbours are close, and zlib sees little more than the
    order-0 entropy of the range."""
    lo, hi = cfg["qual_range"]
    span, step = hi - lo, cfg["qual_step"]
    period = np.arange(2 * span)
    fold = (lo + np.where(period <= span, period, 2 * span - period)
            ).astype(np.uint8)
    out = np.empty(total, np.uint8)
    at = span // 2
    for o in range(0, total, _QUAL_CHUNK):
        k = min(_QUAL_CHUNK, total - o)
        walk = np.cumsum(rng.integers(-step, step + 1, k, dtype=np.int8),
                         dtype=np.int32)
        walk += at
        walk %= 2 * span
        at = int(walk[-1])
        np.take(fold, walk, out=out[o: o + k])
    return out


def _tags(rng, shp: dict, cfg: dict, nm: np.ndarray):
    """minimap2's tags at fixed widths: NM ms AS nn tp cm s1 s2 de rl,
    and ``SA:Z`` (its numbers zero-padded) on split reads."""
    n = len(shp["kind"])
    u32 = lambda v: np.asarray(v).astype("<u4").view(  # noqa: E731
        np.uint8).reshape(n, 4)
    lit = lambda b: np.broadcast_to(  # noqa: E731
        np.frombuffer(b, np.uint8), (n, len(b)))
    aligned = shp["aligned"]
    score = np.maximum(2 * aligned - 6 * nm, 0)
    de = (nm / np.maximum(aligned, 1)).astype("<f4").view(
        np.uint8).reshape(n, 4)
    tp = np.where(shp["kind"] == SECONDARY, ord("S"), ord("P")
                  ).astype(np.uint8)[:, None]
    common = np.concatenate([
        lit(b"NMI"), u32(nm), lit(b"msI"), u32(score), lit(b"ASI"),
        u32(score - rng.integers(0, 40, n) % np.maximum(score, 1)),
        lit(b"nnC"), np.zeros((n, 1), np.uint8), lit(b"tpA"), tp,
        lit(b"cmI"), u32(aligned // 12), lit(b"s1I"), u32(aligned * 3 // 4),
        lit(b"s2I"), u32(rng.integers(0, 1 << 12, n)), lit(b"def"), de,
        lit(b"rlI"), u32(rng.integers(0, 1 << 10, n))], axis=1)
    names = [c["name"].encode() for c in cfg["contigs"]]
    width = max(len(b) for b in names)
    contig = np.zeros((len(names), width), np.uint8)
    for k, b in enumerate(names):
        contig[k, : len(b)] = np.frombuffer(b, np.uint8)
    c_len = np.array([len(b) for b in names])[shp["sa_contig"]]
    comma = lit(b",")
    sa_parts = [
        lit(b"SAZ"), contig[shp["sa_contig"]], comma,
        _digits(rng.integers(1, 60_000_000, n), 9), comma,
        np.where(rng.integers(0, 2, n), ord("+"), ord("-")
                 ).astype(np.uint8)[:, None], comma,
        _digits(rng.integers(1, 99_999, n), 6), lit(b"S"),
        _digits(aligned, 6), lit(b"M"),
        _digits(rng.integers(1, 9_999, n), 5), lit(b"D"), comma,
        _digits(rng.integers(0, 61, n), 2), comma, _digits(nm, 6),
        lit(b";\0")]
    has_tags = shp["kind"] != UNMAPPED
    lens = [np.where(has_tags, common.shape[1], 0)]
    for k, part in enumerate(sa_parts):
        full = c_len if k == 1 else part.shape[1]
        lens.append(np.where(shp["split"], full, 0))
    return ragged([common] + sa_parts, lens)


def generate(n_records: int, seed: int, cfg: dict) -> LongTruth:
    """Exactly ``n_records`` UNSORTED single-end long-read records."""
    rng = np.random.default_rng(seed)
    by_rank = shape(n_records, cfg)
    span = populated_span(by_rank, cfg)
    # the one thing the seed does to the shape: which record of the file
    # is which read
    order = rng.permutation(n_records)
    shp = {k: v[order] for k, v in by_rank.items()}
    n, kind = n_records, shp["kind"]
    mapped = kind != UNMAPPED
    refid = np.where(mapped, rng.integers(0, len(cfg["contigs"]), n), -1
                     ).astype(np.int32)
    pos = np.where(mapped, rng.integers(100, 100 + span, n), -1
                   ).astype(np.int32)
    cigars, cigar_offsets, reflen, indel_bases = _cigars(rng, shp, cfg)
    flag = np.select([kind == k for k in KIND_FLAG],
                     list(KIND_FLAG.values())) | (0x10 * shp["reverse"])
    mapq = np.where(np.isin(kind, (PRIMARY, SUPPLEMENTARY)),
                    rng.integers(0, 61, n), 0)
    beg = np.maximum(pos, 0).astype(np.int64)
    seq_offsets = offsets_of(shp["l_seq"])
    total = int(seq_offsets[-1])
    hexd = np.frombuffer(b"0123456789abcdef", np.uint8)
    name = hexd[rng.integers(0, 16, (n, 36))]
    name[:, [8, 13, 18, 23]] = ord("-")
    # NM: the indel bases and a mismatch every ~25 aligned bases
    nm = indel_bases + shp["aligned"] // 25
    tags, tag_offsets = _tags(rng, shp, cfg, nm)
    return LongTruth(
        refid=refid, pos=pos, mapq=mapq.astype(np.uint8),
        bin=np.where(mapped, reg2bin(beg, beg + np.maximum(reflen, 1)),
                     UNPLACED_BIN).astype(np.uint16),
        flag=flag.astype(np.uint16),
        next_refid=np.full(n, -1, np.int32),
        next_pos=np.full(n, -1, np.int32), tlen=np.zeros(n, np.int32),
        reflen=reflen.astype(np.int64),
        names=name.reshape(-1),
        name_offsets=np.arange(0, 36 * n + 1, 36, dtype=np.int64),
        cigars=cigars, cigar_offsets=cigar_offsets,
        seqs=(1 << rng.integers(0, 4, total, dtype=np.uint8)
              ).astype(np.uint8),
        quals=_quals(rng, total, cfg), seq_offsets=seq_offsets,
        tags=tags, tag_offsets=tag_offsets)
