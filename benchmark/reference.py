"""The plain references, and the comparison that decides ``correct``.

numpy and zlib only: nothing here imports the program or takes
anything the program has made.  Every comparison is exact (limit 0): the
configurations state equality, not a tolerance.
"""

from __future__ import annotations

import os
import struct
import zlib

import numpy as np

from benchmark.gen import ALL_COLUMNS, Truth, ragged


class Checks:
    """The numbers compared in one run, each beside its limit."""

    def __init__(self) -> None:
        self.rows = []

    def add(self, what: str, worst, limit=0) -> None:
        self.rows.append((what, worst, limit, worst <= limit))
        print(f"compared {what}: worst {worst} limit {limit} "
              f"{'ok' if worst <= limit else 'FAILED'}", flush=True)

    @property
    def ok(self) -> bool:
        return bool(self.rows) and all(r[3] for r in self.rows)


# -- whole-file answers -----------------------------------------------------


def flagstat(flag: np.ndarray) -> dict:
    """samtools-flagstat counts: pair categories count primary records
    only; 'mapped' is the read itself."""
    f = flag.astype(np.int64)
    primary = (f & (0x100 | 0x800)) == 0
    paired = primary & ((f & 0x1) != 0)
    mapped = (f & 0x4) == 0
    mate_un = (f & 0x8) != 0
    c = lambda m: int(np.count_nonzero(m))  # noqa: E731
    return {
        "total": len(f), "secondary": c(f & 0x100), "supplementary":
        c(f & 0x800), "duplicates": c(f & 0x400), "mapped": c(mapped),
        "paired": c(paired), "read1": c(paired & ((f & 0x40) != 0)),
        "read2": c(paired & ((f & 0x80) != 0)),
        "proper_pair": c(paired & ((f & 0x2) != 0) & mapped),
        "with_mate_mapped": c(paired & mapped & ~mate_un),
        "singletons": c(paired & mapped & mate_un), "qc_fail": c(f & 0x200),
    }


def depth(truth: Truth, contig_lengths, window: int) -> dict:
    """Windowed depth per contig: +1 at the alignment's first window,
    -1 past its last, cumulative sum (mapped, placed records only)."""
    out = {}
    for r, length in enumerate(contig_lengths):
        nw = max(1, -(-length // window))
        sel = (truth.refid == r) & ((truth.flag & 0x4) == 0)
        p = truth.pos[sel].astype(np.int64)
        e = p + np.maximum(truth.reflen[sel], 1)
        lo = np.clip(p // window, 0, nw - 1)
        hi = np.clip((e - 1) // window, 0, nw - 1)
        diff = np.bincount(lo, minlength=nw + 1).astype(np.int64)
        diff -= np.bincount(hi + 1, minlength=nw + 1)
        out[r] = np.cumsum(diff)[:nw].astype(np.int32)
    return out


def depth_differs(got: dict, want: dict) -> bool:
    return sorted(got) != sorted(want) or any(
        not np.array_equal(np.asarray(got[r]), want[r]) for r in want)


def columns_differing(got, truth: Truth, checks: Checks, what: str) -> None:
    """Every column of a batch the program holds against the generator's
    arrays: dtype, shape and every element."""
    want = truth.columns()
    for name in ALL_COLUMNS:
        a, b = np.asarray(getattr(got, name)), want[name]
        if a.dtype != b.dtype or a.shape != b.shape:
            bad = max(a.size, b.size, 1)
        else:
            bad = int(np.count_nonzero(a != b))
        checks.add(f"{what} column {name}: elements differing from the "
                   "generator's", bad)


# -- the coordinate order and the BAM bytes it gives ------------------------


def coordinate_order(truth: Truth) -> np.ndarray:
    """SAM coordinate order: by reference id with unplaced records last,
    then by position; equal keys keep their input order."""
    rid = np.where(truth.refid < 0, np.int64(1) << 40,
                   truth.refid.astype(np.int64))
    return np.lexsort((truth.pos, rid))


def record_sizes(truth: Truth) -> np.ndarray:
    """``block_size`` of each record (SAM spec section 4.2)."""
    length = truth.seq_mat.shape[1]
    return (32 + truth.name_len + 1 + 4 * truth.cigar_len
            + (length + 1) // 2 + length + truth.tag_len).astype(np.int64)


def record_bytes(truth: Truth) -> int:
    """Decoded bytes of the records, each with its 4-byte length."""
    return int(record_sizes(truth).sum()) + 4 * truth.count


def encode_records(truth: Truth) -> bytes:
    """The BAM record bytes of ``truth``, in its order."""
    n, length = truth.seq_mat.shape
    l_name = (truth.name_len + 1).astype(np.int64)
    seq_bytes = (length + 1) // 2
    size = record_sizes(truth)
    core = np.zeros(n, np.dtype([
        ("block_size", "<i4"), ("refid", "<i4"), ("pos", "<i4"),
        ("l_read_name", "u1"), ("mapq", "u1"), ("bin", "<u2"),
        ("n_cigar", "<u2"), ("flag", "<u2"), ("l_seq", "<i4"),
        ("next_refid", "<i4"), ("next_pos", "<i4"), ("tlen", "<i4")]))
    core["block_size"] = size
    for name in ("refid", "pos", "mapq", "bin", "flag", "next_refid",
                 "next_pos", "tlen"):
        core[name] = getattr(truth, name)
    core["l_read_name"] = l_name
    core["n_cigar"] = truth.cigar_len
    core["l_seq"] = length
    name_nul = np.zeros((n, truth.name_mat.shape[1] + 1), np.uint8)
    name_nul[:, :-1] = truth.name_mat
    # the NUL sits right after the name's own bytes
    name_nul[np.arange(n), truth.name_len] = 0
    seq = truth.seq_mat
    if length % 2:
        seq = np.concatenate([seq, np.zeros((n, 1), np.uint8)], axis=1)
    packed = (seq[:, 0::2] << 4) | seq[:, 1::2]
    flat, _off = ragged(
        [core.view(np.uint8).reshape(n, 36), name_nul,
         truth.cigar_mat.astype("<u4").view(np.uint8).reshape(n, -1),
         packed, truth.qual_mat, truth.tag_mat],
        [np.full(n, 36), l_name, 4 * truth.cigar_len,
         np.full(n, seq_bytes), np.full(n, length), truth.tag_len])
    return flat.tobytes()


def bgzf_members(data: bytes):
    """(offset, size, ISIZE) of each BGZF block, from the block headers
    (the BC subfield is the first extra field, as every writer puts it)."""
    o = 0
    while o < len(data):
        if data[o: o + 4] != b"\x1f\x8b\x08\x04" \
                or data[o + 12: o + 14] != b"BC":
            raise ValueError(f"no BGZF block header at offset {o}")
        bsize = struct.unpack_from("<H", data, o + 16)[0] + 1
        yield o, bsize, struct.unpack_from("<I", data, o + bsize - 4)[0]
        o += bsize


def bam_payload(path: str):
    """(header text, record bytes) of a BAM file, inflated block by block
    with zlib and each block's CRC-32 checked."""
    with open(path, "rb") as f:
        data = f.read()
    out = []
    for o, bsize, isize in bgzf_members(data):
        raw = zlib.decompress(data[o + 18: o + bsize - 8], -15, isize or 1)
        crc, = struct.unpack_from("<I", data, o + bsize - 8)
        if len(raw) != isize or zlib.crc32(raw) != crc:
            raise ValueError(f"{path}: bad BGZF block at offset {o}")
        out.append(raw)
    raw = b"".join(out)
    if raw[:4] != b"BAM\x01":
        raise ValueError(f"{path} is not a BAM file")
    l_text, = struct.unpack_from("<i", raw, 4)
    text = raw[8: 8 + l_text].decode()
    o = 8 + l_text
    n_ref, = struct.unpack_from("<i", raw, o)
    o += 4
    for _ in range(n_ref):
        l_name, = struct.unpack_from("<i", raw, o)
        o += 4 + l_name + 4
    return text, raw[o:]


def bgzf_blocks(path: str) -> int:
    """Number of BGZF blocks of a file that hold data (the empty
    end-of-file block not counted)."""
    with open(path, "rb") as f:
        return sum(isize > 0 for _o, _b, isize in bgzf_members(f.read()))


def sorted_file(path: str, truth_sorted: Truth, checks: Checks) -> None:
    """A sorted BAM with its indexes against the reference order."""
    text, records = bam_payload(path)
    head = text.splitlines()[0] if text else ""
    checks.add("sorted BAM header lines not saying SO:coordinate",
               int("SO:coordinate" not in head))
    want = encode_records(truth_sorted)
    if len(records) != len(want):
        bad = abs(len(records) - len(want)) + 1
    else:
        a = np.frombuffer(records, np.uint8)
        bad = int(np.count_nonzero(a != np.frombuffer(want, np.uint8)))
    checks.add("sorted BAM record bytes differing from the reference "
               "order's", bad)
    for ext in (".bai", ".sbi"):
        present = os.path.exists(path + ext) and os.path.getsize(path + ext)
        checks.add(f"index {ext} missing or empty", int(not present))
