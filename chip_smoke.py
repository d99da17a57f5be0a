#!/usr/bin/env python
"""chip_smoke — the main path, once, on the chip: read → sort → write → serve.

The quickest proof that disq-tpu still starts on a TPU. One process:

1. generates an unsorted paired-read BAM from ``--seed`` with host numpy
   (>= 2,000,000 records and > one default 128 MiB split of BGZF);
2. reads it on the host path (native C++ inflate + numpy parse) and checks
   that read against the generator's own arrays — the plain reference;
3. reads it again through the device path (``DISQ_TPU_DEVICE_INFLATE=1`` +
   ``DISQ_TPU_DEVICE_SERVICE=1`` + ``.resident_decode()``), sorts, writes
   BAM + BAI + SBI (byte-identical to the host path), runs the operator
   chain, a CRAM round trip with ``DISQ_TPU_DEVICE_RANS=1`` and a few serve
   requests — every device result held to the host result;
4. with four or more chips, repeats read → sort → write on a 4-device mesh,
   at size/8 splits and at the configured split size.

Every leg runs twice (cold, then warm in the same process). Any failed
check raises: no leg's failure is turned into a note. Without a TPU the
script refuses to run (``--allow-cpu`` waives that for the tier-1 test,
which also shrinks the BGZF blocks so the Pallas interpreter can cope).

The run's JSON summary (legs, compiles, counters, ..., ``"claim": null``)
is the second-to-last stdout line. The last stdout line is the verdict, one
JSON object with exactly these keys, the device as JAX reports it::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

This is a smoke, not a benchmark: its wall times say the path ran, and
claim nothing about speed.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import shutil
import struct
import subprocess
import sys
import tempfile
import time
import urllib.request
import zlib

import numpy as np

REFS = [("chr1", 248_956_422), ("chr2", 242_193_529), ("chr20", 64_444_167)]
READ_LEN = 100
CLIP = 7                 # soft clip of the clip-shifted duplicate copy
SPAN_BP = 8_000_000      # reads land in the first 8 Mbp of each contig
DEPTH_WINDOW = 1024
FILTER_SPEC = "-F 0x800 -q 0"
PILEUP_BP = 4096         # pileup region width, placed over a chr1 read
DEVICE_ENV = ("DISQ_TPU_DEVICE_INFLATE", "DISQ_TPU_DEVICE_SERVICE")
ALL_COLUMNS = (
    "refid", "pos", "mapq", "bin", "flag", "next_refid", "next_pos", "tlen",
    "name_offsets", "names", "cigar_offsets", "cigars", "seq_offsets",
    "seqs", "quals", "tag_offsets", "tags",
)
FIXED_COLUMNS = ALL_COLUMNS[:8]


# ---------------------------------------------------------------------------
# Input: a vectorised paired-read generator (host numpy only)
# ---------------------------------------------------------------------------


def _reg2bin(beg: np.ndarray, end: np.ndarray) -> np.ndarray:
    """SAM spec §5.3 ``reg2bin`` over arrays (end exclusive)."""
    end = end - 1
    out = np.zeros(len(beg), np.int64)
    done = np.zeros(len(beg), bool)
    for shift, base in ((14, 4681), (17, 585), (20, 73), (23, 9), (26, 1)):
        hit = ~done & ((beg >> shift) == (end >> shift))
        out[hit] = base + (beg[hit] >> shift)
        done |= hit
    return out.astype(np.uint16)


def _ragged(parts, lens):
    """Row-wise concatenation of fixed-width byte matrices ``parts``
    (each (n, w_k)), keeping the first ``lens[k]`` bytes of part k per
    row -> (flat u8, (n+1,) offsets)."""
    n = len(lens[0])
    mat = np.concatenate(parts, axis=1)
    keep = np.concatenate(
        [np.arange(p.shape[1])[None, :] < l[:, None]
         for p, l in zip(parts, lens)], axis=1)
    off = np.zeros(n + 1, np.int64)
    np.cumsum(keep.sum(axis=1), out=off[1:])
    return mat[keep], off


def generate_reads(n_records: int, seed: int):
    """An UNSORTED batch of ~``n_records`` paired reads in the shape of
    ``tests/bam_oracle.synth_paired_records``: proper pairs (R1 forward,
    R2 reverse), every 5th pair with two extra copies of R1's 5' site
    (one soft-clipped, so pos differs but the duplicate key matches),
    every 11th with unmapped / secondary / supplementary members inside
    the cluster, RG:Z + NM:C tags, a tail of unplaced unmapped reads.
    Returns (header, ReadBatch, reference lengths per record)."""
    from disq_tpu.bam.columnar import ReadBatch
    from disq_tpu.bam.header import SamHeader

    rng = np.random.default_rng(seed)
    n_pairs = max(8, int(n_records / (2 + 2 / 5 + 3 / 11 + 0.02)))
    pair = np.arange(n_pairs)
    refid_p = rng.integers(0, len(REFS), n_pairs).astype(np.int32)
    pos1 = rng.integers(100, SPAN_BP, n_pairs).astype(np.int32)
    pos2 = pos1 + rng.integers(80, 400, n_pairs).astype(np.int32)
    dup = pair % 5 == 0
    exc = pair % 11 == 0
    tail = np.arange(max(1, n_pairs // 50))

    # (pair index, pos, flag, kind letter, soft clip, has tags)
    def cls(sel, pos, flag, letter, clip=0, tagged=True):
        idx = pair[sel] if sel is not None else tail
        k = len(idx)
        return dict(
            idx=idx, pos=np.broadcast_to(pos, (k,)).astype(np.int32),
            flag=np.full(k, flag, np.uint16), letter=letter,
            clip=clip, tagged=tagged)

    classes = [
        cls(slice(None), pos1, 0x1 | 0x2 | 0x20 | 0x40, b"p"),
        cls(slice(None), pos2, 0x1 | 0x2 | 0x10 | 0x80, b"p"),
        cls(dup, pos1[dup], 0x1 | 0x2 | 0x20 | 0x40, b"a"),
        cls(dup, pos1[dup] + CLIP, 0x1 | 0x40, b"b", clip=CLIP),
        cls(exc, pos1[exc], 0x4 | 0x1 | 0x40, b"u", tagged=False),
        cls(exc, pos1[exc], 0x100, b"s"),
        cls(exc, pos1[exc], 0x800, b"v"),
        cls(None, -1, 0x4, b"t", tagged=False),
    ]
    idx = np.concatenate([c["idx"] for c in classes])
    n = len(idx)
    pos = np.concatenate([c["pos"] for c in classes])
    flag = np.concatenate([c["flag"] for c in classes])
    letter = np.concatenate(
        [np.full(len(c["idx"]), c["letter"][0], np.uint8) for c in classes])
    clip = np.concatenate(
        [np.full(len(c["idx"]), c["clip"], np.int32) for c in classes])
    tagged = np.concatenate(
        [np.full(len(c["idx"]), c["tagged"], bool) for c in classes])
    unplaced = letter == ord("t")
    unmapped = (flag & 0x4) != 0
    refid = np.where(unplaced, -1, refid_p[np.where(unplaced, 0, idx)])
    refid = refid.astype(np.int32)
    mate = np.concatenate([
        pos2, pos1] + [np.full(len(c["idx"]), -1, np.int32)
                       for c in classes[2:]])
    paired = np.arange(n) < 2 * n_pairs
    next_refid = np.where(paired, refid, -1).astype(np.int32)
    next_pos = mate.astype(np.int32)
    tlen = np.zeros(n, np.int32)
    ins = (pos2 + READ_LEN - pos1).astype(np.int32)
    tlen[:n_pairs] = ins
    tlen[n_pairs: 2 * n_pairs] = -ins

    # names: kind letter + 7 digits (+ the copy letter for non-pair kinds)
    digits = ((idx[:, None] // 10 ** np.arange(6, -1, -1)[None, :]) % 10
              + ord("0")).astype(np.uint8)
    lead = np.where(np.isin(letter, (ord("a"), ord("b"))), ord("d"),
                    np.where(np.isin(letter, (ord("p"), ord("t"))), letter,
                             ord("x"))).astype(np.uint8)
    has_suffix = ~np.isin(letter, (ord("p"), ord("t")))
    names, name_off = _ragged(
        [lead[:, None], digits, letter[:, None]],
        [np.ones(n, int), np.full(n, 7), has_suffix.astype(int)])

    # cigars: 100M, or 7S93M for the clip-shifted copy, none when unmapped
    n_cig = np.where(unmapped, 0, np.where(clip > 0, 2, 1))
    op0 = np.where(clip > 0, (clip << 4) | 4, (READ_LEN << 4) | 0)
    op1 = ((READ_LEN - clip) << 4) | 0
    cig_mat = np.stack([op0, op1], axis=1).astype(np.uint32)
    cig_keep = np.arange(2)[None, :] < n_cig[:, None]
    cigars = cig_mat[cig_keep]
    cigar_off = np.zeros(n + 1, np.int64)
    np.cumsum(n_cig, out=cigar_off[1:])
    reflen = np.where(unmapped, 0, READ_LEN - clip).astype(np.int64)

    # random ACGT bases (2 bits of entropy in each 4-bit code) and
    # run-structured quals: zlib-6 sees ~3x, like genomic BAM, so full
    # BGZF blocks stay under the device kernel's 32 KiB compressed cap
    total = n * READ_LEN
    seqs = (1 << rng.integers(0, 4, total, dtype=np.uint8)).astype(np.uint8)
    quals = np.repeat(
        rng.integers(28, 42, (total + 9) // 10, dtype=np.uint8), 10)[:total]

    # tags: RG:Z:rg0|rg1 + NM:C:<0..4>; the unmapped kinds carry none
    rg_digit = (ord("0") + idx % 2).astype(np.uint8)
    nm = rng.integers(0, 5, n).astype(np.uint8)
    tag_mat = np.empty((n, 11), np.uint8)
    tag_mat[:, :5] = np.frombuffer(b"RGZrg", np.uint8)
    tag_mat[:, 5] = rg_digit
    tag_mat[:, 6] = 0
    tag_mat[:, 7:10] = np.frombuffer(b"NMC", np.uint8)
    tag_mat[:, 10] = nm
    tags, tag_off = _ragged([tag_mat], [np.where(tagged, 11, 0)])

    beg = np.maximum(pos, 0).astype(np.int64)
    batch = ReadBatch(
        refid=refid, pos=pos.astype(np.int32),
        mapq=rng.integers(0, 61, n).astype(np.uint8),
        bin=np.where(unplaced, 4680,
                     _reg2bin(beg, beg + np.maximum(reflen, 1))
                     ).astype(np.uint16),
        flag=flag, next_refid=next_refid, next_pos=next_pos, tlen=tlen,
        name_offsets=name_off, names=names,
        cigar_offsets=cigar_off, cigars=cigars,
        seq_offsets=np.arange(0, total + 1, READ_LEN, dtype=np.int64),
        seqs=seqs, quals=quals, tag_offsets=tag_off, tags=tags,
    )
    order = rng.permutation(n)
    return SamHeader.build(REFS), batch.take(order), reflen[order]


def reblock_bgzf(path: str, payload: int) -> None:
    """Rewrite a BGZF file with ``payload``-byte blocks (stdlib zlib).
    Only the CPU-waived tier-1 run uses this: the Pallas interpreter
    cannot run the full-size block geometry in test time."""
    import gzip

    with open(path, "rb") as f:
        data = gzip.decompress(f.read())
    out = bytearray()
    for o in range(0, len(data), payload):
        chunk = data[o: o + payload]
        c = zlib.compressobj(6, zlib.DEFLATED, -15, 8)
        comp = c.compress(chunk) + c.flush()
        out += struct.pack("<4BI2BH2BHH", 31, 139, 8, 4, 0, 0, 255, 6,
                           66, 67, 2, len(comp) + 25)
        out += comp + struct.pack("<II", zlib.crc32(chunk), len(chunk))
    out += bytes.fromhex(
        "1f8b08040000000000ff0600424302001b0003000000000000000000")
    with open(path, "wb") as f:
        f.write(out)


# ---------------------------------------------------------------------------
# The plain references (numpy; share no code with ops/*)
# ---------------------------------------------------------------------------


def ref_flagstat(flag: np.ndarray) -> dict:
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


def ref_depth(refid, pos, flag, reflen, window: int) -> dict:
    """Windowed depth per contig: +1 at the alignment's first window,
    -1 past its last, cumulative sum (mapped, placed records only)."""
    out = {}
    for r, (_name, length) in enumerate(REFS):
        nw = max(1, -(-length // window))
        sel = (refid == r) & ((flag & 0x4) == 0)
        p = pos[sel].astype(np.int64)
        e = p + np.maximum(reflen[sel], 1)
        lo = np.clip(p // window, 0, nw - 1)
        hi = np.clip((e - 1) // window, 0, nw - 1)
        diff = np.bincount(lo, minlength=nw + 1).astype(np.int64)
        diff -= np.bincount(hi + 1, minlength=nw + 1)
        out[r] = np.cumsum(diff)[:nw].astype(np.int32)
    return out


# ---------------------------------------------------------------------------
# Harness: checks, env knobs, compile/counter accounting
# ---------------------------------------------------------------------------


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


def assert_columns_equal(got, want, columns, what: str) -> None:
    for name in columns:
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        check(a.dtype == b.dtype, f"{what}: {name} dtype {a.dtype}!={b.dtype}")
        check(a.shape == b.shape and np.array_equal(a, b),
              f"{what}: column {name} differs")


def assert_depth_equal(got: dict, want: dict, what: str) -> None:
    check(sorted(got) == sorted(want), f"{what}: depth contigs differ")
    for r in want:
        check(np.array_equal(np.asarray(got[r]), want[r]),
              f"{what}: depth of refid {r} differs")


def sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 22), b""):
            h.update(chunk)
    return h.hexdigest()


def gzip_t(path: str) -> None:
    """External conformance: BGZF is multi-member gzip."""
    subprocess.run(["gzip", "-t", path], check=True, timeout=600)


@contextlib.contextmanager
def env_knobs(**values: str):
    """Set env-only knobs for one leg (none is set outside a leg)."""
    for k, v in values.items():
        os.environ[k] = v
    try:
        yield
    finally:
        for k in values:
            os.environ.pop(k, None)


def device_knobs(*extra: str):
    """Arm the env-only device knobs a TPU user sets, for one leg."""
    return env_knobs(**{k: "1" for k in DEVICE_ENV + extra})


class CompileCounter:
    """jax.monitoring counts of XLA compile requests that went through
    the persistent cache, how many it served, and how many it stored."""

    EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_stores",
    }

    def __init__(self) -> None:
        import jax.monitoring

        self.counts = {v: 0 for v in self.EVENTS.values()}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        key = self.EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)

    def since(self, before: dict) -> dict:
        return {k: self.counts[k] - before[k] for k in self.counts}


def sorted_bam_options() -> tuple:
    """BAM + BAI + SBI: the README's sorted-write contract."""
    from disq_tpu.api import (
        BaiWriteOption, ReadsFormatWriteOption, SbiWriteOption)

    return (ReadsFormatWriteOption.BAM, BaiWriteOption.ENABLE,
            SbiWriteOption.ENABLE)


def device_counters() -> dict:
    """The ``device.*`` counters the smoke's verdict reads."""
    from disq_tpu.runtime.tracing import telemetry_snapshot

    counters = telemetry_snapshot().get("counters", {})
    return {name: counters[name] for name in sorted(counters)
            if name.startswith("device.")}


def counter_value(counters: dict, name: str, label: str = "") -> float:
    series = counters.get(name, {})
    return float(series.get(label, 0))


# ---------------------------------------------------------------------------
# Legs
# ---------------------------------------------------------------------------


class Smoke:
    def __init__(self, args, workdir: str) -> None:
        self.args = args
        self.dir = workdir
        self.input = os.path.join(workdir, "input.bam")
        self.legs: dict = {}
        self.notes: dict = {}
        self.compiles = CompileCounter()

    def path(self, name: str) -> str:
        return os.path.join(self.dir, name)

    def leg(self, name: str, fn, pass_no: int) -> None:
        before = self.compiles.snapshot()
        t0 = time.perf_counter()
        fn()
        wall = time.perf_counter() - t0
        row = self.legs.setdefault(name, {})
        row["cold_s" if pass_no == 0 else "second_s"] = round(wall, 3)
        row["compiles_cold" if pass_no == 0 else "compiles_second"] = \
            self.compiles.since(before)
        print(f"[pass {pass_no}] {name}: {wall:.2f}s "
              f"compiles={self.compiles.since(before)}", flush=True)

    # -- storages -----------------------------------------------------------

    def host_storage(self):
        from disq_tpu import ReadsStorage

        return (ReadsStorage.make_default().executor_workers(4)
                .writer_workers(4).num_shards(4)
                .split_size(self.args.split_size))

    def device_storage(self):
        return self.host_storage().resident_decode()

    # -- set-up: input + truth ----------------------------------------------

    def make_input(self) -> None:
        from disq_tpu.api import ReadsDataset

        t0 = time.perf_counter()
        header, truth, reflen = generate_reads(
            self.args.records, self.args.seed)
        self.header, self.truth = header, truth
        self.host_storage().write(
            ReadsDataset(header=header, reads=truth), self.input)
        if self.args.block_payload:
            reblock_bgzf(self.input, self.args.block_payload)
        size = os.path.getsize(self.input)
        self.want_flagstat = ref_flagstat(truth.flag)
        self.want_depth = ref_depth(truth.refid, truth.pos, truth.flag,
                                    reflen, DEPTH_WINDOW)
        on_chr1 = truth.pos[(truth.refid == 0) & (reflen > 0)]
        start = max(0, int(on_chr1[np.argmin(np.abs(on_chr1 - 1_000_000))])
                    - PILEUP_BP // 2)
        self.pileup = ("pileup", 0, start, start + PILEUP_BP)
        # serve regions: 50 kb around each contig's median read, plus
        # one that runs off the end of the populated span
        self.regions = [("chr1", SPAN_BP - 10_000, SPAN_BP + 100_000)]
        for r, (contig, _length) in enumerate(REFS):
            mid = int(np.median(truth.pos[(truth.refid == r) & (reflen > 0)]))
            self.regions.append((contig, max(1, mid - 20_000), mid + 30_000))
        self.notes["input"] = {
            "records": int(truth.count), "bgzf_bytes": size,
            "bgzf_mib": round(size / 2 ** 20, 1),
            "splits": -(-size // self.args.split_size),
            "seed": self.args.seed,
            "setup_s": round(time.perf_counter() - t0, 2),
        }
        print(f"input: {self.notes['input']}", flush=True)
        if self.args.full_size:
            check(truth.count >= 2_000_000, "input has < 2,000,000 records")
            check(size > self.args.split_size,
                  "input BGZF does not fill one default split")

    # -- host reference -----------------------------------------------------

    def host_read(self) -> None:
        ds = self.host_storage().read(self.input)
        check(ds.count() == self.truth.count, "host read: record count")
        assert_columns_equal(ds.reads, self.truth, ALL_COLUMNS,
                             "host read vs generator")
        self.host_ds = ds

    def host_sort_write(self) -> None:
        out = self.path("host_sorted.bam")
        self.host_sorted = self.host_ds.coordinate_sorted()
        self.host_storage().write(
            self.host_sorted, out, *sorted_bam_options())
        gzip_t(out)
        self.notes["sorted_sha256"] = {
            ext or "bam": sha256_file(out + ext)
            for ext in ("", ".bai", ".sbi")}

    # -- device read --------------------------------------------------------

    def device_read(self) -> None:
        with device_knobs():
            ds = self.device_storage().read(self.input)
            check(getattr(ds.reads, "device_backed", False),
                  "device read: dataset is not device-backed")
            check(ds.count() == self.truth.count, "device read: count")
            check(ds.flagstat() == self.want_flagstat,
                  "device read: flagstat != reference")
            assert_columns_equal(ds.reads, self.host_ds.reads,
                                 FIXED_COLUMNS, "device read vs host read")
            assert_depth_equal(ds.depth(DEPTH_WINDOW), self.want_depth,
                               "device read")
            assert_columns_equal(ds.reads, self.host_ds.reads,
                                 ALL_COLUMNS[8:], "device read vs host read")
        if getattr(self, "device_ds", None) is not None:
            self.device_ds.reads.release()
        self.device_ds = ds

    def assert_sorted_identical(self, out: str, what: str) -> None:
        for ext in ("", ".bai", ".sbi"):
            check(sha256_file(out + ext)
                  == self.notes["sorted_sha256"][ext or "bam"],
                  f"{what}: sorted{ext or '.bam'} not byte-identical to "
                  "the host path's")

    def device_sort_write(self) -> None:
        out = self.path("device_sorted.bam")
        with device_knobs():
            self.device_storage().write(
                self.device_ds.coordinate_sorted(), out,
                *sorted_bam_options())
        gzip_t(out)
        self.assert_sorted_identical(out, "device sort+write")

    # -- operator chain -----------------------------------------------------

    def operators(self) -> None:
        chain = (("filter", FILTER_SPEC), "sort", "markdup", "rgstats",
                 self.pileup)
        host_out, host_stats = self.host_ds.pipeline(*chain)
        with device_knobs():
            dev_out, dev_stats = self.device_ds.pipeline(*chain)
            check(getattr(dev_out.reads, "device_backed", False),
                  "operator chain left the resident dataset")
            for op in ("markdup", "rgstats"):
                check(dev_stats[op] == host_stats[op],
                      f"operator chain: {op} stats differ from host chain")
            check(np.array_equal(dev_stats["pileup"]["coverage"],
                                 host_stats["pileup"]["coverage"]),
                  "operator chain: pileup coverage differs from host chain")
            assert_columns_equal(dev_out.reads, host_out.reads,
                                 FIXED_COLUMNS, "operator chain output")
            dev_out.reads.release()
        check(host_stats["markdup"]["duplicates"] > 0
              and int(host_stats["pileup"]["coverage"].sum()) > 0,
              "operator chain did no work on this input")
        self.notes["operators"] = {
            "kept": int(host_out.count()), "markdup": host_stats["markdup"]}

    # -- CRAM ---------------------------------------------------------------

    def cram(self) -> None:
        """Under the writer's defaults no stream of a framework-written
        CRAM reaches the device kernel: it codes quality scores with
        order-1 rANS (the kernel is order-0) and a 10,000-record slice's
        quality block is far over the kernel's 64 KiB stream cap. So
        this leg writes order-0 (``DISQ_TPU_CRAM_RANS_O1=0``) and
        interleaves the contigs in 600-record runs — slices are
        single-contig runs, 600 x 100 B of quals fits the cap."""
        from disq_tpu.api import ReadsDataset

        out = self.path("subset.cram")
        reads = self.host_sorted.reads
        n = min(self.args.cram_records, reads.count)
        starts = np.concatenate(
            [[0], np.cumsum(np.bincount(reads.refid[reads.refid >= 0],
                                        minlength=len(REFS)))])
        run = 600
        idx = np.concatenate([
            np.arange(min(starts[c] + k * run, starts[c + 1]),
                      min(starts[c] + (k + 1) * run, starts[c + 1]))
            for k in range(max(1, -(-n // (len(REFS) * run))))
            for c in range(len(REFS))])[:n]
        subset = ReadsDataset(header=self.header, reads=reads.take(idx))
        with env_knobs(DISQ_TPU_CRAM_RANS_O1="0"):
            self.host_storage().write(subset, out)
        want = self.host_storage().read(out)
        check(want.count() == len(idx), "CRAM: host read record count")
        assert_columns_equal(want.reads, subset.reads, FIXED_COLUMNS,
                             "CRAM host read vs written records")
        with device_knobs("DISQ_TPU_DEVICE_RANS"):
            got = self.host_storage().read(out)
        assert_columns_equal(got.reads, want.reads, ALL_COLUMNS,
                             "CRAM device-rANS read vs host read")
        self.notes["cram_records"] = int(len(idx))

    # -- serve --------------------------------------------------------------

    def serve(self) -> None:
        """A handful of clients at once: one region each, a reads query
        then a stats query, against a direct host-path traversal read."""
        from concurrent.futures import ThreadPoolExecutor

        from disq_tpu.api import Interval, TraversalParameters, serve

        path = self.path("host_sorted.bam")
        want = []
        for contig, start, end in self.regions:
            tp = TraversalParameters(
                intervals=[Interval(contig, start, end)])
            ds = self.host_storage().read(path, traversal=tp)
            want.append((ds.count(), ref_flagstat(np.asarray(ds.reads.flag))))

        def client(k: int) -> None:
            contig, start, end = self.regions[k]
            count, fs = want[k]
            doc = {"dataset": "smoke", "tenant": f"client{k}",
                   "intervals": [
                       {"contig": contig, "start": start, "end": end}]}
            reads = _post(handle.address, "/query/reads",
                          {**doc, "limit": 2})
            check(reads["count"] == count,
                  f"serve /query/reads {contig}:{start}-{end}: count "
                  f"{reads['count']} != direct read {count}")
            stats = _post(handle.address, "/query/stats",
                          {**doc, "stat": "flagstat"})
            check(stats["count"] == count and stats["flagstat"] == fs,
                  f"serve /query/stats {contig}:{start}-{end} differs "
                  "from the direct read")

        with device_knobs():
            handle = serve({"smoke": path}, port=0)
            try:
                with ThreadPoolExecutor(len(self.regions)) as pool:
                    list(pool.map(client, range(len(self.regions))))
            finally:
                handle.close()
        check(sum(c for c, _ in want) > 0, "serve regions held no reads")
        self.notes["serve_counts"] = [c for c, _ in want]

    # -- four-chip mesh -----------------------------------------------------

    def mesh(self) -> None:
        from disq_tpu.runtime import device_service
        from disq_tpu.runtime.tracing import telemetry_snapshot

        out = self.path("mesh_sorted.bam")
        out_whole = self.path("mesh_sorted_whole_splits.bam")
        before = device_counters()
        # the service snapshots its dispatch devices when it starts, so
        # the mesh knob is armed before the first mesh-leg submission
        device_service.shutdown_service()
        with device_knobs():
            os.environ["DISQ_TPU_MESH"] = "4"
            try:
                # one submission's lanes stay on one chip (the service
                # hands a whole split to the least-loaded device queue),
                # so the mesh read is cut into >= 8 splits: at the
                # default split size this input is 2 splits = 2 chips
                split = max(4096, os.path.getsize(self.input) // 8)
                storage = self.device_storage().mesh(4).split_size(split)
                ds = storage.read(self.input)
                mesh = ds.reads.mesh
                check(mesh is not None, "mesh leg: dataset carries no mesh")
                devs = list(mesh.devices.flat)
                check(len({d.id for d in devs}) == 4
                      and (self.args.allow_cpu
                           or all(d.platform == "tpu" for d in devs)),
                      f"mesh leg: mesh is not 4 distinct TPU devices: {devs}")
                check(ds.flagstat() == self.want_flagstat,
                      "mesh leg: flagstat != reference")
                assert_depth_equal(ds.depth(DEPTH_WINDOW), self.want_depth,
                                   "mesh leg")
                storage.write(ds, out, *sorted_bam_options(), sort=True)
                ds.reads.release()
                # and the shape the benchmark's wgs_mesh4 runs: the
                # same chain at the configured (default 128 MiB) split
                # size, where a chip is fed one whole split at a time
                whole = self.device_storage().mesh(4)
                ds = whole.read(self.input)
                check(ds.reads.mesh is not None
                      and ds.flagstat() == self.want_flagstat,
                      "mesh leg at the default split size: no mesh, or "
                      "flagstat != reference")
                whole.write(ds, out_whole, *sorted_bam_options(), sort=True)
                ds.reads.release()
            finally:
                os.environ.pop("DISQ_TPU_MESH", None)
                device_service.shutdown_service()
        self.assert_sorted_identical(out, "mesh leg")
        self.assert_sorted_identical(
            out_whole, "mesh leg at the default split size")
        after = device_counters()
        fills = telemetry_snapshot()["gauges"].get("device.lane_fill", {})
        rows = sorted(k for k in fills if k.startswith("device="))
        check(len(rows) == 4,
              f"mesh leg: device.lane_fill rows {sorted(fills)} != 4 devices")
        moved = {
            name: counter_value(after, name) - counter_value(before, name)
            for name in ("device.mesh.exchange_bytes",
                         "device.mesh.reshard_bytes", "device.mesh.batches")}
        check(all(v > 0 for v in moved.values()),
              f"mesh leg: device.mesh.* counters did not move: {moved}")
        check(counter_value(after, "device.mesh.sort_host_fallback")
              == counter_value(before, "device.mesh.sort_host_fallback"),
              "mesh leg: the sort fell back to the host argsort")
        self.notes["mesh"] = {
            "devices": [str(d) for d in devs], "lane_fill_rows": rows,
            "split_size": split,
            **{k: int(v) for k, v in moved.items()}}


def _post(address: str, path: str, doc: dict) -> dict:
    req = urllib.request.Request(
        f"http://{address}{path}", data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=300) as resp:
        return json.loads(resp.read())


def fence_probe(jax) -> dict:
    """Does ``jax.block_until_ready`` fence on this installation? Time
    one long computation: dispatch returns early, block_until_ready
    must absorb the run, and a fetch after it must find nothing left."""
    import jax.numpy as jnp

    @jax.jit
    def long_kernel(x):
        return jax.lax.fori_loop(
            0, 400, lambda _i, a: jnp.tanh(a @ a) * 0.5 + 0.1, x)

    # a few tenths of a second of work on either backend
    side = 2048 if jax.default_backend() == "tpu" else 384
    x = jnp.ones((side, side), jnp.float32)
    np.asarray(long_kernel(x)[:1, :1])             # compile + warm both
    t0 = time.perf_counter()
    y = long_kernel(x)
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(y)
    t_block = time.perf_counter() - t0
    np.asarray(y[:1, :1])
    t_fetch = time.perf_counter() - t0 - t_block
    t1 = time.perf_counter()
    np.asarray(long_kernel(x)[:1, :1])             # the fetch-only fence
    t_asarray = time.perf_counter() - t1
    fences = t_fetch < 0.25 * t_block and t_block > 0.5 * t_asarray
    return {"dispatch_s": round(t_dispatch, 4), "block_s": round(t_block, 4),
            "fetch_after_block_s": round(t_fetch, 4),
            "fetch_only_s": round(t_asarray, 4), "fences": bool(fences)}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--records", type=int, default=2_400_000,
                    help="approximate record count of the input")
    ap.add_argument("--cram-records", type=int, default=60_000,
                    help="records of the CRAM leg (the host CRAM writer "
                         "sets the time)")
    ap.add_argument("--chips", type=int, default=0,
                    help="demand at least this many chips; 4 makes a "
                         "skipped mesh leg a failure")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="waive the TPU requirement (tier-1 test only: "
                         "kernels then run in the Pallas interpreter)")
    ap.add_argument("--block-payload", type=int, default=0,
                    help="re-block the input BGZF to this payload size "
                         "(interpreter-sized blocks for --allow-cpu)")
    ap.add_argument("--split-size", type=int, default=128 << 20,
                    help="read split size (default: the library's)")
    args = ap.parse_args(argv)
    # a lowered --records is a debugging run and says so in its result
    args.full_size = args.records >= 2_000_000

    import jax
    import jaxlib

    from disq_tpu import native
    from disq_tpu.runtime import device_service
    from disq_tpu.util import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    backend = jax.default_backend()
    if backend != "tpu" and not args.allow_cpu:
        print(f"chip_smoke: jax.default_backend() is {backend!r}, not "
              "'tpu' — refusing to run (no result)", file=sys.stderr)
        return 2
    if args.chips and len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from importlib import metadata

    versions = {"python": sys.version.split()[0], "jax": jax.__version__,
                "jaxlib": jaxlib.__version__, "numpy": np.__version__}
    try:
        versions["libtpu"] = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        versions["libtpu"] = None
    # fails here (ImportError) when the library cannot be built: the
    # pure-Python fallback would pass every equality check, ten times
    # slower
    variant = native.build_variant()
    print(f"device: {device}  backend: {backend}", flush=True)
    print(f"versions: {versions}", flush=True)
    print(f"compile cache: {cache_dir}  native: {variant}", flush=True)

    fence = fence_probe(jax)
    print(f"block_until_ready: {fence}", flush=True)
    check(fence["fences"], "jax.block_until_ready does not fence here")

    workdir = tempfile.mkdtemp(prefix="disq_chip_smoke_")
    t_start = time.perf_counter()
    try:
        smoke = Smoke(args, workdir)
        smoke.make_input()
        legs = [("host_read", smoke.host_read),
                ("host_sort_write", smoke.host_sort_write),
                ("device_read", smoke.device_read),
                ("device_sort_write", smoke.device_sort_write),
                ("operators", smoke.operators),
                ("cram", smoke.cram),
                ("serve", smoke.serve)]
        if len(devices) >= 4:
            legs.append(("mesh", smoke.mesh))
        else:
            check(args.chips < 4, "mesh leg demanded but < 4 devices")
            print(f"mesh: skipped: {len(devices)} device", flush=True)
            smoke.legs["mesh"] = {"skipped": f"{len(devices)} device"}
        for pass_no in (0, 1):
            for name, fn in legs:
                smoke.leg(name, fn, pass_no)
        smoke.device_ds.reads.release()
        device_service.shutdown_service()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    # -- verdict ------------------------------------------------------------
    counters = device_counters()
    launches = counters.get("device.kernel_launches", {})
    fallbacks = counters.get("device.host_fallback_blocks", {})
    print("device counters:", flush=True)
    for name, series in counters.items():
        print(f"  {name}: {series}", flush=True)
    for kernel in ("inflate_simd", "columnar_parse", "rans_simd"):
        check(launches.get(f"kernel={kernel}", 0) > 0,
              f"no kernel={kernel} launch was booked")
    check(fallbacks.get("reason=flagged", 0) == 0,
          f"device.host_fallback_blocks{{reason=flagged}} = "
          f"{fallbacks.get('reason=flagged')} on well-formed input")
    from disq_tpu.ops import inflate_simd

    served = inflate_simd.last_stats["device_lanes"]
    check(served > 0, "no BGZF block was served by the device")
    cache_entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) \
        else 0
    check(cache_entries > 0, f"compile cache {cache_dir} holds no entries")

    summary = {
        "ok": True, "device": device, "backend": backend,
        "full_size": args.full_size,
        "interpret": backend != "tpu", "versions": versions,
        "native": variant, "compile_cache": {
            "dir": cache_dir, "entries": cache_entries,
            **smoke.compiles.snapshot()},
        "block_until_ready": fence, "input": smoke.notes.pop("input"),
        "legs": smoke.legs, "notes": smoke.notes,
        "blocks": {"device_served": served,
                   "host_oversize": inflate_simd.last_stats["host_big"],
                   "host_flagged": inflate_simd.last_stats["host_fallback"]},
        "counters": {
            "kernel_launches": launches, "host_fallback_blocks": fallbacks,
            "bytes_to_device": counter_value(
                counters, "device.bytes_to_device"),
            "bytes_to_host": counter_value(counters, "device.bytes_to_host")},
        "wall_s": round(time.perf_counter() - t_start, 1),
        "claim": None,
    }
    print(json.dumps(summary, default=str), flush=True)
    # the verdict: exactly these keys, and nothing on stdout after it
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
