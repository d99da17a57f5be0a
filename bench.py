#!/usr/bin/env python
"""Benchmark harness — the BASELINE.json measurement matrix.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

Measurement protocol (repeatability):

- Every timed quantity is measured ``REPS`` times after a warm-up run;
  the reported value is the **median** and the JSON carries the spread
  ``(max - min) / median`` plus the raw per-rep numbers, so a single
  noisy run can never masquerade as a regression (judge-measured 3.5x
  run-to-run variance on this box with the old single-run harness).
- ``vs_baseline`` compares medians.

Baseline (the thing disq actually delegates to, SURVEY.md §2.8): an
htsjdk-style record-at-a-time object decode — but run on **all cores**
via multiprocessing, with record-aligned splits taken from the SBI
index exactly the way disq's Spark executors take them. The previous
single-threaded strawman flattered the framework; this one does not.

Per-config results live under ``"configs"`` in the same JSON line; the
primary metric stays config 1 (BAM decode records/sec) for
round-over-round comparability.
"""

import json
import multiprocessing
import os
import statistics
import struct
import sys
import tempfile
import time
import zlib

import numpy as np

# Top level stays free of jax and disq_tpu: main()'s spawn pool
# re-imports this module in every baseline worker, and a worker that
# touched jax would go after the chip the parent holds.

N_RECORDS = int(os.environ.get("BENCH_RECORDS", "300000"))
REPS = int(os.environ.get("BENCH_REPS", "5"))
BASE_REPS = int(os.environ.get("BENCH_BASE_REPS", "3"))
REFS = [("chr1", 248_956_422), ("chr2", 242_193_529), ("chr20", 64_444_167)]


def synth_bam(path: str, n: int) -> None:
    """Deterministic synthetic BAM written via the framework itself."""
    from disq_tpu.bam.columnar import ReadBatch
    from disq_tpu.bam.header import SamHeader
    from disq_tpu.bam.sink import BamSink
    from disq_tpu.api import ReadsDataset, SbiWriteOption

    rng = np.random.default_rng(0)
    readlen = 100
    refid = rng.integers(0, len(REFS), n).astype(np.int32)
    pos = rng.integers(0, 1_000_000, n).astype(np.int32)
    flag = np.zeros(n, dtype=np.uint16)
    names_list = [f"r{i:08d}".encode() for i in range(n)]
    name_len = np.array([len(x) for x in names_list], dtype=np.int64)
    name_off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(name_len, out=name_off[1:])
    seq_off = np.arange(0, (n + 1) * readlen, readlen, dtype=np.int64)
    cigars = ((readlen << 4) | 0) * np.ones(n, dtype=np.uint32)
    batch = ReadBatch(
        refid=refid, pos=pos, mapq=np.full(n, 60, np.uint8),
        bin=np.zeros(n, np.uint16), flag=flag,
        next_refid=np.full(n, -1, np.int32), next_pos=np.full(n, -1, np.int32),
        tlen=np.zeros(n, np.int32),
        name_offsets=name_off, names=np.frombuffer(b"".join(names_list), np.uint8).copy(),
        cigar_offsets=np.arange(n + 1, dtype=np.int64), cigars=cigars,
        seq_offsets=seq_off,
        # motif-drawn bases + run-structured quals: zlib sees ~3-4x like
        # real genomic data (uniform-random bytes compress ~1.4x and
        # would misrepresent every codec-path measurement)
        seqs=np.tile(rng.integers(1, 16, 4096, dtype=np.uint8),
                     (n * readlen + 4095) // 4096)[: n * readlen],
        quals=np.repeat(rng.integers(28, 42, (n * readlen + 19) // 20,
                                     dtype=np.uint8), 20)[: n * readlen],
        tag_offsets=np.zeros(n + 1, dtype=np.int64), tags=np.zeros(0, np.uint8),
    )
    header = SamHeader.build(REFS)
    ds = ReadsDataset(header=header, reads=batch)

    class _Cfg:
        _num_shards = 8

    BamSink(_Cfg()).save(ds, path, (SbiWriteOption.ENABLE,))


# ---------------------------------------------------------------------------
# Baseline: htsjdk-style per-record object decode, all cores, SBI splits.
# Self-contained (stdlib only) so workers never import the framework.
# ---------------------------------------------------------------------------

def _read_sbi_offsets(path: str):
    """Record-aligned virtual offsets from the SBI index. Parsed with
    the framework reader — only the *workers* must stay stdlib-only."""
    from disq_tpu.index.sbi import SbiIndex

    with open(path + ".sbi", "rb") as f:
        return SbiIndex.from_bytes(f.read()).offsets.tolist()


def _inflate_range(data: bytes, cend_incl: int, uend: int) -> bytes:
    """Inflate BGZF blocks from ``data[0]`` up to (and when ``uend > 0``
    partially including) the block at offset ``cend_incl``."""
    out = bytearray()
    pos = 0
    while pos < cend_incl:
        xlen = struct.unpack_from("<H", data, pos + 10)[0]
        bsize = struct.unpack_from("<H", data, pos + 16)[0] + 1
        comp = data[pos + 12 + xlen: pos + bsize - 8]
        out += zlib.decompress(comp, wbits=-15)
        pos += bsize
    if uend > 0:
        xlen = struct.unpack_from("<H", data, pos + 10)[0]
        bsize = struct.unpack_from("<H", data, pos + 16)[0] + 1
        comp = data[pos + 12 + xlen: pos + bsize - 8]
        out += zlib.decompress(comp, wbits=-15)[:uend]
    return bytes(out)


def _baseline_worker(args) -> int:
    """One executor: inflate its record-aligned split, decode every record
    into Python objects (htsjdk execution model), return the count."""
    path, vstart, vend = args
    cstart, ustart = vstart >> 16, vstart & 0xFFFF
    cend, uend = vend >> 16, vend & 0xFFFF
    # Read only this split's byte range (+1 BGZF block bound for the
    # partially-consumed end block) — executors never hold the whole file.
    with open(path, "rb") as f:
        f.seek(cstart)
        data = f.read(cend - cstart + (0x10000 if uend else 0))
    payload = _inflate_range(data, cend - cstart, uend)
    p = ustart
    count = 0
    while p < len(payload):
        (block_size,) = struct.unpack_from("<i", payload, p)
        refid, rpos, l_name, mapq, b, n_cig, flag, l_seq = struct.unpack_from(
            "<iiBBHHHi", payload, p + 4
        )
        q = p + 36
        _name = payload[q: q + l_name - 1].decode()
        q += l_name
        _cigar = [
            struct.unpack_from("<I", payload, q + 4 * k)[0] for k in range(n_cig)
        ]
        q += 4 * n_cig
        _seq = bytes(payload[q: q + (l_seq + 1) // 2])
        q += (l_seq + 1) // 2
        _qual = bytes(payload[q: q + l_seq])
        count += 1
        p += 4 + block_size
    return count


def baseline_decode(pool, path: str, splits) -> int:
    return sum(pool.map(_baseline_worker, splits))


def make_splits(path: str, n_splits: int):
    """Record-aligned splits from the SBI index (disq's own split scheme)."""
    # offsets[0] is the first record's virtual offset (past the BAM
    # header); the final entry is end-of-data. n_splits+1 fenceposts.
    offsets = _read_sbi_offsets(path)
    idx = np.linspace(0, len(offsets) - 1, n_splits + 1).round().astype(int)
    marks = [offsets[i] for i in idx]
    return [
        (path, marks[i], marks[i + 1])
        for i in range(n_splits)
        if marks[i] < marks[i + 1]
    ]


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------


def _timed(fn, reps: int):
    """Run ``fn`` reps times (after the caller's warm-up); return
    (median_seconds, [seconds...])."""
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return statistics.median(times), times


def _spread(times) -> float:
    med = statistics.median(times)
    return round((max(times) - min(times)) / med, 3) if med else 0.0


def secondary_configs(storage, path: str, tmp: str, reps: int) -> dict:
    """BASELINE.json matrix configs 3-5 (config 2 differs from 1 only in
    input scale). Each reports its own median + spread."""
    from disq_tpu import VariantsStorage
    from disq_tpu.api import (
        BaiWriteOption, Interval, TraversalParameters, VariantsDataset,
    )
    from disq_tpu.vcf.columnar import parse_vcf_lines
    from disq_tpu.vcf.header import VcfHeader

    vcf_hdr_text = (
        "##fileformat=VCFv4.3\n"
        '##contig=<ID=chr1,length=248956422>\n'
        '##INFO=<ID=DP,Number=1,Type=Integer,Description="depth">\n'
        "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\n"
    )

    out = {}
    n = N_RECORDS

    # --- 4: unsorted -> coordinate sort -> write BAM + BAI ---
    sorted_path = os.path.join(tmp, "sorted.bam")

    def run4():
        ds = storage.read(path)
        storage.write(ds.coordinate_sorted(), sorted_path,
                      BaiWriteOption.ENABLE)

    run4()
    med4, t4 = _timed(run4, reps)
    out["4_sort_write_bam_bai"] = {
        "records_per_sec": round(n / med4, 1), "spread": _spread(t4),
    }

    # --- 3: interval-filtered read via traversal + BAI ---
    tp = TraversalParameters(intervals=(
        Interval("chr1", 1, 400_000),
        Interval("chr20", 200_000, 900_000),
    ))

    def run3():
        storage.read(sorted_path, traversal=tp).count()

    run3()
    med3, t3 = _timed(run3, reps)
    sel = storage.read(sorted_path, traversal=tp).count()
    out["3_interval_read_bai"] = {
        "wall_sec": round(med3, 4), "records_selected": sel,
        "spread": _spread(t3),
    }

    # --- 5a: CRAM write+read (reference-less: bases embedded) ---
    cram_path = os.path.join(tmp, "bench.cram")
    storage.write(storage.read(path).coordinate_sorted(), cram_path)

    def run5():
        assert storage.read(cram_path).count() == n

    run5()
    med5, t5 = _timed(run5, reps)
    out["5a_cram_read"] = {
        "records_per_sec": round(n / med5, 1), "spread": _spread(t5),
    }

    # --- 5b: VCF/BCF read ---
    nv = 100_000
    rng = np.random.default_rng(1)
    pos = np.sort(rng.integers(1, 10_000_000, nv))
    lines = [
        f"chr1\t{p}\t.\tA\tG\t50\tPASS\tDP={30 + i % 40}"
        for i, p in enumerate(pos)
    ]
    header = VcfHeader.from_text(vcf_hdr_text)
    batch = parse_vcf_lines(
        [l.encode() for l in lines], header.contig_names)
    vst = VariantsStorage.make_default()
    bcf_path = os.path.join(tmp, "bench.bcf")
    vst.write(VariantsDataset(header=header, variants=batch), bcf_path)

    def run5b():
        assert vst.read(bcf_path).count() == nv

    run5b()
    med5b, t5b = _timed(run5b, reps)
    out["5b_bcf_read"] = {
        "records_per_sec": round(nv / med5b, 1), "spread": _spread(t5b),
    }
    return out


EXEC_WORKERS = [
    int(w) for w in os.environ.get("BENCH_EXEC_WORKERS", "1,2,8").split(",")
]


def executor_scaling_config(path: str, reps: int) -> dict:
    """Config 1 parameterized by ``executor_workers``: the same BAM
    decode through the shard-pipeline executor at each worker count,
    so the fetch/inflate/decode overlap (or its absence) is a row in
    BENCH_*.json, not an assertion."""
    from disq_tpu import ReadsStorage

    rows = {}
    for w in EXEC_WORKERS:
        storage = (ReadsStorage.make_default()
                   .split_size(8 * 1024 * 1024).executor_workers(w))

        def run():
            assert storage.read(path).count() == N_RECORDS

        run()
        med, times = _timed(run, reps)
        rows[f"workers_{w}"] = {
            "records_per_sec": round(N_RECORDS / med, 1),
            "spread": _spread(times),
        }
    return {"6_bam_decode_executor_scaling": rows}


def _range_server(bodies: dict, latency_s: float = 0.0):
    """In-process HTTP range server over ``bodies`` ({path: bytes}) —
    the zero-egress remote store the scaling configs read from.
    Unknown paths 404 (an index-existence probe behaves like a store
    without the object); ``latency_s`` sleeps per GET (simulated RTT).
    Returns ``(server, base_url)``; caller owns ``server.shutdown()``."""
    import threading
    import time as _time
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_HEAD(self):
            body = bodies.get(self.path)
            if body is None:
                self.send_error(404)
                return
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.send_header("Accept-Ranges", "bytes")
            self.end_headers()

        def do_GET(self):
            body = bodies.get(self.path)
            if body is None:
                self.send_error(404)
                return
            if latency_s:
                _time.sleep(latency_s)  # simulated remote RTT
            rng = self.headers.get("Range")
            if rng and rng.startswith("bytes="):
                lo, hi = rng[len("bytes="):].split("-")
                lo, hi = int(lo), min(int(hi), len(body) - 1)
                chunk = body[lo: hi + 1]
                self.send_response(206)
                self.send_header(
                    "Content-Range", f"bytes {lo}-{hi}/{len(body)}")
            else:
                chunk = body
                self.send_response(200)
            self.send_header("Content-Length", str(len(chunk)))
            self.end_headers()
            self.wfile.write(chunk)

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, name="disq-bench-http",
                     daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


def http_read_config(path: str, reps: int) -> dict:
    """Remote-read row: the bench BAM served by an in-process HTTP
    range server (zero egress), read at each ``executor_workers`` —
    the latency-bound path the pipelined executor exists for. Each GET
    carries ``BENCH_HTTP_LATENCY_MS`` of simulated RTT (default 10 ms;
    localhost alone is CPU-bound and would misrepresent the
    latency-bound remote regime). A fresh wrapper per
    run keeps the block cache cold so every rep measures real
    range-request overlap, not cache hits."""
    from disq_tpu import ReadsStorage
    from disq_tpu.fsw import register_filesystem
    from disq_tpu.fsw.http import HttpFileSystemWrapper

    latency_s = float(os.environ.get("BENCH_HTTP_LATENCY_MS", "10")) / 1e3
    with open(path, "rb") as f:
        raw = f.read()
    srv, base = _range_server({"/bench.bam": raw}, latency_s=latency_s)
    url = base + "/bench.bam"
    rows = {}
    try:
        for w in EXEC_WORKERS:
            storage = (ReadsStorage.make_default()
                       .split_size(8 * 1024 * 1024).executor_workers(w))

            def run():
                register_filesystem(
                    "http", HttpFileSystemWrapper(block_size=1024 * 1024))
                assert storage.read(url).count() == N_RECORDS

            run()
            med, times = _timed(run, reps)
            rows[f"workers_{w}"] = {
                "records_per_sec": round(N_RECORDS / med, 1),
                "spread": _spread(times),
            }
        rows["simulated_rtt_ms"] = round(latency_s * 1e3, 1)
    finally:
        srv.shutdown()
    return {"7_http_read_executor_scaling": rows}


WRITE_WORKERS = [
    int(w) for w in os.environ.get("BENCH_WRITE_WORKERS", "1,2,4").split(",")
]


def write_scaling_config(path: str, tmp: str, reps: int) -> dict:
    """Write-path rows: the bench BAM re-written as a single merged
    file through the shard write pipeline at each ``writer_workers``
    count — once to local disk, and once with
    ``BENCH_WRITE_LATENCY_MS`` (default 100 ms — an object-store PUT
    round trip) of simulated per-write staging latency injected
    through ``FaultInjectingFileSystemWrapper`` stall faults. The latency row is the regime the pipelined writer
    exists for (parts staged to a remote object store, the reference's
    deployment shape): encode/deflate of shard *k+1* overlaps the
    staging round-trip of shard *k*, and stage workers overlap each
    other's in-flight writes. On a CPU-saturated local box the local
    row shows deflate is already hardware-bound (the native codec
    threads a single shard's blocks); the latency row shows the
    wall-clock the overlap buys back. ``num_shards`` is pinned (16) so
    the shard fan-out — not the device count of the bench host — sets
    the available overlap, and the serial driver tail (header /
    terminator / concat) is amortized as it would be at fleet shard
    counts."""
    from disq_tpu import ReadsStorage
    from disq_tpu.fsw import (
        FaultInjectingFileSystemWrapper,
        FaultSpec,
        PosixFileSystemWrapper,
        register_filesystem,
    )

    latency_s = float(os.environ.get("BENCH_WRITE_LATENCY_MS", "100")) / 1e3
    register_filesystem("benchw", FaultInjectingFileSystemWrapper(
        PosixFileSystemWrapper(),
        [FaultSpec(kind="stall", probability=1.0, stall_s=latency_s,
                   op="write")],
        scheme="benchw",
    ))
    ds = ReadsStorage.make_default().read(path)
    rows: dict = {"simulated_staging_latency_ms": round(latency_s * 1e3, 1)}
    for w in WRITE_WORKERS:
        storage = (ReadsStorage.make_default()
                   .num_shards(16).writer_workers(w))
        out = os.path.join(tmp, f"bench-write-w{w}.bam")

        def run_local():
            storage.write(ds, out)

        def run_staged():
            storage.write(ds, "benchw://" + out)

        run_local()
        med, times = _timed(run_local, reps)
        med_st, times_st = _timed(run_staged, reps)
        rows[f"workers_{w}"] = {
            "records_per_sec": round(N_RECORDS / med, 1),
            "spread": _spread(times),
            "staged_records_per_sec": round(N_RECORDS / med_st, 1),
            "staged_spread": _spread(times_st),
        }
    return {"8_bam_write_writer_scaling": rows}


def device_inflate_config(path: str) -> dict:
    """Device-kernel row: SIMD Pallas inflate MB/s over the bench BAM's
    BGZF blocks, real chip only (skipped on CPU-only hosts).

    Dispatch accounting comes from the ``device.*`` telemetry registry
    the kernel wrappers book (``device.host_fallback_blocks``,
    ``device.kernel_launches``, transfer-byte counters) — not from
    ad-hoc dict plumbing — so the row's numbers are the same ones
    ``/metrics`` and ``telemetry_report()`` expose."""
    import jax

    if jax.default_backend() != "tpu":
        return {}
    from disq_tpu.bgzf.codec import inflate_blocks_device
    from disq_tpu.bgzf.guesser import find_block_table
    from disq_tpu.fsw import PosixFileSystemWrapper
    from disq_tpu.runtime.tracing import REGISTRY

    fs = PosixFileSystemWrapper()
    blocks = [b for b in find_block_table(fs, path) if b.usize > 0]
    with open(path, "rb") as f:
        data = f.read()
    total = sum(b.usize for b in blocks)

    inflate_blocks_device(data, blocks)  # compile + warm
    fallback = REGISTRY.counter("device.host_fallback_blocks")
    launches = REGISTRY.counter("device.kernel_launches")
    h2d = REGISTRY.counter("device.bytes_to_device")
    d2h = REGISTRY.counter("device.bytes_to_host")
    base = (fallback.total(), launches.total(), h2d.total(), d2h.total())
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        inflate_blocks_device(data, blocks)
        times.append(time.perf_counter() - t0)
    med = statistics.median(times)
    reps = len(times)
    fell = int((fallback.total() - base[0]) / reps)
    return {
        "device_inflate": {
            "mb_per_sec": round(total / med / 1e6, 2),
            "raw_mb": round(total / 1e6, 2),
            "spread": _spread(times),
            "device_served_blocks": len(blocks) - fell,
            "host_fallback_blocks": fell,
            "kernel_launches": int(
                (launches.total() - base[1]) / reps),
            "bytes_to_device": int((h2d.total() - base[2]) / reps),
            "bytes_to_host": int((d2h.total() - base[3]) / reps),
            # end-to-end number includes host<->device transfer; the
            # kernel-only rate is recorded separately in TPU_KERNELS.json
            "note": "e2e incl. transfer; kernel MB/s in TPU_KERNELS.json",
        }
    }


def device_service_config(path: str) -> dict:
    """Config 9: device inflate END-TO-END through the cross-shard
    decode service (``runtime/device_service.py``) at simulated
    executor widths 1 and 4, against the kernel-only ceiling — real
    chip only.

    Each worker thread plays one executor decode stage: it submits its
    shard group's blocks via ``inflate_blocks_device`` exactly as a
    read would with ``DISQ_TPU_DEVICE_SERVICE=1``.  The row reports
    MB/s, the mean ``device.lane_fill`` over the row's launches (the
    cross-shard batching win: partial per-shard chunks coalesce into
    full 128-lane launches), and the e2e/kernel-only ratio — the
    dispatch overhead this PR exists to close."""
    import jax

    if jax.default_backend() != "tpu":
        return {}
    from concurrent.futures import ThreadPoolExecutor

    import jax.numpy as jnp

    from disq_tpu.bgzf.codec import inflate_blocks_device
    from disq_tpu.bgzf.guesser import find_block_table
    from disq_tpu.fsw import PosixFileSystemWrapper
    from disq_tpu.ops import inflate_simd as S
    from disq_tpu.runtime import device_service
    from disq_tpu.runtime.tracing import REGISTRY

    fs = PosixFileSystemWrapper()
    blocks = [b for b in find_block_table(fs, path) if b.usize > 0]
    with open(path, "rb") as f:
        data = f.read()

    # kernel-only ceiling: pre-packed chunks, launch + sync, zero
    # per-block host work (same protocol as the TPU CI lane)
    mv = memoryview(data)
    payloads, usizes = [], []
    for b in blocks:
        xlen = struct.unpack_from("<H", data, b.pos + 10)[0]
        payloads.append(mv[b.pos + 12 + xlen: b.pos + b.csize - 8])
        usizes.append(b.usize)
    small = [i for i in range(len(payloads))
             if len(payloads[i]) <= S.MAX_DEVICE_CSIZE]
    total = sum(usizes[i] for i in small)
    cw, ow = S.buckets_for([payloads[i] for i in small],
                           max(usizes[i] for i in small))
    fn = S._compiled(cw, ow, False)
    consts = S._device_const_tables()
    # pre-upload outside the timed loop (tpu_ci protocol: the ceiling
    # isolates compute from the H2D wall — charging per-rep uploads to
    # it would understate the ceiling and flatter the e2e ratio)
    packed = [
        tuple(jnp.asarray(a) for a in S._pack_chunk(
            [payloads[i] for i in small[lo: lo + 128]], cw))
        for lo in range(0, len(small), 128)
    ]

    def kernel_only():
        outs = [fn(c, l, *consts) for c, l in packed]
        for _w, m in outs:
            np.asarray(m)

    kernel_only()
    medk, timesk = _timed(kernel_only, 3)
    kernel_mbps = total / medk / 1e6

    groups = [blocks[i::16] for i in range(16)]
    fill = REGISTRY.gauge("device.lane_fill")
    rows: dict = {
        "kernel_only_mb_per_sec": round(kernel_mbps, 2),
        "kernel_only_spread": _spread(timesk),
    }
    prev = os.environ.get("DISQ_TPU_DEVICE_SERVICE")
    os.environ["DISQ_TPU_DEVICE_SERVICE"] = "1"
    try:
        for w in (1, 4):
            def run(w=w):
                with ThreadPoolExecutor(max_workers=w) as pool:
                    list(pool.map(
                        lambda g: inflate_blocks_device(data, g), groups))

            run()
            s0 = fill.state() or {"samples": 0, "mean": 0.0}
            med, times = _timed(run, 3)
            s1 = fill.state() or {"samples": 0, "mean": 0.0}
            dn = s1["samples"] - s0["samples"]
            dsum = s1["mean"] * s1["samples"] - s0["mean"] * s0["samples"]
            rows[f"workers_{w}"] = {
                "mb_per_sec": round(
                    sum(b.usize for b in blocks) / med / 1e6, 2),
                "spread": _spread(times),
                "lane_fill_mean": round(dsum / dn, 3) if dn else 0.0,
                # ratio over the SAME byte total the kernel-only row
                # measured (device-served blocks) — oversize host-side
                # blocks must not inflate the headline ratio
                "e2e_vs_kernel_ratio": round(
                    (total / med / 1e6) / kernel_mbps, 3),
            }
    finally:
        if prev is None:
            os.environ.pop("DISQ_TPU_DEVICE_SERVICE", None)
        else:
            os.environ["DISQ_TPU_DEVICE_SERVICE"] = prev
        device_service.shutdown_service()
    return {"9_device_service_inflate": rows}


def resident_decode_config(path: str) -> dict:
    """Config 10: HBM-resident fused decode (inflate → parse →
    flagstat, ``runtime/columnar.py``) against the PR8 split path —
    real chip only.

    Split path = device inflate → blob d2h → host ``decode_records``
    → flagstat with its own flag re-upload. Fused path =
    ``inflate_blocks_device(..., to_columnar=...)``: the SIMD kernel's
    still-resident output is parsed in place and flagstat consumes the
    resident flag column. Each row carries a ``d2h_bytes`` column
    sourced from ``device.bytes_to_host`` registry deltas (and the
    fused row ``d2h_avoided_bytes`` from ``device.d2h_avoided_bytes``)
    so the transfer win is measured, not inferred."""
    import jax

    if jax.default_backend() != "tpu":
        return {}
    from disq_tpu.bam.codec import decode_records, scan_record_offsets
    from disq_tpu.bam.source import read_header
    from disq_tpu.bgzf.codec import inflate_blocks_device
    from disq_tpu.bgzf.guesser import find_block_table
    from disq_tpu.fsw import PosixFileSystemWrapper
    from disq_tpu.ops.flagstat import flagstat_counts
    from disq_tpu.runtime.tracing import REGISTRY

    fs = PosixFileSystemWrapper()
    header, first_vo = read_header(fs, path)
    blocks = [b for b in find_block_table(fs, path) if b.usize > 0]
    with open(path, "rb") as f:
        data = f.read()
    total = sum(b.usize for b in blocks)
    # first record's offset inside the decoded blob: cumulative usize
    # of blocks before its block + the in-block offset
    co, uo = first_vo >> 16, first_vo & 0xFFFF
    lo_u = sum(b.usize for b in blocks if b.pos < co) + uo
    d2h = REGISTRY.counter("device.bytes_to_host")
    avoided = REGISTRY.counter("device.d2h_avoided_bytes")

    def split_path():
        blob = inflate_blocks_device(data, blocks, as_array=True)
        rec = blob[lo_u:]
        batch = decode_records(rec, scan_record_offsets(rec),
                               n_ref=header.n_ref)
        return flagstat_counts(np.asarray(batch.flag))

    def fused_path():
        batch = inflate_blocks_device(
            data, blocks, to_columnar={"n_ref": header.n_ref,
                                       "lo_u": lo_u})
        stats = batch.flagstat()
        batch.release()
        return stats

    out: dict = {}
    n_rec = None
    for name, fn in (("split", split_path), ("fused", fused_path)):
        stats = fn()  # warm (compile caches)
        n_rec = stats["total"]
        d0, a0 = d2h.total(), avoided.total()
        med, times = _timed(fn, 3)
        out[name] = {
            "mb_per_sec": round(total / med / 1e6, 2),
            "records_per_sec": round(n_rec / med, 1),
            "spread": _spread(times),
            "d2h_bytes": int((d2h.total() - d0) / len(times)),
        }
        if name == "fused":
            out[name]["d2h_avoided_bytes"] = int(
                (avoided.total() - a0) / len(times))
    out["fused_vs_split"] = round(
        out["fused"]["mb_per_sec"] / out["split"]["mb_per_sec"], 3)
    return {"10_resident_decode": out}


def device_write_config(path: str, tmp: str) -> dict:
    """Config 11: the symmetric device write path — sort + single-file
    BAM write + BAI through resident encode + device SIMD deflate
    (``DisqOptions.device_deflate`` + ``resident_decode``; the decode
    service coalesces write-shard blocks) against the host zlib path,
    at writer widths 1 and 4 — real chip only.

    Each row carries h2d/d2h byte columns from ``device.*`` registry
    deltas, so "compressed-only d2h" is measured, not asserted: the
    device rows' d2h must sit near the compressed size, far below the
    raw payload bytes the split design would have moved.  Every
    produced file is re-read through the framework reader inside the
    timed body (count asserted), so a byte-invalid stream can never
    post a throughput number."""
    import jax

    if jax.default_backend() != "tpu":
        return {}
    from disq_tpu import ReadsStorage
    from disq_tpu.api import BaiWriteOption
    from disq_tpu.runtime import device_service
    from disq_tpu.runtime.tracing import REGISTRY

    h2d = REGISTRY.counter("device.bytes_to_device")
    d2h = REGISTRY.counter("device.bytes_to_host")
    rows: dict = {}
    prev = os.environ.get("DISQ_TPU_DEVICE_SERVICE")
    os.environ["DISQ_TPU_DEVICE_SERVICE"] = "1"
    try:
        for w in (1, 4):
            for mode in ("host", "device"):
                st = (ReadsStorage.make_default().num_shards(16)
                      .writer_workers(w))
                if mode == "device":
                    st = st.resident_decode().device_deflate()
                ds = st.read(path)
                out = os.path.join(tmp, f"bench-devw-{mode}-w{w}.bam")

                def run(st=st, ds=ds, out=out):
                    st.write(ds, out, BaiWriteOption.ENABLE, sort=True)
                    assert (ReadsStorage.make_default()
                            .read(out).count() == N_RECORDS)

                run()  # warm (compiles, page cache)
                b0 = (h2d.total(), d2h.total())
                med, times = _timed(run, 3)
                rows[f"{mode}_workers_{w}"] = {
                    "records_per_sec": round(N_RECORDS / med, 1),
                    "spread": _spread(times),
                    "h2d_bytes": int((h2d.total() - b0[0]) / len(times)),
                    "d2h_bytes": int((d2h.total() - b0[1]) / len(times)),
                }
            rows[f"device_vs_host_workers_{w}"] = round(
                rows[f"device_workers_{w}"]["records_per_sec"]
                / rows[f"host_workers_{w}"]["records_per_sec"], 3)
    finally:
        if prev is None:
            os.environ.pop("DISQ_TPU_DEVICE_SERVICE", None)
        else:
            os.environ["DISQ_TPU_DEVICE_SERVICE"] = prev
        device_service.shutdown_service()
    return {"11_device_write": rows}


def mesh_pipeline_config(path: str) -> dict:
    """Config 14: the mesh-native device pipeline (``runtime/mesh.py``)
    — decode + coordinate sort + flagstat as ONE sharded program over
    the batch-axis mesh, at 1/2/4/8 devices (clamped to what the host
    has) — real chip only.

    The n_devices=1 row is the plain single-device resident pipeline
    (the mesh knob's off path), so every multi-chip row reads as a
    scaling factor against it.  Each mesh row carries the psum/all_to_all
    exchange bytes and mesh reshard bytes from ``device.mesh.*``
    registry deltas, plus the decode service's per-device
    ``device.lane_fill`` means — the dispatcher must fill ALL chips'
    lanes, not device 0's.  Output equality is asserted inside the
    timed body (flagstat total + sorted count), so a wrong mesh program
    can never post a throughput number."""
    import jax

    if jax.default_backend() != "tpu":
        return {}
    from disq_tpu import ReadsStorage
    from disq_tpu.runtime import device_service
    from disq_tpu.runtime.mesh import _MESH_CACHE
    from disq_tpu.runtime.tracing import REGISTRY

    total_bytes = os.path.getsize(path)
    exch = REGISTRY.counter("device.mesh.exchange_bytes")
    resh = REGISTRY.counter("device.mesh.reshard_bytes")
    rows: dict = {}
    n_avail = len(jax.devices())
    prev = os.environ.get("DISQ_TPU_DEVICE_SERVICE")
    os.environ["DISQ_TPU_DEVICE_SERVICE"] = "1"
    try:
        for n_dev in (1, 2, 4, 8):
            if n_dev > n_avail:
                break
            st = ReadsStorage.make_default().resident_decode()
            if n_dev > 1:
                st = st.mesh(n_dev)

            def run(st=st):
                ds = st.read(path)
                stats = ds.flagstat()
                assert stats["total"] == N_RECORDS
                srt = ds.coordinate_sorted()
                assert srt.count() == N_RECORDS

            run()  # warm (mesh build, compiles, page cache)
            # per-device lane fill resets per width so each row sees
            # only its own launches; service restarts per width so its
            # device snapshot tracks the mesh just built
            device_service.shutdown_service()
            REGISTRY.gauge("device.lane_fill")._reset()
            b0 = (exch.total(), resh.total())
            med, times = _timed(run, 3)
            fill = REGISTRY.gauge("device.lane_fill")
            lane_fill = {
                lbl: round(st_["mean"], 3)
                for lbl, st_ in fill._snapshot().items()}
            rows[f"devices_{n_dev}"] = {
                "mb_per_sec": round(total_bytes / med / 1e6, 2),
                "records_per_sec": round(N_RECORDS / med, 1),
                "spread": _spread(times),
                "exchange_bytes": int((exch.total() - b0[0]) / len(times)),
                "reshard_bytes": int((resh.total() - b0[1]) / len(times)),
                "lane_fill": lane_fill or None,
            }
            if n_dev > 1 and "devices_1" in rows:
                rows[f"speedup_{n_dev}x"] = round(
                    rows[f"devices_{n_dev}"]["records_per_sec"]
                    / rows["devices_1"]["records_per_sec"], 3)
    finally:
        if prev is None:
            os.environ.pop("DISQ_TPU_DEVICE_SERVICE", None)
        else:
            os.environ["DISQ_TPU_DEVICE_SERVICE"] = prev
        device_service.shutdown_service()
    rows["meshes_built"] = sorted(_MESH_CACHE)
    return {"14_mesh_pipeline": rows}


_SCHED_WORKER = r"""
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"  # child: never the parent's chip
sys.path.insert(0, {repo!r})
from disq_tpu import ReadsStorage
from disq_tpu.fsw import (FaultInjectingFileSystemWrapper, FaultSpec,
                          register_filesystem)
from disq_tpu.fsw.http import HttpFileSystemWrapper

# Worker 0 is the deliberate straggler: every range read through its
# HTTP wrapper draws a seeded latency from [0, slow_s) — the faultfs
# "slow" spec layered over the real remote wrapper.
http = HttpFileSystemWrapper(block_size={block_size})
slow_s = {slow_s}
if slow_s > 0:
    # scheme="slowhttp" never matches the http:// paths, so the fault
    # wrapper passes full URLs through to the real HTTP wrapper
    register_filesystem("http", FaultInjectingFileSystemWrapper(
        http, [FaultSpec(kind="slow", probability=1.0, slow_s=slow_s)],
        seed=13, scheme="slowhttp"))
else:
    register_filesystem("http", http)
storage = ReadsStorage.make_default().split_size({split})

# Driver phase (header read) runs BEFORE the barrier: it is identical
# fixed cost in both modes and the scheduler has no lever over it —
# the timed window is exactly the scheduled split loop.
from disq_tpu.bam.source import BamSource, read_header
from disq_tpu.fsw.filesystem import resolve_path

src = BamSource(storage)
fs, p = resolve_path({url!r})
header, fv = read_header(fs, p)

# Barrier start: interpreter/jax startup skew must not decide which
# worker reaches the queue first — every worker signals readiness and
# waits for the parent's go-file before the timed read.
open({ready!r}, "w").write("1")
while not os.path.exists({go!r}):
    time.sleep(0.01)
t0 = time.perf_counter()
batches = src.read_split_batches(fs, p, header, fv)
wall = time.perf_counter() - t0
print(json.dumps({{"host": os.environ.get("DISQ_TPU_SCHED_HOST"),
                   "records": int(sum(b.count for b in batches)),
                   "wall": round(wall, 4)}}))
"""


def operator_suite_config(path: str) -> dict:
    """Config 16: the chained sam2bam operator pipeline
    (``runtime/oppipe.py``: filter → sort → markdup → rgstats) on the
    resident columnar currency against the host-materializing path —
    real chip only.

    Resident leg = decode stays in HBM and every operator
    compacts/permutes/reduces the device columns (zero ``ReadBatch``
    materializations, asserted from the registry, not inferred). Host
    leg = same operators' numpy paths over host batches — identical
    stats by construction (tier-1 golden tests), so the row measures
    pure residency win. ``d2h_bytes`` / ``d2h_avoided_bytes`` come
    from ``device.*`` registry deltas."""
    import jax

    if jax.default_backend() != "tpu":
        return {}
    from disq_tpu import ReadsStorage
    from disq_tpu.runtime.tracing import REGISTRY

    d2h = REGISTRY.counter("device.bytes_to_host")
    avoided = REGISTRY.counter("device.d2h_avoided_bytes")
    mats = REGISTRY.counter("columnar.batch.materializations")
    chain = (("filter", "-F 0x900"), "sort", "markdup", "rgstats")

    def run(resident: bool):
        storage = ReadsStorage.make_default().resident_decode(resident)
        ds = storage.read(path)
        out, stats = ds.pipeline(*chain)
        n = int(out.reads.count)
        if resident and hasattr(out.reads, "release"):
            out.reads.release()
        return n, stats

    out: dict = {}
    for name, resident in (("host", False), ("resident", True)):
        n_rec = run(resident)[0]  # warm (compile caches)
        d0, a0, m0 = d2h.total(), avoided.total(), mats.total()
        med, times = _timed(lambda: run(resident), 3)
        out[name] = {
            "records_per_sec": round(n_rec / med, 1),
            "spread": _spread(times),
            "d2h_bytes": int((d2h.total() - d0) / len(times)),
        }
        if resident:
            out[name]["d2h_avoided_bytes"] = int(
                (avoided.total() - a0) / len(times))
            out[name]["materializations"] = int(mats.total() - m0)
    out["resident_vs_host"] = round(
        out["resident"]["records_per_sec"]
        / out["host"]["records_per_sec"], 3)
    return {"16_operator_suite": out}


def sched_steal_config(path: str, tmp: str) -> dict:
    """Config 12: the cross-host shard scheduler
    (``runtime/scheduler.py``) under a deliberate straggler — 1/2/4
    subprocess workers reading the bench BAM off an in-process HTTP
    range server, worker 0 slowed by a seeded faultfs ``slow`` tail on
    every range read.

    Two modes per width, both *through the scheduler plane* so they
    pay identical RPC overhead: ``static`` assigns shard ``i`` to host
    ``i mod N`` (the historical fixed split, no stealing) and ``sched``
    runs the real queue with locality routing + work stealing.  Each
    row reports aggregate records/sec (total records / slowest worker
    wall), the straggler-tail ratio (slowest / median worker wall) and,
    for ``sched``, the coordinator's locality hit-rate and steal count
    — the closed loop behind "stealing recovers the straggler's
    wall"."""
    import statistics as _stats
    import subprocess
    import time as _time

    from disq_tpu.runtime import scheduler

    repo = os.path.dirname(os.path.abspath(__file__))
    slow_ms = float(os.environ.get("BENCH_SCHED_SLOW_MS", "400"))
    split = 512 * 1024
    block_size = 256 * 1024
    bodies = {"/bench.bam": open(path, "rb").read()}
    if os.path.exists(path + ".sbi"):
        bodies["/bench.bam.sbi"] = open(path + ".sbi", "rb").read()
    srv, base = _range_server(bodies)
    url = base + "/bench.bam"
    coord = scheduler.serve_coordinator(lease_s=60.0, steal_after_s=0.1)

    def run_mode(mode: str, w: int) -> dict:
        salt = f"bench12-{mode}-w{w}"
        procs, readies = [], []
        go = os.path.join(tmp, f"go-{salt}")
        for i in range(w):
            ready = os.path.join(tmp, f"ready-{salt}-{i}")
            readies.append(ready)
            env = {**os.environ, "JAX_PLATFORMS": "cpu",
                   "DISQ_TPU_SCHED": coord,
                   "DISQ_TPU_SCHED_HOST": f"w{i}",
                   "DISQ_TPU_SCHED_LEASE_N": "2",
                   "DISQ_TPU_SCHED_SALT": salt,
                   "DISQ_TPU_SCHED_STEAL":
                       "1" if mode == "sched" else "0"}
            if mode == "static":
                env["DISQ_TPU_SCHED_STATIC"] = f"{i},{w}"
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _SCHED_WORKER.format(
                    repo=repo, url=url, split=split,
                    block_size=block_size,
                    slow_s=(slow_ms / 1e3) if i == 0 else 0.0,
                    ready=ready, go=go)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=env))
        deadline = _time.monotonic() + 300
        while (_time.monotonic() < deadline
               and not all(os.path.exists(r) for r in readies)):
            _time.sleep(0.01)
        open(go, "w").write("1")
        docs = []
        for proc in procs:
            out, err = proc.communicate(timeout=600)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"config 12 worker failed ({mode}, w={w}): "
                    + err[-800:])
            docs.append(json.loads(out.strip().splitlines()[-1]))
        total = sum(d["records"] for d in docs)
        assert total == N_RECORDS, (
            f"config 12 {mode} w={w}: workers decoded {total} records, "
            f"expected {N_RECORDS} (a shard emitted 0 or 2 times)")
        walls = sorted(d["wall"] for d in docs)
        row = {
            "records_per_sec": round(total / walls[-1], 1),
            "tail_ratio": round(walls[-1] / _stats.median(walls), 3),
            "worker_walls_s": walls,
        }
        run = scheduler.active_coordinator().stats().get(
            "runs", {}).get(f"{url}#{run_shards[0]}#{salt}")
        if run is not None:
            row["locality_hit_rate"] = run["locality_hit_rate"]
            row["steals"] = len(run["stolen"])
            row["requeued"] = len(run["requeued"])
        return row

    # shard count is fixed by (file size, split): read it back from the
    # coordinator's first registered run for the stats join
    run_shards = [None]

    rows: dict = {"slow_worker_ms": slow_ms}
    try:
        for w in (1, 2, 4):
            per_w: dict = {}
            for mode in ("static", "sched"):
                if run_shards[0] is None:
                    # derive the shard count exactly as the sources do
                    from disq_tpu.fsw.filesystem import compute_path_splits
                    from disq_tpu.fsw.http import HttpFileSystemWrapper

                    probe = HttpFileSystemWrapper(block_size=block_size)
                    run_shards[0] = len(
                        compute_path_splits(probe, url, split))
                per_w[mode] = run_mode(mode, w)
            per_w["sched_vs_static"] = round(
                per_w["sched"]["records_per_sec"]
                / per_w["static"]["records_per_sec"], 3)
            per_w["tail_ratio_drop"] = round(
                per_w["static"]["tail_ratio"]
                / max(per_w["sched"]["tail_ratio"], 1e-9), 3)
            rows[f"workers_{w}"] = per_w
    finally:
        # the process-wide introspection server stays up (other configs
        # may serve it); only the coordinator state is dropped
        srv.shutdown()
        scheduler.stop_coordinator()
    return {"12_sched_steal": rows}


def serve_latency_config(path: str, tmp: str) -> dict:
    """Config 13: the multi-tenant serving plane (``runtime/serve.py``)
    under a Zipf-skewed region workload — N closed-loop clients
    replaying weighted random intervals against the daemon over HTTP,
    at c ∈ {1, 8, 32} clients, cold cache vs hot.

    Per width the row reports request-latency p50/p99/p999 (ms) and
    QPS; the hot numbers are medians over 3 reps and carry the spread,
    so ``check_bench_regression`` guards ``p99_ms`` (lower is better)
    and ``qps``. Cold numbers (``cold_*``) are informational — a cold
    run is a one-shot by definition. ``hot_over_cold_p99_x`` at c=32
    is the shared hot-block cache's headline, and the ``lane_fill``
    sub-row compares the device service's mean lanes-per-launch for
    sequential (c=1) vs concurrent (c=32) cold traffic — the
    cross-request batching win.

    The headline needs the default BENCH_RECORDS (300k): with a toy
    dataset the cold path is nearly free and both sides collapse onto
    the per-request HTTP floor, understating the cache."""
    import http.client
    import random
    import threading as _threading
    import statistics as _stats

    from disq_tpu import (
        BaiWriteOption, ReadsStorage, SbiWriteOption, stop_introspect_server)
    from disq_tpu.runtime import device_service
    from disq_tpu.runtime import serve as serve_mod
    from disq_tpu.runtime.introspect import introspect_address
    from disq_tpu.runtime.tracing import REGISTRY

    # The serving plane answers interval queries through the BAI, which
    # the synthetic bench BAM does not carry — write a sorted+indexed
    # copy once (outside every timed window).
    indexed = os.path.join(tmp, "bench-serve.bam")
    st = ReadsStorage.make_default().num_shards(8)
    st.write(st.read(path), indexed, BaiWriteOption.ENABLE,
             SbiWriteOption.ENABLE, sort=True)

    # Zipf-skewed workload: 64 regions over the synthetic position
    # range, weight ∝ 1/rank — a handful of hot regions dominate, the
    # tail keeps the cache honest. Fixed seed: every round replays the
    # exact same request sequences.
    rng = random.Random(13)
    span = 20_000
    regions = [(REFS[rng.randrange(len(REFS))][0],
                rng.randrange(0, 1_000_000 - span))
               for _ in range(64)]
    weights = [1.0 / (i + 1) for i in range(len(regions))]

    owns_server = introspect_address() is None
    addr = serve_mod.start_serve(tenant_slots=64, tenant_queue=256)
    daemon = serve_mod.serve_if_running()
    daemon.register("bench", indexed)

    def run_clients(c: int, requests_per_client: int, seed: int):
        """Closed loop: each client issues its own weighted random
        request sequence over one persistent keep-alive connection.
        Returns (sorted per-request latencies [s], wall seconds)."""
        lat_lists = [[] for _ in range(c)]
        errors = []

        def client(k):
            import socket as _socket

            crng = random.Random(seed * 1000 + k)
            host, _, port = addr.partition(":")
            conn = http.client.HTTPConnection(host, int(port), timeout=60)
            try:
                conn.connect()
                # mirror of the server's disable_nagle_algorithm: the
                # request body is a second write after the headers
                conn.sock.setsockopt(
                    _socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
                for _ in range(requests_per_client):
                    contig, start = crng.choices(regions, weights)[0]
                    body = json.dumps({
                        "dataset": "bench", "tenant": f"t{k % 4}",
                        "limit": 0, "digest": False,
                        "intervals": [{"contig": contig, "start": start + 1,
                                       "end": start + span}],
                    })
                    t0 = time.perf_counter()
                    conn.request("POST", "/query/reads", body=body,
                                 headers={"Content-Type":
                                          "application/json"})
                    resp = conn.getresponse()
                    payload = resp.read()
                    lat_lists[k].append(time.perf_counter() - t0)
                    if resp.status != 200:
                        errors.append(
                            f"client {k}: {resp.status} {payload[:200]}")
                        return
            except Exception as e:  # surface, never die silently
                errors.append(f"client {k}: {type(e).__name__}: {e}")
            finally:
                conn.close()

        threads = [_threading.Thread(target=client, args=(k,))
                   for k in range(c)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors:
            raise RuntimeError(f"config 13 client errors: {errors[:3]}")
        return sorted(x for lst in lat_lists for x in lst), wall

    def pcts(lats, wall):
        def pc(p):
            return lats[min(len(lats) - 1, int(p / 100 * len(lats)))]
        return {"p50_ms": pc(50) * 1e3, "p99_ms": pc(99) * 1e3,
                "p999_ms": pc(99.9) * 1e3, "qps": len(lats) / wall}

    rows: dict = {"regions": len(regions), "span_bp": span}
    try:
        for c in (1, 8, 32):
            n_req = max(96, 24 * c) // c
            # cold: empty block cache, one shot (informational — the
            # first pass self-warms, so only its tail stays truly cold)
            daemon.cache.clear()
            cold = pcts(*run_clients(c, n_req, seed=c))
            # hot: same sequences against the warmed cache, 3 reps;
            # medians + spread feed the regression gate
            reps = [pcts(*run_clients(c, n_req, seed=c))
                    for _ in range(3)]
            med = {k: _stats.median(r[k] for r in reps) for k in reps[0]}
            row = {
                "cold_p50_ms": round(cold["p50_ms"], 3),
                "cold_p99_ms": round(cold["p99_ms"], 3),
                "cold_p999_ms": round(cold["p999_ms"], 3),
                "cold_qps": round(cold["qps"], 1),
                "hot": {
                    "p50_ms": round(med["p50_ms"], 3),
                    "p99_ms": round(med["p99_ms"], 3),
                    "p999_ms": round(med["p999_ms"], 3),
                    "spread": _spread([r["p99_ms"] for r in reps]),
                    "qps": round(med["qps"], 1),
                    "qps_spread": _spread([r["qps"] for r in reps]),
                },
            }
            if c == 32:
                row["hot_over_cold_p99_x"] = round(
                    cold["p99_ms"] / max(med["p99_ms"], 1e-9), 2)
            rows[f"clients_{c}"] = row

        # Cross-request batching: route cold misses through the device
        # service dispatcher and compare mean lane fill for sequential
        # vs 32-way-concurrent traffic over identical request sets —
        # real chip only (interpret-mode inflate is not a measurement,
        # same gate as configs 8/9).
        import jax

        if jax.default_backend() != "tpu":
            rows["lane_fill"] = {
                "skipped": "host backend — lane-fill batching is "
                           "measured on a real chip"}
        else:
            fill = REGISTRY.gauge("device.lane_fill")
            prev = os.environ.get("DISQ_TPU_DEVICE_SERVICE")
            os.environ["DISQ_TPU_DEVICE_SERVICE"] = "1"
            try:
                lane_row = {}
                for c in (1, 32):
                    daemon.cache.clear()
                    s0 = fill.state() or {"samples": 0, "mean": 0.0}
                    run_clients(c, max(96, 24 * c) // c, seed=99 + c)
                    s1 = fill.state() or {"samples": 0, "mean": 0.0}
                    dn = s1["samples"] - s0["samples"]
                    dsum = (s1["mean"] * s1["samples"]
                            - s0["mean"] * s0["samples"])
                    lane_row[f"c{c}_lane_fill_mean"] = round(
                        dsum / dn, 4) if dn else 0.0
                if lane_row.get("c1_lane_fill_mean"):
                    lane_row["batching_gain_x"] = round(
                        lane_row["c32_lane_fill_mean"]
                        / lane_row["c1_lane_fill_mean"], 2)
                rows["lane_fill"] = lane_row
            finally:
                if prev is None:
                    os.environ.pop("DISQ_TPU_DEVICE_SERVICE", None)
                else:
                    os.environ["DISQ_TPU_DEVICE_SERVICE"] = prev
                device_service.shutdown_service()
    finally:
        serve_mod.stop_serve()
        if owns_server:
            stop_introspect_server()
    return {"13_serve_latency": rows}


# Replica subprocess for config 15: a real serving daemon in its own
# interpreter, capacity-constrained caches, optional seeded slow-tail
# (sleep wrapped around the query path — models a replica with a cold
# page cache / noisy neighbor). Prints its address then holds on stdin.
_FLEET_REPLICA_CODE = r"""
import json, os, sys, time
cfg = json.loads(sys.argv[1])
sys.path.insert(0, cfg["repo"])
os.environ["JAX_PLATFORMS"] = "cpu"  # child: never the parent's chip
from disq_tpu.runtime import serve as serve_mod
addr = serve_mod.start_serve(
    port=0, tenant_slots=64, tenant_queue=256,
    compressed_cache_mb=cfg["compressed_mb"],
    decoded_cache_mb=cfg["decoded_mb"],
    parsed_cache_mb=cfg["parsed_mb"])
daemon = serve_mod.serve_if_running()
daemon.register("bench", cfg["bam"])
if cfg.get("slow_s"):
    _orig = daemon.handle
    def _slow_handle(method, p, doc, _orig=_orig, _s=cfg["slow_s"]):
        if p.startswith("/query/"):
            time.sleep(_s)
        return _orig(method, p, doc)
    daemon.handle = _slow_handle
print("ADDR", addr, flush=True)
sys.stdin.readline()
"""


def fleet_serve_config(path: str, tmp: str) -> dict:
    """Config 15: the fleet routing tier (``runtime/fleet.py``) over
    real serving subprocesses — the config 13 closed-loop Zipf
    workload replayed against 2 replicas behind the router, locality
    routing vs random, plus cross-replica hedging against a seeded
    slow-tail replica.

    The per-replica cache budgets are **calibrated**: a single
    in-process daemon first warms the full 64-region working set and
    each replica then gets ~55% of the measured bytes per tier — the
    hot set fits the fleet's aggregate cache only when locality
    routing *partitions* it (each replica keeps the regions the
    rendezvous/overlap signal pins to it), while random routing asks
    every replica to hold everything and thrashes both LRUs. The
    guarded leaves are the locality hot ``p99_ms`` (lower is better)
    and ``qps`` at c=32; the random side is informational
    (``baseline_*``) and ``locality_over_random_p99_x`` is the
    headline. The ``hedge`` sub-row adds a third replica with a
    seeded 80ms stall on every query and reports how many hedges
    launched and how often the duplicate beat the slow primary."""
    import http.client
    import random
    import subprocess
    import threading as _threading
    import statistics as _stats

    from disq_tpu import (
        BaiWriteOption, ReadsStorage, SbiWriteOption, stop_introspect_server)
    from disq_tpu.runtime import serve as serve_mod
    from disq_tpu.runtime.introspect import introspect_address
    from disq_tpu.runtime.tracing import counter

    repo = os.path.dirname(os.path.abspath(__file__))
    indexed = os.path.join(tmp, "bench-fleet.bam")
    st = ReadsStorage.make_default().num_shards(8)
    st.write(st.read(path), indexed, BaiWriteOption.ENABLE,
             SbiWriteOption.ENABLE, sort=True)

    # Wider regions than config 13 (40 kbp): a cache miss decodes ~2x
    # the blocks while a parsed-tier hit stays O(lookup) — the
    # hit-vs-miss cost gap IS the signal this config measures.
    rng = random.Random(15)
    span = 40_000
    regions = [(REFS[rng.randrange(len(REFS))][0],
                rng.randrange(0, 1_000_000 - span))
               for _ in range(64)]
    weights = [1.0 / (i + 1) for i in range(len(regions))]

    def run_clients(addr: str, qpath: str, c: int,
                    requests_per_client: int, seed: int,
                    region_pool=None, pool_weights=None):
        """Config 13's closed loop, parameterized by target address
        and query path (replica-direct or through the router)."""
        pool = region_pool or regions
        wts = pool_weights or weights[:len(pool)]
        lat_lists = [[] for _ in range(c)]  # (region rank, latency s)
        errors = []

        def client(k):
            import socket as _socket

            crng = random.Random(seed * 1000 + k)
            host, _, port = addr.partition(":")
            conn = http.client.HTTPConnection(host, int(port), timeout=60)
            try:
                conn.connect()
                conn.sock.setsockopt(
                    _socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
                for _ in range(requests_per_client):
                    rank = crng.choices(range(len(pool)), wts)[0]
                    contig, start = pool[rank]
                    body = json.dumps({
                        "dataset": "bench", "tenant": f"t{k % 4}",
                        "limit": 0, "digest": False,
                        "intervals": [{"contig": contig, "start": start + 1,
                                       "end": start + span}],
                    })
                    t0 = time.perf_counter()
                    conn.request("POST", qpath, body=body,
                                 headers={"Content-Type":
                                          "application/json"})
                    resp = conn.getresponse()
                    payload = resp.read()
                    lat_lists[k].append((rank, time.perf_counter() - t0))
                    if resp.status != 200:
                        errors.append(
                            f"client {k}: {resp.status} {payload[:200]}")
                        return
            except Exception as e:
                errors.append(f"client {k}: {type(e).__name__}: {e}")
            finally:
                conn.close()

        threads = [_threading.Thread(target=client, args=(k,))
                   for k in range(c)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors:
            raise RuntimeError(f"config 15 client errors: {errors[:3]}")
        return [x for lst in lat_lists for x in lst], wall

    N_HOT = 8  # Zipf head: ~50% of the traffic mass

    def pcts(samples, wall):
        def pc(lats, p):
            return lats[min(len(lats) - 1, int(p / 100 * len(lats)))]
        lats = sorted(lat for _rank, lat in samples)
        hot = sorted(lat for rank, lat in samples if rank < N_HOT)
        return {"p50_ms": pc(lats, 50) * 1e3, "p99_ms": pc(lats, 99) * 1e3,
                "hot_p99_ms": pc(hot or lats, 99) * 1e3,
                "qps": len(lats) / wall}

    # --- calibration: size the full working set with one daemon -----------
    owns_server = introspect_address() is None
    serve_mod.start_serve(tenant_slots=64, tenant_queue=256)
    daemon = serve_mod.serve_if_running()
    daemon.register("bench", indexed)
    for contig, start in regions:
        status, _body = daemon.handle("POST", "/query/reads", {
            "dataset": "bench", "limit": 0, "digest": False,
            "intervals": [{"contig": contig, "start": start + 1,
                           "end": start + span}]})
        assert status == 200, _body
    cstats = daemon.cache.stats()
    serve_mod.stop_serve()
    # ~55% of the measured set per tier (>=1 MB): a rendezvous
    # partition gives each replica ~half the regions, which fits —
    # locality routing reaches a near-zero steady-state miss rate —
    # while random routing asks every replica to hold 100% of the set
    # and keeps thrashing the Zipf tail out of both LRUs.
    budgets = {
        f"{tier}_mb": max(1, int(cstats[tier]["bytes"] * 0.55) >> 20)
        for tier in ("compressed", "decoded", "parsed")}

    def spawn_replica(slow_s: float = 0.0):
        cfg = dict(budgets, repo=repo, bam=indexed, slow_s=slow_s)
        proc = subprocess.Popen(
            [sys.executable, "-c", _FLEET_REPLICA_CODE, json.dumps(cfg)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        line = proc.stdout.readline()
        if not line.startswith("ADDR"):
            proc.kill()
            raise RuntimeError(f"config 15 replica failed to start: {line!r}")
        return proc, line.split()[1]

    from disq_tpu.runtime import fleet as fleet_mod

    rows: dict = {"regions": len(regions), "span_bp": span,
                  "replica_cache_mb": budgets}
    procs = []
    try:
        for _ in range(2):
            procs.append(spawn_replica())
        addrs = [a for _p, a in procs]
        c, n_req = 32, max(96, 24 * 32) // 32

        # --- locality vs random routing, same replicas, cold per phase ----
        for policy in ("locality", "random"):
            fleet_addr = fleet_mod.start_fleet(
                addrs, policy=policy, hedge_quantile=None, refresh_s=0.25)
            router = fleet_mod.fleet_if_running()
            status, doc = router.register("bench", indexed)
            assert status == 200, doc  # epoch bump => replicas start cold
            run_clients(fleet_addr, "/fleet/query/reads", c, n_req,
                        seed=c)  # warm: caches fill along routed paths
            reps = [pcts(*run_clients(fleet_addr, "/fleet/query/reads",
                                      c, n_req, seed=c))
                    for _ in range(3)]
            med = {k: _stats.median(r[k] for r in reps) for k in reps[0]}
            if policy == "locality":
                rows["locality"] = {
                    "p50_ms": round(med["p50_ms"], 3),
                    "p99_ms": round(med["p99_ms"], 3),
                    "spread": _spread([r["p99_ms"] for r in reps]),
                    "hot_p99_ms": round(med["hot_p99_ms"], 3),
                    "qps": round(med["qps"], 1),
                    "qps_spread": _spread([r["qps"] for r in reps]),
                }
            else:  # baseline_* keys: informational, not regression-gated
                rows["random"] = {
                    "baseline_p50_ms": round(med["p50_ms"], 3),
                    "baseline_p99_ms": round(med["p99_ms"], 3),
                    "baseline_hot_p99_ms": round(med["hot_p99_ms"], 3),
                    "baseline_qps": round(med["qps"], 1),
                }
            fleet_mod.stop_fleet()
        # The headline: tail latency on the *hot set* — the queries
        # locality routing keeps pinned to a warm replica while random
        # routing lets the Zipf tail churn them out of every LRU.
        rows["locality_over_random_hot_p99_x"] = round(
            rows["random"]["baseline_hot_p99_ms"]
            / max(rows["locality"]["hot_p99_ms"], 1e-9), 2)

        # --- hedging: add a seeded slow-tail replica ----------------------
        # 250ms stall: decisively slower than a CPU-contended cold
        # decode on the runner-up, so the duplicate can actually win.
        slow = spawn_replica(slow_s=0.25)
        procs.append(slow)
        fleet_addr = fleet_mod.start_fleet(
            addrs + [slow[1]], policy="locality",
            hedge_quantile=0.9, hedge_min_s=0.02, refresh_s=0.25)
        router = fleet_mod.fleet_if_running()
        status, doc = router.register("bench", indexed)
        assert status == 200, doc
        # Warm ONLY the slow replica over the hot regions: locality then
        # pins the hot set to it, so its seeded stall is the primary the
        # hedge must beat.
        hot = regions[:8]
        run_clients(slow[1], "/query/reads", 4, len(hot), seed=7,
                    region_pool=hot, pool_weights=[1.0] * len(hot))
        time.sleep(0.3)  # next routed query refreshes the digest view
        launched0 = counter("fleet.hedge.launched").total()
        won0 = counter("fleet.hedge.won").value(winner="hedge")
        lats, wall = run_clients(fleet_addr, "/fleet/query/reads", 8,
                                 24, seed=8, region_pool=hot,
                                 pool_weights=[1.0] * len(hot))
        launched = counter("fleet.hedge.launched").total() - launched0
        won = counter("fleet.hedge.won").value(winner="hedge") - won0
        hp = pcts(lats, wall)
        rows["hedge"] = {
            "launched": int(launched),
            "won_hedge": int(won),
            "win_rate": round(won / launched, 3) if launched else 0.0,
            "hedged_p99_ms": round(hp["p99_ms"], 3),
        }
        fleet_mod.stop_fleet()
    finally:
        fleet_mod.stop_fleet()
        for proc, _addr in procs:
            proc.kill()
            proc.wait()
        if owns_server:
            stop_introspect_server()
    return {"15_fleet_serve": rows}


def main() -> None:
    from disq_tpu.util import enable_compile_cache

    enable_compile_cache()

    # DISQ_TPU_POSTMORTEM_DIR arms the flight recorder for the whole
    # bench: any abort writes a postmortem bundle there, and
    # faulthandler is wired into the dir so a native-extension crash
    # (disq_tpu/native) dumps tracebacks instead of dying silently.
    if os.environ.get("DISQ_TPU_POSTMORTEM_DIR"):
        from disq_tpu.runtime import flightrec

        flightrec.enable(os.environ["DISQ_TPU_POSTMORTEM_DIR"])

    tmp = tempfile.mkdtemp(prefix="disq_bench_")
    path = os.path.join(tmp, "bench.bam")
    synth_bam(path, N_RECORDS)

    # BENCH_INTROSPECT=<port> serves the live endpoint for the whole
    # bench run (port 0 = ephemeral; address on stderr so stdout stays
    # one JSON line) — watch /progress while the configs grind.
    if os.environ.get("BENCH_INTROSPECT"):
        from disq_tpu import start_introspect_server

        addr = start_introspect_server(int(os.environ["BENCH_INTROSPECT"]))
        print(f"bench introspection at http://{addr}", file=sys.stderr)

    from disq_tpu import ReadsStorage

    storage = ReadsStorage.make_default().split_size(8 * 1024 * 1024)

    # --- framework: config 1, BAM decode records/sec ---
    def run_framework():
        ds = storage.read(path)
        assert ds.count() == N_RECORDS

    run_framework()  # warm-up (compile caches, page cache)
    med_fw, times_fw = _timed(run_framework, REPS)

    # --- baseline: all-core htsjdk-style decode over SBI splits ---
    ncpu = os.cpu_count() or 1
    splits = make_splits(path, ncpu)
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(ncpu) as pool:
        n_base = baseline_decode(pool, path, splits)  # warm-up
        assert n_base == N_RECORDS, f"baseline decoded {n_base}"
        med_base, times_base = _timed(
            lambda: baseline_decode(pool, path, splits), BASE_REPS
        )

    rps = N_RECORDS / med_fw
    baseline_rps = N_RECORDS / med_base

    configs = {
        "1_bam_decode": {
            "records_per_sec": round(rps, 1),
            "spread": _spread(times_fw),
            "reps_sec": [round(t, 4) for t in times_fw],
            "baseline_records_per_sec": round(baseline_rps, 1),
            "baseline_spread": _spread(times_base),
            "baseline_cores": ncpu,
        },
    }
    configs.update(secondary_configs(storage, path, tmp, max(2, REPS - 2)))
    configs.update(executor_scaling_config(path, max(2, REPS - 2)))
    configs.update(http_read_config(path, max(2, REPS - 2)))
    configs.update(write_scaling_config(path, tmp, max(2, REPS - 2)))
    configs.update(sched_steal_config(path, tmp))
    configs.update(device_inflate_config(path))
    configs.update(device_service_config(path))
    configs.update(resident_decode_config(path))
    configs.update(device_write_config(path, tmp))
    configs.update(serve_latency_config(path, tmp))
    configs.update(fleet_serve_config(path, tmp))
    configs.update(mesh_pipeline_config(path))
    configs.update(operator_suite_config(path))

    # Telemetry snapshot accumulated across every config above
    # (runtime/tracing.py): phase totals + p50/p99, labeled counters
    # (retries, cache hits/misses, quarantine), gauge peaks — so each
    # BENCH json carries the *why* behind its rows, not just medians.
    # run_id joins this JSON against any span/progress JSONL the same
    # process wrote (scripts/check_bench_regression.py compares the
    # BENCH_r*.json trajectory round over round).
    from disq_tpu.runtime.tracing import RUN_ID, telemetry_summary

    telemetry = telemetry_summary()
    # Device counter rollup pulled to its own key: the accelerator
    # story (transfer bytes, launches, fallbacks, HBM peak) at a
    # glance, without walking the full counters/gauges maps.
    telemetry["device"] = {
        k: v
        for section in ("counters", "gauges")
        for k, v in telemetry.get(section, {}).items()
        if k.startswith("device.")
    }
    print(
        json.dumps(
            {
                "metric": "bam_decode_records_per_sec",
                "value": round(rps, 1),
                "unit": "records/sec",
                "vs_baseline": round(rps / baseline_rps, 3),
                "spread": _spread(times_fw),
                "reps": REPS,
                "run_id": RUN_ID,
                "configs": configs,
                "telemetry": telemetry,
            }
        )
    )


if __name__ == "__main__":
    main()
