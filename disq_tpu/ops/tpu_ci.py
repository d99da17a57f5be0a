"""TPU-mode kernel CI runner (SURVEY.md §4 gap-closing mandate).

Runs the device kernels at production shapes with ``interpret=False``
on a real chip, asserts correctness against host oracles, and writes a
``TPU_KERNELS.json`` artifact with per-kernel throughput rows. This is
the regression net the interpret-mode suite cannot provide: Mosaic
rejects or miscompiles programs the interpreter runs happily, and only
an on-chip run catches that.

Invoked by ``tests/test_tpu_kernels.py`` (in a clean subprocess so the
suite's forced-CPU conftest doesn't apply) or directly:

    python -m disq_tpu.ops.tpu_ci [out.json [wgs30x-record-bytes]]
"""

from __future__ import annotations

import functools
import json
import sys
import time
import zlib

import numpy as np


def _deflate(data: bytes, level: int = 6) -> bytes:
    c = zlib.compressobj(level, zlib.DEFLATED, -15, 8)
    return c.compress(data) + c.flush()


def _bam_like(n: int, rng) -> bytes:
    """BGZF-payload-shaped bytes: motif-drawn packed seq + run-shaped
    quals — compresses ~3.5-4x like real genomic BAM, so payloads stay
    under MAX_DEVICE_CSIZE and really exercise the device kernel."""
    motif = rng.integers(0, 16, 2048, dtype=np.uint8)
    seq = np.tile(motif, (n // 2 + 2047) // 2048)[: n // 2]
    qual = np.repeat(
        rng.integers(30, 42, max(1, n // 40), dtype=np.uint8), 20)[: n // 2]
    return (seq.tobytes() + qual.tobytes())[:n]


def _inflate_kernel_only(raws: list, payloads: list, cw: int = 0):
    """One hand-packed launch of the SIMD inflate kernel, timed alone:
    inputs pre-uploaded, sync on the 2 KiB meta pull (isolates the
    kernel from packing and transfers). Returns (every lane's status 0
    and its output equal to ``raws``, best seconds of 3, meta rows).
    ``cw``: a wider compressed buffer than the payloads ask for (what a
    geometry costs lanes that do not need it)."""
    import jax.numpy as jnp
    from disq_tpu.ops import inflate_simd as S

    assert all(len(p) <= S.MAX_DEVICE_CSIZE for p in payloads)
    need, ow = S.buckets_for(payloads, max(len(r) for r in raws))
    cw = max(cw, need)
    fn = S._compiled(cw, ow, False)
    comp, clen = S._pack_chunk(payloads, cw)
    carg, cl = jnp.asarray(comp), jnp.asarray(clen)
    consts = tuple(jnp.asarray(t) for t in S._CONST_TABLES)
    w, m = fn(carg, cl, *consts)
    meta, words = np.asarray(m), np.asarray(w)
    ok = (int(meta[1].max()) == 0) and all(
        np.ascontiguousarray(words[:, i]).tobytes()[:len(r)] == r
        for i, r in enumerate(raws))
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        _, m = fn(carg, cl, *consts)
        np.asarray(m)
        best = min(best, time.perf_counter() - t0)
    return ok, best, meta


def _superstep_factors(best: float, meta) -> dict:
    """A kernel-only launch's two factors: supersteps (``meta`` row 2)
    and microseconds a superstep."""
    steps = int(meta[2, 0])
    return {"supersteps_per_launch": steps,
            "us_per_superstep": round(best / steps * 1e6, 3)}


def run_inflate_simd(results: list) -> None:
    from disq_tpu.ops.inflate_simd import (
        MAX_DEVICE_CSIZE, inflate_payloads_simd,
    )

    rng = np.random.default_rng(0)
    raws = [_bam_like(60000, rng) for _ in range(128)]
    payloads = [_deflate(r) for r in raws]
    usizes = [len(r) for r in raws]
    n_dev = sum(len(p) <= MAX_DEVICE_CSIZE for p in payloads)
    assert n_dev == len(payloads), (
        f"only {n_dev}/{len(payloads)} payloads fit the device comp cap "
        f"— this would silently measure host zlib")

    got = inflate_payloads_simd(payloads, usizes=usizes, interpret=False)
    ok = all(g == r for g, r in zip(got, raws))
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        inflate_payloads_simd(payloads, usizes=usizes, interpret=False)
        best = min(best, time.perf_counter() - t0)
    total = sum(usizes)
    results.append({
        "kernel": "inflate_simd",
        "shape": "128 lanes x 60000 B",
        "mb_per_sec": round(total / best / 1e6, 2),
        "device_served": n_dev,
        "correct": ok,
    })
    assert ok, "SIMD inflate output != zlib"

    ok_k, best_k, meta = _inflate_kernel_only(raws, payloads)
    results.append({
        "kernel": "inflate_simd_kernel_only",
        "shape": "128 lanes x 60000 B",
        "mb_per_sec": round(total / best_k / 1e6, 2),
        **_superstep_factors(best_k, meta),
        "correct": ok_k,
    })
    assert ok_k, "SIMD inflate kernel-only launch output != zlib"


def run_inflate_simd_literal_heavy(results: list) -> None:
    """Pair-literal regime: pure-literal streams (no LZ77 matches) are
    the kernel's worst case — the speculative second-symbol decode
    roughly doubles it. Kernel-only row at 128 x 25 KB."""
    rng = np.random.default_rng(7)
    raws = [rng.integers(0, 250, 25000, dtype=np.uint8).tobytes()
            for _ in range(128)]
    ok, best, meta = _inflate_kernel_only(raws, [_deflate(r) for r in raws])
    total = sum(len(r) for r in raws)
    results.append({
        "kernel": "inflate_simd_literal_heavy_kernel_only",
        "shape": "128 lanes x 25000 B (no matches)",
        "mb_per_sec": round(total / best / 1e6, 2),
        **_superstep_factors(best, meta),
        "correct": ok,
    })
    assert ok, "literal-heavy SIMD inflate output != zlib"


def run_inflate_simd_wgs30x(results: list, record_bytes: bytes) -> None:
    """The kernel on the benchmark's own bytes: 128 lanes of 65,280
    ``wgs30x`` record bytes each (a full BGZF block), zlib 6. The
    caller hands in the record bytes (``benchmark/gen.py`` +
    ``reference.encode_records``; this package does not import the
    benchmark). Kernel-only, with the launch's two factors (supersteps,
    seconds a superstep), the share of its supersteps in which some
    lane read history past the ring (meta row 3) and the copy chunks
    that ran past the output word they started in (meta row 4, summed
    over the lanes)."""
    block = 65280
    assert len(record_bytes) >= 128 * block, (
        f"{len(record_bytes)} record bytes do not fill 128 lanes")
    raws = [record_bytes[i * block: (i + 1) * block] for i in range(128)]
    payloads = [_deflate(r) for r in raws]
    ok, best, meta = _inflate_kernel_only(raws, payloads)
    factors = _superstep_factors(best, meta)
    results.append({
        "kernel": "inflate_simd_wgs30x_kernel_only",
        "shape": "128 lanes x 65280 B of wgs30x records, zlib 6",
        "mb_per_sec": round(128 * block / best / 1e6, 2),
        "ratio_zlib6": round(128 * block / sum(map(len, payloads)), 3),
        **factors,
        "far_superstep_share": round(
            int(meta[3, 0]) / factors["supersteps_per_launch"], 4),
        "crossing_chunks": int(meta[4].sum()),
        "correct": ok,
    })
    assert ok, "wgs30x SIMD inflate output != its input"


def run_inflate_simd_ont30x(results: list, record_bytes: bytes) -> None:
    """The kernel at its wide geometry on the long-read benchmark's own
    bytes: 128 lanes of 65,280 ``ont30x`` record bytes each, zlib 6
    (``benchmark/gen_longread.py`` + ``reference_longread.encode_records``,
    handed in by the caller). Most payloads are over the narrow
    geometry's 32,752 bytes, so the launch is (16384, 16384). Beside
    the row's own factors: what the same launch costs the lanes under
    the narrow cap when they launch alone at the narrow geometry and
    alone at the wide one (the geometry's own price a superstep)."""
    from disq_tpu.ops.inflate_simd import NARROW_CSIZE

    block = 65280
    assert len(record_bytes) >= 128 * block, (
        f"{len(record_bytes)} record bytes do not fill 128 lanes")
    raws = [record_bytes[i * block: (i + 1) * block] for i in range(128)]
    payloads = [_deflate(r) for r in raws]
    wide = sum(len(p) > NARROW_CSIZE for p in payloads)
    ok, best, meta = _inflate_kernel_only(raws, payloads)
    row = {
        "kernel": "inflate_simd_ont30x_kernel_only",
        "shape": "128 lanes x 65280 B of ont30x records, zlib 6",
        "mb_per_sec": round(128 * block / best / 1e6, 2),
        "ratio_zlib6": round(128 * block / sum(map(len, payloads)), 3),
        "lanes_over_32752_B": wide,
        **_superstep_factors(best, meta),
        "far_superstep_share": round(
            int(meta[3, 0]) / int(meta[2, 0]), 4),
        "crossing_chunks": int(meta[4].sum()),
    }
    narrow = [i for i, p in enumerate(payloads) if len(p) <= NARROW_CSIZE]
    if narrow:
        sub = [raws[i] for i in narrow], [payloads[i] for i in narrow]
        for key, cw in (("narrow_lanes_at_cw8192", 0),
                        ("narrow_lanes_at_cw16384", 16384)):
            ok_n, best_n, meta_n = _inflate_kernel_only(*sub, cw=cw)
            ok = ok and ok_n
            row[key] = {"lanes": len(narrow),
                        **_superstep_factors(best_n, meta_n)}
    row["correct"] = ok
    results.append(row)
    assert ok, "ont30x SIMD inflate output != its input"


def run_rans_simd(results: list) -> None:
    """128-lane SIMD rANS order-0 decode (ops/rans_simd.py): e2e and
    kernel-only rows at the same 128 x 60 KB shape as the inflate
    kernel, correctness vs the host codec."""
    from disq_tpu.cram.rans import rans_encode_order0
    from disq_tpu.ops import rans_simd as RS

    rng = np.random.default_rng(6)
    raws = []
    for _ in range(128):
        n = 60000
        r = np.repeat(
            rng.integers(28, 42, (n + 19) // 20, dtype=np.uint8), 20)[:n]
        raws.append(r.tobytes())
    streams = [rans_encode_order0(r) for r in raws]
    metas = [RS._parse_stream(k, s) for k, s in enumerate(streams)]
    assert all(
        len(m[1]) <= RS.MAX_DEVICE_CSIZE and m[0] <= RS.MAX_DEVICE_RAW
        for m in metas), "payloads exceed device caps — would measure host"

    got = RS.rans0_decode_simd(streams, interpret=False)
    ok = got == raws
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        RS.rans0_decode_simd(streams, interpret=False)
        best = min(best, time.perf_counter() - t0)
    total = sum(len(r) for r in raws)
    results.append({
        "kernel": "rans_order0_simd",
        "shape": "128 lanes x 60000 B",
        "mb_per_sec": round(total / best / 1e6, 2),
        "correct": ok,
    })
    assert ok, "SIMD rANS output != host codec"

    # kernel-only row: inputs pre-uploaded, sync on the 2 KiB meta pull
    import jax.numpy as jnp

    cw, ow = RS.kernel_geometry(metas)
    fn = RS._compiled(cw, ow, False)
    args = [jnp.asarray(x) for x in RS.pack_lane_tables(metas, cw)]
    w, m = fn(*args)
    # this hand-built launch must itself be correct, not just timed
    ok_k = (int(np.asarray(m)[1].max()) == 0) and all(
        np.ascontiguousarray(np.asarray(w)[:, i]).tobytes()[:len(raws[i])]
        == raws[i]
        for i in range(len(raws)))
    best_k = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        _, m = fn(*args)
        np.asarray(m)
        best_k = min(best_k, time.perf_counter() - t0)
    results.append({
        "kernel": "rans_order0_simd_kernel_only",
        "shape": "128 lanes x 60000 B",
        "mb_per_sec": round(total / best_k / 1e6, 2),
        "correct": ok_k,
    })
    assert ok_k, "SIMD rANS kernel-only launch output != host codec"


def run_kernel_fuzz(results: list) -> None:
    """On-chip differential fuzz: mixed payload shapes (motif repeats,
    runs, small alphabets, text-like, single-byte, short periods,
    multi-block full-flush) across zlib levels/strategies vs host
    zlib, plus random rANS streams vs the host codec — the compiled
    Mosaic kernels must never diverge (interpret-mode tests cannot
    catch miscompiles)."""
    from disq_tpu.cram.rans import rans_encode_order0
    from disq_tpu.ops.inflate_simd import (
        MAX_DEVICE_CSIZE, inflate_payloads_simd,
    )
    from disq_tpu.ops.rans_simd import rans0_decode_simd

    rng = np.random.default_rng(123)

    def z(data, level, strategy):
        c = zlib.compressobj(level, zlib.DEFLATED, -15, 8, strategy)
        return c.compress(data) + c.flush()

    def gen(i):
        kind = i % 7
        n = int(rng.integers(1, 60000))
        if kind == 0:
            m = rng.integers(0, 16, int(rng.integers(4, 4096)),
                             dtype=np.uint8)
            raw = np.tile(m, n // len(m) + 1)[:n].tobytes()
        elif kind == 1:
            raw = np.repeat(rng.integers(0, 250, max(1, n // 17),
                                         dtype=np.uint8), 17)[:n].tobytes()
        elif kind == 2:
            raw = rng.integers(0, 7, n, dtype=np.uint8).tobytes()
        elif kind == 3:
            raw = rng.choice(
                np.frombuffer(b"ACGTacgt =\n,the", np.uint8), n).tobytes()
        elif kind == 4:
            raw = bytes([int(rng.integers(0, 256))]) * n
        elif kind == 5:
            d = int(rng.integers(1, 9))
            raw = (bytes(range(d)) * (n // d + 1))[:n]
        else:
            c = zlib.compressobj(int(rng.integers(1, 10)),
                                 zlib.DEFLATED, -15, 8)
            parts, out, left = [], b"", n
            while left > 0:
                k = min(left, int(rng.integers(1, 8000)))
                seg = rng.integers(0, 30, k, dtype=np.uint8).tobytes()
                parts.append(seg)
                out += c.compress(seg)
                if rng.random() < 0.5:
                    out += c.flush(zlib.Z_FULL_FLUSH)
                left -= k
            return b"".join(parts), out + c.flush()
        strat = [zlib.Z_DEFAULT_STRATEGY, zlib.Z_FIXED,
                 zlib.Z_FILTERED][i % 3]
        return raw, z(raw, int(rng.integers(1, 10)), strat)

    from disq_tpu.ops import inflate_simd as _inf
    from disq_tpu.ops import rans_simd as _rns

    # the silent host fallback would mask kernel divergences (a lane
    # that errors or mis-sizes is re-inflated by the oracle itself), so
    # count fallbacks and require zero: every lane decoded ON DEVICE
    inf0 = dict(_inf.last_stats)
    rns0 = dict(_rns.last_stats)
    bad = 0
    for rnd in range(2):
        raws, payloads = [], []
        while len(raws) < 128:
            r, p = gen(len(raws) + rnd * 128)
            if len(p) <= MAX_DEVICE_CSIZE and len(r) <= 65536:
                raws.append(r)
                payloads.append(p)
        got = inflate_payloads_simd(
            payloads, usizes=[len(r) for r in raws], interpret=False)
        bad += sum(g != r for g, r in zip(got, raws))
    r_raws, r_streams = [], []
    while len(r_raws) < 128:
        n = int(rng.integers(0, 40000))
        a = int(rng.integers(1, 250))
        r = rng.integers(0, a, n, dtype=np.uint8).tobytes()
        s = rans_encode_order0(r)
        # keep every stream within the device caps — oversize streams
        # would be host-vs-host comparisons that can never fail
        if len(s) - 9 <= _rns.MAX_DEVICE_CSIZE:
            r_raws.append(r)
            r_streams.append(s)
    r_got = rans0_decode_simd(r_streams, interpret=False)
    bad += sum(g != r for g, r in zip(r_got, r_raws))
    inf_fb = _inf.last_stats["host_fallback"] - inf0["host_fallback"]
    inf_big = _inf.last_stats["host_big"] - inf0["host_big"]
    rns_fb = _rns.last_stats["host_fallback"] - rns0["host_fallback"]
    rns_big = _rns.last_stats["host_big"] - rns0["host_big"]
    results.append({
        "kernel": "on_chip_differential_fuzz",
        "shape": "256 DEFLATE (7 shapes x levels x strategies) + 128 rANS",
        "mismatches": bad,
        "host_fallback_lanes": inf_fb + rns_fb,
        "host_big_lanes": inf_big + rns_big,
        "correct": bad == 0 and inf_fb + rns_fb + inf_big + rns_big == 0,
    })
    assert bad == 0, f"{bad} on-chip kernel divergences from host oracles"
    assert inf_fb + rns_fb == 0, "kernel lanes silently fell back to host"
    assert inf_big + rns_big == 0, "fuzz payloads escaped the device caps"


def run_device_pipeline_row(results: list) -> None:
    """Device-resident read pipeline under jax.transfer_guard:
    decoded bytes -> prefix gather -> Pallas parse -> keys -> sort ->
    flagstat with zero intermediate device<->host copies, on the real
    chip where the guard genuinely bites."""
    from disq_tpu.runtime.device_pipeline import run_device_pipeline

    rng = np.random.default_rng(5)
    n = 200_000
    # synthetic fixed-shape records: block_size word + 8 prefix words
    rec_words = 9 + 16
    blob = np.zeros(n * rec_words * 4, np.uint8)
    w = blob.view("<i4").reshape(n, rec_words)
    w[:, 0] = rec_words * 4 - 4
    w[:, 1] = rng.integers(-1, 5, n)
    w[:, 2] = rng.integers(0, 1 << 20, n)
    w[:, 3] = 8 | (60 << 8)
    w[:, 4] = (rng.integers(0, 16, n) << 16) | 1
    offs = np.arange(0, (n + 1) * rec_words * 4, rec_words * 4,
                     dtype=np.int64)
    keys, order, stats = run_device_pipeline(blob, offs, interpret=False)
    ok = stats["total"] == n and (keys[1:] >= keys[:-1]).all()
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        # unpack: the result fetch is lazy now — materializing keeps
        # this row's timing covering upload + kernels + results d2h
        _k, _o, _s = run_device_pipeline(blob, offs, interpret=False)
        best = min(best, time.perf_counter() - t0)
    results.append({
        "kernel": "device_pipeline_parse_sort_flagstat",
        "shape": f"{n} records",
        "records_per_sec": round(n / best, 1),
        "transfer_guard": "disallow (enforced)",
        "correct": bool(ok),
    })
    assert ok


# ---------------------------------------------------------------------------
# The resident chain's kernels (decode service, HBM-resident decode, device
# write path, operator suite, mesh parse), each against its host twin
# ---------------------------------------------------------------------------

_REFS = (("chr1", 1 << 26), ("chr2", 1 << 26), ("chr3", 1 << 24))


def _record_shard(n: int, seed: int):
    """(record blob u8, (n+1,) offsets, host ReadBatch): single-end
    100 bp reads with duplicate positions, mixed flags/mapq and RG
    tags — enough for every operator to have work."""
    from disq_tpu.bam.codec import decode_records, encode_records_with_offsets
    from disq_tpu.bam.columnar import ReadBatch

    rng = np.random.default_rng(seed)
    rl = 100
    names = np.frombuffer(
        b"".join(b"r%07d" % i for i in range(n)), np.uint8).copy()
    tags = np.tile(np.frombuffer(b"RGZrg0\x00", np.uint8), n).copy()
    tags[5::7] = ord("0") + (np.arange(n) % 2)
    flag = rng.choice(np.array([0, 16, 4, 256, 2048, 1024], np.uint16),
                      n, p=[.45, .35, .05, .05, .05, .05])
    batch = ReadBatch(
        refid=rng.integers(0, len(_REFS), n).astype(np.int32),
        pos=rng.integers(0, 200_000, n).astype(np.int32),
        mapq=rng.integers(0, 61, n).astype(np.uint8),
        bin=np.zeros(n, np.uint16), flag=flag,
        next_refid=np.full(n, -1, np.int32),
        next_pos=np.full(n, -1, np.int32), tlen=np.zeros(n, np.int32),
        name_offsets=np.arange(0, 8 * n + 1, 8, dtype=np.int64), names=names,
        cigar_offsets=np.arange(n + 1, dtype=np.int64),
        cigars=np.full(n, (rl << 4) | 0, np.uint32),
        seq_offsets=np.arange(0, (n + 1) * rl, rl, dtype=np.int64),
        seqs=(1 << rng.integers(0, 4, n * rl, dtype=np.uint8)).astype(
            np.uint8),
        quals=np.repeat(rng.integers(28, 42, n * rl // 10, dtype=np.uint8),
                        10),
        tag_offsets=np.arange(0, 7 * n + 1, 7, dtype=np.int64), tags=tags)
    blob, offsets = encode_records_with_offsets(batch)
    blob = np.frombuffer(blob, np.uint8)
    return blob, offsets, decode_records(blob, offsets, n_ref=len(_REFS))


def _bgzf_payloads(blob: np.ndarray):
    raws = [blob[o: o + 65280].tobytes() for o in range(0, len(blob), 65280)]
    return raws, [_deflate(r) for r in raws]


_FIXED = ("refid", "pos", "mapq", "bin", "flag", "next_refid", "next_pos",
          "tlen")


def _same_fixed(got, want) -> bool:
    return all(
        np.asarray(getattr(got, f)).dtype == np.asarray(getattr(want, f)).dtype
        and np.array_equal(getattr(got, f), getattr(want, f))
        for f in _FIXED)


def run_resident_decode(results: list) -> None:
    """The direct (no service) resident route: SIMD inflate with its
    output kept in HBM -> ``_assemble_words_for`` compaction -> fused
    gather + Pallas parse. The smoke's service route never takes the
    assembly step, so this is where it is compiled and checked."""
    from disq_tpu.ops.inflate_simd import inflate_payloads_simd
    from disq_tpu.runtime.columnar import ColumnarBatch

    blob, offsets, host = _record_shard(200_000, 11)
    raws, payloads = _bgzf_payloads(blob)
    usizes = [len(r) for r in raws]

    def once():
        got, _offs, handle = inflate_payloads_simd(
            payloads, usizes=usizes, as_array=True, keep_device=True)
        words = handle.assemble()
        cb = ColumnarBatch.from_blob(
            got, offsets, n_ref=len(_REFS), device_words=words)
        return got, words, cb

    t0 = time.perf_counter()
    got, words, cb = once()
    cold = time.perf_counter() - t0
    ok_blob = np.array_equal(got, blob)
    ok_words = np.array_equal(
        np.asarray(words).view(np.uint8)[: len(blob)], blob)
    ok_cols = cb.device_backed and _same_fixed(cb, host)
    cb.release()
    t0 = time.perf_counter()
    once()[2].release()
    warm = time.perf_counter() - t0
    results.append({
        "kernel": "resident_decode_direct",
        "shape": f"{len(offsets) - 1} records, {len(payloads)} full blocks",
        "cold_sec": round(cold, 2), "warm_sec": round(warm, 3),
        "records_per_sec": round((len(offsets) - 1) / warm, 1),
        "inflate_equal": bool(ok_blob), "assembled_words_equal":
        bool(ok_words), "parsed_columns_equal": bool(ok_cols),
        "correct": bool(ok_blob and ok_words and ok_cols),
    })
    assert ok_blob and ok_words and ok_cols


def run_decode_service(results: list) -> None:
    """Cross-shard coalescing: four threads submit 40 blocks each; the
    dispatcher must fill lanes across them and deliver zlib's bytes."""
    from concurrent.futures import ThreadPoolExecutor

    from disq_tpu.runtime import device_service
    from disq_tpu.runtime.tracing import telemetry_snapshot

    blob, _offsets, _host = _record_shard(50_000, 12)
    raws, payloads = _bgzf_payloads(blob)
    raws, payloads = raws[:160], payloads[:160]
    service = device_service.DeviceDecodeService()
    try:
        def shard(k):
            part = payloads[k * 40: (k + 1) * 40]
            sizes = [len(r) for r in raws[k * 40: (k + 1) * 40]]
            out, _ = service.submit_inflate(part, sizes).result(600)
            return out.tobytes() == b"".join(raws[k * 40: (k + 1) * 40])

        with ThreadPoolExecutor(4) as pool:
            oks = list(pool.map(shard, range(4)))
    finally:
        service.close()
    fill = telemetry_snapshot()["gauges"].get("device.lane_fill", {})
    results.append({
        "kernel": "decode_service_coalesce",
        "shape": "4 submitters x 40 full blocks",
        "lane_fill": fill.get("", {}),
        "correct": all(oks),
    })
    assert all(oks)


def run_resident_operators(results: list) -> None:
    """flagstat / depth / coordinate sort / read filter / markdup /
    rgstats / pileup on a resident batch, each against the same
    operator's host path on the host-parsed batch."""
    from disq_tpu.ops.depth import window_depth
    from disq_tpu.ops.flagstat import flagstat_counts
    from disq_tpu.ops.markdup import markdup_batch
    from disq_tpu.ops.pileup import region_pileup
    from disq_tpu.ops.rfilter import apply_read_filter, parse_read_filter
    from disq_tpu.ops.rgstats import read_group_stats
    from disq_tpu.runtime.columnar import ColumnarBatch
    from disq_tpu.sort.coordinate import coordinate_sort_batch

    blob, offsets, host = _record_shard(200_000, 13)
    cb = ColumnarBatch.from_blob(blob, offsets, n_ref=len(_REFS))
    lens = [l for _n, l in _REFS]
    rf = parse_read_filter("-F 0x800 -q 10 -s 7.5")
    checks = {}
    checks["flagstat"] = cb.flagstat() == flagstat_counts(
        np.asarray(host.flag))
    d_dev, d_host = window_depth(cb, lens, 1024), window_depth(
        host, lens, 1024)
    checks["depth"] = all(np.array_equal(d_dev[r], d_host[r]) for r in d_host)
    f_dev, f_host = apply_read_filter(cb, rf), apply_read_filter(host, rf)
    checks["read_filter"] = (getattr(f_dev, "device_backed", False)
                             and _same_fixed(f_dev, f_host))
    s_dev = coordinate_sort_batch(f_dev, keep_resident=True)
    s_host = coordinate_sort_batch(f_host, use_mesh=False)
    checks["coordinate_sort"] = (getattr(s_dev, "device_backed", False)
                                 and _same_fixed(s_dev, s_host))
    m_dev, r_dev = markdup_batch(s_dev)
    m_host, r_host = markdup_batch(s_host)
    checks["markdup"] = (r_dev.stats() == r_host.stats()
                         and r_dev.duplicates > 0
                         and np.array_equal(m_dev.flag, m_host.flag))
    checks["rgstats"] = read_group_stats(m_dev) == read_group_stats(m_host)
    checks["pileup"] = np.array_equal(
        region_pileup(m_dev, 0, 50_000, 54_096),
        region_pileup(m_host, 0, 50_000, 54_096))
    results.append({
        "kernel": "resident_operators", "shape": f"{host.count} records",
        "checks": {k: bool(v) for k, v in checks.items()},
        "correct": all(checks.values()),
    })
    assert all(checks.values()), checks


def run_mesh_parse(results: list) -> None:
    """The Pallas parse inside a ``shard_map`` program on real devices
    (needs > 1; one chip records the skip)."""
    import jax

    from disq_tpu.runtime.columnar import ColumnarBatch
    from disq_tpu.runtime.mesh import get_mesh

    mesh = get_mesh(0)
    if mesh is None:
        results.append({"kernel": "mesh_parse",
                        "skipped": f"{len(jax.devices())} device",
                        "correct": True})
        return
    blob, offsets, host = _record_shard(100_000, 15)
    cb = ColumnarBatch.from_blob(blob, offsets, n_ref=len(_REFS), mesh=mesh)
    ok = cb.mesh is mesh and _same_fixed(cb, host) and (
        cb.flagstat()["total"] == host.count)
    results.append({
        "kernel": "mesh_parse", "shape": f"{host.count} records",
        "devices": int(mesh.devices.size), "correct": bool(ok)})
    assert ok


def main(out_path: str = "TPU_KERNELS.json",
         wgs30x_records: str = "", ont30x_records: str = "") -> int:
    """``wgs30x_records``, ``ont30x_records``: files of the benchmark's
    record bytes for the ``inflate_simd_wgs30x_kernel_only`` and
    ``inflate_simd_ont30x_kernel_only`` rows (each left out without)."""
    import jax

    from disq_tpu.util import enable_compile_cache

    enable_compile_cache()
    backend = jax.default_backend()
    if backend != "tpu":
        # this lane exists to test the chip: no chip is an error
        print(f"ERROR: backend is {backend}, not tpu", file=sys.stderr)
        return 2
    results: list = []
    rows = [run_inflate_simd, run_inflate_simd_literal_heavy]
    if wgs30x_records:
        with open(wgs30x_records, "rb") as f:
            rows.append(functools.partial(
                run_inflate_simd_wgs30x, record_bytes=f.read()))
    if ont30x_records:
        with open(ont30x_records, "rb") as f:
            rows.append(functools.partial(
                run_inflate_simd_ont30x, record_bytes=f.read()))
    for fn in (*rows,
               run_rans_simd, run_kernel_fuzz,
               run_device_pipeline_row, run_resident_decode,
               run_decode_service, run_resident_operators,
               run_mesh_parse):
        try:
            fn(results)
        except Exception as e:  # record the failure, keep going
            results.append({
                "kernel": getattr(fn, "func", fn).__name__,
                "error": f"{type(e).__name__}: {e}",
                "correct": False,
            })
    import jaxlib

    artifact = {
        "backend": backend,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__},
        "results": results,
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps(artifact))
    return 0 if all(r.get("correct") for r in results) else 1


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
