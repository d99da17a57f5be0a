"""What the drivers take from the program: the system under test, and
nothing of the yardstick."""

from __future__ import annotations

import os
import struct
import zlib

from benchmark.gen import Truth


def header(cfg: dict, sort_order: str = "unsorted"):
    from disq_tpu.bam.header import SamHeader

    h = SamHeader.build([(c["name"], c["length"]) for c in cfg["contigs"]])
    return h.with_sort_order(sort_order) if sort_order != "unsorted" else h


def storage(cfg: dict, params: dict):
    """The storage a cell states: the device path (resident decode; the
    device inflate and decode service come from the cell's ``env``) at
    the configuration's split size.  A traffic file's ``split_size_bytes``
    cuts a tiny file into several splits (the CPU tests only)."""
    from disq_tpu import ReadsStorage

    s = (ReadsStorage.make_default()
         .executor_workers(params["executor_workers"])
         .writer_workers(params["writer_workers"])
         .num_shards(params["writer_workers"])
         .split_size(params.get("split_size_bytes",
                                cfg["split_size_bytes"])))
    return s.resident_decode()


def write_input(truth: Truth, cfg: dict, params: dict, path: str,
                sort_order: str = "unsorted", index: bool = False) -> None:
    """The data at rest: the generator's records as a BAM written by the
    program's host writer.  ``bgzf_block_payload`` re-blocks it with
    stdlib zlib (interpreter-sized blocks for the CPU tests only)."""
    from disq_tpu import ReadsStorage
    from disq_tpu.api import BaiWriteOption, ReadsDataset
    from disq_tpu.bam.columnar import ReadBatch

    ds = ReadsDataset(header=header(cfg, sort_order),
                      reads=ReadBatch(**truth.columns()))
    host = (ReadsStorage.make_default()
            .writer_workers(params["writer_workers"])
            .num_shards(params["writer_workers"]))
    payload = params.get("bgzf_block_payload", 0)
    if index:
        if payload:
            raise ValueError("an indexed input cannot be re-blocked")
        host.write(ds, path, BaiWriteOption.ENABLE)
    else:
        host.write(ds, path)
        if payload:
            reblock_bgzf(path, payload)


def reblock_bgzf(path: str, payload: int) -> None:
    """Rewrite a BGZF file with ``payload``-byte blocks (stdlib zlib)."""
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


def sorted_bam_options() -> tuple:
    from disq_tpu.api import (
        BaiWriteOption, ReadsFormatWriteOption, SbiWriteOption)

    return (ReadsFormatWriteOption.BAM, BaiWriteOption.ENABLE,
            SbiWriteOption.ENABLE)


def counters() -> dict:
    from disq_tpu.runtime.tracing import telemetry_snapshot

    return telemetry_snapshot().get("counters", {})


def spans_between(t0: float, t1: float) -> list:
    """The program's spans that ended inside [t0, t1] (its clock is
    ``time.perf_counter``, as the harness's is)."""
    from disq_tpu.runtime.tracing import spans

    return [s for s in spans() if t0 <= s["ts"] + s["dur"] <= t1]


def device_lanes() -> dict:
    """Blocks the SIMD inflate served on the device, sent to the host as
    oversize, or flagged back to the host, since the process began."""
    from disq_tpu.ops import inflate_simd

    return dict(inflate_simd.last_stats)


def shutdown() -> None:
    from disq_tpu.runtime import device_service

    device_service.shutdown_service()


def file_sizes(path: str) -> tuple:
    return tuple(os.path.getsize(path + ext) for ext in ("", ".bai", ".sbi"))
