"""Whole-file device reads of an aligned long-read BAM, pass after pass
for the window: release of the pass before's dataset ->
``storage.read(input)`` -> ``count()``, ``flagstat()``, ``depth()``.

A pass ends with its last answer on the host; its dataset stays resident
until the next pass begins, so that the last pass's columns are there to
compare.  The window starts passes until ``seconds`` have gone and ends
with the pass then running; the cell's rate is all the records of those
passes over all of that time.

``correct`` holds the read to the plain reference
(``benchmark/reference_longread.py``): every pass's answers; every BGZF
block inflated on the device and none on the host, the blocks whose
payload is over 32,752 bytes at the wide launch geometry; the last
pass's 17 columns against the generator's arrays.
"""

from __future__ import annotations

import os
import statistics
import time

import numpy as np

from benchmark import gen_longread, reference, reference_longread
from benchmark.drivers import program

# what the SIMD inflate kernel took before it took every BGZF payload:
# a block over it is a "wide" one (launch geometry cw 16384)
NARROW_PAYLOAD = 8192 * 4 - 16
BGZF_LARGEST_PAYLOAD = 65536 - 26
WIDE_LANES = ("device.inflate.lanes", "cw=16384")


def block_table(path: str) -> np.ndarray:
    """(payload bytes, decoded bytes) of each BGZF block that holds
    data, from the file's own block headers."""
    with open(path, "rb") as f:
        data = f.read()
    return np.array([(bsize - 26, isize)
                     for _o, bsize, isize in reference.bgzf_members(data)
                     if isize > 0], np.int64).reshape(-1, 2)


def wide_lanes() -> int:
    name, label = WIDE_LANES
    return int(sum(v for k, v in program.counters().get(name, {}).items()
                   if label in k.split(",")))


class Driver:
    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.p = ctx.traffic
        self.input = os.path.join(ctx.workdir, "input.bam")
        self.passes = []          # (seconds, answer) of each window pass
        self.cpu_s = []           # this process's CPU seconds in each
        self.kept = None          # the last pass's dataset

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        from disq_tpu.ops import inflate_simd

        if inflate_simd.MAX_DEVICE_CSIZE < BGZF_LARGEST_PAYLOAD:
            # a tree whose kernel stops at 32 KiB of payload cannot pass
            # this cell: it ends here, before it generates anything
            raise SystemExit(
                "longread_passes: the program's inflate kernel takes "
                f"payloads up to {inflate_simd.MAX_DEVICE_CSIZE} bytes and "
                f"BGZF's largest is {BGZF_LARGEST_PAYLOAD}: the cell's "
                "blocks would inflate on the host")
        t0 = time.perf_counter()
        n = self.p["records"]
        self.truth = gen_longread.generate(n, self.ctx.seed, self.ctx.config)
        written = self.truth
        if self.ctx.control == "drop_record":
            # the control: the program's answers lack one record
            written = self.truth.take(np.arange(n - 1))
        program.write_input(written, self.ctx.config, self.p, self.input)
        size = os.path.getsize(self.input)
        table = block_table(self.input)
        self.blocks = len(table)
        self.wide_blocks = int((table[:, 0] > NARROW_PAYLOAD).sum())
        self.decoded = reference_longread.record_bytes(self.truth)
        full = table[table[:, 1] >= 65280]
        print(f"input: {n} records, {self.decoded / n:.1f} decoded bytes a "
              f"record, {len(self.truth.cigars) / n:.1f} CIGAR ops a record; "
              f"{size} bytes BGZF in {self.blocks} blocks, zlib ratio "
              f"{int(table[:, 1].sum()) / max(1, int(table[:, 0].sum())):.3f}"
              f"; {len(full)} full blocks, of which "
              f"{int((full[:, 0] > NARROW_PAYLOAD).sum())} have a payload "
              f"over {NARROW_PAYLOAD} bytes ({self.wide_blocks} of all "
              "blocks)", flush=True)
        self.storage = program.storage(self.ctx.config, self.p)
        t1 = time.perf_counter()
        self.one_pass()           # warm-up: exactly the window's shapes
        self.passes.clear()
        self.cpu_s.clear()
        print(f"set-up: generate + write input {t1 - t0:.1f} s, warm-up "
              f"pass {time.perf_counter() - t1:.1f} s", flush=True)

    # -- one pass ---------------------------------------------------------------

    def one_pass(self) -> None:
        t0, cpu0 = time.perf_counter(), time.process_time()
        with self.ctx.annotate("longread_read"):
            if self.kept is not None:
                self.kept.reads.release()
            ds = self.storage.read(self.input)
            answer = (ds.count(), ds.flagstat(),
                      ds.depth(self.ctx.config["depth_window"]))
            self.kept = ds
        self.passes.append((time.perf_counter() - t0, answer))
        self.cpu_s.append(time.process_time() - cpu0)

    def window(self, seconds: float) -> dict:
        lanes0, wide0 = program.device_lanes(), wide_lanes()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self.one_pass()
        elapsed = time.perf_counter() - t0
        lanes1 = program.device_lanes()
        self.lanes = {k: lanes1[k] - lanes0[k] for k in lanes1}
        self.lanes["wide"] = wide_lanes() - wide0
        n = self.p["records"]
        rates = [n / s for s, _ in self.passes]
        print(f"window: {n * len(rates)} records in {elapsed:.3f} s, "
              f"{len(rates)} passes (median pass rate "
              f"{statistics.median(rates):.1f} records/s), rates "
              + " ".join(f"{r:.0f}" for r in rates), flush=True)
        # for the reader of a slow pass: a stall with the usual CPU
        # seconds was a wait (device, disk), one with more was the host
        print("passes: this process's CPU seconds "
              + " ".join(f"{c:.1f}" for c in self.cpu_s), flush=True)
        return {
            self.p["metric"]: n * len(rates) / elapsed,
            "pass_rate_median": statistics.median(rates),
            "passes": len(rates), "records": n * len(rates),
            "attempted": len(rates),
            # what the inflate kernel has to move in a pass: the
            # compressed file in, the decoded record bytes out
            "inflate_bytes": (os.path.getsize(self.input) + self.decoded)
            * len(rates),
        }

    # -- the comparison ---------------------------------------------------------

    def check(self, checks) -> int:
        cfg = self.ctx.config
        want = (self.truth.count, reference.flagstat(self.truth.flag),
                reference_longread.depth(
                    self.truth, [c["length"] for c in cfg["contigs"]],
                    cfg["depth_window"]))
        failed = 0
        for _s, (count, fs, dp) in self.passes:
            failed += (count != want[0] or fs != want[1]
                       or reference.depth_differs(dp, want[2]))
        checks.add("passes whose count, flagstat or depth differ from "
                   "the reference", failed)
        checks.add("blocks the device did not inflate (of "
                   f"{self.blocks} a pass)",
                   self.blocks * len(self.passes)
                   - self.lanes["device_lanes"])
        checks.add("blocks inflated on the host (oversize or flagged)",
                   self.lanes["host_big"] + self.lanes["host_fallback"])
        checks.add("blocks over the narrow payload that missed the wide "
                   f"launch geometry (of {self.wide_blocks} a pass)",
                   self.wide_blocks * len(self.passes) - self.lanes["wide"])
        reference.columns_differing(
            self.kept.reads, self.truth, checks, "resident")
        return failed

    def close(self) -> None:
        if self.kept is not None:
            self.kept.reads.release()
        program.shutdown()
