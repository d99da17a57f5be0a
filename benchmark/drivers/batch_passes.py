"""Whole-file passes, one after another for the window: ``read`` (device
read -> count, flagstat, depth -> release) or ``sort_write`` (coordinate
sort of a resident batch -> BAM + BAI + SBI to a fresh path).

A pass ends with its last answer on the host (the answers are host
values; the file is closed).  The window starts passes until ``seconds``
have gone and ends with the pass then running; the cell's rate is all
the records of those passes over all of that time.  The median of the
per-pass rates stands beside it as a per-layer number.
"""

from __future__ import annotations

import os
import statistics
import time

import numpy as np

from benchmark import gen, reference
from benchmark.drivers import program


class Driver:
    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.p = ctx.traffic
        self.kind = self.p["pass"]
        self.input = os.path.join(ctx.workdir, "input.bam")
        self.passes = []          # (seconds, answer) of each window pass
        self.cpu_s = []           # this process's CPU seconds in each
        self.kept = None          # the last read pass's dataset
        self.resident = None      # the batch every sort_write pass sorts
        self.out = None           # the last sort_write pass's file

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        t0 = time.perf_counter()
        n = self.p["records"]
        self.truth = gen.generate(n, self.ctx.seed, self.ctx.config)
        written = self.truth
        if self.ctx.control == "drop_record":
            # the control: the program's answers lack one record
            written = self.truth.take(np.arange(n - 1))
        program.write_input(written, self.ctx.config, self.p, self.input)
        size = os.path.getsize(self.input)
        self.blocks = reference.bgzf_blocks(self.input)
        self.storage = program.storage(self.ctx.config, self.p)
        t1 = time.perf_counter()
        if self.kind == "sort_write":
            self.resident = self.storage.read(self.input)
        t2 = time.perf_counter()
        self.one_pass(0)          # warm-up: exactly the window's shapes
        self.passes.clear()
        self.cpu_s.clear()
        print(f"set-up: generate + write input {t1 - t0:.1f} s "
              f"({size} bytes BGZF, {self.blocks} blocks), resident read "
              f"{t2 - t1:.1f} s, warm-up pass {time.perf_counter() - t2:.1f} s",
              flush=True)

    # -- one pass ---------------------------------------------------------------

    def one_pass(self, i: int) -> None:
        t0, cpu0 = time.perf_counter(), time.process_time()
        if self.kind == "read":
            with self.ctx.annotate("read"):
                if self.kept is not None:
                    self.kept.reads.release()
                ds = self.storage.read(self.input)
                answer = (ds.count(), ds.flagstat(),
                          ds.depth(self.ctx.config["depth_window"]))
                self.kept = ds
        else:
            with self.ctx.annotate("sort_write"):
                out = os.path.join(self.ctx.workdir, f"sorted_{i % 2}.bam")
                self.storage.write(self.resident.coordinate_sorted(), out,
                                   *program.sorted_bam_options())
                answer = program.file_sizes(out)
                self.out = out
        self.passes.append((time.perf_counter() - t0, answer))
        self.cpu_s.append(time.process_time() - cpu0)

    def window(self, seconds: float) -> dict:
        lanes0 = program.device_lanes()
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            self.one_pass(i)
            i += 1
        elapsed = time.perf_counter() - t0
        lanes1 = program.device_lanes()
        self.lanes = {k: lanes1[k] - lanes0[k] for k in lanes1}
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
        inflated = reference.record_bytes(self.truth) \
            if self.kind == "read" else 0
        return {
            self.p["metric"]: n * len(rates) / elapsed,
            "pass_rate_median": statistics.median(rates),
            "passes": len(rates), "records": n * len(rates),
            "attempted": len(rates),
            # what the inflate kernel has to move in a pass: the
            # compressed file in, the decoded record bytes out
            "inflate_bytes": (os.path.getsize(self.input) + inflated)
            * len(rates),
        }

    # -- the comparison ---------------------------------------------------------

    def check(self, checks) -> int:
        cfg = self.ctx.config
        failed = 0
        if self.kind == "read":
            want = (self.truth.count, reference.flagstat(self.truth.flag),
                    reference.depth(self.truth,
                                    [c["length"] for c in cfg["contigs"]],
                                    cfg["depth_window"]))
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
            reference.columns_differing(
                self.kept.reads, self.truth, checks, "resident")
        else:
            sizes = self.passes[-1][1]
            failed = sum(a != sizes for _s, a in self.passes)
            checks.add("passes whose BAM, BAI or SBI size differs from the "
                       "compared pass's", failed)
            reference.columns_differing(
                self.resident.reads, self.truth, checks, "resident")
            order = reference.coordinate_order(self.truth)
            reference.sorted_file(self.out, self.truth.take(order), checks)
        return failed

    def close(self) -> None:
        for ds in (self.kept, self.resident):
            if ds is not None:
                ds.reads.release()
        program.shutdown()
