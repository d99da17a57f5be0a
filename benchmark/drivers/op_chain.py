"""The resident operator chain on one chip, pass after pass for the
window: release of the pass before's output -> device read of an
unsorted BAM -> ``ds.pipeline(*chain)`` (the configuration's ``chain``:
filter, coordinate sort, duplicate marking) -> ``write`` of what the
operators left to BAM + BAI + SBI at a fresh path.

A pass ends with its file closed; its output batch stays resident until
the next pass begins, so that the last pass's columns are there to
compare.  The window starts passes until ``seconds`` have gone and ends
with the pass then running; the cell's rate is all the *input* records
of those passes over all of that time, as in ``batch_passes``.

``correct`` holds the chain to the plain reference
(``benchmark/reference_chain.py``): what every pass kept, examined and
marked; the output's 17 columns, the eight fixed ones as the device
held them before the write (asked by name, a batch that has been
written answers from its host parse); the written records, and the
three files byte for byte against the program's host writer's of the
reference's records.
"""

from __future__ import annotations

import collections
import os
import statistics
import time
import types

import numpy as np

from benchmark import gen, reference, reference_chain
from benchmark.drivers import program
from benchmark.drivers.mesh_chain import file_hashes, host_write

# one window pass: its seconds, those to the end of its read and of its
# operators, what the operators said, whether their output was
# device-backed, and its files' sizes
Pass = collections.namedtuple(
    "Pass", "seconds read_seconds ops_seconds kept stats resident sizes")

EXAMINED = "ops.markdup.examined"


def scan_bytes(kept: int) -> int:
    """What the duplicate group scan has to move for ``kept`` records:
    four key columns of 4-byte words (reference, unclipped position,
    orientation, negated score) and the examined mask in, the duplicate
    mask out, a byte each, at the power-of-two length (from 64) the
    program pads its key uploads to."""
    padded = 64
    while padded < kept:
        padded *= 2
    return padded * (4 * 4 + 1 + 1)


def operators(cfg: dict) -> list:
    """The configuration's chain as ``ds.pipeline`` takes it."""
    return [tuple(op) if isinstance(op, list) else op
            for op in cfg["chain"]]


def release(ds) -> None:
    """Drop a dataset's device columns (a host batch has none)."""
    if ds is not None and hasattr(ds.reads, "release"):
        ds.reads.release()


class Driver:
    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.p = ctx.traffic
        self.chain = operators(ctx.config)
        self.view = next(op[1] for op in self.chain if op[0] == "filter")
        self.input = os.path.join(ctx.workdir, "input.bam")
        self.passes = []          # a Pass for each window pass
        self.cpu_s = []
        self.kept = None          # the last pass's output, resident
        self.held = None          # its fixed columns, taken before its write
        self.out = None           # its file

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        from disq_tpu.runtime.oppipe import OpPipeline

        OpPipeline(*self.chain)   # building the operators registers theirs
        if EXAMINED not in program.counters():
            # a counter that is absent reads as "did not move"; the
            # cell's metrics and checks read what this one says
            raise SystemExit(f"the program built the chain {self.chain} "
                             f"and registered no counter {EXAMINED}: the "
                             "cell cannot run on this tree")
        self.storage = program.storage(self.ctx.config, self.p)
        t0 = time.perf_counter()
        n = self.p["records"]
        self.truth = gen.generate(n, self.ctx.seed, self.ctx.config)
        self.want = reference_chain.chain(self.truth, self.view)
        written = self.truth
        if self.ctx.control == "drop_record":
            # the control: the program's answers lack one record, the
            # last that the filter passes
            last = np.flatnonzero(reference_chain.view_mask(
                self.truth, self.view))[-1]
            written = self.truth.take(np.delete(np.arange(n), last))
        program.write_input(written, self.ctx.config, self.p, self.input)
        size = os.path.getsize(self.input)
        self.blocks = reference.bgzf_blocks(self.input)
        t1 = time.perf_counter()
        # what the chain's files have to equal: the reference's records
        # through the host writer alone
        ref = os.path.join(self.ctx.workdir, "host_chain.bam")
        host_write(self.want.kept, self.ctx.config, self.p, ref)
        self.want_files = file_hashes(ref)
        for ext in ("", ".bai", ".sbi"):
            os.remove(ref + ext)
        t2 = time.perf_counter()
        self.one_pass(0)          # warm-up: exactly the window's shapes
        self.passes.clear()
        self.cpu_s.clear()
        print(f"set-up: generate + reference + write input {t1 - t0:.1f} s "
              f"({size} bytes BGZF, {self.blocks} blocks; the reference "
              f"keeps {self.want.kept.count} of {n}, examines "
              f"{self.want.examined}, marks {self.want.duplicates}), the "
              f"host writer's file of them {t2 - t1:.1f} s, warm-up pass "
              f"{time.perf_counter() - t2:.1f} s", flush=True)

    # -- one pass ---------------------------------------------------------------

    def one_pass(self, i: int) -> None:
        t0, cpu0 = time.perf_counter(), time.process_time()
        with self.ctx.annotate("chain"):
            out = os.path.join(self.ctx.workdir, f"chain_{i % 2}.bam")
            release(self.kept)
            ds = self.storage.read(self.input)
            t_read = time.perf_counter()
            out_ds, stats = ds.pipeline(*self.chain)
            if out_ds.reads is not ds.reads:
                release(ds)
            t_ops = time.perf_counter()
            resident = bool(getattr(out_ds.reads, "device_backed", False))
            self.held = out_ds.reads.device_columns() if resident else None
            self.storage.write(out_ds, out, *program.sorted_bam_options())
            self.kept = out_ds
            self.out = out
        t1 = time.perf_counter()
        self.passes.append(Pass(
            t1 - t0, t_read - t0, t_ops - t_read, int(out_ds.reads.count),
            stats.get("markdup", {}), resident, program.file_sizes(out)))
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
        rates = [n / p.seconds for p in self.passes]
        print(f"window: {n * len(rates)} records in {elapsed:.3f} s, "
              f"{len(rates)} passes (median pass rate "
              f"{statistics.median(rates):.1f} records/s), rates "
              + " ".join(f"{r:.0f}" for r in rates), flush=True)
        print("passes: seconds of the read "
              + " ".join(f"{p.read_seconds:.1f}" for p in self.passes)
              + ", of the operators "
              + " ".join(f"{p.ops_seconds:.2f}" for p in self.passes)
              + ", of the write "
              + " ".join(f"{p.seconds - p.read_seconds - p.ops_seconds:.1f}"
                         for p in self.passes)
              + "; this process's CPU seconds "
              + " ".join(f"{c:.1f}" for c in self.cpu_s), flush=True)
        return {
            self.p["metric"]: n * len(rates) / elapsed,
            "pass_rate_median": statistics.median(rates),
            "passes": len(rates), "records": n * len(rates),
            "attempted": len(rates),
            # what the inflate kernel has to move in a pass: the
            # compressed file in, the decoded record bytes out
            "inflate_bytes": (os.path.getsize(self.input)
                              + reference.record_bytes(self.truth))
            * len(rates),
            "markdup_scan_bytes": sum(scan_bytes(p.kept)
                                      for p in self.passes),
        }

    # -- the comparison ---------------------------------------------------------

    def check(self, checks) -> int:
        want = self.want
        wrong_kept = sum(p.kept != want.kept.count for p in self.passes)
        checks.add("passes whose kept count differs from the reference's",
                   wrong_kept)
        wrong_marks = sum(
            (p.stats.get("examined"), p.stats.get("duplicates"))
            != (want.examined, want.duplicates) for p in self.passes)
        checks.add("passes whose markdup examined or duplicates differ "
                   "from the reference's", wrong_marks)
        checks.add("duplicate bits flipped by the seam merge (one shard: "
                   "it has nothing to merge)",
                   sum(p.stats.get("boundary_flips", 0)
                       for p in self.passes))
        checks.add("blocks the device did not inflate (of "
                   f"{self.blocks} a pass)",
                   self.blocks * len(self.passes)
                   - self.lanes["device_lanes"])
        checks.add("blocks inflated on the host (oversize or flagged)",
                   self.lanes["host_big"] + self.lanes["host_fallback"])
        checks.add("passes whose output was not device-backed",
                   sum(not p.resident for p in self.passes))
        # a host-backed output holds no device columns: its own are
        # compared, so that the check above is the one that says so
        held = self.held or {}
        reference.columns_differing(types.SimpleNamespace(**{
            c: np.asarray(held[c]) if c in held
            else getattr(self.kept.reads, c)
            for c in reference.ALL_COLUMNS}), want.kept, checks,
            "chain output")
        sizes = self.passes[-1].sizes
        differ = sum(p.sizes != sizes for p in self.passes)
        checks.add("passes whose BAM, BAI or SBI size differs from the "
                   "compared pass's", differ)
        reference.sorted_file(self.out, want.kept, checks)
        checks.add("files of the compared pass (BAM, BAI, SBI) differing "
                   "from the host writer's of the reference's records",
                   sum(a != b for a, b in zip(file_hashes(self.out),
                                              self.want_files)))
        return wrong_kept + wrong_marks + differ

    def close(self) -> None:
        release(self.kept)
        program.shutdown()
