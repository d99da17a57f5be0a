"""An interval read of a coordinate-sorted, BAI-indexed BAM, pass after
pass for the window: release of the pass before's dataset ->
``storage.read(input, TraversalParameters(intervals))`` with the
configuration's padded, merged target list (the same list in every pass
and for every seed) -> ``count()``, ``flagstat()``, ``depth()`` of what
came back.

A pass ends with its last answer on the host; its dataset stays
resident until the next pass begins, so that the last pass's columns are
there to compare.  The window starts passes until ``seconds`` have gone
and ends with the pass then running; the cell's rate is all the
*returned* records of those passes over all of that time.

``correct`` holds the read to the plain reference
(``benchmark/reference_intervals.py``): every pass's answers; the blocks
a pass decoded against the blocks the program's plan touches, counted
from the file's own block table (none more, all on the device); one
plan a pass; the last pass's 17 columns, the eight fixed ones as the
device holds them.
"""

from __future__ import annotations

import collections
import os
import statistics
import time
import types

import numpy as np

from benchmark import gen, reference, reference_intervals
from benchmark.drivers import program
from benchmark.drivers.op_chain import release
# the planned chunks are part of the comparison: a tree whose traversal
# has no plan (the serial per-chunk route) stops here, before it
# generates anything
from disq_tpu.traversal.bai_query import plan_traversal

# one window pass: its seconds, the records it returned, its answers,
# whether its dataset was device-backed
Pass = collections.namedtuple("Pass", "seconds returned answer resident")

COUNTERS = ("traversal.blocks", "traversal.decoded_records",
            "traversal.returned_records")


def counter_total(name: str) -> int:
    return int(sum(program.counters().get(name, {}).values()))


def overlap_bytes(decoded: int, launches: int, table: int) -> int:
    """What the overlap test has to move: a decoded record's reference
    id, position and end in (4 B each) and its mask byte out, and the
    table's starts and ends (4 B each) once a launch."""
    return decoded * 13 + launches * table * 8


def intervals_of(cfg: dict, target_list) -> list:
    """The target list as the program takes it: 1-based closed."""
    from disq_tpu.api import Interval

    names = [c["name"] for c in cfg["contigs"]]
    refid, start0, end0 = target_list
    return [Interval(names[r], s + 1, e) for r, s, e in
            zip(refid.tolist(), start0.tolist(), end0.tolist())]


def shiftable_target(want, target_list):
    """The first target whose last base is the first of a kept record
    that reaches no other target, or None: ending it one base early
    loses that record."""
    refid, start0, end0 = target_list
    reach = {}
    for key, end in zip(zip(want.refid.tolist(), want.pos.tolist()),
                        reference_intervals.alignment_ends(want).tolist()):
        reach[key] = max(end, reach.get(key, 0))
    for j in range(len(refid)):
        end = reach.get((refid[j], end0[j] - 1))
        last = j + 1 == len(refid) or refid[j + 1] != refid[j]
        if end is not None and (last or start0[j + 1] >= end):
            return j
    return None


class Driver:
    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.p = ctx.traffic
        self.input = os.path.join(ctx.workdir, "input.bam")
        self.passes = []          # a Pass for each window pass
        self.cpu_s = []
        self.kept = None          # the last pass's dataset, resident

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        from disq_tpu.api import TraversalParameters
        from disq_tpu.fsw.filesystem import resolve_path

        cfg, n = self.ctx.config, self.p["records"]
        t0 = time.perf_counter()
        truth = gen.generate(n, self.ctx.seed, cfg)
        t1 = time.perf_counter()
        truth = truth.take(reference.coordinate_order(truth))
        target_list = reference_intervals.targets(cfg, n)
        keep = reference_intervals.kept(truth, target_list)
        self.want = truth.take(keep)
        written, asked = truth, target_list
        if self.ctx.control == "drop_record":
            # the control: the input lacks one record a target covers
            written = truth.take(np.delete(np.arange(n), keep[0]))
        elif self.ctx.control == "shift_target":
            # the control: the program's list ends one target one base
            # early, where only that base holds a record to it
            j = shiftable_target(self.want, target_list)
            if j is None:
                raise SystemExit("no target's last base alone holds a "
                                 "record: shift_target has nothing to show")
            refid, start0, end0 = target_list
            end0 = end0.copy()
            end0[j] -= 1
            asked = (refid, start0, end0)
        self.wanted = (
            self.want.count, reference.flagstat(self.want.flag),
            reference.depth(self.want, [c["length"] for c in cfg["contigs"]],
                            cfg["depth_window"]))
        t2 = time.perf_counter()
        program.write_input(written, cfg, self.p, self.input,
                            sort_order="coordinate", index=True)
        del truth, written
        t3 = time.perf_counter()
        self.traversal = TraversalParameters(
            intervals=intervals_of(cfg, asked),
            traverse_unplaced_unmapped=cfg["targets"][
                "traverse_unplaced_unmapped"])
        self.table = len(asked[0])
        self.storage = program.storage(cfg, self.p)
        # the blocks the program's plan touches, from the file's own
        # block table: what a pass may decode, and what its inflate
        # kernel has to move
        with open(self.input, "rb") as f:
            members = np.array(
                [m for m in reference.bgzf_members(f.read()) if m[2] > 0],
                np.int64)
        fs, path = resolve_path(self.input)
        plan = plan_traversal(fs, path, program.header(cfg, "coordinate"),
                              self.traversal, self.p["executor_workers"])
        first = np.searchsorted(members[:, 0], plan.chunks[:, 0] >> 16)
        last = np.searchsorted(
            members[:, 0], (plan.chunks[:, 1] >> 16)
            + ((plan.chunks[:, 1] & 0xFFFF) > 0))
        touched = np.zeros(len(members), bool)
        for a, b in zip(first.tolist(), last.tolist()):
            touched[a:b] = True
        self.blocks = len(members)
        self.planned = int(touched.sum())
        self.planned_bytes = int(members[touched, 1:].sum())
        t4 = time.perf_counter()
        self.one_pass()           # warm-up: exactly the window's shapes
        self.passes.clear()
        self.cpu_s.clear()
        print(f"set-up: generate {t1 - t0:.1f} s, order + reference "
              f"{t2 - t1:.1f} s ({self.table} targets keep "
              f"{self.want.count} of {n}), write BAM + BAI {t3 - t2:.1f} s "
              f"({os.path.getsize(self.input)} bytes BGZF, {self.blocks} "
              f"blocks), plan {t4 - t3:.1f} s ({len(plan.chunks)} chunks in "
              f"{len(plan.tasks)} tasks touch {self.planned} blocks, "
              f"{self.planned_bytes} bytes in and out), warm-up pass "
              f"{time.perf_counter() - t4:.1f} s", flush=True)

    # -- one pass ---------------------------------------------------------------

    def one_pass(self) -> None:
        t0, cpu0 = time.perf_counter(), time.process_time()
        with self.ctx.annotate("interval_read"):
            release(self.kept)
            ds = self.storage.read(self.input, self.traversal)
            answer = (ds.count(), ds.flagstat(),
                      ds.depth(self.ctx.config["depth_window"]))
            self.kept = ds
        self.passes.append(Pass(
            time.perf_counter() - t0, int(ds.reads.count), answer,
            bool(getattr(ds.reads, "device_backed", False))))
        self.cpu_s.append(time.process_time() - cpu0)

    def window(self, seconds: float) -> dict:
        lanes0 = program.device_lanes()
        before = {name: counter_total(name) for name in COUNTERS}
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self.one_pass()
        t1 = time.perf_counter()
        lanes1 = program.device_lanes()
        self.lanes = {k: lanes1[k] - lanes0[k] for k in lanes1}
        self.grew = {name: counter_total(name) - before[name]
                     for name in COUNTERS}
        spans = program.spans_between(t0, t1)
        self.plans = sum(s["name"] == "traversal.plan" for s in spans)
        launches = sum(s["name"] == "traversal.overlap" for s in spans)
        returned = sum(p.returned for p in self.passes)
        rates = [p.returned / p.seconds for p in self.passes]
        print(f"window: {returned} records returned in {t1 - t0:.3f} s, "
              f"{len(rates)} passes (median pass rate "
              f"{statistics.median(rates):.1f} records/s), rates "
              + " ".join(f"{r:.0f}" for r in rates), flush=True)
        print("passes: seconds "
              + " ".join(f"{p.seconds:.2f}" for p in self.passes)
              + "; this process's CPU seconds "
              + " ".join(f"{c:.1f}" for c in self.cpu_s), flush=True)
        return {
            self.p["metric"]: returned / (t1 - t0),
            "pass_rate_median": statistics.median(rates),
            "passes": len(rates), "records": returned,
            "attempted": len(rates),
            # what the inflate kernel has to move in a pass: the blocks
            # the plan touches, compressed in and decoded out
            "inflate_bytes": self.planned_bytes * len(rates),
            "overlap_bytes": overlap_bytes(
                self.grew["traversal.decoded_records"], launches,
                self.table),
        }

    # -- the comparison ---------------------------------------------------------

    def check(self, checks) -> int:
        n = len(self.passes)
        failed = sum(
            p.answer[0] != self.wanted[0] or p.answer[1] != self.wanted[1]
            or reference.depth_differs(p.answer[2], self.wanted[2])
            for p in self.passes)
        checks.add("passes whose count, flagstat or depth differ from the "
                   "reference's over its kept set", failed)
        checks.add(f"blocks decoded more or fewer than the plan's chunks "
                   f"touch ({self.planned} of {self.blocks} a pass)",
                   abs(self.grew["traversal.blocks"] - self.planned * n))
        checks.add("blocks the passes decoded that the device did not "
                   "inflate", self.planned * n - self.lanes["device_lanes"])
        checks.add("blocks inflated on the host (oversize or flagged)",
                   self.lanes["host_big"] + self.lanes["host_fallback"])
        checks.add("traversal.plan spans in the window more or fewer than "
                   "passes", abs(self.plans - n))
        checks.add("passes whose dataset was not device-backed",
                   sum(not p.resident for p in self.passes))
        # a host-backed dataset holds no device columns: its own are
        # compared, so that the check above is the one that says so
        reads = self.kept.reads
        held = reads.device_columns() if self.passes[-1].resident else {}
        reference.columns_differing(types.SimpleNamespace(**{
            c: np.asarray(held[c]) if c in held else getattr(reads, c)
            for c in reference.ALL_COLUMNS}), self.want, checks,
            "returned")
        return failed

    def close(self) -> None:
        release(self.kept)
        program.shutdown()
