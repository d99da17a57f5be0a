"""The chain on a device mesh, pass after pass for the window: release
of the pass before's batch -> mesh read of an unsorted BAM -> count,
flagstat, depth -> ``write(sort=True)`` to BAM + BAI + SBI at a fresh
path.

A pass ends with its sorted file closed; its batch stays resident until
the next pass begins (every pass releases one, the first the warm-up's),
so that the last pass's columns are there to compare.  The window starts
passes until ``seconds`` have gone and ends with the pass then running;
the cell's rate is all the records of those passes over all of that
time, as in ``batch_passes``.

What exists only across chips is part of ``correct``: every chip of the
mesh held inflate launches in every pass (the decode service labels a
launch's spans with its chip), the coordinate sort ran on the mesh in
every pass, and no sort fell back to the host's argsort.  The sorted
BAM, BAI and SBI are held, byte for byte, to what the program's host
writer makes of the reference order with no device in it.
"""

from __future__ import annotations

import collections
import hashlib
import os
import statistics
import time
import types

import numpy as np

from benchmark import gen, reference
from benchmark.drivers import program

# one window pass: its seconds, those to the last answer of its read,
# its answers, its files' sizes, its inflate launches by chip and the
# mesh sorts it ran
Pass = collections.namedtuple(
    "Pass", "seconds read_seconds answer sizes launches sorts")

FALLBACK = "device.mesh.sort_host_fallback"
LAUNCH = "device.launch.wait"        # one span a launch, on every chip
MESH_SORT = "mesh_sort_exchange"     # ``device.kernel``'s ``kernel``


def mesh_storage(cfg: dict, params: dict):
    """``program.storage`` on a mesh of the traffic's ``mesh_devices``;
    the mesh is built here, so that the run ends at once where the
    machine or the program cannot hold the cell."""
    from disq_tpu.runtime.mesh import mesh_for_storage

    want = params["mesh_devices"]
    storage = program.storage(cfg, params).mesh(want)
    mesh = mesh_for_storage(storage)
    got = 0 if mesh is None else int(mesh.devices.size)
    if got != want:
        raise SystemExit(f"the cell asks for a mesh of {want} devices and "
                         f"the program built one of {got}")
    if FALLBACK not in program.counters():
        # a counter that is absent reads as "did not move": the cell
        # holds this one to 0, so the program has to have registered it
        raise SystemExit(f"the program built a mesh and registered no "
                         f"counter {FALLBACK}: the cell cannot hold the "
                         "sort's host fallbacks to 0")
    return storage


def host_write(truth_sorted, cfg: dict, params: dict, path: str) -> None:
    """Records already in order as BAM + BAI + SBI by the program's host
    writer: a host batch, so no mesh, no sort and no device in it."""
    from disq_tpu import ReadsStorage
    from disq_tpu.api import ReadsDataset
    from disq_tpu.bam.columnar import ReadBatch

    ds = ReadsDataset(header=program.header(cfg, "coordinate"),
                      reads=ReadBatch(**truth_sorted.columns()))
    host = (ReadsStorage.make_default()
            .writer_workers(params["writer_workers"])
            .num_shards(params["writer_workers"]))
    host.write(ds, path, *program.sorted_bam_options())


def file_hashes(path: str) -> tuple:
    out = []
    for ext in ("", ".bai", ".sbi"):
        with open(path + ext, "rb") as f:
            out.append(hashlib.file_digest(f, "blake2b").hexdigest())
    return tuple(out)


def as_the_device_holds(batch):
    """A resident batch's columns for ``reference.columns_differing``,
    the fixed ones fetched from the device now.  Asked for them by
    name, a batch that has been written answers from its host parse
    (``ColumnarBatch.to_read_batch`` caches that over them), and the
    sharded parse's columns would go uncompared."""
    held = {k: np.asarray(v) for k, v in batch.device_columns().items()}
    return types.SimpleNamespace(**{
        c: held[c] if c in held else getattr(batch, c)
        for c in reference.ALL_COLUMNS})


def fallbacks() -> float:
    return sum(program.counters().get(FALLBACK, {}).values())


def launches_by_chip(spans) -> dict:
    """Inflate launches among ``spans``, counted by the chip (the
    ``device`` label) each went to."""
    return dict(collections.Counter(
        s["labels"]["device"] for s in spans
        if s["name"] == LAUNCH and s["labels"].get("kind") == "inflate"
        and "device" in s["labels"]))


class Driver:
    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.p = ctx.traffic
        self.input = os.path.join(ctx.workdir, "input.bam")
        self.passes = []          # a Pass for each window pass
        self.cpu_s = []
        self.kept = None          # the last pass's dataset, resident
        self.out = None           # the last pass's sorted file

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        self.storage = mesh_storage(self.ctx.config, self.p)
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
        t1 = time.perf_counter()
        # what the chain's files have to equal: the reference order
        # through the host writer alone
        ref = os.path.join(self.ctx.workdir, "host_sorted.bam")
        host_write(self.truth.take(reference.coordinate_order(self.truth)),
                   self.ctx.config, self.p, ref)
        self.want_files = file_hashes(ref)
        for ext in ("", ".bai", ".sbi"):
            os.remove(ref + ext)
        t2 = time.perf_counter()
        self.one_pass(0)          # warm-up: exactly the window's shapes
        self.passes.clear()
        self.cpu_s.clear()
        print(f"set-up: generate + write input {t1 - t0:.1f} s "
              f"({size} bytes BGZF, {self.blocks} blocks), the host "
              f"writer's sorted file {t2 - t1:.1f} s, warm-up pass "
              f"{time.perf_counter() - t2:.1f} s", flush=True)

    # -- one pass ---------------------------------------------------------------

    def one_pass(self, i: int) -> None:
        t0, cpu0 = time.perf_counter(), time.process_time()
        with self.ctx.annotate("chain"):
            out = os.path.join(self.ctx.workdir, f"sorted_{i % 2}.bam")
            if self.kept is not None:
                self.kept.reads.release()
            ds = self.storage.read(self.input)
            answer = (ds.count(), ds.flagstat(),
                      ds.depth(self.ctx.config["depth_window"]))
            t_read = time.perf_counter()
            self.storage.write(ds, out, *program.sorted_bam_options(),
                               sort=True)
            self.kept = ds
            self.out = out
        t1 = time.perf_counter()
        spans = program.spans_between(t0, t1)
        sorts = sum(s["name"] == "device.kernel"
                    and s["labels"].get("kernel") == MESH_SORT
                    for s in spans)
        self.passes.append(Pass(t1 - t0, t_read - t0, answer,
                                program.file_sizes(out),
                                launches_by_chip(spans), sorts))
        self.cpu_s.append(time.process_time() - cpu0)

    def window(self, seconds: float) -> dict:
        lanes0, fallbacks0 = program.device_lanes(), fallbacks()
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            self.one_pass(i)
            i += 1
        elapsed = time.perf_counter() - t0
        lanes1 = program.device_lanes()
        self.lanes = {k: lanes1[k] - lanes0[k] for k in lanes1}
        self.fallbacks = fallbacks() - fallbacks0
        n = self.p["records"]
        rates = [n / p.seconds for p in self.passes]
        print(f"window: {n * len(rates)} records in {elapsed:.3f} s, "
              f"{len(rates)} passes (median pass rate "
              f"{statistics.median(rates):.1f} records/s), rates "
              + " ".join(f"{r:.0f}" for r in rates), flush=True)
        print("passes: seconds to the last answer of the read "
              + " ".join(f"{p.read_seconds:.1f}" for p in self.passes)
              + "; this process's CPU seconds "
              + " ".join(f"{c:.1f}" for c in self.cpu_s), flush=True)
        by_chip = collections.Counter()
        for p in self.passes:
            by_chip.update(p.launches)
        print("inflate launches by chip: "
              + " ".join(f"{k}:{v}" for k, v in sorted(by_chip.items())),
              flush=True)
        numbers = {
            self.p["metric"]: n * len(rates) / elapsed,
            "pass_rate_median": statistics.median(rates),
            "passes": len(rates), "records": n * len(rates),
            "attempted": len(rates),
            # what the inflate kernel has to move in a pass: the
            # compressed file in, the decoded record bytes out
            "inflate_bytes": (os.path.getsize(self.input)
                              + reference.record_bytes(self.truth))
            * len(rates),
        }
        if by_chip:
            # 100 / mesh_devices is even, 100 is one chip
            numbers["chip_launch_share_max_pct"] = (
                100.0 * max(by_chip.values()) / sum(by_chip.values()))
        return numbers

    # -- the comparison ---------------------------------------------------------

    def check(self, checks) -> int:
        cfg = self.ctx.config
        want = (self.truth.count, reference.flagstat(self.truth.flag),
                reference.depth(self.truth,
                                [c["length"] for c in cfg["contigs"]],
                                cfg["depth_window"]))
        failed = 0
        for p in self.passes:
            count, fs, dp = p.answer
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
        chips = self.p["mesh_devices"]
        checks.add(f"chips of the {chips} with no inflate launch in a pass",
                   sum(chips - len(p.launches) for p in self.passes))
        checks.add("passes whose sort did not run on the mesh",
                   sum(p.sorts < 1 for p in self.passes))
        checks.add("sorts that fell back to the host", self.fallbacks)
        reference.columns_differing(
            as_the_device_holds(self.kept.reads), self.truth, checks,
            "resident")
        sizes = self.passes[-1].sizes
        differ = sum(p.sizes != sizes for p in self.passes)
        checks.add("passes whose BAM, BAI or SBI size differs from the "
                   "compared pass's", differ)
        order = reference.coordinate_order(self.truth)
        reference.sorted_file(self.out, self.truth.take(order), checks)
        checks.add("files of the compared pass (BAM, BAI, SBI) differing "
                   "from the host writer's of the reference order",
                   sum(a != b for a, b in zip(file_hashes(self.out),
                                              self.want_files)))
        return failed + differ

    def close(self) -> None:
        if self.kept is not None:
            self.kept.reads.release()
        program.shutdown()
