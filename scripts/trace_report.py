#!/usr/bin/env python
"""trace_report — replay a span JSONL into a per-shard waterfall.

Input: the JSONL written by ``DISQ_TPU_TRACE_JSONL`` /
``DisqOptions.span_log`` / ``start_span_log(path)`` — one
``{ts, dur, name, run, labels}`` object per line (plus ``meta`` lines
mapping each run's monotonic clock to the epoch).

Output (stdout):

- a per-shard **waterfall**: one row per shard, fetch/decode/stall
  spans rendered as ``F``/``D``/``s`` bars on a common timeline;
- **phase latency percentiles** (p50/p90/p99, computed exactly from
  the raw span durations — no bucket estimation);
- **stall attribution**: total span seconds by stage category (fetch
  vs decode vs emit-stall vs retry/quarantine), answering "where does
  wall-clock go";
- **top-K straggler shards** by busy seconds.

Watchdog stall events (``watchdog.stall`` spans) render as ``!`` bars
painted over the stage they interrupted, stage-attributed via labels;
when a meta line records nonzero ``dropped_spans`` (the in-memory span
ring overflowed), a warning banner flags that ring-derived timelines
are truncated.

Device spans (``device.kernel`` / ``device.transfer`` — the synced
kernel timings from ``runtime/device_pipeline.py`` and the ``ops/``
wrappers) categorize as ``K``/``T``.

Usage::

    python scripts/trace_report.py spans.jsonl [--top 5] [--width 80]
        [--run RUN_ID]
    python scripts/trace_report.py spans.jsonl --analyze
    python scripts/trace_report.py progress.jsonl --progress
    python scripts/trace_report.py profile.collapsed --flame
    python scripts/trace_report.py --postmortem <bundle-dir>
    python scripts/trace_report.py a.jsonl b.jsonl host:port \\
        --request <trace_id>

``--request <trace_id>`` is the cross-process stitcher: every input
(span JSONL files and/or live ``host:port`` introspection endpoints,
freely mixed) contributes the spans stamped with that request's trace
id, each source's monotonic timestamps are aligned to the epoch via
its meta lines (files) or the ``/spans`` response's ``epoch``/``mono``
pair (live), and the result is ONE waterfall for the request's whole
distributed life: serving-edge root span, admission wait, device-batch
share, scheduler RPCs — whichever processes touched it.  Below the
waterfall: the fraction of client wall-clock covered by spans, and
every uncovered gap attributed as ``hop`` (the bounding spans live in
different processes — network/queue handoff) or ``intra``
(uninstrumented time inside one process).

``--analyze`` is the "why is this run slow" mode: a time-sweep
attributes every instant of wall-clock to one bucket (stage / device /
transfer / stall / idle), a backward walk extracts the critical path
through the per-shard fetch→decode→emit chains and device spans, and
a one-line verdict names the bottleneck with the knob that moves it.
``--progress`` instead replays a progress JSONL
(``DisqOptions.progress_log``) into a per-direction
throughput-over-time ASCII sparkline.
``--flame`` treats the input as *collapsed stacks* (the sampling
profiler's export — ``/debug/profile``, ``profiler.collapsed()``, or
a bundle's ``profile.collapsed``) and renders an ASCII flame plus the
top-N functions by self/inclusive samples.
``--postmortem <bundle>`` renders a flight-recorder bundle
(``runtime/flightrec.py``, written on any abort when
``DisqOptions.postmortem_dir`` is set) into a one-page verdict: the
abort reason and error, the stalled/aborting shard named from the
event ring, the event tail, and the span analyzer's wall-clock
attribution merged in.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict
from typing import Any, Dict, List, Optional

# Stage attribution: span name prefix -> waterfall glyph / category.
# Read-direction stages first, then the write pipeline's (every format
# sink emits <fmt>.write.encode/.deflate/.stage per shard).
CATEGORIES = (
    ("fetch", "F", ("executor.fetch",)),
    ("decode", "D", ("executor.decode",)),
    # The writers' cut of the batch (bam/sink.py), inside
    # bam.write.encode and listed before it: a batch that holds its
    # records' bytes copies a shard's out of them there (the whole
    # encode), any other is sliced by columns; a batch some consumer
    # made parse (a host-built wrapper has no bytes) parses once
    # there, under its lock, which is not record encoding.
    ("write_slice", "m", ("bam.write.slice",)),
    ("encode", "E", ("bam.write.encode", "vcf.write.encode",
                     "bcf.write.encode", "cram.write.encode",
                     "sam.write.encode")),
    ("deflate", "Z", ("bam.write.deflate", "vcf.write.deflate",
                      "bcf.write.deflate")),
    ("stage", "S", ("bam.write.stage", "vcf.write.stage",
                    "bcf.write.stage", "cram.write.stage",
                    "sam.write.stage")),
    # Device-pipeline spans (runtime/device_pipeline.py + ops/): synced
    # kernel execution and explicit h2d/d2h transfer phases.
    # The SIMD codecs' launches split it: blocked on the kernel
    # (device.launch.wait), upload and copy back (.submit / .d2h).
    ("device", "K", ("device.kernel", "device.launch.wait")),
    ("transfer", "T", ("device.transfer", "device.launch.submit",
                       "device.launch.d2h")),
    # Decode-service queue wait (runtime/device_service.py): the
    # oldest-lane wait of each flushed chunk — lanes sitting batched
    # before their kernel launched.
    ("service_wait", "w", ("device.service.wait",)),
    # The service's one dispatcher thread between kernels: host lane
    # packing before a launch and handing its lanes out after it.
    ("dispatch", "P", ("device.launch.pack", "device.launch.deliver")),
    # The dispatcher asleep with nothing to launch: the device's queue
    # ran dry, so the host side upstream (fetch, parse, emit) is the
    # one to look at.
    ("service_idle", "i", ("device.service.idle",)),
    # The host's work on what the device decoded (bgzf/codec.py), the
    # tail of codec.inflate.batch after the last lane of a shard is
    # delivered: the direct route's CRCs over the shared pool, and the
    # bytes copy (the decode service checks each launch's blocks as it
    # delivers them, so its route leaves the copy alone here).
    ("verify", "V", ("codec.inflate.verify",)),
    # HBM-resident fused decode (runtime/columnar.py): ColumnarBatch
    # build (upload-or-in-place parse chain; columnar.batch.stage is
    # the host copy of the decoded blob into its padded upload buffer
    # inside it, a view where the decode service decoded into that
    # buffer), lazy per-column fetches,
    # the CIGAR pass for the alignment ends, and release events
    # carrying the batch's d2h-avoided bytes; with them the host side
    # of windowed depth, which is those fetches, that pass and the
    # window arithmetic on a resident batch.
    ("columnar", "C", ("columnar.", "ops.depth.prepare")),
    # Hedged duplicate fetches (runtime/resilience.py): the duplicate's
    # own execution (hedge.fetch) and the loser's burned time
    # (hedge.waste) both paint H — a hedge racing its primary is
    # visible as overlap on the shard's row.
    ("hedge", "H", ("hedge.",)),
    # Cross-host scheduler (runtime/scheduler.py): worker RPC rounds,
    # the idle wait between empty lease rounds, and steal attempts —
    # the coordination cost of the distributed data plane.
    ("sched", "L", ("sched.",)),
    # Serving-plane admission queue (runtime/serve.py): a request
    # parked waiting for one of its tenant's concurrency slots — queue
    # time the QoS knobs (not a pipeline stage) control.
    ("serve_queue", "A", ("serve.admission.wait",)),
    ("emit_stall", "s", ("executor.emit.stall", "writer.emit.stall")),
    ("retry", "r", ("retry.",)),
    ("quarantine", "q", ("quarantine.",)),
    # Watchdog stall events paint last (highest z): a flagged hang must
    # never be hidden under the stage bar it interrupted. The span's
    # duration is the silent age at detection, so the '!' bar covers
    # exactly the dead air, stage-attributed via its labels.
    ("watchdog", "!", ("watchdog.",)),
)


def category_of(name: str) -> Optional[str]:
    for cat, _glyph, prefixes in CATEGORIES:
        for p in prefixes:
            if name == p or (p.endswith(".") and name.startswith(p)):
                return cat
    return None


def load_spans(path: str, run: Optional[str] = None):
    """Spans + meta records from one JSONL, optionally filtered to one
    run id (default: the LAST run seen — the usual 'report on the read
    I just did' case when several runs appended to one file).

    Also returns the total ``dropped_spans`` recorded by any meta
    trailer line: nonzero means the in-memory span ring overflowed
    while this log was being written, so ring-derived views (``/spans``)
    were truncated — the report surfaces it
    as a banner instead of silently rendering a partial waterfall."""
    spans: List[Dict[str, Any]] = []
    runs: List[str] = []
    dropped = 0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue  # torn tail line from a crash
            if rec.get("meta"):
                if rec.get("run_id") and rec["run_id"] not in runs:
                    runs.append(rec["run_id"])
                d = rec.get("dropped_spans")
                if isinstance(d, (int, float)):
                    dropped = max(dropped, int(d))
                continue
            if "name" not in rec or "ts" not in rec:
                continue
            if rec.get("run") and rec["run"] not in runs:
                runs.append(rec["run"])
            spans.append(rec)
    if run is None and runs:
        run = runs[-1]
    if run is not None:
        spans = [s for s in spans if s.get("run") == run]
    return spans, run, runs, dropped


def percentile(sorted_vals: List[float], p: float) -> float:
    """Exact linear-interpolated percentile over raw durations."""
    if not sorted_vals:
        return 0.0
    k = (len(sorted_vals) - 1) * p / 100.0
    lo = int(k)
    hi = min(lo + 1, len(sorted_vals) - 1)
    return sorted_vals[lo] + (sorted_vals[hi] - sorted_vals[lo]) * (k - lo)


def fmt_s(v: float) -> str:
    if v >= 1.0:
        return f"{v:8.3f}s"
    return f"{v * 1e3:7.2f}ms"


# Breaker-window shading (runtime/resilience.py): the open window is a
# solid band, the half-open probe window a lighter one.
_BREAKER_GLYPHS = {"breaker.open": "░", "breaker.half_open": "▒"}


def build_waterfall(spans, width: int) -> List[str]:
    """One row per shard; each executor-stage span paints its glyph
    over its [start, end) slice of the common timeline. Later (higher
    z) categories win inside one cell: stall over decode over fetch
    would hide work, so painting order is fetch < decode < stall —
    overlap shows the *later* pipeline stage.

    Circuit-breaker windows (``breaker.open`` / ``breaker.half_open``
    spans, emitted when the breaker leaves each state) render as
    shaded bands on their own per-filesystem rows below the shards —
    dead air across every shard during an open window reads as the
    breaker's doing, not a mystery stall."""
    by_shard: Dict[int, List[Dict[str, Any]]] = defaultdict(list)
    breaker_rows: Dict[str, List[Dict[str, Any]]] = defaultdict(list)
    t0, t1 = float("inf"), 0.0
    for s in spans:
        labels = s.get("labels") or {}
        if s["name"] in _BREAKER_GLYPHS:
            breaker_rows[str(labels.get("key", "?"))].append(s)
            t0 = min(t0, s["ts"])
            t1 = max(t1, s["ts"] + s["dur"])
            continue
        if "shard" not in labels or category_of(s["name"]) is None:
            continue
        try:
            shard = int(labels["shard"])
        except (TypeError, ValueError):
            continue
        by_shard[shard].append(s)
        t0 = min(t0, s["ts"])
        t1 = max(t1, s["ts"] + s["dur"])
    if not by_shard or t1 <= t0:
        return []
    scale = width / (t1 - t0)
    glyph = {cat: g for cat, g, _ in CATEGORIES}
    z = {cat: i for i, (cat, _, _) in enumerate(CATEGORIES)}
    rows = []
    shard_w = max(len(str(k)) for k in by_shard)
    for shard in sorted(by_shard):
        cells = [" "] * width
        depth = [-1] * width
        busy = 0.0
        for s in sorted(by_shard[shard], key=lambda s: s["ts"]):
            cat = category_of(s["name"])
            busy += s["dur"]
            a = int((s["ts"] - t0) * scale)
            b = max(a + 1, int((s["ts"] + s["dur"] - t0) * scale))
            for i in range(a, min(b, width)):
                if z[cat] >= depth[i]:
                    cells[i] = glyph[cat]
                    depth[i] = z[cat]
        rows.append(
            f"  shard {shard:>{shard_w}} |{''.join(cells)}| "
            f"{fmt_s(busy).strip()} busy")
    for key in sorted(breaker_rows):
        cells = [" "] * width
        for s in sorted(breaker_rows[key], key=lambda s: s["ts"]):
            glyph = _BREAKER_GLYPHS[s["name"]]
            a = int((s["ts"] - t0) * scale)
            b = max(a + 1, int((s["ts"] + s["dur"] - t0) * scale))
            for i in range(a, min(b, width)):
                cells[i] = glyph
        label = f"brk {key}"[: 6 + shard_w]
        rows.append(
            f"  {label:<{6 + shard_w}} |{''.join(cells)}| "
            "breaker open=░ half-open=▒")
    legend = "  " + " ".join(
        f"{g}={cat}" for cat, g, _ in CATEGORIES)
    span_line = (f"  timeline: {t1 - t0:.3f}s across "
                 f"{len(by_shard)} shards")
    return [span_line, legend, ""] + rows


def report(spans, run, runs, top: int, width: int,
           dropped: int = 0) -> str:
    out: List[str] = []
    if not spans:
        return "no spans found (empty or filtered-out trace)\n"
    out.append(f"run {run}  ({len(spans)} spans"
               + (f"; file holds runs: {', '.join(runs)}" if len(runs) > 1
                  else "") + ")")
    if dropped:
        out.append(
            f"WARNING: span ring overflowed ({dropped} spans dropped "
            "from the in-memory ring) — ring-derived timelines "
            "(/spans) are truncated")
    out.append("")

    # -- waterfall ---------------------------------------------------------
    wf = build_waterfall(spans, width)
    if wf:
        out.append("per-shard waterfall")
        out.extend(wf)
        out.append("")

    # -- phase latency percentiles ----------------------------------------
    by_name: Dict[str, List[float]] = defaultdict(list)
    for s in spans:
        by_name[s["name"]].append(s["dur"])
    out.append("phase latency percentiles")
    name_w = max(len(n) for n in by_name)
    out.append(f"  {'phase':<{name_w}}  {'calls':>6} {'total':>9} "
               f"{'p50':>9} {'p90':>9} {'p99':>9} {'max':>9}")
    for name in sorted(by_name, key=lambda n: -sum(by_name[n])):
        durs = sorted(by_name[name])
        out.append(
            f"  {name:<{name_w}}  {len(durs):>6} {fmt_s(sum(durs))} "
            f"{fmt_s(percentile(durs, 50))} {fmt_s(percentile(durs, 90))} "
            f"{fmt_s(percentile(durs, 99))} {fmt_s(durs[-1])}")
    out.append("")

    # -- stall attribution -------------------------------------------------
    by_cat: Dict[str, float] = defaultdict(float)
    for s in spans:
        cat = category_of(s["name"])
        if cat is not None:
            by_cat[cat] += s["dur"]
    if by_cat:
        total = sum(by_cat.values())
        out.append("stall attribution (span-seconds by stage)")
        for cat, _g, _p in CATEGORIES:
            if cat in by_cat:
                v = by_cat[cat]
                out.append(f"  {cat:<11} {fmt_s(v)}  "
                           f"{v / total * 100:5.1f}%")
        out.append("")

    # -- straggler shards --------------------------------------------------
    busy: Dict[int, float] = defaultdict(float)
    for s in spans:
        labels = s.get("labels") or {}
        if "shard" in labels and category_of(s["name"]) is not None:
            try:
                busy[int(labels["shard"])] += s["dur"]
            except (TypeError, ValueError):
                continue
    if busy:
        out.append(f"top-{top} straggler shards (busy seconds)")
        mean = sum(busy.values()) / len(busy)
        for shard, v in sorted(busy.items(), key=lambda kv: -kv[1])[:top]:
            out.append(f"  shard {shard:<6} {fmt_s(v)}  "
                       f"{v / mean:5.2f}x mean")
        out.append("")
    return "\n".join(out) + "\n"


# ---------------------------------------------------------------------------
# --analyze: critical path + wall-clock attribution + bottleneck verdict
# ---------------------------------------------------------------------------

# Stall-ish categories merge into one "stall" bucket for attribution;
# everything else keeps its stage name, plus "idle" for uninstrumented
# wall-clock.
STALL_CATEGORIES = {"emit_stall", "retry", "quarantine", "watchdog"}

# Tie-break priority when several work buckets are live in the same
# instant: the most downstream/specific work wins (a device kernel
# running concurrently with a host fetch means the run is device-side
# at that instant).  A hedge duplicate ranks below real stage work —
# it only wins instants where nothing else is making progress — and
# hedge-wasted time ranks last among work: it is burned concurrency,
# attributed to its own bucket so the --analyze verdict can name it.
WORK_PRIORITY = ("device", "transfer", "dispatch",
                 "columnar", "verify",
                 "decode", "write_slice", "encode", "deflate",
                 "stage", "fetch", "hedge", "hedge_wasted",
                 # service queue wait ranks last: it only wins instants
                 # where nothing is making progress — lanes parked in
                 # the batcher while the device sits idle
                 "service_wait",
                 # and the dispatcher's sleep after it: nothing queued
                 # at all
                 "service_idle",
                 # scheduler coordination ranks below all real work:
                 # RPC rounds only win instants where no stage runs,
                 # and steal/idle-wait time is by definition a worker
                 # with nothing to do
                 "sched", "steal",
                 # admission-queue wait ranks last: a parked request
                 # only wins instants where nothing else progresses
                 "serve_queue")

ADVICE = {
    "fetch": "I/O-bound range reads: raise executor_workers / "
             "prefetch_shards, or move the input closer",
    "decode": "CPU-bound record decode: raise executor_workers or "
              "enable the device codec",
    "encode": "CPU-bound record encode: raise writer_workers",
    "write_slice": "the writers' cut of the batch dominates "
                   "(bam.write.slice inside bam.write.encode): a batch "
                   "that holds its records' bytes copies each shard's "
                   "out of them (columnar.batch.materialize{how=bytes}) "
                   "— raise writer_workers / num_shards; a "
                   "how=parse span there is one writer parsing the "
                   "whole batch while the others wait",
    "verify": "the host's check of device-decoded blocks dominates "
              "(codec.inflate.verify: CRCs and the bytes copy after "
              "the device has answered) — it runs per shard on the "
              "decode worker; route the read through the decode "
              "service (DISQ_TPU_DEVICE_SERVICE=1), which checks each "
              "launch's blocks under the launches that follow",
    "deflate": "CPU-bound compression: raise writer_workers (the "
               "native codec already threads within a shard)",
    "stage": "staging-latency-bound writes: raise writer_workers / "
             "writer_prefetch_shards",
    "device": "device-bound: kernel time dominates; grow per-launch "
              "batches or add chips",
    "transfer": "transfer-bound: host<->device copies dominate; keep "
                "shards device-resident between stages",
    "stall": "serialization-bound: ordered-emit / retry stalls "
             "dominate; raise prefetch_shards",
    "idle": "pipeline starved: wall-clock outside instrumented stages "
            "(driver-side gaps between runs)",
    "hedge": "hedge duplicates dominate: the latency tail is wide — "
             "check the store, or raise hedge_quantile/hedge_min_s",
    "hedge_wasted": "hedge losses dominate: duplicates launch but "
                    "rarely win; raise hedge_quantile/hedge_min_s so "
                    "only real stragglers hedge",
    "service_wait": "decode-service queue wait dominates: lanes sit "
                    "batched while the device idles — lower "
                    "DISQ_TPU_SERVICE_FLUSH_MS, or raise "
                    "executor_workers so more shards feed the batcher",
    "dispatch": "the decode service's dispatcher dominates: its one "
                "thread packs and delivers lanes while no kernel runs "
                "— device.launch.pack against .deliver says which; "
                "widen DISQ_TPU_DISPATCH_WINDOW so kernels in flight "
                "cover it",
    "service_idle": "the decode service sleeps: no lanes queued — the "
                    "host upstream of it (fetch, parse hand-over, "
                    "ordered emit) starves the device; raise "
                    "executor_workers / prefetch_shards",
    "columnar": "resident-decode build/fetch dominates: columns are "
                "being materialized host-side after all — check which "
                "consumer forces the fetches (ops.depth.prepare is "
                "depth's: its ends label says whether the batch "
                "answered from its CIGAR bytes or a host parse), or "
                "widen shards so one parse launch covers more records",
    "d2h_avoided": "the fused resident path is paying off: these "
                   "bytes stayed in HBM instead of crossing d2h — "
                   "keep consumers on the resident columns "
                   "(flagstat/sort/depth) to grow this number",
    "sched": "scheduler RPC overhead dominates: raise sched_lease_n "
             "so each lease round carries more shards, or shrink the "
             "shard count (bigger split_size) — the queue is being "
             "polled more than it is worked; if "
             "sched.failover.rediscoveries is nonzero the time went "
             "into coordinator loss instead — check "
             "sched.failover.takeovers{host=} for who replayed the "
             "journal, and sched.quota.deferred for lease rounds the "
             "fairness quota trimmed under multi-run contention",
    "steal": "work-stealing wait dominates: this host idled while "
             "another held stale leases — lower sched_lease_n so "
             "stragglers hold fewer shards at a time, lower "
             "sched_lease_s so a dead host's leases requeue sooner, "
             "or check the victim host named in sched.steals{victim=}",
    "serve_queue": "admission-queue wait dominates: requests sit "
                   "parked for tenant slots — raise tenant_slots (or "
                   "spread load across tenants), or lower tenant_queue "
                   "so excess load sheds with 429 instead of burning "
                   "p99 in the queue; serve.admission{tenant=} names "
                   "who is queuing",
}


# Spans of the hand-over between the device's answer and the next
# kernel, by the word --analyze prints them under (device.transfer
# joins them by its site label).
HAND_OVER = {"codec.inflate.verify": "verify",
             "columnar.batch.stage": "stage",
             "bam.write.slice": "write_slice"}


def bucket_of(name: str) -> Optional[str]:
    # Hedge-wasted time (the losing side of a hedge race) attributes
    # to its own bucket: it is real wall-clock the hedging knob — not
    # a pipeline stage — controls.
    if name == "hedge.waste":
        return "hedge_wasted"
    # Steal rounds and the idle wait between empty lease rounds get
    # their own bucket: wall-clock a worker spent hungry — the signal
    # the stealing knobs (not a pipeline stage) control.  Plain
    # sched.rpc coordination stays in the "sched" bucket.
    if name in ("sched.steal", "sched.wait"):
        return "steal"
    cat = category_of(name)
    if cat is None:
        return None
    return "stall" if cat in STALL_CATEGORIES else cat


def attribute_wall(spans) -> "tuple[dict, float, float, float]":
    """Time-sweep wall-clock attribution: the run window [t0, t1] is
    split at every span boundary and each elementary interval is
    attributed to exactly ONE bucket — a live work bucket beats the
    stall bucket (work anywhere means the run is progressing), the
    busiest work bucket wins the interval, ties break by
    ``WORK_PRIORITY``; intervals with no categorized span live are
    ``idle``.  Returns ({bucket: seconds}, t0, t1, wall)."""
    events = []  # (time, delta, bucket)
    for s in spans:
        b = bucket_of(s["name"])
        if b is None or s["dur"] <= 0:
            continue
        events.append((s["ts"], 1, b))
        events.append((s["ts"] + s["dur"], -1, b))
    if not events:
        return {}, 0.0, 0.0, 0.0
    events.sort(key=lambda e: (e[0], -e[1]))
    t0 = events[0][0]
    t1 = max(e[0] for e in events)
    live: Dict[str, int] = defaultdict(int)
    out: Dict[str, float] = defaultdict(float)
    prev = t0
    i = 0
    rank = {b: i for i, b in enumerate(WORK_PRIORITY)}
    while i < len(events):
        t = events[i][0]
        if t > prev:
            work = [(b, n) for b, n in live.items()
                    if n > 0 and b != "stall"]
            if work:
                winner = min(work,
                             key=lambda bn: (-bn[1],
                                             rank.get(bn[0], 99)))[0]
            elif live.get("stall", 0) > 0:
                winner = "stall"
            else:
                winner = "idle"
            out[winner] += t - prev
            prev = t
        while i < len(events) and events[i][0] == t:
            live[events[i][2]] += events[i][1]
            i += 1
    return dict(out), t0, t1, t1 - t0


def critical_path(spans, max_segments: int = 512):
    """Backward walk from the end of the run: at each point pick the
    *innermost* (latest-starting) span covering it, jump to that
    span's start, and bridge uncovered gaps as ``idle`` — the chain of
    spans that actually determined the makespan.  Returns
    ``[(label, bucket, seconds), ...]`` in forward order."""
    import bisect

    items = []
    for s in spans:
        b = bucket_of(s["name"])
        if b is None or s["dur"] <= 0:
            continue
        items.append((s["ts"], s["ts"] + s["dur"], s, b))
    if not items:
        return []
    # Descending start time: the walk wants the LATEST-starting span
    # covering t, so a bisect into this order plus a forward scan that
    # stops at the first still-open span replaces the old full rescan
    # per segment (quadratic on big logs).
    items.sort(key=lambda i: -i[0])
    neg_starts = [-i[0] for i in items]      # ascending, for bisect
    sorted_ends = sorted(i[1] for i in items)  # for gap jumps
    eps = 1e-9
    t0 = items[-1][0]
    t = sorted_ends[-1]
    path = []
    while t > t0 + eps and len(path) < max_segments:
        # candidates: ts < t - eps  <=>  -ts > -(t - eps)
        idx = bisect.bisect_right(neg_starts, -(t - eps))
        winner = None
        for i in range(idx, len(items)):
            if items[i][1] >= t - eps:
                winner = items[i]
                break
        if winner is not None:
            ts, te, s, b = winner
            labels = s.get("labels") or {}
            if "shard" in labels:
                label = f"{b}[shard {labels['shard']}]"
            elif "kernel" in labels:
                label = f"{b}[{labels['kernel']}]"
            else:
                label = b
            path.append((label, b, min(te, t) - ts))
            t = ts
        else:
            # uncovered gap: jump to the latest span end before t
            j = bisect.bisect_left(sorted_ends, t - eps)
            if j == 0:
                break
            te = sorted_ends[j - 1]
            path.append(("idle", "idle", t - te))
            t = te
    path.reverse()
    return path


def analyze(spans, run, runs, dropped: int = 0) -> str:
    """The "why is this run slow" report: wall-clock attribution by
    bucket, the critical path, and a one-line bottleneck verdict."""
    if not spans:
        return "no spans found (empty or filtered-out trace)\n"
    buckets, _t0, _t1, wall = attribute_wall(spans)
    if not buckets or wall <= 0:
        return ("no categorized spans found (nothing to attribute)\n")
    out: List[str] = []
    out.append(f"run {run}  ({len(spans)} spans, wall {wall:.3f}s"
               + (f"; file holds runs: {', '.join(runs)}"
                  if len(runs) > 1 else "") + ")")
    if dropped:
        out.append(
            f"WARNING: span ring overflowed ({dropped} spans dropped "
            "from the in-memory ring) — attribution, critical path "
            "and verdict are computed from a truncated timeline")
    out.append("")
    out.append("wall-clock attribution")
    order = sorted(buckets, key=lambda b: -buckets[b])
    name_w = max(len(b) for b in order)
    for b in order:
        v = buckets[b]
        out.append(f"  {b:<{name_w}}  {fmt_s(v)}  "
                   f"{v / wall * 100:5.1f}%")
    out.append("")

    path = critical_path(spans)
    if path:
        out.append(f"critical path ({len(path)} segments)")
        shown = path if len(path) <= 12 else (
            path[:6] + [("...", None, None)] + path[-5:])
        parts = [
            lbl if dur is None else f"{lbl} {fmt_s(dur).strip()}"
            for lbl, _b, dur in shown
        ]
        # wrap at ~72 cols for readability
        line = "  "
        for j, part in enumerate(parts):
            token = part + (" -> " if j < len(parts) - 1 else "")
            if len(line) + len(token) > 74 and line.strip():
                out.append(line.rstrip())
                line = "    "
            line += token
        if line.strip():
            out.append(line.rstrip())
        out.append("")

    # d2h_avoided: a bytes bucket, not a wall-clock one — summed from
    # the columnar.batch.release spans' avoided_bytes labels (each
    # batch's device-resident columns that never crossed d2h).
    avoided = 0
    for s in spans:
        if s["name"] == "columnar.batch.release":
            try:
                avoided += int((s.get("labels") or {}).get(
                    "avoided_bytes", 0))
            except (TypeError, ValueError):
                pass
    if avoided:
        out.append(
            f"d2h_avoided: {avoided / 1e6:.2f} MB stayed "
            "device-resident (never fetched)")
        out.append(f"  ({ADVICE['d2h_avoided']})")
        out.append("")

    # the inflate kernel's two factors: supersteps a launch (the d2h
    # spans' supersteps labels, meta row 2 of each launch) and seconds
    # a superstep (the dispatcher's device.launch.wait over them); and
    # the share of the supersteps in which some lane read history past
    # the ring (the far_supersteps labels, meta row 3); and the copy
    # chunks that ran past the output word they started in (the
    # crossing_chunks labels, meta row 4 summed over a launch's lanes);
    # and the supersteps in which the kernel swept the compressed
    # buffer for the carry's window (the comp_fetches labels, meta row
    # 5: a log from before the window was carried has none)
    launches = steps = far_steps = crossing = fetches = 0
    wait_s = 0.0
    full: List[int] = []   # the full launches' own counts (128 lanes)
    for s in spans:
        labels = s.get("labels") or {}
        if labels.get("kind") != "inflate":
            continue
        if s["name"] == "device.launch.wait":
            wait_s += s["dur"]
        elif s["name"] == "device.launch.d2h" and "supersteps" in labels:
            launches += 1
            steps += int(labels["supersteps"])
            if int(labels.get("lanes", 0)) == 128:
                full.append(int(labels["supersteps"]))
            far_steps += int(labels.get("far_supersteps", 0))
            crossing += int(labels.get("crossing_chunks", 0))
            fetches += int(labels.get("comp_fetches", 0))
    if steps:
        out.append(
            f"inflate_supersteps: {steps / launches:,.0f} a launch over "
            f"{launches} launches, {wait_s / steps * 1e6:.2f} us a "
            f"superstep (device.launch.wait), {far_steps / steps * 100:.1f}% "
            f"of them read history past the ring, {crossing / launches:,.0f} "
            "copy chunks a launch crossed an output word's boundary"
            + (f", the compressed buffer swept in {fetches / steps:.3f} "
               "of them" if fetches else "")
            + (f"; {len(full)} full launches of {min(full):,} to "
               f"{max(full):,} supersteps" if full else ""))
        out.append("")

    # the hand-over between the device's answer and the next kernel:
    # the host check of the decoded blob, its copy into the padded
    # upload buffer, the uploads by site, and the writers' slice
    hand: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0, 0])
    for s in spans:
        labels = s.get("labels") or {}
        key = HAND_OVER.get(s["name"])
        if s["name"] == "device.transfer" and "site" in labels:
            key = f"transfer{{site={labels['site']}}}"
        if key is None:
            continue
        row = hand[key]
        row[0] += 1
        row[1] += s["dur"]
        try:
            row[2] += int(labels.get("bytes", 0))
        except (TypeError, ValueError):
            pass
    if hand:
        out.append("hand_over: " + "; ".join(
            f"{key} {fmt_s(sec).strip()} in {n}"
            + (f" ({nbytes / 1e6:.1f} MB)" if nbytes else "")
            for key, (n, sec, nbytes) in sorted(hand.items())))
        out.append("")

    top = order[0]
    out.append(
        f"verdict: {top} is the bottleneck — "
        f"{buckets[top] / wall * 100:.1f}% of wall-clock")
    out.append(f"  ({ADVICE.get(top, 'no advice for this bucket')})")
    return "\n".join(out) + "\n"


# ---------------------------------------------------------------------------
# --flame: collapsed stacks -> ASCII flame + top-N function table
# ---------------------------------------------------------------------------


def load_collapsed(path: str) -> List:
    """``(frames, count)`` pairs from a collapsed-stack file (one
    ``frame;frame;frame count`` line per folded stack)."""
    stacks = []
    with open(path) as f:
        for line in f:
            line = line.rstrip("\n")
            if not line.strip():
                continue
            stack, _, count = line.rpartition(" ")
            try:
                n = int(count)
            except ValueError:
                continue
            frames = [p for p in stack.split(";") if p]
            if frames and n > 0:
                stacks.append((frames, n))
    return stacks


def flame_report(stacks, top: int, width: int,
                 min_fraction: float = 0.01) -> str:
    """ASCII flame (inclusive samples down a prefix trie, pruned below
    ``min_fraction`` of the total) + top-N functions by self and by
    inclusive samples.  The profiler roots every stack at its thread
    role, so the first tier of the flame is the per-stage CPU split."""
    if not stacks:
        return "no samples found (empty or non-collapsed input)\n"
    total = sum(n for _f, n in stacks)
    root: Dict[str, list] = {}
    self_counts: Dict[str, int] = defaultdict(int)
    incl_counts: Dict[str, int] = defaultdict(int)
    for frames, n in stacks:
        node = root
        for f in frames:
            entry = node.setdefault(f, [0, {}])
            entry[0] += n
            node = entry[1]
        self_counts[frames[-1]] += n
        for f in set(frames):
            incl_counts[f] += n
    out: List[str] = [
        f"flame: {total} samples, {len(stacks)} folded stacks",
        "",
        f"ascii flame (inclusive; branches under "
        f"{min_fraction * 100:.0f}% pruned)",
    ]
    bar_w = max(10, width - 46)
    threshold = max(1.0, total * min_fraction)

    def walk(node: Dict[str, list], depth: int) -> None:
        for name, (count, children) in sorted(
                node.items(), key=lambda kv: -kv[1][0]):
            if count < threshold:
                continue
            bar = max(1, int(count / total * bar_w))
            label = ("  " * depth + name)[:42]
            out.append(f"  {label:<42} {'#' * bar:<{bar_w}} "
                       f"{count / total * 100:5.1f}%")
            walk(children, depth + 1)

    walk(root, 0)
    out.append("")
    out.append(f"top-{top} functions by self samples")
    for name, n in sorted(self_counts.items(),
                          key=lambda kv: -kv[1])[:top]:
        out.append(f"  {name:<46} {n:>8}  {n / total * 100:5.1f}%")
    out.append("")
    out.append(f"top-{top} functions by inclusive samples")
    for name, n in sorted(incl_counts.items(),
                          key=lambda kv: -kv[1])[:top]:
        out.append(f"  {name:<46} {n:>8}  {n / total * 100:5.1f}%")
    return "\n".join(out) + "\n"


# ---------------------------------------------------------------------------
# --postmortem: render a flight-recorder bundle into a one-page verdict
# ---------------------------------------------------------------------------


def _load_bundle_json(bundle: str, name: str) -> Dict[str, Any]:
    try:
        with open(os.path.join(bundle, name)) as f:
            doc = json.load(f)
        return doc if isinstance(doc, dict) else {}
    except (OSError, json.JSONDecodeError):
        return {}


def _load_bundle_jsonl(bundle: str, name: str) -> List[Dict[str, Any]]:
    out: List[Dict[str, Any]] = []
    try:
        with open(os.path.join(bundle, name)) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn tail line
                if isinstance(rec, dict):
                    out.append(rec)
    except OSError:
        pass
    return out


def _fmt_event(e: Dict[str, Any]) -> str:
    extra = " ".join(
        f"{k}={v}" for k, v in e.items()
        if k not in ("ts", "mono", "kind") and v is not None)
    return f"{e.get('kind', '?'):<18} {extra}"


def postmortem_report(bundle: str, top: int, width: int) -> str:
    """One-page bundle verdict: the abort, the shard it names, the
    event-ring tail, and the span analyzer's attribution merged in."""
    manifest = _load_bundle_json(bundle, "MANIFEST.json")
    options = _load_bundle_json(bundle, "options.json")
    healthz = _load_bundle_json(bundle, "healthz.json")
    events = _load_bundle_jsonl(bundle, "events.jsonl")
    if not (manifest or options or events):
        return f"not a postmortem bundle (no MANIFEST.json / " \
               f"events.jsonl under {bundle})\n"
    out: List[str] = []
    out.append(f"postmortem bundle {bundle}")
    out.append(
        f"  run {manifest.get('run_id', '?')}  "
        f"pid {manifest.get('pid', '?')}  "
        f"reason {manifest.get('reason', '?')}")
    error = manifest.get("error") or options.get("error")
    if error:
        out.append(f"  error: {error}")
    if healthz.get("status"):
        out.append(f"  healthz at dump: {healthz['status']}"
                   + (f" ({len(healthz.get('stalls') or [])} live "
                      "stalls)" if healthz.get("stalls") else ""))
    out.append("")

    # -- verdict: name the shard the event ring blames -----------------------
    stall = next((e for e in reversed(events)
                  if e.get("kind") == "watchdog_stall"), None)
    abort = next((e for e in reversed(events)
                  if e.get("kind") == "abort"), None)
    if stall is not None:
        out.append(
            f"verdict: shard {stall.get('shard', '?')} stalled in "
            f"{stall.get('stage', '?')} "
            f"({stall.get('age_s', '?')}s silent, "
            f"direction {stall.get('direction', '?')}, "
            f"policy {stall.get('policy', '?')})")
    elif abort is not None and abort.get("shard") is not None:
        out.append(
            f"verdict: aborted on shard {abort['shard']} — "
            f"{abort.get('error', '?')}")
    elif abort is not None:
        out.append(f"verdict: run aborted — {abort.get('error', '?')}")
    else:
        out.append(
            f"verdict: {manifest.get('reason', 'explicit')} dump "
            "(no abort recorded in the event ring)")
    out.append("")

    # -- event ring ----------------------------------------------------------
    if events:
        tally: Dict[str, int] = defaultdict(int)
        for e in events:
            tally[e.get("kind", "?")] += 1
        out.append(
            f"event ring ({len(events)} events): "
            + ", ".join(f"{k}={n}" for k, n in sorted(
                tally.items(), key=lambda kv: -kv[1])))
        t0 = events[0].get("mono", 0.0)
        out.append(f"  last {min(15, len(events))} events "
                   "(t relative to the oldest kept)")
        for e in events[-15:]:
            rel = (e.get("mono", 0.0) or 0.0) - (t0 or 0.0)
            out.append(f"    +{rel:9.3f}s  {_fmt_event(e)}")
        out.append("")

    # -- analyzer merge ------------------------------------------------------
    spans_path = os.path.join(bundle, "spans.jsonl")
    if os.path.exists(spans_path):
        spans, run, runs, dropped = load_spans(spans_path)
        if spans:
            out.append("span analyzer over the bundle's span tail")
            out.append("")
            out.append(analyze(spans, run, runs, dropped).rstrip("\n"))
            out.append("")
    return "\n".join(out) + "\n"


# ---------------------------------------------------------------------------
# --request: stitch one request's spans from N processes into a single
# epoch-aligned waterfall with coverage + per-hop gap attribution
# ---------------------------------------------------------------------------


def _load_trace_source_file(path: str):
    """One span JSONL as a stitcher source: ``(label, offset, spans)``.

    ``offset`` maps the writer's monotonic clock to the epoch
    (``epoch_time = ts + offset``), read from the file's meta lines
    (``{"meta": 1, "epoch": ..., "mono": ...}``).  A file that several
    process incarnations appended to carries one meta line per
    incarnation — each span is stamped with the offset of the meta
    line above it (``_off``), so restarts don't skew alignment."""
    spans: List[Dict[str, Any]] = []
    offset: Optional[float] = None
    pid = None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue  # torn tail line
            if rec.get("meta"):
                epoch, mono = rec.get("epoch"), rec.get("mono")
                if isinstance(epoch, (int, float)) and isinstance(
                        mono, (int, float)):
                    offset = float(epoch) - float(mono)
                if rec.get("pid") is not None:
                    pid = rec["pid"]
                continue
            if "name" not in rec or "ts" not in rec:
                continue
            rec["_off"] = offset
            spans.append(rec)
    label = f"pid{pid}" if pid is not None else os.path.basename(path)
    return label, offset, spans


def _load_trace_source_endpoint(endpoint: str):
    """A live introspection endpoint as a stitcher source: fetch
    ``/spans`` (which reports the serving process's ``pid`` and an
    ``epoch``/``mono`` clock pair alongside the span ring)."""
    import urllib.request

    base = endpoint if "://" in endpoint else "http://" + endpoint
    with urllib.request.urlopen(base + "/spans", timeout=5) as resp:
        doc = json.loads(resp.read())
    offset: Optional[float] = None
    epoch, mono = doc.get("epoch"), doc.get("mono")
    if isinstance(epoch, (int, float)) and isinstance(
            mono, (int, float)):
        offset = float(epoch) - float(mono)
    pid = doc.get("pid")
    label = f"pid{pid}" if pid is not None else endpoint
    return label, offset, list(doc.get("spans") or [])


def load_trace_sources(inputs: List[str]):
    """Resolve each CLI input to a stitcher source: an existing path is
    read as a span JSONL, anything else is treated as a live
    ``host:port`` endpoint."""
    sources = []
    for inp in inputs:
        if os.path.exists(inp):
            sources.append(_load_trace_source_file(inp))
        else:
            sources.append(_load_trace_source_endpoint(inp))
    return sources


def request_report(sources, trace_id: str, width: int) -> str:
    """The stitched cross-process waterfall for one trace id (see the
    module doc's ``--request`` section)."""
    rows: List[Dict[str, Any]] = []
    unaligned = False
    for label, default_off, spans in sources:
        for s in spans:
            if s.get("trace") != trace_id:
                continue
            off = s.get("_off")
            if off is None:
                off = default_off
            if off is None:
                off = 0.0
                unaligned = True
            try:
                t = float(s["ts"]) + off
                dur = max(0.0, float(s.get("dur") or 0.0))
            except (TypeError, ValueError):
                continue
            rows.append({
                "t": t, "dur": dur, "name": s["name"], "src": label,
                "tenant": s.get("tenant"),
                "labels": s.get("labels") or {},
            })
    if not rows:
        return f"no spans found for trace {trace_id}\n"
    rows.sort(key=lambda r: (r["t"], -r["dur"]))
    t0 = min(r["t"] for r in rows)
    t1 = max(r["t"] + r["dur"] for r in rows)
    wall = max(t1 - t0, 1e-9)
    procs = sorted({r["src"] for r in rows})
    tenants = sorted({r["tenant"] for r in rows if r.get("tenant")})
    out: List[str] = []
    out.append(
        f"trace {trace_id}  ({len(rows)} spans across "
        f"{len(procs)} process{'es' if len(procs) != 1 else ''}: "
        + ", ".join(procs)
        + (f"; tenant {', '.join(tenants)}" if tenants else "") + ")")
    out.append(f"client wall-clock {wall * 1e3:.2f}ms (epoch-aligned)")
    if unaligned:
        out.append(
            "WARNING: a source carries no epoch/mono clock pair — its "
            "spans are unaligned (offset 0); cross-process ordering "
            "may be wrong")
    out.append("")
    scale = width / wall
    src_w = max(len(r["src"]) for r in rows)
    name_w = max(len(r["name"]) for r in rows)
    for r in rows:
        cells = [" "] * width
        a = int((r["t"] - t0) * scale)
        b = max(a + 1, int((r["t"] + r["dur"] - t0) * scale))
        for i in range(a, min(b, width)):
            cells[i] = "#"
        detail = " ".join(
            f"{k}={r['labels'][k]}" for k in
            ("endpoint", "status", "kind", "lanes", "batch_lanes")
            if k in r["labels"])
        out.append(
            f"  {r['src']:>{src_w}} {r['name']:<{name_w}} "
            f"|{''.join(cells)}| {r['dur'] * 1e3:8.2f}ms"
            + (f"  {detail}" if detail else ""))
    out.append("")

    # -- coverage: union of span intervals over the trace window ------------
    ivals = sorted((r["t"], r["t"] + r["dur"]) for r in rows)
    covered = 0.0
    gaps: List[Any] = []
    cur_s, cur_e = ivals[0]
    for s, e in ivals[1:]:
        if s > cur_e:
            gaps.append((cur_e, s))
            covered += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    covered += cur_e - cur_s
    out.append(
        f"coverage: {covered / wall * 100:.1f}% of client wall-clock "
        f"instrumented ({covered * 1e3:.2f}ms of {wall * 1e3:.2f}ms)")
    if gaps:
        out.append("gap attribution (uninstrumented wall-clock)")
        for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
            before = max(
                (r for r in rows if r["t"] + r["dur"] <= gs + 1e-9),
                key=lambda r: r["t"] + r["dur"])
            after = min((r for r in rows if r["t"] >= ge - 1e-9),
                        key=lambda r: r["t"])
            kind = ("hop" if before["src"] != after["src"]
                    else "intra")
            out.append(
                f"  {(ge - gs) * 1e3:8.2f}ms  {kind:<5} "
                f"{before['src']}/{before['name']} -> "
                f"{after['src']}/{after['name']}")
    return "\n".join(out) + "\n"


# ---------------------------------------------------------------------------
# --progress: replay a progress JSONL (DisqOptions.progress_log) into a
# throughput-over-time sparkline
# ---------------------------------------------------------------------------

SPARK_BLOCKS = "▁▂▃▄▅▆▇█"


def load_progress(path: str, run: Optional[str] = None):
    """Progress lines from one JSONL (written by
    ``runtime/introspect.py``), filtered to one run id (default: the
    last run seen)."""
    recs: List[Dict[str, Any]] = []
    runs: List[str] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue  # torn tail line
            rid = rec.get("run_id")
            if rid and rid not in runs:
                runs.append(rid)
            if rec.get("meta") or "direction" not in rec:
                continue
            recs.append(rec)
    if run is None and runs:
        run = runs[-1]
    if run is not None:
        recs = [r for r in recs if r.get("run_id") == run]
    return recs, run, runs


def sparkline(values: List[float], width: int) -> str:
    """Bucket ``values`` (already time-ordered) into ``width`` columns,
    rendering each bucket's max as a block glyph."""
    if not values:
        return ""
    if len(values) <= width:
        buckets = [float(v) for v in values]
    else:
        buckets = []
        for i in range(width):
            lo = i * len(values) // width
            hi = max(lo + 1, (i + 1) * len(values) // width)
            buckets.append(max(values[lo:hi]))
    peak = max(buckets)
    if peak <= 0:
        return SPARK_BLOCKS[0] * len(buckets)
    return "".join(
        SPARK_BLOCKS[min(len(SPARK_BLOCKS) - 1,
                         int(v / peak * (len(SPARK_BLOCKS) - 1) + 0.5))]
        for v in buckets)


def fmt_rate(v: float) -> str:
    if v >= 1e6:
        return f"{v / 1e6:.2f}M/s"
    if v >= 1e3:
        return f"{v / 1e3:.1f}k/s"
    return f"{v:.1f}/s"


def progress_report(recs, run, runs, width: int) -> str:
    """Per-direction throughput-over-time replay of a progress JSONL."""
    if not recs:
        return "no progress records found (empty or filtered-out log)\n"
    out: List[str] = []
    out.append(f"progress replay: run {run}  ({len(recs)} samples"
               + (f"; file holds runs: {', '.join(runs)}" if len(runs) > 1
                  else "") + ")")
    by_dir: Dict[str, List[Dict[str, Any]]] = defaultdict(list)
    for r in recs:
        by_dir[r["direction"]].append(r)
    for direction in sorted(by_dir):
        rows = sorted(by_dir[direction], key=lambda r: r.get("mono", 0.0))
        rates = [float(r.get("records_per_sec") or 0.0) for r in rows]
        if not any(rates):
            rates = [float(r.get("shards_per_sec") or 0.0) for r in rows]
            unit = "shards/sec"
        else:
            unit = "records/sec"
        last = rows[-1]
        t0, t1 = rows[0].get("mono", 0.0), rows[-1].get("mono", 0.0)
        out.append("")
        out.append(
            f"  [{direction}] {unit} over {max(0.0, t1 - t0):.2f}s  "
            f"(peak {fmt_rate(max(rates) if rates else 0.0)}, "
            f"final {fmt_rate(rates[-1] if rates else 0.0)})")
        out.append("    " + sparkline(rates, width))
        eta = last.get("eta_s")
        out.append(
            f"    shards {last.get('shards_done', '?')}/"
            f"{last.get('shards_total', '?')} done, "
            f"{last.get('in_flight', 0)} in flight, "
            f"{last.get('records', 0):,} records"
            + (f", eta {eta:.1f}s" if isinstance(eta, (int, float)) and eta
               else ""))
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="per-shard waterfall + latency report from a "
                    "disq_tpu span JSONL")
    ap.add_argument("inputs", nargs="*", default=[], metavar="input",
                    help="span log written via "
                    "DISQ_TPU_TRACE_JSONL / DisqOptions.span_log "
                    "(with --progress, a DisqOptions.progress_log "
                    "JSONL; with --flame, a collapsed-stack profile; "
                    "unused with --postmortem; with --request, any "
                    "mix of span JSONLs and live host:port "
                    "introspection endpoints)")
    ap.add_argument("--top", type=int, default=5,
                    help="straggler shards to list (default 5)")
    ap.add_argument("--width", type=int, default=72,
                    help="waterfall width in columns (default 72)")
    ap.add_argument("--run", default=None,
                    help="run id to report (default: last run in file)")
    ap.add_argument("--progress", action="store_true",
                    help="treat the input as a progress JSONL "
                    "(DisqOptions.progress_log) and replay it as a "
                    "throughput-over-time sparkline")
    ap.add_argument("--analyze", action="store_true",
                    help="critical-path analysis instead of the "
                    "waterfall: wall-clock attribution by "
                    "stage/stall/transfer bucket and a one-line "
                    "bottleneck verdict")
    ap.add_argument("--flame", action="store_true",
                    help="treat the input as collapsed stacks (the "
                    "sampling profiler's export) and render an ASCII "
                    "flame + top-N function tables")
    ap.add_argument("--postmortem", default=None, metavar="BUNDLE",
                    help="render a flight-recorder postmortem bundle "
                    "directory (DisqOptions.postmortem_dir) into a "
                    "one-page verdict")
    ap.add_argument("--request", default=None, metavar="TRACE_ID",
                    help="stitch one request's spans from every input "
                    "(span JSONLs and/or live host:port endpoints) "
                    "into a single cross-process waterfall with "
                    "coverage + gap attribution")
    args = ap.parse_args(argv)

    if args.postmortem:
        sys.stdout.write(
            postmortem_report(args.postmortem, args.top, args.width))
        return 0

    if not args.inputs:
        ap.error("an input file is required (or use --postmortem "
                 "<bundle-dir>)")

    if args.request:
        sys.stdout.write(request_report(
            load_trace_sources(args.inputs), args.request, args.width))
        return 0

    path = args.inputs[0]

    if args.flame:
        sys.stdout.write(flame_report(
            load_collapsed(path), args.top, args.width))
        return 0

    if args.progress:
        recs, run, runs = load_progress(path, args.run)
        sys.stdout.write(progress_report(recs, run, runs, args.width))
        return 0

    if args.analyze:
        spans, run, runs, dropped = load_spans(path, args.run)
        sys.stdout.write(analyze(spans, run, runs, dropped))
        return 0

    spans, run, runs, dropped = load_spans(path, args.run)
    sys.stdout.write(report(spans, run, runs, args.top, args.width,
                            dropped))
    return 0


if __name__ == "__main__":
    sys.exit(main())
