#!/usr/bin/env python
"""check_metrics — metric/span name registry lint (tier-1 via
``tests/test_metric_names.py``).

Walks ``disq_tpu/`` for metric and span name *literals* (first string
argument of ``span`` / ``wrap_span`` / ``trace_phase`` /
``record_span`` / ``counter`` / ``gauge`` /
``histogram`` / ``observe_gauge`` calls) and enforces:

1. **Dotted taxonomy** — every name is lower_snake dotted with at
   least two segments, and its first segment is one of the allowed
   prefixes below (``executor.*``, ``retry.*``, ``fsw.http.*``, …).
2. **No kind conflicts** — one name must not be registered as two
   incompatible kinds (counter vs gauge vs timing; spans and
   histograms share the timing kind because a span books its
   same-named histogram).
3. **No drift from the docs** — the README's metric table (between
   ``<!-- metrics:begin -->`` and ``<!-- metrics:end -->``) must list
   exactly the names found in code: an undocumented metric fails, and
   so does a documented-but-deleted one.  Renames are therefore a
   deliberate two-file change, never an accident.

Dynamic (non-literal) metric names defeat the lint AND explode
Prometheus label cardinality — put the variable part in a label, not
the name (see ``retry.attempts{what=…}``).
"""

from __future__ import annotations

import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Set, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CODE_ROOT = os.path.join(REPO, "disq_tpu")
README = os.path.join(REPO, "README.md")

ALLOWED_PREFIXES = {
    "executor", "writer", "retry", "errors", "quarantine", "fsw",
    "codec", "bam", "sam", "vcf", "bcf", "cram", "sort", "telemetry",
    # Live introspection (runtime/introspect.py): heartbeat-watchdog
    # stall events and the /progress feed.
    "watchdog", "progress",
    # Device observability (runtime/device_pipeline.py + ops/): synced
    # kernel spans, transfer counters, HBM gauge; and the cluster
    # aggregator's scrape telemetry (runtime/cluster.py).
    "device", "cluster",
    # Adaptive resilience (runtime/resilience.py): hedged-fetch
    # bookkeeping, circuit-breaker state machine, per-shard deadline
    # escalation, and the shared retry token bucket.
    "hedge", "breaker", "deadline", "budget",
    # Postmortem + profiling (runtime/flightrec.py /
    # runtime/profiler.py): event-ring + bundle bookkeeping and the
    # sampling profiler's per-role sample counters.
    "flightrec", "profile",
    # HBM-resident fused decode (runtime/columnar.py): ColumnarBatch
    # build/fetch/release spans and the resident-bytes gauge.
    "columnar",
    # Indexed reads (traversal/bai_query.py): the plan and the overlap
    # test of an interval read, and what it decoded and returned.
    "traversal",
    # Cross-host shard scheduler (runtime/scheduler.py): queue depth,
    # lease/steal/locality accounting, membership gauge, worker RPC
    # spans.
    "sched",
    # Serving plane (runtime/serve.py): request latency histograms,
    # two-tier hot-block cache accounting, index-cache hit/miss, and
    # per-tenant admission results + queue-wait spans.
    "serve",
    # Per-tenant SLO layer (runtime/slo.py): multi-window burn-rate
    # gauges, the fast-burn page flag, and evaluator tick counter.
    "slo",
    # Fleet routing tier (runtime/fleet.py): locality-routing
    # decisions, cross-replica hedge accounting, fleet-wide admission,
    # replica liveness gauge and cachemap refresh spans.
    "fleet",
    # Resident operator suite (runtime/oppipe.py + ops/{rfilter,
    # markdup,pileup,rgstats}.py): per-operator apply spans, filter
    # in/kept counters, duplicate + boundary-flip counters, pileup
    # record counter and the chained-pipeline run span.
    "ops",
}

NAME_RE = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)+$")

# Literal first-arg of a telemetry call (optionally alias-imported with
# a leading underscore, e.g. http.py's ``_span`` / ``_counter``).
CALL_RE = re.compile(
    r"""\b_?(span|wrap_span|trace_phase|record_span|
             device_span|synced_timer|
             counter|gauge|histogram|observe_gauge)\s*\(\s*
        (["'])([^"'\n]+)\2""",
    re.VERBOSE,
)

KIND_OF = {
    "counter": "counter",
    "gauge": "gauge",
    "observe_gauge": "gauge",
    # spans book a same-named duration histogram, so they are one kind
    "span": "timing",
    "wrap_span": "timing",
    "trace_phase": "timing",
    "record_span": "timing",
    "device_span": "timing",
    "synced_timer": "timing",
    "histogram": "timing",
}

MARK_BEGIN = "<!-- metrics:begin -->"
MARK_END = "<!-- metrics:end -->"


def scan_code() -> Tuple[Dict[str, Set[str]], Dict[str, List[str]]]:
    """{name: kinds} and {name: ["file:line", …]} over disq_tpu/."""
    kinds: Dict[str, Set[str]] = defaultdict(set)
    sites: Dict[str, List[str]] = defaultdict(list)
    for dirpath, dirnames, filenames in os.walk(CODE_ROOT):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as f:
                text = f.read()
            for m in CALL_RE.finditer(text):
                func, _q, name = m.group(1), m.group(2), m.group(3)
                line = text.count("\n", 0, m.start()) + 1
                rel = os.path.relpath(path, REPO)
                kinds[name].add(KIND_OF[func])
                sites[name].append(f"{rel}:{line}")
    return dict(kinds), dict(sites)


def scan_readme() -> Set[str]:
    """Backticked dotted names inside the README metric table."""
    with open(README) as f:
        text = f.read()
    try:
        block = text.split(MARK_BEGIN, 1)[1].split(MARK_END, 1)[0]
    except IndexError:
        return set()
    return {
        m.group(1)
        for m in re.finditer(r"`([a-z0-9_]+(?:\.[a-z0-9_]+)+)`", block)
    }


# README "kind" column text -> the canonical kind the code scan uses.
_DOC_KIND = {
    "counter": "counter",
    "gauge": "gauge",
    "histogram": "timing",
    "span": "timing",
    "span/histogram": "timing",
}

_ROW_RE = re.compile(
    r"^\|\s*`([a-z0-9_]+(?:\.[a-z0-9_]+)+)`\s*\|\s*([^|]+?)\s*\|")


def scan_readme_kinds() -> Dict[str, str]:
    """{name: kind-column text} for every standard metric-table row —
    the second drift axis: a metric documented as the wrong *kind* is
    as misleading as an undocumented one."""
    with open(README) as f:
        text = f.read()
    try:
        block = text.split(MARK_BEGIN, 1)[1].split(MARK_END, 1)[0]
    except IndexError:
        return {}
    out: Dict[str, str] = {}
    for line in block.splitlines():
        m = _ROW_RE.match(line.strip())
        if m:
            out[m.group(1)] = m.group(2).strip()
    return out


def main() -> int:
    kinds, sites = scan_code()
    errors: List[str] = []

    for name in sorted(kinds):
        where = ", ".join(sites[name][:3])
        if not NAME_RE.match(name):
            errors.append(
                f"{name!r}: not a dotted lower_snake name ({where})")
            continue
        prefix = name.split(".", 1)[0]
        if prefix not in ALLOWED_PREFIXES:
            errors.append(
                f"{name!r}: prefix {prefix!r} not in taxonomy "
                f"{sorted(ALLOWED_PREFIXES)} ({where})")
        if len(kinds[name]) > 1:
            errors.append(
                f"{name!r}: registered as conflicting kinds "
                f"{sorted(kinds[name])} ({where})")

    documented = scan_readme()
    if not documented:
        errors.append(
            f"README.md: no metric table found between {MARK_BEGIN!r} "
            f"and {MARK_END!r}")
    else:
        code_names = set(kinds)
        for name in sorted(code_names - documented):
            errors.append(
                f"{name!r}: used in code ({', '.join(sites[name][:2])}) "
                "but missing from the README metric table")
        for name in sorted(documented - code_names):
            errors.append(
                f"{name!r}: documented in README but not found in code "
                "(stale doc, or the name drifted)")
        doc_kinds = scan_readme_kinds()
        for name in sorted(code_names & set(doc_kinds)):
            if len(kinds[name]) != 1:
                continue  # kind conflict already reported above
            doc_kind = _DOC_KIND.get(doc_kinds[name].lower())
            code_kind = next(iter(kinds[name]))
            if doc_kind is not None and doc_kind != code_kind:
                errors.append(
                    f"{name!r}: README documents kind "
                    f"{doc_kinds[name]!r} but code registers "
                    f"{code_kind!r} ({', '.join(sites[name][:2])})")

    if errors:
        print(f"check_metrics: {len(errors)} problem(s)")
        for e in errors:
            print(f"  - {e}")
        return 1
    print(f"check_metrics: OK ({len(kinds)} metric names, "
          f"{len(documented)} documented)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
