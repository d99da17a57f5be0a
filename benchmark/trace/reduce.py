"""From a profiler trace to device numbers: busy union, per-op seconds,
and the idle gaps labelled by what the host was doing.

Two steps, so that the second can be checked on a small recorded trace
(``tests/benchmark_harness``): ``load_xplane`` turns the profiler's
``.xplane.pb`` into plain lists, ``reduce_trace`` does the arithmetic.
Times are nanoseconds on the profiler's one clock; results are seconds.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW = "bench.window"
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, host_names=()) -> dict:
    """{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    duration_ns], ...]}]}]} with every device plane whole and, of the
    host planes, only the events named in ``host_names``."""
    from jax.profiler import ProfileData

    keep = set(host_names) | {WINDOW}
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events if device or ev.name in keep]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union(intervals):
    """Sorted, merged copy of (start, end) intervals."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def intersect(xs, ys):
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append([a, b])
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs, ys):
    """Merged list ``xs`` minus merged list ``ys``."""
    out = []
    for a, b in xs:
        for c, d in ys:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append([a, c])
            a = max(a, d)
            if a >= b:
                break
        if a < b:
            out.append([a, b])
    return out


def total(xs) -> int:
    return sum(b - a for a, b in xs)


def op_key(module: str, op: str) -> str:
    """``jit_step(123)`` and ``%fusion.3 = f32[..] fusion(..)`` ->
    ``jit_step/fusion.3``: stable across runs (no hash, no shapes)."""
    module = re.sub(r"\(\d+\)$", "", module)
    return f"{module}/{op.split(' = ')[0].lstrip('%').strip()}"


def reduce_trace(trace: dict, gap_labels=()) -> dict:
    """Numbers of the ``bench.window`` annotation's interval:

    - ``window_s``; ``busy_s``: the union of the intervals in which an
      operation ran on a device, averaged over the device planes;
    - ``ops``: seconds by module (``jit_step``) and by op within it
      (``jit_step/fusion.3``), device planes summed;
    - ``idle_gaps``: the idle time of the first device, split by the
      host annotation open at the time (``gap_labels`` in order of
      precedence, then ``none``)."""
    host = {}
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                host.setdefault(name, []).append((start, start + dur))
    if WINDOW not in host:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    w0, w1 = host[WINDOW][0]
    window = [[w0, w1]]
    ops, busy, gaps = {}, [], None
    for plane in trace["planes"]:
        if not plane["name"].startswith("/device:"):
            continue
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        modules = sorted((s, s + d, n) for n, s, d in
                         lines.get(MODULE_LINE, ()))
        starts = [m[0] for m in modules]
        for start, end, name in modules:
            inside = total(intersect([[start, end]], window))
            if inside:
                key = re.sub(r"\(\d+\)$", "", name)
                ops[key] = ops.get(key, 0) + inside
        for name, start, dur in lines.get(OP_LINE, ()):
            inside = total(intersect([[start, start + dur]], window))
            if not inside:
                continue
            k = bisect.bisect_right(starts, start) - 1
            module = modules[k][2] if k >= 0 and start < modules[k][1] \
                else "?"
            key = op_key(module, name)
            ops[key] = ops.get(key, 0) + inside
        ran = lines.get(OP_LINE) or lines.get(MODULE_LINE) or ()
        on = intersect(union((s, s + d) for _n, s, d in ran), window)
        busy.append(total(on))
        if gaps is None:
            gaps = subtract(window, on)
    idle = []
    rest = gaps or []
    for label in gap_labels:
        spans = union(host.get(label, ()))
        idle.append([label, total(intersect(rest, spans)) / 1e9])
        rest = subtract(rest, spans)
    idle.append(["none", total(rest) / 1e9])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9 if busy else 0.0,
        "ops": {k: v / 1e9 for k, v in ops.items()},
        "idle_gaps": sorted((g for g in idle if g[1] > 0),
                            key=lambda g: -g[1])[:10],
    }
