"""Readers of per-layer metrics.  A metric is one data file under
``layer_metrics/`` naming one of these readers and what it reads; a
reader that finds nothing to read returns None and the metric is left
out of the result line.

What a reader sees (``w``): the program's counters before and after the
window, the program's spans that ended inside it, the reduced device
trace, the driver's own numbers (counts of passes, records, requests;
client-side times), the compile counts and the device's peaks.
"""

from __future__ import annotations

import re


def _labels_match(label_str: str, want) -> bool:
    return all(part in label_str.split(",") for part in want)


def _counter_delta(w, name, labels=()):
    after = w["counters_after"].get(name)
    if not after:
        return None
    before = w["counters_before"].get(name, {})
    return sum(v - before.get(k, 0) for k, v in after.items()
               if _labels_match(k, labels))


def _spans(w, spec):
    want = spec.get("labels", {})
    return [s for s in w["spans"] if s["name"] == spec["key"]
            and all(str(s["labels"].get(k)) == str(v)
                    for k, v in want.items())]


def _per(w, spec, value):
    """``value`` scaled, over the driver's count named by ``per``."""
    if value is None:
        return None
    value *= spec.get("scale", 1)
    if "per" not in spec:
        return value
    n = w["numbers"].get(spec["per"], 0)
    return value / n if n else None


def _op_seconds(w, spec):
    if not w["trace"]:
        return None
    hit = [s for k, s in w["trace"]["ops"].items()
           if re.search(spec["match"], k)]
    return sum(hit) if hit else None


def counter(w, spec):
    """Growth of a program counter over the window."""
    return _per(w, spec, _counter_delta(w, spec["key"],
                                        spec.get("labels", ())))


def span_sum(w, spec):
    """Seconds of the program's spans of one name (host clock)."""
    hit = _spans(w, spec)
    return _per(w, spec, sum(s["dur"] for s in hit)) if hit else None


def span_count(w, spec):
    hit = _spans(w, spec)
    return _per(w, spec, float(len(hit))) if hit else None


def span_label_mean(w, spec):
    """Mean of a numeric label over the spans of one name."""
    vals = [float(s["labels"][spec["label"]]) for s in _spans(w, spec)
            if spec["label"] in s["labels"]]
    return _per(w, spec, sum(vals) / len(vals)) if vals else None


def trace_op(w, spec):
    """Device seconds of the trace's ops whose key matches."""
    return _per(w, spec, _op_seconds(w, spec))


def roofline(w, spec):
    """Share of the memory roofline: the bytes the kernel has to move
    (the driver's own count) over the device's peak bytes/s, over the
    kernel's device time.  No clamp: a share over 100 % is a fault."""
    seconds = _op_seconds(w, spec)
    nbytes = w["numbers"].get(spec["bytes"])
    if not seconds or not nbytes:
        return None
    return 100.0 * (nbytes / w["peaks"][spec["peak"]]) / seconds


def number(w, spec):
    """A number the driver took itself (its own clock or count)."""
    return _per(w, spec, w["numbers"].get(spec["key"]))


def device(w, spec):
    """``idle_pct`` from the trace's busy union, or ``hbm_peak_bytes``
    from the device's memory statistics."""
    if spec["key"] == "hbm_peak_bytes":
        return w["device"].get("memory_peak_bytes")
    if not w["trace"] or not w["trace"]["window_s"]:
        return None
    t = w["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def compiles(w, spec):
    return float(w["compiles"][spec["key"]])


READERS = {f.__name__: f for f in (
    counter, span_sum, span_count, span_label_mean,
    trace_op, roofline, number, device, compiles)}
