#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names its
configuration (a file of sizes under ``configs/``), its traffic mix (a
file of parameters under ``traffic/``, which names its driver in
``drivers/``) and its per-layer metrics (a reader file each under
``layer_metrics/``).  A later cell, configuration or metric is new files
and new entries; nothing here is edited.

One process.  Set-up (generate from the seed, start the program, warm up
exactly the window's shapes) is timed as ``setup_s`` from process start;
then the window runs for ``--seconds``; then the answers the window
produced are held to the plain reference.  The last line of standard
output is the result, one JSON object.  Without a TPU, or with fewer
chips than the cell asks for, the run fails and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

COMPILE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_misses": "compiled",
}


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> dict:
    """Everything ``BENCHMARK.json`` and the data files say of a cell."""
    manifest = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(it has {sorted(cells)})")
    cell = cells[workload]
    bench = os.path.join(root, manifest["paths"][0])
    config = next(c for c in manifest["configs"]
                  if c["name"] == cell["config"])

    def mine(m):
        return workload in m.get("workloads", [workload])

    layer = [m for m in manifest["per_layer"] if mine(m)]
    return {
        "cell": cell,
        "config": load_json(root, config["file"]),
        "traffic": load_json(bench, "traffic", cell["traffic"] + ".json"),
        "end_to_end": [m for m in manifest["end_to_end"] if mine(m)],
        "per_layer": layer,
        "readers": {m["name"]: load_json(bench, "layer_metrics",
                                         m["name"] + ".json")
                    for m in layer},
        "peaks": load_json(bench, "peaks.json"),
    }


class CompileCounter:
    """Compile requests that reached jax's compilation cache, and how
    many of them it could not serve (so XLA compiled)."""

    def __init__(self) -> None:
        import jax.monitoring

        self.counts = {v: 0 for v in COMPILE_EVENTS.values()}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        key = COMPILE_EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1


def layer_values(spec: dict, w: dict, strict: bool) -> dict:
    """The cell's per-layer metrics, each through its reader file.  A
    reader that finds nothing to read returns nothing; on the chip
    (``strict``) that fails the run, because BENCHMARK.json lists this
    cell for the metric: a span, counter or kernel that has gone or was
    renamed does not drop out of the line unseen."""
    from benchmark import readers

    out = {}
    for m in spec["per_layer"]:
        reader = spec["readers"][m["name"]]
        value = readers.READERS[reader["reader"]](w, reader)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
        elif strict:
            raise SystemExit(
                f"per-layer metric {m['name']}: its reader found nothing "
                f"to read ({reader}) in a cell that lists it")
    return out


_COMPILES = None


def compile_counter() -> CompileCounter:
    # jax keeps every listener for the life of the process: one is enough
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = CompileCounter()
    return _COMPILES


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = ROOT, require_chip: bool = True,
             control: str = None):
    """Run the cell and return its result line as a dict, or None where
    there is no chip to run it on."""
    spec = load_cell(root, workload)
    cell, tr = spec["cell"], spec["traffic"]
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax

    from benchmark.reference import Checks
    from benchmark.trace import reduce as trace_reduce
    from disq_tpu.util import enable_compile_cache

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if require_chip and (dev["platform"] != "tpu"
                         or dev["count"] < cell["chips"]):
        print(f"benchmark: the cell asks for {cell['chips']} TPU chip(s) "
              f"and jax sees {dev} - no result", file=sys.stderr)
        return None
    if require_chip and dev["kind"] not in spec["peaks"]:
        raise SystemExit(f"device kind {dev['kind']!r} is not in peaks.json")
    cache_dir = enable_compile_cache()
    # every program goes to the cache, however quickly it compiled, so
    # that a second run's set-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    print(f"device: {dev['platform']} {dev['kind']} x{dev['count']}; "
          f"compile cache: {cache_dir}", flush=True)
    compiles = compile_counter()
    compiles_at_start = dict(compiles.counts)

    def annotate(name):
        return jax.profiler.TraceAnnotation(name) if trace \
            else contextlib.nullcontext()

    workdir = tempfile.mkdtemp(prefix="disq_bench_")
    env_before = {k: os.environ.get(k) for k in tr.get("env", {})}
    os.environ.update(tr.get("env", {}))
    ctx = types.SimpleNamespace(
        config=spec["config"], traffic=tr, seed=int(seed), workdir=workdir,
        control=control, annotate=annotate)
    driver = importlib.import_module(
        "benchmark.drivers." + tr["driver"]).Driver(ctx)
    from benchmark.drivers import program

    try:
        driver.setup()
        setup_s = time.perf_counter() - T_START
        setup_compiles = {k: compiles.counts[k] - compiles_at_start[k]
                          for k in compiles.counts}
        if trace:
            seconds = min(seconds, tr["trace_seconds"])
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(os.path.join(workdir, "trace"),
                                     profiler_options=options)
        counters_before = program.counters()
        t0 = time.perf_counter()
        try:
            with annotate(trace_reduce.WINDOW):
                numbers = driver.window(seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        t1 = time.perf_counter()
        counters_after = program.counters()
        window_compiles = (compiles.counts["requests"]
                           - compiles_at_start["requests"]
                           - setup_compiles["requests"])
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        dev["memory_peak_bytes"] = int(peak)

        checks = Checks()
        t_check = time.perf_counter()
        failed = driver.check(checks)
        if require_chip:
            # on the chip every full BGZF block lands in one launch
            # geometry; the CPU tests' tiny blocks straddle a bucket
            # edge, so there the count is printed and not held to 0
            checks.add("compile requests inside the window",
                       window_compiles)
        print(f"setup_s {setup_s:.2f}; compiled in set-up "
              f"{setup_compiles['compiled']} of {setup_compiles['requests']}"
              f" requests, requests in the window {window_compiles}; the "
              f"comparison took {time.perf_counter() - t_check:.2f} s",
              flush=True)

        line = {"correct": bool(checks.ok and not failed),
                "attempted": int(numbers["attempted"]),
                "failed": int(failed), "metrics": {}, "device": dev}
        if not trace:
            values = dict(numbers, setup_s=setup_s)
            for m in spec["end_to_end"]:
                line["metrics"][m["name"]] = {
                    "value": values[m["name"]], "unit": m["unit"]}
            return line
        labels = tr.get("gap_labels", ())
        reduced = trace_reduce.reduce_trace(
            trace_reduce.load_xplane(
                trace_reduce.find_xplane(os.path.join(workdir, "trace")),
                labels), labels)
        if require_chip and not reduced["busy_s"] > 0:
            raise SystemExit("no operation ran on the device in the "
                             "traced window")
        w = {"counters_before": counters_before,
             "counters_after": counters_after,
             "spans": program.spans_between(t0, t1), "trace": reduced,
             "numbers": numbers, "device": dev,
             "compiles": {"window": window_compiles,
                          "setup": setup_compiles["compiled"]},
             "peaks": spec["peaks"].get(dev["kind"], {})}
        for name, value in layer_values(spec, w, strict=require_chip).items():
            line["metrics"][name] = value
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:10]
        line["breakdown"] = {"device_ops": [list(kv) for kv in ops],
                             "idle_gaps": reduced["idle_gaps"]}
        return line
    finally:
        try:
            driver.close()
        finally:
            for k, v in env_before.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None,
                    help="break one guarantee under the comparison (the "
                         "control that has to come out not correct); "
                         "never set in a benchmark run")
    args = ap.parse_args(argv)
    line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                    control=args.control)
    if line is None:
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
