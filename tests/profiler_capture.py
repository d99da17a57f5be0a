"""A ``jax.profiler`` capture round a body, read back with the
benchmark's own loader (``benchmark/trace/reduce.py``): what the
program's spans look like to the reduction that splits the device's idle
time by host annotation."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def captured_events(trace_dir, body, names):
    """Run ``body()`` under a capture written to ``trace_dir``; the host
    planes' events named in ``names``, as ``[name, start_ns, dur_ns]``
    in order of start."""
    import jax

    from benchmark.trace.reduce import find_xplane, load_xplane

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # as benchmark/run.py captures
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    trace = load_xplane(find_xplane(str(trace_dir)), names)
    return sorted(
        (ev for plane in trace["planes"]
         if not plane["name"].startswith("/device:")
         for line in plane["lines"] for ev in line["events"]),
        key=lambda ev: ev[1])
