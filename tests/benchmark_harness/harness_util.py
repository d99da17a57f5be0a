"""Shared by the benchmark's own tests: the repo's benchmark as data, and
a temporary copy of it cut to a size the CPU interpreter can hold."""

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# interpreter-sized: small BGZF blocks, a few dozen records, one pass
TINY = {
    "read": {"records": 90, "bgzf_block_payload": 300, "trace_seconds": 1,
             "split_size_bytes": 12288},
    "sort_write": {"records": 90, "bgzf_block_payload": 300,
                   "trace_seconds": 1},
}


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def copy_benchmark(dst: str, traffic_patches=None) -> str:
    """A copy of BENCHMARK.json and the benchmark's data files under
    ``dst`` (the code stays where it is), traffic files patched."""
    dst = str(dst)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    for d in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmark", d),
                        os.path.join(dst, "benchmark", d))
    shutil.copy(os.path.join(REPO, "benchmark", "peaks.json"),
                os.path.join(dst, "benchmark"))
    for name, patch in (traffic_patches or {}).items():
        path = os.path.join(dst, "benchmark", "traffic", name + ".json")
        with open(path) as f:
            doc = json.load(f)
        doc.update(patch)
        with open(path, "w") as f:
            json.dump(doc, f)
    return dst


def run_tiny(root, workload, trace=False, control=None, seed=2147483999):
    """One run of a cell at the tiny size, the look for a chip waived."""
    from benchmark import run

    return run.run_cell(workload, seed, 1.0, trace, root=str(root),
                        require_chip=False, control=control)
