"""Small shared helpers."""

from __future__ import annotations

import threading


def bucket_pow2(n: int, lo: int = 64) -> int:
    """Power-of-two compile-shape bucket (floor ``lo``): the ONE
    padding policy shared by the SIMD inflate chunk shapes, the device
    parse starts, and ColumnarBatch concat — so their jit caches bucket
    identically and a policy change cannot silently diverge them."""
    b = lo
    while b < n:
        b *= 2
    return b


def pad_quantum(n: int, coarse: bool = False) -> int:
    """Compile-shape quantization with bounded waste: power-of-two
    below 64K units (cheap), then 1/16-octave steps — retraces stay a
    handful per octave while zero-pad overhead is capped at ~6%
    (plain power-of-two would zero-fill and upload up to 2x the blob,
    defeating the transfer win the resident path exists for).
    ``coarse``: the power of two throughout, for a caller that bounds
    its sizes itself and whose many sizes would each be a shape (an
    indexed read's chunk runs, a launch's lanes a worker each: they
    fill the bucket they are cut to).  Pure arithmetic, here and not
    beside the parse that uploads at these shapes, because the decode
    service allocates a split's blob at them and imports no jax."""
    if coarse or n <= 1 << 16:
        return bucket_pow2(n)
    step = 1 << max((n - 1).bit_length() - 5, 0)
    return -(-n // step) * step

_HOST_POOL = None
_HOST_POOL_LOCK = threading.Lock()


def shared_host_pool():
    """The process-wide helper ThreadPoolExecutor for short GIL-released
    host work on device decode paths (batch CRC verification, kernel
    host-zlib fallback lanes).  Created lazily on first use — the
    default/host path never touches it — and never shut down (stdlib
    joins idle workers at interpreter exit).  ONE pool, min(4, cpus)
    threads, shared by every caller, instead of per-call or per-module
    singletons."""
    global _HOST_POOL
    import os
    from concurrent.futures import ThreadPoolExecutor

    with _HOST_POOL_LOCK:
        if _HOST_POOL is None:
            _HOST_POOL = ThreadPoolExecutor(
                max_workers=min(4, os.cpu_count() or 1),
                thread_name_prefix="disq-hostwork")
        return _HOST_POOL


def pallas_interpret() -> bool:
    """THE decision whether a device kernel runs in Pallas interpret
    mode — every kernel entry point asks here, nowhere else.

    False on a TPU backend: kernels compile for the chip.  True only
    when the process explicitly selected the CPU platform
    (``JAX_PLATFORMS=cpu`` / ``jax.config.jax_platforms`` — the test
    route, where the interpreter is the point).  Any other backend
    means jax did not attach the chip the armed device knob asked for
    (it dropped to CPU on its own, or found some other accelerator);
    interpreting there would return right answers from the wrong
    machine, so it raises instead."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return False
    requested = (jax.config.jax_platforms or "").split(",")[0].strip()
    if backend == "cpu" and requested == "cpu":
        return True
    raise RuntimeError(
        f"device kernels need a TPU backend, but jax.default_backend() "
        f"is {backend!r} and the platform was not explicitly set to cpu "
        f"(jax_platforms={jax.config.jax_platforms!r}). Set "
        f"JAX_PLATFORMS=cpu to run them in Pallas interpret mode, or "
        f"disarm the DISQ_TPU_DEVICE_* / resident-decode knobs.")


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its
    directory.  Called by the program's entry points (``chip_smoke.py``,
    ``benchmark/run.py``, ``scripts/serve.py``, ``python -m
    disq_tpu.ops.tpu_ci``) before their first compile — never by
    library import.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it on its own, so this
    sets nothing.  Unset: the cache lives at ``<checkout>/.jax_cache``,
    resolved from this package's own path — a fixed place, because the
    path is part of what a later run must find again (never a temp dir,
    a pid or a timestamp)."""
    import os

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def resolve_num_shards(storage) -> int:
    """Shard count for write paths: the storage's ``num_shards`` override,
    else the attached device count. Single source of truth for every
    sink (BAM/SAM/VCF/CRAM). A backend that fails to initialize raises
    here — a chip that did not attach must not turn into one shard."""
    n = getattr(storage, "_num_shards", None)
    if n:
        return n
    import jax

    return len(jax.devices())


def shard_bounds(storage, count: int):
    """(n_shards, bounds) for partitioning ``count`` records across write
    shards — single source of truth for every sink."""
    import numpy as np

    n_shards = min(resolve_num_shards(storage), max(1, count))
    bounds = np.linspace(0, count, n_shards + 1).astype(np.int64)
    return n_shards, bounds
