"""Batch-axis device mesh for the mesh-native resident pipeline.

ROADMAP item 3 / SNIPPETS.md [1]: one sharded decode→sort→reduce
program across all local chips instead of N independent single-device
lanes.  This module owns the ONE mesh the process ever builds — a 1-D
``Mesh(devices[:n], ("batch",))`` — plus the ``NamedSharding`` helpers
every mesh-aware stage shares, so the sharding vocabulary cannot drift
between the parse (`runtime/device_pipeline.py`), the columnar currency
(`runtime/columnar.py`), the multi-chip sort (`sort/sharded.py`) and
the psum reductions (`ops/flagstat.py`, `ops/depth.py`).

Zero-overhead-when-off contract (scripts/check_overhead.py section 1d):
with no knob set — ``DisqOptions.mesh is None`` and ``DISQ_TPU_MESH``
unset — nothing here touches jax: no mesh object is built
(``mesh_if_built() is None``), no resharding happens, and every caller
takes the identical single-device dispatch it took before this module
existed.  A knob that resolves to <= 1 usable device (a 1-chip host,
``mesh=1``, or ``DISQ_TPU_MESH=1``) is the same OFF path: callers get
``None`` back and never branch onto mesh code.

Knob semantics (README "Mesh-native pipeline"):

- ``DisqOptions.mesh``: ``None`` = off; ``0`` = all local devices;
  ``n >= 1`` = the first ``n`` local devices.  Builders:
  ``DisqOptions.with_mesh`` / ``ReadsStorage.mesh`` /
  ``VariantsStorage.mesh``.
- ``DISQ_TPU_MESH`` env: unset/""/"0"/"off" = off; ``all``/``auto`` =
  all local devices; an integer = that many devices.
- Device counts round DOWN to a power of two (2/4/8/...): the batch
  axis shards power-of-two-bucketed compile shapes
  (``util.bucket_pow2``), so a pow2 axis always divides them evenly.
- Absent devices: asking for more devices than exist clamps to what is
  present (an 8-way knob on a 4-chip host runs 4-wide); a host left
  with one device runs the plain single-device pipeline — the knob is
  a capacity hint, never a hard failure.
"""

from __future__ import annotations

import os
import threading
from typing import Any, List, Optional

MESH_AXIS = "batch"
MESH_COUNTERS = ("device.mesh.batches", "device.mesh.exchange_bytes",
                 "device.mesh.reshard_bytes",
                 "device.mesh.sort_host_fallback")

_MESH_CACHE: dict = {}
_MESH_LOCK = threading.Lock()


def _env_devices() -> Optional[int]:
    """``DISQ_TPU_MESH`` → requested device count (0 = all), or None
    when the env knob is off."""
    raw = os.environ.get("DISQ_TPU_MESH", "").strip().lower()
    if raw in ("", "0", "false", "off", "no"):
        return None
    if raw in ("all", "auto", "true", "on", "yes"):
        return 0
    try:
        n = int(raw)
    except ValueError:
        return 0
    return n if n > 0 else None


def mesh_devices_requested(storage: Any = None) -> Optional[int]:
    """Resolve the knob without touching jax: ``DisqOptions.mesh``
    first, then ``DISQ_TPU_MESH``; None means off."""
    opts = getattr(storage, "_options", None) if storage is not None \
        else None
    n = getattr(opts, "mesh", None) if opts is not None else None
    if n is not None:
        return int(n)
    return _env_devices()


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def get_mesh(requested: int = 0):
    """The cached batch-axis mesh over the first ``requested`` local
    devices (0 = all), rounded DOWN to a power of two; ``None`` when
    that resolves to a single device (the off path).  Only this
    function ever constructs a Mesh — ``mesh_if_built`` is the
    overhead guard's witness that the off path built nothing."""
    import jax

    devs = jax.devices()
    n = len(devs) if requested <= 0 else min(requested, len(devs))
    n = _pow2_floor(max(1, n))
    if n <= 1:
        return None
    with _MESH_LOCK:
        mesh = _MESH_CACHE.get(n)
        if mesh is None:
            import numpy as np
            from jax.sharding import Mesh

            mesh = Mesh(np.array(devs[:n]), (MESH_AXIS,))
            _MESH_CACHE[n] = mesh
            from disq_tpu.runtime.tracing import observe_gauge

            observe_gauge("device.mesh.devices", float(n))
    # registered at 0 wherever a mesh is handed out (the cached one
    # too: telemetry may have been reset since it was built), so that
    # a reader tells "did not move" (the host fallback of a sound
    # sort) from "no such counter"
    from disq_tpu.runtime.tracing import counter

    for name in MESH_COUNTERS:
        counter(name).inc(0)
    return mesh


def mesh_if_built():
    """The largest mesh this process has built, or None — the
    check_overhead witness that mesh-off allocated nothing."""
    with _MESH_LOCK:
        if not _MESH_CACHE:
            return None
        return _MESH_CACHE[max(_MESH_CACHE)]


def mesh_for_storage(storage: Any):
    """Storage-scoped entry: the batch mesh when the knob is armed and
    more than one device is usable, else None.  Cheap when off — two
    attribute reads and one env lookup, no jax import."""
    req = mesh_devices_requested(storage)
    if req is None:
        return None
    return get_mesh(req)


def shard_count(mesh) -> int:
    return int(mesh.shape[MESH_AXIS])


def batch_sharding(mesh):
    """NamedSharding splitting axis 0 over the batch axis (SNIPPETS.md
    [1]: shard dim 0 when it divides, which bucketed shapes always
    do)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(MESH_AXIS))


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def mesh_put(x, mesh, batch: bool = True):
    """Normalize an array onto the mesh (batch-sharded or replicated),
    booking moved bytes into ``device.mesh.reshard_bytes`` when the
    placement actually changes.  Already-conforming arrays pass through
    untouched — the permute/concat hot path pays one sharding
    comparison, not a copy."""
    import jax

    sh = batch_sharding(mesh) if batch else replicated(mesh)
    cur = getattr(x, "sharding", None)
    try:
        if cur is not None and cur.is_equivalent_to(sh, x.ndim):
            return x
    except Exception:  # noqa: BLE001 — unequal mesh shapes compare False
        pass
    from disq_tpu.runtime.tracing import counter

    nbytes = int(x.size) * x.dtype.itemsize
    if not batch:
        # replication fans the buffer out to every device
        nbytes *= shard_count(mesh)
    counter("device.mesh.reshard_bytes").inc(nbytes)
    return jax.device_put(x, sh)


def service_devices() -> List[Any]:
    """Dispatch targets for the device decode service: the mesh's
    devices when the knob is armed at service start, else ``[None]``
    (= default-device semantics, byte-identical to the pre-mesh
    service).  Snapshotted once at service creation."""
    req = _env_devices()
    mesh = get_mesh(req) if req is not None else mesh_if_built()
    if mesh is None:
        return [None]
    return list(mesh.devices.flat)
