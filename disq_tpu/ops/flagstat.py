"""flagstat — per-category read counts (the ``samtools flagstat``
equivalent), computed on device from the columnar flag/mapq arrays.

Single-chip: one fused jnp pass. Multi-chip: the same op under
``shard_map`` with a ``psum`` over the mesh axis — counts are the
canonical "reduce over shards" pattern (SURVEY.md §5: counters returned
per shard and reduced).
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

FLAGSTAT_FIELDS = (
    "total", "secondary", "supplementary", "duplicates", "mapped",
    "paired", "read1", "read2", "proper_pair", "with_mate_mapped",
    "singletons", "qc_fail",
)


def _counts(flag, valid):
    """samtools-flagstat semantics: pair-related categories count only
    PRIMARY records (secondary 0x100 and supplementary 0x800 excluded),
    and 'with itself and mate mapped' requires the read itself mapped."""
    f = flag.astype(jnp.int32)
    v = valid.astype(jnp.int32)

    def c(hit):
        return jnp.sum(hit.astype(jnp.int32) * v)

    primary = ((f & (0x100 | 0x800)) == 0)
    paired = primary & ((f & 0x1) != 0)
    self_mapped = (f & 0x4) == 0
    mate_unmapped = (f & 0x8) != 0
    return jnp.stack(
        [
            jnp.sum(v),
            c((f & 0x100) != 0),                     # secondary
            c((f & 0x800) != 0),                     # supplementary
            c((f & 0x400) != 0),                     # duplicates
            c(self_mapped),                          # mapped
            c(paired),                               # paired
            c(paired & ((f & 0x40) != 0)),           # read1
            c(paired & ((f & 0x80) != 0)),           # read2
            c(paired & ((f & 0x2) != 0) & self_mapped),  # proper pair
            c(paired & self_mapped & ~mate_unmapped),    # with mate mapped
            c(paired & self_mapped & mate_unmapped),     # singletons
            c((f & 0x200) != 0),                     # qc fail
        ]
    )


@jax.jit
def _flagstat_single(flag: jax.Array) -> jax.Array:
    return _counts(flag, jnp.ones(flag.shape, jnp.int32))


@jax.jit
def _flagstat_masked(flag: jax.Array, n) -> jax.Array:
    """``_counts`` over the first ``n`` entries of a (possibly
    bucket-padded) device flag column — the resident-batch form, where
    padded tail entries duplicate a real record and must not count."""
    valid = (jnp.arange(flag.shape[0]) < n).astype(jnp.int32)
    return _counts(flag.astype(jnp.int32), valid)


def flagstat_resident(flag_dev, n: int) -> Dict[str, int]:
    """flagstat straight from a device-resident flag column
    (``runtime/columnar.ColumnarBatch``): zero h2d — the split path's
    re-upload of the flag column is exactly what the fused decode
    avoids — and d2h is the 48-byte count row."""
    from disq_tpu.runtime.tracing import count_transfer, device_span

    import jax as _jax

    # the record-count scalar is staged OUTSIDE the guard (it is the
    # only non-resident operand; 4 bytes)
    n_dev = jnp.asarray(np.int32(n))
    with device_span("device.kernel", kernel="flagstat",
                     records=int(n)) as fence:
        with _jax.transfer_guard("disallow"):
            out = _flagstat_masked(flag_dev, n_dev)
            _jax.block_until_ready(out)
        fence.sync(out)
    row = np.asarray(out)
    count_transfer("d2h", row.nbytes)
    return {k: int(v) for k, v in zip(FLAGSTAT_FIELDS, row)}


import functools


@functools.lru_cache(maxsize=8)
def _flagstat_sharded_compiled(mesh, axis: str, per: int):
    """shard_map'd masked flagstat over a BATCH-SHARDED resident flag
    column: each device counts its local slice (validity derived from
    its axis index — global index < n), then one 12-lane ``psum`` over
    ICI merges the rows. The column never moves; only the 48-byte
    count row crosses d2h."""
    def body(f, n):
        i = lax.axis_index(axis)
        base = (i * per).astype(jnp.int32)
        valid = ((base + jnp.arange(per, dtype=jnp.int32)) <
                 n).astype(jnp.int32)
        return lax.psum(_counts(f.astype(jnp.int32), valid), axis)

    return jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(axis), P()), out_specs=P()))


def flagstat_resident_sharded(
    flag_dev, n: int, mesh, axis: Optional[str] = None
) -> Dict[str, int]:
    """``flagstat_resident`` for a mesh-sharded resident flag column
    (tentpole c): same zero-h2d contract, reduction via ``lax.psum``
    over the batch axis instead of a single-device pass.  Exact —
    integer adds reassociate freely, so the row equals the host
    truth bit-for-bit."""
    from disq_tpu.runtime.mesh import MESH_AXIS, shard_count

    if axis is None:
        axis = MESH_AXIS if MESH_AXIS in mesh.axis_names \
            else mesh.axis_names[0]
    n_dev = int(shard_count(mesh) if axis == MESH_AXIS
                else mesh.shape[axis])
    per = int(flag_dev.shape[0]) // n_dev
    from disq_tpu.runtime.tracing import count_transfer, device_span

    # staged pre-guard with its mesh placement (4 bytes, replicated) —
    # an implicit reshard inside the guard would raise
    n_arr = jax.device_put(
        jnp.asarray(np.int32(n)), NamedSharding(mesh, P()))
    with device_span("device.kernel", kernel="flagstat",
                     records=int(n), devices=n_dev) as fence:
        with jax.transfer_guard("disallow"):
            out = _flagstat_sharded_compiled(mesh, axis, per)(
                flag_dev, n_arr)
            jax.block_until_ready(out)
        fence.sync(out)
    row = np.asarray(out)
    count_transfer("d2h", row.nbytes)
    return {k: int(v) for k, v in zip(FLAGSTAT_FIELDS, row)}


def flagstat_counts(
    flag: np.ndarray, mesh: Optional[Mesh] = None, axis: str = "shards"
) -> Dict[str, int]:
    """flag column → category counts. With a mesh, the column is sharded
    over it and the reduction is a psum over ICI."""
    if mesh is not None and axis not in mesh.axis_names:
        if len(mesh.axis_names) == 1:
            axis = mesh.axis_names[0]
        else:
            raise ValueError(
                f"axis {axis!r} not in mesh axes {mesh.axis_names}; pass "
                "axis= explicitly for multi-axis meshes"
            )
    from disq_tpu.runtime.tracing import (
        count_transfer, device_span, hbm_resident)

    if mesh is None or mesh.shape[axis] <= 1 or len(flag) == 0:
        staged = flag.astype(np.int32)
        count_transfer("h2d", staged.nbytes)
        with hbm_resident(staged.nbytes):
            with device_span("device.kernel", kernel="flagstat",
                             records=len(flag)) as fence:
                out = fence.sync(_flagstat_single(jnp.asarray(staged)))
            row = np.asarray(out)
            count_transfer("d2h", row.nbytes)
        return {k: int(v) for k, v in zip(FLAGSTAT_FIELDS, row)}
    n_shards = mesh.shape[axis]
    per = -(-len(flag) // n_shards)
    padded = np.zeros(per * n_shards, dtype=np.int32)
    padded[: len(flag)] = flag
    validity = np.zeros(per * n_shards, dtype=np.int32)
    validity[: len(flag)] = 1
    sharding = NamedSharding(mesh, P(axis, None))
    count_transfer("h2d", padded.nbytes + validity.nbytes)
    with hbm_resident(padded.nbytes + validity.nbytes):
        fd = jax.device_put(padded.reshape(n_shards, per), sharding)
        vd = jax.device_put(validity.reshape(n_shards, per), sharding)

        def body(f, v):
            local = _counts(f.reshape(-1), v.reshape(-1))
            return lax.psum(local, axis)

        with device_span("device.kernel", kernel="flagstat",
                         records=len(flag), shards=n_shards) as fence:
            out = fence.sync(jax.jit(
                shard_map(
                    body, mesh=mesh,
                    in_specs=(P(axis, None), P(axis, None)),
                    out_specs=P(),
                )
            )(fd, vd))
        row = np.asarray(out)
        count_transfer("d2h", row.nbytes)
    return {k: int(v) for k, v in zip(FLAGSTAT_FIELDS, row)}
