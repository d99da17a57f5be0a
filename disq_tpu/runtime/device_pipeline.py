"""Device-resident read pipeline: decoded bytes → parse → sort keys →
flagstat, all as jax Arrays with no host numpy between stages.

BASELINE.json north star ("HBM-resident shard
buffers ... bypassing per-record htsjdk object allocation"): the host
inflate/stage step puts a shard's decoded BGZF bytes on device ONCE;
everything after — record-prefix gather, the Pallas fixed-field parse
kernel, coordinate-key construction, the sort, flag filtering, the
flagstat histogram — runs on device arrays inside a single jit.

Residency is PROVEN, not claimed: the jitted steps execute under
``jax.transfer_guard("disallow")``, which raises on any implicit
device↔host copy. The only transfers in the whole flow are the
explicit up-front blob/offset uploads and the final (tiny, LAZY)
results fetch. Record *offsets* are planning metadata (the shard
manifest), computed during the decode walk like split bounds — the
record columns themselves never round-trip through the host.

Three entry points:

- ``run_device_pipeline`` — the parse→sort→flagstat chain; returns a
  ``DevicePipelineResult`` whose keys / order / stats fetch d2h
  **lazily on attribute access** (tuple unpacking materializes all
  three under one transfer span, exactly the old behavior), so a
  caller that only wants ``stats`` never moves the key vectors.
- ``parse_columns_resident`` — the fused-decode half: upload (or reuse
  a device-assembled blob from the SIMD inflate kernels) + one parse
  launch, returning the raw device column dict for
  ``runtime/columnar.ColumnarBatch``.
- ``assemble_device_words`` — compaction of the 128-lane inflate
  kernel's *still-resident* transposed output chunks into one
  contiguous device word blob (per-byte searchsorted gather, host
  fallback lanes patched from a small upload), so the parse chain
  reads the decoded bytes where the inflate kernel left them instead
  of round-tripping them through host and back.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp


from disq_tpu.util import bucket_pow2 as _bucket
from disq_tpu.util import pad_quantum


def gather_record_words(blob_words: jax.Array,
                        starts: jax.Array) -> jax.Array:
    """Record-prefix gather (jit-traceable): 9 consecutive u32 words
    per record from a device word blob. BAM records are 4-byte aligned
    only at the word level of their own offsets, so unaligned words are
    assembled from adjacent pairs."""
    from disq_tpu.ops.parse import N_WORDS

    w0 = starts >> 2
    sh = ((starts & 3) << 3).astype(jnp.uint32)
    idx = w0[:, None] + jnp.arange(N_WORDS + 1)[None, :]
    raw = blob_words[jnp.clip(idx, 0, blob_words.shape[0] - 1)]
    lo = raw[:, :N_WORDS].astype(jnp.uint32)
    hi = raw[:, 1:].astype(jnp.uint32)
    shv = sh[:, None]
    return jnp.where(
        shv == 0, lo,
        (lo >> shv) | (hi << ((jnp.uint32(32) - shv) & jnp.uint32(31))),
    ).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pipeline(blob_words: jax.Array, starts: jax.Array,
              interpret: bool = False):
    """blob_words: decoded shard bytes as LE u32 words (device);
    starts: per-record byte offsets of the block_size word (device).
    Returns (sorted u32-pair keys, sort permutation, flagstat vector) —
    all device arrays."""
    from disq_tpu.ops.flagstat import _flagstat_single
    from disq_tpu.ops.parse import parse_fixed_words_pallas

    words = gather_record_words(blob_words, starts)
    cols = parse_fixed_words_pallas(words, interpret=interpret)
    refid, pos, flag = cols["refid"], cols["pos"], cols["flag"]

    # coordinate keys as u32 pairs (no x64): unmapped after everything
    hi_k = jnp.where(refid < 0, jnp.uint32(0x7FFFFFFF),
                     refid.astype(jnp.uint32))
    lo_k = (pos + 1).astype(jnp.uint32)
    order = jnp.lexsort((lo_k, hi_k))
    # flagstat is permutation-invariant: no need to gather by order
    fs = _flagstat_single(flag.astype(jnp.int32))
    return hi_k[order], lo_k[order], order.astype(jnp.int32), fs


@functools.partial(jax.jit, static_argnames=("interpret",))
def _parse_columns(blob_words: jax.Array, starts: jax.Array,
                   interpret: bool = False) -> Dict[str, jax.Array]:
    """Fused gather + Pallas fixed-field parse over a device word blob;
    ``starts`` may be bucket-padded (pads duplicate a valid start) —
    the caller slices columns back to the true record count."""
    from disq_tpu.ops.parse import parse_fixed_words_pallas

    words = gather_record_words(blob_words, starts)
    return parse_fixed_words_pallas(words, interpret=interpret)


@functools.lru_cache(maxsize=8)
def _mesh_parse_compiled(mesh, interpret: bool):
    """shard_map'd gather+parse over the batch mesh axis: the word
    blob is replicated (every record's prefix may straddle any byte),
    the bucket-padded starts shard over ``batch``, and each device
    runs the SAME local gather + Pallas parse the single-device jit
    runs — out columns come back 1-D and batch-sharded, exactly the
    ``ColumnarBatch`` column shape."""
    from disq_tpu.runtime.mesh import MESH_AXIS
    from disq_tpu.ops.parse import parse_fixed_words_pallas
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    # its name is the program's in a device trace
    # (``jit_mesh_parse_columns``): a reader keys on it
    def mesh_parse_columns(blob_words, starts):
        words = gather_record_words(blob_words, starts)
        return parse_fixed_words_pallas(words, interpret=interpret)

    # check_vma=False: shard_map cannot track the varying-mesh-axes
    # type through pallas_call; the body is per-device-local by
    # construction
    return jax.jit(shard_map(
        mesh_parse_columns, mesh=mesh, in_specs=(P(None), P(MESH_AXIS)),
        out_specs=P(MESH_AXIS), check_vma=False))


def upload_blob_words(blob: np.ndarray) -> Tuple[jax.Array, int]:
    """Word-align a decoded byte blob with ONE preallocated buffer +
    tail write and upload it; returns (device u32 words, bytes moved).
    Transfer accounting is the caller's (some callers batch it with
    the starts upload under one span)."""
    pad = (-len(blob)) % 4
    if pad:
        padded = np.empty(len(blob) + pad, np.uint8)
        padded[: len(blob)] = blob
        padded[len(blob):] = 0
        blob = padded
    words_host = np.ascontiguousarray(blob).view("<u4")
    return jax.device_put(jnp.asarray(words_host)), words_host.nbytes


def pad_starts(offsets: np.ndarray, origin: int = 0) -> np.ndarray:
    """Record starts as bucket-padded i32 (pads repeat the last valid
    start so padded lanes parse a real record and compile shapes
    quantize to a handful of buckets instead of one per shard)."""
    starts = offsets[:-1].astype(np.int64) + origin
    n = len(starts)
    padded = np.empty(_bucket(max(1, n)), np.int32)
    padded[:n] = starts
    padded[n:] = starts[-1] if n else 0
    return padded


# ---------------------------------------------------------------------------
# Device blob assembly: inflate-kernel chunks -> one contiguous word blob
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("total_words",))
def _assemble_words_for(flat_lanes: jax.Array, offsets: jax.Array,
                        lane_of: jax.Array, patch_flat: jax.Array,
                        patch_base: jax.Array,
                        total_words: int) -> jax.Array:
    """Compact per-lane decoded bytes (still device-resident from the
    128-lane inflate kernel, lanes-major words) into one contiguous LE
    u32 word blob, entirely on device.

    ``flat_lanes``: (n_lanes, ow) u32 — stacked transposed chunk
    outputs. ``offsets``: (nblocks_padded + 1,) i32 cumulative usizes
    (pads repeat the total). ``lane_of``: flat lane index per block, or
    0 for host-patched blocks. ``patch_flat``/``patch_base``: bytes of
    host-fallback blocks (oversize / kernel-flagged lanes), gathered
    when ``patch_base[i] >= 0``.

    Per output byte: block via searchsorted, byte via one lane gather —
    O(blob) work with log(nblocks) index math, no host round-trip."""
    ow = flat_lanes.shape[1]
    total = jnp.int32(offsets[-1])

    def byte_at(b):
        # b: (total_words,) i32 byte positions
        i = jnp.searchsorted(offsets, b, side="right") - 1
        i = jnp.clip(i, 0, lane_of.shape[0] - 1)
        within = b - offsets[i]
        lane = lane_of[i]
        w = flat_lanes[lane, jnp.clip(within >> 2, 0, ow - 1)]
        dev_b = (w >> ((within.astype(jnp.uint32) & 3) << 3)) & 0xFF
        pb = patch_base[i]
        pidx = jnp.clip(pb + within, 0, patch_flat.shape[0] - 1)
        host_b = patch_flat[pidx].astype(jnp.uint32)
        byte = jnp.where(pb >= 0, host_b, dev_b)
        return jnp.where(b < total, byte, jnp.uint32(0))

    w_iota = jnp.arange(total_words, dtype=jnp.int32) << 2
    out = byte_at(w_iota)
    out = out | (byte_at(w_iota + 1) << 8)
    out = out | (byte_at(w_iota + 2) << 16)
    out = out | (byte_at(w_iota + 3) << 24)
    return out


def assemble_device_words(chunks, lane_of: np.ndarray,
                          offsets: np.ndarray,
                          patches) -> Tuple[jax.Array, int]:
    """Host driver for ``_assemble_words_for``: uploads only the small
    per-block index arrays (and any host-fallback patch bytes), stacks
    the still-resident chunk outputs, and returns (device word blob,
    bytes of the index uploads). The decoded payload bytes themselves
    never leave the device."""
    from disq_tpu.runtime.tracing import count_transfer

    total = int(offsets[-1])
    if total >= 2 ** 31:
        # the gather indexes (and the offsets upload) are i32 — refuse
        # here rather than let the int32 cast below wrap silently
        raise ValueError(
            f"decoded blob is {total} bytes; device assembly indexes "
            "with i32 — split the shard below 2 GiB")
    # quantum-padded like the upload path: a plain power-of-two bucket
    # would run the 4 per-word gathers (and hold HBM) over up to 2x the
    # real data on large shards
    total_words = pad_quantum(max(1, (total + 3) // 4))
    nb = len(offsets) - 1
    nb_pad = _bucket(max(1, nb))
    off_pad = np.empty(nb_pad + 1, np.int32)
    off_pad[: nb + 1] = offsets
    off_pad[nb + 1:] = total
    lane_pad = np.zeros(nb_pad, np.int32)
    lane_pad[:nb] = np.where(lane_of[:nb] >= 0, lane_of[:nb], 0)
    patch_base = np.full(nb_pad, -1, np.int64)
    parts = []
    acc = 0
    for i, data in patches:
        patch_base[i] = acc
        parts.append(np.frombuffer(data, np.uint8)
                     if not isinstance(data, np.ndarray) else data)
        acc += len(parts[-1])
    patch_flat = (np.concatenate(parts) if parts
                  else np.zeros(1, np.uint8))
    flat = jnp.concatenate([jnp.reshape(c, (c.shape[0], -1))
                            for c in chunks], axis=0)
    up = off_pad.nbytes + lane_pad.nbytes + patch_flat.nbytes \
        + patch_base.nbytes
    count_transfer("h2d", up)
    words = _assemble_words_for(
        flat, jnp.asarray(off_pad), jnp.asarray(lane_pad),
        jnp.asarray(patch_flat), jnp.asarray(patch_base.astype(np.int32)),
        total_words=total_words)
    return words, up


# ---------------------------------------------------------------------------
# Fused columnar parse (the ColumnarBatch build step)
# ---------------------------------------------------------------------------


def parse_columns_resident(
    blob: Optional[np.ndarray],
    offsets: np.ndarray,
    words_dev: Optional[jax.Array] = None,
    origin: int = 0,
    interpret: bool = False,
    mesh=None,
    coarse: bool = False,
    staged: Optional[np.ndarray] = None,
) -> Tuple[Dict[str, jax.Array], int, int]:
    """One fused upload(+)gather(+)parse launch chain producing the raw
    device column dict (bucket-padded; callers slice to ``n``).

    ``words_dev`` (from ``assemble_device_words``) skips the blob
    upload entirely — the parse reads the inflate kernel's output where
    it already lives in HBM; ``origin`` rebases record offsets into
    that blob. Returns (cols, resident word bytes, record count).

    ``staged`` is a host buffer that is an upload buffer already (the
    decode service's ``Submission.base``: uint8, ``pad_quantum`` words
    long, zero past the decoded bytes) and holds ``blob`` from byte
    ``origin`` on: it goes up whole, as it is, where any other host
    blob is first copied into such a buffer.  Either way the span
    ``columnar.batch.stage`` and the counter
    ``columnar.batch.stage_bytes{how=in_place|copied}`` book the padded
    size.

    With ``mesh`` (runtime/mesh.py batch-axis mesh) the parse runs as
    ONE sharded program: the word blob replicates to every device (h2d
    and HBM booked per copy — accounting stays per-device-correct),
    the bucket-padded starts shard over ``batch`` (power-of-two bucket
    sizes always divide the power-of-two axis), and the returned
    columns are batch-sharded device arrays.  ``coarse`` pads the
    uploaded blob in ``pad_quantum``'s coarse steps."""
    from disq_tpu.runtime.tracing import (
        count_transfer, counter, device_span, span)

    n = len(offsets) - 1
    if int(offsets[-1]) + origin >= 2 ** 31:
        raise ValueError(
            f"decoded shard is {int(offsets[-1]) + origin} bytes; the "
            "device pipeline indexes with i32 — split the shard below "
            "2 GiB")
    n_dev = 1
    if mesh is not None:
        from disq_tpu.runtime.mesh import (
            batch_sharding, mesh_put, replicated, shard_count)

        n_dev = shard_count(mesh)
    starts_host = pad_starts(offsets, origin)
    if words_dev is None:
        # quantum-pad the blob like the starts: shard blob sizes vary
        # per split, and an exact-shape upload would retrace the parse
        # jit once per shard — quantized shapes keep compiles to a
        # handful per run at <=~6% pad overhead on big shards
        nbytes = (staged.nbytes if staged is not None else 4 * pad_quantum(
            max(1, (len(blob) + 3) // 4), coarse))
        with span("columnar.batch.stage", bytes=nbytes):
            padded = staged
            if padded is None:
                padded = np.empty(nbytes, np.uint8)
                padded[: len(blob)] = blob
                padded[len(blob):] = 0
        counter("columnar.batch.stage_bytes").inc(
            nbytes, how="copied" if staged is None else "in_place")
        # the replicated blob lands on every device: book each copy
        word_bytes = padded.nbytes * n_dev
        up = word_bytes + starts_host.nbytes
        # ``device_put`` is asynchronous: this span is the enqueue
        with span("device.transfer", direction="h2d", site="parse_blob",
                  bytes=up):
            if mesh is None:
                words_dev = jax.device_put(jnp.asarray(padded.view("<u4")))
                starts_dev = jax.device_put(jnp.asarray(starts_host))
            else:
                words_dev = jax.device_put(
                    jnp.asarray(padded.view("<u4")), replicated(mesh))
                starts_dev = jax.device_put(
                    jnp.asarray(starts_host), batch_sharding(mesh))
        count_transfer("h2d", up)
    else:
        with span("device.transfer", direction="h2d", site="parse_starts",
                  bytes=starts_host.nbytes):
            if mesh is None:
                starts_dev = jax.device_put(jnp.asarray(starts_host))
            else:
                # the inflate chain left the blob on one device —
                # replicate it over ICI (mesh_put books the fan-out
                # into device.mesh.reshard_bytes, not h2d: it never
                # crosses the host)
                words_dev = mesh_put(words_dev, mesh, batch=False)
                starts_dev = jax.device_put(
                    jnp.asarray(starts_host), batch_sharding(mesh))
        count_transfer("h2d", starts_host.nbytes)
        word_bytes = int(words_dev.size) * 4 * n_dev
    # bind the compiled fn outside the guard (building the shard_map
    # program is host work; only its launch belongs under the guard)
    parse_fn = (_parse_columns if mesh is None
                else _mesh_parse_compiled(mesh, interpret))
    with device_span("device.kernel", kernel="columnar_parse",
                     records=n, devices=n_dev) as fence:
        with jax.transfer_guard("disallow"):
            if mesh is None:
                cols = parse_fn(words_dev, starts_dev,
                                interpret=interpret)
            else:
                cols = parse_fn(words_dev, starts_dev)
            jax.block_until_ready(cols["pos"])
        fence.sync(cols["pos"])
    if mesh is not None:
        counter("device.mesh.batches").inc()
    return cols, word_bytes + starts_host.nbytes, n


# ---------------------------------------------------------------------------
# run_device_pipeline with a lazy result fetch
# ---------------------------------------------------------------------------


class DevicePipelineResult:
    """Lazy handle over the pipeline's device outputs.

    Tuple unpacking (``keys, order, stats = run_device_pipeline(...)``)
    materializes all three under ONE d2h transfer span — the historical
    behavior. Attribute access (``res.stats``) fetches only that piece,
    once: repeated access returns the cache, so ``device.transfer``
    bytes are never double-booked on the fused path. ``release()``
    (also ``__del__``) books never-fetched results into
    ``device.d2h_avoided_bytes`` and returns the HBM estimate."""

    __slots__ = ("_dev", "_np", "_hbm", "_released", "__weakref__")

    def __init__(self, hi=None, lo=None, order=None, fs=None,
                 hbm_bytes: int = 0,
                 host: Optional[Dict[str, np.ndarray]] = None) -> None:
        self._dev = (None if host is not None
                     else {"hi": hi, "lo": lo, "order": order, "fs": fs})
        self._np: Dict[str, np.ndarray] = dict(host or {})
        self._hbm = hbm_bytes
        self._released = False

    @classmethod
    def empty(cls) -> "DevicePipelineResult":
        from disq_tpu.ops.flagstat import FLAGSTAT_FIELDS

        return cls(host={
            "hi": np.zeros(0, np.uint32), "lo": np.zeros(0, np.uint32),
            "order": np.zeros(0, np.int32),
            "fs": np.zeros(len(FLAGSTAT_FIELDS), np.int32),
        })

    def _fetch(self, *names: str) -> None:
        from disq_tpu.runtime.tracing import count_transfer, span

        if self._dev is None:
            if any(m not in self._np for m in names):
                raise RuntimeError(
                    "result accessed after release() — fetch before "
                    "releasing the DevicePipelineResult")
            return
        missing = [m for m in names if m not in self._np]
        if not missing:
            return
        with span("device.transfer", direction="d2h",
                  site="pipeline_fetch") as labels:
            got = {m: np.asarray(self._dev[m]) for m in missing}
            labels["bytes"] = sum(a.nbytes for a in got.values())
        count_transfer("d2h", labels["bytes"])
        self._np.update(got)
        if all(k in self._np for k in ("hi", "lo", "order", "fs")):
            self._release_hbm()

    def _release_hbm(self) -> None:
        if self._hbm:
            from disq_tpu.runtime.tracing import track_hbm

            track_hbm(-self._hbm)
            self._hbm = 0
        self._dev = None

    @property
    def keys(self) -> np.ndarray:
        """Sorted u64 coordinate keys (fetches the u32 key pair)."""
        self._fetch("hi", "lo")
        return (self._np["hi"].astype(np.uint64) << np.uint64(32)) | \
            self._np["lo"].astype(np.uint64)

    @property
    def order(self) -> np.ndarray:
        self._fetch("order")
        return self._np["order"]

    @property
    def stats(self) -> Dict[str, int]:
        from disq_tpu.ops.flagstat import FLAGSTAT_FIELDS

        self._fetch("fs")
        return {k: int(v)
                for k, v in zip(FLAGSTAT_FIELDS, self._np["fs"])}

    def release(self) -> None:
        """Drop device results; columns never fetched are booked into
        ``device.d2h_avoided_bytes`` — the d2h the lazy fetch skipped."""
        if self._released:
            return
        self._released = True
        if self._dev is not None:
            avoided = sum(
                int(np.prod(self._dev[m].shape)) * self._dev[m].dtype.itemsize
                for m in ("hi", "lo", "order", "fs")
                if m not in self._np and self._dev.get(m) is not None)
            if avoided:
                from disq_tpu.runtime.tracing import counter

                counter("device.d2h_avoided_bytes").inc(avoided)
        self._release_hbm()

    def __del__(self) -> None:  # pragma: no cover - GC timing
        try:
            self.release()
        except Exception:  # noqa: BLE001 — interpreter shutdown
            pass

    def __iter__(self) -> Iterator:
        """Back-compat tuple protocol: one bulk fetch, then
        (keys, order, stats)."""
        self._fetch("hi", "lo", "order", "fs")
        yield self.keys
        yield self.order
        yield self.stats


def run_device_pipeline(
    blob: np.ndarray, offsets: np.ndarray, interpret: bool = False,
) -> DevicePipelineResult:
    """Upload a decoded shard once, run the device-resident step under a
    transfer guard, and hand back a LAZY result: d2h happens per result
    on first access (or all at once under tuple unpacking).

    blob: decoded BGZF payload bytes (u8). offsets: (n+1,) record byte
    offsets (the decode-walk manifest)."""
    from disq_tpu.runtime.tracing import (
        count_transfer, device_span, span, track_hbm)

    if len(offsets) <= 1:
        return DevicePipelineResult.empty()
    if int(offsets[-1]) >= 2 ** 31:
        raise ValueError(
            f"decoded shard is {int(offsets[-1])} bytes; the device "
            "pipeline indexes with i32 — split the shard below 2 GiB")
    starts_host = np.ascontiguousarray(offsets[:-1].astype(np.int32))
    # explicit uploads — the ONLY host->device transfers in the flow.
    # Upload accounting covers what actually moves: the word-aligned
    # blob (pad bytes included) plus the starts vector.
    with span("device.transfer", direction="h2d",
              site="pipeline_upload") as labels:
        blob_dev, blob_bytes = upload_blob_words(blob)
        starts_dev = jax.device_put(jnp.asarray(starts_host))
        labels["bytes"] = up_bytes = blob_bytes + starts_host.nbytes
    count_transfer("h2d", up_bytes)
    track_hbm(up_bytes)
    try:
        with device_span("device.kernel", kernel="device_pipeline") as fence:
            with jax.transfer_guard("disallow"):
                hi_k, lo_k, order, fs = _pipeline(
                    blob_dev, starts_dev, interpret=interpret)
            fence.sync(fs)
    except BaseException:
        track_hbm(-up_bytes)
        raise
    # the uploaded blob/starts die with this frame — from here on only
    # the (small) result vectors are resident, so the gauge must carry
    # their footprint, not the upload's, for the result's lifetime
    res_bytes = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in (hi_k, lo_k, order, fs))
    track_hbm(res_bytes - up_bytes)
    return DevicePipelineResult(hi_k, lo_k, order, fs,
                                hbm_bytes=res_bytes)
