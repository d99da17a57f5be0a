"""Resident predicate filtering + seeded subsampling (the
``samtools view -f/-F/-q/-s`` family) pushed into the decode path.

The filter is a device mask over the resident flag/mapq columns —
built and applied (via ``ColumnarBatch.filter``'s device compaction
gather) BEFORE any record column crosses d2h, so a filtered resident
read never pays transfer for records it drops. The host path
(``ReadBatch``) evaluates the *same* predicate in numpy; both sides
share the integer-exact subsample hash, so the kept set is identical
bit for bit regardless of where the mask was built.

Grammar (``DisqOptions.read_filter`` / env ``DISQ_TPU_READ_FILTER`` /
``ReadsStorage.read_filter()``), mirroring ``samtools view``::

    -f INT    require all of these flag bits (int or 0x hex)
    -F INT    exclude records with any of these flag bits
    -q INT    minimum MAPQ
    -s SEED.FRAC   keep ~FRAC of records, seeded subsample keyed on a
                   hash of the read name (both mates of a pair share a
                   name, so they are kept or dropped together)

e.g. ``"-F 0x904 -q 30 -s 42.25"``.
"""

from __future__ import annotations

import functools
import re
from dataclasses import dataclass
from typing import Optional

import numpy as np

# splitmix32-style finalizer constants — shared verbatim by the numpy
# and jnp mask builders (u32 wraparound arithmetic on both sides).
_SEED_MIX = 0x9E3779B9
_MIX_A = 0x7FEB352D
_MIX_B = 0x846CA68B
_FNV_BASIS = 0x811C9DC5
_FNV_PRIME = 0x01000193


@dataclass(frozen=True)
class ReadFilter:
    """Parsed predicate — immutable so sources can cache it."""

    require_flags: int = 0
    exclude_flags: int = 0
    min_mapq: int = 0
    subsample: Optional[float] = None  # keep fraction in [0, 1)
    seed: int = 0

    @property
    def needs_name_hash(self) -> bool:
        return self.subsample is not None

    @property
    def threshold(self) -> int:
        """u32 keep threshold for the subsample hash comparison."""
        if self.subsample is None:
            return 0xFFFFFFFF
        return min(0xFFFFFFFF, int(round(self.subsample * 2 ** 32)))


_TOKEN_RE = re.compile(r"^(0[xX][0-9a-fA-F]+|\d+)$")


def _parse_int(tok: str, opt: str) -> int:
    if not _TOKEN_RE.match(tok):
        raise ValueError(
            f"read_filter: {opt} wants an integer (or 0x hex), got {tok!r}")
    return int(tok, 0)


def parse_read_filter(spec: str) -> ReadFilter:
    """Parse the ``samtools view``-shaped grammar above. Raises
    ``ValueError`` on unknown options or malformed operands — at
    options-build time, never mid-read."""
    toks = spec.split()
    req = exc = minq = 0
    frac: Optional[float] = None
    seed = 0
    i = 0
    while i < len(toks):
        opt = toks[i]
        if i + 1 >= len(toks):
            raise ValueError(f"read_filter: {opt} missing its operand")
        val = toks[i + 1]
        if opt == "-f":
            req = _parse_int(val, opt)
        elif opt == "-F":
            exc = _parse_int(val, opt)
        elif opt == "-q":
            minq = _parse_int(val, opt)
        elif opt == "-s":
            # samtools -s: integer part is the seed, fraction the rate
            try:
                f = float(val)
            except ValueError:
                raise ValueError(
                    f"read_filter: -s wants SEED.FRAC, got {val!r}")
            if f < 0:
                raise ValueError(f"read_filter: -s must be >= 0, got {val}")
            seed = int(f)
            frac = f - seed
            if frac >= 1.0 or (frac == 0.0 and "." not in val):
                # "-s 3" (no fractional part) keeps everything: not a
                # subsample at all — treat as a spec error, it is
                # always a typo for "-s 3.x"
                raise ValueError(
                    f"read_filter: -s {val!r} has no keep fraction")
        else:
            raise ValueError(
                f"read_filter: unknown option {opt!r} "
                "(grammar: -f/-F/-q INT, -s SEED.FRAC)")
        i += 2
    return ReadFilter(require_flags=req, exclude_flags=exc,
                      min_mapq=minq, subsample=frac, seed=seed)


# -- name hashing (subsample key) -------------------------------------------


def _fnv_loop(h: np.ndarray, char_at, nlen: np.ndarray) -> np.ndarray:
    """Shared FNV-1a loop: ``char_at(i)`` yields the i-th name byte per
    record (0 past end); vectorized over records, looped over the max
    name length (~tens of passes, no per-record Python)."""
    maxlen = int(nlen.max()) if len(nlen) else 0
    for i in range(maxlen):
        live = i < nlen
        ch = char_at(i)
        h = np.where(live,
                     (h ^ ch.astype(np.uint32)) * np.uint32(_FNV_PRIME), h)
    return h


def name_hashes_from_blob(blob: np.ndarray, offsets: np.ndarray,
                          order: Optional[np.ndarray] = None) -> np.ndarray:
    """u32 FNV-1a of each record's read name straight from the raw
    record bytes — no host record parse. ``order`` maps logical record
    index -> blob record index (a ``permuted()`` batch)."""
    off = np.asarray(offsets[:-1], dtype=np.int64)
    if order is not None:
        off = off[np.asarray(order, dtype=np.int64)]
    n = len(off)
    if n == 0:
        return np.zeros(0, np.uint32)
    # l_read_name (u8 at record offset 12) includes the trailing NUL
    nlen = blob[off + 12].astype(np.int64) - 1
    limit = len(blob) - 1
    h = np.full(n, _FNV_BASIS, np.uint32)
    return _fnv_loop(
        h, lambda i: blob[np.minimum(off + 36 + i, limit)], nlen)


def name_hashes_from_columns(names: np.ndarray,
                             name_offsets: np.ndarray) -> np.ndarray:
    """Same hash from a host batch's ragged name column."""
    off = np.asarray(name_offsets[:-1], dtype=np.int64)
    n = len(off)
    if n == 0:
        return np.zeros(0, np.uint32)
    nlen = np.diff(np.asarray(name_offsets, dtype=np.int64))
    limit = max(0, len(names) - 1)
    h = np.full(n, _FNV_BASIS, np.uint32)
    pad = names if len(names) else np.zeros(1, np.uint8)
    return _fnv_loop(
        h, lambda i: pad[np.minimum(off + i, limit)], nlen)


def _subsample_keep_host(h: np.ndarray, seed: int,
                         threshold: int) -> np.ndarray:
    x = h.astype(np.uint32) ^ np.uint32((seed * _SEED_MIX) & 0xFFFFFFFF)
    x ^= x >> np.uint32(16)
    x *= np.uint32(_MIX_A)
    x ^= x >> np.uint32(15)
    x *= np.uint32(_MIX_B)
    x ^= x >> np.uint32(16)
    return x < np.uint32(threshold)


# -- mask builders ----------------------------------------------------------


def host_mask(rf: ReadFilter, flag: np.ndarray, mapq: np.ndarray,
              name_hash: Optional[np.ndarray] = None) -> np.ndarray:
    """The predicate on host columns — the non-resident decode path
    and the oracle the resident compaction is tested against."""
    f = flag.astype(np.uint32)
    keep = ((f & np.uint32(rf.require_flags)) == np.uint32(rf.require_flags))
    keep &= (f & np.uint32(rf.exclude_flags)) == 0
    keep &= mapq.astype(np.uint32) >= np.uint32(rf.min_mapq)
    if rf.needs_name_hash:
        if name_hash is None:
            raise ValueError("subsample filter needs name hashes")
        keep &= _subsample_keep_host(name_hash, rf.seed, rf.threshold)
    return keep


@functools.lru_cache(maxsize=1)
def _mask_kernel():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def read_filter_mask(flag, mapq, nh, req, exc, minq, seed_mix, thresh,
                         n):
        f = flag.astype(jnp.uint32)
        keep = (f & req) == req
        keep &= (f & exc) == 0
        keep &= mapq.astype(jnp.uint32) >= minq
        x = nh ^ seed_mix
        x ^= x >> 16
        x = x * jnp.uint32(_MIX_A)
        x ^= x >> 15
        x = x * jnp.uint32(_MIX_B)
        x ^= x >> 16
        keep &= x < thresh
        # padded tail lanes duplicate a real record — never keep them
        keep &= jnp.arange(flag.shape[0], dtype=jnp.int32) < n
        return keep

    return read_filter_mask


def resident_mask(rf: ReadFilter, batch) -> np.ndarray:
    """Build the keep mask on device from a ``ColumnarBatch``'s
    resident flag/mapq columns (one bool/record crosses d2h — the
    compaction needs it host-side to gather the record blob anyway).
    The subsample hash column is host-derived from the record bytes
    (names are ragged; same precedent as ``ops/depth.py``'s host
    bound math) and uploaded once, 4 B/record."""
    from disq_tpu.runtime.tracing import count_transfer, device_span

    import jax
    import jax.numpy as jnp

    dev = batch._dev_snapshot()
    if dev is None:
        raise ValueError("resident_mask needs a device-backed batch")
    n = batch.count
    padded = int(dev["flag"].shape[0])
    if rf.needs_name_hash:
        src = batch.encode_source()
        if src is None:
            raise ValueError(
                "subsample filter needs the record blob for name hashes")
        blob, offsets, order = src
        nh_host = np.zeros(padded, np.uint32)
        nh_host[:n] = name_hashes_from_blob(blob, offsets, order)
        count_transfer("h2d", nh_host.nbytes)
    else:
        nh_host = np.zeros(padded, np.uint32)
    # scalar operands staged pre-guard (tiny, like flagstat's n)
    scalars = [jnp.asarray(np.uint32(v)) for v in (
        rf.require_flags, rf.exclude_flags, rf.min_mapq,
        (rf.seed * _SEED_MIX) & 0xFFFFFFFF, rf.threshold)]
    n_dev = jnp.asarray(np.int32(n))
    nh = jnp.asarray(nh_host)
    with device_span("device.kernel", kernel="read_filter",
                     records=n) as fence:
        with jax.transfer_guard("disallow"):
            keep = _mask_kernel()(dev["flag"], dev["mapq"], nh,
                                  *scalars, n_dev)
            jax.block_until_ready(keep)
        fence.sync(keep)
    out = np.asarray(keep[:n])
    count_transfer("d2h", out.nbytes)
    return out


def apply_read_filter(batch, rf: ReadFilter):
    """Filter any batch flavor: a device-backed ``ColumnarBatch``
    compacts on device (mask built resident, gather before any column
    d2h); host batches evaluate the same predicate in numpy. Books
    ``ops.filter.records_{in,kept}``."""
    from disq_tpu.runtime.tracing import counter, span

    n = batch.count if hasattr(batch, "count") else len(batch)
    n = int(n)
    with span("ops.filter.apply", records=n):
        device_backed = getattr(batch, "device_backed", False)
        if device_backed:
            mask = resident_mask(rf, batch)
        else:
            nh = None
            if rf.needs_name_hash:
                nh = name_hashes_from_columns(
                    batch.names, batch.name_offsets)
            mask = host_mask(rf, np.asarray(batch.flag),
                             np.asarray(batch.mapq), nh)
        out = batch.filter(mask)
        counter("ops.filter.records_in").inc(n)
        counter("ops.filter.records_kept").inc(int(out.count if hasattr(
            out, "count") else len(out)))
    return out
