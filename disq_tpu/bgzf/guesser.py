"""BGZF block-boundary guessing from an arbitrary byte offset.

Reference parity: ``impl/formats/bgzf/BgzfBlockGuesser.java`` (itself a
descendant of Hadoop-BAM's ``BGZFSplitGuesser``). Mechanism: scan forward
from the split offset for bytes that look like a BGZF member header
(gzip magic ``1f 8b``, CM=8, FLG.FEXTRA, an XLEN-bounded extra field whose
``BC`` subfield yields BSIZE), then *confirm* by checking that BSIZE
chains to further plausible block headers — false positives die
geometrically with chain depth.

TPU-first design note: rather than the reference's byte-at-a-time stream
scan, candidate positions are found with a vectorized numpy compare over
the staged split buffer (the same algorithm a Pallas scan kernel would
run; host numpy is already memory-bound here), then only candidates pay
the chain-validation cost.
"""

from __future__ import annotations

import struct
from typing import List, Optional

import numpy as np

from disq_tpu.bgzf.block import (
    BGZF_HEADER_SIZE,
    BGZF_FOOTER_SIZE,
    BGZF_MAX_BLOCK_SIZE,
    BgzfBlock,
    parse_block_header,
)
from disq_tpu.fsw.filesystem import FileSystemWrapper

# How many successor headers must chain-validate before we accept a
# candidate. The reference confirms by following BSIZE to the next block;
# two extra links make the false-positive probability negligible
# (each link requires 4 magic bytes + structural fields to match).
CHAIN_DEPTH = 2

# When guessing near a split boundary we must look at most one maximal
# block past the boundary to find a block start.
_OVERRUN = 2 * BGZF_MAX_BLOCK_SIZE


def _candidate_positions(buf: np.ndarray) -> np.ndarray:
    """Vectorized scan: positions where the 4 fixed header bytes match."""
    if buf.size < BGZF_HEADER_SIZE:
        return np.empty(0, dtype=np.int64)
    m = (
        (buf[:-3] == 0x1F)
        & (buf[1:-2] == 0x8B)
        & (buf[2:-1] == 0x08)
        & (buf[3:] == 0x04)
    )
    return np.nonzero(m)[0].astype(np.int64)


def _chain_validate(
    data: bytes, pos: int, file_tail_known: bool, depth: int = CHAIN_DEPTH
) -> bool:
    """Follow BSIZE links from ``pos``; True iff ``depth`` links hold.

    ``file_tail_known`` — ``data`` extends to EOF, so running out of bytes
    mid-header is a *failure* unless we are exactly at EOF.
    """
    p = pos
    for _ in range(depth + 1):
        if p == len(data) and file_tail_known:
            return True  # clean EOF — the chain ran off the end of the file
        try:
            total = parse_block_header(data, p)
        except ValueError:
            # Not enough bytes to judge: optimistic accept when the buffer
            # simply ended (caller gave a bounded window, not the file).
            if p + BGZF_HEADER_SIZE > len(data) and not file_tail_known:
                return True
            return False
        p += total
        if p > len(data) and not file_tail_known:
            return True
    return True


class BgzfBlockGuesser:
    """Find the first true BGZF block at-or-after an arbitrary offset."""

    def __init__(self, fs: FileSystemWrapper, path: str):
        self.fs = fs
        self.path = path
        self.length = fs.get_file_length(path)

    def guess_block_start(self, offset: int) -> Optional[int]:
        """Absolute file offset of the first block starting at ``>= offset``,
        or None if none exists before EOF."""
        if offset >= self.length:
            return None
        window_len = min(_OVERRUN + BGZF_HEADER_SIZE, self.length - offset)
        data = self.fs.read_range(self.path, offset, window_len)
        tail_known = offset + window_len >= self.length
        arr = np.frombuffer(data, dtype=np.uint8)
        for cand in _candidate_positions(arr):
            if _chain_validate(data, int(cand), tail_known):
                return offset + int(cand)
        return None

    def blocks_in_split(self, start: int, end: int) -> List[BgzfBlock]:
        """All blocks whose *start* lies in ``[start, end)`` — the
        "first owner" rule of ``BgzfBlockSource`` (a block straddling
        ``end`` belongs to this split)."""
        first = self.guess_block_start(start)
        if first is None or first >= end:
            return []
        return _walk_blocks(self.fs, self.path, first, end, self.length)


def _walk_blocks(
    fs: FileSystemWrapper, path: str, first: int, end: int, file_length: int
) -> List[BgzfBlock]:
    """Walk the BSIZE chain from a known block start, collecting blocks
    that start before ``end``. Buffered: reads ahead in large chunks so
    walking is one range-read per ~8 MiB, not per block."""
    return _walk_blocks_collect(fs, path, first, end, file_length)[0]


def _walk_buffer(buf: bytes, stop: int) -> tuple[list, int]:
    """Walk complete blocks in ``buf`` whose start is ``< stop``.
    Returns ([(rel_pos, csize, usize), …], consumed_bytes). Native C walk
    when built; pure-Python header parse otherwise."""
    try:
        from disq_tpu.native import walk_bgzf_blocks_native

        rel, cs, us = walk_bgzf_blocks_native(buf, stop)
        if len(rel) == 0:
            return [], 0
        return (
            list(zip(rel.tolist(), cs.tolist(), us.tolist())),
            int(rel[-1]) + int(cs[-1]),
        )
    except ImportError:
        pass
    entries = []
    p = 0
    while p < stop:
        # Break (not raise) on any header that isn't complete in the
        # buffer — including an XLEN that runs past the end — so the
        # caller re-reads from p; malformed headers with all bytes
        # present still raise via parse_block_header.
        if p + 12 > len(buf):
            break
        xlen = struct.unpack_from("<H", buf, p + 10)[0]
        if p + 12 + xlen > len(buf):
            break
        total = parse_block_header(buf, p)
        if p + total > len(buf):
            break
        isize = struct.unpack_from("<I", buf, p + total - 4)[0]
        entries.append((p, total, isize))
        p += total
    return entries, p


def _walk_blocks_collect(
    fs: FileSystemWrapper, path: str, first: int, end: int, file_length: int,
    chunk: int = 8 * 1024 * 1024,
) -> tuple[List[BgzfBlock], bytes]:
    """As ``_walk_blocks``, but also returns the staged compressed bytes
    covering exactly ``[first, last_block.end)`` — so callers that go on
    to inflate don't re-read the range from storage.

    Each iteration stages a chunk from the current block start, walks all
    complete blocks in it in one native call, and re-reads from the first
    straddling block — so the staged parts concatenate contiguously."""
    blocks: List[BgzfBlock] = []
    parts: List[bytes] = []
    pos = first
    while pos < end and pos < file_length:
        # no further than the last wanted block can reach: an indexed
        # read's chunks are tens of blocks, not the staging chunk
        want = min(max(chunk, 2 * BGZF_MAX_BLOCK_SIZE),
                   end - pos + BGZF_MAX_BLOCK_SIZE, file_length - pos)
        buf = fs.read_range(path, pos, want)
        entries, consumed = _walk_buffer(buf, min(end - pos, len(buf)))
        if not entries:
            # A whole-buffer read with no complete block. If the read
            # came back short (a flaky remote can cut a body) the
            # failure is retryable: TruncatedReadError subclasses
            # ValueError (callers treating this as corrupt still catch
            # it) while the shard retrier classifies it transient. But
            # if every requested byte arrived and the buffer reaches
            # EOF, the FILE ends mid-block — deterministic at-rest
            # damage a re-read can never fix: raise it as corrupt so
            # the error policy (not the retry loop) owns it.
            if len(buf) == want and pos + len(buf) >= file_length:
                raise ValueError(
                    f"BGZF file ends mid-block at {pos} in {path}"
                )
            from disq_tpu.runtime.errors import TruncatedReadError

            raise TruncatedReadError(
                f"truncated BGZF block at {pos} in {path}"
            )
        for rel, cs, us in entries:
            blocks.append(BgzfBlock(pos=pos + rel, csize=cs, usize=us))
        parts.append(buf[:consumed])
        pos += consumed
    if not blocks:
        return [], b""
    return blocks, b"".join(parts)


def walk_blocks_salvage(
    fs: FileSystemWrapper, path: str, start: int, end: int, length: int,
    ctx, owned_until: int,
):
    """One-block-at-a-time walk used only after the batched chain walk
    (``_walk_blocks_collect``) raised on a malformed block header. Each
    corrupt span is policy-handled via ``ctx`` (a
    ``runtime.errors.ShardErrorContext`` — STRICT raises with the span's
    coordinates) and the walk re-syncs at the next chain-validated block
    start found by the guesser. Returns (blocks, data, gaps): ``data``
    is contiguous from ``start`` (corrupt spans included, so block
    offsets index it directly) and ``gaps`` lists the corrupt [lo, hi)
    spans. Spans at or past ``owned_until`` are handled silently — their
    owner counts them."""
    from disq_tpu.bgzf.block import make_virtual_offset
    from disq_tpu.runtime.errors import TruncatedReadError

    blocks: List[BgzfBlock] = []
    parts: List[bytes] = []
    gaps: List[tuple] = []
    guesser = BgzfBlockGuesser(fs, path)
    pos = start
    # This walk issues one small read per block: transient-fault retry
    # must be per READ, not per walk — re-running the whole walk would
    # never converge under a sustained fault rate. Each read is also
    # length-checked: a short range read (flaky remote) must be retried
    # as transient, never misclassified as at-rest corruption by the
    # header parse below.
    retry = ctx.retrier.call

    def read_exact(p, n):
        def attempt():
            b = fs.read_range(path, p, n)
            if len(b) < n:
                raise TruncatedReadError(
                    f"short read at {p} in {path}: {len(b)} < {n}")
            return b
        return retry(attempt, what="salvage_walk")

    while pos < end and pos < length:
        buf = read_exact(pos, min(BGZF_MAX_BLOCK_SIZE, length - pos))
        try:
            total = parse_block_header(buf, 0)
            if total > len(buf):
                raise ValueError(
                    f"BGZF file ends mid-block at {pos} in {path}")
            usize = struct.unpack_from("<I", buf, total - 4)[0]
        except ValueError as e:
            nxt = retry(guesser.guess_block_start, pos + 1,
                        what="salvage_resync")
            span_end = min(end, length)
            if nxt is not None and nxt < span_end:
                span_end = nxt
            # Assemble the FULL corrupt span before quarantining it: the
            # sidecar must hold the verbatim bytes, not just the first
            # staged 64 KiB.
            gap_raw = buf[: span_end - pos]
            if len(gap_raw) < span_end - pos:
                gap_raw += read_exact(
                    pos + len(gap_raw), span_end - pos - len(gap_raw))
            target = ctx.silent() if pos >= owned_until else ctx
            target.handle_corrupt_block(
                e, block_offset=pos,
                raw=bytes(gap_raw),
                virtual_offset=make_virtual_offset(pos, 0),
                kind="BGZF block header",
            )
            parts.append(gap_raw)
            gaps.append((pos, span_end))
            if nxt is None or nxt >= min(end, length):
                break
            pos = span_end
            continue
        blocks.append(BgzfBlock(pos=pos, csize=total, usize=usize))
        parts.append(buf[:total])
        pos += total
    return blocks, b"".join(parts), gaps


def find_block_table(
    fs: FileSystemWrapper, path: str, start: int = 0, end: Optional[int] = None
) -> List[BgzfBlock]:
    """Full (or range-bounded) block table of a BGZF file.

    From offset 0 no guessing is needed (a BGZF file begins with a block);
    from a nonzero offset the guesser finds the first boundary.
    """
    length = fs.get_file_length(path)
    if end is None:
        end = length
    if start == 0:
        if length == 0:
            return []
        return _walk_blocks(fs, path, 0, end, length)
    return BgzfBlockGuesser(fs, path).blocks_in_split(start, end)
