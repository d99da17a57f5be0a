"""The filter keeps a selection, not a copy (ISSUE 46): a resident
batch that keeps half of the records its blob holds, or more, shares
its source's record bytes under a pending order; one that keeps fewer
copies the kept bytes as before. Either form is the same batch to
every reader: the 17 columns, the writer's bytes, a spill, a concat,
and the operators that read the bytes through ``encode_source()``;
and a flag patched through a filtered child never reaches the
source's bytes, nor a sibling's."""

import pickle

import numpy as np
import pytest

from bam_oracle import (
    DEFAULT_REFS, make_bam_bytes, synth_paired_records, synth_records)
from disq_tpu.runtime.tracing import REGISTRY, reset_telemetry, spans
from test_alignment_ends import _host, _resident

FIXED = ("refid", "pos", "mapq", "bin", "flag", "next_refid", "next_pos",
         "tlen")
RAGGED = ("name_offsets", "names", "cigar_offsets", "cigars",
          "seq_offsets", "seqs", "quals", "tag_offsets", "tags")
SHARES = (0.1, 0.5, 0.9)
STATES = ("source_order", "permuted")
N = 80


@pytest.fixture(autouse=True)
def _clean_telemetry():
    reset_telemetry()
    yield
    reset_telemetry()


def _pair(state, records=None, seed=11):
    """``(resident batch, the host batch of the same records in the
    same order)``; ``permuted`` puts a pending order on the first."""
    if records is None:
        records = synth_records(N - 3, seed=seed, unmapped_tail=3)
        assert len(records) == N
    batch, host = _resident(records), _host(records)
    if state == "permuted":
        order = np.random.default_rng(seed).permutation(len(records))
        batch, host = batch.permuted(order), host.take(order)
    return batch, host


def _mask(n, share, seed=5):
    """Exactly ``round(share * n)`` records kept, anywhere."""
    return np.random.default_rng(seed).permutation(n) < round(share * n)


def _host_order(host):
    """``host`` in stable coordinate order, unmapped last."""
    from disq_tpu.sort.coordinate import coordinate_keys

    return host.take(np.argsort(
        coordinate_keys(host.refid, host.pos), kind="stable"))


def _form(kept, held):
    return "deferred" if 2 * kept >= held else "copied"


def _compacts():
    return [s["labels"] for s in spans()
            if s["name"] == "columnar.batch.compact"]


def _bytes_booked(how):
    return REGISTRY.counter("columnar.batch.compact_bytes").value(how=how)


def _record_bytes(host):
    from disq_tpu.bam.codec import encode_records_with_offsets

    blob, offs = encode_records_with_offsets(host)
    return bytes(blob), offs


def _assert_same_records(got, want):
    """The 17 columns, dtype included, and the device's own 8."""
    assert got.count == want.count
    on_device = got.device_columns()
    for c in FIXED + RAGGED:
        col = np.asarray(getattr(got, c))
        assert col.dtype == getattr(want, c).dtype, c
        np.testing.assert_array_equal(col, getattr(want, c), c)
    for c in FIXED:
        np.testing.assert_array_equal(
            np.asarray(on_device[c]), getattr(want, c), c)


def _assert_writer_bytes(got, want):
    """``encoded_slice`` over every cut of a 4-shard write, the empty
    cut included, equals the column encoder's bytes; nothing parsed."""
    parses = REGISTRY.counter("columnar.batch.materializations").total()
    want_blob, want_offs = _record_bytes(want)
    cuts = np.linspace(0, got.count, 5).astype(int)
    for lo, hi in [*zip(cuts, cuts[1:]), (0, got.count), (3, 3)]:
        blob, offs = got.encoded_slice(lo, hi)
        assert blob.tobytes() == want_blob[want_offs[lo]: want_offs[hi]]
        np.testing.assert_array_equal(
            offs, want_offs[lo: hi + 1] - want_offs[lo])
    assert got._ragged_rb is None
    assert REGISTRY.counter(
        "columnar.batch.materializations").total() == parses


@pytest.mark.parametrize("state", STATES)
@pytest.mark.parametrize("share", SHARES)
def test_either_form_is_the_host_filters_batch(share, state):
    source, host = _pair(state)
    mask = _mask(N, share)
    k = int(mask.sum())
    held = source.encode_source()[0].tobytes()
    out = source.filter(mask)
    want = host.filter(mask)
    how = _form(k, N)
    assert how == ("copied" if share < 0.5 else "deferred")
    kept_bytes = len(_record_bytes(want)[0])
    label, = _compacts()
    assert label == {"records": N, "kept": k, "how": how,
                     "bytes": kept_bytes if how == "copied" else 0}
    assert _bytes_booked(how) == kept_bytes
    assert _bytes_booked("copied" if how == "deferred" else "deferred") == 0
    assert out.device_backed and out.count == k
    blob, offsets, order = out.encode_source()
    if how == "deferred":
        assert blob is source.encode_source()[0]
        assert out._offsets is source._offsets
        assert out._span_cache is source._span_cache
        assert len(order) == k < len(offsets) - 1 == N
        assert not out._blob_owned and not source._blob_owned
    else:
        assert order is None and len(offsets) - 1 == k
        assert len(blob) == kept_bytes and out._blob_owned
    _assert_writer_bytes(out, want)
    ends = out.alignment_ends()
    assert ends.dtype == np.int32
    np.testing.assert_array_equal(ends, want.alignment_ends())
    np.testing.assert_array_equal(
        out.reference_lengths(), want.reference_lengths())
    _assert_same_records(out, want)
    assert source.encode_source()[0].tobytes() == held


@pytest.mark.parametrize("sort_between", [False, True],
                         ids=["as_filtered", "sorted_after"])
@pytest.mark.parametrize("state", STATES)
@pytest.mark.parametrize("share", SHARES)
def test_a_flag_patched_through_a_child_stays_in_the_child(
        share, state, sort_between):
    """``or_flags`` on a filtered child, with and without a sort
    between, leaves the source's bytes and a sibling child's as they
    were; and a patch of the source afterwards does not reach the
    child."""
    from disq_tpu.sort.coordinate import coordinate_sort_batch

    source, host = _pair(state)
    mask = _mask(N, share)
    child, want = source.filter(mask), host.filter(mask)
    sibling_mask = _mask(N, 0.9, seed=6)
    sibling = source.filter(sibling_mask)
    assert sibling.encode_source()[0] is source.encode_source()[0]
    if sort_between:
        order = child.sort_permutation()
        child = coordinate_sort_batch(child, keep_resident=True)
        want = want.take(order)
    held = source.encode_source()[0].tobytes()
    sibling_held = sibling.encoded_slice(0, sibling.count)[0].tobytes()
    dup = np.arange(child.count) % 3 == 0
    shared = share >= 0.5 or sort_between
    reset_telemetry()
    child.or_flags(dup, 0x400)
    patch, = [s["labels"] for s in spans()
              if s["name"] == "columnar.batch.patch"]
    assert patch == {"records": int(dup.sum()), "copied": int(shared),
                     "bytes": len(child.encode_source()[0])}
    assert child._blob_owned
    assert source.encode_source()[0].tobytes() == held
    assert sibling.encoded_slice(
        0, sibling.count)[0].tobytes() == sibling_held
    want.flag = want.flag | np.where(dup, 0x400, 0).astype(np.uint16)
    _assert_writer_bytes(child, want)
    _assert_same_records(child, want)
    _assert_same_records(sibling, host.filter(sibling_mask))
    # the other way round: the source is not the only holder either
    marked = child.encoded_slice(0, child.count)[0].tobytes()
    source.or_flags(np.ones(N, bool), 0x200)
    assert child.encoded_slice(0, child.count)[0].tobytes() == marked
    assert sibling.encoded_slice(
        0, sibling.count)[0].tobytes() == sibling_held
    np.testing.assert_array_equal(source.flag, host.flag | 0x200)


@pytest.mark.parametrize("joined", ["by_the_source_first", "by_the_child"])
def test_a_join_of_shared_parts_is_the_childs_own(joined):
    """A concat's parts, shared un-joined: the child that joins them
    holds a fresh array and patches it without another copy; a join
    the source made first is shared, and copied before a patch."""
    from disq_tpu.bam.columnar import ReadBatch
    from disq_tpu.runtime.columnar import ColumnarBatch

    halves = [synth_records(40, seed=50), synth_records(40, seed=51)]
    source = ColumnarBatch.concat([_resident(r) for r in halves])
    host = ReadBatch.concat([_host(r) for r in halves])
    parts = list(source._blob_parts)
    held = [p.tobytes() for p in parts]
    if joined == "by_the_source_first":
        source.encode_source()
        assert source._blob_owned
    mask = _mask(N, 0.9)
    child, sibling = source.filter(mask), source.filter(mask)
    assert not source._blob_owned
    child = child.permuted(child.sort_permutation())
    want = _host_order(host.filter(mask))
    dup = np.arange(child.count) % 2 == 0
    reset_telemetry()
    child.or_flags(dup, 0x400)
    patch, = [s["labels"] for s in spans()
              if s["name"] == "columnar.batch.patch"]
    assert patch["copied"] == int(joined == "by_the_source_first")
    assert [p.tobytes() for p in parts] == held
    want.flag = want.flag | np.where(dup, 0x400, 0).astype(np.uint16)
    _assert_writer_bytes(child, want)
    _assert_same_records(child, want)
    _assert_same_records(sibling, host.filter(mask))
    _assert_same_records(source, host)


def test_children_made_while_the_source_joins_hold_the_bytes():
    """More threads than cores filter, sort and patch children of one
    un-joined concat while another joins it: a child takes the blob
    or the parts, never neither, and a patch stays in its child."""
    import sys
    import threading

    from disq_tpu.bam.columnar import ReadBatch
    from disq_tpu.runtime.columnar import ColumnarBatch

    halves = [synth_records(40, seed=60), synth_records(40, seed=61)]
    host = ReadBatch.concat([_host(r) for r in halves])
    mask = _mask(N, 0.9)
    want = host.filter(mask)
    dup = np.arange(want.count) % 2 == 0
    want.flag = want.flag | np.where(dup, 0x400, 0).astype(np.uint16)
    want_bytes = _record_bytes(want)[0]
    failures, rounds = [], 6

    def child_of(source, go):
        go.wait(10)
        try:
            child = source.filter(mask)
            child.or_flags(dup, 0x400)
            got = child.encoded_slice(0, child.count)[0].tobytes()
            if got != want_bytes:
                failures.append("a child's bytes differ")
        except Exception as e:  # noqa: BLE001 - reported below
            failures.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for _ in range(rounds):
            source = ColumnarBatch.concat([_resident(r) for r in halves])
            go = threading.Event()
            threads = [threading.Thread(target=child_of, args=(source, go))
                       for _ in range(24)]
            threads.append(threading.Thread(
                target=lambda: (go.wait(10), source.encode_source())))
            for t in threads:
                t.start()
            go.set()
            for t in threads:
                t.join(60)
            assert not any(t.is_alive() for t in threads)
            assert not failures, failures[:3]
            _assert_same_records(source, host)
    finally:
        sys.setswitchinterval(interval)


@pytest.mark.parametrize("state", STATES)
@pytest.mark.parametrize("second, how", [
    (0.9, "deferred"), (0.56, "deferred"), (0.52, "copied"),
    (0.1, "copied")])
def test_a_filter_of_a_deferred_batch(second, how, state):
    """The share that decides is of the records the BLOB holds: a
    chain of filters that each keep a little over half of what they
    are given never pins more than twice its answer."""
    source, host = _pair(state)
    first = _mask(N, 0.9)
    mid, want = source.filter(first), host.filter(first)
    assert mid.encode_source()[2] is not None and mid.count == 72
    again = _mask(72, second, seed=8)
    k = int(again.sum())
    assert _form(k, N) == how
    assert _form(k, 72) == ("copied" if second == 0.1 else "deferred")
    reset_telemetry()
    out, want = mid.filter(again), want.filter(again)
    label, = _compacts()
    assert (label["records"], label["kept"], label["how"]) == (72, k, how)
    blob, offsets, order = out.encode_source()
    if how == "deferred":
        assert blob is source.encode_source()[0] and len(order) == k
        assert len(offsets) - 1 == N
    else:
        assert order is None and len(offsets) - 1 == k
    _assert_writer_bytes(out, want)
    _assert_same_records(out, want)
    # ... and a sort of it, and a filter of that
    order = out.sort_permutation()
    out, want = out.permuted(order), want.take(order)
    last = _mask(k, 0.8, seed=9)
    out, want = out.filter(last), want.filter(last)
    _assert_writer_bytes(out, want)
    _assert_same_records(out, want)


@pytest.mark.parametrize("state", STATES)
@pytest.mark.parametrize("share", SHARES + (1.0,))
def test_a_spill_holds_the_kept_records_in_order(share, state):
    """Pickle round trip: the bytes spilled are the batch's own
    records in logical order, not its source's blob; the restored
    batch is device-backed with no pending order."""
    source, host = _pair(state)
    mask = _mask(N, share)
    out, want = source.filter(mask), host.filter(mask)
    fn, (blob, offsets, _n_ref) = out.__reduce__()
    want_blob, want_offs = _record_bytes(want)
    assert blob.tobytes() == want_blob
    np.testing.assert_array_equal(offsets, want_offs)
    back = pickle.loads(pickle.dumps(out))
    assert back.device_backed and back.encode_source()[2] is None
    _assert_writer_bytes(back, want)
    _assert_same_records(back, want)
    _assert_same_records(out, want)


@pytest.mark.parametrize("cached", [False, True],
                         ids=["spans_not_held", "spans_held"])
@pytest.mark.parametrize("shapes", [
    ("deferred", "copied", "permuted", "plain"),
    ("permuted", "deferred"), ("copied", "deferred", "deferred"),
    ("plain", "permuted")], ids="+".join)
def test_concat_gives_logical_order(shapes, cached):
    """Shards under a pending order (a filter's selection, a sort's
    permutation) beside shards in source order: the concat's columns,
    bytes and reference spans are the records' in logical order."""
    from disq_tpu.bam.columnar import ReadBatch
    from disq_tpu.runtime.columnar import ColumnarBatch

    parts, wants = [], []
    for i, shape in enumerate(shapes):
        records = synth_records(40 + 6 * i, seed=20 + i)
        batch, host = _pair(
            "permuted" if shape == "permuted" else "source_order",
            records, seed=30 + i)
        if shape in ("deferred", "copied"):
            mask = _mask(len(records), 0.8 if shape == "deferred" else 0.2)
            batch, host = batch.filter(mask), host.filter(mask)
        assert (batch.encode_source()[2] is not None) == (
            shape in ("deferred", "permuted"))
        if cached:
            batch.alignment_ends()
        parts.append(batch)
        wants.append(host)
    want = ReadBatch.concat(wants)
    out = ColumnarBatch.concat(parts)
    assert out.device_backed and out.encode_source()[2] is None
    _assert_writer_bytes(out, want)
    np.testing.assert_array_equal(
        out.alignment_ends(), want.alignment_ends())
    assert out.ends_source == ("cached" if cached else "cigar")
    _assert_same_records(out, want)


# -- the readers of ``encode_source()`` under a selection --------------------


def markdup_keys(out, want):
    from disq_tpu.ops.markdup import _key_columns

    got, resident = _key_columns(out)
    ref, _ = _key_columns(want)
    assert resident and got.keys() == ref.keys()
    for name in ref:
        np.testing.assert_array_equal(got[name], ref[name], name)
    keys, = [s["labels"] for s in spans() if s["name"] == "ops.markdup.keys"]
    assert keys["records"] == out.count


def markdup_marks(out, want):
    from disq_tpu.ops.markdup import markdup_batch

    _, got = markdup_batch(out)
    _, ref = markdup_batch(want)
    np.testing.assert_array_equal(got.dup_mask, ref.dup_mask)
    assert got.stats() == ref.stats()
    np.testing.assert_array_equal(out.flag, want.flag)


def subsample_filter(out, want):
    from disq_tpu.ops.rfilter import apply_read_filter, parse_read_filter

    rf = parse_read_filter("-s 7.6")
    got, ref = apply_read_filter(out, rf), apply_read_filter(want, rf)
    assert 0 < ref.count < want.count
    _assert_same_records(got, ref)


def pileup_bounds(out, want):
    from disq_tpu.ops.pileup import _span_bounds

    for a, b in zip(_span_bounds(out), _span_bounds(want)):
        np.testing.assert_array_equal(a, b)


def read_groups(out, want):
    from disq_tpu.ops.rgstats import read_group_ids

    (ids, names), (ref_ids, ref_names) = (
        read_group_ids(out), read_group_ids(want))
    assert names == ref_names and len(names) > 1
    np.testing.assert_array_equal(ids, ref_ids)


@pytest.mark.parametrize("state", STATES)
@pytest.mark.parametrize("share", SHARES)
@pytest.mark.parametrize("reader", [
    markdup_keys, markdup_marks, subsample_filter, pileup_bounds,
    read_groups, _assert_writer_bytes], ids=lambda f: f.__name__)
def test_a_reader_of_the_bytes_indexes_through_the_order(
        reader, share, state):
    records = synth_paired_records(N // 2, seed=13)
    source, host = _pair(state, records)
    mask = _mask(len(records), share)
    out = source.filter(mask)
    order = out.encode_source()[2]
    assert (order is not None) == (share >= 0.5)
    reader(out, host.filter(mask))


# -- the chain, written ------------------------------------------------------


@pytest.fixture(scope="module")
def paired_bam(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("deferred") / "paired.bam")
    with open(path, "wb") as f:
        f.write(make_bam_bytes(
            DEFAULT_REFS, synth_paired_records(120, seed=41), blocksize=900))
    return path


@pytest.mark.parametrize("split", [100000, 4000],
                         ids=["one_split", "splits_joined_late"])
@pytest.mark.parametrize("spec, how", [("-q 15", "deferred"),
                                       ("-q 55", "copied")])
def test_the_chain_writes_the_host_writers_files(
        paired_bam, tmp_path, spec, how, split):
    """read -> ``ds.pipeline(filter, sort, markdup)`` -> BAM + BAI +
    SBI: the resident chain's three files are the host chain's, the
    filter keeping ~0.9 and ~0.1; no record is parsed, and the
    dataset that was read keeps its bytes."""
    from disq_tpu.api import BaiWriteOption, ReadsStorage, SbiWriteOption

    def chain(resident, out):
        st = ReadsStorage.make_default().split_size(split).num_shards(3)
        ds = (st.resident_decode() if resident else st).read(paired_bam)
        marked, stats = ds.pipeline(("filter", spec), "sort", "markdup")
        st.write(marked, str(out), BaiWriteOption.ENABLE,
                 SbiWriteOption.ENABLE)
        files = tuple(open(str(out) + ext, "rb").read()
                      for ext in ("", ".bai", ".sbi"))
        return ds, marked, stats, files

    parses = REGISTRY.counter("columnar.batch.materializations")
    ds, marked, stats, got = chain(True, tmp_path / "resident.bam")
    assert parses.total() == 0 and marked.reads.device_backed
    label, = _compacts()
    assert label["how"] == how
    # who joins the splits' parts owns the join: the sorted batch, for
    # markdup's keys, which then patches in place. The filter and the
    # sort join nothing; a blob that came in one piece is copied first
    names = [s["name"] for s in spans()]
    patch, = [s["labels"] for s in spans()
              if s["name"] == "columnar.batch.patch"]
    if split == 4000 and how == "deferred":
        assert names.count("columnar.batch.join") == 1
        assert names.index("sort.gather") < names.index(
            "columnar.batch.join") < names.index("ops.markdup.keys")
        assert patch["copied"] == 0
    elif how == "deferred":
        assert "columnar.batch.join" not in names and patch["copied"] == 1
    share = label["kept"] / label["records"]
    assert (0.8 < share < 1) if how == "deferred" else (0 < share < 0.2)
    assert _bytes_booked(how) > 0
    _, _, host_stats, want = chain(False, tmp_path / "host.bam")
    assert stats == host_stats
    if how == "deferred":
        assert stats["markdup"]["duplicates"] > 0
    for ext, a, b in zip(("bam", "bai", "sbi"), got, want):
        assert a == b, f"the .{ext} differs"
    # the source dataset was not written through: no duplicate bit,
    # the records as they were read
    assert parses.total() == 0
    _assert_same_records(
        ds.reads, ReadsStorage.make_default().read(paired_bam).reads)
