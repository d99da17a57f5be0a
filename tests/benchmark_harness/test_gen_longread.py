"""The long-read generator: the same (records, seed, configuration) gives
the same records, and every seed offers the same work."""

import json
import os

import numpy as np
import pytest

from harness_util import REPO

from benchmark import gen, gen_longread, reference, reference_longread


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark", "configs", "ont30x.json")) as f:
        return json.load(f)


def _sorted(a):
    return np.sort(np.asarray(a)).tolist()


@pytest.mark.parametrize("n", [12, 61, 500, 2001])
def test_exactly_the_records_asked_for_and_all_17_columns(cfg, n):
    t = gen_longread.generate(n, 5, cfg)
    assert t.count == n
    cols = t.columns()
    assert set(cols) == set(gen.ALL_COLUMNS)
    for flat, off in gen_longread.RAGGED:
        assert len(cols[off]) == n + 1 and cols[off][0] == 0
        assert cols[off][-1] == len(cols[flat])
        assert (np.diff(cols[off]) >= 0).all()


def test_the_same_seed_gives_the_same_records(cfg):
    a, b = (gen_longread.generate(300, 11, cfg) for _ in range(2))
    for name, col in a.columns().items():
        assert np.array_equal(col, b.columns()[name]), name


@pytest.mark.parametrize("n", [61, 500, 2001])
def test_two_seeds_offer_the_same_work(cfg, n):
    a = gen_longread.generate(n, 3, cfg)
    b = gen_longread.generate(n, 2147483999 + 5, cfg)
    for off in ("seq_offsets", "cigar_offsets", "tag_offsets",
                "name_offsets"):
        assert _sorted(np.diff(getattr(a, off))) == _sorted(
            np.diff(getattr(b, off))), off
    # a read's kind, strand and op count go with its length
    key = lambda t: sorted(zip(  # noqa: E731
        (np.diff(t.seq_offsets)).tolist(), t.flag.tolist(),
        np.diff(t.cigar_offsets).tolist()))
    assert key(a) == key(b)
    assert reference.flagstat(a.flag) == reference.flagstat(b.flag)
    assert reference_longread.record_bytes(a) == \
        reference_longread.record_bytes(b)
    # and the seed moves what it may: order, places, bases, sites
    assert (a.pos != b.pos).any() and (a.seqs != b.seqs).any()
    assert (np.diff(a.seq_offsets) != np.diff(b.seq_offsets)).any()
    assert (a.cigars != b.cigars).any()


def test_the_lengths_are_the_log_normal_s_quantiles(cfg):
    n = 32001
    length = gen_longread.read_lengths(n, cfg)
    assert (np.diff(length) >= 0).all()
    lo, hi = cfg["read_length_clip"]
    assert length.min() == lo and length.max() == hi
    assert length.mean() == pytest.approx(cfg["read_length_mean"], rel=0.01)
    assert np.median(length) == pytest.approx(3908, rel=0.01)
    down = np.sort(length)[::-1]
    n50 = down[np.searchsorted(np.cumsum(down), down.sum() / 2)]
    assert n50 == pytest.approx(cfg["read_length_n50"], rel=0.02)
    # ~750 ops and ~12.8 KB a record, from the shape alone
    shp = gen_longread.shape(n, cfg)
    assert shp["n_ops"].mean() == pytest.approx(700, rel=0.1)
    assert shp["n_ops"].max() < 65536


def test_the_record_kinds_are_the_configuration_s(cfg):
    n = 6000
    t = gen_longread.generate(n, 9, cfg)
    fs = reference.flagstat(t.flag)
    assert fs["total"] - fs["mapped"] == n // cfg["unmapped_every"]
    assert fs["supplementary"] == n // cfg["supplementary_every"]
    assert fs["secondary"] == n // cfg["secondary_every"]
    assert fs["paired"] == 0 and (t.next_refid == -1).all()
    assert (t.tlen == 0).all() and (t.next_pos == -1).all()
    mapped = (t.flag & 0x4) == 0
    reverse = (t.flag & 0x10) != 0
    assert 0.45 < reverse[mapped].mean() < 0.55 and not reverse[~mapped].any()
    l_seq, n_ops = np.diff(t.seq_offsets), np.diff(t.cigar_offsets)
    secondary = (t.flag & 0x100) != 0
    assert (l_seq[secondary] == 0).all() and (n_ops[secondary] > 3).all()
    assert (n_ops[~mapped] == 0).all() and (t.refid[~mapped] == -1).all()
    assert (np.diff(t.tag_offsets)[~mapped] == 0).all()
    assert (np.diff(t.name_offsets) == 36).all()
    # a supplementary record is hard-clipped at both ends, the others
    # soft-clipped, and SEQ is as long as the CIGAR says
    first = t.cigars[t.cigar_offsets[:-1][mapped]] & 0xF
    last = t.cigars[t.cigar_offsets[1:][mapped] - 1] & 0xF
    supp = ((t.flag & 0x800) != 0)[mapped]
    assert (first[supp] == 5).all() and (last[supp] == 5).all()
    assert (first[~supp] == 4).all() and (last[~supp] == 4).all()
    query = (t.cigars >> 4).astype(np.int64) * np.isin(
        t.cigars & 0xF, (0, 1, 4))
    total = np.concatenate([[0], np.cumsum(query)])
    q_len = total[t.cigar_offsets[1:]] - total[t.cigar_offsets[:-1]]
    plain = mapped & ~secondary
    assert (q_len[plain] == l_seq[plain]).all()
    assert ((t.cigars >> 4) >= 1).all()
    # one indel event per indel_every aligned bases, 2 insertions in 5
    ops = t.cigars & 0xF
    events = np.isin(ops, (1, 2)).sum()
    aligned = (t.cigars >> 4)[np.isin(ops, (0, 1))].sum()
    assert aligned / events == pytest.approx(cfg["indel_every"], rel=0.05)
    assert (ops == 1).sum() / events == pytest.approx(0.4, abs=0.01)
    assert t.quals.min() >= cfg["qual_range"][0]
    assert t.quals.max() <= cfg["qual_range"][1]
    assert set(np.unique(t.seqs)) <= {1, 2, 4, 8}


def test_the_populated_span_gives_the_configuration_s_coverage(cfg):
    n = 6000
    t = gen_longread.generate(n, 5, cfg)
    span = gen_longread.populated_span(gen_longread.shape(n, cfg), cfg)
    placed = t.refid >= 0
    assert 100 <= t.pos[placed].min() and t.pos[placed].max() < 100 + span
    # at the cell's size the span follows the aligned bases (under a few
    # thousand records its floor, four of the longest read, holds)
    cell = gen_longread.shape(32001, cfg)
    covered = cell["aligned"].sum() / (
        gen_longread.populated_span(cell, cfg) * len(cfg["contigs"]))
    assert covered == pytest.approx(cfg["coverage"], rel=0.01)
    lengths = np.array([c["length"] for c in cfg["contigs"]])
    assert (t.pos[placed] + t.reflen[placed]
            < lengths[t.refid[placed]]).all()
    # long alignments land in the upper levels of reg2bin
    assert (t.bin[placed] < 4681).any() and (t.bin[~placed] == 4680).all()


def test_take_gathers_every_ragged_column(cfg):
    t = gen_longread.generate(200, 13, cfg)
    idx = np.array([199, 0, 17, 17, 64])
    s = t.take(idx)
    assert s.count == 5 and (s.pos == t.pos[idx]).all()
    for flat, off in gen_longread.RAGGED:
        for k, i in enumerate(idx):
            want = getattr(t, flat)[getattr(t, off)[i]: getattr(t, off)[i + 1]]
            got = getattr(s, flat)[getattr(s, off)[k]: getattr(s, off)[k + 1]]
            assert np.array_equal(got, want), (flat, i)
    head = t.take(np.arange(199))
    assert head.count == 199
    assert np.array_equal(head.quals, t.quals[: t.seq_offsets[199]])
