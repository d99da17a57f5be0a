"""The benchmark's array copy of the chain's plain reference
(``benchmark/reference_chain.py``) against the record-by-record one the
program's own tests use (``tests/reference_markdup.py``), on the same
seeded records, so that the two copies cannot drift.  (ISSUE 32 put this
test into ``test_reference.py``; a file the benchmark already has is
not this PR's to edit, so it is a file of its own.)"""

import dataclasses
import json
import os

import numpy as np
import pytest

from harness_util import REPO

from benchmark import gen, reference, reference_chain
from tests import reference_markdup
from tests.bam_oracle import decode_all, encode_record


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "wgs30x_markdup.json")) as f:
        return json.load(f)


def _parsed(truth):
    return decode_all(reference.encode_records(truth))


@pytest.mark.parametrize("n,seed,view", [
    (3000, 2147484003, "-q 20"), (901, 7, "-q 20"),
    (1500, 11, "-f 0x1 -F 0x400 -q 30")])
def test_the_two_copies_of_the_reference_agree(cfg, n, seed, view):
    truth = gen.generate(n, seed, cfg)
    records = _parsed(truth)
    want = reference_markdup.chain(records, view)
    got = reference_chain.chain(truth, view)
    assert (got.examined, got.duplicates) == (
        want["examined"], want["duplicates"])
    assert 0 < got.duplicates < got.examined < got.kept.count < n
    np.testing.assert_array_equal(got.marked, want["marked"])
    np.testing.assert_array_equal(got.kept.flag, want["flags"])
    # the same records in the same order, byte for byte
    assert reference.encode_records(got.kept) == b"".join(
        encode_record(dataclasses.replace(records[i], flag=flag))
        for i, flag in zip(want["kept"], want["flags"]))
    # and the pieces one by one, on every record of the input
    np.testing.assert_array_equal(
        reference_chain.view_mask(truth, view),
        [reference_markdup.view_keeps(
            r, reference_markdup.parse_view(view)) for r in records])
    np.testing.assert_array_equal(
        reference_chain.scores(truth),
        [reference_markdup.score(r) for r in records])
    keys = [reference_markdup.duplicate_key(r) for r in records]
    np.testing.assert_array_equal(
        reference_chain.unclipped_five_prime(truth), [k[1] for k in keys])
    assert [k[2] for k in keys] == ((truth.flag & 0x10) != 0).tolist()


def test_equal_scores_go_to_the_earlier_record_in_both(cfg):
    """Every quality alike, so every group is a tie."""
    truth = gen.generate(1200, 13, cfg)
    truth.qual_mat[:] = 30
    want = reference_markdup.chain(_parsed(truth), "-q 20")
    got = reference_chain.chain(truth, "-q 20")
    np.testing.assert_array_equal(got.marked, want["marked"])
    assert got.duplicates == want["duplicates"] > 0
    # the first of a run of one key stays: no marked record comes
    # before an unmarked examined one of its key
    t = got.kept
    key = list(zip(t.refid.tolist(),
                   reference_chain.unclipped_five_prime(t).tolist(),
                   ((t.flag & 0x10) != 0).tolist()))
    seen = set()
    for i in range(t.count):
        if (t.flag[i] & 0x904) or t.refid[i] < 0:
            assert not got.marked[i]
            continue
        assert got.marked[i] == (key[i] in seen)
        seen.add(key[i])
