"""The trace reduction against a small recorded trace (a two-pass traced
``wgs_read`` window on a TPU v5e, recorded by an earlier session's chip
run; op names cut to 70 characters) and on hand-made intervals."""

import json
import os

import pytest

from harness_util import DATA

from benchmark.trace import reduce as tr


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_read.json")) as f:
        return json.load(f)


def test_interval_arithmetic():
    assert tr.union([(5, 7), (1, 3), (2, 4), (9, 9)]) == [[1, 4], [5, 7]]
    assert tr.intersect([[1, 4], [5, 7]], [[3, 6]]) == [[3, 4], [5, 6]]
    assert tr.subtract([[0, 10]], [[1, 4], [5, 7]]) == [
        [0, 1], [4, 5], [7, 10]]
    assert tr.subtract([[0, 10]], []) == [[0, 10]]
    assert tr.subtract([[2, 3]], [[0, 10]]) == []
    assert tr.total([[0, 1], [4, 6]]) == 3
    assert tr.op_key("jit_step(123)", "%fusion.3 = f32[8]{0} fusion(%p)") \
        == "jit_step/fusion.3"


def test_recorded_window_busy_and_idle(recorded):
    r = tr.reduce_trace(recorded, ["read"])
    assert r["window_s"] == pytest.approx(7.190639213)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["busy_s"] == pytest.approx(6.6218, abs=1e-3)
    idle = dict(r["idle_gaps"])
    # busy and the labelled gaps add up to the window
    assert r["busy_s"] + sum(idle.values()) == pytest.approx(r["window_s"])
    assert idle["read"] > 10 * idle["none"]


def test_recorded_ops_are_keyed_by_module_and_op(recorded):
    ops = tr.reduce_trace(recorded)["ops"]
    assert ops["jit_call/call.1"] == pytest.approx(6.5720, abs=1e-3)
    assert ops["jit_call"] >= ops["jit_call/call.1"]
    assert ops["jit__parse_columns"] == pytest.approx(0.0491, abs=1e-3)
    assert not any("(" in k for k in ops)       # no hashes, no shapes
    # an op's seconds never pass its module's
    for key, seconds in ops.items():
        if "/" in key:
            assert seconds <= ops[key.split("/")[0]] + 1e-9


def test_window_clips_what_lies_outside_it():
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_f(1)", 0, 100],
                                               ["jit_f(1)", 200, 100]]},
            {"name": "XLA Ops", "events": [["%a = x", 0, 100],
                                           ["%a = x", 200, 50],
                                           ["%b = y", 250, 50]]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [["%a = x", 100, 100]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [["bench.window", 50, 300],
                                        ["step", 100, 50]]}]}]}
    r = tr.reduce_trace(trace, ["step"])
    assert r["window_s"] == pytest.approx(300e-9)
    # device 0 is busy 50..100 and 200..300, device 1 100..200
    assert r["busy_s"] == pytest.approx((150 + 100) / 2 * 1e-9)
    assert r["ops"]["jit_f/a"] == pytest.approx(100e-9)
    assert r["ops"]["?/a"] == pytest.approx(100e-9)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"step": 50e-9, "none": 100e-9})


def test_a_trace_without_the_window_annotation_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_trace({"planes": []})
