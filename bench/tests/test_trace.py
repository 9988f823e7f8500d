"""The trace reduction, on a trace recorded on one v5e chip
(``bench/tools/record_trace.py``) and on intervals built by hand."""

import os

import pytest

from bench.lib import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_and_overlap():
    u = trace.union([(3, 4), (0, 1), (0.5, 2), (5, 6)])
    assert u == [(0, 2), (3, 4), (5, 6)]
    assert trace.overlap(u, [(1, 3.5), (5.5, 10)]) == pytest.approx(2.0)


def _events():
    return {"devices": {"/device:TPU:0": {
        "op_s": {"fusion.1": 1.5, "copy.3": 0.5},
        "modules": [["jit_run", 1.0, 2.5], ["jit_run", 4.0, 4.5]]}},
        "spans": [["bench.window", 0.5, 5.0], ["bench.tick", 0.9, 2.6],
                  ["bench.tick", 3.9, 4.6], ["bench.read", 3.0, 3.2]]}


def test_reduced_by_hand():
    r = trace.Reduced(_events())
    assert r.window_s == pytest.approx(4.5)
    assert r.busy_s() == pytest.approx(2.0)
    assert r.busy_in("bench.tick")["/device:TPU:0"] == pytest.approx(2.0)
    b = r.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(1.5)]
    assert b["idle_gaps"][0] == ["bench.window", pytest.approx(1.5)]
    assert ["bench.read", pytest.approx(1.5)] not in b["idle_gaps"]


def test_recorded_chip_trace():
    r = trace.Reduced(trace.load(os.path.join(DATA, "trace_small.json.gz")))
    assert [d for d in r.devices if d.startswith("/device:TPU")]
    assert r.n_spans("bench.tick") == 2
    busy = r.busy_s()
    assert 0 < busy < r.window_s
    ticks = max(r.busy_in("bench.tick").values())
    assert 0 < ticks <= busy + 1e-9
    # the sleep between the two ticks is the longest idle gap, and it lies
    # in the window span, outside both ticks
    gap = r.breakdown()["idle_gaps"][0]
    assert gap[0] == "bench.window" and gap[1] > 0.04
