"""Device time by program scope and what the program's spans explain, on
events built by hand, on the optimized HLO of a small scan, and on traces
recorded on one v5e chip (``bench/tools/record_trace.py``,
``bench/tools/record_scoped_trace.py``)."""

import os

import pytest

from bench.lib import scopes, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV = "/device:TPU:0"


def _events(spans):
    """One device busy over [1, 2] and [4, 5] of a window [0, 6], and
    three ops: a loop that contains the other two."""
    return {"devices": {DEV: {
        "op_s": {"while.1": 1.8, "fusion.2": 1.2, "broadcast.3": 0.6},
        "modules": [["jit_run", 1.0, 2.0], ["jit_run", 4.0, 5.0]]}},
        "spans": [["bench.window", 0.0, 6.0], ["bench.job", 0.5, 5.9]]
        + spans}


OPS = {"while.1": ("while", "scan.R"),
       "fusion.2": ("fusion", "scan.R/accumulate"),
       "broadcast.3": ("broadcast", "scan.R/partials")}


def test_scope_of_drops_what_jax_adds():
    assert scopes.scope_of("jit(<lambda>)/scan.Sales/while/body/closed_call/"
                           "partials/scatter-add") == "scan.Sales/partials"
    assert scopes.scope_of("jit(<lambda>)/outputs/jit(_take)/gather") \
        == "outputs"
    assert scopes.scope_of("x") == ""


HLO = """\
HloModule jit_run, is_scheduled=true

%fused (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(run)/scan.R/while/body/accumulate/add"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0:T(128)}) parameter(0)
  %broadcast.3 = f32[8]{0:T(128)} broadcast(%c), dimensions={}, metadata={op_name="jit(run)/scan.R/while/body/partials/broadcast_in_dim" stack_frame_id=2}
  ROOT %fusion.2 = f32[8]{0} fusion(%broadcast.3), kind=kLoop, calls=%fused
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %while.1 = (s32[], f32[8]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(run)/scan.R/while"}
  ROOT %get-tuple-element.4 = f32[8]{0} get-tuple-element(%while.1), index=1
}
"""


def test_hlo_ops_opcodes_and_scopes():
    ops = scopes.hlo_ops(HLO)
    assert ops["while.1"] == ("while", "scan.R")
    assert ops["broadcast.3"] == ("broadcast", "scan.R/partials")
    # a fusion without an op name takes its root's
    assert ops["fusion.2"] == ("fusion", "scan.R/accumulate")
    assert ops["get-tuple-element.4"] == ("get-tuple-element", "")
    assert ops["x"] == ("parameter", "")


def test_scopes_leave_out_loops_and_stay_under_busy():
    r = trace.Reduced(_events([]))
    per = scopes.scope_s(r, OPS)[DEV]
    assert per == {"scan.R/accumulate": pytest.approx(1.2),
                   "scan.R/partials": pytest.approx(0.6)}
    assert sum(per.values()) <= r.busy_s() + 1e-12
    assert scopes.share_under(r, OPS, "partials") == pytest.approx(30.0)
    assert scopes.share_under(r, OPS, "gather") is None
    # an op the program does not hold falls under no scope
    assert scopes.by_scope({"copy.9": 0.1}, OPS) == {"": 0.1}


def test_gap_inside_a_program_span_is_explained():
    """The idle gap [2, 4] lies inside ``repro.ml.ridge.bgd``: it counts in
    the application's host time, not in the unspanned idle time, and the
    breakdown names it by that span."""
    r = trace.Reduced(_events([["repro.ml.ridge.bgd", 2.0, 4.0]]))
    assert scopes.app_host_s(r) == pytest.approx(2.0)
    # idle: [0, 1], [2, 4], [5, 6]; unspanned: [0, 1] and [5, 6]
    assert scopes.idle_unspanned(r) == pytest.approx(100.0 * 2.0 / 6.0)
    gaps = r.breakdown()["idle_gaps"]
    assert gaps[0] == ["repro.ml.ridge.bgd", pytest.approx(2.0)]


def test_gap_under_the_job_alone_is_unspanned():
    """With the program's span elsewhere, the gap [2, 4] is under
    ``bench.job`` alone: it counts in the unspanned idle time."""
    r = trace.Reduced(_events([["repro.ml.covar.assemble", 5.0, 5.5]]))
    assert scopes.app_host_s(r) == pytest.approx(0.5)
    assert scopes.idle_unspanned(r) == pytest.approx(100.0 * 3.5 / 6.0)
    assert r.breakdown()["idle_gaps"][0] == ["bench.job",
                                             pytest.approx(2.0)]
    # without any program span there is nothing to read
    r = trace.Reduced(_events([]))
    assert scopes.app_host_s(r) is None and scopes.idle_unspanned(r) is None


def test_program_spans_join_the_benchmarks():
    ev = scopes.with_spans(_events([]), [["repro.ml.ridge.bgd", 2.0, 4.0]])
    assert ["repro.ml.ridge.bgd", 2.0, 4.0] in ev["spans"]
    assert [s[1] for s in ev["spans"]] == sorted(s[1] for s in ev["spans"])


def test_small_recorded_trace_reads_as_before():
    """The benchmark's numbers from ``trace_small.json.gz`` hold with the
    program's spans added to it (it has none): window, busy time, spans,
    busy time in spans, and the breakdown."""
    ev = trace.load(os.path.join(DATA, "trace_small.json.gz"))
    r0 = trace.Reduced(ev)
    r1 = trace.Reduced(scopes.with_spans(ev, []))
    assert r1.window_s == r0.window_s and r1.busy_s() == r0.busy_s()
    assert r1.n_spans("bench.tick") == r0.n_spans("bench.tick") == 2
    assert r1.busy_in("bench.tick") == r0.busy_in("bench.tick")
    assert r1.breakdown() == r0.breakdown()
    assert scopes.idle_unspanned(r1) is None


def test_scoped_chip_trace():
    """A trace from one chip with a program span and a ``partials`` scope:
    the host work under ``repro.ml.ridge.bgd`` is the application's, the
    sleep outside it is unspanned idle time, and the scan's ops sum to no
    more than busy time with the partial sums a share of it."""
    from bench.tools.record_scoped_trace import SPANNED_S, UNSPANNED_S

    ev = trace.load(os.path.join(DATA, "trace_scoped.json.gz"))
    ops = {k: tuple(v) for k, v in ev["ops"].items()}
    r = trace.Reduced(ev)
    assert [d for d in r.devices if d.startswith("/device:TPU")]
    assert r.n_spans("bench.job") == 2
    assert r.n_spans("repro.ml.ridge.bgd") == 2
    assert SPANNED_S <= scopes.app_host_s(r) < SPANNED_S + 0.01
    unspanned = scopes.idle_unspanned(r) / 100.0 * r.window_s
    assert 2 * UNSPANNED_S <= unspanned
    assert unspanned + 2 * SPANNED_S <= r.window_s - r.busy_s() + 1e-6
    busy = r.busy_s()
    for per in scopes.scope_s(r, ops).values():
        assert 0 < sum(per.values()) <= busy + 1e-9
    assert 0 < scopes.share_under(r, ops, "partials") <= 100
    assert any(o == "while" for o, _ in ops.values())
    gaps = r.breakdown()["idle_gaps"]
    assert gaps[0][0] == "repro.ml.ridge.bgd"


def test_events_in_clips_ranks_and_skips_lines():
    from types import SimpleNamespace as NS

    def ev(name, s, e):
        return NS(name=name, start_ns=int(s * 1e9),
                  duration_ns=int((e - s) * 1e9))

    pd = NS(planes=[
        NS(name="/host:CPU", lines=[
            NS(name="python3", events=[ev("np.asarray", 1.0, 3.0)]),
            NS(name="pjrt-tasks", events=[ev("D2H", 1.5, 1.6),
                                          ev("D2H", 2.5, 2.7)])]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=[ev("fusion.1", 0.0, 1.2)])])])
    assert scopes.events_in(pd, 1.0, 2.0) == [
        ("python3", "np.asarray", pytest.approx(1.0), 1),
        ("pjrt-tasks", "D2H", pytest.approx(0.1), 1)]
    assert scopes.events_in(pd, 0.0, 3.0, skip_line="python") == [
        ("pjrt-tasks", "D2H", pytest.approx(0.3), 2)]
    assert scopes.events_in(pd, 1.0, 2.0, "/device:") == [
        ("XLA Ops", "fusion.1", pytest.approx(0.2), 1)]
