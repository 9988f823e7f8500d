"""Engine-wide telemetry (DESIGN.md §11): program spans in the profiler's
trace, named scopes in the compiled program, metrics, workload recording,
structured logging — and the headline design constraint that
instrumentation must NOT break the steady-state contracts: the sharded tick
stays zero-transfer / zero-retrace and the epoch-pinning serving semantics
hold with a profiler session, metrics and workload recording all on.

Spans are read back the way an operator reads them: captured with
``jax.profiler.trace`` and read from the trace's host plane with
``jax.profiler.ProfileData``."""

import contextlib
import glob
import gzip
import inspect
import json
import logging
import re
import sys
import threading
import time

import jax
import numpy as np
import pytest

import repro
from repro import obs
from repro.core import COUNT, Delta, Var, agg, query, schema, sum_of
from repro.data import DeltaBatchUpdate, from_numpy
from repro.obs import trace
from repro.obs.metrics import Counter, Gauge, Histogram, Registry
from repro.obs.workload import WorkloadRecorder, signature_of


def read_spans(logdir):
    """The program's spans in the newest trace under ``logdir``, read from
    its host plane: ``(name, start_ns, end_ns, {stat: value})``."""
    import glob
    import os

    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def make_schema():
    return schema(
        [("x1", "categorical", 3), ("x2", "key", 4), ("x3", "key", 5),
         ("x4", "categorical", 3), ("u", "continuous", 0)],
        [("R1", ["x1", "x2"]), ("R2", ["x2", "x3", "u"]),
         ("R3", ["x3", "x4"])])


def make_tables(seed=0):
    rng = np.random.default_rng(seed)
    return {"R1": {"x1": rng.integers(0, 3, 17), "x2": rng.integers(0, 4, 17)},
            "R2": {"x2": rng.integers(0, 4, 29), "x3": rng.integers(0, 5, 29),
                   "u": rng.normal(size=29).astype(np.float32)},
            "R3": {"x3": rng.integers(0, 5, 13), "x4": rng.integers(0, 3, 13)}}


QUERIES = [
    query("q_count", [], [COUNT]),
    query("q_g1", ["x1"], [COUNT, sum_of("u")]),
    query("q_delta", ["x4"], [agg(Var("u"), Delta("x1", "==", 1))]),
]


def r2_rows(rng, k):
    return {"x2": rng.integers(0, 4, k), "x3": rng.integers(0, 5, k),
            "u": rng.normal(size=k).astype(np.float32)}


@contextlib.contextmanager
def profiled(logdir, **options):
    """A profiler session; the list it yields holds the session's program
    spans (``read_spans``) once the block has ended."""
    spans = []
    with jax.profiler.trace(str(logdir), **options):
        yield spans
    spans += read_spans(str(logdir))


@pytest.fixture
def tracing(tmp_path):
    """A profiler session around the test body's block."""
    return lambda: profiled(tmp_path / "trace")


# ------------------------------------------------------------------- metrics

def test_histogram_percentiles_without_samples():
    h = Histogram("t", bounds=(10.0, 100.0, 1000.0))
    for v in (5, 5, 50, 50, 50, 500, 5000):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 7 and s["min"] == 5 and s["max"] == 5000
    assert s["mean"] == pytest.approx(sum((5, 5, 50, 50, 50, 500, 5000)) / 7)
    # p50 falls in the (10, 100] bucket; interpolation stays inside it
    assert 10 <= s["p50"] <= 100
    # p99 lands in the overflow bucket, clamped by the tracked max
    assert 1000 <= s["p99"] <= 5000
    assert s["p50"] <= s["p95"] <= s["p99"]


def test_histogram_degenerate_cases():
    h = Histogram("t")
    assert h.snapshot()["p99"] == 0.0          # empty
    h.observe(42.0)
    s = h.snapshot()                           # single sample: min==max clamp
    assert s["p50"] == pytest.approx(42.0)
    assert s["p99"] == pytest.approx(42.0)
    with pytest.raises(ValueError):
        Histogram("bad", bounds=(5.0, 1.0))


def test_counter_gauge_registry():
    r = Registry()
    c = r.counter("n")
    c.inc(); c.inc(2)
    assert c.value == 3
    g = r.gauge("hwm")
    g.set(2.0); g.max(5.0); g.max(1.0)
    assert g.value == 5.0
    assert r.counter("n") is c                 # same name -> same metric
    with pytest.raises(TypeError):
        r.gauge("n")                           # name/type conflict
    snap = r.snapshot()
    assert snap["n"] == 3 and snap["hwm"] == 5.0


def test_metrics_are_thread_safe():
    h = Histogram("t")
    c = Counter("c")

    def work():
        for _ in range(500):
            h.observe(7.0)
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert h.count == 2000 and c.value == 2000


# -------------------------------------------------------------------- tracing

def test_span_noop_when_disabled():
    """With no profiler session a span is the shared null object: no
    allocation, and nothing reaches the next session's trace."""
    import repro.core  # noqa: F401  (installs the profiler's annotation)

    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert obs.span("never.recorded", x=1) is trace._NULL
    assert obs.span("never.recorded") is obs.span("other")
    with obs.span("never.recorded", x=1):
        pass


def test_spans_nest_and_export_chrome(tmp_path):
    """Inside a session spans are ``repro.``-named host events carrying
    their args as stats, nested by time containment; the profiler writes
    them beside everything else, also as a Perfetto trace."""
    with profiled(tmp_path, create_perfetto_trace=True) as spans:
        with obs.span("outer", step=1, rel="R2"):
            with obs.span("inner"):
                time.sleep(0.001)
    assert sorted(n for n, *_ in spans) == ["repro.inner", "repro.outer"]
    outer = next(s for s in spans if s[0] == "repro.outer")
    inner = next(s for s in spans if s[0] == "repro.inner")
    assert outer[3] == {"step": 1, "rel": "R2"} and inner[3] == {}
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    assert inner[2] - inner[1] >= 1_000_000    # slept >= 1ms, in ns

    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "perfetto_trace.json.gz"))
    with gzip.open(path, "rt") as f:
        text = f.read()
    assert "repro.outer" in text and "repro.inner" in text


def test_span_prefix_is_added_once(tmp_path):
    """Only ``span`` adds the prefix: the program's names stay as written
    (``ivm.tick``) and read back as ``repro.ivm.tick``."""
    with profiled(tmp_path) as spans:
        with obs.span("ivm.tick", rel="R2"):
            pass
    assert [(n, a) for n, _, _, a in spans] == [("repro.ivm.tick",
                                                 {"rel": "R2"})]


# ------------------------------------------------------------------- workload

def test_query_signatures_render_structurally():
    sigs = {q.name: signature_of(q) for q in QUERIES}
    assert sigs["q_count"].dims == () and sigs["q_count"].aggs == ("1",)
    assert sigs["q_g1"].dims == ("x1",) and sigs["q_g1"].aggs == ("1", "u")
    # filters: advisor-facing rollup (normalized constants); matching
    # soundness lives in the per-agg renders, where the Delta factor rides
    # inline so it stays attached to its aggregate
    assert sigs["q_delta"].filters == ("x1==1.0",)
    assert sigs["q_delta"].aggs == ("1[x1==1.0]*u",)
    # stable, distinct keys
    keys = {s.key() for s in sigs.values()}
    assert len(keys) == 3
    assert sigs["q_g1"].key() == signature_of(QUERIES[1]).key()


def test_signature_canonicalization_commutes():
    """Routing equality (DESIGN.md §13): signatures are order-insensitive
    in group-by dims, aggregate order, and product term order, and
    normalize filter constants — semantically identical queries must not
    miss the router's cache on spelling."""
    from repro.core import Pow
    from repro.obs.workload import agg_renders

    a = query("qa", ["x1", "x4"], [COUNT, sum_of("u")])
    b = query("qb", ["x4", "x1"], [sum_of("u"), COUNT])   # permuted both
    assert signature_of(a).key() == signature_of(b).key()

    # term order within a product commutes
    c = query("qc", ["x4"], [agg(Var("u"), Delta("x1", "==", 1))])
    d = query("qd", ["x4"], [agg(Delta("x1", "==", 1), Var("u"))])
    assert signature_of(c).key() == signature_of(d).key()

    # filter constants normalize: int 5 == float 5.0 == np.float32(5)
    e = query("qe", [], [agg(Var("u"), Delta("x2", "<", 2))])
    f = query("qf", [], [agg(Var("u"), Delta("x2", "<", 2.0))])
    g = query("qg", [], [agg(Var("u"), Delta("x2", "<", np.float32(2)))])
    assert signature_of(e).key() == signature_of(f).key() \
        == signature_of(g).key()

    # but different structure stays distinct
    assert signature_of(a).key() != signature_of(c).key()
    assert signature_of(e).key() != \
        signature_of(query("qh", [], [agg(Var("u"),
                                          Delta("x2", "<", 3))])).key()
    assert signature_of(query("qi", [], [sum_of("u")])).key() != \
        signature_of(query("qj", [], [agg(Pow("u", 2))])).key()

    # agg_renders preserves query order (the router's column map) while
    # signature_of sorts
    k = query("qk", [], [sum_of("u"), COUNT])
    assert agg_renders(k) == ("u", "1")
    assert signature_of(k).aggs == ("1", "u")

    # a filter attached to one agg differs from the same filter on both
    m = query("qm", [], [agg(Var("u"), Delta("x1", "==", 1)), COUNT])
    n = query("qn", [], [agg(Var("u"), Delta("x1", "==", 1)),
                         agg(Delta("x1", "==", 1))])
    assert signature_of(m).key() != signature_of(n).key()
    assert signature_of(m).filters == signature_of(n).filters


def test_workload_recorder_bounded_and_aggregates(tmp_path):
    rec = WorkloadRecorder(capacity=4)
    sig = signature_of(QUERIES[0])
    for i in range(10):
        rec.record("read", "q_count", sig, "pinned_read", 100.0 + i, epoch=i)
    assert rec.n_recorded == 10 and rec.n_dropped == 6
    assert len(rec.records()) == 4
    by = rec.by_signature()
    e = by[sig.key()]
    assert e["n"] == 4 and e["hits"] == {"pinned_read": 4}
    assert e["views"] == ["q_count"]
    assert e["latency_us_mean"] == pytest.approx(107.5)

    path = tmp_path / "workload.json"
    payload = rec.export_json(str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk["n_recorded"] == payload["n_recorded"] == 10
    assert len(on_disk["records"]) == 4

    off = WorkloadRecorder(capacity=0)         # disabled: record is a no-op
    off.record("read", "q", sig, "pinned_read", 1.0)
    assert not off.enabled and off.n_recorded == 0


def test_structured_logger_rate_limits(caplog):
    log = obs.get_logger("repro.test_obs")
    with caplog.at_level(logging.WARNING, logger="repro.test_obs"):
        assert log.warning_every(60.0, "k", "lagging", lag=3)
        assert not log.warning_every(60.0, "k", "lagging", lag=4)
        assert log.warning_every(60.0, "k2", "other key passes")
    assert sum("lagging lag=3" in r.message for r in caplog.records) == 1
    assert not any("lag=4" in r.message for r in caplog.records)


# ------------------------------------------------- wiring: compile/IVM/serve

def test_spans_thread_through_engine(tracing):
    """One session exercising compile -> init -> tick -> serve leaves the
    full span taxonomy in the profiler's trace."""
    with tracing() as spans:
        db = repro.connect(make_schema(), tables=make_tables(),
                           config=repro.ExecutionConfig(block_size=8))
        v = db.views(QUERIES)
        v.run()
        live = db.views(QUERIES, maintain=True)
        live.run()
        rng = np.random.default_rng(3)
        live.apply(DeltaBatchUpdate().insert("R2", r2_rows(rng, 3)))
        srv = live.serve(max_pinned_epochs=4)
        srv.read("q_count")

    names = {n for n, *_ in spans}
    assert {"repro." + n for n in (
        "compile", "compile.roots", "compile.pushdown", "compile.group",
        "compile.ir", "compile.schedule", "compile.bind",
        "ivm.init", "ivm.apply", "ivm.validate", "ivm.tick",
        "ivm.publish", "serve.read")} <= names
    tick = next(s for s in spans if s[0] == "repro.ivm.tick")
    assert tick[3]["rel"] == "R2"


def test_application_spans(tracing):
    """The application layer's host work is spanned: the covar assembly
    and the ridge solver, with the model's width as ``p``."""
    from repro.data import datasets as D
    from repro.ml.covar import compute_covar
    from repro.ml.ridge import bgd

    ds = D.make("favorita", scale=0.005)
    with tracing() as spans:
        C, N, layout, _ = compute_covar(ds)
        bgd(C, N, layout, max_iters=5)
    names = [n for n, *_ in spans]
    assert "repro.ml.covar.assemble" in names
    (fit,) = [s for s in spans if s[0] == "repro.ml.ridge.bgd"]
    assert fit[3] == {"p": layout.p}


def test_scan_scopes_name_device_ops():
    """The compiled covar batch carries the plan's parts in its ops' op
    names: the relation each scan step reads, and the block body's
    gather, payload, partial sums and accumulation."""
    from repro.data import datasets as D
    from repro.ml.covar import covar_queries

    ds = D.make("favorita", scale=0.005)
    qs, _ = covar_queries(ds)
    text = repro.connect(ds).views(qs).lower().compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    scopes = {p for o in op_names for p in o.split("/")}
    assert "scan.Sales" in scopes and "outputs" in scopes
    assert {"gather", "payload", "partials", "accumulate"} <= scopes
    assert any(re.search(r"/scan\.Sales/.*/partials/", o) for o in op_names)


def test_autotune_span_and_delta_provenance(tmp_path, tracing):
    """Auto blocking resolves through autotune.tune spans, and explain()
    carries BOTH labeled resolutions (batch + delta) for maintained views —
    the delta lane no longer shadows the init full scan's."""
    cfg = repro.ExecutionConfig(
        block_size="auto", autotune_cache=str(tmp_path / "cache.json"))
    with tracing() as spans:
        db = repro.connect(make_schema(), tables=make_tables(), config=cfg)
        live = db.views(QUERIES, maintain=True)
        live.run()
        rng = np.random.default_rng(3)
        live.apply(DeltaBatchUpdate().insert("R2", r2_rows(rng, 3)))
    rep = live.explain()
    assert rep.autotune and rep.autotune_delta
    s = rep.summary()
    assert "autotune[batch]:" in s and "autotune[delta]:" in s
    names = {n for n, *_ in spans}
    assert {"repro.compile.autotune", "repro.autotune.tune",
            "repro.autotune.probe"} <= names


def test_server_stats_latency_lag_and_warning():
    db = repro.connect(make_schema(), tables=make_tables(),
                       config=repro.ExecutionConfig(block_size=8))
    live = db.views(QUERIES, maintain=True)
    srv = live.serve(max_pinned_epochs=8, warn_epoch_lag=1)
    rng = np.random.default_rng(5)

    def upd():
        return DeltaBatchUpdate().insert("R2", r2_rows(rng, 2))

    srv.read()
    with srv.snapshot() as snap:               # laggard pin
        assert snap.epoch_lag == 0
        srv.apply(upd())
        srv.apply(upd())
        assert snap.epoch_lag == 2             # head advanced past the pin
        st = srv.stats()
        assert st["epoch_lag"] == 2
        assert st["n_lag_warnings"] >= 1       # lag 2 > threshold 1
    assert srv.epoch_lag == 0                  # pin released
    st = srv.stats()
    assert st["read_us"]["count"] == 1 and st["read_us"]["p50"] > 0
    assert st["tick_us"]["count"] == 2         # the init full scan not counted
    assert st["pinned_epochs_hwm"] >= 1
    # summary renders the serving latency line
    s = live.explain().summary()
    assert "serve:" in s and "lag=" in s and "read_p50=" in s


def test_workload_records_every_path():
    """The recorder sees one signature per view through every hit path:
    batch scan, maintained full scan, epoch read, pinned serving read."""
    db = repro.connect(make_schema(), tables=make_tables(),
                       config=repro.ExecutionConfig(block_size=8))
    v = db.views(QUERIES)
    v.run()                                    # batch_scan
    live = db.views(QUERIES, maintain=True)
    live.run()                                 # full_scan
    live.run()                                 # epoch_read
    srv = live.serve()
    srv.read()                                 # pinned_read x all views
    srv.read("q_g1")                           # pinned_read x one view

    by = db.workload.by_signature()
    assert len(by) == len(QUERIES)
    for q in QUERIES:
        e = by[signature_of(q).key()]
        assert e["hits"]["batch_scan"] == 1
        assert e["hits"]["full_scan"] == 1
        assert e["hits"]["epoch_read"] == 1
        assert e["hits"]["pinned_read"] >= 1
        assert e["latency_us_mean"] > 0
    assert by[signature_of(QUERIES[1]).key()]["hits"]["pinned_read"] == 2
    # capacity 0 disables recording end to end
    db0 = repro.connect(make_schema(), tables=make_tables(),
                        config=repro.ExecutionConfig(block_size=8,
                                                     workload_capacity=0))
    db0.views(QUERIES).run()
    assert db0.workload.n_recorded == 0


def test_execution_config_validates_telemetry_knobs():
    with pytest.raises(ValueError):
        repro.ExecutionConfig(warn_epoch_lag=0)
    with pytest.raises(ValueError):
        repro.ExecutionConfig(workload_capacity=-1)
    with pytest.raises(ValueError):
        from repro.serve.views import ViewServer
        db = repro.connect(make_schema(), tables=make_tables())
        ViewServer(db.views(QUERIES, maintain=True).maintained,
                   warn_epoch_lag=0)


# ----------------------------------------- contracts with telemetry enabled

def test_sharded_steady_state_contract_with_telemetry(subproc):
    """Headline constraint: the sharded steady-state tick keeps the
    zero-transfer / zero-retrace contract with a profiler session, metrics,
    and the workload recorder ALL on — identical contract counters to the
    telemetry-off run in test_ivm_sharded.py."""
    subproc(inspect.getsource(read_spans) + """
import tempfile

import numpy as np
import jax

import repro
from repro import obs
from repro.core import COUNT, Delta, Var, agg, query, schema, sum_of
from repro.data import DeltaBatchUpdate, from_numpy
from repro.data import relations as relmod

S = schema(
    [("x1", "categorical", 3), ("x2", "key", 4), ("x3", "key", 5),
     ("x4", "categorical", 3), ("u", "continuous", 0)],
    [("R1", ["x1", "x2"]), ("R2", ["x2", "x3", "u"]), ("R3", ["x3", "x4"])])
rng = np.random.default_rng(7)
tables = {
    "R1": {"x1": rng.integers(0, 3, 17), "x2": rng.integers(0, 4, 17)},
    "R2": {"x2": rng.integers(0, 4, 29), "x3": rng.integers(0, 5, 29),
           "u": rng.normal(size=29).astype(np.float32)},
    "R3": {"x3": rng.integers(0, 5, 13), "x4": rng.integers(0, 3, 13)}}
QUERIES = [
    query("q_count", [], [COUNT]),
    query("q_g1", ["x1"], [COUNT, sum_of("u")]),
    query("q_delta", ["x4"], [agg(Var("u"), Delta("x1", "==", 1))]),
]

logdir = tempfile.mkdtemp()
jax.profiler.start_trace(logdir)     # telemetry ON for the whole run
mesh = jax.make_mesh((len(jax.devices()),), ("data",))
sharded = repro.connect(from_numpy(S, tables),
                        config=repro.ExecutionConfig(block_size=8, mesh=mesh))
vs = sharded.views(QUERIES, maintain=True)
vs.run()
mb = vs.maintained
srv = vs.serve(max_pinned_epochs=8, warn_epoch_lag=2)

def r2_rows(k):
    return {"x2": rng.integers(0, 4, k), "x3": rng.integers(0, 5, k),
            "u": rng.normal(size=k).astype(np.float32)}

def fixed_update():
    return (DeltaBatchUpdate().insert("R2", r2_rows(4))
            .delete("R2", rng.choice(20, 2, replace=False)))

for _ in range(3):                      # warm pad buckets and capacity
    srv.apply(fixed_update())
srv.read()                              # warm the read path
runners = len(mb._runners)
traces = mb.n_fold_traces + relmod.advance_trace_count()
with jax.transfer_guard("disallow"):    # the steady-state contract
    for _ in range(5):
        srv.apply(fixed_update())
        srv.read("q_count")             # telemetry-on serving read, no sync
assert mb.n_fold_traces + relmod.advance_trace_count() == traces
assert len(mb._runners) == runners == 1

# telemetry actually observed the steady-state work it rode along with
st = srv.stats()
assert st["tick_us"]["count"] >= 8 and st["tick_us"]["p50"] > 0
assert st["read_us"]["count"] >= 6
jax.profiler.stop_trace()
names = {n for n, *_ in read_spans(logdir)}
assert {"repro.ivm.apply", "repro.ivm.tick", "repro.ivm.publish",
        "repro.serve.read"} <= names
assert sharded.workload.n_recorded > 0
print("OK")
""", 4)


@pytest.mark.slow
def test_serving_epoch_consistent_under_updates_with_telemetry(tracing):
    """The concurrent-updater serving semantics (mirrors
    test_serve_views.py) hold with a profiler session, metrics and workload
    recording on: a pinned reader's epoch stays frozen while the writer
    publishes, and the contract counters match the telemetry-off run."""
    with tracing() as spans:
        db = repro.connect(make_schema(), tables=make_tables(),
                           config=repro.ExecutionConfig(block_size=8))
        live = db.views(QUERIES, maintain=True)
        srv = live.serve(max_pinned_epochs=8, warn_epoch_lag=4)
        rng = np.random.default_rng(9)
        updates = [DeltaBatchUpdate().insert("R2", r2_rows(rng, 3))
                   for _ in range(6)]
        errors = []
        with srv.snapshot() as snap:
            first = {n: np.asarray(v).copy()
                     for n, v in snap.results().items()}
            e0 = snap.epoch

            def updater():
                try:
                    for upd in updates:
                        srv.apply(upd)
                except Exception as exc:
                    errors.append(exc)

            t = threading.Thread(target=updater)
            t.start()
            for _ in range(6):          # re-extract, bypassing the cache
                got = srv.maintained.results(epoch=snap.epoch)
                for n in first:
                    np.testing.assert_allclose(
                        first[n], np.asarray(got[n]), rtol=1e-5, err_msg=n)
            t.join()
            assert not errors, errors
            assert srv.epoch == e0 + len(updates)
        st = srv.stats()
        assert st["n_updates"] == len(updates)
        assert st["n_rejected_updates"] == 0
        assert st["tick_us"]["count"] == len(updates)
    names = {n for n, *_ in spans}
    assert {"repro.ivm.apply", "repro.serve.read"} <= names


def test_spans_add_no_sync(tmp_path, monkeypatch):
    """The no-sync instrumentation rule (DESIGN.md §11), asserted by count:
    inside a profiler session, the same fixed ticks make as many host syncs
    (``jax.block_until_ready``, ``jax.device_get``, ``np.asarray`` of a
    device array) with the engine's spans written as with them muted, and
    none of those syncs is made from ``repro.obs``."""
    db = repro.connect(make_schema(), tables=make_tables(),
                       config=repro.ExecutionConfig(block_size=8))
    live = db.views(QUERIES, maintain=True)
    live.run()
    mb = live.maintained
    n_ticks = 6

    def ticks():
        # as many rows in as out: every tick runs at one relation size
        rng = np.random.default_rng(13)
        for _ in range(n_ticks):
            jax.block_until_ready(mb.apply(
                DeltaBatchUpdate().insert("R2", r2_rows(rng, 3))
                .delete("R2", rng.choice(20, 3, replace=False)))["q_count"])

    ticks()                                     # warm pad buckets + runners

    def from_obs():
        f = sys._getframe(2)
        while f is not None:
            if f.f_globals.get("__name__", "").startswith("repro.obs"):
                return True
            f = f.f_back
        return False

    syncs = []

    def counted(name, fn, device_only=False):
        def call(x, *args, **kwargs):
            if not device_only or isinstance(x, jax.Array):
                syncs.append((name, from_obs()))
            return fn(x, *args, **kwargs)
        return call

    monkeypatch.setattr(jax, "block_until_ready",
                        counted("block_until_ready", jax.block_until_ready))
    monkeypatch.setattr(jax, "device_get",
                        counted("device_get", jax.device_get))
    monkeypatch.setattr(np, "asarray",
                        counted("np.asarray", np.asarray, device_only=True))

    with profiled(tmp_path) as spans:
        live_spans = trace._is_enabled
        assert live_spans()
        monkeypatch.setattr(trace, "_is_enabled", trace._never)
        ticks()
        muted = syncs[:]
        monkeypatch.setattr(trace, "_is_enabled", live_spans)
        del syncs[:]
        ticks()
        written = syncs[:]
    # the spans were written in the second run only, one per tick
    assert [n for n, *_ in spans].count("repro.ivm.apply") == n_ticks
    # the count sees the ticks' own syncs, so it is live
    assert len(muted) >= n_ticks
    assert sorted(written) == sorted(muted)
    assert [s for s in written + muted if s[1]] == []
