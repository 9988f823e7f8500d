"""Sharded IVM (DESIGN.md §6/§8): maintained views over a mesh must be
indistinguishable from the single-device path — same results under
deterministic delta sequences (allclose vs the single-device oracle), same
zero-host-transfer / bounded-retrace steady-state contract, interchangeable
checkpoints, and epoch-consistent serving under a concurrent updater.

Each test runs in a subprocess with a forced 4-device host mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``), on the xla and
pallas-interpret backends."""

import pytest

# Shared subprocess preamble: the 3-relation chain schema of the serving
# tests, a deterministic mixed update stream (inserts + deletes on the
# sharded fact R2 AND the replicated R1/R3), and a side-by-side sharded /
# local pair of maintained batches.
PREAMBLE = """
import numpy as np
import jax

import repro
from repro.core import COUNT, Delta, Var, agg, query, schema, sum_of
from repro.data import DeltaBatchUpdate, apply_delta, from_numpy
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
NAMES = [q.name for q in QUERIES]
mesh = jax.make_mesh((len(jax.devices()),), ("data",))

def r2_rows(k):
    return {"x2": rng.integers(0, 4, k), "x3": rng.integers(0, 5, k),
            "u": rng.normal(size=k).astype(np.float32)}

def update_stream(n2):
    # deterministic mixed stream; yields (update, new |R2|)
    out = []
    for i in range(6):
        upd = DeltaBatchUpdate()
        k = int(rng.integers(1, 7))
        upd.insert("R2", r2_rows(k))
        nd = int(rng.integers(1, 5))
        upd.delete("R2", rng.choice(n2, nd, replace=False))
        if i % 2:
            upd.insert("R1", {"x1": rng.integers(0, 3, 2),
                              "x2": rng.integers(0, 4, 2)})
        if i % 3 == 2:
            upd.delete("R3", np.array([i]))
        n2 += k - nd
        out.append(upd)
    return out, n2

def connect_pair(backend, interpret):
    cfg = repro.ExecutionConfig(block_size=8, backend=backend,
                                interpret=interpret)
    db = from_numpy(S, tables)
    local = repro.connect(db, config=cfg)
    sharded = repro.connect(db, config=cfg.replace(mesh=mesh))
    return local, sharded

def assert_close(a, b, msg):
    for n in NAMES:
        np.testing.assert_allclose(np.asarray(a[n]), np.asarray(b[n]),
                                   rtol=1e-3, atol=1e-3,
                                   err_msg=f"{msg} {n}")
"""

BACKENDS = [("xla", "None"), ("pallas", "True")]


@pytest.mark.parametrize("backend,interpret", BACKENDS,
                         ids=["xla", "pallas-interpret"])
def test_sharded_matches_local_oracle(subproc, backend, interpret):
    """Deterministic delta sequence: after every apply, the sharded batch's
    results AND its gathered relation contents equal the single-device
    oracle's; the final epoch equals a from-scratch recompute."""
    if backend == "pallas":
        pytest.importorskip("jax.experimental.pallas")
    subproc(PREAMBLE + f"""
local, sharded = connect_pair({backend!r}, {interpret})
vl = local.views(QUERIES, maintain=True)
vs = sharded.views(QUERIES, maintain=True)
assert vs.maintained.mesh is not None
assert_close(vs.run(), vl.run(), "init")
assert vs.maintained.shard_rel == "R2"   # largest relation by default

oracle = from_numpy(S, tables)
updates, _ = update_stream(29)
for i, upd in enumerate(updates):
    out_s, out_l = vs.apply(upd), vl.apply(upd)
    oracle = apply_delta(oracle, upd)
    assert_close(out_s, out_l, f"apply {{i}}")

# gathered relations restore the oracle row order exactly (gid contract)
for name in ("R1", "R2", "R3"):
    got, exp = vs.maintained.db.relation(name), oracle.relation(name)
    for a in exp.columns:
        np.testing.assert_array_equal(np.asarray(got.columns[a]),
                                      np.asarray(exp.columns[a]),
                                      err_msg=f"{{name}}.{{a}}")

# final epoch == from-scratch recompute on the post-update database
fresh = repro.connect(oracle, config=repro.ExecutionConfig(
    block_size=8, backend={backend!r}, interpret={interpret}))
assert_close(vs.results(), fresh.views(QUERIES).run(), "fresh")
print("OK")
""", 4)


def test_sharded_steady_state_no_transfers_no_retrace(subproc):
    """The sharded tentpole contract: after warmup, fixed-size update
    batches run under ``jax.transfer_guard("disallow")`` — zero implicit
    host transfers of relation columns — without growing the fold- or
    advance-trace counters, and the runner cache stays one entry per
    (relation, pad bucket)."""
    subproc(PREAMBLE + """
_, sharded = connect_pair("xla", None)
vs = sharded.views(QUERIES, maintain=True)
vs.run()
mb = vs.maintained

def fixed_update():
    return (DeltaBatchUpdate().insert("R2", r2_rows(4))
            .delete("R2", rng.choice(20, 2, replace=False)))

for _ in range(3):                      # warm pad buckets and capacity
    vs.apply(fixed_update())
runners = len(mb._runners)
traces = mb.n_fold_traces + relmod.advance_trace_count()
with jax.transfer_guard("disallow"):
    for _ in range(5):
        vs.apply(fixed_update())
assert mb.n_fold_traces + relmod.advance_trace_count() == traces
assert len(mb._runners) == runners == 1   # one cached shard_map tick
print("OK")
""", 4)


@pytest.mark.parametrize("backend,interpret", BACKENDS,
                         ids=["xla", "pallas-interpret"])
def test_sharded_snapshot_restore_roundtrip(subproc, backend, interpret, tmp_path):
    """Checkpoints are placement-free: a sharded epoch snapshot restores
    into a local batch and vice versa, allclose to the single-device
    oracle, and the restored sharded batch keeps maintaining."""
    if backend == "pallas":
        pytest.importorskip("jax.experimental.pallas")
    subproc(PREAMBLE + f"""
import tempfile
local, sharded = connect_pair({backend!r}, {interpret})
vl = local.views(QUERIES, maintain=True)
vs = sharded.views(QUERIES, maintain=True)
vl.run(); vs.run()
updates, _ = update_stream(29)
for upd in updates[:3]:
    vl.apply(upd); vs.apply(upd)

d_sharded, d_local = {str(tmp_path / 's')!r}, {str(tmp_path / 'l')!r}
vs.snapshot(d_sharded)
vl.snapshot(d_local)

# sharded -> local and local -> sharded
vl2 = local.views(QUERIES, maintain=True)
assert vl2.restore(d_sharded) == 3
assert_close(vl2.results(), vl.results(), "sharded->local")
vs2 = sharded.views(QUERIES, maintain=True)
assert vs2.restore(d_local) == 3
assert_close(vs2.results(), vl.results(), "local->sharded")

# the re-sharded batch keeps maintaining, still matching the oracle
for i, upd in enumerate(updates[3:]):
    assert_close(vs2.apply(upd), vl.apply(upd), f"post-restore {{i}}")
print("OK")
""", 4)


@pytest.mark.slow
def test_sharded_server_epoch_consistent_under_updates(subproc):
    """A sharded ViewServer under a concurrent updater: a pinned reader's
    epoch is frozen while updates publish, post-swap reads equal the
    from-scratch oracle, and stats report the shard topology."""
    subproc(PREAMBLE + """
import threading
_, sharded = connect_pair("xla", None)
vs = sharded.views(QUERIES, maintain=True)
srv = vs.serve(max_pinned_epochs=8)
updates, _ = update_stream(29)
oracle = from_numpy(S, tables)
errors = []
with srv.snapshot() as snap:
    first = {n: np.asarray(snap.results()[n]).copy() for n in NAMES}
    e0 = snap.epoch
    def updater():
        global oracle
        try:
            for upd in updates:
                srv.apply(upd)
        except Exception as exc:
            errors.append(exc)
    t = threading.Thread(target=updater)
    t.start()
    for _ in range(6):   # re-extract from the pinned epoch, bypassing cache
        assert_close(first, srv.maintained.results(epoch=snap.epoch),
                     "pinned")
    t.join()
    assert not errors, errors
    assert srv.epoch == e0 + len(updates)
    assert_close(first, snap.results(), "pinned-final")
for upd in updates:
    oracle = apply_delta(oracle, upd)
fresh = repro.connect(oracle, config=repro.ExecutionConfig(block_size=8))
assert_close(srv.read(), fresh.views(QUERIES).run(), "post-swap")
st = srv.stats()
assert st["n_updates"] == len(updates)
assert st["shard"]["n_devices"] == 4
assert st["shard"]["shard_rel"] == "R2"
assert st["shard"]["psums_per_tick"]["R2"] >= 1
print("OK")
""", 4)


def test_explain_reports_shard_topology(subproc):
    """Satellite: ``explain()`` on sharded runs carries the topology dict —
    device count, rows/shard, psum counts — for maintained AND batch mode
    (no bare ``sharded=True`` flag)."""
    subproc(PREAMBLE + """
_, sharded = connect_pair("xla", None)
vs = sharded.views(QUERIES, maintain=True)
vs.run()
vs.apply(DeltaBatchUpdate().insert("R2", r2_rows(3)))
rep = vs.explain()
t = rep.shard
assert t["n_devices"] == 4 and t["mesh_axis"] == "data"
assert t["shard_rel"] == "R2"
assert t["rows_per_shard"] == -(-t["rows"] // 4)
assert t["capacity_per_shard"] >= t["rows_per_shard"]
assert t["psums_per_tick"]["R2"] >= 1
s = rep.summary()
assert "devices=4" in s and "psums/tick" in s

vb = sharded.views(QUERIES)          # batch mode over the same mesh
vb.run()
tb = vb.explain().shard
assert tb["n_devices"] == 4 and tb["shard_rel"] == "R2"
assert tb["psums_per_run"] >= 1
assert "psums/run" in vb.explain().summary()
print("OK")
""", 4)


def test_engine_sharded_matches_local(subproc):
    subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import Engine, schema, query, COUNT, sum_of, agg, Pow
from repro.data import from_numpy
rng = np.random.default_rng(1)
S = schema([("k","key",16),("c","categorical",5),("u","continuous",0)],
           [("F",["k","u"]),("D",["k","c"])])
n = 1003
T = {"F": {"k": rng.integers(0,16,n), "u": rng.normal(size=n).astype(np.float32)},
     "D": {"k": np.arange(16), "c": rng.integers(0,5,16)}}
db = from_numpy(S, T)
eng = Engine(S, sizes=db.sizes())
batch = eng.compile([query("byc", ["c"], [COUNT, sum_of("u"), agg(Pow("u",2))])],
                    block_size=64)
local = batch(db)
mesh = jax.make_mesh((8,), ("data",))
shard = batch.run_sharded(db, mesh)
for k in local:
    assert np.allclose(local[k], shard[k], rtol=1e-4, atol=1e-4)
print("OK")
""", n_devices=8)


def test_engine_sharded_matches_local_1xN_mesh(subproc):
    """run_sharded on a (1, N) mesh (data axis second) must equal the local
    __call__ — covers the fused-scan schedule under shard_map + psum."""
    subproc("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import Engine, schema, query, COUNT, sum_of, agg, Pow
from repro.data import from_numpy
rng = np.random.default_rng(7)
S = schema([("k","key",16),("c","categorical",5),("u","continuous",0)],
           [("F",["k","u"]),("D",["k","c"])])
n = 517
T = {"F": {"k": rng.integers(0,16,n), "u": rng.normal(size=n).astype(np.float32)},
     "D": {"k": np.arange(16), "c": rng.integers(0,5,16)}}
db = from_numpy(S, T)
eng = Engine(S, sizes=db.sizes())
batch = eng.compile([query("byc", ["c"], [COUNT, sum_of("u"), agg(Pow("u",2))]),
                     query("tot", [], [COUNT, sum_of("u")])],
                    block_size=32)
local = batch(db)
mesh = jax.make_mesh((1, 4), ("model", "data"))
shard = batch.run_sharded(db, mesh, axis="data")
for k in local:
    assert np.allclose(local[k], shard[k], rtol=1e-4, atol=1e-4), k
print("OK")
""", n_devices=4)
