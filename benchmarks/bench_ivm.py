"""Incremental view maintenance vs full recomputation (DESIGN.md §8).

Maintains the ridge covar batch under a streaming 1% update to the fact
table (equal-count inserts + deletes, so sizes — and jit cache entries —
stay fixed) and compares the warm per-tick cost against rerunning the full
compiled batch over the current database.  The delta path scans only the
delta tuples (all covar queries root at the fact), so the gap is the
engine's |update| vs |database| work ratio — the IVM promise.

Also measures the device-residency win: a steady-state tick is one cached
jit call (epoch-versioned resident state), versus the pre-resident
baseline that round-tripped the stored fact relation through host numpy
every tick.  Results land in ``JSON_PAYLOAD`` (retrace counts included),
which ``benchmarks/run.py`` serializes to ``BENCH_ivm.json`` so CI records
the perf trajectory.

Sharded rows (DESIGN.md §6): the same ridge workload over 2- and 4-device
meshes — steady-state tick under ``jax.transfer_guard("disallow")`` plus
sharded serving read latency.  On an accelerator they run in this process
on its devices; on the CPU host each mesh size runs in a child with that
many forced host devices (``common.on_devices``).  The contract fields
(retraces, allclose vs a local recompute) ride along so the perf gate can
hold them hard while wall times gate loose.

    PYTHONPATH=src python -m benchmarks.bench_ivm
"""

from __future__ import annotations

import sys

import numpy as np

from benchmarks.common import BENCH_SCALE, devices_main, on_devices, row, timeit
from repro.data import datasets as D
from repro.data import relations as relmod
from repro.data.relations import DeltaBatchUpdate
from repro.ml.cubes import StreamingCube, cube_name
from repro.ml.online import OnlineRidge

SHARDED_DEVICE_COUNTS = (2, 4)

#: machine-readable results of the last ``main()`` run (benchmarks/run.py
#: writes this out as BENCH_ivm.json)
JSON_PAYLOAD: dict = {}


def _fact_update(ds, rng, frac: float) -> DeltaBatchUpdate:
    """Insert/delete ``frac`` of the fact rows each (sampled with repl.)."""
    fact = ds.tables[ds.fact]
    n = len(next(iter(fact.values())))
    k = max(int(n * frac), 1)
    pick = rng.integers(0, n, k)
    ins = {a: np.asarray(c)[pick] for a, c in fact.items()}
    return (DeltaBatchUpdate().insert(ds.fact, ins)
            .delete(ds.fact, rng.choice(n, k, replace=False)))


def sharded_main(ndev: int) -> dict:
    """Sharded-IVM measurement body over ``ndev`` devices
    (``common.on_devices`` places it); measures the steady-state sharded
    tick under ``transfer_guard("disallow")`` — the zero-host-transfer
    contract — and the sharded serving read latency."""
    import jax

    from repro.api import ExecutionConfig

    mesh = jax.make_mesh((ndev,), ("data",), devices=jax.devices()[:ndev])
    ds = D.make("favorita", scale=BENCH_SCALE)
    rng = np.random.default_rng(11)
    # shard the fact explicitly: at small BENCH_SCALE the dense
    # date×store Transactions table out-sizes Sales, and the default
    # largest-relation pick would leave the updated fact replicated
    olr = OnlineRidge(ds, config=ExecutionConfig(
        block_size=4096, mesh=mesh, shard_rel=ds.fact))
    olr.fit()
    mb = olr.maintained
    upd = _fact_update(ds, rng, 0.01)        # fixed sizes -> one pad bucket

    timeit(lambda: mb.apply(upd))            # warm pad buckets and capacity
    traces0 = mb.n_fold_traces + relmod.advance_trace_count()
    with jax.transfer_guard("disallow"):     # steady-state contract
        t_tick = timeit(lambda: mb.apply(upd))
    retraces = mb.n_fold_traces + relmod.advance_trace_count() - traces0

    srv = olr.view.serve()
    t_read = timeit(lambda: srv.read())

    # numeric agreement: the maintained sharded epoch vs a from-scratch
    # single-device recompute over the gathered post-update relations
    check = OnlineRidge(ds, config=ExecutionConfig(block_size=4096))
    check.fit(db=mb.db)
    a, b = mb.results(), check.maintained.results()
    allclose = all(np.allclose(np.asarray(a[k]), np.asarray(b[k]),
                               rtol=1e-3, atol=1e-3) for k in a)
    topo = mb.shard_topology()
    return {
        "n_devices": ndev,
        "tick_us_sharded": t_tick * 1e6,
        "read_us_sharded": t_read * 1e6,
        "steady_state_retraces": int(retraces),
        "allclose_local": bool(allclose),
        "rows_per_shard": int(topo["rows_per_shard"]),
        "psums_per_tick_fact": int(topo["psums_per_tick"][ds.fact]),
    }


def main():
    import jax

    ds = D.make("favorita", scale=BENCH_SCALE)
    rng = np.random.default_rng(11)
    lines = []

    olr = OnlineRidge(ds)
    olr.fit()
    mb = olr.maintained
    n_fact = ds.db.relation(ds.fact).n_rows
    upd = _fact_update(ds, rng, 0.01)

    t_delta = timeit(lambda: mb.apply(upd))
    t_full = timeit(lambda: mb.batch(mb.db))
    dp = mb.delta_program(ds.fact)
    lines.append(row(
        "ivm/ridge_delta_1pct", t_delta,
        f"rows={upd.updates[ds.fact].n_rows};delta_scans={dp.n_scans}"))
    lines.append(row(
        "ivm/ridge_full_recompute", t_full,
        f"rows={n_fact};scans={mb.batch.stats.n_scan_steps};"
        f"speedup={t_full / t_delta:.1f}x"))

    # device residency: steady-state resident tick (one cached jit call,
    # zero relation-column host transfers) vs the pre-resident baseline's
    # per-tick host round-trip of the stored fact relation (delete-mask +
    # concat on host numpy, then back to device)
    def resident_tick():
        mb.apply(_fact_update(ds, rng, 0.01))

    def host_roundtrip_tick():
        mb.apply(_fact_update(ds, rng, 0.01))
        r = mb.db.relation(ds.fact)
        cols = {a: np.asarray(c) for a, c in r.columns.items()}  # dev->host
        jax.block_until_ready(jax.device_put(cols))              # host->dev

    t_tick = timeit(resident_tick)           # timeit warms before measuring
    traces0 = mb.n_fold_traces + relmod.advance_trace_count()
    timeit(resident_tick)
    retraces = mb.n_fold_traces + relmod.advance_trace_count() - traces0
    t_tick_host = timeit(host_roundtrip_tick)
    lines.append(row(
        "ivm/tick_resident", t_tick,
        f"epoch={mb.epoch};steady_retraces={retraces}"))
    lines.append(row(
        "ivm/tick_host_roundtrip", t_tick_host,
        f"overhead={t_tick_host / t_tick:.2f}x"))

    # streaming cube: every 2^k cell live under the same update stream
    dims = ["promo", "city", "stype"]
    cube = StreamingCube(ds, dims, measures=["units"])
    upd_c = _fact_update(ds, rng, 0.01)
    t_cube = timeit(lambda: cube.update(upd_c))
    lines.append(row(
        "ivm/cube_delta_1pct", t_cube,
        f"cells={2 ** len(dims)};finest={cube_name(dims)}"))

    # sharded IVM: steady-state tick + serving read per mesh size
    sharded = {}
    for ndev in SHARDED_DEVICE_COUNTS:
        r = on_devices("benchmarks.bench_ivm", ndev, sharded_main)
        sharded[f"ndev{ndev}"] = r
        lines.append(row(
            f"ivm/sharded_tick_{ndev}dev", r["tick_us_sharded"] / 1e6,
            f"devices={ndev};retraces={r['steady_state_retraces']};"
            f"allclose={r['allclose_local']}"))
        lines.append(row(
            f"ivm/sharded_read_{ndev}dev", r["read_us_sharded"] / 1e6,
            f"devices={ndev};rows_per_shard={r['rows_per_shard']};"
            f"psums={r['psums_per_tick_fact']}"))

    JSON_PAYLOAD.clear()
    JSON_PAYLOAD.update({
        "dataset": "favorita", "scale": BENCH_SCALE,
        "fact_rows": int(n_fact),
        "update_rows": int(upd.updates[ds.fact].n_rows),
        "delta_scans": int(dp.n_scans),
        "tick_us_resident": t_tick * 1e6,
        "tick_us_host_roundtrip": t_tick_host * 1e6,
        "host_roundtrip_overhead_x": t_tick_host / t_tick,
        "steady_state_retraces": int(retraces),
        "full_recompute_us": t_full * 1e6,
        "delta_us": t_delta * 1e6,
        "speedup_delta_vs_full_x": t_full / t_delta,
        "cube_tick_us": t_cube * 1e6,
        "sharded": sharded,
    })
    return lines


if __name__ == "__main__":
    if not devices_main(sys.argv, sharded_main):
        print("\n".join(main()))
