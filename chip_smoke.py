#!/usr/bin/env python3
"""Smoke run of the aggregate engine's main path on a TPU.

Drives the entry points a user calls — ``repro.connect`` →
``Database.views`` → ``run``, ``apply`` on maintained views, and
``Database.query`` — over the Favorita star schema at the paper's ``Sales``
row count (Table 1: 125M rows), and checks every answer against a numpy
float64 reference written here, which shares no code with the engine.

    python chip_smoke.py                    # one chip, every phase
    python chip_smoke.py --chips 4          # only the sharded path, 4 chips
    python chip_smoke.py --fact-rows 2000000 --seed 1

Phases (one process): device check, data, covar batch on the ``xla``
backend, the same batch on ``pallas`` with ``interpret=False``, maintained
views under three update ticks, routed queries.  Each phase prints its
check, its wall and compile seconds (smoke timings, not metrics) and the
device's peak bytes.  Any failed check raises; the last line of a passing
run is one JSON object naming the device.  Without a TPU it exits non-zero
before any work.  The phase functions run on whatever devices JAX has, so
``tests/test_chip_smoke.py`` drives them on the CPU.
"""

from __future__ import annotations

import argparse
import contextlib
import copy
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: Favorita ``Sales`` rows in the paper's Table 1
PAPER_SALES_ROWS = 125_000_000
#: ``datasets.make_favorita`` makes this many fact rows per unit of scale
ROWS_PER_SCALE = 60_000
#: each maintenance tick inserts and deletes this share of ``Sales``
UPDATE_FRAC = 0.001
N_TICKS = 3

#: JAX's monitoring events for lowering to MLIR and compiling it (tracing
#: is left out: its events nest, one per inner ``jit``)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CheckFailed(AssertionError):
    """An engine answer disagreed with the numpy reference."""


# --------------------------------------------------------------- reporting


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes() -> str:
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    if any(s is None for s in stats):
        return "n/a"
    return ",".join(str(s.get("peak_bytes_in_use", "n/a")) for s in stats)


@contextlib.contextmanager
def phase(name: str):
    """Time a phase: wall seconds, and the seconds JAX spent lowering and
    compiling inside it."""
    import jax

    compile_s = [0.0]

    def on_event(event, secs, **_):
        if event in _COMPILE_EVENTS:
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    wall = time.perf_counter() - t0
    log(f"[{name}] smoke timing (not a metric): wall {wall}s, of which "
        f"lower+compile {compile_s[0]}s; peak_bytes_in_use {peak_bytes()}")


# ---------------------------------------------------------------- reference


def f32_sum_tol(levels: int, n_rows: int, block: int) -> float:
    """Error bound, relative to the sum of |terms|, of an f32 aggregate.

    Every accumulator is f32 (``COUNT`` passes 2^24 at deployment size).
    A view entry is summed by at most ``block`` adds inside a scan block
    and ``ceil(n_rows / block)`` carry adds across blocks, each rounding by
    at most 2^-24 of the running sum, which is bounded by the sum of |terms|.
    Views feed views for ``levels`` levels of the join tree; each level adds
    its own bound.  Payload terms are products of up to three f32 factors
    (4 more roundings)."""
    per_level = block + math.ceil(max(n_rows, 1) / block)
    return 2.0 ** -24 * (levels * per_level + 4)


def _mixed_radix(cols, attrs, domains) -> np.ndarray:
    size = int(np.prod([domains[a] for a in attrs]))
    idx = np.zeros(len(cols[attrs[0]]),
                   np.int32 if size < 2**31 else np.int64)
    for a in attrs:
        idx = idx * domains[a] + cols[a]
    return idx


class Reference:
    """Star-join aggregates in numpy float64, by foreign-key gathers and
    ``np.bincount``.

    Fact rows are first summed per cell: a combination of the fact's
    discrete attributes the checks read (its dimension keys and categorical
    features).  Every other attribute is a function of the cell, so later
    sums run over cells rather than rows, and an update adds and subtracts
    its rows' cell sums (:meth:`updated`).  Each dimension joins through a
    dense lookup on its key; cells with no match in a dimension weigh
    nothing, as in the natural join."""

    def __init__(self, ds, fact_cols):
        schema, fact = ds.schema, ds.fact
        self.schema = schema
        dims = [r for r in schema.relations if r != fact]
        have = set(fact_cols)
        keys = {d: [a for a in schema.relation(d).attrs if a in have]
                for d in dims}
        self.grid = sorted({a for ks in keys.values() for a in ks}
                           | {c for c in ds.features_cat if c in have})
        self.dom = {a: schema.domain(a) for a in self.grid}
        self.measures = [a for a in (*ds.features_cont, ds.label) if a in have]
        self.shape = shape = tuple(self.dom[a] for a in self.grid)
        n_cells = int(np.prod(shape))
        self.vals = {a: c.astype(np.int32) for a, c in zip(
            self.grid, np.unravel_index(np.arange(n_cells), shape))}
        #: grid axes each attribute is a function of
        self.axes_of = {a: (i,) for i, a in enumerate(self.grid)}
        ok = np.ones(n_cells, bool)
        for d in dims:
            tab = ds.tables[d]
            k = keys[d]
            kdom = {a: schema.domain(a) for a in k}
            tkey = _mixed_radix(tab, k, kdom)
            if len(np.unique(tkey)) != len(tkey):
                raise ValueError(f"{d}: key {k} is not unique")
            pos = np.full(int(np.prod(list(kdom.values()))), -1, np.int64)
            pos[tkey] = np.arange(len(tkey))
            p = pos[_mixed_radix(self.vals, k, kdom)]
            ok &= p >= 0
            for a, col in tab.items():
                if a not in k:
                    self.axes_of[a] = tuple(self.grid.index(x) for x in k)
                    col = np.asarray(col)[p]
                    self.vals[a] = (col if schema.attr(a).is_discrete
                                    else col.astype(np.float64))
        self.ok = ok.astype(np.float64)
        # per-cell row count, sums of each measure and of each product of
        # two, and the same over absolute values (keyed ("abs", ...))
        self.n = np.zeros(n_cells)
        self.sums = {}
        self._add_rows(fact_cols, 1.0)

    def _add_rows(self, cols, sign: float) -> None:
        size = len(self.n)
        cell = _mixed_radix(cols, self.grid, self.dom)
        self.n += sign * np.bincount(cell, minlength=size)
        for i, m in enumerate(self.measures):
            x = np.asarray(cols[m], np.float64)
            terms = {(m,): x, ("abs", m): np.abs(x)}
            for m2 in self.measures[i:]:
                xy = x * np.asarray(cols[m2], np.float64)
                terms[(m, m2)] = xy
                if m2 != m:         # a square is its own absolute value
                    terms[("abs", m, m2)] = np.abs(xy)
            for key, w in terms.items():
                self.sums.setdefault(key, np.zeros(size))
                self.sums[key] += sign * np.bincount(cell, w, size)
        for m in self.measures:
            self.sums[("abs", m, m)] = self.sums[(m, m)]
        self._moments = {}

    def updated(self, inserted, deleted) -> "Reference":
        """The reference after deleting the fact rows ``deleted`` and
        inserting ``inserted`` (column dicts)."""
        new = copy.copy(self)
        new.n = self.n.copy()
        new.sums = {k: v.copy() for k, v in self.sums.items()}
        new._add_rows(inserted, 1.0)
        new._add_rows(deleted, -1.0)
        return new

    def moment(self, f: str, g: str = "1", absolute: bool = False):
        """Per-cell sum over rows of ``f·g`` (``"1"`` is the constant)."""
        key = (f, g, absolute)
        if key not in self._moments:
            self._moments[key] = self._moment(f, g, absolute) * self.ok
        return self._moments[key]

    def _moment(self, f, g, absolute):
        tag = ("abs",) if absolute else ()
        fm, gm = f in self.measures, g in self.measures
        if fm and gm:
            return self.sums[tag + tuple(sorted((f, g),
                                                key=self.measures.index))]
        if gm:
            f, g, fm = g, f, True
        v = self.sums[tag + (f,)] if fm else self.n
        for a in (f, g):
            if a != "1" and a not in self.measures:
                x = self.vals[a]
                v = v * (np.abs(x) if absolute else x)
        return v

    def grouped(self, attrs, f: str = "1", absolute: bool = False):
        """Dense ``(*domains(attrs),)`` array of sums of ``f`` per group.

        Each attribute depends on a few grid axes (its dimension's key), so
        the cell sums are first summed over every other axis."""
        keep = sorted({i for a in attrs for i in self.axes_of[a]})
        drop = tuple(i for i in range(len(self.grid)) if i not in keep)
        w = self.moment(f, absolute=absolute).reshape(self.shape).sum(drop)
        at = tuple(slice(None) if i in keep else 0
                   for i in range(len(self.grid)))
        codes = {a: self.vals[a].reshape(self.shape)[at].ravel()
                 for a in attrs}
        dom = {a: self.schema.domain(a) for a in attrs}
        idx = _mixed_radix(codes, list(attrs), dom)
        size = int(np.prod(list(dom.values())))
        return np.bincount(idx, w.ravel(), size).reshape(
            [dom[a] for a in attrs])

    def covar(self, ds):
        """The non-centred covar matrix over the dataset's features [1, cont,
        one-hot cat blocks, label] (the order ``ml/covar.py`` documents),
        and the same sums over absolute values."""
        cont, cat = ds.features_cont, ds.features_cat
        xs = ["1", *cont, ds.label]
        dom = {c: self.schema.domain(c) for c in cat}
        p = 1 + len(cont) + sum(dom.values()) + 1
        xidx = [0, *range(1, 1 + len(cont)), p - 1]
        off, o = {}, 1 + len(cont)
        for c in cat:
            off[c] = slice(o, o + dom[c])
            o += dom[c]
        out = []
        for absolute in (False, True):
            C = np.zeros((p, p))
            for i, f in enumerate(xs):
                for j, g in enumerate(xs[i:], start=i):
                    C[xidx[i], xidx[j]] = C[xidx[j], xidx[i]] = \
                        self.moment(f, g, absolute).sum()
            for c in cat:
                for f, fi in zip(xs, xidx):
                    C[off[c], fi] = C[fi, off[c]] = self.grouped(
                        [c], f, absolute)
            out.append(C)
        # one-hot × one-hot blocks are row counts, the same in both
        for ci, c in enumerate(cat):
            diag = np.diag(self.grouped([c]))
            for C in out:
                C[off[c], off[c]] = diag
            for c2 in cat[ci + 1:]:
                block = self.grouped([c, c2])
                for C in out:
                    C[off[c], off[c2]] = block
                    C[off[c2], off[c]] = block.T
        return tuple(out)


def check(name: str, got, want, want_abs, tol: float) -> None:
    """``|got - want| <= tol * max(sum |terms|, 1)`` entrywise, finite."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape:
        raise CheckFailed(f"{name}: shape {got.shape} != {want.shape}")
    if not np.isfinite(got).all():
        raise CheckFailed(f"{name}: non-finite values")
    bound = tol * np.maximum(want_abs, 1.0)
    rel = np.abs(got - want) / bound
    worst = np.unravel_index(np.argmax(rel), rel.shape)
    ratio = float(rel[worst])
    log(f"  check {name}: max |err|/bound {ratio} (tol {tol} of sum |terms|,"
        f" {got.size} entries; worst at {tuple(map(int, worst))}: "
        f"{got[worst]} vs {want[worst]}) -> "
        f"{'pass' if ratio <= 1.0 else 'FAIL'}")
    if ratio > 1.0:
        raise CheckFailed(f"{name}: error {ratio}x the bound")


# ------------------------------------------------------------------- phases


def make_data(fact_rows: int, seed: int):
    """Phase 2: Favorita from ``seed`` with ``fact_rows`` ``Sales`` rows."""
    from repro.data import datasets as D

    with phase("data"):
        ds = D.make_favorita(scale=fact_rows / ROWS_PER_SCALE, seed=seed)
        n_items = ds.schema.domain("item")
        log(f"  favorita: Sales {len(ds.tables[ds.fact]['units'])} rows "
            f"(paper Table 1: {PAPER_SALES_ROWS}); cut: the generator caps "
            f"item at {n_items} keys (about 4,100 in the source)")
        ds.db                       # columns to the device
    return ds


def _covar_of(out, layout):
    from repro.ml.covar import assemble_covar

    return assemble_covar({k: np.asarray(v) for k, v in out.items()},
                          layout)[0]


def _tol(handle, n_rows: int) -> float:
    return f32_sum_tol(handle.stats.group_levels, n_rows,
                       handle.config.block_size)


def phase_batch(db, qs, layout, ref_c, ref_abs, n_rows: int):
    """Phase 3: the covar batch on the session's (default) config."""
    import jax

    with phase(f"batch {db.config.backend}"):
        v = db.views(qs)
        log(f"  {v.explain().summary().splitlines()[0]}: "
            f"{v.stats.summary()}")
        out = jax.block_until_ready(v.run())
        C = _covar_of(out, layout)
        tol = _tol(v, n_rows)
        check(f"covar[{db.config.backend}] vs numpy", C, ref_c, ref_abs, tol)
    return C, tol


def phase_pallas(db, qs, layout, ref_c, ref_abs, n_rows: int, c_xla,
                 interpret: bool = False):
    """Phase 4: the same batch on the Pallas kernels, interpret mode set
    explicitly (``False`` on the chip)."""
    import jax

    from repro.core.lowering.pallas import resolve_interpret

    pdb = db.with_config(backend="pallas", interpret=interpret)
    with phase("batch pallas"):
        v = pdb.views(qs)
        resolved = resolve_interpret(v.compiled.plan.config)
        log(f"  pallas interpret={resolved} fuse_kernels="
            f"{pdb.config.fuse_kernels} double_buffer="
            f"{pdb.config.double_buffer}; {v.stats.summary()}")
        if resolved != interpret:
            raise CheckFailed(f"interpret resolved to {resolved}")
        out = jax.block_until_ready(v.run())
        C = _covar_of(out, layout)
        tol = _tol(v, n_rows)
        check("covar[pallas] vs numpy", C, ref_c, ref_abs, tol)
        check("covar[pallas] vs covar[xla]", C, c_xla, ref_abs, 2 * tol)
    return C


def make_update(rng, ds, fact_cols, frac: float):
    """One tick: insert and delete ``frac`` of the fact rows.  Inserted keys
    follow the generator's skew (Zipf stores and items, uniform dates and
    promo); measures are copied from random existing rows.  Returns the
    update, the fact table after it, and the inserted and deleted rows."""
    from repro.data.datasets import zipf_codes
    from repro.data.relations import DeltaBatchUpdate

    n = len(fact_cols[ds.label])
    k = max(1, round(n * frac))
    dom = ds.schema.domain
    src = rng.integers(0, n, k)
    ins = {a: np.asarray(c)[src] for a, c in fact_cols.items()}
    ins["date"] = rng.integers(0, dom("date"), k).astype(np.int32)
    ins["store"] = zipf_codes(rng, k, dom("store"))
    ins["item"] = zipf_codes(rng, k, dom("item"))
    ins["promo"] = rng.integers(0, dom("promo"), k).astype(np.int32)
    dels = np.sort(rng.choice(n, k, replace=False))
    upd = (DeltaBatchUpdate().insert(ds.fact, ins).delete(ds.fact, dels))
    keep = np.ones(n, bool)
    keep[dels] = False
    after = {a: np.concatenate([np.asarray(c)[keep], ins[a]])
             for a, c in fact_cols.items()}
    deleted = {a: np.asarray(c)[dels] for a, c in fact_cols.items()}
    return upd, after, ins, deleted


def phase_maintained(db, ds, qs, layout, ref: Reference, seed: int,
                     n_rows: int):
    """Phase 5: maintained views, one full scan then ``N_TICKS`` ticks;
    returns the reference over the updated tables and the tolerance."""
    import jax

    rng = np.random.default_rng(seed + 1)
    fact_cols = ds.tables[ds.fact]
    with phase("maintained"):
        m = db.views(qs, maintain=True)
        jax.block_until_ready(m.run())
        for tick in range(N_TICKS):
            upd, fact_cols, ins, dels = make_update(rng, ds, fact_cols,
                                                    UPDATE_FRAC)
            t0 = time.perf_counter()
            jax.block_until_ready(m.apply(upd))
            log(f"  tick {tick}: +/-{upd.updates[ds.fact].n_inserts} rows, "
                f"wall {time.perf_counter() - t0}s (compile included)")
            ref = ref.updated(ins, dels)
        tol = _tol(m, n_rows) + N_TICKS * _tol(
            m, 2 * max(1, round(n_rows * UPDATE_FRAC)))
        check(f"maintained epoch {m.maintained.epoch} vs numpy",
              _covar_of(m.results(), layout), *ref.covar(ds), tol)
        log(m.explain().summary())
    return ref, tol


def phase_routing(db, ref_base: Reference, tol_base: float,
                  ref_maintained: Reference, tol_maintained: float):
    """Phase 6: one query a maintained view subsumes, and one miss that
    compiles.  A miss scans the session's base relations, which maintenance
    does not advance, so it is checked against the generated tables."""
    from repro.core import COUNT, query, sum_of

    cases = [   # (query, expected tier, reference, tolerance, its columns)
        (query("units_by_family", ["family"], [COUNT, sum_of("units")]),
         "subsumed", ref_maintained, tol_maintained, ["1", "units"]),
        (query("units_by_city_family", ["city", "family"], [sum_of("units")]),
         "compiled", ref_base, tol_base, ["units"]),
    ]
    with phase("routing"):
        for q, tier, ref, tol, cols in cases:
            r = db.route(q)
            log(f"  query {q.name}: tier {r.tier} (source {r.source})")
            if r.tier != tier:
                raise CheckFailed(f"{q.name}: tier {r.tier}, want {tier}")
            want, want_abs = (
                np.stack([ref.grouped(q.group_by, f, a) for f in cols], -1)
                for a in (False, True))
            check(f"query {q.name} vs numpy", np.asarray(r.value), want,
                  want_abs, tol)


def phase_sharded(ds, ndev: int, seed: int):
    """The path users shard: ``Sales`` row-partitioned over ``ndev``
    devices — the covar batch and ``N_TICKS`` maintained ticks — checked
    against the same computation on one device and against numpy."""
    import jax

    import repro
    from repro.ml.covar import covar_queries

    if len(jax.devices()) < ndev:
        raise RuntimeError(f"need {ndev} devices, have {len(jax.devices())}")
    n_rows = len(ds.tables[ds.fact][ds.label])
    qs, layout = covar_queries(ds)
    with phase("numpy reference"):
        ref = Reference(ds, ds.tables[ds.fact])
        ref_c, ref_abs = ref.covar(ds)
    mesh = jax.make_mesh((ndev,), ("data",))
    sdb = repro.connect(ds, config=repro.ExecutionConfig(
        mesh=mesh, shard_rel=ds.fact))
    ldb = repro.connect(ds)

    def on_every_device(name, arr):
        got = {s.device for s in arr.addressable_shards}
        if got != set(mesh.devices.flat):
            raise CheckFailed(f"{name} lives on {len(got)} of {ndev} devices")

    with phase(f"sharded batch x{ndev}"):
        v = sdb.views(qs)
        out = jax.block_until_ready(v.run())
        on_every_device("sharded batch output", next(iter(out.values())))
        log(v.explain().summary())
        C = _covar_of(out, layout)
        tol = _tol(v, n_rows)
        check(f"covar[{ndev} devices] vs numpy", C, ref_c, ref_abs, tol)
        del v, out            # the runner caches its sharded columns
    with phase("one-device batch"):
        C1 = _covar_of(jax.block_until_ready(ldb.views(qs).run()), layout)
        check(f"covar[{ndev} devices] vs one device", C, C1, ref_abs,
              2 * tol)
    rng = np.random.default_rng(seed + 1)
    fact_cols = ds.tables[ds.fact]
    updates = []
    for _ in range(N_TICKS):
        upd, fact_cols, ins, dels = make_update(rng, ds, fact_cols,
                                                UPDATE_FRAC)
        updates.append(upd)
        ref = ref.updated(ins, dels)
    del fact_cols
    with phase(f"sharded maintained x{ndev}"):
        sm = sdb.views(qs, maintain=True)
        jax.block_until_ready(sm.run())
        for tick, upd in enumerate(updates):
            t0 = time.perf_counter()
            jax.block_until_ready(sm.apply(upd))
            log(f"  sharded tick {tick}: wall {time.perf_counter() - t0}s "
                "(compile included)")
        buffers = sm.maintained.epoch_state().relations[ds.fact].buffers
        for a, buf in buffers.items():
            on_every_device(f"resident {ds.fact}.{a}", buf)
        log(sm.explain().summary())
        tol = _tol(sm, n_rows) + N_TICKS * _tol(
            sm, 2 * max(1, round(n_rows * UPDATE_FRAC)))
        Cs = _covar_of(sm.results(), layout)
        want, want_abs = ref.covar(ds)
        check(f"maintained[{ndev} devices] vs numpy", Cs, want, want_abs,
              tol)
        del sm, buffers       # one maintained copy of the fact at a time
    with phase("one-device maintained"):
        lm = ldb.views(qs, maintain=True)
        jax.block_until_ready(lm.run())
        for upd in updates:
            jax.block_until_ready(lm.apply(upd))
        check(f"maintained[{ndev} devices] vs one device", Cs,
              _covar_of(lm.results(), layout), want_abs, 2 * tol)


def run_all(fact_rows: int, seed: int, interpret: bool = False):
    """Phases 2–6 on JAX's default device."""
    import repro
    from repro.ml.covar import covar_queries

    ds = make_data(fact_rows, seed)
    n_rows = len(ds.tables[ds.fact][ds.label])
    qs, layout = covar_queries(ds)
    with phase("numpy reference"):
        ref = Reference(ds, ds.tables[ds.fact])
        ref_c, ref_abs = ref.covar(ds)
        if ref_c.shape != (layout.p, layout.p):
            raise CheckFailed(f"reference p={ref_c.shape[0]} != {layout.p}")
    db = repro.connect(ds)
    c_xla, tol = phase_batch(db, qs, layout, ref_c, ref_abs, n_rows)
    phase_pallas(db, qs, layout, ref_c, ref_abs, n_rows, c_xla,
                 interpret=interpret)
    ref_after, tol_after = phase_maintained(db, ds, qs, layout, ref, seed,
                                            n_rows)
    phase_routing(db, ref, tol, ref_after, tol_after)


# --------------------------------------------------------------------- main


def device_check(chips: int):
    """Phase 1: the run needs ``chips`` TPU devices; anything else exits
    non-zero before any work."""
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        log("no TPU: this smoke run refuses other platforms")
        sys.exit(2)
    if len(devs) < chips:
        log(f"need {chips} TPU devices, found {len(devs)}")
        sys.exit(2)
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fact-rows", type=int, default=PAPER_SALES_ROWS,
                    help="Favorita Sales rows (default: paper Table 1)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path, on four chips")
    args = ap.parse_args(argv)
    if "LIBTPU_INIT_ARGS" in os.environ:
        log(f"LIBTPU_INIT_ARGS={os.environ['LIBTPU_INIT_ARGS']}")
    device = device_check(args.chips)

    from repro.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache() or 'from environment'}")
    t0 = time.perf_counter()
    if args.chips == 4:
        ds = make_data(args.fact_rows, args.seed)
        phase_sharded(ds, 4, args.seed)
    else:
        run_all(args.fact_rows, args.seed, interpret=False)
    log(f"total wall {time.perf_counter() - t0}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
