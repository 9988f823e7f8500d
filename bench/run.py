#!/usr/bin/env python3
"""One run of one benchmark cell, on the chips of this machine.

    python3 bench/run.py --workload favorita.ridge --seed 7 --seconds 10 \
        --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<name>.json``: schema, sizes, how each column is drawn) and
a traffic mix (``bench/traffic/<name>.json``: the job and its solver).  The
run

1. refuses anything but a TPU with the chips the cell asks for;
2. sets up: makes the data on the device from ``--seed``, opens a session
   (``repro.connect``), registers the covar batch and compiles it (from the
   persistent cache after a cell's first run) without running it;
3. measures for ``--seconds``: whole jobs back to back (the batch, the
   covar assembled on the host, ridge by batch gradient descent), the job
   in flight when the time is up finished and counted; with ``--trace 1``
   the window is traced and the per-layer metrics
   (``bench/metrics/<name>.py``) are read from it;
4. checks what the window produced against the plain reference
   (``bench/lib/reference.py``) once the program's state is freed.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``: each compared number beside its limit
(``bench/limits/<workload>.json``).  The same numbers end standard error.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench.lib import datagen, drive, reference  # noqa: E402

#: the end-to-end metrics a window yields, with their units
UNITS = {"setup_s": "s", "job_s": "s"}

#: JAX's monitoring events for lowering and compiling a program
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def phase(name: str, t: float) -> float:
    """Log the seconds since ``t`` under ``name``; returns the time now."""
    now = time.perf_counter()
    log(f"phase {name}: {now - t} s")
    return now


# ------------------------------------------------------------------- specs


class Cell:
    """One ``workloads`` entry with its configuration, traffic, limits and
    metrics, all found by name."""

    def __init__(self, name: str):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r} "
                             f"(have {sorted(cells)})")
        self.name = name
        self.spec = cells[name]
        self.cfg = datagen.Config.load(os.path.join(
            BENCH, "configs", self.spec["config"] + ".json"))
        self.traffic = self._json("traffic", self.spec["traffic"])
        self.limits = self._json("limits", name)
        self.chips = int(self.spec["chips"])
        ends = [m["name"] for m in self.bench["end_to_end"]
                if "workloads" not in m or name in m["workloads"]]
        self.per_layer = [m for m in self.bench["per_layer"]
                          if name in m.get("workloads", [])
                          or ("workloads" not in m and m["moves"] in ends)]

    @staticmethod
    def _json(kind: str, name: str) -> dict:
        with open(os.path.join(BENCH, kind, name + ".json")) as f:
            return json.load(f)


def reader(metric: str):
    """``bench/metrics/<metric>.py``'s ``read(run)``."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# ----------------------------------------------------------------- the run


class Run:
    """What the window produced, for the metric readers."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.trace = None            # trace.Reduced of a traced window
        self.jobs = None             # drive.Units of the window
        self.scan_steps = None
        self.peak = None             # bench/peaks.json row of this device


def device_check(chips: int) -> dict:
    """The cell's chips, as JAX sees them; anything else ends the run."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" or len(devs) < chips:
        log(f"needs {chips} TPU chip(s); JAX has {len(devs)} "
            f"{d.platform} device(s) ({d.device_kind})")
        sys.exit(3)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": chips}


def open_session(cfg, cols):
    """A ``repro.connect`` session over device columns (no host copy)."""
    import repro
    from repro.core.schema import schema
    from repro.data.datasets import Dataset
    from repro.data.relations import Database, Relation

    S = schema([tuple(a) for a in cfg.spec["attributes"]],
               [(r, cfg.attrs[r]) for r in cfg.relations])
    data = Database(S, {r: Relation(r, dict(c)) for r, c in cols.items()})
    data.validate()
    ds = Dataset(cfg.name, S, {}, [tuple(e) for e in cfg.edges],
                 cfg.features_cont, cfg.features_cat, cfg.label, cfg.fact,
                 _db=data)
    return ds, repro.connect(ds)


def host(tree):
    import jax

    return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))


class CellRun:
    """One run of a cell in three steps: :meth:`setup` (data, session,
    the compiled batch), :meth:`window` (the measurement), and
    :meth:`result` (metrics, then the checks once the program is freed).
    ``cfg`` replaces the cell's configuration (tests run a shrunk copy)."""

    def __init__(self, cell: Cell, seed: int, cfg=None):
        self.cell, self.seed = cell, seed
        self.cfg = cfg or cell.cfg
        self.solver = cell.traffic["jobs"]
        self.res = Run(self.cfg)
        self.compiles = []

    def setup(self) -> None:
        import jax

        from repro.ml.covar import assemble_covar, covar_queries
        from repro.ml.ridge import bgd

        cfg, solver = self.cfg, self.solver
        t = time.perf_counter()
        gen = datagen.Generator(cfg)
        self.dims = gen.dimensions(self.seed)
        self.fact = gen.fact_rows(self.seed, self.dims,
                                  cfg.n_rows(cfg.fact))
        jax.block_until_ready(self.fact)
        t = phase("data on the device", t)
        ds, db = open_session(cfg, {cfg.fact: self.fact, **self.dims})
        qs, layout = covar_queries(ds)
        handle = db.views(qs)
        self.res.scan_steps = handle.stats.n_scan_steps
        t = phase("session", t)
        # the program ViewHandle.run() dispatches (the same plan, bound to
        # the same sizes), compiled without running it
        batch = handle.lower().compile()
        cols = {r: dict(rel.columns) for r, rel in db.data.relations.items()}
        t = phase("batch compiled", t)

        def job(_):
            out = host(batch(cols, {}))
            C, N = assemble_covar(out, layout)
            fit = bgd(C, N, layout, lam=solver["lam"],
                      max_iters=solver["max_iters"], tol=solver["tol"])
            return C, fit

        self.units = drive.Units(job, "bench.job")
        gc.collect()

    def window(self, seconds: float, trace: bool) -> None:
        """Jobs back to back for ``seconds``, the last one finished."""
        import jax

        listening = [True]

        def on_event(event, secs, **_):
            if listening[0] and event in COMPILE_EVENTS:
                self.compiles.append(event)

        jax.monitoring.register_event_duration_secs_listener(on_event)
        if trace:
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(tdir)
        with jax.profiler.TraceAnnotation("bench.window"):
            self.t0 = time.perf_counter()
            self.units.run(self.t0, seconds)
        listening[0] = False
        if trace:
            jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(on_event)
        if trace:
            from bench.lib import trace as tr_mod

            t = time.perf_counter()
            events = tr_mod.extract(tdir)
            shutil.rmtree(tdir, ignore_errors=True)
            self.res.trace = tr_mod.Reduced(events)
            phase("trace read", t)

    def result(self, device: dict, setup_s: float) -> dict:
        import jax

        cell, cfg, res, units = self.cell, self.cfg, self.res, self.units
        metrics = {"setup_s": setup_s,
                   "job_s": units.time_per_unit(self.t0)}
        res.jobs = units
        n_jobs = len(units.done)
        C_prog, fit = units.last
        stats = [d.memory_stats() or {}
                 for d in jax.devices()[:cell.chips]]
        device = dict(device, memory_peak_bytes=max(
            int(s.get("peak_bytes_in_use", 0)) for s in stats))
        layer = {}
        if res.trace is not None:
            device["busy_s"] = res.trace.busy_s()
            device["window_s"] = res.trace.window_s
            res.peak = peaks(device["kind"])
            for m in cell.per_layer:
                v = reader(m["name"])(res)
                if v is not None:
                    layer[m["name"]] = {"value": v, "unit": m["unit"]}
        log(f"window: {n_jobs} jobs, {len(self.compiles)} "
            f"compiles inside the window, {fit.iterations} solver "
            "iterations in the last job; "
            + ", ".join(f"{k} {v}" for k, v in metrics.items()))

        # -- free the program's state, then the reference -----------------
        del self.units, units, res.jobs
        gc.collect()
        t = time.perf_counter()
        dims, fact = host(self.dims), host(self.fact)
        del self.fact, self.dims
        gc.collect()
        exact = answers(reference.Reference(cfg, dims), fact, self.solver)
        checks = numbers(C_prog, fit.theta, exact)
        t = phase("reference", t)
        result = {"correct": all(checks[k] <= cell.limits[k]
                                 for k in cell.limits),
                  "attempted": n_jobs,
                  "failed": 0,
                  "metrics": layer if res.trace is not None else {
                      k: {"value": v, "unit": UNITS[k]}
                      for k, v in metrics.items()},
                  "device": device}
        if res.trace is not None:
            result["breakdown"] = res.trace.breakdown()
        result["checks"] = {k: {"value": checks[k],
                                "limit": cell.limits[k]}
                            for k in cell.limits}
        for k, v in result["checks"].items():
            log(f"check {k}: {v['value']} (limit {v['limit']})")
        return result


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        device: dict, cfg=None) -> dict:
    """Set up, measure, check."""
    r = CellRun(cell, seed, cfg)
    r.setup()
    setup_s = time.perf_counter() - T_PROCESS
    r.window(seconds, trace)
    return r.result(device, setup_s)


def answers(ref, fact, solver: dict) -> dict:
    """What the reference says a job should produce: the covar matrix (and
    its sums over |terms|) and the ridge model, the exact minimum."""
    C, C_abs = reference.covar_matrix(
        ref.cfg, ref.moments(reference.covar_requests(ref.cfg), fact))
    return {"covar": (C, C_abs),
            "theta": reference.ridge_closed_form(C, solver["lam"])}


def numbers(C, theta, exact: dict) -> dict:
    """Each compared number: the covar's worst entry and the ridge model's
    prediction gap, both against the reference."""
    C_ref, C_abs = exact["covar"]
    return {"covar_err": reference.max_rel_err(C, C_ref, C_abs),
            "ridge_gap": reference.prediction_gap(theta, exact["theta"],
                                                  C_ref)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    device = device_check(cell.chips)

    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run(cell, args.seed, args.seconds, bool(args.trace), device)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
