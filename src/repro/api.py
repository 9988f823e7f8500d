"""Session-centric public API: one `Database` facade over every engine mode.

LMFAO's pitch is *one* engine behind every workload — ridge, trees,
Chow-Liu, cubes are all "a batch of group-by aggregates over the join tree"
(PAPER.md) — and this module is where that shows in the API (DESIGN.md §9).
A session owns the schema, join tree, resident relations, and ONE frozen
:class:`ExecutionConfig`; queries become **named views** with a uniform
lifecycle, and batch / frontier-batched / incremental / sharded / served
execution are config and method choices on the *same* compiled artifact,
not four parallel class hierarchies:

    import repro
    db = repro.connect(dataset, config=repro.ExecutionConfig(backend="pallas"))

    v = db.views(queries)                  # compile once
    out = v.run()                          # batch (sharded iff config.mesh)
    out = v.run_batched(params)            # param-batched node frontier
    print(v.explain().summary())           # unified stats report

    m = db.views(queries, maintain=True)   # incremental views
    m.run()                                # full scan -> epoch 0
    m.apply(update)                        # work ∝ |update|
    srv = m.serve(max_pinned_epochs=8)     # epoch-pinned concurrent serving
    m.snapshot(ckpt_dir)                   # crash-safe epoch checkpoint

The legacy entry points (``Engine.compile``, ``Engine.compile_incremental``)
still work but emit :class:`~repro.core.engine.EngineDeprecationWarning`;
they are thin shims over the same internals this facade drives.
"""

from __future__ import annotations

import dataclasses
import time as _time
from typing import Dict, Mapping, Optional, Sequence, Tuple

from repro.core.aggregates import Params, Query
from repro.core.engine import BatchStats, CompiledBatch, Engine
from repro.core.schema import DatabaseSchema
from repro.data import relations as rel_mod

__all__ = ["ExecutionConfig", "Database", "ViewHandle", "ViewReport",
           "connect"]


@dataclasses.dataclass(frozen=True)
class ExecutionConfig:
    """One frozen execution policy for a whole session, threaded once at
    :func:`connect` instead of per-call kwargs.

    Compilation: ``backend`` selects the lowering path ("xla": blocked
    lax.scan; "pallas": MXU kernels, ``interpret`` controlling CPU interpret
    mode — None auto-detects); ``fuse_scans`` toggles shared-scan fusion;
    ``multi_root`` enables the paper's find-roots layer.

    Kernel blocking: ``block_size`` is the outer lax.scan row block,
    ``block_rows`` the Pallas kernel row grid (a positive multiple of 128 —
    kernel rows ride the lane axis).  Either may be the string ``"auto"``:
    blocking is then resolved per scan step by the compile-time autotuner
    (``core/autotune.py``), which times candidate grids against the step's
    signature and persists winners to an on-disk cache
    (``autotune_cache`` path > ``REPRO_AUTOTUNE_CACHE`` env >
    ``~/.cache/repro/autotune.json``) so warm sessions never re-tune; the
    resolution shows up in ``ViewHandle.explain()``.  ``fuse_kernels``
    (default) collapses each step's bucket/hist reductions into ONE fused
    Pallas launch per row block; ``double_buffer`` enables that kernel's
    manual HBM→VMEM DMA pipeline (DESIGN.md §10).

    Placement: a non-None ``mesh`` makes every ``ViewHandle.run`` /
    ``run_batched`` domain-parallel over ``mesh_axis`` (``shard_rel``
    defaults to the largest relation, the paper's choice) — sharding is a
    config choice, not a different method on a different class.  Maintained
    views shard the same way: ``shard_rel`` lives row-partitioned on device
    and every delta tick runs as one cached ``jit(shard_map)``
    (DESIGN.md §6/§8), so serving and maintenance scale together.

    Frontier batching: ``pad_nodes_to_pow2`` rounds the param-batch (node)
    axis up to a power of two so a growing tree frontier hits at most log2
    distinct jit entries.

    Serving: ``max_pinned_epochs`` bounds how many epochs concurrent readers
    may keep device-resident; beyond it the least-recently-used pin is
    evicted (reads of an evicted epoch raise
    :class:`~repro.core.ivm.EpochEvictedError`).

    Telemetry (DESIGN.md §11): ``warn_epoch_lag`` sets the pinned-reader lag
    (served head minus oldest pin) past which the server logs a rate-limited
    warning (None disables); ``workload_capacity`` bounds the session's
    in-memory workload recorder (``Database.workload``) — every run/read
    records its query signature, hit path, and latency there; 0 disables
    recording.

    Verification (DESIGN.md §12): ``verify_plans`` runs the static plan
    verifier (``repro.analysis.verify``) over every compiled artifact —
    group programs, the shared-scan schedule, delta and tick programs,
    resident-relation metadata — raising
    :class:`~repro.analysis.verify.PlanInvariantError` at compile time on
    any violated invariant.  ``None`` (default) auto-enables under pytest
    or when the ``REPRO_VERIFY`` env var is truthy;
    ``Database.views(debug=True)`` forces it on per batch.

    Routing (DESIGN.md §13): ``route_cache_capacity`` bounds the ad-hoc
    query router's LRU cache of serving-time compiled plans
    (``Database.query`` / ``Database.route``); 0 disables caching, so
    every routed miss is answered by a one-shot ``fallback_scan``.
    Plans the router compiles are always admission-gated by the static
    verifier, independent of ``verify_plans``.
    """

    backend: str = "xla"
    block_size: object = 4096               # int | "auto"
    interpret: Optional[bool] = None
    fuse_scans: bool = True
    block_rows: object = 512                # int (multiple of 128) | "auto"
    fuse_kernels: bool = True
    double_buffer: bool = True
    autotune_cache: Optional[str] = None
    multi_root: bool = True
    mesh: Optional[object] = None           # jax.sharding.Mesh
    mesh_axis: str = "data"
    shard_rel: Optional[str] = None
    pad_nodes_to_pow2: bool = True
    max_pinned_epochs: Optional[int] = None
    warn_epoch_lag: Optional[int] = None
    workload_capacity: int = 4096
    verify_plans: Optional[bool] = None
    route_cache_capacity: int = 32

    def __post_init__(self):
        from repro.core.plan import validate_blocking

        if self.backend not in ("xla", "pallas"):
            raise ValueError(f"unknown backend {self.backend!r} "
                             "(expected 'xla' or 'pallas')")
        validate_blocking(self.block_size, self.block_rows)
        if self.max_pinned_epochs is not None and self.max_pinned_epochs < 1:
            raise ValueError("max_pinned_epochs must be >= 1 (or None)")
        if self.warn_epoch_lag is not None and self.warn_epoch_lag < 1:
            raise ValueError("warn_epoch_lag must be >= 1 (or None)")
        if self.verify_plans not in (None, True, False):
            raise ValueError("verify_plans must be True, False, or None "
                             f"(auto); got {self.verify_plans!r}")
        if (not isinstance(self.workload_capacity, int)
                or isinstance(self.workload_capacity, bool)
                or self.workload_capacity < 0):
            raise ValueError("workload_capacity must be an int >= 0 "
                             "(0 disables recording)")
        if (not isinstance(self.route_cache_capacity, int)
                or isinstance(self.route_cache_capacity, bool)
                or self.route_cache_capacity < 0):
            raise ValueError("route_cache_capacity must be an int >= 0 "
                             "(0 disables plan caching)")
        if self.mesh is not None and self.mesh_axis not in self.mesh.shape:
            raise ValueError(f"mesh has no axis {self.mesh_axis!r} "
                             f"(axes: {tuple(self.mesh.shape)})")

    def replace(self, **overrides) -> "ExecutionConfig":
        """A copy with the given fields changed."""
        return dataclasses.replace(self, **overrides)

    def compile_kwargs(self) -> Dict[str, object]:
        """The compile-stage subset, as `Engine._compile` keywords."""
        return dict(multi_root=self.multi_root, block_size=self.block_size,
                    backend=self.backend, interpret=self.interpret,
                    fuse_scans=self.fuse_scans, block_rows=self.block_rows,
                    fuse_kernels=self.fuse_kernels,
                    double_buffer=self.double_buffer,
                    autotune_cache=self.autotune_cache,
                    verify_plans=self.verify_plans)


@dataclasses.dataclass
class ViewReport:
    """Unified ``explain()`` report across execution modes: the compile-time
    layer statistics (paper Table 2) always, plus the IVM epoch counters for
    maintained views and the server counters once ``serve()`` is live."""

    mode: str                    # "batch" | "maintained" | "served"
    backend: str
    sharded: bool
    batch: BatchStats
    # batch-mode device dispatches; None for maintained views (their unit of
    # work is the delta tick: see step / n_delta_scan_steps / n_fold_traces)
    n_dispatches: Optional[int]
    # maintained-view counters (None in batch mode)
    epoch: Optional[int] = None
    step: Optional[int] = None
    n_delta_scan_steps: Optional[int] = None
    n_fold_traces: Optional[int] = None
    n_pinned_epochs: Optional[int] = None
    n_evicted_pins: Optional[int] = None
    max_pinned_epochs: Optional[int] = None
    # full server stats dict (None until serve()) — counters plus the
    # read/tick latency distributions and epoch lag (DESIGN.md §11)
    serving: Optional[Dict[str, object]] = None
    # per-step blocking resolution from the last bind with "auto" blocking
    # (None when blocking is static or nothing has bound yet); the delta
    # variant is the IVM tick resolution — both render, labeled, when set
    autotune: Optional[list] = None
    autotune_delta: Optional[list] = None
    # shard topology for sharded runs (None when config.mesh is None):
    # device count, mesh axis, partitioned relation, per-shard row/capacity
    # geometry, and the psum count per tick (maintained) or per run (batch)
    shard: Optional[Dict[str, object]] = None
    # static-verification coverage (DESIGN.md §12): joined summaries of the
    # plan / delta / tick reports, or None when verification is off
    verification: Optional[str] = None
    # session query-router stats (DESIGN.md §13): tier hit mix, cache
    # occupancy, eviction count — None until Database.query has routed
    # something
    routing: Optional[Dict[str, object]] = None

    @staticmethod
    def _render_autotune(report: list) -> str:
        return ", ".join(
            f"{a['rel']}: bs={a['block_size']} br={a['block_rows']}"
            + (" (cached)" if a["from_cache"] else "")
            + (" (fallback)" if a.get("fallback") else "")
            for a in report)

    @staticmethod
    def _render_latency(label: str, snap: Optional[Dict[str, float]]) -> str:
        if not snap or not snap.get("count"):
            return ""
        return (f" {label}_p50={snap['p50']:.0f}us"
                f" {label}_p99={snap['p99']:.0f}us")

    def summary(self) -> str:
        """Every populated field renders — the line set is keyed on what the
        report carries, not on the mode label, so batch / maintained / served
        handles print consistently."""
        lines = [f"[{self.mode}] backend={self.backend}"
                 f"{' sharded' if self.sharded else ''}"
                 + (f" dispatches={self.n_dispatches}"
                    if self.n_dispatches is not None else ""),
                 "  " + self.batch.summary()]
        if self.shard is not None:
            t = self.shard
            geom = (f" rows/shard={t['rows_per_shard']}"
                    f" cap/shard={t['capacity_per_shard']}"
                    if "rows_per_shard" in t else "")
            if "psums_per_tick" in t:
                psums = " psums/tick={%s}" % ", ".join(
                    f"{r}: {n}" for r, n in t["psums_per_tick"].items())
            else:
                psums = f" psums/run={t['psums_per_run']}"
            lines.append(f"  shard: devices={t['n_devices']} "
                         f"axis={t['mesh_axis']} rel={t['shard_rel']}"
                         + geom + psums)
        if self.step is not None:
            lines.append(
                "  ivm: epoch="
                + ("-" if self.epoch is None else str(self.epoch))
                + f" step={self.step} "
                f"delta_scans={self.n_delta_scan_steps} "
                f"fold_traces={self.n_fold_traces} "
                f"pinned={self.n_pinned_epochs}"
                + (f"/{self.max_pinned_epochs}"
                   if self.max_pinned_epochs else "")
                + f" evicted={self.n_evicted_pins}")
        if self.serving is not None:
            s = self.serving
            lines.append(f"  serve: reads={s['n_reads']} "
                         f"updates={s['n_updates']} "
                         f"rejected={s['n_rejected_updates']} "
                         f"lag={s.get('epoch_lag', 0)}"
                         + self._render_latency("read", s.get("read_us"))
                         + self._render_latency("tick", s.get("tick_us")))
        if self.routing is not None and self.routing.get("n_queries"):
            r = self.routing
            tiers = r["tiers"]
            lines.append(
                f"  routing: n={r['n_queries']} "
                + " ".join(f"{t}={tiers[t]}" for t in
                           ("exact", "subsumed", "compiled", "fallback_scan")
                           if tiers.get(t))
                + f" hit_rate={r['hit_rate']:.2f}"
                  f" cache={r['cache_size']}/{r['capacity']}"
                  f" evicted={r['n_evictions']}")
        if self.verification:
            lines.append("  verify: " + self.verification)
        if self.autotune:
            lines.append("  autotune[batch]: "
                         + self._render_autotune(self.autotune))
        if self.autotune_delta:
            lines.append("  autotune[delta]: "
                         + self._render_autotune(self.autotune_delta))
        return "\n".join(lines)


class ViewHandle:
    """A registered batch of named views — the one handle every execution
    mode dispatches through (create via :meth:`Database.views`).

    Batch views: ``run(params=)`` (one fused device dispatch; domain-parallel
    when the session config carries a mesh), ``run_batched(params)`` (the
    param-batch / node-frontier axis, DESIGN.md §7.4), ``lower()``.

    Maintained views (``maintain=True``): ``run()`` materializes epoch 0 via
    a full scan (later calls read the current epoch), ``apply(update)`` folds
    a delta batch and publishes the next epoch, ``serve()`` wraps the state
    in an epoch-pinning :class:`~repro.serve.views.ViewServer`, and
    ``snapshot()``/``restore()`` checkpoint one clean epoch.

    ``explain()`` returns one :class:`ViewReport` for all of it.
    """

    def __init__(self, database: "Database", compiled: CompiledBatch,
                 maintained=None):
        self._database = database
        self.compiled = compiled        #: the underlying CompiledBatch
        self._maintained = maintained
        self._server = None
        self._sharded = {}              # cached (fn, cols) mesh runners
        self._signatures = None         # lazy {name: QuerySignature}

    # -- workload recording (DESIGN.md §11) ----------------------------------

    def signatures(self) -> Dict[str, "object"]:
        """Structural query signatures per view name (the workload
        recorder's router key; see ``repro.obs.workload``)."""
        if self._signatures is None:
            from repro.obs.workload import signature_of

            self._signatures = {
                q: signature_of(qo.query)
                for q, qo in self.compiled.result.outputs.items()}
        return self._signatures

    def _record(self, kind: str, hit: str, t0: float,
                epoch: Optional[int] = None) -> None:
        rec = self._database.workload
        if not rec.enabled:
            return
        us = (_time.perf_counter() - t0) * 1e6
        for name, sig in self.signatures().items():
            rec.record(kind, name, sig, hit, us, epoch=epoch)

    # -- introspection -------------------------------------------------------

    @property
    def config(self) -> ExecutionConfig:
        return self._database.config

    @property
    def is_maintained(self) -> bool:
        return self._maintained is not None

    @property
    def maintained(self):
        """The underlying :class:`~repro.core.ivm.MaintainedBatch`."""
        if self._maintained is None:
            raise ValueError(
                "views were compiled without maintenance; register them with "
                "db.views(queries, maintain=True) to get apply()/serve()")
        return self._maintained

    @property
    def names(self) -> Tuple[str, ...]:
        """The registered view (query) names, in output order."""
        return tuple(self.compiled.result.outputs)

    @property
    def stats(self) -> BatchStats:
        """Compile-time layer statistics (paper Table 2 analogue)."""
        return self.compiled.stats

    @property
    def schedule(self):
        return self.compiled.schedule

    @property
    def batched_params(self):
        return self.compiled.batched_params

    # -- batch execution -----------------------------------------------------

    def _run_sharded(self, params: Optional[Params],
                     n_nodes: Optional[int] = None):
        """Mesh execution with the runner cached per (shard choice, node
        axis, relation sizes) — repeated ``run()`` calls hit the same jitted
        shard_map program.  The node axis pads to the next power of two
        (``config.pad_nodes_to_pow2``, like the local ``run_batched``) so a
        growing tree frontier reuses at most log2 runners instead of
        rebuilding the collective program every level."""
        import jax.numpy as jnp

        from repro.core.distributed import sharded_runner

        cfg = self.config
        params = dict(params or {})
        plan = self.compiled.plan
        if plan.batched_params and n_nodes is None:
            name = sorted(plan.batched_params)[0]
            n_nodes = int(jnp.shape(params[name])[0])
        n_run = n_nodes
        if n_nodes is not None and cfg.pad_nodes_to_pow2:
            n_run = 1
            while n_run < n_nodes:
                n_run *= 2
            if n_run != n_nodes:
                pad = n_run - n_nodes
                for name in plan.batched_params:
                    v = jnp.asarray(params[name])
                    params[name] = jnp.pad(
                        v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
        db = self._database.data
        shard_rel = cfg.shard_rel or max(db.sizes(), key=lambda k: db.sizes()[k])
        key = (cfg.mesh_axis, shard_rel, n_run,
               tuple(sorted(db.sizes().items())))
        if key not in self._sharded:
            self._sharded[key] = sharded_runner(plan, db, cfg.mesh,
                                                cfg.mesh_axis, shard_rel,
                                                n_nodes=n_run)
        fn, cols = self._sharded[key]
        self.compiled.n_dispatches += 1
        out = fn(cols, params)
        if n_run != n_nodes and n_nodes is not None:
            batched_vids = plan.batched_vids
            outputs = self.compiled.result.outputs
            out = {q: (v[:n_nodes] if outputs[q].vid in batched_vids else v)
                   for q, v in out.items()}
        return out

    def run(self, params: Optional[Params] = None):
        """Evaluate the views and return ``{name: dense array}``.

        Batch views: one fused device dispatch over the session's relations
        (domain-parallel over ``config.mesh`` when set).  Maintained views:
        the first call runs the full scan and publishes epoch 0; later calls
        read the current epoch (no rescans — use :meth:`apply` to advance)."""
        t0 = _time.perf_counter()
        if self._maintained is not None:
            mb = self._maintained
            if not mb.initialized:
                out = mb.init(self._database.data, params=params)
                self._record("run", "full_scan", t0, epoch=mb.epoch)
                return out
            if params:
                raise ValueError(
                    "maintained views bind params at the initial full scan; "
                    "re-init via handle.maintained.init(db, params=...) to "
                    "change them (a later run() only reads the epoch)")
            out = mb.results()
            self._record("run", "epoch_read", t0, epoch=mb.epoch)
            return out
        if self.config.mesh is not None:
            out = self._run_sharded(params)
            self._record("run", "sharded_scan", t0)
            return out
        out = self.compiled(self._database.data, params)
        self._record("run", "batch_scan", t0)
        return out

    def run_batched(self, params: Params, n_nodes: Optional[int] = None):
        """Evaluate N parameter settings in ONE fused dispatch (the node
        frontier of DESIGN.md §7.4); batched outputs gain a leading N axis.
        Sharded iff the session config carries a mesh."""
        if self._maintained is not None:
            raise ValueError("maintained views do not support the "
                             "param-batch axis; register a batch view")
        if not self.compiled.plan.batched_params:
            raise ValueError("views were compiled without batched params; "
                             "declare Param(..., batched=True) terms first")
        t0 = _time.perf_counter()
        if self.config.mesh is not None:
            out = self._run_sharded(params, n_nodes=n_nodes)
            self._record("run_batched", "sharded_scan", t0)
            return out
        out = self.compiled.run_batched(
            self._database.data, params, n_nodes=n_nodes,
            pad_to_pow2=self.config.pad_nodes_to_pow2)
        self._record("run_batched", "batch_scan", t0)
        return out

    def lower(self, params: Optional[Params] = None,
              n_nodes: Optional[int] = None):
        """Lower without executing (dry-run / HLO inspection)."""
        return self.compiled.lower(self._database.data, params,
                                   n_nodes=n_nodes)

    # -- incremental maintenance ---------------------------------------------

    def apply(self, update, params: Optional[Params] = None):
        """Fold a :class:`~repro.data.relations.DeltaBatchUpdate` into the
        maintained state and publish the next epoch; returns the refreshed
        results.  Initializes (full scan) first if :meth:`run` has not."""
        mb = self.maintained
        if not mb.initialized:
            mb.init(self._database.data)
        return mb.apply(update, params=params)

    def results(self, epoch: Optional[int] = None):
        """Maintained-view outputs read from one epoch's frozen state."""
        return self.maintained.results(epoch=epoch)

    def serve(self, max_pinned_epochs: Optional[int] = None,
              warn_epoch_lag: Optional[int] = None):
        """An epoch-pinning :class:`~repro.serve.views.ViewServer` over the
        maintained state (started — epoch 0 is published if needed).  The
        pin budget defaults to ``config.max_pinned_epochs``, the lag-warning
        threshold to ``config.warn_epoch_lag``; reads record into the
        session's workload recorder (``Database.workload``)."""
        from repro.serve.views import ViewServer

        mb = self.maintained
        if max_pinned_epochs is None:
            max_pinned_epochs = self.config.max_pinned_epochs
        if max_pinned_epochs is not None and max_pinned_epochs < 1:
            raise ValueError("max_pinned_epochs must be >= 1 (or None)")
        if warn_epoch_lag is None:
            warn_epoch_lag = self.config.warn_epoch_lag
        if self._server is None:
            self._server = ViewServer(mb, max_pinned_epochs=max_pinned_epochs,
                                      warn_epoch_lag=warn_epoch_lag,
                                      workload=self._database.workload,
                                      router=self._database.router)
        elif max_pinned_epochs is not None:
            mb.max_pinned_epochs = max_pinned_epochs
        if not mb.initialized:
            self._server.start(self._database.data)
        return self._server

    def snapshot(self, ckpt_dir: str, keep: int = 3,
                 epoch: Optional[int] = None) -> str:
        """Crash-safe checkpoint of one clean epoch of maintained state."""
        return self.maintained.save(ckpt_dir, keep=keep, epoch=epoch)

    def restore(self, ckpt_dir: str, step: Optional[int] = None) -> int:
        """Restore maintained state from a checkpoint (works before any
        ``run()`` — the state skeleton comes from the compiled plan)."""
        return self.maintained.restore(ckpt_dir, step=step)

    # -- unified report ------------------------------------------------------

    def explain(self) -> ViewReport:
        """One report across modes: compile-time layer stats (always), IVM
        epoch counters (maintained views), serving counters (after
        ``serve()``)."""
        cfg = self.config
        rep = ViewReport(
            mode="batch", backend=cfg.backend,
            sharded=cfg.mesh is not None, batch=self.compiled.stats,
            n_dispatches=self.compiled.n_dispatches,
            autotune=self.compiled.plan.last_autotune)
        mb = self._maintained
        if mb is not None:
            rep.mode = "served" if self._server is not None else "maintained"
            rep.n_dispatches = None
            rep.epoch = mb.epoch if mb.initialized else None
            rep.step = mb.step
            rep.n_delta_scan_steps = mb.n_delta_scan_steps
            rep.n_fold_traces = mb.n_fold_traces
            rep.n_pinned_epochs = mb.n_pinned_epochs
            rep.n_evicted_pins = mb.n_evicted_pins
            rep.max_pinned_epochs = mb.max_pinned_epochs
            # both resolutions, labeled — the delta lane no longer shadows
            # the init full scan's
            rep.autotune_delta = self.compiled.plan.last_autotune_delta
            rep.shard = mb.shard_topology()
            if self._server is not None:
                rep.serving = self._server.stats()
        elif cfg.mesh is not None:
            rep.shard = self._shard_topology_batch()
        pieces = []
        if self.compiled.plan.last_verification is not None:
            pieces.append(self.compiled.plan.last_verification.summary())
        if mb is not None:
            pieces.extend(r.summary() for _, r in
                          sorted(mb.last_verifications.items()))
        rep.verification = "; ".join(pieces) if pieces else None
        rep.routing = self._database.routing_stats()
        return rep

    def _shard_topology_batch(self) -> Dict[str, object]:
        """Shard facts for a batch-mode mesh run: the relation the next
        ``run()`` would partition, its per-shard geometry, and how many
        psums one sharded pass issues (one per view of every step scanning
        the partitioned relation — distributed.py's combine rule)."""
        cfg = self.config
        sizes = self._database.sizes()
        shard_rel = cfg.shard_rel or max(sorted(sizes), key=lambda k: sizes[k])
        ndev = int(cfg.mesh.shape[cfg.mesh_axis])
        n = sizes.get(shard_rel, 0)
        return {"n_devices": ndev, "mesh_axis": cfg.mesh_axis,
                "shard_rel": shard_rel, "rows": n,
                "rows_per_shard": -(-n // ndev) if n else 0,
                "capacity_per_shard": -(-max(n, 1) // ndev),
                "psums_per_run": sum(
                    len(step.vids) for step in self.compiled.schedule.steps
                    if step.rel == shard_rel)}


class Database:
    """The session facade: schema + join tree + resident relations + one
    frozen :class:`ExecutionConfig`.  Create via :func:`repro.connect`;
    register query batches as named views with :meth:`views`."""

    def __init__(self, schema: DatabaseSchema, data: rel_mod.Database,
                 edges: Optional[Sequence[Tuple[str, str]]] = None,
                 config: Optional[ExecutionConfig] = None,
                 fact: Optional[str] = None,
                 _engine: Optional[Engine] = None):
        from repro.obs.workload import WorkloadRecorder

        self.schema = schema
        self.data = data                      #: resident relations
        self.config = config or ExecutionConfig()
        self.fact = fact
        self.edges = list(edges) if edges is not None else None
        self._engine = _engine or Engine(schema, edges=edges,
                                         sizes=data.sizes())
        #: session-wide workload recorder (DESIGN.md §11): every view run
        #: and served read lands here; ``workload.export_json(path)`` is
        #: the future view advisor's input (ROADMAP item 2)
        self.workload = WorkloadRecorder(self.config.workload_capacity)
        #: registered view handles, in registration order — the query
        #: router's answerable sources (DESIGN.md §13)
        self._registered = []
        self._router = None

    # -- data access ---------------------------------------------------------

    @property
    def tree(self):
        """The join tree every view batch is pushed down over."""
        return self._engine.tree

    def sizes(self) -> Dict[str, int]:
        return self.data.sizes()

    def relation(self, name: str):
        return self.data.relation(name)

    # -- configuration -------------------------------------------------------

    def with_config(self, **overrides) -> "Database":
        """A sibling session over the same schema/data/join tree with some
        config fields changed (e.g. ``db.with_config(backend="pallas")``) —
        the cheap way to compare backends or toggle sharding."""
        return Database(self.schema, self.data, edges=self.edges,
                        config=self.config.replace(**overrides),
                        fact=self.fact, _engine=self._engine)

    # -- view registration ---------------------------------------------------

    def views(self, queries: Sequence[Query], maintain: bool = False, *,
              roots: Optional[Dict[str, str]] = None,
              warm_rels: Sequence[str] = (),
              debug: bool = False, register: bool = True) -> ViewHandle:
        """Compile a query batch into one :class:`ViewHandle`.

        ``maintain=False``: a batch view — ``run()``/``run_batched()`` scan
        the session's relations on every call.  ``maintain=True``: an
        incrementally maintained view — ``run()`` materializes epoch 0 and
        ``apply(update)`` folds delta batches with work ∝ |update|
        (DESIGN.md §8); ``warm_rels`` pre-builds delta programs.

        ``roots`` overrides the find-roots layer per query (e.g. rooting
        every covar view at the fact table so fact-only update streams stay
        delta-only).  ``debug=True`` forces the static plan verifier on for
        this batch regardless of the session's ``verify_plans`` setting
        (DESIGN.md §12) — ``explain()`` then reports the coverage.

        Registered handles (``register=True``, the default) become the
        query router's answerable sources: :meth:`query` matches routed
        aggregates against them by signature and, for maintained handles,
        by subsumption (DESIGN.md §13).  ``register=False`` keeps a handle
        private (the router uses it for its own cached plans)."""
        cfg = self.config
        if debug and cfg.verify_plans is not True:
            cfg = cfg.replace(verify_plans=True)
        if maintain:
            mb = self._engine._compile_incremental(
                queries, root_override=roots, warm_rels=warm_rels,
                mesh=cfg.mesh, mesh_axis=cfg.mesh_axis,
                shard_rel=cfg.shard_rel, **cfg.compile_kwargs())
            handle = ViewHandle(self, mb.batch, maintained=mb)
        else:
            batch = self._engine._compile(queries, root_override=roots,
                                          **cfg.compile_kwargs())
            handle = ViewHandle(self, batch)
        if register:
            self._registered.append(handle)
        return handle

    def view(self, q: Query, maintain: bool = False, **kw) -> ViewHandle:
        """Single-query convenience wrapper around :meth:`views`."""
        return self.views([q], maintain=maintain, **kw)

    # -- ad-hoc query routing (DESIGN.md §13) --------------------------------

    @property
    def router(self):
        """The session's signature router (created on first use; its LRU
        plan-cache bound comes from ``config.route_cache_capacity``)."""
        if self._router is None:
            from repro.serve.router import QueryRouter

            self._router = QueryRouter(
                self, capacity=self.config.route_cache_capacity)
        return self._router

    def route(self, q: Query, params: Optional[Params] = None):
        """Answer an *arbitrary* group-by aggregate — no prior
        registration — returning a
        :class:`~repro.serve.router.RouteResult` with the value plus
        provenance (tier, answering view, pinned epoch, latency).  Exact
        and subsumed matches answer from registered views (maintained
        sources: one pinned epoch, no base scan); misses compile a fresh
        verified plan and cache it for the next ask."""
        return self.router.route(q, params=params)

    def query(self, q: Query, params: Optional[Params] = None):
        """Value-only front door: ``db.query(q)`` → dense answer tensor
        shaped ``(*[domain(a) for a in q.group_by], n_aggs)``."""
        return self.route(q, params=params).value

    def routing_stats(self) -> Optional[Dict[str, object]]:
        """Router telemetry (tier mix, hit rate, cache occupancy), or
        None if nothing was ever routed in this session."""
        return None if self._router is None else self._router.stats()


def connect(source, config: Optional[ExecutionConfig] = None, *,
            tables: Optional[Mapping[str, Mapping[str, object]]] = None,
            data: Optional[rel_mod.Database] = None,
            edges: Optional[Sequence[Tuple[str, str]]] = None,
            fact: Optional[str] = None) -> Database:
    """Open a session: ``repro.connect(dataset_or_schema, config=...)``.

    ``source`` may be a :class:`~repro.data.datasets.Dataset` (schema, join
    edges, relations, and fact table all come from it), a
    :class:`~repro.data.relations.Database` (schema and relations), or a
    bare :class:`~repro.core.schema.DatabaseSchema` plus either ``data=``
    (a relations Database) or ``tables=`` (numpy column dicts).  ``edges``
    overrides the join tree (otherwise built from relation sizes)."""
    if hasattr(source, "schema") and hasattr(source, "db"):       # Dataset
        return Database(source.schema, source.db,
                        edges=edges if edges is not None else source.edges,
                        config=config,
                        fact=fact if fact is not None else source.fact)
    if isinstance(source, rel_mod.Database):
        return Database(source.schema, source, edges=edges, config=config,
                        fact=fact)
    if isinstance(source, DatabaseSchema):
        if data is None:
            if tables is None:
                raise ValueError("connect(schema, ...) needs data= (a "
                                 "relations Database) or tables= (numpy "
                                 "column dicts)")
            data = rel_mod.from_numpy(source, tables)
        return Database(source, data, edges=edges, config=config, fact=fact)
    raise TypeError(f"cannot connect to {type(source).__name__}: expected a "
                    "Dataset, a relations Database, or a DatabaseSchema")
