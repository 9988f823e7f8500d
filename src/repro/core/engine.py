"""Engine internals: compile a batch of aggregate queries into an executable.

The *public* entry point is the session facade (``repro.connect`` →
``Database.views``, DESIGN.md §9); this module is what it drives:

    eng = Engine(schema, sizes=db.sizes())
    batch = eng._compile(queries)             # layers 1-6 + jit (codegen)
    results = batch(db)                       # {query name: dense array}
    results = batch.run_sharded(db, mesh)     # domain-parallel over chips

``Engine.compile`` / ``Engine.compile_incremental`` remain as deprecated
shims (one release) that emit :class:`EngineDeprecationWarning`.

Compilation lowers through three separable stages (DESIGN.md §3-§5): the
group-program IR (``ir.py``), the shared-scan scheduler (``schedule.py``),
and a pluggable lowering backend (``lowering/``: ``xla`` or ``pallas``).
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import roots as roots_mod
from repro.core.aggregates import Params, Query
from repro.core.groups import ViewGroup, group_views, independent_sets
from repro.core.jointree import JoinTree
from repro.core.plan import ExecutablePlan, PlanConfig
from repro.core.pushdown import PushdownResult, push_down
from repro.core.schema import DatabaseSchema
from repro.obs.trace import span


class EngineDeprecationWarning(DeprecationWarning):
    """Raised (as a warning) by the legacy compile entry points; the
    session facade (``repro.connect`` → ``Database.views``, DESIGN.md §9)
    replaces them.  A distinct category so CI can fail hard on deprecated
    API leaking out of this package without tripping on third-party
    DeprecationWarnings."""


@dataclasses.dataclass
class BatchStats:
    """Paper Table 2 analogue.  ``n_scan_steps`` counts the relation scans
    actually executed after shared-scan fusion; ``n_fused_scans`` is how many
    of the ``n_groups`` group scans the scheduler eliminated."""

    n_app_aggregates: int
    n_intermediate_cols: int
    n_views_premerge: int
    n_views: int
    n_groups: int
    group_levels: int
    n_scan_steps: int
    n_fused_scans: int
    roots: Dict[str, str]
    #: static kernel-launch sites per full pass (pallas; 0 for xla) — the
    #: quantity launch fusion shrinks to one per scan step
    n_kernel_launches: int = 0
    #: views the xla backend accumulates through a compact per-block
    #: partial over the segments a block touches (``lowering/xla.py``)
    n_compact_views: int = 0
    #: accumulators the xla backend carries, summed over steps: one per
    #: (segment key, batched) group of a step's views
    n_accumulators: int = 0
    #: steps the xla backend sorts by a compact key before their scan, at
    #: the relation sizes the batch was compiled for (``lowering/xla.py``)
    n_clustered_steps: int = 0

    def summary(self) -> str:
        return (f"A={self.n_app_aggregates} I={self.n_intermediate_cols} "
                f"V={self.n_views} (pre-merge {self.n_views_premerge}) "
                f"G={self.n_groups} levels={self.group_levels} "
                f"scans={self.n_scan_steps} (fused {self.n_fused_scans}) "
                f"launches={self.n_kernel_launches} "
                f"compact={self.n_compact_views} "
                f"accumulators={self.n_accumulators} "
                f"clustered={self.n_clustered_steps}")


def _jit_batch(run):
    """``run`` jitted as the program ``aggregate_batch``: the name a
    profiler trace gives its module and every op name it carries
    (``jit(aggregate_batch)/scan.Sales/...``).  The persistent
    compilation cache keys a program without its op names, so an
    executable it holds keeps the op names it was compiled with."""

    def aggregate_batch(c, p):
        return run(c, p)

    return jax.jit(aggregate_batch)


class CompiledBatch:
    def __init__(self, schema: DatabaseSchema, tree: JoinTree,
                 result: PushdownResult, groups: List[ViewGroup],
                 config: PlanConfig, roots: Dict[str, str],
                 sizes: Optional[Mapping[str, int]] = None):
        self.schema = schema
        #: relation row counts the batch was compiled for
        self.sizes = dict(sizes or {})
        self.tree = tree
        self.result = result
        self.groups = groups
        self.config = config
        self.roots = roots
        self.plan = ExecutablePlan(schema, tree, result, groups, config)
        self._jitted = {}
        #: device dispatches issued (``__call__`` + ``run_batched``); the
        #: frontier-batched tree builder asserts one per tree level on this
        self.n_dispatches = 0

    @property
    def stats(self) -> BatchStats:
        s = self.result.stats
        sched = self.plan.schedule
        return BatchStats(
            n_app_aggregates=s.n_app_aggregates,
            n_intermediate_cols=s.n_intermediate_cols,
            n_views_premerge=s.n_views_premerge,
            n_views=s.n_views,
            n_groups=len(self.groups),
            group_levels=len(independent_sets(self.groups)),
            n_scan_steps=sched.n_scans,
            n_fused_scans=sched.n_fused_groups,
            roots=self.roots,
            n_kernel_launches=self.plan.n_kernel_launches(),
            n_compact_views=self.plan.n_compact_views(),
            n_accumulators=self.plan.n_accumulators(),
            n_clustered_steps=self.plan.n_clustered_steps(self.sizes),
        )

    @property
    def schedule(self):
        """The fused scan schedule this batch executes."""
        return self.plan.schedule

    # -- single-device ------------------------------------------------------

    def __call__(self, db, params: Optional[Params] = None) -> Dict[str, jnp.ndarray]:
        params = dict(params or {})
        n_rows = db.sizes()
        key = ("local", tuple(sorted(n_rows.items())), tuple(sorted(params)))
        if key not in self._jitted:
            run = self.plan.bind(n_rows)
            self._jitted[key] = _jit_batch(run)
        cols = {name: dict(rel.columns) for name, rel in db.relations.items()}
        self.n_dispatches += 1
        return self._jitted[key](cols, params)

    # -- param-batched (node frontier) ---------------------------------------

    @property
    def batched_params(self):
        """Names of the batch's ``Param(batched=True)`` declarations."""
        return self.plan.batched_params

    def run_batched(self, db, params: Params, n_nodes: Optional[int] = None,
                    pad_to_pow2: bool = True) -> Dict[str, jnp.ndarray]:
        """Evaluate ``N`` parameter settings of the compiled batch in ONE
        fused device dispatch (DESIGN.md §7.4).

        Every batched param in ``params`` must carry a leading axis of size
        ``N`` (inferred from the first batched param when ``n_nodes`` is
        omitted); batched query outputs come back as ``(N, *group_dims,
        n_aggs)``.  The relation-scan schedule is identical to the N=1 case —
        one pass over each relation serves all ``N`` nodes.

        ``pad_to_pow2`` (default) rounds the node axis up to the next power
        of two with zeroed param rows (sliced off the outputs), so a growing
        tree frontier hits at most ``log2`` distinct jit cache entries
        instead of one per level."""
        params = dict(params or {})
        if not self.plan.batched_params:
            raise ValueError("batch was compiled without batched params; "
                             "declare Param(..., batched=True) terms first")
        if n_nodes is None:
            name = sorted(self.plan.batched_params)[0]
            n_nodes = int(jnp.shape(params[name])[0])
        n_run = n_nodes
        if pad_to_pow2:
            n_run = 1
            while n_run < n_nodes:
                n_run *= 2
            if n_run != n_nodes:
                pad = n_run - n_nodes
                for name in self.plan.batched_params:
                    v = jnp.asarray(params[name])
                    params[name] = jnp.pad(
                        v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
        n_rows = db.sizes()
        key = ("batched", n_run, tuple(sorted(n_rows.items())),
               tuple(sorted(params)))
        if key not in self._jitted:
            run = self.plan.bind(n_rows, n_nodes=n_run)
            self._jitted[key] = _jit_batch(run)
        cols = {name: dict(rel.columns) for name, rel in db.relations.items()}
        self.n_dispatches += 1
        out = self._jitted[key](cols, params)
        if n_run != n_nodes:
            batched_vids = self.plan.batched_vids
            out = {q: (v[:n_nodes]
                       if self.result.outputs[q].vid in batched_vids else v)
                   for q, v in out.items()}
        return out

    def lower(self, db, params: Optional[Params] = None,
              n_nodes: Optional[int] = None):
        """Lower without executing (dry-run / HLO inspection); pass
        ``n_nodes`` for plans with batched params."""
        params = dict(params or {})
        run = self.plan.bind(db.sizes(), n_nodes=n_nodes)
        cols = {name: {a: jax.ShapeDtypeStruct(c.shape, c.dtype)
                       for a, c in rel.columns.items()}
                for name, rel in db.relations.items()}
        pspec = {k: jax.ShapeDtypeStruct(jnp.shape(v), jnp.asarray(v).dtype)
                 for k, v in params.items()}
        return _jit_batch(run).lower(cols, pspec)

    # -- domain-parallel (paper layer 7 on a chip mesh) ----------------------

    def run_sharded(self, db, mesh, axis: str = "data",
                    shard_rel: Optional[str] = None,
                    params: Optional[Params] = None,
                    n_nodes: Optional[int] = None) -> Dict[str, jnp.ndarray]:
        """Partition ``shard_rel`` (default: the largest relation — the
        paper's choice) across the mesh axis; every device runs the
        multi-output plans on its partition; partial dense views are psum'd
        right after their group (LMFAO's merge of per-thread results).

        Batched plans shard too: ``n_nodes`` is inferred from the first
        batched param when omitted, so a node frontier can be evaluated
        domain-parallel in one collective pass."""
        from repro.core.distributed import sharded_runner

        params = dict(params or {})
        if self.plan.batched_params and n_nodes is None:
            name = sorted(self.plan.batched_params)[0]
            n_nodes = int(jnp.shape(params[name])[0])
        shard_rel = shard_rel or max(db.sizes(), key=lambda k: db.sizes()[k])
        fn, cols = sharded_runner(self.plan, db, mesh, axis, shard_rel,
                                  n_nodes=n_nodes)
        self.n_dispatches += 1
        return fn(cols, params)


class Engine:
    """Layer driver: join tree -> roots -> pushdown+merge -> groups -> IR ->
    schedule -> backend lowering."""

    def __init__(self, schema: DatabaseSchema,
                 edges: Optional[Sequence[Tuple[str, str]]] = None,
                 sizes: Optional[Dict[str, int]] = None):
        self.schema = schema
        self.sizes = dict(sizes or {})
        if edges is not None:
            self.tree = JoinTree(schema, edges)
        else:
            self.tree = JoinTree.build(schema, self.sizes)

    def compile(self, queries: Sequence[Query], *, multi_root: bool = True,
                block_size=4096, backend: str = "xla",
                interpret: Optional[bool] = None, fuse_scans: bool = True,
                block_rows=512, fuse_kernels: bool = True,
                double_buffer: bool = True,
                autotune_cache: Optional[str] = None,
                root_override: Optional[Dict[str, str]] = None,
                verify_plans: Optional[bool] = None) -> CompiledBatch:
        """Deprecated shim over :meth:`_compile` — use the session facade:
        ``repro.connect(..., config=ExecutionConfig(...)).views(queries)``."""
        warnings.warn(
            "Engine.compile is deprecated; open a session with "
            "repro.connect(dataset_or_schema, config=ExecutionConfig(...)) "
            "and register the batch with Database.views(queries) "
            "(DESIGN.md §9)", EngineDeprecationWarning, stacklevel=2)
        return self._compile(queries, multi_root=multi_root,
                             block_size=block_size, backend=backend,
                             interpret=interpret, fuse_scans=fuse_scans,
                             block_rows=block_rows, fuse_kernels=fuse_kernels,
                             double_buffer=double_buffer,
                             autotune_cache=autotune_cache,
                             root_override=root_override,
                             verify_plans=verify_plans)

    def _compile(self, queries: Sequence[Query], *, multi_root: bool = True,
                 block_size=4096, backend: str = "xla",
                 interpret: Optional[bool] = None, fuse_scans: bool = True,
                 block_rows=512, fuse_kernels: bool = True,
                 double_buffer: bool = True,
                 autotune_cache: Optional[str] = None,
                 root_override: Optional[Dict[str, str]] = None,
                 verify_plans: Optional[bool] = None) -> CompiledBatch:
        """Compile a query batch.  ``backend`` selects the lowering path
        (``"xla"``: blocked lax.scan; ``"pallas"``: MXU kernels, with
        ``interpret`` controlling CPU interpret mode — None auto-detects);
        ``fuse_scans`` toggles the scheduler's shared-scan fusion.

        Blocking: ``block_size`` is the outer lax.scan row block,
        ``block_rows`` the Pallas kernel row grid — either may be the string
        ``"auto"`` to defer to the bind-time autotuner (``core/autotune.py``,
        cache path overridable via ``autotune_cache``).  ``fuse_kernels``
        collapses each step's bucket/hist reductions into one fused launch
        per row block; ``double_buffer`` enables its manual HBM→VMEM DMA
        pipeline (DESIGN.md §10)."""
        with span("compile", n_queries=len(queries), backend=backend):
            with span("compile.roots"):
                if root_override is not None:
                    roots = dict(root_override)
                elif multi_root:
                    roots = roots_mod.find_roots(self.tree, queries,
                                                 self.sizes)
                else:
                    roots = roots_mod.single_root(self.tree, queries,
                                                  self.sizes)
            with span("compile.pushdown"):
                result = push_down(self.tree, queries, roots)
            with span("compile.group"):
                groups = group_views(result)
            cfg = PlanConfig(block_size=block_size, backend=backend,
                             interpret=interpret, fuse_scans=fuse_scans,
                             block_rows=block_rows, fuse_kernels=fuse_kernels,
                             double_buffer=double_buffer,
                             autotune_cache=autotune_cache,
                             verify_plans=verify_plans)
            # CompiledBatch builds the ExecutablePlan, which emits the
            # compile.ir / compile.schedule child spans
            return CompiledBatch(self.schema, self.tree, result, groups, cfg,
                                 roots, self.sizes)

    def compile_incremental(self, queries: Sequence[Query], *,
                            multi_root: bool = True, block_size=4096,
                            backend: str = "xla",
                            interpret: Optional[bool] = None,
                            fuse_scans: bool = True, block_rows=512,
                            fuse_kernels: bool = True,
                            double_buffer: bool = True,
                            autotune_cache: Optional[str] = None,
                            root_override: Optional[Dict[str, str]] = None,
                            warm_rels: Sequence[str] = (),
                            verify_plans: Optional[bool] = None):
        """Deprecated shim over :meth:`_compile_incremental` — use
        ``repro.connect(...).views(queries, maintain=True)``."""
        warnings.warn(
            "Engine.compile_incremental is deprecated; open a session with "
            "repro.connect(...) and register maintained views with "
            "Database.views(queries, maintain=True) (DESIGN.md §9)",
            EngineDeprecationWarning, stacklevel=2)
        return self._compile_incremental(
            queries, multi_root=multi_root, block_size=block_size,
            backend=backend, interpret=interpret, fuse_scans=fuse_scans,
            block_rows=block_rows, fuse_kernels=fuse_kernels,
            double_buffer=double_buffer, autotune_cache=autotune_cache,
            root_override=root_override, warm_rels=warm_rels,
            verify_plans=verify_plans)

    def _compile_incremental(self, queries: Sequence[Query], *,
                             multi_root: bool = True, block_size=4096,
                             backend: str = "xla",
                             interpret: Optional[bool] = None,
                             fuse_scans: bool = True, block_rows=512,
                             fuse_kernels: bool = True,
                             double_buffer: bool = True,
                             autotune_cache: Optional[str] = None,
                             root_override: Optional[Dict[str, str]] = None,
                             warm_rels: Sequence[str] = (),
                             mesh=None, mesh_axis: str = "data",
                             shard_rel: Optional[str] = None,
                             verify_plans: Optional[bool] = None):
        """Compile a query batch for incremental view maintenance: returns a
        :class:`~repro.core.ivm.MaintainedBatch` whose ``init(db)``
        materializes every view as persistent state and whose ``apply``
        folds a :class:`~repro.data.relations.DeltaBatchUpdate` into that
        state via per-relation delta programs (DESIGN.md §8).

        With a ``mesh`` the maintained state shards: ``shard_rel`` (default
        the largest relation at init) partitions row-wise over ``mesh_axis``
        and every relation tick runs as one cached ``jit(shard_map)``
        (DESIGN.md §6/§8).

        Delta programs are derived lazily on first update of each relation
        and cached; ``warm_rels`` pre-builds the programs for relations you
        expect to stream updates for (e.g. the fact table), moving that
        compile cost out of the first ``apply``.

        Rejects non-invertible (MIN/MAX-style) aggregates up front: signed
        multiplicities maintain SUM-like aggregates only, and a silent wrong
        retraction is far worse than a compile error."""
        from repro.core.ivm import MaintainedBatch

        for q in queries:
            for a in q.aggregates:
                for prod in a.products:
                    for t in prod.terms:
                        if not t.is_invertible():
                            raise ValueError(
                                f"query {q.name!r}: aggregate term {t.key()!r} "
                                "is not invertible under retraction (MIN/MAX-"
                                "style UDAF) — incremental maintenance by "
                                "signed multiplicities would produce wrong "
                                "results on deletes; use Engine.compile for "
                                "batch recomputation instead")

        batch = self._compile(queries, multi_root=multi_root,
                              block_size=block_size, backend=backend,
                              interpret=interpret, fuse_scans=fuse_scans,
                              block_rows=block_rows,
                              fuse_kernels=fuse_kernels,
                              double_buffer=double_buffer,
                              autotune_cache=autotune_cache,
                              root_override=root_override,
                              verify_plans=verify_plans)
        mb = MaintainedBatch(batch, mesh=mesh, mesh_axis=mesh_axis,
                             shard_rel=shard_rel)
        for rel in warm_rels:
            mb.delta_program(rel)
        return mb
