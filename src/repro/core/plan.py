"""Executable plan: IR build -> shared-scan schedule -> backend lowering.

The paper's bottom layers (Fig. 1 layers 6–8) as three separable stages:

  * ``ir.py`` compiles each view group into a typed :class:`GroupProgram`
    (gather specs, product axis frames, segment layouts, output perms) —
    built once here, never re-derived per call;
  * ``schedule.py`` fuses same-relation, dependency-independent groups into
    single shared scans and fixes execution order;
  * ``lowering/`` turns each fused step into device code: the ``xla``
    backend traces a blocked ``lax.scan`` (tracing *is* LMFAO's code
    generation, DESIGN.md §2 — the emitted HLO is specialized to the schema,
    the fused view set, and the aggregate batch), the ``pallas`` backend
    launches the MXU kernels in ``repro.kernels``.

Dynamic UDAF parameters (decision-tree thresholds) arrive through ``params``
as traced arrays — no recompilation between CART iterations.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.verify import verification_enabled
from repro.core.aggregates import Params
from repro.core.groups import ViewGroup
from repro.core.ir import (StepProgram, batched_param_names, build_programs,
                           compute_batched_vids, fuse_programs)
from repro.core.jointree import JoinTree
from repro.core.lowering import get_backend
from repro.core.pushdown import PushdownResult
from repro.core.schedule import Schedule, build_schedule
from repro.core.schema import DatabaseSchema
from repro.obs.trace import span

Columns = Mapping[str, Mapping[str, jnp.ndarray]]  # rel -> attr -> (n,)


def _ceil_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def validate_blocking(block_size, block_rows) -> None:
    """Shared validation for the two blocking knobs (PlanConfig and the
    session ExecutionConfig raise identically).  ``"auto"`` defers either to
    the compile-time autotuner (``core/autotune.py``)."""
    if block_size != "auto" and (
            not isinstance(block_size, int) or isinstance(block_size, bool)
            or block_size < 1):
        raise ValueError("block_size must be a positive int or 'auto'; "
                         f"got {block_size!r}")
    if block_rows != "auto" and (
            not isinstance(block_rows, int) or isinstance(block_rows, bool)
            or block_rows < 1 or block_rows % 128):
        raise ValueError("block_rows must be a positive multiple of 128 (the "
                         "lane tile: kernel rows ride the lane axis, and row "
                         f"blocks off that alignment cannot be lowered) or "
                         f"'auto'; got {block_rows!r}")


@dataclasses.dataclass
class PlanConfig:
    block_size: object = 4096       # lax.scan row-block (int | "auto")
    backend: str = "xla"            # lowering backend: "xla" | "pallas"
    interpret: Optional[bool] = None  # Pallas interpret mode; None = auto
                                      # (True everywhere except real TPU)
    fuse_scans: bool = True         # shared-scan fusion across view groups
    block_rows: object = 512        # Pallas kernel row grid (int | "auto")
    fuse_kernels: bool = True       # whole-step fused kernel launch (pallas)
    double_buffer: bool = True      # manual HBM→VMEM DMA pipeline (pallas)
    autotune_cache: Optional[str] = None  # autotuner cache path override
    verify_plans: Optional[bool] = None   # static plan verification
                                          # (DESIGN.md §12); None = auto:
                                          # on under pytest / REPRO_VERIFY

    def __post_init__(self):
        validate_blocking(self.block_size, self.block_rows)
        if self.verify_plans not in (None, True, False):
            raise ValueError("verify_plans must be True, False, or None "
                             f"(auto); got {self.verify_plans!r}")


class ExecutablePlan:
    """Executes a pushed-down, merged, grouped aggregate batch by driving the
    scheduler's fused scan steps through the configured lowering backend."""

    def __init__(self, schema: DatabaseSchema, tree: JoinTree, result: PushdownResult,
                 groups: Sequence[ViewGroup], config: Optional[PlanConfig] = None):
        self.schema = schema
        self.tree = tree
        self.result = result
        self.views = result.views
        self.groups = list(groups)
        self.config = config or PlanConfig()
        with span("compile.ir", n_groups=len(self.groups)):
            self.programs = build_programs(schema, result.views, self.groups)
        with span("compile.schedule", fuse=self.config.fuse_scans):
            self.schedule: Schedule = build_schedule(
                self.groups, fuse=self.config.fuse_scans)
            self.step_programs: List[StepProgram] = [
                fuse_programs([self.programs[gid] for gid in step.gids])
                for step in self.schedule.steps]
        #: :class:`~repro.analysis.verify.VerificationReport` of the static
        #: plan check (DESIGN.md §12), or None when verification is off
        self.last_verification = None
        if verification_enabled(self.config.verify_plans):
            from repro.analysis.verify import verify_plan
            with span("compile.verify"):
                self.last_verification = verify_plan(self)
        self.backend = get_backend(self.config.backend)
        # param-batch (node) axis bookkeeping (DESIGN.md §7.4)
        self.batched_vids = compute_batched_vids(result.views)
        self.batched_params = batched_param_names(result.views)
        self._autotuner = None
        #: per-step record of the last blocking resolution (``bind`` fills
        #: it when the config carries "auto"); surfaced by ``explain()``
        self.last_autotune: Optional[List[Dict[str, object]]] = None
        #: same, for the IVM delta tick (``resolve_delta_configs``)
        self.last_autotune_delta: Optional[List[Dict[str, object]]] = None

    # ------------------------------------------------------------- autotune

    @property
    def autotuner(self):
        """Lazily constructed (loads the on-disk cache once per plan)."""
        if self._autotuner is None:
            from repro.core.autotune import Autotuner
            self._autotuner = Autotuner(self.config.autotune_cache)
        return self._autotuner

    def concrete_config(self) -> PlanConfig:
        """The config with any ``"auto"`` blocking replaced by the static
        defaults — the last-resort fallback for paths that execute without a
        bind-time resolution.  The IVM delta tick no longer uses this: it
        resolves per-step via :meth:`resolve_delta_configs` against
        |update|-bucketed signatures."""
        from repro.core import autotune as at

        cfg = self.config
        if cfg.block_size == "auto" or cfg.block_rows == "auto":
            cfg = dataclasses.replace(
                cfg,
                block_size=(at.DEFAULT_BLOCK_SIZE
                            if cfg.block_size == "auto" else cfg.block_size),
                block_rows=(at.DEFAULT_BLOCK_ROWS
                            if cfg.block_rows == "auto" else cfg.block_rows))
        return cfg

    def resolve_step_configs(self, n_rows: Mapping[str, int],
                             n_nodes: Optional[int] = None) -> List[PlanConfig]:
        """One concrete :class:`PlanConfig` per scan step.  Static blocking
        passes the session config through untouched; ``"auto"`` resolves via
        the autotuner, keyed per step on (relation row count, widest segment
        layout, total payload width, node axis, backend, platform) — runs at
        ``bind`` time, *outside* any jit trace, so timing probes are legal."""
        cfg = self.config
        steps = self.schedule.steps
        if cfg.block_size != "auto" and cfg.block_rows != "auto":
            return [cfg] * len(steps)
        from repro.core import autotune as at

        platform = jax.default_backend()
        interpret = self._interpret_flag(platform)
        out, report = [], []
        with span("compile.autotune", n_steps=len(steps)):
            for step, prog in zip(steps, self.step_programs):
                n_seg, width = self._prog_tune_dims(prog, n_nodes)
                sig = at.signature_for_step(cfg.backend, platform, interpret,
                                            n_rows[step.rel], n_seg, width,
                                            n_nodes)
                res = self.autotuner.tune(sig)
                bs = (res.block_size if cfg.block_size == "auto"
                      else cfg.block_size)
                br = (res.block_rows if cfg.block_rows == "auto"
                      else cfg.block_rows)
                out.append(dataclasses.replace(cfg, block_size=bs,
                                               block_rows=br))
                report.append({"rel": step.rel, "key": sig.key(),
                               "block_size": bs, "block_rows": br,
                               "from_cache": res.from_cache,
                               "fallback": res.fallback})
        self.last_autotune = report
        return out

    def _interpret_flag(self, platform: str) -> bool:
        from repro.core.lowering.pallas import resolve_interpret

        return (self.config.backend == "pallas"
                and resolve_interpret(self.config, platform))

    def _prog_tune_dims(self, prog: StepProgram, n_nodes: Optional[int]):
        """(widest segment layout, total payload width) of one fused step —
        the shape facts a tuning signature carries besides the row count."""
        n_seg, width = 1, 0
        for vp in prog.views:
            lead = (n_nodes or 1) if vp.batched else 1
            if vp.hist is not None:
                n_seg = max(n_seg, vp.hist.n_buckets)
                width += 3 * lead
            else:
                if vp.seg is not None:
                    n_seg = max(n_seg, vp.seg.n_segments)
                w = vp.n_aggs * lead
                for d in vp.pulled_dims:
                    w *= d
                width += w
        return n_seg, max(width, 1)

    def resolve_delta_configs(self, steps, n_rows: Sequence[int],
                              n_nodes: Optional[int] = None) -> List[PlanConfig]:
        """One concrete :class:`PlanConfig` per IVM delta step (objects with
        ``.prog`` / ``.rel`` / ``.scans_delta``, see ``core/ivm.py``).
        ``n_rows[i]`` is step i's static scan length: the |update| pad bucket
        for delta scans, the rescanned relation's (per-shard) capacity
        otherwise.  Delta scans tune under ``delta=True`` signatures — their
        own cache lane — so ``block_size="auto"`` no longer degrades to the
        static defaults on the tick path.  Runs at tick-runner *build* time,
        outside any jit trace, so timing probes are legal."""
        cfg = self.config
        if cfg.block_size != "auto" and cfg.block_rows != "auto":
            return [cfg] * len(steps)
        from repro.core import autotune as at

        platform = jax.default_backend()
        interpret = self._interpret_flag(platform)
        out, report = [], []
        with span("compile.autotune", n_steps=len(steps), delta=True):
            for st, rows in zip(steps, n_rows):
                n_seg, width = self._prog_tune_dims(st.prog, n_nodes)
                sig = at.signature_for_step(cfg.backend, platform, interpret,
                                            max(int(rows), 1), n_seg, width,
                                            n_nodes, delta=st.scans_delta)
                res = self.autotuner.tune(sig)
                bs = (res.block_size if cfg.block_size == "auto"
                      else cfg.block_size)
                br = (res.block_rows if cfg.block_rows == "auto"
                      else cfg.block_rows)
                out.append(dataclasses.replace(cfg, block_size=bs,
                                               block_rows=br))
                report.append({"rel": st.rel, "delta": st.scans_delta,
                               "key": sig.key(), "block_size": bs,
                               "block_rows": br, "from_cache": res.from_cache,
                               "fallback": res.fallback})
        self.last_autotune_delta = report
        return out

    def n_kernel_launches(self) -> int:
        """Static kernel-launch *sites* per full pass (how many distinct
        device kernels one scan block dispatches, summed over steps) — the
        quantity launch fusion shrinks.  0 for the xla backend (no custom
        kernels)."""
        return self._count("count_launches")

    def n_compact_views(self) -> int:
        """Views lowered on the xla backend's compact accumulate path,
        summed over steps; 0 for other backends."""
        return self._count("count_compact")

    def n_accumulators(self) -> int:
        """Accumulators the xla backend carries (one per segment key and
        node axis of a step's views), summed over steps; 0 for other
        backends."""
        return self._count("count_accumulators")

    def n_clustered_steps(self, n_rows: Mapping[str, int]) -> int:
        """Steps the xla backend sorts by a compact key before their scan,
        at the relations' row counts ``n_rows``; 0 for other backends."""
        count = getattr(self.backend, "count_clustered", None)
        if count is None:
            return 0
        return sum(count(prog, self.config, n_rows.get(prog.rel, 0))
                   for prog in self.step_programs)

    def _count(self, counter: str) -> int:
        count = getattr(self.backend, counter, None)
        if count is None:
            return 0
        return sum(count(prog, self.config) for prog in self.step_programs)

    # ------------------------------------------------------------------ api

    def bind(self, n_rows: Dict[str, int], n_nodes: Optional[int] = None):
        """Returns a pure fn(columns, params, offsets) -> {query: array}; the
        caller jits it.  ``n_rows`` are the *valid* row counts (columns may be
        padded beyond them); ``offsets`` shift validity windows for sharded
        execution (see distributed.py).  ``n_nodes`` is the param-batch (node)
        axis size — required iff the plan has batched params, in which case
        each batched param must carry a leading axis of that size and batched
        query outputs gain a leading node axis."""
        # the closure must capture its own copy: a retrace of a cached runner
        # would otherwise read row counts from whichever bind() ran last
        n_rows = dict(n_rows)
        if self.batched_params and n_nodes is None:
            raise ValueError(
                f"plan has batched params {sorted(self.batched_params)}; "
                "bind with n_nodes (use CompiledBatch.run_batched)")
        # "auto" blocking resolves here, once per bind, outside any trace —
        # the closure runs with concrete per-step configs
        with span("compile.bind", n_steps=len(self.schedule.steps)):
            step_configs = self.resolve_step_configs(n_rows, n_nodes)

        def run(columns: Columns, params: Params, offsets: Optional[Mapping[str, jnp.ndarray]] = None,
                psum_axes: Optional[Mapping[str, str]] = None):
            arrays = self._run_steps(columns, params, n_rows, n_nodes,
                                     offsets, psum_axes,
                                     step_configs=step_configs)
            return self.extract_outputs(arrays)

        return run

    def bind_arrays(self, n_rows: Dict[str, int], n_nodes: Optional[int] = None):
        """Like :meth:`bind`, but the returned fn yields *every* materialized
        view array keyed by vid (not just query outputs) — the full-recompute
        entry point of the IVM subsystem (``core/ivm.py``), which persists
        these arrays as maintained state.

        ``n_rows`` fixes the *column lengths* (static shapes).  The optional
        ``n_valid`` argument of the returned fn overrides per-relation valid
        row counts with **traced scalars** — how capacity-padded resident
        relations scan only their live prefix: the executable is keyed on
        buffer capacity while the row count stays a runtime value, so a
        growing stream retraces log2 times, not per tick."""
        n_rows = dict(n_rows)
        if self.batched_params and n_nodes is None:
            raise ValueError(
                f"plan has batched params {sorted(self.batched_params)}; "
                "bind with n_nodes")
        with span("compile.bind", n_steps=len(self.schedule.steps),
                  arrays=True):
            step_configs = self.resolve_step_configs(n_rows, n_nodes)

        def run(columns: Columns, params: Params,
                n_valid: Optional[Mapping[str, jnp.ndarray]] = None,
                psum_axes: Optional[Mapping[str, str]] = None):
            nv = dict(n_rows)
            if n_valid:
                nv.update(n_valid)
            return self._run_steps(columns, params, nv, n_nodes,
                                   psum_axes=psum_axes,
                                   step_configs=step_configs)

        return run

    def _run_steps(self, columns: Columns, params: Params,
                   n_rows: Dict[str, int], n_nodes: Optional[int],
                   offsets: Optional[Mapping[str, jnp.ndarray]] = None,
                   psum_axes: Optional[Mapping[str, str]] = None,
                   step_configs: Optional[Sequence[PlanConfig]] = None) -> Dict[int, jnp.ndarray]:
        offsets = offsets or {}
        psum_axes = psum_axes or {}
        if step_configs is None:
            step_configs = [self.concrete_config()] * len(self.schedule.steps)
        arrays: Dict[int, jnp.ndarray] = {}
        for step, prog, cfg in zip(self.schedule.steps, self.step_programs,
                                   step_configs):
            # every op of the step carries its relation in its op name
            with jax.named_scope(f"scan.{step.rel}"):
                self.backend.run_step(
                    prog, columns[step.rel], arrays, params,
                    n_valid=n_rows[step.rel],
                    offset=offsets.get(step.rel, 0), config=cfg,
                    n_nodes=n_nodes)
                if step.rel in psum_axes:
                    for vid in step.vids:
                        arrays[vid] = jax.lax.psum(arrays[vid],
                                                   psum_axes[step.rel])
        return arrays

    def extract_outputs(self, arrays: Mapping[int, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        """Read query results out of view arrays (column select + transpose
        from canonical to user group-by order)."""
        out = {}
        with jax.named_scope("outputs"):
            for qname, qo in self.result.outputs.items():
                arr = arrays[qo.vid]
                cols = jnp.take(arr, jnp.asarray(qo.cols), axis=-1)
                # canonical axis order -> user group-by order; a leading
                # node axis (batched outputs) stays in front
                lead = 1 if qo.vid in self.batched_vids else 0
                perm = [qo.canonical_group_by.index(a) + lead
                        for a in qo.query.group_by]
                perm = (list(range(lead)) + perm
                        + [lead + len(qo.query.group_by)])
                out[qname] = jnp.transpose(cols, perm)
        return out


# ---------------------------------------------------------------------------
# Naive baseline: materialize the join, then aggregate (the "DBMS" strategy
# the paper outperforms; used by benchmarks and as a test oracle).
# ---------------------------------------------------------------------------

def materialize_join(schema: DatabaseSchema, tables: Mapping[str, Mapping[str, np.ndarray]],
                     order: Optional[Sequence[str]] = None) -> Dict[str, np.ndarray]:
    """Host-side hash join of all relations (natural join), numpy columns."""
    names = list(order or schema.relations)
    joined: Dict[str, np.ndarray] = {a: np.asarray(c) for a, c in tables[names[0]].items()}
    for name in names[1:]:
        right = {a: np.asarray(c) for a, c in tables[name].items()}
        shared = sorted(set(joined) & set(right))
        if not shared:
            raise ValueError(f"cartesian product at {name}; provide a join order")
        # build hash index on right
        rkeys = list(zip(*[right[a].tolist() for a in shared]))
        index: Dict[Tuple, List[int]] = {}
        for i, k in enumerate(rkeys):
            index.setdefault(k, []).append(i)
        lkeys = list(zip(*[joined[a].tolist() for a in shared]))
        li, ri = [], []
        for i, k in enumerate(lkeys):
            for j in index.get(k, ()):
                li.append(i)
                ri.append(j)
        li = np.asarray(li, dtype=np.int64)
        ri = np.asarray(ri, dtype=np.int64)
        out = {a: c[li] for a, c in joined.items()}
        for a, c in right.items():
            if a not in out:
                out[a] = c[ri]
        joined = out
    return joined
