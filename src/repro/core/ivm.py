"""Incremental view maintenance: delta programs over the materialized view DAG.

``Engine.compile_incremental(queries)`` returns a :class:`MaintainedBatch`
that keeps every view's dense accumulator as **persistent state** and, per
base relation, derives a **delta program**: the sub-DAG of views transitively
reachable from that relation, re-derived so that an update batch (inserts and
deletes with signed multiplicities) is folded into the stored view tensors
with work proportional to the update — not the database (DESIGN.md §8).

Soundness for the engine's SUM-of-products aggregates, updating relation R:

* every view is linear in the rows of its scanned relation, so a view
  scanning R is maintained by running its *unchanged* scan program over the
  delta tuples only, with per-row weights +1 (insert) / -1 (delete) folded
  into the validity mask (``lowering/*.run_step(weights=...)``);
* a view scanning S ≠ R sees R through **exactly one** child edge — join-tree
  subtrees below distinct children are disjoint, so no product ever has two
  R-dependent factors and the product rule collapses to first order:
  ``Δ(terms × c_R × rest) = terms × Δc_R × rest`` with ``rest`` unchanged.
  The delta view rescans S, gathering the child's *delta* array in place of
  its materialized value; products with no R-dependent factor are dropped
  (their delta is zero), and columns left empty contribute explicit zeros so
  the column layout — which parents index by position — is preserved.

Delta programs reuse the whole existing pipeline unchanged in the inner
loop: view programs are built by ``ir.build_group_program`` from filtered
``ViewDef``s, fused by ``schedule.build_schedule``, and executed by the
batch's configured lowering backend (``xla`` or ``pallas``); a delta scan is
just a scan over a smaller relation plus a ``+=`` into view state.

State is **epoch-versioned and device-resident** (DESIGN.md §8): every
epoch is an immutable :class:`EpochState` — view tensors plus
capacity-padded :class:`~repro.data.relations.ResidentRelation` buffers —
and ``apply`` validates the whole update batch up front, folds deltas and
advances relations *functionally* (JAX arrays are immutable, so the
previous epoch doubles as the read buffer at zero copy cost), then
publishes the next epoch with a single atomic reference swap.  Readers
(``results``, ``serve/views.py``) resolve an epoch once and see a frozen
snapshot; a failed batch publishes nothing and is a clean no-op.  A
steady-state tick is one cached jit call per updated relation — no host
round-trip of relation columns and no retrace.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Dict, FrozenSet, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.verify import (verification_enabled,
                                   verify_delta_program, verify_resident,
                                   verify_tick_program)
from repro.core.groups import ViewGroup
from repro.obs.metrics import Registry
from repro.obs.trace import span
from repro.core.ir import StepProgram, build_programs, fuse_programs
from repro.core.pushdown import AggColSpec, ViewDef
from repro.core.schedule import build_schedule
from repro.core.schema import DatabaseSchema
from repro.data.relations import (Database, DeltaBatchUpdate, Relation,
                                  ResidentRelation, ShardedResidentRelation,
                                  _resident_advance, check_delete_idx,
                                  check_update_columns, next_pow2)

_pow2 = next_pow2


def _replicate_resident(rr: ResidentRelation, mesh) -> ResidentRelation:
    """Pin a resident relation replicated across a mesh (explicit placement,
    so GSPMD never guesses and the transfer-guard contract stays clean)."""
    from jax.sharding import NamedSharding, PartitionSpec
    sh = NamedSharding(mesh, PartitionSpec())
    return ResidentRelation(
        rr.name, {a: jax.device_put(c, sh) for a, c in rr.buffers.items()},
        rr.n_valid, jax.device_put(rr.n_valid_dev, sh))


# ----------------------------------------------------------- delta derivation

def relation_reach(views: Mapping[int, ViewDef]) -> Dict[int, FrozenSet[str]]:
    """vid → set of base relations its value depends on (scanned relation
    plus, transitively, every child's).  Memoized walk over the view DAG."""
    memo: Dict[int, FrozenSet[str]] = {}

    def reach(vid: int) -> FrozenSet[str]:
        if vid not in memo:
            w = views[vid]
            s = {w.rel}
            for col in w.agg_cols:
                for prod in col.products:
                    for ref in prod.child_cols:
                        s |= reach(ref.vid)
            memo[vid] = frozenset(s)
        return memo[vid]

    for vid in views:
        reach(vid)
    return memo


@dataclasses.dataclass(frozen=True)
class DeltaStep:
    """One fused scan step of a delta program.  ``scans_delta`` steps scan
    the update's delta tuples (weighted); the rest rescan their full base
    relation against child *deltas*."""

    prog: StepProgram
    rel: str
    scans_delta: bool


@dataclasses.dataclass(frozen=True)
class DeltaProgram:
    """Compiled maintenance plan for updates to one base relation."""

    rel: str
    affected: FrozenSet[int]        # vids whose state the update changes
    steps: Tuple[DeltaStep, ...]
    base_rels: Tuple[str, ...]      # relations rescanned in full
    state_vids: Tuple[int, ...]     # state entries the runner needs as input

    @property
    def n_scans(self) -> int:
        return len(self.steps)

    def summary(self) -> str:
        return (f"Δ{self.rel}: {len(self.affected)} views, "
                f"{self.n_scans} scans ({sum(s.scans_delta for s in self.steps)} delta, "
                f"rescans {sorted(self.base_rels)})")


@dataclasses.dataclass(frozen=True)
class TickStep:
    """One step of a tick, with its runtime obligations made declarative:
    ``weighted`` steps fold the update's signed ±1 multiplicities into the
    validity mask; ``partitioned`` steps scanned row-partitioned buffers, so
    their view deltas in ``psum_vids`` must all-reduce over the mesh axis
    *before* any later gather or the state fold reads them."""

    prog: StepProgram
    rel: str
    scans_delta: bool
    weighted: bool
    partitioned: bool
    psum_vids: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class TickProgram:
    """The declarative form of one relation's tick: which steps apply
    update weights, which psum, and which vids the state fold covers.  Both
    tick runners (local and ``shard_map``) execute exactly this artifact,
    so the psum-before-fold soundness rule (DESIGN.md §8) is data the
    verifier can check, not control flow buried in a traced closure."""

    rel: str
    axis: Optional[str]             # mesh axis name (None = unsharded)
    shard_rel: Optional[str]        # row-partitioned relation (None = local)
    steps: Tuple[TickStep, ...]
    fold_vids: Tuple[int, ...]      # state entries the fold writes

    def summary(self) -> str:
        n_psum = sum(len(ts.psum_vids) for ts in self.steps)
        shard = f", {n_psum} psums @{self.axis}" if self.shard_rel else ""
        return (f"tick Δ{self.rel}: {len(self.steps)} steps, "
                f"folds {len(self.fold_vids)} views{shard}")


def build_tick_program(dp: DeltaProgram, shard_rel: Optional[str] = None,
                       axis: Optional[str] = None) -> TickProgram:
    """Lower a delta program to its tick form under a placement: weights
    ride exactly the delta-tuple scans, and under a mesh every step that
    scans the partitioned relation (tier-1 delta scan of partitioned delta
    tuples, or tier-2 rescan of the partitioned base rows) psums all of its
    view deltas immediately.  Pure — safe to build and verify without a
    mesh or any device state."""
    steps = []
    for st in dp.steps:
        partitioned = shard_rel is not None and st.rel == shard_rel
        steps.append(TickStep(
            prog=st.prog, rel=st.rel, scans_delta=st.scans_delta,
            weighted=st.scans_delta, partitioned=partitioned,
            psum_vids=(tuple(vp.vid for vp in st.prog.views)
                       if partitioned else ())))
    return TickProgram(rel=dp.rel, axis=axis, shard_rel=shard_rel,
                       steps=tuple(steps),
                       fold_vids=tuple(sorted(dp.affected)))


def build_delta_program(schema: DatabaseSchema, views: Mapping[int, ViewDef],
                        rel: str, fuse: bool = True) -> DeltaProgram:
    """Derive the delta program for updates to base relation ``rel``."""
    reach = relation_reach(views)
    affected = frozenset(vid for vid, rs in reach.items() if rel in rs)
    if not affected:
        return DeltaProgram(rel=rel, affected=affected, steps=(),
                            base_rels=(), state_vids=())

    # delta view defs: tier-1 (scan rel) keep every product — they are linear
    # in rel's rows; tier-2 keep only products with an affected child factor
    delta_defs: Dict[int, ViewDef] = {}
    for vid in affected:
        w = views[vid]
        if w.rel == rel:
            delta_defs[vid] = w
            continue
        cols = []
        for colspec in w.agg_cols:
            kept = []
            for p in colspec.products:
                hit = [r for r in p.child_cols if r.vid in affected]
                if not hit:
                    continue            # R-independent product: delta is zero
                if len(hit) > 1:
                    # would need second-order delta terms; cannot happen for
                    # join-tree pushdown (subtrees below distinct children
                    # are disjoint), so treat it as a soundness bug
                    raise ValueError(
                        f"view {vid}: product with {len(hit)} {rel}-dependent "
                        "factors — first-order delta derivation is unsound")
                kept.append(p)
            cols.append(AggColSpec(tuple(kept)))
        delta_defs[vid] = ViewDef(
            vid=w.vid, edge=w.edge, rel=w.rel, group_by=w.group_by,
            local_keys=w.local_keys, pulled_keys=w.pulled_keys, agg_cols=cols)

    # group the delta sub-DAG: peel dependency levels restricted to affected
    # vids, bucketing ready views per scanned relation (mirrors group_views)
    deps = {vid: {r.vid for col in delta_defs[vid].agg_cols
                  for p in col.products for r in p.child_cols} & affected
            for vid in affected}
    groups: List[ViewGroup] = []
    vid_group: Dict[int, int] = {}
    remaining, done = set(affected), set()
    level = 0
    while remaining:
        ready = sorted(v for v in remaining if deps[v] <= done)
        if not ready:
            raise ValueError("cyclic delta-view dependencies (bug)")
        buckets: Dict[str, List[int]] = {}
        for vid in ready:
            buckets.setdefault(delta_defs[vid].rel, []).append(vid)
        for r in sorted(buckets):
            vids = tuple(buckets[r])
            gdeps = sorted({vid_group[d] for vid in vids for d in deps[vid]})
            gid = len(groups)
            groups.append(ViewGroup(gid=gid, rel=r, vids=vids, level=level,
                                    deps=tuple(gdeps)))
            for vid in vids:
                vid_group[vid] = gid
        done.update(ready)
        remaining.difference_update(ready)
        level += 1

    # lower through the existing IR builder + shared-scan scheduler; child
    # gather specs only need the (unchanged) group_by of each child ViewDef
    merged = dict(views)
    merged.update(delta_defs)
    progs = build_programs(schema, merged, groups)
    sched = build_schedule(groups, fuse=fuse)
    # a fused step scans one relation, so it is either all-delta (rel == R:
    # every view scanning R is tier-1) or all-base — never mixed
    steps = tuple(DeltaStep(prog=fuse_programs([progs[gid] for gid in st.gids]),
                            rel=st.rel, scans_delta=(st.rel == rel))
                  for st in sched.steps)
    base_rels = tuple(sorted({s.rel for s in steps if not s.scans_delta}))
    gathered = {gs.vid for s in steps for gs in s.prog.gathers}
    return DeltaProgram(rel=rel, affected=affected, steps=steps,
                        base_rels=base_rels,
                        state_vids=tuple(sorted(affected | gathered)))


# -------------------------------------------------------------- maintenance

class EpochEvictedError(KeyError):
    """A read hit an epoch whose pin was evicted under the server's
    ``max_pinned_epochs`` budget.  Long-lived pins retain whole epochs of
    device memory, so the budget force-releases the least-recently-used pin
    once exceeded; a reader holding an evicted handle must re-snapshot."""


@dataclasses.dataclass(frozen=True)
class EpochState:
    """One immutable published version of the maintained state: every view
    tensor plus every base relation's device-resident buffers.  Epochs are
    never mutated — ``apply`` builds the successor functionally and swaps a
    single reference, so any number of readers holding (or pinning) an
    epoch see a frozen, mutually consistent snapshot for free."""

    epoch: int
    step: int
    views: Mapping[int, jnp.ndarray]
    relations: Mapping[str, ResidentRelation]

    def database(self, schema) -> Database:
        return Database(schema, {name: rr.to_relation()
                                 for name, rr in self.relations.items()})


class MaintainedBatch:
    """A compiled aggregate batch with epoch-versioned, device-resident view
    state and per-base-relation delta programs —
    ``Engine.compile_incremental``'s return type.

        mb = eng.compile_incremental(queries)
        mb.init(db)                     # full scan; state device-resident
        mb.apply(update)                # work ∝ |update|; publishes epoch+1
        results = mb.results()          # current epoch
        e = mb.pin(); ... mb.results(epoch=e) ...; mb.unpin(e)

    ``apply`` is transactional: the **whole** update batch is validated
    before anything folds, the fold itself only builds new arrays (one
    cached jit call per updated relation: delta-tuple assembly, delta scans,
    and the relation's scatter/compaction advance all fused), and the new
    epoch becomes visible in a single atomic swap — so an invalid batch is
    a clean no-op and readers never observe half-folded state.

    Runners are cached on (relation, pad-bucket, capacity) keys — delta
    batches pad to the next power of two with zero-weight rows and resident
    buffers grow by doubling, so a stream of varying batch sizes against
    growing relations compiles at most log₂ distinct executables per
    relation and a steady-state tick retraces nothing.

    With a ``mesh`` the batch is **sharded** (DESIGN.md §6/§8): one relation
    (``shard_rel``, default the largest) lives row-partitioned over
    ``mesh_axis`` as a :class:`ShardedResidentRelation`, the rest replicate,
    and each relation tick is a single cached ``jit(shard_map(...))`` —
    delta tuples partition like their relation, every step's view tensors
    psum right after the step that scans the sharded relation (before the
    state fold, so replicated state stays replicated), and
    compaction/append never leave their shard.  The zero-host-transfer /
    log₂-retrace contract is unchanged.
    """

    def __init__(self, batch, mesh=None, mesh_axis: str = "data",
                 shard_rel: Optional[str] = None):
        self.batch = batch
        self.plan = batch.plan
        if self.plan.batched_params:
            raise ValueError(
                "incremental maintenance does not support param-batched "
                f"plans (batched params: {sorted(self.plan.batched_params)})")
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        if mesh is not None and mesh_axis not in mesh.shape:
            raise ValueError(f"mesh has no axis {mesh_axis!r} "
                             f"(axes: {tuple(mesh.shape)})")
        self.shard_rel = shard_rel    # resolved at first init/load_state
        self._current: Optional[EpochState] = None
        #: delta scan steps executed across all applied updates
        self.n_delta_scan_steps = 0
        #: tick-runner traces (steady-state applies must not grow this)
        self.n_fold_traces = 0
        self._delta_programs: Dict[str, DeltaProgram] = {}
        self._tick_programs: Dict[str, TickProgram] = {}
        # static verification (DESIGN.md §12): checked once per compiled
        # artifact at build time — never on the per-tick hot path
        self._verify = verification_enabled(self.plan.config.verify_plans)
        #: artifact name -> :class:`~repro.analysis.verify.VerificationReport`
        #: for every delta/tick program verified so far (``explain()`` shows
        #: them); empty when verification is off
        self.last_verifications: Dict[str, object] = {}
        self._runners: Dict[Tuple, object] = {}
        self._init_runners: Dict[Tuple, object] = {}
        self._extract = jax.jit(self.plan.extract_outputs)
        # epoch -> [EpochState, refs]; ordered LRU-first (reads/pins
        # move_to_end) so the pin budget can evict the coldest epoch
        self._pins: "collections.OrderedDict[int, list]" = \
            collections.OrderedDict()
        self._pin_lock = threading.Lock()
        #: pin budget: beyond this many distinct pinned epochs the LRU pin
        #: is force-released (None = unbounded; serve/views.py sets it)
        self.max_pinned_epochs: Optional[int] = None
        #: pins force-released under the budget (reads of those epochs
        #: raise :class:`EpochEvictedError`)
        self.n_evicted_pins = 0
        # evicted epoch ids, newest last, for clear read errors; bounded by
        # trimming the oldest records into _evicted_floor, so the
        # unpin-after-evict no-op contract survives arbitrarily long streams
        self._evicted: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        self._evicted_floor = -1      # every evicted epoch <= this is trimmed
        #: per-batch telemetry (DESIGN.md §11): ``ivm.tick_us`` is the host
        #: dispatch wall of each ``apply`` — no sync, so async dispatch cost,
        #: which is what the steady-state contract allows us to measure
        self.metrics = Registry()
        self._tick_hist = self.metrics.histogram("ivm.tick_us")

    # -- lifecycle -----------------------------------------------------------

    def _require(self) -> EpochState:
        es = self._current
        if es is None:
            raise ValueError("call init(db) first")
        return es

    @property
    def initialized(self) -> bool:
        """Whether an epoch has been published (init/restore has run)."""
        return self._current is not None

    @property
    def epoch(self) -> int:
        """Id of the currently published epoch."""
        return self._require().epoch

    @property
    def step(self) -> int:
        """Update batches applied since (or encoded in) the last init/restore."""
        es = self._current
        return es.step if es is not None else 0

    @property
    def state(self) -> Optional[Dict[int, jnp.ndarray]]:
        """Current epoch's view tensors keyed by vid (back-compat read API)."""
        es = self._current
        return dict(es.views) if es is not None else None

    @property
    def db(self) -> Database:
        """Current database snapshot (base relations after applied updates;
        columns are lazy device slices of the resident buffers)."""
        return self._require().database(self.batch.schema)

    def _resolve_shard_rel(self, sizes: Mapping[str, int]) -> str:
        """Fix the partitioned relation (config override or the largest) the
        first time state materializes; frozen afterwards so runner caches
        and epochs agree."""
        if self.shard_rel is None:
            self.shard_rel = max(sorted(sizes), key=lambda r: sizes[r])
        elif self.shard_rel not in sizes:
            raise ValueError(f"shard_rel {self.shard_rel!r} is not a "
                             f"relation (have: {sorted(sizes)})")
        return self.shard_rel

    def _make_resident(self, rel: Relation):
        """Relation → device-resident form under the batch's placement."""
        if self.mesh is None:
            return ResidentRelation.from_relation(rel)
        if rel.name == self.shard_rel:
            return ShardedResidentRelation.from_relation(
                rel, self.mesh, self.mesh_axis)
        return _replicate_resident(ResidentRelation.from_relation(rel),
                                   self.mesh)

    def init(self, db: Database, params=None) -> Dict[str, jnp.ndarray]:
        """Full recompute: move every base relation into capacity-padded
        device buffers and materialize every view array, then publish the
        first epoch.  Re-init on a live batch publishes a fresh epoch (the
        epoch clock keeps counting so pinned readers stay unambiguous)."""
        with span("ivm.init"):
            if self.mesh is not None:
                self._resolve_shard_rel(db.sizes())
            rels = {name: self._make_resident(r)
                    for name, r in db.relations.items()}
            if self._verify:
                for rr in rels.values():
                    verify_resident(rr)
            params = dict(params or {})
            caps = {name: rr.capacity for name, rr in rels.items()}
            runner = self._init_runner(caps, rels, params)
            cols = {name: dict(rr.buffers) for name, rr in rels.items()}
            n_valid = {name: rr.n_valid_dev for name, rr in rels.items()}
            views = dict(runner(cols, params, n_valid))
            prev = self._current
            self._current = EpochState(epoch=prev.epoch + 1 if prev else 0,
                                       step=0, views=views, relations=rels)
        return self.results()

    def _init_runner(self, caps: Mapping[str, int], rels, params):
        """Cached jitted full-scan runner.  Under a mesh it is a
        ``shard_map``: the sharded relation scans its local rows against its
        local ``n_valid``, every other scan sees replicated inputs, and the
        sharded relation's view tensors psum right after their step (the
        batch path's rule, distributed.py) so outputs land replicated."""
        key = (tuple(sorted(caps.items())), tuple(sorted(params)),
               self.mesh is None or ("mesh", self.mesh_axis, self.shard_rel))
        if key in self._init_runners:
            return self._init_runners[key]
        run = self.plan.bind_arrays(caps)   # sharded rel: per-shard capacity
        if self.mesh is None:
            self._init_runners[key] = jax.jit(
                lambda c, p, nv: run(c, p, n_valid=nv))
            return self._init_runners[key]
        from jax.sharding import PartitionSpec as P
        mesh, axis, srel = self.mesh, self.mesh_axis, self.shard_rel
        col_specs = {name: {a: (P(axis) if name == srel else P())
                            for a in rels[name].buffers} for name in rels}
        nv_specs = {name: (P(axis) if name == srel else P()) for name in rels}

        def local(cols, p, nv):
            nvv = {name: (v[0] if name == srel else v)
                   for name, v in nv.items()}
            return run(cols, p, n_valid=nvv, psum_axes={srel: axis})

        self._init_runners[key] = jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=(col_specs, P(), nv_specs),
            out_specs=P(), check_vma=False))
        return self._init_runners[key]

    def epoch_state(self, epoch: Optional[int] = None) -> EpochState:
        """Resolve an epoch to its immutable state: the published epoch by
        default, or a previously pinned one."""
        es = self._require()
        if epoch is None or epoch == es.epoch:
            return es
        with self._pin_lock:
            ent = self._pins.get(epoch)
            if ent is not None:
                self._pins.move_to_end(epoch)     # LRU touch
                return ent[0]
            if epoch in self._evicted or epoch <= self._evicted_floor:
                raise EpochEvictedError(
                    f"epoch {epoch} was evicted under the pin budget "
                    f"(max_pinned_epochs={self.max_pinned_epochs}); its "
                    "device state has been released — take a fresh "
                    "snapshot/pin to read current state")
        raise KeyError(
            f"epoch {epoch} is neither current ({es.epoch}) nor pinned — "
            "pin() an epoch before reading it across updates")

    def results(self, epoch: Optional[int] = None) -> Dict[str, jnp.ndarray]:
        """Query outputs read from one epoch's state (no relation scans).
        Always snapshot-consistent: every output comes from the same epoch,
        regardless of concurrently folding updates."""
        return dict(self._extract(dict(self.epoch_state(epoch).views)))

    # -- epoch pinning (serve/views.py) --------------------------------------

    def pin(self) -> int:
        """Retain the current epoch for consistent reads across updates;
        returns its id.  Balance every pin with :meth:`unpin` — the epoch's
        device arrays stay alive while pinned.  With a ``max_pinned_epochs``
        budget set, pinning past it force-releases the least-recently-used
        pinned epoch (its readers get :class:`EpochEvictedError`)."""
        es = self._require()
        with self._pin_lock:
            ent = self._pins.setdefault(es.epoch, [es, 0])
            ent[1] += 1
            self._pins.move_to_end(es.epoch)
            budget = self.max_pinned_epochs
            while budget is not None and len(self._pins) > budget:
                victim, _ = self._pins.popitem(last=False)   # LRU
                self._evicted[victim] = None
                self.n_evicted_pins += 1
                while len(self._evicted) > 1024:             # bound bookkeeping
                    old, _ = self._evicted.popitem(last=False)
                    self._evicted_floor = max(self._evicted_floor, old)
        return es.epoch

    def unpin(self, epoch: int) -> None:
        with self._pin_lock:
            ent = self._pins.get(epoch)
            if ent is None:
                if epoch in self._evicted or epoch <= self._evicted_floor:
                    return          # pin was force-released by the budget
                raise KeyError(f"epoch {epoch} is not pinned")
            ent[1] -= 1
            if ent[1] <= 0:
                del self._pins[epoch]

    @contextlib.contextmanager
    def pinned(self):
        """``with mb.pinned() as epoch:`` — pin for the block's duration."""
        epoch = self.pin()
        try:
            yield epoch
        finally:
            self.unpin(epoch)

    @property
    def n_pinned_epochs(self) -> int:
        with self._pin_lock:
            return len(self._pins)

    def pinned_epochs(self) -> Tuple[int, ...]:
        """Currently pinned epoch ids (ascending) — the server derives
        epoch lag (head minus oldest pin) from this."""
        with self._pin_lock:
            return tuple(sorted(self._pins))

    # -- delta path ----------------------------------------------------------

    def delta_program(self, rel: str) -> DeltaProgram:
        """The (cached) maintenance plan for updates to ``rel``."""
        if rel not in self._delta_programs:
            dp = build_delta_program(
                self.batch.schema, self.plan.views, rel,
                fuse=self.plan.config.fuse_scans)
            if self._verify:
                self.last_verifications[f"Δ{rel}"] = \
                    verify_delta_program(self.plan, dp)
            self._delta_programs[rel] = dp
        return self._delta_programs[rel]

    def tick_program(self, rel: str) -> TickProgram:
        """The (cached, verified) tick form of ``rel``'s delta program
        under this batch's placement — the artifact both tick runners
        execute."""
        if rel not in self._tick_programs:
            dp = self.delta_program(rel)
            shard = self.shard_rel if self.mesh is not None else None
            axis = self.mesh_axis if self.mesh is not None else None
            tp = build_tick_program(dp, shard_rel=shard, axis=axis)
            if self._verify:
                self.last_verifications[f"tick Δ{rel}"] = \
                    verify_tick_program(tp, dp)
            self._tick_programs[rel] = tp
        return self._tick_programs[rel]

    def apply(self, update: DeltaBatchUpdate, params=None) -> Dict[str, jnp.ndarray]:
        """Fold an update batch into view state and the resident relations,
        publishing the next epoch.  Relations are processed sequentially in
        sorted order; the published state is exactly ``init`` on the
        post-update database (up to fp32 summation order).

        Transactional: *every* relation's delta is validated before any
        state folds, so a rejected batch raises without publishing and the
        current epoch is untouched.  Thread safety: any number of readers
        may overlap with one ``apply``; concurrent writers need external
        serialization (``serve.views.ViewServer`` provides it)."""
        cur = self._require()
        params = dict(params or {})
        t_tick = time.perf_counter()

        with span("ivm.apply", epoch=cur.epoch):
            # phase 1 — validate the whole batch against the current epoch
            # (host-side numpy on the update only; state untouched)
            with span("ivm.validate"):
                prepared = []
                for rel in update.relations():
                    if rel not in cur.relations:
                        raise ValueError(
                            f"update targets unknown relation {rel!r}")
                    rr = cur.relations[rel]
                    d = update.updates[rel]
                    ins = (check_update_columns(self.batch.schema, rel,
                                                d.inserts)
                           if d.n_inserts else None)
                    del_idx = (check_delete_idx(rel, d.delete_idx, rr.n_valid)
                               if d.n_deletes else None)
                    prepared.append((rel, ins, del_idx))

            # phase 2 — functional fold: new arrays only, current epoch
            # readable throughout; the update's columns cross to the device
            # exactly once (explicit device_put), relation columns never
            # cross back
            views = dict(cur.views)
            rels = dict(cur.relations)
            n_scans = 0
            for rel, ins, del_idx in prepared:
                with span("ivm.tick", rel=rel):
                    rr = rels[rel]
                    n_ins = (0 if ins is None
                             else int(next(iter(ins.values())).shape[0]))
                    n_del = 0 if del_idx is None else len(del_idx)
                    if self.mesh is not None:
                        n_scans += self._apply_rel_mesh(
                            views, rels, rel, ins, del_idx, n_ins, n_del,
                            params)
                        continue
                    ins_pad = _pow2(n_ins) if n_ins else 0
                    del_pad = _pow2(n_del) if n_del else 0
                    ins_dev = {a: jax.device_put(np.pad(c, (0, ins_pad - n_ins)))
                               for a, c in (ins or {}).items()}
                    # delete pads point past the valid region: harmless for
                    # the compaction scatter, zero-filled by the delta gather
                    del_dev = jax.device_put(
                        np.pad(del_idx.astype(np.int32), (0, del_pad - n_del),
                               constant_values=rr.capacity)
                        if n_del else np.zeros((0,), np.int32))
                    rr = rr.grown(rr.n_valid - n_del + n_ins)
                    rels[rel] = rr
                    dp = self.delta_program(rel)
                    if dp.steps:
                        n_ins_dev = jax.device_put(np.asarray(n_ins, np.int32))
                        n_del_dev = jax.device_put(np.asarray(n_del, np.int32))
                        runner = self._tick_runner(dp, rr.capacity, ins_pad,
                                                   del_pad, rels, params)
                        state_in = {vid: views[vid] for vid in dp.state_vids}
                        base_cols = {r: dict(rels[r].buffers)
                                     for r in dp.base_rels}
                        base_n = {r: rels[r].n_valid_dev for r in dp.base_rels}
                        new_views, bufs, n_valid_dev = runner(
                            state_in, dict(rr.buffers), rr.n_valid_dev,
                            base_cols, base_n, ins_dev, del_dev, n_ins_dev,
                            n_del_dev, params)
                        views.update(new_views)
                        rels[rel] = ResidentRelation(rel, bufs,
                                                     rr.n_valid - n_del + n_ins,
                                                     n_valid_dev)
                        n_scans += dp.n_scans
                    else:
                        rels[rel] = rr.advance(ins_dev, del_dev, n_ins, n_del)

            # phase 3 — atomic publish; capacity contracts re-checked on the
            # advanced relations first (host metadata only — no sync)
            if self._verify:
                for rel, _, _ in prepared:
                    verify_resident(rels[rel])
            with span("ivm.publish"):
                self._current = EpochState(epoch=cur.epoch + 1,
                                           step=cur.step + 1,
                                           views=views, relations=rels)
                self.n_delta_scan_steps += n_scans
        # host dispatch wall of the whole tick (validate + fold + publish);
        # no block_until_ready — the no-sync instrumentation rule
        self._tick_hist.observe((time.perf_counter() - t_tick) * 1e6)
        return self.results()

    def _tick_runner(self, dp: DeltaProgram, cap: int, ins_pad: int,
                     del_pad: int, rels: Mapping[str, ResidentRelation],
                     params):
        """One jitted device program for a whole relation tick: assemble the
        delta tuples ([insert block | deleted-row gather block], pads carry
        weight 0), run the delta scans, add into view state, and advance the
        relation's resident buffers — so a steady-state ``apply`` is a
        single cached dispatch with no host transfer of relation columns.

        Cache key: (relation, pad buckets, own + rescanned capacities) —
        true row counts and delta sizes enter as traced scalars."""
        base_caps = {r: rels[r].capacity for r in dp.base_rels}
        key = (dp.rel, cap, ins_pad, del_pad,
               tuple(sorted(base_caps.items())), tuple(sorted(params)))
        if key in self._runners:
            return self._runners[key]
        # per-step blocking resolves at runner-build time (outside the jit)
        # against |update|-bucketed delta signatures — "auto" no longer
        # degrades to the static defaults on the tick path
        backend = self.plan.backend
        n_delta = ins_pad + del_pad
        tp = self.tick_program(dp.rel)
        step_cfgs = self.plan.resolve_delta_configs(
            dp.steps, [n_delta if st.scans_delta else base_caps[st.rel]
                       for st in dp.steps])

        def run(state, rel_bufs, rel_n, base_cols, base_n, ins, del_idx,
                n_ins, n_del, p):
            self.n_fold_traces += 1   # python side effect: counts traces only
            delta_cols = {}
            for a, buf in rel_bufs.items():
                segs = []
                if ins_pad:
                    segs.append(ins[a].astype(buf.dtype))
                if del_pad:
                    segs.append(jnp.take(buf, del_idx, mode="fill",
                                         fill_value=0))
                delta_cols[a] = (jnp.concatenate(segs) if len(segs) > 1
                                 else segs[0])
            w = []
            if ins_pad:
                w.append((jnp.arange(ins_pad) < n_ins).astype(jnp.float32))
            if del_pad:
                w.append(-(jnp.arange(del_pad) < n_del).astype(jnp.float32))
            weights = jnp.concatenate(w) if len(w) > 1 else w[0]
            # arrays doubles as state reads (unaffected children) and delta
            # writes: a step's finalize overwrites its vid, so a later
            # gather of an affected child reads its *delta*
            arrays = dict(state)
            for ts, cfg in zip(tp.steps, step_cfgs):
                if ts.scans_delta:
                    backend.run_step(ts.prog, delta_cols, arrays, p,
                                     n_valid=n_delta, offset=0, config=cfg,
                                     weights=weights if ts.weighted
                                     else None)
                else:
                    backend.run_step(ts.prog, base_cols[ts.rel], arrays, p,
                                     n_valid=base_n[ts.rel], offset=0,
                                     config=cfg)
            new_views = {vid: state[vid] + arrays[vid]
                         for vid in tp.fold_vids}
            new_bufs, new_n = _resident_advance(
                rel_bufs, rel_n, ins, del_idx, n_ins, n_del,
                compact=bool(del_pad))
            return new_views, new_bufs, new_n

        self._runners[key] = jax.jit(run)
        return self._runners[key]

    # -- sharded delta path (DESIGN.md §6/§8) --------------------------------

    def _apply_rel_mesh(self, views, rels, rel, ins, del_idx, n_ins, n_del,
                        params) -> int:
        """One relation's tick under a mesh: stage the update (explicit
        device_put — partitioned inserts / replicated deletes for the
        sharded relation, replicated both for the rest), then run the cached
        ``jit(shard_map)`` tick runner.  Returns the delta scan count."""
        from repro.core import distributed as dist
        mesh, axis, srel = self.mesh, self.mesh_axis, self.shard_rel
        ndev = int(mesh.shape[axis])
        rr = rels[rel]
        sharded = rel == srel
        if sharded:
            # inserts go round-robin to shards; deletes travel replicated as
            # *sorted global oracle positions* and route on device by gid
            blk = _pow2(-(-n_ins // ndev)) if n_ins else 0
            ins_pad = blk * ndev
            del_pad = _pow2(n_del) if n_del else 0
            if n_ins:
                perm = dist.strided_insert_layout(blk, ndev)
                ins_dev = {a: dist.put_sharded(
                    np.pad(c, (0, ins_pad - n_ins))[perm], mesh, axis)
                    for a, c in ins.items()}
            else:
                ins_dev = {}
            del_dev = dist.put_replicated(
                np.pad(np.sort(del_idx).astype(np.int32),
                       (0, del_pad - n_del),
                       constant_values=dist.GID_SENTINEL)
                if n_del else np.zeros((0,), np.int32), mesh)
            # growth check against the per-shard upper bound; sync the exact
            # (ndev,) counters — metadata, not columns — only on overflow
            shares = np.maximum(
                (n_ins - np.arange(ndev) + ndev - 1) // ndev, 0)
            if _pow2(max(int((rr.n_valid_ub + shares).max()), 1)) > rr.capacity:
                rr = rr.synced()
                rr = rr.grown(int((rr.n_valid_ub + shares).max()))
        else:
            ins_pad = _pow2(n_ins) if n_ins else 0
            del_pad = _pow2(n_del) if n_del else 0
            ins_dev = {a: dist.put_replicated(
                np.pad(c, (0, ins_pad - n_ins)), mesh)
                for a, c in (ins or {}).items()}
            del_dev = dist.put_replicated(
                np.pad(del_idx.astype(np.int32), (0, del_pad - n_del),
                       constant_values=rr.capacity)
                if n_del else np.zeros((0,), np.int32), mesh)
            rr = rr.grown(rr.n_valid - n_del + n_ins)
        rels[rel] = rr
        dp = self.delta_program(rel)
        runner = self._tick_runner_mesh(dp, rr.capacity, ins_pad, del_pad,
                                        rels, params)

        def scal(v):
            return dist.put_replicated(np.asarray(v, np.int32), mesh)

        state_in = {vid: views[vid] for vid in dp.state_vids}
        base_cols = {r: dict(rels[r].buffers) for r in dp.base_rels}
        base_n = {r: rels[r].n_valid_dev for r in dp.base_rels}
        if sharded:
            new_views, bufs, gids, nv_dev = runner(
                state_in, dict(rr.buffers), rr.gids, rr.n_valid_dev,
                base_cols, base_n, ins_dev, del_dev, scal(n_ins),
                scal(n_del), scal(rr.n_valid - n_del), params)
            rels[rel] = dataclasses.replace(
                rr, buffers=bufs, gids=gids,
                n_valid=rr.n_valid - n_del + n_ins,
                n_valid_ub=rr.n_valid_ub + shares, n_valid_dev=nv_dev)
        else:
            new_views, bufs, nv_dev = runner(
                state_in, dict(rr.buffers), rr.n_valid_dev, base_cols,
                base_n, ins_dev, del_dev, scal(n_ins), scal(n_del), params)
            rels[rel] = ResidentRelation(rel, bufs,
                                         rr.n_valid - n_del + n_ins, nv_dev)
        views.update(new_views)
        return dp.n_scans

    def _tick_runner_mesh(self, dp: DeltaProgram, cap: int, ins_pad: int,
                          del_pad: int, rels, params):
        """The sharded counterpart of :meth:`_tick_runner`: one cached
        ``jit(shard_map)`` per (relation, pad buckets, capacities) running
        delta-tuple assembly, the delta scans, the psum-before-fold combine,
        and the shard-local buffer advance in a single dispatch.

        Partitioned view deltas psum immediately after any step that scans
        the sharded relation — a tier-1 delta scan of partitioned delta
        tuples, or a tier-2 rescan of the partitioned base rows — so every
        later gather and the final ``state + delta`` fold read replicated
        values and the published epoch stays replicated (the soundness
        argument of DESIGN.md §8)."""
        from jax.sharding import PartitionSpec as P
        from repro.core import distributed as dist
        mesh, axis, srel = self.mesh, self.mesh_axis, self.shard_rel
        ndev = int(mesh.shape[axis])
        sharded = dp.rel == srel
        base_caps = {r: rels[r].capacity for r in dp.base_rels}
        key = ("mesh", dp.rel, cap, ins_pad, del_pad,
               tuple(sorted(base_caps.items())), tuple(sorted(params)))
        if key in self._runners:
            return self._runners[key]
        backend = self.plan.backend
        blk = ins_pad // ndev if sharded else ins_pad
        n_delta = blk + del_pad
        tp = self.tick_program(dp.rel)
        step_cfgs = self.plan.resolve_delta_configs(
            dp.steps, [n_delta if st.scans_delta else base_caps[st.rel]
                       for st in dp.steps])
        base_col_specs = {r: {a: (P(axis) if r == srel else P())
                              for a in rels[r].buffers} for r in dp.base_rels}
        base_n_specs = {r: (P(axis) if r == srel else P())
                        for r in dp.base_rels}

        def scan_steps(state, delta_cols, weights, base_cols, base_n, p):
            arrays = dict(state)
            for ts, cfg in zip(tp.steps, step_cfgs):
                if ts.scans_delta:
                    backend.run_step(ts.prog, delta_cols, arrays, p,
                                     n_valid=n_delta, offset=0, config=cfg,
                                     weights=weights if ts.weighted
                                     else None)
                else:
                    bn = base_n[ts.rel]
                    backend.run_step(ts.prog, base_cols[ts.rel], arrays, p,
                                     n_valid=bn[0] if ts.partitioned else bn,
                                     offset=0, config=cfg)
                # psum-before-fold: partitioned-row scans all-reduce their
                # view deltas before anything downstream reads them
                for vid in ts.psum_vids:
                    arrays[vid] = jax.lax.psum(arrays[vid], tp.axis)
            return {vid: state[vid] + arrays[vid] for vid in tp.fold_vids}

        def delta_block(rel_bufs, ins, slots, n_ins_loc, n_del_loc, b):
            delta_cols = {}
            for a, buf in rel_bufs.items():
                segs = []
                if b:
                    segs.append(ins[a].astype(buf.dtype))
                if del_pad:
                    segs.append(jnp.take(buf, slots, mode="fill",
                                         fill_value=0))
                delta_cols[a] = (jnp.concatenate(segs) if len(segs) > 1
                                 else segs[0])
            w = []
            if b:
                w.append((jnp.arange(b) < n_ins_loc).astype(jnp.float32))
            if del_pad:
                w.append(-(jnp.arange(del_pad) < n_del_loc).astype(jnp.float32))
            return delta_cols, (jnp.concatenate(w) if len(w) > 1 else w[0])

        if sharded:
            def run(state, rel_bufs, gid, rel_n, base_cols, base_n, ins,
                    dels, n_ins, n_del, gid_base, p):
                self.n_fold_traces += 1   # python side effect: traces only
                shard = jax.lax.axis_index(axis).astype(jnp.int32)
                nv = rel_n[0]
                live = jnp.arange(cap, dtype=jnp.int32) < nv
                if del_pad:
                    hit, slots, n_del_loc = dist.local_delete(
                        gid, live, dels, del_pad, cap)
                else:
                    hit = jnp.zeros((cap,), bool)
                    slots, n_del_loc = None, jnp.int32(0)
                n_ins_loc = (dist.local_insert_count(n_ins, shard, ndev, blk)
                             if blk else jnp.int32(0))
                delta_cols, weights = delta_block(rel_bufs, ins, slots,
                                                  n_ins_loc, n_del_loc, blk)
                new_views = scan_steps(state, delta_cols, weights,
                                       base_cols, base_n, p)
                new_bufs, new_gid, new_nv = dist.local_advance(
                    rel_bufs, gid, nv, hit, dels, ins, gid_base, shard,
                    ndev, blk, n_ins_loc, n_del_loc, compact=bool(del_pad))
                return new_views, new_bufs, new_gid, new_nv[None]

            in_specs = (P(), P(axis), P(axis), P(axis), base_col_specs,
                        base_n_specs, P(axis), P(), P(), P(), P(), P())
            out_specs = (P(), P(axis), P(axis), P(axis))
        else:
            def run(state, rel_bufs, rel_n, base_cols, base_n, ins, dels,
                    n_ins, n_del, p):
                self.n_fold_traces += 1   # python side effect: traces only
                delta_cols, weights = delta_block(rel_bufs, ins, dels,
                                                  n_ins, n_del, ins_pad)
                new_views = scan_steps(state, delta_cols, weights,
                                       base_cols, base_n, p)
                new_bufs, new_nv = _resident_advance(
                    rel_bufs, rel_n, ins, dels, n_ins, n_del,
                    compact=bool(del_pad))
                return new_views, new_bufs, new_nv

            in_specs = (P(), P(), P(), base_col_specs, base_n_specs,
                        P(), P(), P(), P(), P())
            out_specs = (P(), P(), P())

        self._runners[key] = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False))
        return self._runners[key]

    # -- explain/serve introspection -----------------------------------------

    def shard_topology(self) -> Optional[Dict[str, object]]:
        """Shard facts for ``explain()``/server stats: device count, the
        partitioned relation and its per-shard geometry, and the psum count
        one tick of each relation issues.  ``None`` when unsharded."""
        if self.mesh is None:
            return None
        ndev = int(self.mesh.shape[self.mesh_axis])
        top: Dict[str, object] = {
            "n_devices": ndev, "mesh_axis": self.mesh_axis,
            "shard_rel": self.shard_rel}
        es = self._current
        if es is not None and self.shard_rel in es.relations:
            rr = es.relations[self.shard_rel]
            top["rows"] = rr.n_valid
            top["rows_per_shard"] = -(-rr.n_valid // ndev)
            top["capacity_per_shard"] = rr.capacity
        top["psums_per_tick"] = {
            rel: sum(len(st.prog.views) for st in dp.steps
                     if st.rel == self.shard_rel)
            for rel, dp in sorted(self._delta_programs.items())}
        return top

    # -- snapshots (checkpoint/store.py hooks) -------------------------------

    def state_skeleton(self):
        """A pytree with the snapshot's structure (leaf values unused) —
        lets ``restore`` run before ``init``."""
        return {"epoch": 0, "step": 0,
                "views": {f"v{vid:04d}": 0 for vid in sorted(self.plan.views)},
                "relations": {name: {a: 0 for a in rs.attrs}
                              for name, rs in self.batch.schema.relations.items()}}

    def snapshot_state(self, epoch: Optional[int] = None):
        """Host pytree of one epoch's full maintained state: epoch/update
        counters, every view tensor, and the base relations trimmed to their
        valid rows.  Resolving the epoch up front makes the snapshot
        atomic — a concurrent ``apply`` publishing mid-serialization cannot
        tear it, and passing a pinned ``epoch`` checkpoints that exact
        version."""
        es = self.epoch_state(epoch)
        # one explicit device→host gather for the view tensors; sharded
        # relations likewise gather once inside to_relation()
        views_host = jax.device_get({f"v{vid:04d}": a
                                     for vid, a in sorted(es.views.items())})
        return {"epoch": np.asarray(es.epoch, np.int64),
                "step": np.asarray(es.step, np.int64),
                "views": {k: np.asarray(v) for k, v in views_host.items()},
                "relations": {name: {a: np.asarray(c) for a, c in
                                     rr.to_relation().columns.items()}
                              for name, rr in es.relations.items()}}

    def load_state(self, tree) -> None:
        """Rebuild an epoch from a host snapshot.  Snapshots are placement-
        free (oracle-ordered trimmed relations), so a checkpoint written by
        a single-device batch restores into a sharded one and vice versa —
        relations re-residentify under *this* batch's mesh config."""
        views = {int(k[1:]): jnp.asarray(v)
                 for k, v in tree["views"].items()}
        if self.mesh is not None:
            self._resolve_shard_rel(
                {name: int(np.asarray(next(iter(cols.values()))).shape[0])
                 for name, cols in tree["relations"].items()})
            from repro.core.distributed import put_replicated
            views = {vid: put_replicated(v, self.mesh)
                     for vid, v in views.items()}
        conv = np.asarray if self.mesh is not None else jnp.asarray
        rels = {name: self._make_resident(
                    Relation(name, {a: conv(c) for a, c in cols.items()}))
                for name, cols in tree["relations"].items()}
        if self._verify:
            for rr in rels.values():
                verify_resident(rr)
        self._current = EpochState(epoch=int(np.asarray(tree["epoch"])),
                                   step=int(np.asarray(tree["step"])),
                                   views=views, relations=rels)

    def save(self, ckpt_dir: str, keep: int = 3,
             epoch: Optional[int] = None) -> str:
        from repro.checkpoint import store
        return store.save_view_state(ckpt_dir, self, keep=keep, epoch=epoch)

    def restore(self, ckpt_dir: str, step: Optional[int] = None) -> int:
        from repro.checkpoint import store
        return store.restore_view_state(ckpt_dir, self, step=step)
