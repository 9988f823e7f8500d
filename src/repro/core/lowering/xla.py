"""XLA lowering backend: blocked ``lax.scan`` + ``segment_sum``.

The paper-faithful default.  Rows stream through ``lax.scan`` in fixed-size
blocks (HBM→VMEM tiles on real hardware); each block gathers incoming views
once, evaluates every fused view's payload, and accumulates via
``jax.ops.segment_sum`` (local group-bys) or a plain axis-sum (scalar /
pulled-only views).  The views of a step that share a segment key (and the
node axis) share one accumulator, their payloads side by side along its last
axis, so a block takes one partial and one accumulate per key
(:func:`accumulator_groups`).  A key with many more segments than a block
has rows sums each block into a compact partial over only the segments the
block touches, then scatter-adds it into the accumulator
(:func:`takes_compact`).  A step whose relation is long against its widest
compact key sorts its rows by that key once, before the scan; each block
then covers a short run of segments and is added into one contiguous window
of the accumulator (:func:`cluster_key`).
Tracing the step program *is* LMFAO's code-generation layer (DESIGN.md §2):
the emitted HLO is specialized to the schema, the fused view set, and the
aggregate batch.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.aggregates import Params
from repro.core.ir import SegmentSpec, StepProgram, ViewProgram
from repro.core.lowering import common

#: a key takes the compact path once its segments outnumber this many
#: times a block's rows: below, the dense zero fill and add over every
#: segment is cheaper than a sort (one-block timings on a v5e, PERF.md §6)
COMPACT_SEGMENTS_PER_ROW = 8

#: a step sorts its rows by its widest compact key once its relation holds
#: this many rows a segment of that key: a block of sorted rows then spans
#: about a quarter of a block's segments, so its window (a block's rows of
#: segments) rarely misses (one-sort and one-block timings on a v5e,
#: PERF.md §6)
CLUSTER_ROWS_PER_SEGMENT = 4


def takes_compact(seg: Optional[SegmentSpec], block_size: int) -> bool:
    """Whether an accumulator on segment key ``seg``, scanned in blocks of
    the step's ``block_size`` rows, takes each block through a compact
    partial over the block's distinct segments, in place of a partial
    zero-filled over every segment."""
    return (seg is not None
            and seg.n_segments > COMPACT_SEGMENTS_PER_ROW * block_size)


def cluster_key(groups, n_rows: int,
                block_size: int) -> Optional[SegmentSpec]:
    """The key a step of ``n_rows`` rows sorts them by before its scan: that
    of its widest compact group, when the relation holds at least
    ``CLUSTER_ROWS_PER_SEGMENT`` rows a segment of it; else ``None`` (no
    compact group, or a block's expected span reaches its window).  A
    compact key has more than ``COMPACT_SEGMENTS_PER_ROW`` blocks' rows of
    segments, so a step that sorts has more than 32 blocks to amortise the
    sort over."""
    compact = [g for g in groups if takes_compact(g.seg, block_size)]
    if not compact:
        return None
    seg = max(compact, key=lambda g: g.width).seg
    return (seg if n_rows >= CLUSTER_ROWS_PER_SEGMENT * seg.n_segments
            else None)


@dataclasses.dataclass(frozen=True)
class AccumulatorGroup:
    """Views of one step that accumulate alike: the same segment key (or
    none) and node axis.  They share an accumulator ``((N,)?,
    (n_segments,)?, width)``; each view's payload, flattened to
    ``prod(pulled_dims) × n_aggs`` columns, takes ``offsets[i]`` onward."""

    seg: Optional[SegmentSpec]
    batched: bool
    views: Tuple[ViewProgram, ...]
    offsets: Tuple[int, ...]
    width: int


def _view_width(vp: ViewProgram) -> int:
    return math.prod(vp.pulled_dims) * vp.n_aggs


def accumulator_groups(prog: StepProgram) -> Tuple[AccumulatorGroup, ...]:
    """The step's views grouped by (segment key, batched), in the order
    each group's first view comes."""
    members: Dict[tuple, list] = {}
    for vp in prog.views:
        members.setdefault((vp.seg, vp.batched), []).append(vp)
    groups = []
    for (seg, batched), views in members.items():
        ends = tuple(itertools.accumulate(_view_width(vp) for vp in views))
        groups.append(AccumulatorGroup(seg, batched, tuple(views),
                                       (0,) + ends[:-1], ends[-1]))
    return tuple(groups)


class XlaBackend:
    """Lowers one scan step to a blocked ``lax.scan`` over the relation."""

    name = "xla"

    @staticmethod
    def count_compact(prog: StepProgram, config) -> int:
        """Views of ``prog`` on the compact path at the config's block size
        ("auto" counts at the default, as an unresolved step runs)."""
        bs = _block_size(config)
        return sum(takes_compact(vp.seg, bs) for vp in prog.views)

    @staticmethod
    def count_accumulators(prog: StepProgram, config) -> int:
        """Accumulators ``prog`` carries: one per (segment key, batched)
        group of its views."""
        return len(accumulator_groups(prog))

    @staticmethod
    def count_clustered(prog: StepProgram, config, n_rows: int) -> int:
        """1 if ``prog``, scanning ``n_rows`` rows, sorts them by a compact
        key before its scan, else 0."""
        return int(cluster_key(accumulator_groups(prog), n_rows,
                               _block_size(config)) is not None)

    def run_step(self, prog: StepProgram, rel_cols: Mapping[str, jnp.ndarray],
                 arrays: Dict[int, jnp.ndarray], params: Params, *,
                 n_valid, offset, config, n_nodes=None,
                 weights=None) -> None:
        """``weights`` (optional, (n_rows,) float) multiply each row's
        contribution — signed multiplicities for IVM delta scans (+1 insert,
        -1 delete, 0 padding).  ``None`` keeps the unweighted path.
        ``n_valid``/``offset`` may be Python ints or traced scalars (dynamic
        valid-row counts of capacity-padded resident relations)."""
        block_size = _block_size(config)
        groups = accumulator_groups(prog)
        compact = tuple(takes_compact(g.seg, block_size) for g in groups)
        key = cluster_key(groups, int(next(iter(rel_cols.values())).shape[0]),
                          block_size)
        if key is not None:
            with jax.named_scope("cluster"):
                rel_cols, weights, n_valid = _cluster(
                    rel_cols, weights, key, n_valid, offset)
            offset = 0
        cols_blocked, iota, B, n_pad = common.block_columns(
            rel_cols, weights, block_size)
        # a sorted step adds each block of a compact group into a window of
        # B segments from the block's least id; B spare segments keep a
        # window at the accumulator's end in bounds
        window = tuple(cp and key is not None for cp in compact)

        # batched views carry the param-batch (node) axis in front: one
        # relation pass accumulates all N parameter settings at once
        accs = tuple(jnp.zeros(_lead(g, n_nodes)
                               + ((g.seg.n_segments + B * w,) if g.seg
                                  else ())
                               + (g.width,), dtype=jnp.float32)
                     for g, w in zip(groups, window))

        def body(carry, xs):
            accs = carry
            blk_cols, blk_i = xs
            blk_cols, valid = common.block_validity(
                dict(blk_cols), blk_i, B, n_pad, n_valid, offset)

            # named scopes put the part of the block each op comes from
            # into its op name; they change no op
            with jax.named_scope("gather"):
                gathered = common.gather_children(prog.gathers, blk_cols,
                                                  arrays, B)

            contribs, payloads, keys = [], [], {}
            for g, cp, w in zip(groups, compact, window):
                with jax.named_scope("payload"):
                    payload = _group_payload(g, [common.view_payload(
                        vp, blk_cols, gathered, params, valid, B, n_nodes)
                        for vp in g.views])
                # a window group forms its partial in _window_accumulate
                payloads.append(payload if w else None)
                with jax.named_scope("partials"):
                    if w:
                        contribs.append(None)
                    elif cp:
                        # a batched and an unbatched group on one key share
                        # the block's distinct ids
                        if g.seg not in keys:
                            keys[g.seg] = _block_segments(
                                common.segment_ids(blk_cols, g.seg),
                                g.seg.n_segments)
                        ids, slot = keys[g.seg]
                        contribs.append(
                            (ids, _segment_sum(g, payload, slot, B)))
                    else:
                        contribs.append(_partials(g, payload, blk_cols))
            # the block's partial sums (with the segment ids of compact
            # ones) are formed apart from the carried accumulators: XLA would
            # otherwise fold the block's segment_sum into the update of
            # ``acc`` (the dense add or the compact scatter), adding rows one
            # at a time to the running f32 total (a COUNT stalls at 2^24 on
            # the TPU)
            contribs = jax.lax.optimization_barrier(tuple(contribs))
            out = []
            for g, a, c, cp, p in zip(groups, accs, contribs, compact,
                                      payloads):
                if p is not None:
                    out.append(_window_accumulate(
                        g, a, p, common.segment_ids(blk_cols, g.seg), valid))
                else:
                    with jax.named_scope("accumulate"):
                        out.append(_accumulate(g, a, c, cp))
            return tuple(out), None

        accs, _ = jax.lax.scan(body, accs, (cols_blocked, iota))

        with jax.named_scope("finalize"):
            for g, acc, w in zip(groups, accs, window):
                if w:
                    acc = acc[..., :g.seg.n_segments, :]
                for vp, off in zip(g.views, g.offsets):
                    view_acc = acc[..., off:off + _view_width(vp)]
                    arrays[vp.vid] = common.finalize(vp, view_acc.reshape(
                        _lead(g, n_nodes) + vp.acc_shape))


def _block_size(config) -> int:
    """The step's scan block: an unresolved "auto" runs at the default."""
    from repro.core.autotune import DEFAULT_BLOCK_SIZE

    return (config.block_size if isinstance(config.block_size, int)
            else DEFAULT_BLOCK_SIZE)


def _lead(g: AccumulatorGroup, n_nodes) -> Tuple[int, ...]:
    """The node axis of a batched group's arrays, else nothing."""
    return (n_nodes,) if g.batched else ()


def _group_payload(g: AccumulatorGroup, payloads) -> jnp.ndarray:
    """The group's views' ``((N,)?, B, *pulled_dims, n_aggs)`` payloads side
    by side as one ``((N,)?, B, width)``.  They are joined along a leading
    column axis and moved behind the rows once: joined along the last axis,
    the TPU's compiler copied each one-column piece into a lane-padded
    layout, every block (on a v5e retailer's job took 29.5 s against 19.9,
    PERF.md §6)."""
    lead = 2 if g.batched else 1
    return jnp.moveaxis(jnp.concatenate(
        [jnp.moveaxis(p.reshape(p.shape[:lead] + (-1,)), -1, 0)
         for p in payloads], axis=0), 0, -1)


def _partials(g: AccumulatorGroup, payload: jnp.ndarray,
              blk_cols) -> jnp.ndarray:
    """One block's contribution to a group: ``segment_sum`` over the
    group's key (a zero-filled partial per block), or the row sum of
    scalar or pulled-only views."""
    if g.seg is None:
        return payload.sum(axis=-2)
    seg = common.segment_ids(blk_cols, g.seg)
    return _segment_sum(g, payload, seg, g.seg.n_segments)


def _segment_sum(g: AccumulatorGroup, payload: jnp.ndarray,
                 seg: jnp.ndarray, n: int) -> jnp.ndarray:
    """``segment_sum`` over the row axis, which follows the node axis of a
    batched group."""
    if g.batched:
        # segment_sum reduces axis 0: rows forward, node axis back, then
        # restore the leading node axis
        return jnp.swapaxes(jax.ops.segment_sum(
            jnp.swapaxes(payload, 0, 1), seg, num_segments=n), 0, 1)
    return jax.ops.segment_sum(payload, seg, num_segments=n)


def _block_segments(seg: jnp.ndarray, n: int):
    """The block's distinct segment ids ``seg`` in its ``B`` slots, and
    each row's slot.  Spare slots, and the slot of rows whose id lies
    outside the ``n`` segments, hold the id ``n``, past the last segment,
    which the scatter drops as ``segment_sum`` drops such rows (or adds
    into a window accumulator's spare segments, which are sliced off)."""
    seg = jnp.where((seg >= 0) & (seg < n), seg, n)
    return jnp.unique(seg, size=seg.shape[0], fill_value=n,
                      return_inverse=True)


def _cluster(rel_cols, weights, seg: SegmentSpec, n_valid, offset):
    """The relation's columns (and row weights) sorted by their segment id
    on ``seg`` in one sort, and how many leading rows are valid.  Rows
    outside the scan's ``[offset, offset+n_valid)`` sort last, after the
    rows whose id lies outside the key's segments, so the valid rows stay
    the leading ones and the scan takes them from offset 0."""
    n_pad = int(next(iter(rel_cols.values())).shape[0])
    n = seg.n_segments
    limit = common.valid_limit(n_pad, n_valid, offset)
    ids = common.segment_ids(rel_cols, seg)
    key = jnp.where(jnp.arange(n_pad, dtype=jnp.int32) < limit,
                    jnp.where((ids >= 0) & (ids < n), ids, n), n + 1)
    names = tuple(rel_cols)
    extra = (() if weights is None
             else (jnp.asarray(weights, dtype=jnp.float32),))
    out = jax.lax.sort((key,) + tuple(rel_cols[a] for a in names) + extra,
                       num_keys=1, is_stable=False)
    return (dict(zip(names, out[1:1 + len(names)])),
            out[-1] if extra else None, limit)


def _window_accumulate(g: AccumulatorGroup, acc: jnp.ndarray,
                       payload: jnp.ndarray, seg: jnp.ndarray,
                       valid: jnp.ndarray) -> jnp.ndarray:
    """Add a block of a sorted step into its compact group's accumulator
    through one window of ``W`` (the block's rows) segments from ``lo``,
    the least id among its counted rows (``valid`` nonzero, id in range): a
    partial over the window's segments, then one read-modify-write of
    ``acc[lo:lo + W]``.  A block whose ids span ``W`` or more takes the
    compact path's distinct segments and scatter instead, under
    ``accumulate.fallback``."""
    n, W = g.seg.n_segments, seg.shape[0]
    counted = (valid != 0) & (seg >= 0) & (seg < n)
    lo = jnp.min(jnp.where(counted, seg, n))
    hi = jnp.max(jnp.where(counted, seg, 0))

    def window(acc):
        with jax.named_scope("partials"):
            partial = _segment_sum(g, payload,
                                   jnp.where(counted, seg - lo, W), W)
        # kept apart from the add into ``acc``, as in ``run_step``
        partial = jax.lax.optimization_barrier(partial)
        with jax.named_scope("accumulate"):
            at = (0, lo, 0) if g.batched else (lo, 0)
            cur = jax.lax.dynamic_slice(acc, at, partial.shape)
            return jax.lax.dynamic_update_slice(acc, cur + partial, at)

    def fallback(acc):
        with jax.named_scope("accumulate.fallback"):
            ids, slot = _block_segments(seg, n)
            partial = jax.lax.optimization_barrier(
                _segment_sum(g, payload, slot, W))
            return _accumulate(g, acc, (ids, partial), True)

    return jax.lax.cond(hi - lo < W, window, fallback, acc)


def _accumulate(g: AccumulatorGroup, acc: jnp.ndarray, contrib,
                compact: bool) -> jnp.ndarray:
    """Add a block's contribution into the carried accumulator: the dense
    partial whole, or the compact one scattered onto its segments (each
    segment takes one add; spare slots fall outside and are dropped).  The
    scatter is not told its ids are sorted or unique: on a v5e that hint
    made it about eight times slower at 4,096 × 435 (PERF.md §6)."""
    if not compact:
        return acc + contrib
    ids, partial = contrib
    idx = (slice(None), ids) if g.batched else ids
    return acc.at[idx].add(partial, mode="drop")
