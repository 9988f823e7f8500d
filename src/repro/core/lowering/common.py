"""Backend-shared lowering primitives over the group-program IR.

Payload construction — gathers of incoming views, term evaluation in the
product's axis frame, marginalization of extra axes, validity masking — is
identical across backends; only the scan strategy and the reduction differ
(``xla.py``: blocked ``lax.scan`` + ``segment_sum``; ``pallas.py``:
whole-relation payloads + MXU one-hot kernels).  Everything here is shape
polymorphic in the leading row axis: ``B`` is a block for the XLA backend and
the whole padded relation for the Pallas backend.

Param-batch (node) axis (DESIGN.md §7.4): batched products/views carry an
extra *leading* node axis of size ``N`` before the row axis, so arrays are
``(N, B, *frame)``.  Non-batched factors stay ``(B, *frame)`` and broadcast
against batched ones from the right; the static ``batched`` flags on the IR
decide where the axis exists, so every shape is known at trace time.
"""

from __future__ import annotations

from typing import Dict, Mapping, Optional, Tuple

import jax.numpy as jnp

from repro.core.aggregates import Params
from repro.core.ir import (ColProgram, GatherSpec, ProductProgram,
                           SegmentSpec, ViewProgram)

Cols = Mapping[str, jnp.ndarray]

#: synthetic column carrying per-row signed multiplicities through the
#: blocked scan (IVM delta weights ride the same xs pytree as real columns)
ROW_WEIGHT = "__row_weight__"


def block_columns(rel_cols: Cols, weights: Optional[jnp.ndarray],
                  block_size: int):
    """Reshape relation columns (and optional row weights) into scan blocks:
    returns ``(cols_blocked, iota, B, n_pad)`` where every column becomes
    ``(n_blocks, B)`` and ``iota`` indexes blocks.  Shared by both backends —
    the scan strategy differs below this split, the blocking does not."""
    n_pad = int(next(iter(rel_cols.values())).shape[0])
    B = min(block_size, max(n_pad, 1))
    n_blocks = max(-(-n_pad // B), 1)
    total = n_blocks * B
    pad = total - n_pad
    cols_blocked = {a: (jnp.pad(c, (0, pad)) if pad else c).reshape(n_blocks, B)
                    for a, c in rel_cols.items()}
    if weights is not None:
        w = jnp.asarray(weights, dtype=jnp.float32)
        cols_blocked[ROW_WEIGHT] = (jnp.pad(w, (0, pad)) if pad else
                                    w).reshape(n_blocks, B)
    iota = jnp.arange(n_blocks, dtype=jnp.int32)
    return cols_blocked, iota, B, n_pad


def valid_limit(n_pad: int, n_valid, offset) -> jnp.ndarray:
    """How many leading rows of a partition of ``n_pad`` rows lie inside the
    global window ``[offset, offset+n_valid)``: rows at or past it are
    padding (it may be negative: no row is valid)."""
    return jnp.minimum(jnp.asarray(n_pad, jnp.int32),
                       jnp.asarray(n_valid, jnp.int32)
                       - jnp.asarray(offset, jnp.int32))


def block_validity(blk_cols: Dict[str, jnp.ndarray], blk_i: jnp.ndarray,
                   B: int, n_pad: int, n_valid, offset):
    """Per-row validity of one scan block: inside both the local (possibly
    capacity-padded) partition and the global ``[offset, offset+n_valid)``
    window, times the signed row weight if present.  ``n_valid`` and
    ``offset`` may be Python ints *or traced scalars* — device-resident
    relations pass their dynamic valid-row count here, which is what keeps
    steady-state IVM ticks retrace-free while buffers stay capacity-shaped.
    Pops the weight column; returns ``(blk_cols, valid)``."""
    w_blk = blk_cols.pop(ROW_WEIGHT, None)
    row_idx = blk_i * B + jnp.arange(B, dtype=jnp.int32)
    valid = (row_idx < valid_limit(n_pad, n_valid, offset)).astype(
        jnp.float32)
    if w_blk is not None:
        valid = valid * w_blk
    return blk_cols, valid


def align(x: jnp.ndarray, src_axes: Tuple[str, ...],
          dst_axes: Tuple[str, ...], lead: int = 1) -> jnp.ndarray:
    """Map (*lead, *src_dims) onto (*lead, *dst positions) with singleton axes
    elsewhere.  All src axes must appear in dst; ``lead`` counts the leading
    non-frame axes kept in place (row axis, or node+row axes)."""
    present = [a for a in dst_axes if a in src_axes]
    if tuple(present) != tuple(src_axes):
        perm = list(range(lead)) + [lead + src_axes.index(a) for a in present]
        x = jnp.transpose(x, perm)
    shape = list(x.shape[:lead]) + [
        x.shape[lead + present.index(a)] if a in present else 1
        for a in dst_axes]
    return x.reshape(shape)


def reshape_axes(col: jnp.ndarray, dst_axes: Tuple[str, ...]) -> jnp.ndarray:
    """Row vector -> (B, 1, ..., 1) in the destination axis frame."""
    return col.reshape((col.shape[0],) + (1,) * len(dst_axes))


def segment_ids(cols: Cols, seg: SegmentSpec) -> jnp.ndarray:
    """Mixed-radix flattening of the local group-by columns."""
    out = jnp.zeros_like(cols[seg.attrs[0]])
    for a, d in zip(seg.attrs, seg.dims):
        out = out * d + cols[a]
    return out


def gather_children(gathers: Tuple[GatherSpec, ...], cols: Cols,
                    arrays: Mapping[int, jnp.ndarray],
                    n_rows: int) -> Dict[int, jnp.ndarray]:
    """Per child view: the (B, *rest_dims) slice each row sees — the paper's
    'lookup into incoming views', shared by all aggregates of the step.
    Batched children ((N, ...) arrays) gather past their node axis, yielding
    (N, B, *rest_dims) slices."""
    out: Dict[int, jnp.ndarray] = {}
    for gs in gathers:
        idx = tuple(cols[a] for a in gs.gather)
        arr = arrays[gs.vid]
        if gs.batched:
            if idx:
                out[gs.vid] = arr[(slice(None),) + idx]
            else:
                out[gs.vid] = jnp.broadcast_to(
                    arr[:, None], arr.shape[:1] + (n_rows,) + arr.shape[1:])
        else:
            out[gs.vid] = arr[idx] if idx else (
                jnp.broadcast_to(arr, (n_rows,) + arr.shape))
    return out


def product_payload(pp: ProductProgram, cols: Cols,
                    gathered: Mapping[int, jnp.ndarray], params: Params,
                    n_rows: int) -> jnp.ndarray:
    """(B, *kept_axis_dims) contribution of one product, extra axes summed;
    (N, B, *kept) when the product is batched."""
    n_frame = len(pp.axes)
    acc = None
    for ref in pp.child_refs:
        x = gathered[ref.vid][..., ref.col]        # (N?, B, *rest_dims)
        x = align(x, ref.rest, pp.axes, lead=2 if ref.batched else 1)
        acc = x if acc is None else acc * x
    for ta in pp.local_terms:
        env = {}
        for a in ta.col_attrs:
            env[a] = reshape_axes(cols[a], pp.axes)
        for a, d in zip(ta.dom_attrs, ta.dom_dims):
            dom = jnp.arange(d, dtype=jnp.int32)
            env[a] = align(dom[None, :], (a,), pp.axes)
        x = ta.term.evaluate(env, params)
        x = jnp.asarray(x, dtype=jnp.float32)
        if ta.batched:
            if x.ndim == 1:        # (N,) per-node scalar -> (N, 1, ..., 1)
                x = x.reshape(x.shape + (1,) * (1 + n_frame))
        elif x.ndim == 0:
            x = jnp.broadcast_to(x, (n_rows,) + (1,) * n_frame)
        acc = x if acc is None else acc * x
    if acc is None:  # pure count: Π over empty set = 1
        acc = jnp.ones((n_rows,) + (1,) * n_frame, dtype=jnp.float32)
    lead = acc.ndim - n_frame  # 1, or 2 when the node axis is present
    if n_frame > pp.n_keep:  # marginalize the non-output axes
        full = acc.shape[:lead - 1] + (n_rows,) + pp.axis_dims
        acc = jnp.broadcast_to(acc, full)
        acc = acc.sum(axis=tuple(range(lead + pp.n_keep, lead + n_frame)))
    return acc


def col_payload(cp: ColProgram, cols: Cols,
                gathered: Mapping[int, jnp.ndarray], params: Params,
                n_rows: int) -> jnp.ndarray:
    out = None
    for pp in cp.products:
        p = product_payload(pp, cols, gathered, params, n_rows)
        out = p if out is None else out + p
    return out


def view_payload(vp: ViewProgram, cols: Cols,
                 gathered: Mapping[int, jnp.ndarray], params: Params,
                 valid: jnp.ndarray, n_rows: int,
                 n_nodes: Optional[int] = None) -> jnp.ndarray:
    """(B, *pulled_dims, n_aggs) contributions of a row block to view vp —
    (N, B, *pulled_dims, n_aggs) for batched views.  Columns with no
    products contribute zeros (IVM delta views keep the full column layout
    of their base view and zero out products the delta cannot reach)."""
    target = (n_rows,) + vp.pulled_dims
    if vp.batched:
        assert n_nodes is not None, f"view {vp.vid}: batched but n_nodes unset"
        target = (n_nodes,) + target
    out_cols = []
    for cp in vp.cols:
        if cp.products:
            c = (col_payload(cp, cols, gathered, params, n_rows)
                 * reshape_axes(valid, vp.pulled))
        else:
            c = jnp.zeros(target, dtype=jnp.float32)
        out_cols.append(jnp.broadcast_to(c, target))
    return jnp.stack(out_cols, axis=-1)


def finalize(vp: ViewProgram, acc: jnp.ndarray) -> jnp.ndarray:
    """Unflatten the segment axis and transpose to canonical group-by order;
    leading node axis (batched views) stays in place."""
    lead = acc.ndim - len(vp.acc_shape)
    arr = acc.reshape(acc.shape[:lead] + vp.out_dims + (vp.n_aggs,))
    perm = tuple(range(lead)) + tuple(lead + p for p in vp.out_perm)
    return jnp.transpose(arr, perm)
