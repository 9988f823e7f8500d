"""Jit'd public wrappers around the Pallas kernels.

Handles padding to MXU-aligned tile multiples, dtype management, and the
``interpret`` switch (True on CPU — the kernel body executes in Python for
validation; False on real TPU).  Every wrapper has a matching oracle in
``ref.py``; tests sweep shapes/dtypes asserting allclose.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.covar_xtx import covar_xtx_pallas
from repro.kernels.fused_scan import ReduceSpec, fused_scan_block_pallas
from repro.kernels.padding import pad_dim as _pad_dim
from repro.kernels.padding import pad_rows as _pad_rows
from repro.kernels.seg_aggregate import seg_aggregate_pallas
from repro.kernels.tree_hist import tree_hist_batched_pallas, tree_hist_pallas

__all__ = ["covar_xtx", "seg_aggregate", "tree_hist", "tree_hist_batched",
           "fused_scan_block", "ReduceSpec"]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret", "feature_align"))
def covar_xtx(x: jnp.ndarray, w: Optional[jnp.ndarray] = None, *,
              block_rows: int = 512, interpret: bool = False,
              feature_align: int = 8) -> jnp.ndarray:
    """C = Xᵀ diag(w) X with row/feature padding; returns (F, F) unpadded."""
    n, f = x.shape
    if w is None:
        w = jnp.ones((n,), jnp.float32)
    x = _pad_dim(x.astype(jnp.float32), 1, feature_align)
    xp = _pad_rows(x, block_rows)
    wp = _pad_rows(w.astype(jnp.float32), block_rows)  # pad weight = 0
    c = covar_xtx_pallas(xp, wp, block_rows=block_rows, interpret=interpret)
    return c[:f, :f]


@functools.partial(jax.jit, static_argnames=("n_segments", "block_rows", "interpret"))
def seg_aggregate(seg: jnp.ndarray, payload: jnp.ndarray, n_segments: int, *,
                  block_rows: int = 512, interpret: bool = False) -> jnp.ndarray:
    """Segment-sum payload rows into n_segments (ids outside
    ``[0, n_segments)`` contribute nowhere)."""
    return seg_aggregate_pallas(seg.astype(jnp.int32),
                                payload.astype(jnp.float32), n_segments,
                                block_rows=block_rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_buckets", "block_rows", "interpret"))
def tree_hist(codes: jnp.ndarray, y: jnp.ndarray, cond: jnp.ndarray,
              n_buckets: int, *, block_rows: int = 512,
              interpret: bool = False) -> jnp.ndarray:
    """Per-bucket [count, Σy, Σy²] under the node mask."""
    return tree_hist_pallas(codes, y, cond.astype(jnp.float32), n_buckets,
                            block_rows=block_rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_buckets", "block_rows", "interpret"))
def tree_hist_batched(codes: jnp.ndarray, y: jnp.ndarray, cond: jnp.ndarray,
                      n_buckets: int, *, block_rows: int = 512,
                      interpret: bool = False) -> jnp.ndarray:
    """Per-node, per-bucket [count, Σy, Σy²]: ``cond`` is (n, N) — one mask
    column per frontier node — and the result is (N, n_buckets, 3), computed
    in one fused kernel pass over the rows (DESIGN.md §7.4)."""
    return tree_hist_batched_pallas(codes.astype(jnp.int32),
                                    y.astype(jnp.float32),
                                    cond.astype(jnp.float32), n_buckets,
                                    block_rows=block_rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("specs", "block_rows",
                                             "interpret", "double_buffer"))
def fused_scan_block(codes: jnp.ndarray, fpay: jnp.ndarray,
                     specs, *, block_rows: int = 512,
                     interpret: bool = False, double_buffer: bool = True):
    """Whole-step fused reduction: every bucket/hist reduction of a scan
    step in ONE kernel launch over the shared row block (DESIGN.md §10).
    ``specs`` is a (hashable) tuple of :class:`ReduceSpec`; returns a tuple
    of ``(n_segments, width)`` arrays aligned with it.  Rows pad with zeroed
    payload (validity is pre-folded into the payloads), so any ``n`` works;
    ``double_buffer`` selects the manual two-slot HBM→VMEM DMA pipeline."""
    return fused_scan_block_pallas(codes.astype(jnp.int32),
                                   fpay.astype(jnp.float32), tuple(specs),
                                   block_rows=block_rows, interpret=interpret,
                                   double_buffer=double_buffer)

