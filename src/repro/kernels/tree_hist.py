"""Pallas TPU kernel: fused decision-tree node histogram.

The paper's regression-tree-node workload (Table 3 row 3): for a candidate
split attribute with D buckets, compute per-bucket [COUNT, SUM(y), SUM(y²)]
under the node's ancestor-condition mask — eq. (8) extended with a group-by.
Fuses payload construction (cond·[1, y, y²]) with the one-hot scatter matmul
so the row block is read once from VMEM.

The batched variant evaluates a whole *node frontier* at once: ``cond`` is
``(n, N)`` — one mask column per tree node — and the kernel forms the
payload ``cond ⊗ [1, y, y²]`` before the one-hot matmuls, so one pass over
the rows serves all ``N`` nodes (DESIGN.md §7.4).

Both are the fused scan-block kernel with one "hist" reduction.  Arbitrary
row counts are handled by padding the row axis with zeroed ``cond`` (padded
rows contribute nothing), so callers never need ``n % block_rows == 0``.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.fused_scan import ReduceSpec, fused_scan_block_pallas


def _hist(codes, y, cond, n_buckets, block_rows, interpret, name):
    """(n_buckets, N·3) histogram of ``cond (n, N)`` columns."""
    n_cond = cond.shape[1]
    y = y.astype(jnp.float32)
    fpay = jnp.concatenate(
        [cond.astype(jnp.float32),
         jnp.stack([jnp.ones_like(y), y, y * y], axis=1)], axis=1)
    spec = ReduceSpec("hist", 0, n_buckets, 3 * n_cond, 0, n_cond=n_cond,
                      yk_off=n_cond)
    (out,) = fused_scan_block_pallas(
        codes.astype(jnp.int32)[:, None], fpay, (spec,),
        block_rows=block_rows, interpret=interpret, double_buffer=False,
        name=name)
    return out


def tree_hist_pallas(codes: jnp.ndarray, y: jnp.ndarray, cond: jnp.ndarray,
                     n_buckets: int, *, block_rows: int = 512,
                     interpret: bool = False) -> jnp.ndarray:
    """out[b] = [Σ cond, Σ cond·y, Σ cond·y²] over rows with codes==b."""
    return _hist(codes, y, cond[:, None], n_buckets, block_rows, interpret,
                 "tree_hist")


def tree_hist_batched_pallas(codes: jnp.ndarray, y: jnp.ndarray,
                             cond: jnp.ndarray, n_buckets: int, *,
                             block_rows: int = 512,
                             interpret: bool = False) -> jnp.ndarray:
    """out[j, b] = [Σ cond_j, Σ cond_j·y, Σ cond_j·y²] over rows with
    codes==b, for every node column j of ``cond`` (shape (n, N)); returned
    as (N, n_buckets, 3)."""
    n_nodes = cond.shape[1]
    out = _hist(codes, y, cond, n_buckets, block_rows, interpret,
                "tree_hist_batched")
    return jnp.transpose(out.reshape(n_buckets, n_nodes, 3), (1, 0, 2))
