"""Pallas TPU kernel: masked blocked XᵀX accumulation — the covar batch.

The paper's flagship workload (the covar matrix, 814 aggregates for Retailer)
reduces on TPU to ``C = Xᵀ·diag(w)·X`` over the (factorized) feature matrix:
LMFAO's scalar accumulator loops become one systolic-array matmul per row
block (DESIGN.md §2).

Tiling: rows stream HBM→VMEM in ``(bm, F)`` tiles; the ``(F, F)`` fp32
accumulator block is pinned in VMEM across the whole grid (its index_map is
constant), so partial products never round-trip to HBM.  ``bm`` and ``F`` are
padded to MXU-friendly multiples (8×128 lanes) by the ops wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _covar_kernel(x_ref, w_ref, o_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                       # (bm, F)  VMEM tile
    w = w_ref[...]                       # (bm, 1)  row weights / validity
    xw = x * w                           # VPU elementwise
    acc_ref[...] += jnp.dot(xw.T, x, preferred_element_type=jnp.float32)  # MXU

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def covar_xtx_pallas(x: jnp.ndarray, w: jnp.ndarray, *, block_rows: int = 512,
                     interpret: bool = False) -> jnp.ndarray:
    """C[f,g] = Σ_n w[n]·x[n,f]·x[n,g].  x: (N, F) f32, w: (N,) f32.

    N must be a multiple of ``block_rows`` (ops.py pads with w=0 rows)."""
    n, f = x.shape
    assert n % block_rows == 0, (n, block_rows)
    grid = (n // block_rows,)
    return pl.pallas_call(
        _covar_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, f), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((f, f), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((f, f), jnp.float32),
        scratch_shapes=[pltpu.VMEM((f, f), jnp.float32)],
        interpret=interpret, name="covar_xtx",
    )(x, w.reshape(n, 1))
