"""XLA lowering backend: blocked ``lax.scan`` + ``segment_sum``.

The paper-faithful default.  Rows stream through ``lax.scan`` in fixed-size
blocks (HBM→VMEM tiles on real hardware); each block gathers incoming views
once, evaluates every fused view's payload, and accumulates via
``jax.ops.segment_sum`` (local group-bys) or a plain axis-sum (scalar /
pulled-only views).  Tracing the step program *is* LMFAO's code-generation
layer (DESIGN.md §2): the emitted HLO is specialized to the schema, the
fused view set, and the aggregate batch.
"""

from __future__ import annotations

from typing import Dict, Mapping

import jax
import jax.numpy as jnp

from repro.core.aggregates import Params
from repro.core.ir import StepProgram
from repro.core.lowering import common


class XlaBackend:
    """Lowers one scan step to a blocked ``lax.scan`` over the relation."""

    name = "xla"

    def run_step(self, prog: StepProgram, rel_cols: Mapping[str, jnp.ndarray],
                 arrays: Dict[int, jnp.ndarray], params: Params, *,
                 n_valid, offset, config, n_nodes=None,
                 weights=None) -> None:
        """``weights`` (optional, (n_rows,) float) multiply each row's
        contribution — signed multiplicities for IVM delta scans (+1 insert,
        -1 delete, 0 padding).  ``None`` keeps the unweighted path.
        ``n_valid``/``offset`` may be Python ints or traced scalars (dynamic
        valid-row counts of capacity-padded resident relations)."""
        from repro.core.autotune import DEFAULT_BLOCK_SIZE

        block_size = (config.block_size if isinstance(config.block_size, int)
                      else DEFAULT_BLOCK_SIZE)  # unresolved "auto" -> default
        cols_blocked, iota, B, n_pad = common.block_columns(
            rel_cols, weights, block_size)

        # batched views carry the param-batch (node) axis in front: one
        # relation pass accumulates all N parameter settings at once
        accs = tuple(jnp.zeros(((n_nodes,) if vp.batched else ())
                               + vp.acc_shape, dtype=jnp.float32)
                     for vp in prog.views)

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

            contribs = []
            for vp in prog.views:
                with jax.named_scope("payload"):
                    payload = common.view_payload(vp, blk_cols, gathered,
                                                  params, valid, B, n_nodes)
                with jax.named_scope("partials"):
                    contribs.append(_partials(vp, payload, blk_cols))
            # the block's partial sums are formed apart from the carried
            # accumulators: XLA would otherwise fold ``acc + segment_sum``
            # into one scatter-add onto ``acc``, adding rows one at a time
            # to the running f32 total (a COUNT stalls at 2^24 on the TPU)
            contribs = jax.lax.optimization_barrier(tuple(contribs))
            with jax.named_scope("accumulate"):
                return tuple(a + c for a, c in zip(accs, contribs)), None

        accs, _ = jax.lax.scan(body, accs, (cols_blocked, iota))

        with jax.named_scope("finalize"):
            for vp, acc in zip(prog.views, accs):
                arrays[vp.vid] = common.finalize(vp, acc)


def _partials(vp, payload: jnp.ndarray, blk_cols) -> jnp.ndarray:
    """One block's contribution to a view: ``segment_sum`` over the view's
    local group-by (a zero-filled partial per block), or the axis sum of a
    scalar or pulled-only view."""
    if vp.seg is None:
        return payload.sum(axis=1 if vp.batched else 0)
    seg = common.segment_ids(blk_cols, vp.seg)
    if vp.batched:
        # segment_sum reduces axis 0: rows forward, node axis back, then
        # restore the leading node axis
        return jnp.swapaxes(jax.ops.segment_sum(
            jnp.swapaxes(payload, 0, 1), seg,
            num_segments=vp.seg.n_segments), 0, 1)
    return jax.ops.segment_sum(payload, seg, num_segments=vp.seg.n_segments)
