"""Pallas lowering backend: segment reductions on the MXU kernels.

Rows stream through ``lax.scan`` in ``PlanConfig.block_size`` blocks (same
bounded-memory structure as the XLA backend — payloads never materialize
beyond one block), but each block's reduction runs through the one-hot-matmul
kernels — the TPU-native form of the multi-output trie scan, with the dense
view accumulators held in VMEM one segment tile at a time across the
kernel's row grid.

Launch fusion (``PlanConfig.fuse_kernels``, default): the **union of a
step's reductions** — every local group-by bucket *and* every histogram-
pattern view — dispatches as ONE ``kernels/fused_scan`` launch per row
block, so the shared row block is read from HBM once and the MXU runs
back-to-back contractions against it; with ``double_buffer`` the kernel
drives its own two-slot HBM→VMEM DMA pipeline so compute on block *i*
overlaps the copy of block *i+1* (DESIGN.md §10).  The unfused path (one
``seg_aggregate`` launch per bucket + one ``tree_hist`` per hist view)
remains as the comparison baseline the roofline harness measures against.

Kernel blocking comes from the config: ``block_rows`` sizes the kernel row
grid (``"auto"`` is resolved by the bind-time autotuner before this backend
ever runs; an unresolved "auto" degrades to the static default rather than
raising).  On CPU the kernels run in interpret mode (``PlanConfig.
interpret``; ``None`` auto-selects interpret off-TPU), which keeps this
backend testable everywhere and allclose to the XLA backend up to fp32
reduction order.
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

from repro.core.aggregates import Params
from repro.core.autotune import DEFAULT_BLOCK_ROWS, DEFAULT_BLOCK_SIZE
from repro.core.ir import StepProgram, ViewProgram
from repro.core.lowering import common


def resolve_interpret(config, platform=None) -> bool:
    """Whether the kernels run in interpret mode under ``config``: its
    ``interpret`` when set, else on every platform but the TPU
    (``platform`` defaults to JAX's default backend)."""
    if config.interpret is not None:
        return bool(config.interpret)
    return (platform or jax.default_backend()) != "tpu"


def _step_split(prog: StepProgram):
    """Static split of a step's views: hist-pattern views, then general
    views bucketed by their local segment key (views sharing a key reduce in
    one scatter pass — the MOO promise at kernel granularity)."""
    hist_views = [vp for vp in prog.views if vp.hist is not None]
    bucket_map: Dict[Tuple[str, ...], List[ViewProgram]] = {}
    for vp in prog.views:
        if vp.hist is None:
            key = vp.seg.attrs if vp.seg is not None else ()
            bucket_map.setdefault(key, []).append(vp)
    return hist_views, sorted(bucket_map.items())


class PallasBackend:
    """Lowers one scan step to blocked Pallas kernel launches."""

    name = "pallas"

    @staticmethod
    def count_launches(prog: StepProgram, config) -> int:
        """Kernel-launch sites this step dispatches per row block: 1 fused,
        or one per bucket plus one per hist view unfused."""
        hist_views, buckets = _step_split(prog)
        if getattr(config, "fuse_kernels", True):
            return 1 if (hist_views or buckets) else 0
        return len(hist_views) + len(buckets)

    def run_step(self, prog: StepProgram, rel_cols: Mapping[str, jnp.ndarray],
                 arrays: Dict[int, jnp.ndarray], params: Params, *,
                 n_valid, offset, config, n_nodes=None,
                 weights=None) -> None:
        """``weights`` (optional, (n_rows,) float) multiply each row's
        contribution — signed multiplicities for IVM delta scans (+1 insert,
        -1 delete, 0 padding).  ``None`` keeps the unweighted path.
        ``n_valid``/``offset`` may be Python ints or traced scalars (dynamic
        valid-row counts of capacity-padded resident relations)."""
        from repro.kernels import ops

        interpret = resolve_interpret(config)
        block_size = (config.block_size if isinstance(config.block_size, int)
                      else DEFAULT_BLOCK_SIZE)
        block_rows = (config.block_rows if isinstance(config.block_rows, int)
                      else DEFAULT_BLOCK_ROWS)
        cols_blocked, iota, B, n_pad = common.block_columns(
            rel_cols, weights, block_size)

        hist_views, buckets = _step_split(prog)

        def flat_width(vp: ViewProgram) -> int:
            # batched views fold the node axis into the kernel's aggregate
            # column axis: one launch still reduces every node's columns
            w = vp.n_aggs * (n_nodes if vp.batched else 1)
            for d in vp.pulled_dims:
                w *= d
            return w

        def _flat_payload(vp: ViewProgram, blk_cols, gathered, valid):
            p = common.view_payload(vp, blk_cols, gathered, params, valid, B,
                                    n_nodes)
            if vp.batched:   # (N, B, *pulled, n_aggs) -> (B, N·pulled·n_aggs)
                p = jnp.moveaxis(p, 0, 1)
            return p.reshape(B, -1)

        if getattr(config, "fuse_kernels", True) and (hist_views or buckets):
            self._run_fused(prog, arrays, params, cols_blocked, iota, B,
                            n_pad, n_valid, offset, n_nodes, hist_views,
                            buckets, flat_width, _flat_payload,
                            block_rows=block_rows, interpret=interpret,
                            double_buffer=getattr(config, "double_buffer",
                                                  True))
            return

        hist_accs = tuple(
            jnp.zeros(((n_nodes,) if vp.batched else ())
                      + (vp.hist.n_buckets, 3), jnp.float32)
            for vp in hist_views)
        bucket_accs = tuple(
            jnp.zeros((vps[0].seg.n_segments if key else 1,
                       sum(flat_width(vp) for vp in vps)), jnp.float32)
            for key, vps in buckets)

        def body(carry, xs):
            hist_accs, bucket_accs = carry
            blk_cols, blk_i = xs
            blk_cols, valid = common.block_validity(
                dict(blk_cols), blk_i, B, n_pad, n_valid, offset)

            gathered = common.gather_children(prog.gathers, blk_cols, arrays, B)

            new_hist = []
            for vp, acc in zip(hist_views, hist_accs):
                cond = common.col_payload(vp.hist.cond, blk_cols, gathered,
                                          params, B) * valid
                if vp.batched:
                    # cond (N, B): one multi-node kernel pass serves the
                    # entire frontier (accumulator (N, D, 3) stays in VMEM)
                    out = ops.tree_hist_batched(
                        blk_cols[vp.hist.code_attr],
                        blk_cols[vp.hist.y_attr].astype(jnp.float32),
                        jnp.swapaxes(cond, 0, 1), vp.hist.n_buckets,
                        block_rows=block_rows, interpret=interpret)
                else:
                    out = ops.tree_hist(
                        blk_cols[vp.hist.code_attr],
                        blk_cols[vp.hist.y_attr].astype(jnp.float32),
                        cond, vp.hist.n_buckets,
                        block_rows=block_rows, interpret=interpret)
                new_hist.append(acc + out)

            new_buckets = []
            for (key, vps), acc in zip(buckets, bucket_accs):
                payload = jnp.concatenate(
                    [_flat_payload(vp, blk_cols, gathered, valid)
                     for vp in vps], axis=1)
                if key:
                    seg = common.segment_ids(blk_cols, vps[0].seg)
                    n_seg = vps[0].seg.n_segments
                else:
                    seg = jnp.zeros((B,), dtype=jnp.int32)
                    n_seg = 1
                out = ops.seg_aggregate(seg, payload, n_seg,
                                        block_rows=block_rows,
                                        interpret=interpret)
                new_buckets.append(acc + out)
            return (tuple(new_hist), tuple(new_buckets)), None

        (hist_accs, bucket_accs), _ = jax.lax.scan(
            body, (hist_accs, bucket_accs), (cols_blocked, iota))

        for vp, acc in zip(hist_views, hist_accs):
            arrays[vp.vid] = common.finalize(vp, acc)
        self._unpack_buckets(arrays, buckets, bucket_accs, flat_width,
                             n_nodes)

    # -- fused whole-step launch ---------------------------------------------

    def _run_fused(self, prog, arrays, params, cols_blocked, iota, B, n_pad,
                   n_valid, offset, n_nodes, hist_views, buckets, flat_width,
                   _flat_payload, *, block_rows, interpret, double_buffer):
        """One ``fused_scan_block`` launch per row block reduces the union of
        the step's buckets and hist views: the block's codes/payloads pack
        into two arrays and static :class:`ReduceSpec` offsets route each
        reduction to its slice (hist payloads ``cond ⊗ [1,y,y²]`` are formed
        inside the kernel's VMEM, never materialized in HBM)."""
        from repro.kernels import ops

        # static packing layout: bucket specs first, then hist specs; the
        # [1, y, y²] triple is shared by every hist view on the same y attr
        specs: List[ops.ReduceSpec] = []
        c, off = 0, 0
        for key, vps in buckets:
            w = sum(flat_width(vp) for vp in vps)
            n_seg = vps[0].seg.n_segments if key else 1
            specs.append(ops.ReduceSpec("seg", c, n_seg, w, off))
            c += 1
            off += w
        cond_slots = []
        for vp in hist_views:
            nc = n_nodes if vp.batched else 1
            cond_slots.append((c, off, nc))
            c += 1
            off += nc
        yk_offs: Dict[str, int] = {}
        for vp in hist_views:
            if vp.hist.y_attr not in yk_offs:
                yk_offs[vp.hist.y_attr] = off
                off += 3
        for (ci, po, nc), vp in zip(cond_slots, hist_views):
            specs.append(ops.ReduceSpec("hist", ci, vp.hist.n_buckets, nc * 3,
                                        po, n_cond=nc,
                                        yk_off=yk_offs[vp.hist.y_attr]))
        specs = tuple(specs)

        accs = tuple(jnp.zeros((sp.n_segments, sp.width), jnp.float32)
                     for sp in specs)

        def body(carry, xs):
            accs = carry
            blk_cols, blk_i = xs
            blk_cols, valid = common.block_validity(
                dict(blk_cols), blk_i, B, n_pad, n_valid, offset)
            gathered = common.gather_children(prog.gathers, blk_cols, arrays,
                                              B)
            code_cols, pay_cols = [], []
            for key, vps in buckets:
                if key:
                    code_cols.append(common.segment_ids(
                        blk_cols, vps[0].seg).astype(jnp.int32))
                else:
                    code_cols.append(jnp.zeros((B,), jnp.int32))
                pay_cols.append(jnp.concatenate(
                    [_flat_payload(vp, blk_cols, gathered, valid)
                     for vp in vps], axis=1))
            for vp in hist_views:
                cond = common.col_payload(vp.hist.cond, blk_cols, gathered,
                                          params, B) * valid
                cond = (jnp.swapaxes(cond, 0, 1) if vp.batched
                        else cond[:, None])
                code_cols.append(blk_cols[vp.hist.code_attr].astype(jnp.int32))
                pay_cols.append(cond.astype(jnp.float32))
            for ya in yk_offs:
                y = blk_cols[ya].astype(jnp.float32)
                pay_cols.append(jnp.stack([jnp.ones_like(y), y, y * y],
                                          axis=1))
            outs = ops.fused_scan_block(
                jnp.stack(code_cols, axis=1),
                jnp.concatenate(pay_cols, axis=1), specs,
                block_rows=block_rows, interpret=interpret,
                double_buffer=double_buffer)
            return tuple(a + o for a, o in zip(accs, outs)), None

        accs, _ = jax.lax.scan(body, accs, (cols_blocked, iota))

        self._unpack_buckets(arrays, buckets, accs[:len(buckets)], flat_width,
                             n_nodes)
        for vp, acc in zip(hist_views, accs[len(buckets):]):
            if vp.batched:
                # fused hist columns are [node j, stat k] -> node axis front
                acc = jnp.moveaxis(
                    acc.reshape(vp.hist.n_buckets, n_nodes, 3), 1, 0)
            arrays[vp.vid] = common.finalize(vp, acc)

    # -- shared unpacking ----------------------------------------------------

    @staticmethod
    def _unpack_buckets(arrays, buckets, bucket_accs, flat_width, n_nodes):
        for (key, vps), out in zip(buckets, bucket_accs):
            o = 0
            for vp in vps:
                w = flat_width(vp)
                n_seg = vp.seg.n_segments if vp.seg is not None else 1
                lead = (n_nodes,) if vp.batched else ()
                acc = out[:, o:o + w].reshape((n_seg,) + lead + vp.pulled_dims
                                              + (vp.n_aggs,))
                if vp.seg is None:
                    acc = acc[0]
                elif vp.batched:
                    acc = jnp.moveaxis(acc, 1, 0)   # node axis back in front
                arrays[vp.vid] = common.finalize(vp, acc)
                o += w
