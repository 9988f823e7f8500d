"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def covar_xtx_ref(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum("nf,n,ng->fg", x.astype(jnp.float32), w.astype(jnp.float32),
                      x.astype(jnp.float32))


def seg_aggregate_ref(seg: jnp.ndarray, payload: jnp.ndarray, n_segments: int) -> jnp.ndarray:
    # out-of-range segment ids must contribute nowhere (padding convention)
    ok = (seg >= 0) & (seg < n_segments)
    pay = payload * ok[:, None].astype(payload.dtype)
    sid = jnp.where(ok, seg, 0)
    return jax.ops.segment_sum(pay, sid, num_segments=n_segments)


def tree_hist_ref(codes: jnp.ndarray, y: jnp.ndarray, cond: jnp.ndarray,
                  n_buckets: int) -> jnp.ndarray:
    payload = jnp.stack([cond, cond * y, cond * y * y], axis=1)
    return seg_aggregate_ref(codes, payload, n_buckets)


def tree_hist_batched_ref(codes: jnp.ndarray, y: jnp.ndarray,
                          cond: jnp.ndarray, n_buckets: int) -> jnp.ndarray:
    """cond (n, N) node-mask columns -> (N, n_buckets, 3)."""
    return jnp.stack([tree_hist_ref(codes, y, cond[:, j], n_buckets)
                      for j in range(cond.shape[1])])


def fused_scan_block_ref(codes: jnp.ndarray, fpay: jnp.ndarray, specs):
    """Oracle for the whole-step fused kernel: each :class:`ReduceSpec` is
    just a seg-sum of its payload slice (hist payloads formed as cond⊗yk)."""
    outs = []
    for sp in specs:
        code = codes[:, sp.code_col]
        if sp.kind == "seg":
            pay = fpay[:, sp.pay_off:sp.pay_off + sp.width]
        else:
            cond = fpay[:, sp.pay_off:sp.pay_off + sp.n_cond]
            yk = fpay[:, sp.yk_off:sp.yk_off + 3]
            pay = (cond[:, :, None] * yk[:, None, :]).reshape(
                codes.shape[0], sp.n_cond * 3)
        outs.append(seg_aggregate_ref(code, pay, sp.n_segments))
    return tuple(outs)

