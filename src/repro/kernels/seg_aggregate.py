"""Pallas TPU kernel: multi-aggregate segment reduction — the MOO scan.

One pass over a relation block computes *all* aggregate columns of a view
group keyed by a (flattened) group-by code: the TPU-native form of LMFAO's
multi-output trie scan.  The scatter-accumulate is expressed as a one-hot
matmul ``onehot(seg)ᵀ @ payload`` so it runs on the MXU instead of a serial
scatter.  It is the fused scan-block kernel with one "seg" reduction, so it
shares that kernel's lane-major layout and segment tiling: the view
accumulator is held in VMEM one segment tile at a time.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.fused_scan import ReduceSpec, fused_scan_block_pallas


def seg_aggregate_pallas(seg: jnp.ndarray, payload: jnp.ndarray, n_segments: int,
                         *, block_rows: int = 512, interpret: bool = False) -> jnp.ndarray:
    """out[s, a] = Σ_{n: seg[n]=s} payload[n, a].

    seg: (N,) int32 in [0, n_segments) (out-of-range rows contribute
    nowhere); payload: (N, A) f32.  Rows are padded to a ``block_rows``
    multiple with zeroed payload, so any N works."""
    assert seg.shape == (payload.shape[0],)
    spec = ReduceSpec("seg", 0, n_segments, payload.shape[1], 0)
    (out,) = fused_scan_block_pallas(
        seg.astype(jnp.int32)[:, None], payload, (spec,),
        block_rows=block_rows, interpret=interpret, double_buffer=False,
        name="seg_aggregate")
    return out
