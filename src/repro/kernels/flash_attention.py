"""Pallas TPU kernel: blockwise (flash) attention with online softmax.

Used by the LM-zoo side of the framework for prefill: O(S²) attention without
materializing the (S, S) score matrix.  Supports causal masking, sliding
windows (SWA archs), and GQA via an index_map that folds the query-head →
kv-head mapping into the BlockSpec (no KV replication in HBM).

Grid: (batch, q_heads, q_blocks, kv_blocks).  Running max / normalizer /
accumulator live in VMEM scratch pinned across the kv_blocks axis; the output
block is written once on the final kv step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, bq: int, bk: int,
                  kv_len: int):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                  # (bq, d)
    k = k_ref[0, 0]                                  # (bk, d)
    v = v_ref[0, 0]                                  # (bk, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)

    rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = cols < kv_len                             # padded key tail
    if causal:
        mask = jnp.logical_and(mask, cols <= rows)
    if window > 0:
        mask = jnp.logical_and(mask, cols > rows - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                              # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == pl.num_programs(3) - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, window: int = 0,
                           kv_len: int = 0, block_q: int = 128,
                           block_k: int = 128, interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, S, D); k, v: (B, Hkv, S, D) with H % Hkv == 0.

    S must divide into block_q/block_k tiles (ops.py pads + re-slices);
    ``kv_len`` is the valid (unpadded) key length."""
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert h % hkv == 0
    group = h // hkv
    assert sq % block_q == 0 and sk % block_k == 0
    scale = 1.0 / (d ** 0.5)
    grid = (b, h, sq // block_q, sk // block_k)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, bq=block_q, bk=block_k,
                               kv_len=kv_len or sk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret, name="flash_attention",
    )(q, k, v)
