"""Per-kernel shape/dtype sweeps vs. the pure-jnp oracles (interpret mode)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("n,f,block", [(64, 4, 32), (1000, 13, 256), (513, 7, 128),
                                       (2048, 32, 512)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_covar_xtx(n, f, block, dtype):
    rng = np.random.default_rng(n + f)
    x = rng.normal(size=(n, f)).astype(dtype)
    w = (rng.random(n) < 0.8).astype(np.float32)
    got = ops.covar_xtx(jnp.asarray(x), jnp.asarray(w), block_rows=block,
                        interpret=True)
    want = ref.covar_xtx_ref(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("n,s,a,block", [(64, 5, 3, 32), (1000, 37, 5, 128),
                                         (777, 20, 1, 256), (4096, 64, 16, 512)])
def test_seg_aggregate(n, s, a, block):
    rng = np.random.default_rng(n + s)
    seg = rng.integers(0, s, n).astype(np.int32)
    pay = rng.normal(size=(n, a)).astype(np.float32)
    got = ops.seg_aggregate(jnp.asarray(seg), jnp.asarray(pay), s,
                            block_rows=block, interpret=True)
    want = ref.seg_aggregate_ref(jnp.asarray(seg), jnp.asarray(pay), s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,d,block", [(100, 20, 64), (1000, 20, 128), (333, 7, 64)])
def test_tree_hist(n, d, block):
    rng = np.random.default_rng(n)
    codes = rng.integers(0, d, n).astype(np.int32)
    y = rng.normal(size=n).astype(np.float32)
    cond = (rng.random(n) < 0.5).astype(np.float32)
    got = ops.tree_hist(jnp.asarray(codes), jnp.asarray(y), jnp.asarray(cond), d,
                        block_rows=block, interpret=True)
    want = ref.tree_hist_ref(jnp.asarray(codes), jnp.asarray(y),
                             jnp.asarray(cond), d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,d,n_nodes,block", [(100, 20, 1, 64), (517, 20, 8, 128),
                                               (1000, 7, 3, 512)])
def test_tree_hist_batched(n, d, n_nodes, block):
    """Multi-node kernel == per-node oracle, including unaligned row counts."""
    rng = np.random.default_rng(n + n_nodes)
    codes = rng.integers(0, d, n).astype(np.int32)
    y = rng.normal(size=n).astype(np.float32)
    cond = (rng.random((n, n_nodes)) < 0.5).astype(np.float32)
    got = ops.tree_hist_batched(jnp.asarray(codes), jnp.asarray(y),
                                jnp.asarray(cond), d, block_rows=block,
                                interpret=True)
    want = ref.tree_hist_batched_ref(jnp.asarray(codes), jnp.asarray(y),
                                     jnp.asarray(cond), d)
    assert got.shape == (n_nodes, d, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n", [1, 100, 517, 513])
def test_kernels_pad_unaligned_rows(n):
    """The raw pallas entry points accept any row count: rows are padded with
    zeroed cond/payload instead of hard-asserting n % block_rows == 0."""
    from repro.kernels.seg_aggregate import seg_aggregate_pallas
    from repro.kernels.tree_hist import tree_hist_pallas
    rng = np.random.default_rng(n)
    d = 6
    codes = rng.integers(0, d, n).astype(np.int32)
    y = rng.normal(size=n).astype(np.float32)
    cond = (rng.random(n) < 0.5).astype(np.float32)
    got = tree_hist_pallas(jnp.asarray(codes), jnp.asarray(y),
                           jnp.asarray(cond), d, block_rows=256,
                           interpret=True)
    want = ref.tree_hist_ref(jnp.asarray(codes), jnp.asarray(y),
                             jnp.asarray(cond), d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    pay = rng.normal(size=(n, 3)).astype(np.float32)
    seg = rng.integers(0, d, n).astype(np.int32)
    got = seg_aggregate_pallas(jnp.asarray(seg), jnp.asarray(pay), d,
                               block_rows=256, interpret=True)
    want = ref.seg_aggregate_ref(jnp.asarray(seg), jnp.asarray(pay), d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def _fused_case(n, n_cond=1, extra_hist=False, seed=0):
    """Two seg buckets + hist(s) over one shared row block — the whole-step
    union the launch-level fusion path builds (DESIGN.md §10)."""
    rng = np.random.default_rng(n + n_cond + seed)
    S1, W1, S2, W2, D = 13, 5, 7, 3, 6
    c1 = rng.integers(0, S1, n).astype(np.int32)
    c2 = rng.integers(0, S2, n).astype(np.int32)
    ch = rng.integers(0, D, n).astype(np.int32)
    p1 = rng.normal(size=(n, W1)).astype(np.float32)
    p2 = rng.normal(size=(n, W2)).astype(np.float32)
    cond = (rng.random((n, n_cond)) < 0.5).astype(np.float32)
    y = rng.normal(size=n).astype(np.float32)
    yk = np.stack([np.ones(n, np.float32), y, y * y], axis=1)
    off = W1 + W2
    pay_cols = [p1, p2, cond]
    codes_cols = [c1, c2, ch]
    specs = [ops.ReduceSpec("seg", 0, S1, W1, 0),
             ops.ReduceSpec("seg", 1, S2, W2, W1),
             ops.ReduceSpec("hist", 2, D, 3 * n_cond, off, n_cond=n_cond,
                            yk_off=off + n_cond)]
    off += n_cond
    if extra_hist:
        # second hist on a different code column but SHARING the yk triple
        # (the lowering dedups yk per distinct y attribute)
        D2 = 9
        codes_cols.append(rng.integers(0, D2, n).astype(np.int32))
        c2nd = (rng.random((n, n_cond)) < 0.5).astype(np.float32)
        pay_cols.append(c2nd)
        specs.append(ops.ReduceSpec("hist", 3, D2, 3 * n_cond, off,
                                    n_cond=n_cond, yk_off=off + n_cond))
        off += n_cond
    pay_cols.append(yk)
    codes = jnp.asarray(np.stack(codes_cols, axis=1))
    fpay = jnp.asarray(np.concatenate(pay_cols, axis=1))
    return codes, fpay, tuple(specs)


@pytest.mark.parametrize("n", [64, 100, 517, 2048])
@pytest.mark.parametrize("double_buffer", [True, False])
def test_fused_scan_block_multi_spec(n, double_buffer):
    codes, fpay, specs = _fused_case(n)
    got = ops.fused_scan_block(codes, fpay, specs, block_rows=128,
                               interpret=True, double_buffer=double_buffer)
    want = ref.fused_scan_block_ref(codes, fpay, specs)
    for sp, g, w in zip(specs, got, want):
        assert g.shape == (sp.n_segments, sp.width)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4, err_msg=str(sp))


@pytest.mark.parametrize("n,n_cond", [(257, 4), (1000, 8)])
def test_fused_scan_block_batched_cond(n, n_cond):
    """Frontier-batched hists (n_cond = node-axis width) inside the fused
    launch, plus a second hist sharing the same yk columns."""
    codes, fpay, specs = _fused_case(n, n_cond=n_cond, extra_hist=True)
    got = ops.fused_scan_block(codes, fpay, specs, block_rows=256,
                               interpret=True)
    want = ref.fused_scan_block_ref(codes, fpay, specs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("double_buffer", [True, False])
def test_fused_scan_block_segment_tiles(double_buffer):
    """Reductions wider than one VMEM segment tile split over the grid's
    tile axis; a narrow reduction in the same launch sits out later tiles,
    and a wide hist owns fewer tiles than the widest reduction."""
    from repro.kernels.fused_scan import segment_tile
    n, br = 1500, 1024
    rng = np.random.default_rng(3)
    S1, W1, S2, W2, D, nc = 3000, 3, 40, 2, 2000, 2
    codes = np.stack([rng.integers(0, S1, n), rng.integers(0, S2, n),
                      rng.integers(0, D, n)], axis=1).astype(np.int32)
    y = rng.normal(size=n).astype(np.float32)
    fpay = np.concatenate(
        [rng.normal(size=(n, W1 + W2)).astype(np.float32),
         (rng.random((n, nc)) < 0.5).astype(np.float32),
         np.stack([np.ones(n, np.float32), y, y * y], axis=1)], axis=1)
    specs = (ops.ReduceSpec("seg", 0, S1, W1, 0),
             ops.ReduceSpec("seg", 1, S2, W2, W1),
             ops.ReduceSpec("hist", 2, D, 3 * nc, W1 + W2, n_cond=nc,
                            yk_off=W1 + W2 + nc))
    tile = segment_tile(specs, 8, 8, br)
    assert D > tile and S1 > 2 * tile
    got = ops.fused_scan_block(jnp.asarray(codes), jnp.asarray(fpay), specs,
                               block_rows=br, interpret=True,
                               double_buffer=double_buffer)
    want = ref.fused_scan_block_ref(jnp.asarray(codes), jnp.asarray(fpay),
                                    specs)
    for sp, g, w in zip(specs, got, want):
        assert g.shape == (sp.n_segments, sp.width)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4, err_msg=str(sp))


def test_fused_scan_block_dbuf_matches_grid_bitwise():
    """The two-slot DMA pipeline is a pure data-movement change: it must be
    bit-identical to the grid-pipelined path, not merely close."""
    codes, fpay, specs = _fused_case(517, n_cond=2, extra_hist=True)
    a = ops.fused_scan_block(codes, fpay, specs, block_rows=128,
                             interpret=True, double_buffer=True)
    b = ops.fused_scan_block(codes, fpay, specs, block_rows=128,
                             interpret=True, double_buffer=False)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("b,h,hkv,s,d", [(1, 2, 1, 64, 8), (2, 4, 2, 100, 16),
                                         (1, 4, 4, 96, 32)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 24), (False, 0)])
def test_flash_attention(b, h, hkv, s, d, causal, window):
    rng = np.random.default_rng(b * s)
    q = rng.normal(size=(b, h, s, d)).astype(np.float32)
    k = rng.normal(size=(b, hkv, s, d)).astype(np.float32)
    v = rng.normal(size=(b, hkv, s, d)).astype(np.float32)
    got = ops.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=causal, window=window, block_q=32,
                              block_k=32, interpret=True)
    want = ref.attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, 2, 64, 16)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 64, 16)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 64, 16)), jnp.bfloat16)
    got = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                              interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=5e-2, atol=5e-2)
