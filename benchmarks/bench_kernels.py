"""Kernel roofline harness: achieved vs peak FLOPs and bandwidth, per kernel
and per end-to-end batch (ISSUE 6 / ROADMAP item 5).

Per kernel (``seg_aggregate``, ``tree_hist``, the whole-step
``fused_scan_block``, ``covar_xtx``): analytic FLOP/byte counts over a fixed
shape, warm median wall time, and utilization against the host platform's
peaks from ``benchmarks.common.peaks()`` (auto-detected backend;
env/CLI-overridable — CPU CI reports against honest host ceilings, not TPU
constants).  Every kernel is also checked against its jnp oracle, so the
bench doubles as a correctness gate.

End-to-end: the warm ridge-covar batch and the warm frontier-batched tree
build, each autotuned+fused (``block_size="auto"``, ``block_rows="auto"``,
``fuse_kernels=True``) vs static-block unfused — the ``speedup_fused_auto``
ratio is the machine-portable number CI's perf gate tracks (absolute times
vary per runner; the ratio is the trajectory claim: the fused, tuned path
must keep beating the static path).

Machine-readable results land in ``JSON_PAYLOAD``; ``benchmarks/run.py``
writes them to ``BENCH_kernels.json`` (env ``BENCH_KERNELS_JSON``) and CI
diffs that against ``benchmarks/baselines/BENCH_kernels.json`` via
``tools/perf_gate.py``.

    PYTHONPATH=src python -m benchmarks.bench_kernels
"""

from __future__ import annotations

import os
import tempfile
import time

import numpy as np
import jax.numpy as jnp

from benchmarks.common import BENCH_SCALE, peaks, row, timeit
from repro.kernels import ops, ref

#: machine-readable results of the last ``main()`` run (benchmarks/run.py
#: writes this out as BENCH_kernels.json)
JSON_PAYLOAD: dict = {}

#: on CPU the kernels execute in interpret mode — a correctness vehicle with
#: real (if modest) throughput; on TPU the same harness measures the MXU
def _interpret() -> bool:
    import jax
    return jax.default_backend() != "tpu"


def _entry(t_s: float, flops: float, nbytes: float, pk: dict,
           maxerr: float) -> dict:
    return {"t_s": t_s, "flops": flops, "bytes": nbytes,
            "achieved_flops": flops / t_s, "achieved_bw": nbytes / t_s,
            "util_flops": flops / t_s / pk["flops"],
            "util_bw": nbytes / t_s / pk["hbm_bw"],
            "maxerr": maxerr}


def _kernel_rows(pk: dict, interpret: bool):
    rng = np.random.default_rng(0)
    lines, kernels = [], {}

    # seg_aggregate: one-hot matmul scatter, (n, W) rows into S segments
    n, S, W = 32768, 128, 16
    seg = jnp.asarray(rng.integers(0, S, n).astype(np.int32))
    pay = jnp.asarray(rng.normal(size=(n, W)).astype(np.float32))
    t = timeit(lambda: ops.seg_aggregate(seg, pay, S, interpret=interpret))
    err = float(jnp.max(jnp.abs(ops.seg_aggregate(seg, pay, S,
                                                  interpret=interpret)
                                - ref.seg_aggregate_ref(seg, pay, S))))
    kernels["seg_aggregate"] = _entry(t, 2.0 * n * S * W,
                                      4.0 * n * (1 + W) + 4.0 * S * W, pk, err)

    # tree_hist: cond ⊗ [1, y, y²] histogram over D buckets
    D = 64
    codes = jnp.asarray(rng.integers(0, D, n).astype(np.int32))
    y = jnp.asarray(rng.normal(size=n).astype(np.float32))
    cond = jnp.asarray((rng.random(n) < 0.5).astype(np.float32))
    t = timeit(lambda: ops.tree_hist(codes, y, cond, D, interpret=interpret))
    err = float(jnp.max(jnp.abs(ops.tree_hist(codes, y, cond, D,
                                              interpret=interpret)
                                - ref.tree_hist_ref(codes, y, cond, D))))
    kernels["tree_hist"] = _entry(t, 2.0 * n * D * 3 + 5.0 * n,
                                  4.0 * n * 3 + 4.0 * D * 3, pk, err)

    # fused_scan_block: the whole-step union — two seg buckets + one hist
    # in ONE launch (the row block is read once for all three)
    S2, W2 = 32, 8
    specs = (ops.ReduceSpec("seg", 0, S, W, 0),
             ops.ReduceSpec("seg", 1, S2, W2, W),
             ops.ReduceSpec("hist", 2, D, 3, W + W2, n_cond=1,
                            yk_off=W + W2 + 1))
    fcodes = jnp.stack([seg, jnp.asarray(rng.integers(0, S2, n, dtype=np.int32)),
                        codes], axis=1)
    yk = jnp.stack([jnp.ones_like(y), y, y * y], axis=1)
    fpay = jnp.concatenate(
        [pay, jnp.asarray(rng.normal(size=(n, W2)).astype(np.float32)),
         cond[:, None], yk], axis=1)
    t = timeit(lambda: ops.fused_scan_block(fcodes, fpay, specs,
                                            interpret=interpret))
    outs = ops.fused_scan_block(fcodes, fpay, specs, interpret=interpret)
    refs = ref.fused_scan_block_ref(fcodes, fpay, specs)
    err = max(float(jnp.max(jnp.abs(o - r))) for o, r in zip(outs, refs))
    fl = 2.0 * n * (S * W + S2 * W2 + D * 3)
    nb = 4.0 * n * (3 + fpay.shape[1]) + 4.0 * (S * W + S2 * W2 + D * 3)
    kernels["fused_scan_block"] = _entry(t, fl, nb, pk, err)

    # covar_xtx: Xᵀ diag(w) X
    nc, F = 8192, 32
    x = jnp.asarray(rng.normal(size=(nc, F)).astype(np.float32))
    w = jnp.ones(nc, jnp.float32)
    t = timeit(lambda: ops.covar_xtx(x, w, interpret=interpret))
    err = float(jnp.max(jnp.abs(ops.covar_xtx(x, w, interpret=interpret)
                                - ref.covar_xtx_ref(x, w))))
    kernels["covar_xtx"] = _entry(t, 2.0 * nc * F * F,
                                  4.0 * nc * (F + 1) + 4.0 * F * F, pk, err)

    for name, k in kernels.items():
        lines.append(row(
            f"kern/{name}", k["t_s"],
            f"gflops={k['achieved_flops'] / 1e9:.2f};"
            f"gbps={k['achieved_bw'] / 1e9:.2f};"
            f"util_f={k['util_flops']:.4f};util_b={k['util_bw']:.4f};"
            f"maxerr={k['maxerr']:.1e}"))
    return lines, kernels


#: e2e datasets never shrink below this scale: the fused-vs-static ratio is
#: the gated trajectory claim, and at bench-smoke scale (0.01) the warm runs
#: are ~100µs — pure dispatch noise, not kernel work
E2E_SCALE = max(BENCH_SCALE, 0.05)


def _warm_run(handle, reps: int = 5) -> float:
    handle.run()                      # compile + autotune warm-up
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        handle.run()
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def _e2e_ridge(cache: str):
    import repro
    from repro.data import datasets as D
    from repro.ml.covar import covar_queries

    ds = D.make("retailer", scale=E2E_SCALE)
    qs, _ = covar_queries(ds)
    v_auto = repro.connect(ds, config=repro.ExecutionConfig(
        backend="pallas", block_size="auto", block_rows="auto",
        fuse_kernels=True, autotune_cache=cache)).views(qs)
    v_stat = repro.connect(ds, config=repro.ExecutionConfig(
        backend="pallas", fuse_kernels=False)).views(qs)
    v_xla = repro.connect(ds, config=repro.ExecutionConfig(
        backend="xla")).views(qs)

    t_auto = _warm_run(v_auto)
    t_stat = _warm_run(v_stat)
    o_auto, o_xla = v_auto.run(), v_xla.run()
    close = all(np.allclose(np.asarray(o_auto[k]), np.asarray(o_xla[k]),
                            rtol=1e-4, atol=1e-4) for k in o_xla)
    return {"t_fused_auto_s": t_auto, "t_static_unfused_s": t_stat,
            "speedup_fused_auto": t_stat / t_auto,
            "allclose_xla": bool(close),
            "n_launches_fused": v_auto.stats.n_kernel_launches,
            "n_launches_unfused": v_stat.stats.n_kernel_launches}


def _e2e_tree_frontier(cache: str, n_nodes: int = 8):
    """One frontier-batched histogram dispatch (N node masks — the per-level
    unit of CART work), timed warm.  Full ``fit()`` would mix in host-side
    split selection that dilutes the kernel work the gate is about."""
    import jax
    import repro
    from repro.data import datasets as D
    from repro.ml.trees import DecisionTree, stack_mask_params

    ds = D.make("favorita", scale=E2E_SCALE)
    kw = dict(task="regression", max_depth=3, min_instances=20, max_nodes=15,
              node_batch=True)

    def warm_level(config):
        rng = np.random.default_rng(7)
        dt = DecisionTree(ds, config=config, **kw)
        masks = [{f.attr: (rng.random(f.domain) < 0.7).astype(np.float32)
                  for f in dt.features} for _ in range(n_nodes)]
        params = stack_mask_params(dt.features, masks)
        out = jax.block_until_ready(dt.batch.run_batched(ds.db, params))
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(dt.batch.run_batched(ds.db, params))
            times.append(time.perf_counter() - t0)
        return dt, out, sorted(times)[len(times) // 2]

    dt_auto, o_auto, t_auto = warm_level(repro.ExecutionConfig(
        backend="pallas", block_size="auto", block_rows="auto",
        fuse_kernels=True, autotune_cache=cache))
    dt_stat, _, t_stat = warm_level(repro.ExecutionConfig(
        backend="pallas", fuse_kernels=False))
    _, o_xla, _ = warm_level(repro.ExecutionConfig(backend="xla"))

    close = all(np.allclose(np.asarray(o_auto[k]), np.asarray(o_xla[k]),
                            rtol=1e-4, atol=1e-4) for k in o_xla)
    return {"t_fused_auto_s": t_auto, "t_static_unfused_s": t_stat,
            "speedup_fused_auto": t_stat / t_auto,
            "allclose_xla": bool(close),
            "n_launches_fused": dt_auto.batch.stats.n_kernel_launches,
            "n_launches_unfused": dt_stat.batch.stats.n_kernel_launches}


def main():
    pk = peaks()
    interpret = _interpret()
    lines, kernels = _kernel_rows(pk, interpret)

    # e2e comparisons share one autotune cache file so the "warm" claim is
    # honest within the run without leaking state between CI jobs
    cache = os.environ.get("REPRO_AUTOTUNE_CACHE") or os.path.join(
        tempfile.gettempdir(), f"repro_autotune_bench_{os.getpid()}.json")
    e2e = {"ridge": _e2e_ridge(cache), "tree_frontier": _e2e_tree_frontier(cache)}
    for name, r in e2e.items():
        lines.append(row(
            f"e2e/{name}/fused_auto", r["t_fused_auto_s"],
            f"speedup={r['speedup_fused_auto']:.2f}x;"
            f"launches={r['n_launches_fused']}vs{r['n_launches_unfused']};"
            f"allclose_xla={r['allclose_xla']}"))

    JSON_PAYLOAD.clear()
    JSON_PAYLOAD.update({"peaks": pk, "interpret": interpret,
                         "bench_scale": BENCH_SCALE, "e2e_scale": E2E_SCALE,
                         "kernels": kernels, "e2e": e2e})
    return lines


if __name__ == "__main__":
    import json
    print("\n".join(main()))
    print(json.dumps(JSON_PAYLOAD, indent=1, sort_keys=True))
