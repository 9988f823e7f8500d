#!/usr/bin/env python
"""CI perf gate: diff a fresh benchmark payload against its committed
baseline and fail on regression beyond tolerance (ROADMAP item 5).

Understands both payload schemas — the gated sections are whatever the
baseline file carries:

* ``BENCH_kernels.json``: kernel rooflines + fused/autotuned e2e speedups.
* ``BENCH_ivm.json``: IVM tick/read latencies plus the sharded rows
  (per-mesh steady-state tick and serving read).  Contract fields gate
  hard — ``steady_state_retraces`` must stay 0 (a retrace in steady state
  is a jit-cache bug, not noise) and the sharded epochs must stay allclose
  to the single-device recompute; wall times gate loose.
* ``BENCH_serving.json``: the sustained-load serving stress
  (``benchmarks/bench_serving.py``).  Contract fields gate hard — zero
  rejected updates, zero reader-thread errors, eviction churn actually
  exercised, one recorded workload signature per served view, and a
  non-degenerate latency distribution; p50/p99 read latency and ticks/s
  gate loose.
* ``BENCH_routing.json``: ad-hoc query routing
  (``benchmarks/bench_routing.py``).  Contract fields gate hard — every
  tier allclose to a from-scratch compile (a routed answer that drifts is
  a soundness bug, not noise), zero admission failures, LRU eviction
  exercised, and the workload hit rate within ``--ratio-tol`` of
  baseline; per-tier routed latencies gate loose.

Two classes of metric, gated differently:

* **machine-portable ratios** (the real trajectory claims) gate tight:
  each end-to-end ``speedup_fused_auto`` (autotuned+fused pallas vs
  static-block unfused) must stay within ``--ratio-tol`` of baseline AND
  above the ``--min-speedup`` hard floor; ``allclose_xla`` must hold; the
  static kernel-launch-site counts must not grow (launch fusion is a
  compile-time property — any increase is a code regression, not noise).

* **wall times** gate loose (``--time-tol``, default 1.5 → a kernel may be
  up to 2.5x slower than baseline before failing): CI runners vary, and the
  generous multiple only catches catastrophic regressions (an interpret-mode
  fallback on TPU, a lost jit cache, an accidentally quadratic path).

Refresh the baseline intentionally with ``tools/update_perf_baseline.py``
after a change that legitimately moves the numbers.

    python tools/perf_gate.py BENCH_kernels.json benchmarks/baselines/BENCH_kernels.json
"""

from __future__ import annotations

import argparse
import json
import sys


def check(current: dict, baseline: dict, *, time_tol: float,
          ratio_tol: float, min_speedup: float):
    """Yields (name, baseline_value, current_value, limit, ok) rows."""
    for name, base in sorted(baseline.get("kernels", {}).items()):
        cur = current.get("kernels", {}).get(name)
        if cur is None:
            yield (f"kernels/{name}/t_s", base["t_s"], None, "present", False)
            continue
        limit = base["t_s"] * (1.0 + time_tol)
        yield (f"kernels/{name}/t_s", base["t_s"], cur["t_s"],
               f"<= {limit:.3g}", cur["t_s"] <= limit)

    for name, base in sorted(baseline.get("e2e", {}).items()):
        cur = current.get("e2e", {}).get(name)
        if cur is None:
            yield (f"e2e/{name}", base.get("speedup_fused_auto"), None,
                   "present", False)
            continue
        floor = max(base["speedup_fused_auto"] * (1.0 - ratio_tol),
                    min_speedup)
        sp = cur["speedup_fused_auto"]
        yield (f"e2e/{name}/speedup_fused_auto", base["speedup_fused_auto"],
               sp, f">= {floor:.3g}", sp >= floor)
        yield (f"e2e/{name}/allclose_xla", base["allclose_xla"],
               cur["allclose_xla"], "== True", bool(cur["allclose_xla"]))
        yield (f"e2e/{name}/n_launches_fused", base["n_launches_fused"],
               cur["n_launches_fused"],
               f"<= {base['n_launches_fused']}",
               cur["n_launches_fused"] <= base["n_launches_fused"])

    # --- BENCH_ivm.json schema ---------------------------------------
    if "steady_state_retraces" in baseline:
        cur_r = current.get("steady_state_retraces")
        yield ("ivm/steady_state_retraces", baseline["steady_state_retraces"],
               cur_r, "== 0", cur_r == 0)
        for t in ("tick_us_resident", "delta_us"):
            if t not in baseline:
                continue
            cur_t = current.get(t)
            limit = baseline[t] * (1.0 + time_tol)
            yield (f"ivm/{t}", baseline[t], cur_t, f"<= {limit:.3g}",
                   cur_t is not None and cur_t <= limit)

    # --- BENCH_serving.json schema -----------------------------------
    if "ticks_per_s" in baseline:
        # contract fields: hard gates (concurrency bugs, not noise)
        for c in ("n_rejected_updates", "n_reader_errors"):
            yield (f"serving/{c}", baseline.get(c), current.get(c),
                   "== 0", current.get(c) == 0)
        n_views = current.get("n_served_views")
        sigs = current.get("served_view_signatures")
        yield ("serving/served_view_signatures",
               baseline.get("served_view_signatures"), sigs,
               f">= {n_views}",
               sigs is not None and n_views is not None and sigs >= n_views)
        yield ("serving/n_evictions", baseline.get("n_evictions"),
               current.get("n_evictions"), ">= 1",
               (current.get("n_evictions") or 0) >= 1)
        p50 = current.get("read_p50_us")
        p99 = current.get("read_p99_us")
        yield ("serving/read_count", baseline.get("read_count"),
               current.get("read_count"), ">= 1",
               bool(current.get("read_count")))
        yield ("serving/read_p50_us_nonzero", baseline.get("read_p50_us"),
               p50, "> 0", p50 is not None and p50 > 0)
        yield ("serving/read_p99_ge_p50", baseline.get("read_p99_us"), p99,
               ">= p50",
               p99 is not None and p50 is not None and p99 >= p50)
        # wall times / throughput: loose gates (runner noise)
        for t in ("read_p50_us", "read_p99_us"):
            if t not in baseline:
                continue
            limit = baseline[t] * (1.0 + time_tol)
            cur_t = current.get(t)
            yield (f"serving/{t}", baseline[t], cur_t, f"<= {limit:.3g}",
                   cur_t is not None and cur_t <= limit)
        floor = baseline["ticks_per_s"] / (1.0 + time_tol)
        cur_tps = current.get("ticks_per_s")
        yield ("serving/ticks_per_s", baseline["ticks_per_s"], cur_tps,
               f">= {floor:.3g}",
               cur_tps is not None and cur_tps >= floor)

    # --- BENCH_routing.json schema -----------------------------------
    if "route_hit_rate" in baseline:
        # contract fields: hard gates (routing soundness, not noise)
        for c in ("allclose_exact", "allclose_subsumed",
                  "allclose_compiled", "evicted_recompiles"):
            yield (f"routing/{c}", baseline.get(c), current.get(c),
                   "== True", bool(current.get(c)))
        yield ("routing/n_admission_failures",
               baseline.get("n_admission_failures"),
               current.get("n_admission_failures"), "== 0",
               current.get("n_admission_failures") == 0)
        yield ("routing/n_evictions", baseline.get("n_evictions"),
               current.get("n_evictions"), ">= 1",
               (current.get("n_evictions") or 0) >= 1)
        hr_floor = baseline["route_hit_rate"] * (1.0 - ratio_tol)
        cur_hr = current.get("route_hit_rate")
        yield ("routing/route_hit_rate", baseline["route_hit_rate"], cur_hr,
               f">= {hr_floor:.3g}",
               cur_hr is not None and cur_hr >= hr_floor)
        # routed latencies: loose gates (runner noise)
        for t in ("route_exact_p50_us", "route_exact_p99_us",
                  "route_subsumed_p50_us", "route_subsumed_p99_us",
                  "route_cached_scan_p50_us", "route_cached_scan_p99_us",
                  "route_compile_us"):
            if t not in baseline:
                continue
            limit = baseline[t] * (1.0 + time_tol)
            cur_t = current.get(t)
            yield (f"routing/{t}", baseline[t], cur_t, f"<= {limit:.3g}",
                   cur_t is not None and cur_t <= limit)

    for name, base in sorted(baseline.get("sharded", {}).items()):
        cur = current.get("sharded", {}).get(name)
        if cur is None:
            yield (f"sharded/{name}", base["tick_us_sharded"], None,
                   "present", False)
            continue
        yield (f"sharded/{name}/steady_state_retraces",
               base["steady_state_retraces"], cur.get("steady_state_retraces"),
               "== 0", cur.get("steady_state_retraces") == 0)
        yield (f"sharded/{name}/allclose_local", base["allclose_local"],
               cur.get("allclose_local"), "== True",
               bool(cur.get("allclose_local")))
        for t in ("tick_us_sharded", "read_us_sharded"):
            limit = base[t] * (1.0 + time_tol)
            yield (f"sharded/{name}/{t}", base[t], cur.get(t),
                   f"<= {limit:.3g}",
                   cur.get(t) is not None and cur[t] <= limit)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("current", help="fresh BENCH_kernels.json")
    ap.add_argument("baseline",
                    default="benchmarks/baselines/BENCH_kernels.json",
                    nargs="?", help="committed baseline")
    ap.add_argument("--time-tol", type=float, default=1.5,
                    help="allowed relative wall-time growth (1.5 -> 2.5x)")
    ap.add_argument("--ratio-tol", type=float, default=0.4,
                    help="allowed relative drop of speedup ratios")
    ap.add_argument("--min-speedup", type=float, default=0.9,
                    help="hard floor for fused-vs-static speedups")
    args = ap.parse_args(argv)

    with open(args.current) as f:
        current = json.load(f)
    with open(args.baseline) as f:
        baseline = json.load(f)

    failed = 0
    print(f"{'metric':<44} {'baseline':>12} {'current':>12} "
          f"{'limit':>12}  status")
    for name, base, cur, limit, ok in check(
            current, baseline, time_tol=args.time_tol,
            ratio_tol=args.ratio_tol, min_speedup=args.min_speedup):
        failed += not ok

        def fmt(v):
            if isinstance(v, bool):
                return str(v)
            if v is None:
                return "missing"
            return f"{v:.4g}"

        print(f"{name:<44} {fmt(base):>12} {fmt(cur):>12} {limit:>12}  "
              f"{'ok' if ok else 'FAIL'}")
    if failed:
        print(f"\nperf gate: {failed} metric(s) regressed beyond tolerance "
              "(refresh intentionally via tools/update_perf_baseline.py)")
        return 1
    print("\nperf gate: all metrics within tolerance")
    return 0


if __name__ == "__main__":
    sys.exit(main())
