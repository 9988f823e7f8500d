"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  BENCH_SCALE env (default 0.1)
scales the synthetic datasets.  Machine-readable payloads are written per
module — ``BENCH_ivm.json`` (tick latency with/without host round-trips,
retrace counts), ``BENCH_kernels.json`` (rooflines, fused/autotuned e2e),
``BENCH_serving.json`` (sustained-load read p50/p99, ticks/s, eviction
churn; a chrome-trace sample lands in ``trace_serving.json``),
``BENCH_routing.json`` (ad-hoc routing: per-tier latency, hit rate, plan
cache churn) — paths overridable via BENCH_IVM_JSON / BENCH_KERNELS_JSON /
BENCH_SERVING_JSON / BENCH_ROUTING_JSON — so CI can archive the perf
trajectory as artifacts.
"""

from __future__ import annotations

import json
import os
import sys
import traceback


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (bench_fig5_ablation, bench_ivm, bench_kernels,
                            bench_routing, bench_serving, bench_table2_views,
                            bench_table3_aggregates, bench_table45_training,
                            bench_tree_frontier)
    print("name,us_per_call,derived")
    ok = True
    for mod in [bench_table2_views, bench_table3_aggregates,
                bench_table45_training, bench_fig5_ablation, bench_kernels,
                bench_tree_frontier, bench_ivm, bench_serving,
                bench_routing]:
        try:
            for line in mod.main():
                print(line, flush=True)
        except Exception:
            ok = False
            print(f"{mod.__name__},0,FAILED", flush=True)
            traceback.print_exc()

    for payload, env, default in [
            (bench_ivm.JSON_PAYLOAD, "BENCH_IVM_JSON", "BENCH_ivm.json"),
            (bench_kernels.JSON_PAYLOAD, "BENCH_KERNELS_JSON",
             "BENCH_kernels.json"),
            (bench_serving.JSON_PAYLOAD, "BENCH_SERVING_JSON",
             "BENCH_serving.json"),
            (bench_routing.JSON_PAYLOAD, "BENCH_ROUTING_JSON",
             "BENCH_routing.json")]:
        if not payload:
            continue
        path = os.environ.get(env, default)
        with open(path, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"# wrote {path}", file=sys.stderr)

    # dry-run + roofline tables (read from reports/, written by
    # repro.launch.dryrun --all and benchmarks.roofline)
    try:
        if os.path.isdir("reports/dryrun"):
            from benchmarks import report_experiments
            print()
            report_experiments.main()
    except Exception:
        traceback.print_exc()
    if not ok:
        sys.exit(1)


if __name__ == '__main__':
    main()
