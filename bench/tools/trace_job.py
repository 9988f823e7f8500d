#!/usr/bin/env python3
"""A cell's traced run, as ``bench/run.py --trace 1`` makes it, then where
its window's time went by the program's own names.

    python3 bench/tools/trace_job.py --workload retailer.ridge \
        --seed 3000000301 --out trace_job.retailer.json

Standard output ends with the benchmark's own result line (the same
set-up, window, readers and check).  The trace is kept, and ``--out``
gets, from it:

* ``breakdown``: the benchmark's breakdown with the program's ``repro.*``
  spans among the host spans, so each idle gap is named by the innermost
  span around it, the program's where one covers it;
* ``gaps``: the longest idle gaps of the device, each with the events
  inside it: the host's (JAX's own included: dispatch, transfers), the
  runtime's threads' alone (all but the Python thread), and the device's;
* ``app_host_s``, ``idle_unspanned``: what the program's spans explain of
  the window (``bench/lib/scopes.py``);
* ``scopes``: device seconds by program scope (``scan.<relation>/<part>``)
  and each part's share of busy time;
* ``top_ops``: the device's longest ops with their opcodes and scopes;
* ``seconds``: the run to its result line, and the reading of the trace.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import run as R  # noqa: E402  (sets the paths and T_PROCESS)
from bench.lib import scopes, trace  # noqa: E402


def traced_run(cell, seed: int, seconds: float, device: dict, cfg=None):
    """``bench.run.run`` with the trace directory kept: ``(result, the
    window's ``trace.Reduced``, the trace directory, the batch's
    instructions by name)``."""
    r = R.CellRun(cell, seed, cfg)
    r.setup()
    setup_s = time.perf_counter() - R.T_PROCESS
    kept = []
    mkdtemp = tempfile.mkdtemp

    def keep(*a, **k):
        kept.append(mkdtemp(*a, **k))
        return kept[-1]

    with mock.patch.object(R.tempfile, "mkdtemp", keep), \
            mock.patch.object(R.shutil, "rmtree", lambda *a, **k: None):
        r.window(seconds, True)
    ops = scopes.hlo_ops(scopes.compiled_text(r.units.unit))
    reduced = r.res.trace
    result = r.result(device, setup_s)
    return result, reduced, kept[0], ops


def analyse(reduced: trace.Reduced, tdir: str, ops: dict) -> dict:
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    pd = ProfileData.from_file(path)
    r = trace.Reduced(scopes.with_spans(
        {"spans": [list(s) for s in reduced.spans],
         "devices": reduced.devices}, scopes.program_spans(pd)))
    busy = r.busy_s()
    gaps = []
    if r.busy:
        dev = max(r.busy, key=lambda d: sum(e - s for s, e in r.busy[d]))
        gaps = sorted(scopes.idle(r, r.busy[dev]), key=lambda g: g[1] - g[0],
                      reverse=True)
    per = scopes.scope_s(r, ops)
    tot = {}
    for v in per.values():
        for s, t in v.items():
            tot[s] = tot.get(s, 0.0) + t / len(per)
    op_s = {}
    for v in r.devices.values():
        for n, t in v["op_s"].items():
            op_s[n] = op_s.get(n, 0.0) + t / len(r.devices)
    top = sorted(op_s, key=op_s.get, reverse=True)[:10]
    return {
        "window_s": r.window_s, "busy_s": busy,
        "breakdown": r.breakdown(),
        "program_spans": sorted({n for n, _, _ in r.spans
                                 if n.startswith(scopes.PROGRAM)}),
        "app_host_s": scopes.app_host_s(r),
        "idle_unspanned": scopes.idle_unspanned(r),
        "partials_share": scopes.share_under(r, ops, "partials"),
        "gaps": [{"start_s": s - r.window[0], "seconds": e - s,
                  "named": r._host_at((s + e) / 2),
                  "host_events": scopes.events_in(pd, s, e),
                  "runtime_events": scopes.events_in(pd, s, e,
                                                     skip_line="python"),
                  "device_events": scopes.events_in(pd, s, e, "/device:")}
                 for s, e in gaps[:3]],
        "scopes": {s: [t, 100.0 * t / busy if busy else None] for s, t in
                   sorted(tot.items(), key=lambda kv: -kv[1])},
        "op_s_in_hlo": sum(t for n, t in op_s.items() if n in ops)
        / max(sum(op_s.values()), 1e-30),
        "top_ops": [[n, op_s[n]] + list(ops.get(n, ("?", "?")))
                    for n in top],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = R.Cell(args.workload)
    device = R.device_check(cell.chips)

    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result, reduced, tdir, ops = traced_run(cell, args.seed, args.seconds,
                                            device)
    to_line = time.perf_counter() - R.T_PROCESS
    print(json.dumps(result), flush=True)
    t = time.perf_counter()
    out = analyse(reduced, tdir, ops)
    shutil.rmtree(tdir, ignore_errors=True)
    out["seconds"] = {"run_to_line": to_line,
                      "analysis": time.perf_counter() - t}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    R.log(json.dumps({k: out[k] for k in (
        "app_host_s", "idle_unspanned", "partials_share", "program_spans",
        "seconds", "top_ops")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
