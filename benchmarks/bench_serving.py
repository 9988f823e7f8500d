"""Sustained-load serving stress: one writer, N readers, one ViewServer.

The serving contract (DESIGN.md §8) is concurrency machinery — wait-free
epoch-pinned reads behind a single-writer update stream — so its benchmark
must *be* concurrent: a writer thread folds fixed-size fact updates through
``ViewServer.apply`` while reader threads hammer ``read()``; a deterministic
laggard phase then pins more epochs than the budget allows to exercise LRU
eviction (``EpochEvictedError``) under churn.

What it measures (``JSON_PAYLOAD`` → ``BENCH_serving.json`` via
``benchmarks/run.py``):

* reader-observed read latency p50/p99 (includes ``block_until_ready`` —
  the caller's sync, like real serving traffic) and the server's own
  dispatch-wall histogram (``stats()["read_us"]``);
* sustained ticks/s through the writer;
* eviction churn: evicted pins + reads that landed on an evicted epoch;
* contract fields the perf gate holds hard: zero rejected updates, zero
  reader errors, one recorded workload signature per served view, and a
  non-degenerate latency distribution.

Metrics and the workload recorder are ON for the whole run — the harness
doubles as the regression net for the no-sync rule.  The engine's spans go
to the JAX profiler's trace (DESIGN.md §11): run it under
``jax.profiler.trace`` to see them beside the device's operations.

    PYTHONPATH=src python -m benchmarks.bench_serving
"""

from __future__ import annotations

import json
import os
import threading
import time

import numpy as np

from benchmarks.common import BENCH_SCALE, row

#: machine-readable results of the last ``main()`` run (benchmarks/run.py
#: writes this out as BENCH_serving.json)
JSON_PAYLOAD: dict = {}

N_READERS = 3
MAX_PINNED = 4
#: laggard phase holds this many distinct-epoch pins (> MAX_PINNED)
N_LAGGARD_PINS = MAX_PINNED + 2


def _n_ticks() -> int:
    env = os.environ.get("BENCH_SERVING_TICKS")
    if env:
        return max(int(env), 4)
    return max(int(round(200 * BENCH_SCALE)), 8)


def main():
    import jax

    from repro import obs
    from repro.data import datasets as D
    from repro.ml.online import OnlineRidge
    from benchmarks.bench_ivm import _fact_update

    ds = D.make("favorita", scale=BENCH_SCALE)
    rng = np.random.default_rng(7)
    n_ticks = _n_ticks()

    olr = OnlineRidge(ds)
    olr.fit()
    srv = olr.view.serve(max_pinned_epochs=MAX_PINNED, warn_epoch_lag=2)
    workload = olr.view._database.workload

    # fixed-size updates -> one pad bucket -> steady state after the warmup
    upd = _fact_update(ds, rng, 0.01)
    srv.apply(upd)                           # warm the tick runner
    srv.read()                               # warm the read path
    read_hist = obs.Histogram("bench.read_synced_us")

    stop = threading.Event()
    errors = []

    def writer():
        try:
            for _ in range(n_ticks):
                srv.apply(upd)
        except Exception as e:               # pragma: no cover - bench guard
            errors.append(f"writer: {e!r}")
        finally:
            stop.set()

    def reader():
        try:
            while not stop.is_set():
                t0 = time.perf_counter()
                out = srv.read()
                jax.block_until_ready(out)   # the caller's sync
                read_hist.observe((time.perf_counter() - t0) * 1e6)
        except Exception as e:               # pragma: no cover - bench guard
            errors.append(f"reader: {e!r}")

    threads = [threading.Thread(target=writer)]
    threads += [threading.Thread(target=reader) for _ in range(N_READERS)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall_s = time.perf_counter() - t0

    # deterministic eviction churn: hold more distinct-epoch pins than the
    # budget, advancing an epoch between takes, then read the oldest —
    # transient reader pins alone never outlive the LRU window
    from repro.core.ivm import EpochEvictedError

    evicted_before = olr.maintained.n_evicted_pins
    held = []
    for _ in range(N_LAGGARD_PINS):
        pin = srv.snapshot()
        held.append((pin, pin.__enter__()))
        srv.apply(upd)
    n_evicted_reads = 0
    for pin, view in held:
        try:
            view.results()
        except EpochEvictedError:
            n_evicted_reads += 1
        pin.__exit__(None, None, None)
    n_evictions = olr.maintained.n_evicted_pins - evicted_before

    stats = srv.stats()
    rh = read_hist.snapshot()
    wl = workload.by_signature()
    served_sigs = sum(1 for e in wl.values()
                      if "pinned_read" in e["hits"])

    JSON_PAYLOAD.clear()
    JSON_PAYLOAD.update({
        "dataset": "favorita", "scale": BENCH_SCALE,
        "n_ticks": n_ticks, "n_readers": N_READERS,
        "max_pinned_epochs": MAX_PINNED,
        "wall_s": wall_s,
        "ticks_per_s": n_ticks / wall_s,
        # reader-observed (synced) latency — the serving SLO numbers
        "read_count": int(rh["count"]),
        "read_p50_us": rh["p50"], "read_p99_us": rh["p99"],
        # server-side dispatch walls (no sync — the telemetry view)
        "server_read_p50_us": stats["read_us"]["p50"],
        "server_read_p99_us": stats["read_us"]["p99"],
        "tick_p50_us": stats["tick_us"]["p50"],
        "tick_p99_us": stats["tick_us"]["p99"],
        # eviction churn
        "n_evictions": int(n_evictions),
        "n_evicted_reads": int(n_evicted_reads),
        "pinned_epochs_hwm": stats["pinned_epochs_hwm"],
        # contract fields (perf gate holds these hard)
        "n_rejected_updates": int(stats["n_rejected_updates"]),
        "n_reader_errors": len(errors),
        "served_view_signatures": int(served_sigs),
        "n_served_views": len(olr.view.names),
        "errors": errors,
    })
    return [
        row("serving/read_p50", rh["p50"] / 1e6,
            f"readers={N_READERS};n={int(rh['count'])}"),
        row("serving/read_p99", rh["p99"] / 1e6,
            f"readers={N_READERS};n={int(rh['count'])}"),
        row("serving/tick", 1.0 / max(JSON_PAYLOAD["ticks_per_s"], 1e-9),
            f"ticks_per_s={JSON_PAYLOAD['ticks_per_s']:.1f};"
            f"evictions={n_evictions};"
            f"evicted_reads={n_evicted_reads};"
            f"rejected={stats['n_rejected_updates']};"
            f"errors={len(errors)}"),
    ]


if __name__ == "__main__":
    lines = main()
    print("\n".join(lines))
    path = os.environ.get("BENCH_SERVING_JSON", "BENCH_serving.json")
    with open(path, "w") as f:
        json.dump(JSON_PAYLOAD, f, indent=1, sort_keys=True)
    print(f"# wrote {path}")
