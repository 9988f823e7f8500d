"""Paper Figure 5: the covar-matrix batch under increasing optimization.

  per_query    one compile+run per query, nothing shared (the AC/DC-like
               interpreted proxy: no cross-query view sharing)
  single_root  one batch, shared views, all queries at one root
  multi_root   + find-roots (the paper's 2-5x layer)
  parallel     + domain parallelism over 4 devices (``common.on_devices``:
               the accelerator's own devices, or 4 forced host devices on
               the CPU host)
"""

from __future__ import annotations

import os
import sys

from benchmarks.common import (BENCH_SCALE, devices_main, on_devices, row,
                               timeit)
from repro.api import ExecutionConfig, connect
from repro.data import datasets as D
from repro.ml.covar import covar_queries


def parallel_main(ndev: int, name: str) -> dict:
    """The multi-root batch over an ``ndev``-device mesh: median seconds."""
    import jax

    ds = D.make(name, scale=BENCH_SCALE)
    qs, _ = covar_queries(ds)
    mesh = jax.make_mesh((ndev,), ("data",), devices=jax.devices()[:ndev])
    v = connect(ds, config=ExecutionConfig(mesh=mesh)).views(qs)
    return {"seconds": timeit(lambda: v.run())}


def main():
    name = os.environ.get("ABLATION_DATASET", "favorita")
    ds = D.make(name, scale=BENCH_SCALE)
    qs, _ = covar_queries(ds)
    db = connect(ds)
    lines = []

    # per-query: no sharing across queries
    batches = [db.views([q]) for q in qs]
    t_pq = timeit(lambda: [b.run() for b in batches], warmup=1, iters=2)
    lines.append(row(f"f5/{name}/per_query", t_pq, f"queries={len(qs)}"))

    b_sr = db.with_config(multi_root=False).views(qs)
    t_sr = timeit(lambda: b_sr.run())
    lines.append(row(f"f5/{name}/single_root", t_sr,
                     f"V={b_sr.stats.n_views};speedup={t_pq / t_sr:.1f}x"))

    b_mr = db.views(qs)
    t_mr = timeit(lambda: b_mr.run())
    lines.append(row(f"f5/{name}/multi_root", t_mr,
                     f"V={b_mr.stats.n_views};speedup={t_sr / t_mr:.2f}x"))

    # parallel: the same batch domain-parallel over a 4-device mesh
    t_par = on_devices("benchmarks.bench_fig5_ablation", 4, parallel_main,
                       name)["seconds"]
    lines.append(row(f"f5/{name}/parallel4", t_par,
                     f"speedup={t_mr / t_par:.2f}x"))
    return lines


if __name__ == "__main__":
    if not devices_main(sys.argv, parallel_main):
        print("\n".join(main()))
