#!/usr/bin/env python3
"""The control's readings behind each limit of
``bench/limits/<workload>.json``.

    python3 bench/tools/readings.py favorita.ridge --seeds 101-103

The control is the plain reference computed from bfloat16 inputs, put in
the program's place: for each seed, on the chip's host and at the cell's
own size, the cell's data is made from the seed (``bench/lib/datagen.py``),
the control's covar comes from the bfloat16 reference and its model from
the job's solver (``reference.ridge_bgd``), and its compared numbers are
taken against the exact reference (``bench.run.answers`` and
``bench.run.numbers``), as a benchmark run takes the program's.  Each seed prints one JSON line.  The program's readings are
the ``checks`` of benchmark runs (``bench/run.py``); a limit lies above the
largest program reading and below the smallest control reading.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import run as R  # noqa: E402
from bench.lib import datagen, reference  # noqa: E402


def seeds(spec: str):
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def control(cell, seed: int, cfg=None) -> dict:
    """The control's compared numbers on the data of ``seed``."""
    cfg = cfg or cell.cfg
    gen = datagen.Generator(cfg)
    dims = gen.dimensions(seed)
    fact = gen.fact_rows(seed, dims, cfg.n_rows(cfg.fact))
    dims, fact = R.host(dims), R.host(fact)
    s = cell.traffic["jobs"]
    exact = R.answers(reference.Reference(cfg, dims), fact, s)
    low = R.answers(reference.Reference(cfg, dims, reference.bf16_round),
                    fact, s)
    C = low["covar"][0]
    # the control's model comes from the job's own solver, as the program's
    theta = reference.ridge_bgd(C, s["lam"], s["tol"], s["max_iters"])
    return R.numbers(C, theta, exact)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("workload")
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    cell = R.Cell(args.workload)
    R.device_check(cell.chips)
    for s in seeds(args.seeds):
        t = time.perf_counter()
        res = {"seed": s, "control": control(cell, s),
               "wall_s": time.perf_counter() - t}
        print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
