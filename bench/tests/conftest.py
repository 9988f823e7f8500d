"""The benchmark's own tests, run by path on the CPU:

    JAX_PLATFORMS=cpu python -m pytest bench/tests

They drive ``bench.run.run`` at a test size (the chip check is the CLI's,
so it is skipped), and the trace reduction on a recorded trace."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

#: the test size: fact rows and the cap on every key or category domain
ROWS, DOMAIN_CAP = 20_000, 60
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
#: a seed past 32 bits, as the benchmark's are
SEED = 3_000_000_019


def run_cell(name: str, seconds: float = 1.0, seed: int = SEED):
    from bench import run as R

    cell = R.Cell(name)
    return R.run(cell, seed, seconds, False, CPU,
                 cfg=cell.cfg.shrunk(ROWS, DOMAIN_CAP))
