"""Shared benchmark utilities."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Callable

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BENCH_SCALE = float(os.environ.get("BENCH_SCALE", "0.1"))


def timeit(fn: Callable, *, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds over ``iters`` runs (after warmup).  Blocks on
    JAX async dispatch so device work is actually measured."""
    import jax

    def run():
        return jax.block_until_ready(fn())

    for _ in range(warmup):
        run()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def row(name: str, seconds: float, derived: str = "") -> str:
    return f"{name},{seconds * 1e6:.1f},{derived}"


def on_devices(module: str, ndev: int, fn: Callable[..., dict], *args: str):
    """Run ``fn(ndev, *args)``, a phase that needs ``ndev`` devices, so that
    its numbers come from the same platform as the rest of the run.

    On an accelerator it runs in this process, which holds the chips (a
    child could not open them), and raises if fewer than ``ndev`` are
    present.  On the CPU host it runs in a child whose host platform is
    forced to ``ndev`` devices, because the count is fixed when JAX starts:
    ``python -m <module> --devices <ndev> *args`` must print ``fn``'s JSON
    result as its last line."""
    import jax

    if jax.default_backend() != "cpu":
        if len(jax.devices()) < ndev:
            raise RuntimeError(f"{module}: needs {ndev} devices, "
                               f"{len(jax.devices())} present")
        return fn(ndev, *args)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={ndev}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(REPO, "src"), REPO])
    out = subprocess.run(
        [sys.executable, "-m", module, "--devices", str(ndev), *args],
        check=True, env=env, capture_output=True, text=True, cwd=REPO)
    return json.loads(out.stdout.splitlines()[-1])


def devices_main(argv, fn: Callable[..., dict]) -> bool:
    """The child side of :func:`on_devices`: with ``--devices N *args`` on
    the command line, print ``fn(N, *args)`` as JSON and return True."""
    if len(argv) > 2 and argv[1] == "--devices":
        print(json.dumps(fn(int(argv[2]), *argv[3:]), sort_keys=True))
        return True
    return False
