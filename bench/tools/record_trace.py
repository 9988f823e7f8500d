#!/usr/bin/env python3
"""Record the small trace that ``bench/tests/test_trace.py`` reduces.

    python3 bench/tools/record_trace.py bench/tests/data/trace_small.json.gz

On the chip: a traced window with two host spans around a few jitted
programs and an idle gap between them, extracted by ``bench.lib.trace``
into plain events and written gzipped."""

import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from bench.lib import trace

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((2048, 2048), jnp.float32)
    f(x).block_until_ready()
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.tick"):
                for _ in range(5):
                    f(x).block_until_ready()
            time.sleep(0.05)
    jax.profiler.stop_trace()
    trace.save(trace.extract(tdir), out)


if __name__ == "__main__":
    main(sys.argv[1])
