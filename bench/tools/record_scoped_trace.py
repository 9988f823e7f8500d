#!/usr/bin/env python3
"""Record the small scoped trace that ``bench/tests/test_scopes.py`` reduces.

    python3 bench/tools/record_scoped_trace.py \
        bench/tests/data/trace_scoped.json.gz

On the chip: a traced window of two ``bench.job`` spans.  Each job runs a
jitted blocked scan shaped as the program's (a ``scan.R`` scope holding
``partials``, a ``segment_sum``, and ``accumulate``), then host work under
the program span ``repro.ml.ridge.bgd`` and host work under no program
span, both with the device idle.  Written gzipped: ``trace.extract``'s
events, the program spans added, and ``ops``, the scan's instructions with
their opcodes and scopes (``scopes.hlo_ops``)."""

import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src"))

#: host seconds of each job under the program span, and under none
SPANNED_S, UNSPANNED_S = 0.03, 0.02


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    import repro.core  # noqa: F401  (the program's spans reach the profiler)
    from bench.lib import scopes, trace
    from repro.obs.trace import span

    n_seg, block = 4096, 1024

    def body(acc, xs):
        seg, x = xs
        with jax.named_scope("partials"):
            part = jax.ops.segment_sum(x, seg, num_segments=n_seg)
        with jax.named_scope("accumulate"):
            return acc + part, None

    @jax.jit
    def scan(seg, x):
        with jax.named_scope("scan.R"):
            acc, _ = jax.lax.scan(body, jnp.zeros((n_seg, 128), jnp.float32),
                                  (seg, x))
        return acc

    k1, k2 = jax.random.split(jax.random.key(0))
    seg = jax.random.randint(k1, (64, block), 0, n_seg)
    x = jax.random.normal(k2, (64, block, 128), jnp.float32)
    scan = scan.lower(seg, x).compile()
    scan(seg, x).block_until_ready()
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.job"):
                scan(seg, x).block_until_ready()
                with span("ml.ridge.bgd", p=3):
                    time.sleep(SPANNED_S)
                time.sleep(UNSPANNED_S)
    jax.profiler.stop_trace()
    events = trace.extract(tdir)
    path = max((os.path.join(d, f) for d, _, fs in os.walk(tdir)
                for f in fs if f.endswith(".xplane.pb")),
               key=os.path.getmtime)
    events = scopes.with_spans(events, scopes.program_spans(
        ProfileData.from_file(path)))
    events["ops"] = scopes.hlo_ops(scan.as_text())
    trace.save(events, out)


if __name__ == "__main__":
    main(sys.argv[1])
