#!/usr/bin/env python3
"""Time one scan block's accumulate on the device, dense against compact,
at the shape of one segmented view: the choice the ``xla`` backend makes
by ``lowering/xla.py:COMPACT_SEGMENTS_PER_ROW``.

    python3 tools/time_accumulate.py --view 1158960x435 --view 90936x337 \
        --out accumulate.json

Each case scans ``--blocks`` blocks of ``--block`` rows with the backend's
own block functions: ``dense`` forms the partial over every segment and
adds the whole accumulator; ``compact`` forms it over the block's distinct
segments and scatter-adds them.  Segment ids are drawn uniformly over the
view's segments (``uniform``, as the benchmark's fact rows are) or from a
run of ``--block`` // 16 neighbouring segments (``clustered``).  A case's
time is the fastest of ``--repeats`` timed scans over its blocks, after a
warm-up; both paths sum the same floats, which the script checks.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.ir import SegmentSpec  # noqa: E402
from repro.core.lowering import xla  # noqa: E402


def scan_fn(n_segments: int, compact: bool):
    """A jitted scan of blocks ``(seg, payload)`` into a donated
    ``(n_segments, width)`` accumulator through one path."""
    spec = SegmentSpec(("k",), (n_segments,), n_segments)
    vp = SimpleNamespace(seg=spec, batched=False)

    def body(acc, xs):
        seg, payload = xs
        cols = {"k": seg}
        if compact:
            ids, slot = xla._block_segments(cols, spec)
            contrib = (ids, xla._segment_sum(vp, payload, slot,
                                             seg.shape[0]))
        else:
            contrib = xla._partials(vp, payload, cols)
        contrib = jax.lax.optimization_barrier(contrib)
        return xla._accumulate(vp, acc, contrib, compact), None

    return jax.jit(lambda acc, seg, x: jax.lax.scan(body, acc, (seg, x))[0],
                   donate_argnums=0)


def inputs(n_segments: int, width: int, blocks: int, block: int,
           keys: str, seed: int):
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    if keys == "uniform":
        seg = jax.random.randint(k1, (blocks, block), 0, n_segments)
    else:
        run = max(block // 16, 1)
        start = jax.random.randint(k3, (blocks, 1), 0, n_segments - run)
        seg = start + jax.random.randint(k1, (blocks, block), 0, run)
    x = jax.random.normal(k2, (blocks, block, width), jnp.float32)
    return seg.astype(jnp.int32), x


def time_case(n_segments, width, blocks, block, keys, compact, repeats,
              seed):
    seg, x = inputs(n_segments, width, blocks, block, keys, seed)
    f = scan_fn(n_segments, compact)
    zeros = lambda: jnp.zeros((n_segments, width), jnp.float32)  # noqa: E731
    out = f(zeros(), seg, x)
    out.block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        acc = zeros()
        acc.block_until_ready()
        t0 = time.perf_counter()
        f(acc, seg, x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return [best, out]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--view", action="append", required=True,
                    help="<segments>x<width>, e.g. 1158960x435")
    ap.add_argument("--block", type=int, default=4096)
    ap.add_argument("--blocks", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    rows = []
    for view in args.view:
        n_segments, width = (int(v) for v in view.split("x"))
        for keys in ("uniform", "clustered"):
            res = {}
            for compact in (False, True):
                res[compact] = time_case(n_segments, width, args.blocks,
                                         args.block, keys, compact,
                                         args.repeats, args.seed)
            diff = float(jnp.abs(res[False][1] - res[True][1]).max())
            del res[False][1], res[True][1]
            row = {"segments": n_segments, "width": width, "keys": keys,
                   "block": args.block, "blocks": args.blocks,
                   "device": jax.devices()[0].device_kind,
                   "dense_ms_per_block": res[False][0] / args.blocks * 1e3,
                   "compact_ms_per_block": res[True][0] / args.blocks * 1e3,
                   "max_abs_diff": diff}
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
