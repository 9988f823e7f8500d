#!/usr/bin/env python3
"""Time one scan block's accumulate on the device: dense against compact,
at the shape of one segmented view (the choice the ``xla`` backend makes by
``lowering/xla.py:COMPACT_SEGMENTS_PER_ROW``), one compact accumulator
shared by several views on one key against one per view, a sorted step's
window against compact, and the sort of a relation's rows by its key.

    python3 tools/time_accumulate.py --view 1158960x435 --view 90936x337 \
        --group 90936x337,2,2,33 --window 90936x374x1380 \
        --sort-rows 125497040 --out accumulate.json

Each case but ``--sort-rows`` scans ``--blocks`` blocks of ``--block`` rows
with the backend's own block functions.  ``--view``: ``dense`` forms the
partial over every segment and adds the whole accumulator; ``compact``
forms it over the block's distinct segments and scatter-adds them.
``--group``: on the compact path, ``grouped`` forms one partial of the
views' summed width and scatters it once; ``views`` forms and scatters one
per view, the block's distinct segments shared.  Segment ids are drawn
uniformly over the key's segments (``uniform``, as the benchmark's fact
rows are) or from a run of ``--block`` // 16 neighbouring segments
(``clustered``).  ``--window <segments>x<width>x<rows a segment>``: blocks
of a relation sorted by the key, each a sorted run of ``--block`` // rows
neighbouring segments; ``window`` adds each block into one window of the
accumulator (``_window_accumulate``), ``compact`` takes the compact path.
A case's time is the fastest of ``--repeats`` timed scans over its blocks,
after a warm-up; both sides sum the same floats, which the script checks.

``--sort-rows N`` times the reordering of ``N`` rows of ``--sort-cols``
columns by a key over ``--sort-segments`` segments on the device: ``sort``
carries the columns through one ``lax.sort`` (what ``lowering/xla.py``'s
``_cluster`` does), ``argsort`` sorts the key with the row index and takes
each column by it; the fastest of ``--repeats`` after a warm-up.
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


def _key(n_segments: int):
    spec = SegmentSpec(("k",), (n_segments,), n_segments)
    return spec, SimpleNamespace(seg=spec, batched=False)


def scan_fn(n_segments: int, compact: bool, window: bool = False):
    """A jitted scan of blocks ``(seg, payload)`` into a donated
    ``(n_segments, width)`` accumulator through one path; with ``window``,
    into a ``(n_segments + block, width)`` one through windows."""
    _, g = _key(n_segments)

    def body(acc, xs):
        seg, payload = xs
        if window:
            valid = jnp.ones(seg.shape, jnp.float32)
            return xla._window_accumulate(g, acc, payload, seg,
                                          valid), None
        if compact:
            ids, slot = xla._block_segments(seg, n_segments)
            contrib = (ids, xla._segment_sum(g, payload, slot,
                                             seg.shape[0]))
        else:
            contrib = xla._partials(g, payload, {"k": seg})
        contrib = jax.lax.optimization_barrier(contrib)
        return xla._accumulate(g, acc, contrib, compact), None

    return jax.jit(lambda acc, seg, x: jax.lax.scan(body, acc, (seg, x))[0],
                   donate_argnums=0)


def group_scan_fn(n_segments: int, widths, grouped: bool):
    """A jitted scan of blocks ``(seg, payload)``, the payload the views'
    columns side by side, into donated compact accumulators on one key:
    one ``(n_segments, sum(widths))`` when ``grouped``, else one
    ``(n_segments, width)`` per view."""
    _, g = _key(n_segments)
    cuts = [sum(widths[:i]) for i in range(len(widths) + 1)]

    def body(accs, xs):
        seg, payload = xs
        ids, slot = xla._block_segments(seg, n_segments)
        parts = ([payload] if grouped else
                 [payload[:, a:b] for a, b in zip(cuts, cuts[1:])])
        contribs = jax.lax.optimization_barrier(tuple(
            (ids, xla._segment_sum(g, p, slot, seg.shape[0]))
            for p in parts))
        return tuple(xla._accumulate(g, a, c, True)
                     for a, c in zip(accs, contribs)), None

    return jax.jit(lambda accs, seg, x: jax.lax.scan(body, accs,
                                                     (seg, x))[0],
                   donate_argnums=0)


def inputs(n_segments: int, width: int, blocks: int, block: int,
           keys, seed: int):
    """Blocks of segment ids and payloads; ``keys`` is ``"uniform"``,
    ``"clustered"``, or the rows a segment of a sorted relation."""
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    if keys == "uniform":
        seg = jax.random.randint(k1, (blocks, block), 0, n_segments)
    else:
        run = max(block // (16 if keys == "clustered" else keys), 1)
        start = jax.random.randint(k3, (blocks, 1), 0, n_segments - run)
        seg = start + jax.random.randint(k1, (blocks, block), 0, run)
        if keys != "clustered":
            seg = jnp.sort(seg, axis=1)
    x = jax.random.normal(k2, (blocks, block, width), jnp.float32)
    return seg.astype(jnp.int32), x


def time_scan(f, zeros, seg, x, repeats):
    """The fastest of ``repeats`` scans of ``f`` from fresh accumulators,
    after a warm-up; returns it with the warm-up's sums."""
    out = f(zeros(), seg, x)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        acc = zeros()
        jax.block_until_ready(acc)
        t0 = time.perf_counter()
        jax.block_until_ready(f(acc, seg, x))
        best = min(best, time.perf_counter() - t0)
    return [best, out]


def time_case(n_segments, width, blocks, block, keys, compact, repeats,
              seed):
    seg, x = inputs(n_segments, width, blocks, block, keys, seed)
    return time_scan(scan_fn(n_segments, compact),
                     lambda: jnp.zeros((n_segments, width), jnp.float32),
                     seg, x, repeats)


def time_window(n_segments, width, rows_per_segment, blocks, block,
                window, repeats, seed):
    seg, x = inputs(n_segments, width, blocks, block, rows_per_segment,
                    seed)
    rows = n_segments + block if window else n_segments
    best, out = time_scan(scan_fn(n_segments, True, window),
                          lambda: jnp.zeros((rows, width), jnp.float32),
                          seg, x, repeats)
    return [best, out[:n_segments]]


def sort_fn(carry: bool):
    """A jitted reorder of ``cols`` by ``key``: one sort carrying them, or
    the key sorted with the row index and each column taken by it."""
    if carry:
        return jax.jit(lambda key, cols: jax.lax.sort(
            (key,) + tuple(cols), num_keys=1, is_stable=False))

    def argsort(key, cols):
        key, perm = jax.lax.sort((key, jnp.arange(key.shape[0],
                                                  dtype=jnp.int32)),
                                 num_keys=1, is_stable=False)
        return (key,) + tuple(jnp.take(c, perm) for c in cols)

    return jax.jit(argsort)


def time_sort(n_rows, n_cols, n_segments, repeats, seed):
    """``{side: (seconds, (key sorted, column sums))}`` of reordering
    ``n_rows`` rows of ``n_cols`` columns, int32 and float32 by turns, by a
    key."""
    ks = jax.random.split(jax.random.key(seed), n_cols + 1)
    key = jax.random.randint(ks[0], (n_rows,), 0, n_segments)
    cols = tuple(jax.random.randint(k, (n_rows,), 0, 1 << 20) if i % 2
                 else jax.random.normal(k, (n_rows,), jnp.float32)
                 for i, k in enumerate(ks[1:]))
    out = {}
    for side, carry in (("sort", True), ("argsort", False)):
        f = sort_fn(carry)
        res = jax.block_until_ready(f(key, cols))
        check = (bool(jnp.all(res[0][1:] >= res[0][:-1])),
                 [float(jnp.sum(c.astype(jnp.float32))) for c in res[1:]])
        del res
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(f(key, cols))
            best = min(best, time.perf_counter() - t0)
        out[side] = (best, check)
    return out


def time_group(n_segments, widths, blocks, block, keys, grouped, repeats,
               seed):
    seg, x = inputs(n_segments, sum(widths), blocks, block, keys, seed)
    shapes = [sum(widths)] if grouped else widths
    best, out = time_scan(
        group_scan_fn(n_segments, widths, grouped),
        lambda: tuple(jnp.zeros((n_segments, w), jnp.float32)
                      for w in shapes), seg, x, repeats)
    return [best, jnp.concatenate(out, axis=-1)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--view", action="append", default=[],
                    help="<segments>x<width>, e.g. 1158960x435")
    ap.add_argument("--group", action="append", default=[],
                    help="<segments>x<width>,<width>,..., one width a "
                         "view, e.g. 90936x337,2,2,33")
    ap.add_argument("--window", action="append", default=[],
                    help="<segments>x<width>x<rows a segment>, e.g. "
                         "90936x374x1380")
    ap.add_argument("--sort-rows", type=int, action="append", default=[])
    ap.add_argument("--sort-cols", type=int, default=5)
    ap.add_argument("--sort-segments", type=int, default=90936)
    ap.add_argument("--block", type=int, default=4096)
    ap.add_argument("--blocks", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    device = jax.devices()[0].device_kind
    rows = []

    def add(row, res, sides):
        diff = float(jnp.abs(res[0][1] - res[1][1]).max())
        row.update({"block": args.block, "blocks": args.blocks,
                    "device": device, "max_abs_diff": diff})
        for side, (t, _) in zip(sides, res):
            row[f"{side}_ms_per_block"] = t / args.blocks * 1e3
        rows.append(row)
        print(json.dumps(row), flush=True)

    for view in args.view:
        n_segments, width = (int(v) for v in view.split("x"))
        for keys in ("uniform", "clustered"):
            add({"segments": n_segments, "width": width, "keys": keys},
                [time_case(n_segments, width, args.blocks, args.block, keys,
                           compact, args.repeats, args.seed)
                 for compact in (False, True)], ("dense", "compact"))
    for group in args.group:
        n_segments, widths = group.split("x")
        n_segments = int(n_segments)
        widths = [int(w) for w in widths.split(",")]
        for keys in ("uniform", "clustered"):
            add({"segments": n_segments, "widths": widths, "keys": keys},
                [time_group(n_segments, widths, args.blocks, args.block,
                            keys, grouped, args.repeats, args.seed)
                 for grouped in (False, True)], ("views", "grouped"))
    for window in args.window:
        n_segments, width, per = (int(v) for v in window.split("x"))
        add({"segments": n_segments, "width": width,
             "rows_per_segment": per},
            [time_window(n_segments, width, per, args.blocks, args.block,
                         w, args.repeats, args.seed)
             for w in (False, True)], ("compact", "window"))
    for n_rows in args.sort_rows:
        res = time_sort(n_rows, args.sort_cols, args.sort_segments,
                        args.repeats, args.seed)
        row = {"sort_rows": n_rows, "cols": args.sort_cols,
               "segments": args.sort_segments, "device": device,
               "key_sorted": res["sort"][1][0] and res["argsort"][1][0],
               "max_col_sum_diff": max(
                   abs(a - b) / max(abs(a), 1.0) for a, b in
                   zip(res["sort"][1][1], res["argsort"][1][1]))}
        for side, (t, _) in res.items():
            row[f"{side}_s"] = t
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
