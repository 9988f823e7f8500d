"""From a profiler trace to device busy time, time inside the benchmark's
host spans and the breakdown of a traced run.

Two steps, kept apart so the second can be checked on a recorded trace:

* :func:`extract` reads the newest ``.xplane.pb`` under a trace directory
  into plain data: per device, the intervals of its programs (the ``XLA
  Modules`` line of each ``/device:`` plane) and its operations' time by
  name (``XLA Ops``), and the host's ``bench.*`` spans
  (``jax.profiler.TraceAnnotation``).  The profiler writes host and device
  events on one clock.
* :class:`Reduced` computes every number from those lists.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import math
import os
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]           # (start_s, end_s)

def extract(logdir: str, window_span: str = "bench.window") -> dict:
    """The events of the newest trace under ``logdir``, as plain data:
    ``{"spans": [[name, start_s, end_s], ...], "devices": {device:
    {"modules": [[name, start_s, end_s], ...], "op_s": {op: seconds}}}}``.
    Programs are kept whole; operations (one event per op per loop
    iteration, millions of them) are summed by name inside the window as
    they are read."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9]
                          for e in line.events if e.name.startswith("bench.")]
    win = [s for s in spans if s[0] == window_span]
    lo, hi = (win[0][1], win[0][2]) if win else (-math.inf, math.inf)
    devices = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        dev = {"modules": [], "op_s": collections.Counter()}
        for line in plane.lines:
            if line.name == "XLA Modules":
                dev["modules"] = [[e.name, e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9]
                                  for e in line.events]
            elif line.name == "XLA Ops":
                op_s = dev["op_s"]
                for e in line.events:
                    s0 = e.start_ns * 1e-9
                    s1 = s0 + e.duration_ns * 1e-9
                    s0, s1 = max(s0, lo), min(s1, hi)
                    if s1 <= s0:
                        continue
                    # an op's event is named by its HLO text: keep the name
                    name = e.name.split(" = ")[0].lstrip("%")
                    op_s[name] += s1 - s0
        if dev["modules"] or dev["op_s"]:
            dev["op_s"] = dict(dev["op_s"])
            devices[plane.name] = dev
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def save(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Total length of the intersection of two disjoint sorted lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


class Reduced:
    """Every number the benchmark reads from one traced window."""

    def __init__(self, events: dict, window_span: str = "bench.window"):
        self.spans = [tuple(s) for s in events["spans"]]
        win = [s for s in self.spans if s[0] == window_span]
        if not win:
            raise ValueError(f"trace has no {window_span!r} span")
        self.window: Interval = (win[0][1], win[0][2])
        self.devices = events["devices"]
        self.busy = {d: self._clip(union(
            [(s, e) for _, s, e in v["modules"]]))
            for d, v in self.devices.items()}

    def _clip(self, ivs: List[Interval]) -> List[Interval]:
        lo, hi = self.window
        return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Seconds in which a program ran, averaged over the devices."""
        if not self.busy:
            return 0.0
        return sum(e - s for ivs in self.busy.values()
                   for s, e in ivs) / len(self.busy)

    def span_intervals(self, name: str) -> List[Interval]:
        return union([(s, e) for n, s, e in self.spans if n == name])

    def n_spans(self, name: str) -> int:
        return sum(1 for n, _, _ in self.spans if n == name)

    def busy_in(self, name: str) -> Dict[str, float]:
        """Per device: busy seconds inside the spans called ``name``."""
        spans = self.span_intervals(name)
        return {d: overlap(ivs, spans) for d, ivs in self.busy.items()}

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (summed by name over
        devices, divided by their number), and the longest idle gaps of the
        busiest device, each named by the innermost host span around it."""
        tot = collections.Counter()
        for v in self.devices.values():
            tot.update(v["op_s"])
        nd = max(len(self.devices), 1)
        ops = [[n, t / nd] for n, t in tot.most_common(top)]
        gaps = []
        if self.busy:
            dev = max(self.busy, key=lambda d: sum(e - s for s, e in
                                                   self.busy[d]))
            edges = [self.window[0]] + [x for iv in self.busy[dev]
                                        for x in iv] + [self.window[1]]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append([self._host_at((s + e) / 2), e - s])
            gaps.sort(key=lambda g: -g[1])
        return {"device_ops": ops, "idle_gaps": gaps[:top]}

    def _host_at(self, t: float) -> str:
        inner: Optional[tuple] = None
        for n, s, e in self.spans:
            if s <= t < e and (inner is None or e - s < inner[2] - inner[1]):
                inner = (n, s, e)
        return inner[0] if inner else "no span"
