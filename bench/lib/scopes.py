"""Where a traced window's time went, by the program's own names.

``bench/lib/trace.py`` gives device time by HLO instruction (``op_s``) and
the host's ``bench.*`` spans.  This module adds, from the same trace:

* device time by **program scope**: the ``jax.named_scope`` path in each
  instruction's ``op_name`` metadata (``scan.Sales/partials``), read from
  the optimized HLO text of the program the window ran, once per
  instruction.  ``while`` and ``conditional`` instructions, whose events
  contain their body's, are left out, so scopes never sum above busy time;
* the program's host spans (``repro.*``, ``repro.obs.trace``) and what they
  explain of the window: the host time of the application layer, and the
  device-idle time no program span covers.
"""

from __future__ import annotations

import collections
import inspect
import re
from typing import Dict, Iterable, List, Optional, Tuple

from bench.lib.trace import Interval, Reduced, overlap, union

#: what the program's spans are named with in the trace
PROGRAM = "repro."
#: the application layer's spans (``ml/covar.py``, ``ml/ridge.py``)
APP_SPANS = ("repro.ml.covar.assemble", "repro.ml.ridge.bgd")
#: op-name components JAX adds itself (control flow, inlined calls)
JAX_PARTS = frozenset({"while", "body", "cond", "closed_call"})
#: instructions whose device events contain other instructions' events
CONTAINERS = frozenset({"while", "conditional"})

_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s")


def scope_of(op_name: str) -> str:
    """The program's scopes in an op name: ``jit(run)/scan.Sales/while/body/
    partials/scatter-add`` is ``scan.Sales/partials``."""
    return "/".join(p for p in op_name.split("/")[:-1]
                    if "(" not in p and p not in JAX_PARTS)


def hlo_ops(text: str) -> Dict[str, Tuple[str, str]]:
    """``{instruction: (opcode, scope)}`` for every instruction of an HLO
    module's text.  An instruction without an op name (a fusion the
    compiler made) takes the op name of its called computation's root."""
    rows, root_name, comp = [], {}, None
    for line in text.splitlines():
        if line[:1] not in (" ", "\t", ""):
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m and line.rstrip().endswith("{") else None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        calls = _CALLS.search(line)
        rows.append((m.group(1), m.group(2), op and op.group(1),
                     calls and calls.group(1)))
        if comp and line.lstrip().startswith("ROOT") and op:
            root_name[comp] = op.group(1)
    out = {}
    for name, opcode, op, calls in rows:
        if op is None and calls:
            op = root_name.get(calls)
        out[name] = (opcode, scope_of(op) if op else "")
    return out


def by_scope(op_s: Dict[str, float],
             ops: Dict[str, Tuple[str, str]]) -> Dict[str, float]:
    """Device seconds by program scope over the leaf instructions; time of
    an instruction the HLO does not hold falls under ``""``."""
    out = collections.Counter()
    for name, secs in op_s.items():
        opcode, scope = ops.get(name, ("", ""))
        if opcode not in CONTAINERS:
            out[scope] += secs
    return dict(out)


def scope_s(r: Reduced, ops: Dict[str, Tuple[str, str]]
            ) -> Dict[str, Dict[str, float]]:
    """Per device: device seconds by program scope inside the window."""
    return {d: by_scope(v["op_s"], ops) for d, v in r.devices.items()}


def share_under(r: Reduced, ops: Dict[str, Tuple[str, str]],
                scope: str) -> Optional[float]:
    """Percent of device busy time in the window spent in leaf ops under a
    scope named ``scope``, averaged over devices; ``None`` when no
    instruction of the program carries that scope."""
    if not any(scope in s.split("/") for _, s in ops.values()):
        return None
    busy = r.busy_s()
    if busy <= 0:
        return None
    per = scope_s(r, ops)
    under = sum(t for v in per.values() for s, t in v.items()
                if scope in s.split("/")) / len(per)
    return 100.0 * under / busy


def compiled_text(job) -> Optional[str]:
    """The optimized HLO text of the compiled program a window's job
    dispatches: the ``batch`` its closure holds (``bench/run.py``)."""
    batch = inspect.getclosurevars(job).nonlocals.get("batch")
    return batch.as_text() if hasattr(batch, "as_text") else None


# ---------------------------------------------------------- program spans


def app_host_s(r: Reduced, per: str = "bench.job") -> Optional[float]:
    """Seconds under the application layer's spans inside the window, over
    the number of ``per`` spans; ``None`` without such spans."""
    ivs = r._clip(union([(s, e) for n, s, e in r.spans if n in APP_SPANS]))
    n = r.n_spans(per)
    if not ivs or not n:
        return None
    return sum(e - s for s, e in ivs) / n


def idle(r: Reduced, busy: List[Interval]) -> List[Interval]:
    """The window's intervals outside ``busy`` (disjoint, sorted)."""
    lo, hi = r.window
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def idle_unspanned(r: Reduced) -> Optional[float]:
    """Percent of the window in which the device is idle and no program
    span is open on the host, averaged over devices; ``None`` when the
    trace holds no program span."""
    spans = r._clip(union([(s, e) for n, s, e in r.spans
                           if n.startswith(PROGRAM)]))
    if not spans or not r.busy:
        return None
    tot = 0.0
    for ivs in r.busy.values():
        gaps = idle(r, ivs)
        tot += sum(e - s for s, e in gaps) - overlap(gaps, spans)
    return 100.0 * tot / len(r.busy) / r.window_s


def program_spans(pd, prefix: str = PROGRAM) -> List[list]:
    """``[[name, start_s, end_s], ...]`` of the host events named with
    ``prefix`` in a ``jax.profiler.ProfileData``."""
    return [[e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9]
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix)]


def events_in(pd, lo: float, hi: float, plane: str = "/host:",
              skip_line: Optional[str] = None, top: int = 30
              ) -> List[Tuple[str, str, float, int]]:
    """The events inside ``[lo, hi]`` on every line of the planes whose
    names start with ``plane``, but lines whose names start with
    ``skip_line``: ``(line, name, seconds inside, count)`` by name, the
    longest first."""
    secs: Dict[Tuple[str, str], float] = collections.Counter()
    count: Dict[Tuple[str, str], int] = collections.Counter()
    for p in pd.planes:
        if not p.name.startswith(plane):
            continue
        for line in p.lines:
            if skip_line and line.name.startswith(skip_line):
                continue
            for e in line.events:
                s0 = max(e.start_ns * 1e-9, lo)
                s1 = min((e.start_ns + e.duration_ns) * 1e-9, hi)
                if s1 > s0:
                    secs[line.name, e.name] += s1 - s0
                    count[line.name, e.name] += 1
    return [(k[0], k[1], v, count[k])
            for k, v in sorted(secs.items(), key=lambda kv: -kv[1])[:top]]


def with_spans(events: dict, spans: Iterable[list]) -> dict:
    """``events`` (``trace.extract``) with more host spans added."""
    return dict(events, spans=sorted(list(events["spans"]) + list(spans),
                                     key=lambda s: s[1]))
