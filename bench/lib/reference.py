"""The plain reference: aggregates over a star or snowflake join in numpy
float64, in time proportional to the fact's rows.

It shares no code with the engine.  Every dimension is joined by a dense
lookup on its join key, so an attribute of a dimension is a function of a
few of the fact's key columns (its *key set*: ``txns`` of ``(date, store)``,
``population`` of ``locn`` through ``Location.zip``).  An aggregate

    sum over fact rows of  onehot(g_1) x ... x onehot(g_k) x f_1 x f_2

is then a sum over the cells of the union of its factors' key sets: the
fact's rows are counted (or their own measures summed) per cell with
``np.bincount``, and the cells are weighted by the dimension values.  Where
that union has more than ``LIMIT`` cells, the factor with the widest key set
is read per row instead, so its own code (or value) takes the place of its
key set.  Requests that need the same cells share one pass over the rows.
Sums are linear in the rows, so the rows are taken in blocks.
"""

from __future__ import annotations

import itertools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

#: most cells one grouped table may have (float64: 128 MiB per weight)
LIMIT = 1 << 24

#: fact rows per block: each per-row value of a block is 128 MiB (float64)
BLOCK = 1 << 24

#: one aggregate: (group-by attrs, measure attrs (0 to 2), over |values|)
Request = Tuple[Tuple[str, ...], Tuple[str, ...], bool]


def _radix(codes: Sequence[np.ndarray], doms: Sequence[int]) -> np.ndarray:
    idx = np.zeros(np.shape(codes[0]), np.int64)
    for c, d in zip(codes, doms):
        idx = idx * d + c
    return idx


def bf16_round(x: np.ndarray) -> np.ndarray:
    """Round float values to bfloat16 (the control's inputs)."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


class Reference:
    """Aggregates of one configuration's join.  ``dims`` are the dimension
    relations as host numpy columns; ``rounding`` is applied to every
    continuous value before it is used (``None``: exact)."""

    def __init__(self, cfg, dims: Dict[str, Dict[str, np.ndarray]],
                 rounding: Optional[Callable] = None):
        self.cfg = cfg
        self.round = rounding or (lambda x: np.asarray(x, np.float64))
        self.fact_attrs = list(cfg.attrs[cfg.fact])
        self.dims = dims
        self.join = {r: [a for a in cfg.attrs[r] if a in cfg.attrs[p]]
                     for r, p in cfg.parent.items()}
        self.pos = {r: self._positions(r) for r in self.join}
        self._values: Dict[Tuple[str, bool], np.ndarray] = {}

    # -- the join ------------------------------------------------------------

    def _positions(self, rel: str) -> np.ndarray:
        """Row of ``rel`` for each value of its join key; every key value
        must occur exactly once (a complete dimension), so every fact row
        joins once with every relation."""
        key = self.join[rel]
        doms = [self.cfg.domains[a] for a in key]
        tab = self.dims[rel]
        at = _radix([np.asarray(tab[a], np.int64) for a in key], doms)
        pos = np.full(int(np.prod(doms)), -1, np.int64)
        pos[at] = np.arange(len(at))
        if len(np.unique(at)) != len(at) or (pos < 0).any():
            raise ValueError(f"{rel}: join key {key} is not a complete, "
                             "unique key")
        return pos

    def keyset(self, attr: str) -> Tuple[str, ...]:
        """The fact columns that determine ``attr``, in the fact's order."""
        if attr in self.fact_attrs:
            return (attr,)
        rel = self.cfg.home(attr)
        ks = set()
        for j in self.join[rel]:
            ks |= set(self.keyset(j))
        return tuple(a for a in self.fact_attrs if a in ks)

    def _resolve(self, attr: str, codes: Dict[str, np.ndarray]) -> np.ndarray:
        if attr in codes:
            return codes[attr]
        rel = self.cfg.home(attr)
        key = self.join[rel]
        p = self.pos[rel][_radix([self._resolve(j, codes) for j in key],
                                 [self.cfg.domains[j] for j in key])]
        return np.asarray(self.dims[rel][attr])[p]

    def _dom(self, attrs) -> int:
        return int(np.prod([self.cfg.domains[a] for a in attrs]))

    def values(self, attr: str, absolute: bool = False) -> np.ndarray:
        """``attr`` over every cell of its key set (mixed radix, the key
        set's order): codes for a discrete attribute, float64 values (after
        the rounding) for a continuous one."""
        k = (attr, absolute)
        if k not in self._values:
            ks = self.keyset(attr)
            cells = np.unravel_index(np.arange(self._dom(ks)),
                                     [self.cfg.domains[a] for a in ks])
            v = self._resolve(attr, dict(zip(ks, cells)))
            if self.cfg.kinds[attr] == "continuous":
                v = self.round(v)
                v = np.abs(v) if absolute else v
            else:
                v = np.asarray(v, np.int64)
            self._values[k] = v
        return self._values[k]

    # -- planning ------------------------------------------------------------

    def _plan(self, req: Request):
        """Which factors are read per row, and the table's axes and
        weights.  Axes are fact columns or ``@attr`` (a dimension category
        read per row); weights are row-level values ``(attr, absolute)``."""
        group, measures, absolute = req
        factors = list(group) + list(measures)
        per_row = []

        def axes_of():
            ax = set()
            for x in factors:
                if x in per_row:
                    if self.cfg.kinds[x] != "continuous":
                        ax.add("@" + x)
                elif x not in self.fact_attrs or \
                        self.cfg.kinds[x] != "continuous":
                    ax |= set(self.keyset(x))
            return ax

        while True:
            ax = axes_of()
            if self._axes_dom(ax) <= LIMIT:
                break
            wide = [x for x in factors if x not in per_row
                    and x not in self.fact_attrs]
            if not wide:
                break
            per_row.append(max(wide, key=lambda x: self._dom(self.keyset(x))))
        weights = tuple(sorted(
            (x, absolute and (x not in measures[:i] + measures[i + 1:]))
            for i, x in enumerate(measures)
            if x in per_row or (x in self.fact_attrs
                                and self.cfg.kinds[x] == "continuous")))
        return self._order(ax), weights, tuple(per_row)

    def _axes_dom(self, axes) -> int:
        return int(np.prod([self.cfg.domains[a.lstrip("@")] for a in axes]))

    def _order(self, axes) -> Tuple[str, ...]:
        fact = [a for a in self.fact_attrs if a in axes]
        return tuple(fact) + tuple(sorted(a for a in axes if a[0] == "@"))

    # -- evaluation ----------------------------------------------------------

    def moments(self, requests: Sequence[Request],
                rows: Dict[str, np.ndarray]) -> Dict[Request, np.ndarray]:
        """Each request's dense result over the fact's rows ``rows``."""
        t = Tables(self, requests)
        n = len(next(iter(rows.values())))
        for i in range(0, n, BLOCK):
            t.add({a: c[i:i + BLOCK] for a, c in rows.items()})
        return t.evaluate()

    def _merge(self, axes_sets) -> List[Tuple[str, ...]]:
        """Greedy: fold each axis set into the table whose union with it
        stays smallest under ``LIMIT``, so requests share passes."""
        tables: List[Tuple[str, ...]] = []
        for ax in axes_sets:
            if any(set(ax) <= set(t) for t in tables):
                continue
            fits = [t for t in tables
                    if self._axes_dom(set(t) | set(ax)) <= LIMIT]
            if fits:
                t = min(fits, key=lambda t: self._axes_dom(set(t) | set(ax)))
                tables[tables.index(t)] = self._order(set(t) | set(ax))
            else:
                tables.append(ax)
        return tables

    @staticmethod
    def _home_table(tables, axes):
        return next(t for t in tables if set(axes) <= set(t))

    def _evaluate(self, req: Request, plan, total: np.ndarray, table_axes,
                  memo: dict):
        """One request from its table; ``memo`` keeps what requests of one
        evaluation share (a table summed down to some axes, cell codes)."""
        group, measures, absolute = req
        axes, weights, per_row = plan
        k = (table_axes, weights, axes)
        if k not in memo:
            drop = tuple(i for i, a in enumerate(table_axes)
                         if a not in axes)
            memo[k] = (total.sum(axis=drop) if drop else total).reshape(-1)
        t = memo[k]
        if axes not in memo:
            memo[axes] = dict(zip(axes, np.unravel_index(
                np.arange(t.size), [self.cfg.domains[a.lstrip("@")]
                                    for a in axes]))) if axes else {}
        codes = memo[axes]

        def at_cells(x, ab=False):
            if x in per_row:
                return codes["@" + x]
            if x in self.fact_attrs:
                return codes[x]
            if (axes, x, ab) not in memo:
                ks = self.keyset(x)
                memo[axes, x, ab] = self.values(x, ab)[_radix(
                    [codes[a] for a in ks],
                    [self.cfg.domains[a] for a in ks])]
            return memo[axes, x, ab]

        w = t
        weighted = {a for a, _ in weights}
        for i, x in enumerate(measures):
            if x not in weighted:
                ab = absolute and x not in measures[:i] + measures[i + 1:]
                w = w * at_cells(x, ab)
        if not group:
            return np.asarray(w.sum())
        if (axes, group) not in memo:
            memo[axes, group] = _radix([at_cells(x) for x in group],
                                       [self.cfg.domains[x] for x in group])
        return np.bincount(memo[axes, group], w, self._dom(group)).reshape(
            [self.cfg.domains[x] for x in group])



class Tables:
    """Grouped sums of the fact's rows for a fixed set of requests, summed
    over blocks of rows."""

    def __init__(self, ref: Reference, requests: Sequence[Request]):
        self.ref = ref
        self.requests = list(dict.fromkeys(requests))
        self.plans = {r: ref._plan(r) for r in self.requests}
        self.axes = ref._merge(sorted({p[0] for p in self.plans.values()},
                                      key=ref._axes_dom, reverse=True))
        self.home = {r: ref._home_table(self.axes, p[0])
                     for r, p in self.plans.items()}
        self.need: Dict[Tuple[str, ...], set] = {}
        for r, (_, weights, _) in self.plans.items():
            self.need.setdefault(self.home[r], set()).add(weights)
        self.totals: Dict[tuple, np.ndarray] = {}

    def add(self, rows: Dict[str, np.ndarray]) -> None:
        ref = self.ref
        cache = _RowCache(ref, rows)
        if cache.n == 0:
            return
        for t, wset in self.need.items():
            doms = [ref.cfg.domains[a.lstrip("@")] for a in t]
            key = (_radix([cache.code(a) for a in t], doms) if t
                   else np.zeros(cache.n, np.int64))
            for w in wset:
                weight = None
                for attr, ab in w:
                    v = cache.value(attr, ab)
                    weight = v if weight is None else weight * v
                s = np.bincount(key, weight, ref._axes_dom(t)).reshape(
                    doms).astype(np.float64, copy=False)
                if (t, w) in self.totals:
                    self.totals[t, w] += s
                else:
                    self.totals[t, w] = s

    def evaluate(self) -> Dict[Request, np.ndarray]:
        out, memo = {}, {}
        for r, plan in self.plans.items():
            t = self.home[r]
            total = self.totals.get((t, plan[1]))
            if total is None:
                total = np.zeros([self.ref.cfg.domains[a.lstrip("@")]
                                  for a in t])
            out[r] = self.ref._evaluate(r, plan, total, t, memo)
        return out

class _RowCache:
    """Per-row codes and values of one row set, each computed once."""

    def __init__(self, ref: Reference, rows: Dict[str, np.ndarray]):
        self.ref, self.rows = ref, rows
        self.n = len(next(iter(rows.values())))
        self._codes, self._vals = {}, {}

    def code(self, axis: str) -> np.ndarray:
        if axis not in self._codes:
            if axis[0] == "@":
                self._codes[axis] = self._dim(axis[1:], False)
            else:
                self._codes[axis] = np.asarray(self.rows[axis], np.int64)
        return self._codes[axis]

    def _dim(self, attr: str, absolute: bool) -> np.ndarray:
        ref = self.ref
        ks = ref.keyset(attr)
        return ref.values(attr, absolute)[_radix(
            [self.code(a) for a in ks], [ref.cfg.domains[a] for a in ks])]

    def value(self, attr: str, absolute: bool) -> np.ndarray:
        k = (attr, absolute)
        if k not in self._vals:
            if attr in self.rows:
                v = self.ref.round(self.rows[attr])
                v = np.abs(v) if absolute else v
            else:
                v = self._dim(attr, absolute)
            self._vals[k] = v
        return self._vals[k]


# ---------------------------------------------------------------- covar


def covar_requests(cfg) -> List[Request]:
    """Every aggregate of the non-centred covar matrix over [1, continuous
    features, one-hot categorical blocks, label], with and without |.|."""
    xs = list(cfg.features_cont) + [cfg.label]
    reqs = []
    for ab in (False, True):
        reqs.append(((), (), ab))
        for i, f in enumerate(xs):
            reqs.append(((), (f,), ab))
            for g in xs[i:]:
                reqs.append(((), (f, g), ab))
        for c in cfg.features_cat:
            reqs += [((c,), (), ab)] + [((c,), (f,), ab) for f in xs]
    for c1, c2 in itertools.combinations(cfg.features_cat, 2):
        reqs.append(((c1, c2), (), False))
    return reqs


def covar_matrix(cfg, m: Dict[Request, np.ndarray]):
    """The (p, p) covar matrix and the same sums over |terms|, from the
    moments of :func:`covar_requests`."""
    cont, cat = list(cfg.features_cont), list(cfg.features_cat)
    xs = cont + [cfg.label]
    dom = {c: cfg.domains[c] for c in cat}
    p = 1 + len(cont) + sum(dom.values()) + 1
    xidx = list(range(1, 1 + len(cont))) + [p - 1]
    off, o = {}, 1 + len(cont)
    for c in cat:
        off[c] = slice(o, o + dom[c])
        o += dom[c]
    out = []
    for ab in (False, True):
        C = np.zeros((p, p))
        C[0, 0] = m[(), (), ab]
        for i, f in enumerate(xs):
            C[0, xidx[i]] = C[xidx[i], 0] = m[(), (f,), ab]
            for j in range(i, len(xs)):
                C[xidx[i], xidx[j]] = C[xidx[j], xidx[i]] = \
                    m[(), (f, xs[j]), ab]
        for c in cat:
            n = m[(c,), (), ab]
            C[off[c], 0] = C[0, off[c]] = n
            C[off[c], off[c]] = np.diag(n)
            for i, f in enumerate(xs):
                C[off[c], xidx[i]] = C[xidx[i], off[c]] = m[(c,), (f,), ab]
        for c1, c2 in itertools.combinations(cat, 2):
            b = m[(c1, c2), (), False]
            C[off[c1], off[c2]] = b
            C[off[c2], off[c1]] = b.T
        out.append(C)
    return out[0], out[1]


def ridge_closed_form(C: np.ndarray, lam: float) -> np.ndarray:
    """Ridge over the covar matrix: (Cff/N + lam I) theta = Cfl/N, with the
    label last and N = C[0, 0]."""
    n = C[0, 0]
    A = C[:-1, :-1] / n + lam * np.eye(C.shape[0] - 1)
    return np.linalg.solve(A, C[:-1, -1] / n)


def ridge_bgd(C: np.ndarray, lam: float, tol: float,
              max_iters: int) -> np.ndarray:
    """The same ridge model by the solver the job names, in float64, for
    the control: batch gradient descent from zero with a Jacobi
    preconditioner, Barzilai-Borwein steps and Armijo backtracking, until
    the gradient's norm is under ``tol`` times max(1, |theta|) in the
    preconditioned space, or ``max_iters`` steps."""
    n = C[0, 0]
    A, b, c = C[:-1, :-1], C[:-1, -1], C[-1, -1]
    d = 1.0 / np.sqrt(np.maximum(np.diag(A) / n + lam, 1e-12))
    A, b, d2 = A * np.outer(d, d), b * d, d * d

    def cost(x):
        return (x @ A @ x - 2 * x @ b + c) / (2 * n) + 0.5 * lam * (x * x) @ d2

    def grad(x):
        return (A @ x - b) / n + lam * d2 * x

    x = np.zeros(len(b))
    g = grad(x)
    x_prev, g_prev, step = x, g, 1e-6
    for i in range(max_iters):
        if np.linalg.norm(g) <= tol * max(1.0, np.linalg.norm(x)):
            break
        if i > 0:
            dx, dg = x - x_prev, g - g_prev
            den = dx @ dg
            if abs(den) > 1e-300:
                step = abs((dx @ dx) / den)
            step = float(np.clip(step, 1e-12, 1e6))
        j, gg = cost(x), g @ g
        while cost(x - step * g) > j - 0.5 * step * gg and step > 1e-16:
            step *= 0.5
        x_prev, g_prev = x, g
        x = x - step * g
        g = grad(x)
    return x * d


def prediction_gap(theta: np.ndarray, theta_ref: np.ndarray,
                   C: np.ndarray) -> float:
    """RMS over the joined rows of the two models' predictions' difference,
    as a share of the label's RMS (both from the reference's covar)."""
    d = np.asarray(theta, np.float64) - theta_ref
    return float(np.sqrt(max(d @ C[:-1, :-1] @ d, 0.0) / C[-1, -1]))


def max_rel_err(got, want, want_abs) -> float:
    """Largest |got - want| over max(sum of |terms|, 1), entrywise; inf
    where the program's answer is not finite or has the wrong shape."""
    got = np.asarray(got, np.float64)
    if got.shape != np.shape(want) or not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want) / np.maximum(want_abs, 1.0),
                        initial=0.0))
