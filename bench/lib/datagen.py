"""Data of a configuration, made on the device from the seed.

A configuration file (``bench/configs/<name>.json``) describes a star or
snowflake schema and how each column is drawn:

* a dimension relation has a ``key``: it holds one row per combination of
  its key attributes (row-major over their domains), so a key is a dense
  index into it;
* the fact has ``rows`` and draws its own keys;
* a column is ``uniform`` over its attribute's domain, ``zipf`` (Zipf(a) over
  1, 2, ... folded into the domain, as ``numpy``'s ``zipf(a) - 1 % domain``),
  ``normal`` (optionally ``abs`` or floored at ``min``), or ``linear`` (a
  constant, terms that reach other relations' attributes through the join
  keys, per-category effects and Gaussian noise).

Everything is drawn by ``jax.random`` inside jitted calls, so the columns
never pass through the host.  The same seed gives the same columns.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from scipy.special import zeta


class Config:
    """A configuration file, with the join structure the generator and the
    reference both need."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.name = spec["name"]
        self.fact = spec["fact"]
        self.label = spec["label"]
        self.features_cont = list(spec["features_cont"])
        self.features_cat = list(spec["features_cat"])
        self.kinds = {a: k for a, k, _ in spec["attributes"]}
        self.domains = {a: d for a, _, d in spec["attributes"]}
        self.relations = {r: dict(v) for r, v in spec["relations"].items()}
        self.attrs = {r: self.relation_attrs(r) for r in self.relations}
        self.edges = [tuple(e) for e in spec["edges"]]
        self.parent = self._parents()

    @classmethod
    def load(cls, path: str) -> "Config":
        with open(path) as f:
            return cls(json.load(f))

    def relation_attrs(self, rel: str) -> List[str]:
        r = self.relations[rel]
        return list(r.get("key", [])) + [a for a in r["columns"]
                                         if a not in r.get("key", [])]

    def _parents(self) -> Dict[str, str]:
        """The join tree rooted at the fact: relation -> its parent."""
        parent, todo = {}, [self.fact]
        while todo:
            r = todo.pop()
            for a, b in self.edges:
                for x, y in ((a, b), (b, a)):
                    if x == r and y != self.fact and y not in parent:
                        parent[y] = r
                        todo.append(y)
        if set(parent) | {self.fact} != set(self.relations):
            raise ValueError(f"{self.name}: edges do not span the relations")
        return parent

    def home(self, attr: str) -> str:
        """The relation that owns a non-join attribute: the fact if it has
        it, else the one dimension that lists it outside its key."""
        if attr in self.attrs[self.fact]:
            return self.fact
        owners = [r for r in self.relations if r != self.fact
                  and attr in self.relations[r]["columns"]]
        if len(owners) != 1:
            raise ValueError(f"{self.name}: attribute {attr!r} has owners "
                             f"{owners}")
        return owners[0]

    def n_rows(self, rel: str) -> int:
        r = self.relations[rel]
        if "key" in r:
            return int(np.prod([self.domains[k] for k in r["key"]]))
        return int(self.spec["fact_rows"])

    def shrunk(self, fact_rows: int, domain_cap: int) -> "Config":
        """A copy at a test size: ``fact_rows`` fact rows and every key or
        categorical domain capped at ``domain_cap``."""
        spec = json.loads(json.dumps(self.spec))
        spec["fact_rows"] = int(fact_rows)
        spec["attributes"] = [[a, k, min(d, domain_cap) if d else d]
                              for a, k, d in spec["attributes"]]
        return Config(spec)


def folded_zipf_cdf(a: float, domain: int) -> np.ndarray:
    """CDF over ``[0, domain)`` of ``(Z - 1) % domain`` with Z ~ Zipf(a):
    P(c) is proportional to the Hurwitz zeta ``zeta(a, (c + 1) / domain)``."""
    p = zeta(a, (np.arange(domain) + 1.0) / domain)
    cdf = np.cumsum(p / p.sum())
    cdf[-1] = 1.0
    return cdf.astype(np.float32)


def _radix(codes: List[jnp.ndarray], doms: List[int]) -> jnp.ndarray:
    idx = jnp.zeros_like(codes[0])
    for c, d in zip(codes, doms):
        idx = idx * d + c
    return idx


class Generator:
    """Draws a configuration's relations from a seed, on the device."""

    def __init__(self, cfg: Config):
        self.cfg = cfg

    def _key(self, seed: int, rel: str, attr: str):
        # seeds run past 32 bits: fold the high word in
        k = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
        k = jax.random.fold_in(k, sorted(self.cfg.relations).index(rel))
        return jax.random.fold_in(k, self.cfg.relation_attrs(rel).index(attr))

    def dimensions(self, seed: int) -> Dict[str, Dict[str, jnp.ndarray]]:
        keys = {(r, a): self._key(seed, r, a)
                for r in self.cfg.relations if r != self.cfg.fact
                for a in self.cfg.relation_attrs(r)}
        return self._dims(keys)

    @functools.partial(jax.jit, static_argnums=0)
    def _dims(self, keys):
        cfg, out = self.cfg, {}
        for rel, spec in cfg.relations.items():
            if rel == cfg.fact:
                continue
            key = spec["key"]
            doms = [cfg.domains[k] for k in key]
            n = int(np.prod(doms))
            cols = {}
            rest = jnp.arange(n, dtype=jnp.int32)
            for k, d in reversed(list(zip(key, doms))):
                cols[k] = rest % d
                rest = rest // d
            for a, col in spec["columns"].items():
                cols[a] = self._draw(keys[rel, a], a, col, n)
            out[rel] = {a: cols[a] for a in cfg.relation_attrs(rel)}
        return out

    def _draw(self, key, attr: str, col: dict, n: int) -> jnp.ndarray:
        dist = col["dist"]
        dom = self.cfg.domains[attr]
        if dist == "uniform":
            return jax.random.randint(key, (n,), 0, dom, jnp.int32)
        if dist == "zipf":
            cdf = jnp.asarray(folded_zipf_cdf(col["a"], dom))
            u = jax.random.uniform(key, (n,), jnp.float32)
            return jnp.minimum(jnp.searchsorted(cdf, u, side="right"),
                               dom - 1).astype(jnp.int32)
        if dist == "normal":
            x = col["mean"] + col["sd"] * jax.random.normal(key, (n,))
            if col.get("abs"):
                x = jnp.abs(x)
            if "min" in col:
                x = jnp.maximum(x, col["min"])
            return x.astype(jnp.float32)
        raise ValueError(f"{self.cfg.name}: {attr}: unknown dist {dist!r}")

    def resolve(self, attr: str, cols: Dict[str, jnp.ndarray],
                dims: Dict[str, Dict[str, jnp.ndarray]]) -> jnp.ndarray:
        """``attr`` for each row of the fact columns ``cols``: a fact column,
        or a dimension's column read through its key (recursively, so a
        snowflake's outer attributes reach the fact through the inner ones)."""
        if attr in cols:
            return cols[attr]
        rel = self.cfg.home(attr)
        key = self.cfg.relations[rel]["key"]
        idx = _radix([self.resolve(k, cols, dims) for k in key],
                     [self.cfg.domains[k] for k in key])
        return dims[rel][attr][idx]

    def fact_rows(self, seed: int, dims, n: int):
        """``n`` fact rows drawn from ``seed``."""
        fact = self.cfg.fact
        keys = {a: self._key(seed, fact, a)
                for a in self.cfg.relation_attrs(fact)}
        return self._fact(keys, dims, n)

    @functools.partial(jax.jit, static_argnums=(0, 3))
    def _fact(self, keys, dims, n: int):
        cfg = self.cfg
        spec = cfg.relations[cfg.fact]["columns"]
        cols = {}
        for a, col in spec.items():
            if col["dist"] != "linear":
                cols[a] = self._draw(keys[a], a, col, n)
        for a, col in spec.items():
            if col["dist"] == "linear":
                x = jnp.full((n,), col["const"], jnp.float32)
                x = x + col["noise_sd"] * jax.random.normal(keys[a], (n,))
                for i, t in enumerate(col["terms"]):
                    v = self.resolve(t["attr"], cols, dims)
                    if "effect_sd" in t:
                        # one effect per category: a property of the data
                        eff = t["effect_sd"] * jax.random.normal(
                            jax.random.fold_in(keys[a], 1 + i),
                            (cfg.domains[t["attr"]],))
                        x = x + eff[v]
                    else:
                        x = x + t["coef"] * v
                cols[a] = x.astype(jnp.float32)
        return {a: cols[a] for a in cfg.relation_attrs(cfg.fact)}
