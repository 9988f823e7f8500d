"""Shared by ``test_routes_oracle_star.py`` and
``test_routes_oracle_snowflake.py``: the ``xla`` lowering's accumulate
routes against a numpy float64 oracle on the four dataset schemas.

A view accumulates by one of three routes, chosen by shape
(``core/lowering/xla.py``): dense (a partial over every segment), compact (a
partial over the segments a block touches, scattered into the accumulator)
and clustered (the step's rows sorted by its widest compact key once, each
block added into one window).  Each case draws a schema through
``D.make``, picks its route by ``ExecutionConfig.block_size`` and the
dataset's scale alone, asserts the route from ``stats``, runs one
application's batch through ``repro.connect(...).views(...)``, and compares
every output with the oracle over ``materialize_join``.  The delta-tick
cases fold one insert-and-delete update into a maintained covar batch and
compare against the oracle over the updated tables.

The oracle evaluates each query's aggregates term by term in float64 over
the materialized join; the only UDAF it knows is the tree batch's node
mask, a lookup of the node's mask row by the attribute's code.  Its join
orders (``ORDERS``) are ``test_ml.py``'s too."""

import functools

import numpy as np
import pytest

import repro
from repro.core import Lambda, Pow, Var
from repro.core.lowering.xla import XlaBackend
from repro.core.plan import materialize_join
from repro.data import DeltaBatchUpdate
from repro.data import datasets as D
from repro.ml import chowliu, covar, cubes, trees

ORDERS = {
    "favorita": ["Oil", "Transactions", "Stores", "Sales", "Holiday", "Items"],
    "retailer": ["Census", "Location", "Weather", "Inventory", "Items"],
    "yelp": ["User", "Review", "Business", "Category", "Attribute"],
    "tpcds": ["customer_demographics", "customer", "household_demographics",
              "customer_address", "store_sales", "date_dim", "time_dim", "item",
              "store", "promotion"],
}

#: (scale, block_size) of each route on each schema.  Dense keeps every key
#: under ``COMPACT_SEGMENTS_PER_ROW`` blocks' rows of segments (favorita's
#: (date, store, promo) key has 36,072 segments, past the default 4,096-row
#: block's 32,768); compact takes a block small enough for the join keys
#: and a scale at which no relation holds ``CLUSTER_ROWS_PER_SEGMENT`` rows
#: a segment of its widest compact key; clustered takes a scale at which
#: the fact relation does.
ROUTES = {
    "favorita": {"dense": (0.05, 8192), "compact": (0.04, 64),
                 "clustered": (1.3, 64)},
    "retailer": {"dense": (0.05, 4096), "compact": (0.05, 8),
                 "clustered": (0.35, 64)},
    "yelp": {"dense": (0.05, 4096), "compact": (0.0075, 8),
             "clustered": (1.0, 8)},
    "tpcds": {"dense": (0.05, 4096), "compact": (0.01, 16),
              "clustered": (0.05, 8)},
}

#: each schema's cube: three categorical dimensions from different
#: relations, and two measures
CUBES = {
    "favorita": (["stype", "locale", "family"], ["units", "txns"]),
    "retailer": (["rain", "rgn_cd", "category"],
                 ["inventoryunits", "maxtemp"]),
    "yelp": (["b_city", "cat", "attr_val"], ["stars", "useful"]),
    "tpcds": (["d_year", "i_category", "ca_state"],
              ["ss_quantity", "ss_sales_price"]),
}

BATCHES = ["covar", "tree", "chowliu", "cube"]


def cases(*schemas):
    """The cases of ``schemas``: each batch on each route, and the delta
    tick on the dense and the compact route (a tick's few rows never
    sort), as ``pytest.param`` with ids ``<schema>-<batch>-<route>``."""
    out = ([(s, b, r) for s in schemas for b in BATCHES
            for r in ("dense", "compact", "clustered")]
           + [(s, "tick", r) for s in schemas for r in ("dense", "compact")])
    return [pytest.param(*c, id="-".join(c)) for c in out]


@functools.lru_cache(maxsize=None)
def dataset(name, scale):
    return D.make(name, scale=scale)


@functools.lru_cache(maxsize=None)
def joined(name, scale):
    ds = dataset(name, scale)
    return materialize_join(ds.schema, ds.tables, order=ORDERS[name])


# -- the oracle ----------------------------------------------------------------

def _term(t, J, params):
    """The terms the four batches use, per joined row (a node mask: per
    node, then per row)."""
    if isinstance(t, Var):
        return np.asarray(J[t.attr], np.float64)
    if isinstance(t, Pow):
        return np.asarray(J[t.attr], np.float64) ** t.k
    if isinstance(t, Lambda) and t.tag.startswith("mask_"):
        (attr,), (p,) = t.attr_order, t.param_refs
        return np.asarray(params[p.name], np.float64)[..., J[attr]]
    raise TypeError(f"the oracle cannot evaluate {t!r}")


def oracle(q, schema, J, params=None, n_nodes=None):
    """``q`` over the joined rows ``J`` in float64: ``((N,)?, *domains,
    n_aggs)``, the node axis in front when the batch has one."""
    n = len(next(iter(J.values())))
    lead = (n_nodes,) if n_nodes is not None else (1,)
    cols = []
    for a in q.aggregates:
        total = np.zeros(lead + (n,))
        for prod in a.products:
            v = np.ones(n)
            for t in prod.terms:
                v = v * _term(t, J, params or {})
            total = total + v
        cols.append(total)
    dims = [schema.domain(g) for g in q.group_by]
    seg = (np.ravel_multi_index([np.asarray(J[g]) for g in q.group_by], dims)
           if dims else np.zeros(n, np.int64))
    size = int(np.prod(dims)) if dims else 1
    out = np.stack([np.stack([np.bincount(seg, weights=c[i], minlength=size)
                              for c in cols], -1)
                    for i in range(lead[0])])
    out = out.reshape(lead + tuple(dims) + (len(cols),))
    return out if n_nodes is not None else out[0]


def assert_matches(got, want, name):
    """``test_ml``'s covar tolerance, output by output: the largest error
    under 1e-5 of the output's largest magnitude (or of 1)."""
    got = np.asarray(got, np.float64)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    scale = max(1.0, np.abs(want).max())
    err = np.abs(got - want).max() / scale
    assert err < 1e-5, (name, err)


# -- the batches ---------------------------------------------------------------

#: a tree frontier's nodes: the two children of a split of the root on the
#: first feature, and one node with random masks
N_NODES = 3


def frontier(features, seed=0):
    """``N_NODES`` frontier masks over the tree's split features."""
    root = {f.attr: np.ones(f.domain, np.float32) for f in features}
    f0 = features[0]
    left, right = trees.child_masks(root, f0.attr, f0.kind, f0.domain // 2)
    rng = np.random.default_rng(seed)
    rand = {f.attr: (rng.random(f.domain) < 0.7).astype(np.float32)
            for f in features}
    return trees.stack_mask_params(features, [left, right, rand])


def run_batch(ds, db, batch):
    """Register ``batch`` on the session ``db`` and run it: ``(handle,
    queries, outputs, params, n_nodes)``."""
    if batch == "tree":
        features = trees.build_tree_features(ds, None, None)
        handle, qs = trees.build_tree_batch(
            ds, features, "regression", ds.label, 0, database=db)
        params = frontier(features)
        return handle, qs, handle.run_batched(params), params, N_NODES
    if batch == "covar":
        qs = covar.covar_queries(ds)[0]
    elif batch == "chowliu":
        qs = chowliu.mi_queries(ds.features_cat)
    else:
        qs = cubes.cube_queries(*CUBES[ds.name])
    handle = db.views(qs)
    return handle, qs, handle.run(), None, None


def assert_route(handle, route, ds):
    stats = handle.stats
    if route == "dense":
        assert stats.n_compact_views == 0 and stats.n_clustered_steps == 0
    elif route == "compact":
        assert stats.n_compact_views > 0 and stats.n_clustered_steps == 0
    else:
        assert stats.n_clustered_steps >= 1
        # and one of the sorted steps scans the fact relation
        plan, n = handle.compiled.plan, ds.db.sizes()[ds.fact]
        assert any(XlaBackend.count_clustered(p, plan.config, n)
                   for p in plan.step_programs if p.rel == ds.fact)


def fact_update(ds, seed=0, k=24):
    """One insert-and-delete update of the fact relation, ``k`` rows drawn
    from the fact's own (with new measures) in and ``k`` rows out, and the
    tables after it (deletes first, inserts appended)."""
    rng = np.random.default_rng(seed)
    fact = {a: np.asarray(c) for a, c in ds.tables[ds.fact].items()}
    n = len(fact[ds.label])
    src, out = rng.choice(n, k), rng.choice(n, k, replace=False)
    ins = {a: (c[src] if ds.schema.attr(a).is_discrete
               else (c[src] + rng.normal(size=k)).astype(np.float32))
           for a, c in fact.items()}
    keep = np.setdiff1d(np.arange(n), out)
    tables = dict(ds.tables)
    tables[ds.fact] = {a: np.concatenate([c[keep], ins[a]])
                       for a, c in fact.items()}
    return DeltaBatchUpdate().insert(ds.fact, ins).delete(ds.fact, out), tables


def check_route(schema_name, batch, route):
    """One case: assert its route, run it, compare every output."""
    scale, block_size = ROUTES[schema_name][route]
    ds = dataset(schema_name, scale)
    db = repro.connect(ds, config=repro.ExecutionConfig(
        block_size=block_size))
    if batch == "tick":
        qs = covar.covar_queries(ds)[0]
        handle = db.views(qs, maintain=True)
        assert_route(handle, route, ds)
        handle.run()
        update, tables = fact_update(ds)
        handle.apply(update)
        got = handle.results()
        J = materialize_join(ds.schema, tables, order=ORDERS[schema_name])
        params = n_nodes = None
    else:
        handle, qs, got, params, n_nodes = run_batch(ds, db, batch)
        assert_route(handle, route, ds)
        J = joined(schema_name, scale)
    assert set(got) == {q.name for q in qs}
    for q in qs:
        assert_matches(got[q.name], oracle(q, ds.schema, J, params, n_nodes),
                       q.name)
