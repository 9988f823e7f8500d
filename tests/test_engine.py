"""Core engine correctness: layers, merging, multi-root, and a hypothesis
property test — engine output == brute-force (materialize join, then
aggregate) on random chain schemas/data/queries.

All compilation goes through the session facade (``repro.connect`` →
``Database.views``); the legacy ``Engine.compile*`` entry points are
core-internal (enforced by the engine-contract linter, DESIGN.md §12).
"""

import numpy as np
import pytest

try:  # optional dev dependency: only the property test needs it
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    st = None

from repro.api import ExecutionConfig, connect
from repro.core import (COUNT, Delta, Lambda, Pow, Var, agg, query, schema,
                        sum_of, sum_prod)
from repro.core.groups import group_views, independent_sets
from repro.core.jointree import JoinTree
from repro.core.plan import materialize_join
from repro.core.pushdown import push_down
from repro.core.roots import find_roots, single_root
from repro.data import from_numpy


def chain_schema():
    return schema(
        [("x1", "categorical", 3), ("x2", "key", 4), ("x3", "key", 5),
         ("x4", "categorical", 3), ("u", "continuous", 0)],
        [("R1", ["x1", "x2"]), ("R2", ["x2", "x3", "u"]), ("R3", ["x3", "x4"])])


def chain_db(seed=0, n1=17, n2=29, n3=13):
    rng = np.random.default_rng(seed)
    T = {"R1": {"x1": rng.integers(0, 3, n1), "x2": rng.integers(0, 4, n1)},
         "R2": {"x2": rng.integers(0, 4, n2), "x3": rng.integers(0, 5, n2),
                "u": rng.normal(size=n2).astype(np.float32)},
         "R3": {"x3": rng.integers(0, 5, n3), "x4": rng.integers(0, 3, n3)}}
    return T


def brute(schema_, tables, q):
    J = materialize_join(schema_, tables, order=["R1", "R2", "R3"])
    n = len(J["x1"])
    cols = []
    for a in q.aggregates:
        tot = np.zeros(1)
        val = np.zeros(n)
        for prod in a.products:
            v = np.ones(n)
            for t in prod.terms:
                env = {at: J[at] for at in t.attrs()}
                v = v * np.asarray(t.evaluate(env, {}), dtype=np.float64)
            val = val + v
        if q.group_by:
            dims = [schema_.domain(g) for g in q.group_by]
            out = np.zeros(dims)
            np.add.at(out, tuple(J[g] for g in q.group_by), val)
        else:
            out = np.sum(val)
        cols.append(out)
    return np.stack([np.asarray(c, dtype=np.float64) for c in cols], axis=-1)


QUERIES = [
    query("q_count", [], [COUNT]),
    query("q_sums", [], [sum_of("u"), agg(Pow("u", 2)), sum_prod("u", "u")]),
    query("q_g1", ["x1"], [COUNT, sum_of("u")]),
    query("q_g2", ["x1", "x4"], [COUNT]),
    query("q_delta", ["x4"], [agg(Var("u"), Delta("x1", "==", 1))]),
    query("q_lambda", ["x2"], [agg(Lambda(("x1", "x4"),
                                          lambda a, b, p: (a * 2 + b).astype(np.float32),
                                          tag="t1"))]),
]


@pytest.mark.parametrize("multi_root", [True, False])
@pytest.mark.parametrize("block_size", [7, 64])
def test_engine_matches_bruteforce(multi_root, block_size):
    S = chain_schema()
    T = chain_db()
    db = from_numpy(S, T)
    sess = connect(db, config=ExecutionConfig(multi_root=multi_root,
                                              block_size=block_size))
    out = sess.views(QUERIES).run()
    for q in QUERIES:
        expect = brute(S, T, q)
        got = np.asarray(out[q.name], dtype=np.float64)
        np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-4,
                                   err_msg=q.name)


def test_merging_reduces_views():
    S = chain_schema()
    db = from_numpy(S, chain_db())
    h = connect(db).views(QUERIES)
    st_ = h.stats
    assert st_.n_views < st_.n_views_premerge
    assert st_.n_groups >= 1
    assert st_.n_app_aggregates == sum(len(q.aggregates) for q in QUERIES)


def test_multi_root_uses_multiple_roots():
    S = chain_schema()
    db = from_numpy(S, chain_db())
    tree = JoinTree.build(S, db.sizes())
    roots = find_roots(tree, QUERIES, db.sizes())
    assert len(set(roots.values())) > 1          # Example 3.3's point
    sroots = single_root(tree, QUERIES, db.sizes())
    assert len(set(sroots.values())) == 1


def test_group_dependency_levels():
    S = chain_schema()
    db = from_numpy(S, chain_db())
    tree = JoinTree.build(S, db.sizes())
    result = push_down(tree, QUERIES, find_roots(tree, QUERIES, db.sizes()))
    groups = group_views(result)
    levels = independent_sets(groups)
    seen = set()
    for lv in levels:
        for gid in lv:
            for dep in groups[gid].deps:
                assert dep in seen
        seen.update(lv)


def test_schedule_topology_and_fusion():
    """Fused steps must stay topologically ordered, cover every group exactly
    once, and only ever fuse same-relation groups."""
    from repro.core.schedule import build_schedule
    from repro.data import datasets as D
    from repro.ml.covar import covar_queries

    ds = D.make("retailer", scale=0.02)
    qs, _ = covar_queries(ds)
    h = connect(ds).views(qs)
    groups = h.compiled.groups
    sched = h.schedule
    # partition of groups
    all_gids = sorted(g for s in sched.steps for g in s.gids)
    assert all_gids == sorted(g.gid for g in groups)
    by_gid = {g.gid: g for g in groups}
    sid_of = {g: s.sid for s in sched.steps for g in s.gids}
    for s in sched.steps:
        assert all(by_gid[g].rel == s.rel for g in s.gids)
        # every group dependency resolves to a strictly earlier step (fused
        # groups are dependency-independent, so never in the same step)
        for g in s.gids:
            for dep in by_gid[g].deps:
                assert sid_of[dep] < s.sid
    # the multi-root covar batch has cross-level same-relation groups: fusion
    # must strictly reduce the scan count (paper's shared-scan claim)
    assert sched.n_scans < len(groups)
    unfused = build_schedule(groups, fuse=False)
    assert unfused.n_scans == len(groups)


def test_fused_scans_match_oracle():
    """Shared-scan fusion must not change any query output (retailer covar
    batch vs the materialized-join oracle)."""
    from repro.data import datasets as D
    from repro.ml.covar import covar_queries

    ds = D.make("retailer", scale=0.02)
    qs, _ = covar_queries(ds)
    h = connect(ds).views(qs)
    assert h.stats.n_fused_scans > 0
    out = h.run()
    J = materialize_join(ds.schema, ds.tables,
                         order=["Census", "Location", "Weather", "Inventory",
                                "Items"])
    n = len(next(iter(J.values())))
    for q in qs[:8]:
        cols = []
        for a in q.aggregates:
            val = np.zeros(n)
            for prod in a.products:
                v = np.ones(n)
                for t in prod.terms:
                    env = {at: J[at] for at in t.attrs()}
                    v = v * np.asarray(t.evaluate(env, {}), dtype=np.float64)
                val += v
            if q.group_by:
                o = np.zeros([ds.schema.domain(g) for g in q.group_by])
                np.add.at(o, tuple(J[g] for g in q.group_by), val)
            else:
                o = np.sum(val)
            cols.append(np.asarray(o, np.float64))
        expect = np.stack(cols, axis=-1)
        got = np.asarray(out[q.name], dtype=np.float64)
        np.testing.assert_allclose(got, expect, rtol=1e-3, atol=1e-3,
                                   err_msg=q.name)


def test_dynamic_params_no_retrace():
    """Decision-tree-style dynamic UDAFs: changing the threshold params must
    reuse the same compiled executable (paper's dynamic functions, minus the
    recompilation)."""
    from repro.core.aggregates import Param
    S = chain_schema()
    T = chain_db()
    db = from_numpy(S, T)
    q = query("qd", ["x4"], [agg(Var("u"), Delta("x1", "==", Param("t")))])
    h = connect(db).views([q])
    o1 = h.run(params={"t": np.int32(1)})["qd"]
    o2 = h.run(params={"t": np.int32(2)})["qd"]
    J = materialize_join(S, T, order=["R1", "R2", "R3"])
    for t, o in [(1, o1), (2, o2)]:
        exp = np.zeros(3)
        np.add.at(exp, J["x4"], J["u"] * (J["x1"] == t))
        np.testing.assert_allclose(np.asarray(o)[..., 0], exp, rtol=1e-4, atol=1e-4)
    assert len(h.compiled._jitted) == 1  # one executable served both


# -- hypothesis property test -------------------------------------------------

if st is None:
    def test_property_engine_equals_bruteforce():
        pytest.skip("hypothesis not installed (pip install .[dev])")
else:
    @st.composite
    def random_case(draw):
        d1 = draw(st.integers(2, 4))
        d2 = draw(st.integers(2, 4))
        d3 = draw(st.integers(2, 4))
        n1 = draw(st.integers(1, 25))
        n2 = draw(st.integers(1, 25))
        rng = np.random.default_rng(draw(st.integers(0, 2**31)))
        S = schema(
            [("a", "categorical", d1), ("k", "key", d2), ("b", "categorical", d3),
             ("u", "continuous", 0)],
            [("L", ["a", "k"]), ("R", ["k", "b", "u"])])
        T = {"L": {"a": rng.integers(0, d1, n1), "k": rng.integers(0, d2, n1)},
             "R": {"k": rng.integers(0, d2, n2), "b": rng.integers(0, d3, n2),
                   "u": rng.normal(size=n2).astype(np.float32)}}
        gb = draw(st.sampled_from([[], ["a"], ["b"], ["a", "b"], ["k"], ["k", "b"]]))
        aggs = draw(st.lists(st.sampled_from(
            [COUNT, sum_of("u"), agg(Pow("u", 2)), agg(Var("u"), Delta("a", "<=", 1)),
             agg(Delta("b", "==", 0))]), min_size=1, max_size=3))
        return S, T, query("q", gb, aggs)

    @settings(max_examples=25, deadline=None)
    @given(random_case())
    def test_property_engine_equals_bruteforce(case):
        S, T, q = case
        db = from_numpy(S, T)
        h = connect(db, config=ExecutionConfig(block_size=8)).views([q])
        got = np.asarray(h.run()[q.name], dtype=np.float64)

        J = materialize_join(S, T, order=["L", "R"])
        n = len(J["a"])
        cols = []
        for a in q.aggregates:
            val = np.zeros(n)
            for prod in a.products:
                v = np.ones(n)
                for t in prod.terms:
                    env = {at: J[at] for at in t.attrs()}
                    v = v * np.asarray(t.evaluate(env, {}), dtype=np.float64)
                val += v
            if q.group_by:
                out = np.zeros([S.domain(g) for g in q.group_by])
                np.add.at(out, tuple(J[g] for g in q.group_by), val)
            else:
                out = np.sum(val)
            cols.append(np.asarray(out, np.float64))
        expect = np.stack(cols, axis=-1)
        np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shrink,n_compact", [(0, 0), (1, 1)])
def test_compact_threshold_by_segments_per_block(shrink, n_compact):
    """A view takes the xla backend's compact accumulate path only when its
    segments outnumber ``COMPACT_SEGMENTS_PER_ROW`` times a block's rows:
    64 (a, b) segments stay dense at exactly that many and go compact in
    blocks one row shorter; both give the sums."""
    from repro.core.lowering.xla import COMPACT_SEGMENTS_PER_ROW

    block_size = 64 // COMPACT_SEGMENTS_PER_ROW - shrink
    assert 64 == COMPACT_SEGMENTS_PER_ROW * (block_size + shrink)
    S = schema([("a", "categorical", 8), ("b", "categorical", 8),
                ("u", "continuous", 0)], [("R", ["a", "b", "u"])])
    rng = np.random.default_rng(5)
    T = {"R": {"a": rng.integers(0, 8, 300), "b": rng.integers(0, 8, 300),
               "u": rng.normal(size=300).astype(np.float32)}}
    q = query("q", ["a", "b"], [COUNT, sum_of("u")])
    h = connect(from_numpy(S, T), config=ExecutionConfig(
        block_size=block_size)).views([q])
    assert h.stats.n_compact_views == n_compact
    assert f"compact={n_compact}" in h.stats.summary()
    got = np.asarray(h.run()["q"], dtype=np.float64)
    expect = np.zeros((8, 8, 2))
    np.add.at(expect, (T["R"]["a"], T["R"]["b"]),
              np.stack([np.ones(300), T["R"]["u"]], axis=-1))
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-5)


def test_rip_validation_rejects_bad_tree():
    S = schema([("a", "key", 2), ("b", "key", 2), ("c", "key", 2)],
               [("R1", ["a", "b"]), ("R2", ["b", "c"]), ("R3", ["a", "c"])])
    with pytest.raises(ValueError):
        JoinTree(S, [("R1", "R2"), ("R2", "R3")])  # a shared by R1,R3 missing in R2
