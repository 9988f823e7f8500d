"""ML applications vs. materialized-join oracles (the paper's §4.2 workloads)."""

import numpy as np
import pytest

from repro.core.plan import materialize_join
from repro.data import datasets as D
from repro.ml import chowliu, cubes, ridge, trees
from repro.ml.covar import compute_covar

from routes_oracle import ORDERS


@pytest.fixture(scope="module")
def fav():
    ds = D.make("favorita", scale=0.05)
    J = materialize_join(ds.schema, ds.tables, order=ORDERS["favorita"])
    return ds, J


def _oracle_covar(J, layout):
    n = len(J[layout.label])
    X = [np.ones(n)]
    for c in layout.cont:
        X.append(np.asarray(J[c], np.float64))
    for c in layout.cat:
        oh = np.zeros((n, layout.cat_domains[c]))
        oh[np.arange(n), J[c]] = 1
        X += list(oh.T)
    X.append(np.asarray(J[layout.label], np.float64))
    Xm = np.stack(X, 1)
    return Xm.T @ Xm, n


def test_covar_matches_oracle(fav):
    ds, J = fav
    C, N, layout, batch = compute_covar(ds)
    Cref, n = _oracle_covar(J, layout)
    assert n == N
    scale = max(1.0, np.abs(Cref).max())
    assert np.abs(C - Cref).max() / scale < 1e-5
    # Table-2-style invariants: merging collapsed the view count
    assert batch.stats.n_views < batch.stats.n_views_premerge


def test_ridge_closed_form_vs_bgd(fav):
    ds, J = fav
    C, N, layout, _ = compute_covar(ds)
    th_cf = ridge.closed_form(C, N, layout, lam=1e-3)
    res = ridge.bgd(C, N, layout, lam=1e-3, max_iters=5000)
    r_cf = ridge.rmse(th_cf, layout, J)
    r_b = ridge.rmse(res.theta, layout, J)
    base = float(np.std(np.asarray(J[layout.label])))
    assert r_cf < 0.8 * base          # the model actually learns
    assert r_b < 1.2 * r_cf           # BGD reaches closed-form-level accuracy


def test_regression_tree_learns(fav):
    ds, J = fav
    dt = trees.DecisionTree(ds, task="regression", max_depth=3,
                            min_instances=50, max_nodes=15).fit()
    yhat = dt.predict(J)
    y = np.asarray(J[ds.label], np.float64)
    base = np.sqrt(np.mean((y - y.mean()) ** 2))
    got = np.sqrt(np.mean((y - yhat) ** 2))
    assert dt.n_split_nodes() >= 1
    assert got < 0.95 * base


def test_classification_tree_learns():
    ds = D.make("tpcds", scale=0.05)
    J = materialize_join(ds.schema, ds.tables, order=ORDERS["tpcds"])
    dt = trees.DecisionTree(ds, task="classification", label="c_preferred",
                            max_depth=3, min_instances=50, max_nodes=15).fit()
    yhat = dt.predict(J)
    y = np.asarray(J["c_preferred"])
    base = max(y.mean(), 1 - y.mean())   # majority-class accuracy
    acc = (yhat.astype(np.int64) == y).mean()
    assert acc > base + 0.02             # demographics carry real signal


def test_chow_liu_recovers_dependence(fav):
    ds, _ = fav
    # city & state are both store attributes (correlated through store);
    # htype lives on an independent date dimension
    res = chowliu.chow_liu(ds, attrs=["city", "state", "htype"])
    i, j = res.attrs.index("city"), res.attrs.index("state")
    k = res.attrs.index("htype")
    assert res.mi[i, j] > res.mi[i, k]
    assert len(res.edges) == 2           # spanning tree over 3 nodes


def test_cubes_engine_equals_rollup(fav):
    ds, J = fav
    dims = ["stype", "locale", "family"]
    meas = ["units", "txns"]
    a = cubes.cube_via_engine(ds, dims, meas)
    b = cubes.cube_rollup(ds, dims, meas)
    assert set(a) == set(b) and len(a) == 8
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-4, atol=1e-3, err_msg=k)
    # oracle for the finest cell
    fin = np.zeros((5, 3, 33, 2))
    np.add.at(fin, (J["stype"], J["locale"], J["family"]),
              np.stack([J["units"], J["txns"]], -1))
    np.testing.assert_allclose(a[cubes.cube_name(dims)], fin, rtol=1e-4, atol=1e-2)


def test_engine_backed_dataset_statistics(fav):
    """data/statistics.py: feature moments and key-load counts run
    through the LMFAO engine and match the materialized join."""
    from repro.data.statistics import expert_load_aggregate, feature_moments
    ds, J = fav
    stats = feature_moments(ds, attrs=["txns", "price"])
    for a in ("txns", "price"):
        col = np.asarray(J[a], np.float64)
        assert abs(stats[a]["mean"] - col.mean()) < 1e-3 * max(1, abs(col.mean()))
        assert abs(stats[a]["var"] - col.var()) < 1e-2 * max(1.0, col.var())
    ids = np.random.default_rng(0).integers(0, 8, 1000)
    load = expert_load_aggregate(ids, 8)
    np.testing.assert_array_equal(load, np.bincount(ids, minlength=8))


def test_polynomial_regression_degree2(fav):
    """PR_2 (paper §2 eq. (5)): engine covar == materialized-join oracle, and
    the quadratic model beats linear on curvature-bearing data."""
    from repro.ml.polyreg import (compute_poly_covar, fit_polyreg,
                                  monomials, predict_poly)
    ds, J = fav
    attrs = ["txns", "price"]
    C, b, N, layout, batch = compute_poly_covar(ds, degree=2, attrs=attrs)
    assert batch.result.stats.n_dedup_hits > 0   # monomial sharing really happens

    # oracle design matrix on the materialized join
    n = len(J[ds.label])
    X = np.stack([np.prod([np.asarray(J[a], np.float64) ** p for a, p in m],
                          axis=0) if m else np.ones(n)
                  for m in layout.features], axis=1)
    y = np.asarray(J[ds.label], np.float64)
    np.testing.assert_allclose(C, X.T @ X, rtol=1e-5)
    np.testing.assert_allclose(b, X.T @ y, rtol=1e-5)
    assert N == n

    theta, layout2, _ = fit_polyreg(ds, degree=2, attrs=attrs)
    rmse2 = float(np.sqrt(np.mean((predict_poly(theta, layout2, J) - y) ** 2)))
    base = float(np.std(y))
    assert rmse2 < base                      # it learns
    assert len(monomials(attrs, 2)) == 6     # 1, t, p, t², tp, p²
