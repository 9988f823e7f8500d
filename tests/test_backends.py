"""Lowering-backend equivalence: ``backend="pallas"`` (interpret mode on CPU)
must produce the same results as the default ``backend="xla"`` path across
the example workload batches (ridge covar and decision-tree node batches),
and the Pallas hist fast path must actually engage for the tree batch."""

import numpy as np
import pytest

from repro.core import COUNT, Engine, agg, query, schema, sum_of
from repro.core.aggregates import Delta, Lambda, Param, Pow, Var
from repro.data import datasets as D
from repro.data import from_numpy


def _run_both(S_or_ds, queries, **compile_kw):
    if hasattr(S_or_ds, "db"):
        ds = S_or_ds
        db, edges = ds.db, ds.edges
        eng_kw = dict(edges=edges, sizes=db.sizes())
        Ssch = ds.schema
    else:
        Ssch, db = S_or_ds
        eng_kw = dict(sizes=db.sizes())
    outs = {}
    for be in ("xla", "pallas"):
        eng = Engine(Ssch, **eng_kw)
        batch = eng.compile(queries, backend=be, **compile_kw)
        outs[be] = {k: np.asarray(v, np.float64)
                    for k, v in batch(db).items()}
    return outs


def _assert_equal(outs):
    assert outs["xla"].keys() == outs["pallas"].keys()
    for k in outs["xla"]:
        np.testing.assert_allclose(outs["pallas"][k], outs["xla"][k],
                                   rtol=1e-4, atol=1e-4, err_msg=k)


def _chain_batch():
    S = schema(
        [("x1", "categorical", 3), ("x2", "key", 4), ("x3", "key", 5),
         ("x4", "categorical", 3), ("u", "continuous", 0)],
        [("R1", ["x1", "x2"]), ("R2", ["x2", "x3", "u"]), ("R3", ["x3", "x4"])])
    rng = np.random.default_rng(3)
    T = {"R1": {"x1": rng.integers(0, 3, 21), "x2": rng.integers(0, 4, 21)},
         "R2": {"x2": rng.integers(0, 4, 33), "x3": rng.integers(0, 5, 33),
                "u": rng.normal(size=33).astype(np.float32)},
         "R3": {"x3": rng.integers(0, 5, 11), "x4": rng.integers(0, 3, 11)}}
    queries = [
        query("q_count", [], [COUNT]),
        query("q_sums", [], [sum_of("u"), agg(Pow("u", 2))]),
        query("q_g", ["x1", "x4"], [COUNT, sum_of("u")]),
        query("q_delta", ["x4"], [agg(Var("u"), Delta("x1", "==", 1))]),
    ]
    return (S, from_numpy(S, T)), queries, dict(block_size=16)


def _ridge_batch():
    from repro.ml.covar import covar_queries
    ds = D.make("retailer", scale=0.02)
    qs, _ = covar_queries(ds)
    return ds, qs, {}


@pytest.mark.parametrize("make", [_chain_batch, _ridge_batch],
                         ids=["chain", "ridge"])
def test_pallas_matches_xla(make):
    data, queries, compile_kw = make()
    _assert_equal(_run_both(data, queries, **compile_kw))


def test_pallas_matches_xla_tree_batch():
    from repro.ml.trees import DecisionTree
    ds = D.make("favorita", scale=0.02)
    masks = None
    outs = {}
    for be in ("xla", "pallas"):
        # node_batch=False: the single-node hist fast path (the batched
        # variant is covered by tests/test_frontier.py)
        dt = DecisionTree(ds, task="regression", max_depth=1, min_instances=10,
                          max_nodes=3, backend=be, node_batch=False)
        if masks is None:
            masks = {f"mask_{f.attr}": np.ones(f.domain, dtype=np.float32)
                     for f in dt.features}
        if be == "pallas":
            # the node-histogram pattern must route through tree_hist
            nhist = sum(1 for sp in dt.batch.plan.step_programs
                        for vp in sp.views if vp.hist is not None)
            assert nhist > 0
        outs[be] = {k: np.asarray(v, np.float64)
                    for k, v in dt.batch(ds.db, params=masks).items()}
    _assert_equal(outs)


def test_pallas_matches_xla_dynamic_params():
    """Dynamic UDAF params (decision-tree thresholds) stay recompile-free and
    equivalent on the Pallas path."""
    from repro.core.aggregates import Param
    S = schema([("k", "key", 6), ("c", "categorical", 4), ("u", "continuous", 0)],
               [("F", ["k", "u"]), ("D", ["k", "c"])])
    rng = np.random.default_rng(5)
    n = 257
    T = {"F": {"k": rng.integers(0, 6, n),
               "u": rng.normal(size=n).astype(np.float32)},
         "D": {"k": np.arange(6), "c": rng.integers(0, 4, 6)}}
    db = from_numpy(S, T)
    q = query("qd", ["c"], [agg(Var("u"), Delta("c", "==", Param("t")))])
    for be in ("xla", "pallas"):
        eng = Engine(S, sizes=db.sizes())
        batch = eng.compile([q], backend=be, block_size=64)
        o1 = np.asarray(batch(db, params={"t": np.int32(1)})["qd"])
        o2 = np.asarray(batch(db, params={"t": np.int32(2)})["qd"])
        assert len(batch._jitted) == 1
        if be == "xla":
            ref1, ref2 = o1, o2
        else:
            np.testing.assert_allclose(o1, ref1, rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(o2, ref2, rtol=1e-4, atol=1e-4)


def test_unknown_backend_rejected():
    from repro.core.lowering import get_backend
    with pytest.raises(ValueError):
        get_backend("cuda")


# ---------------------------------------------------------------------------
# launch-level kernel fusion (ISSUE 6): fused == unfused == xla, and the
# static launch-site count actually reflects the fusion


def _ridge_setup():
    from repro.ml.covar import covar_queries
    ds = D.make("retailer", scale=0.02)
    qs, _ = covar_queries(ds)
    return ds, qs


@pytest.mark.parametrize("fuse_scans", [True, False])
def test_fused_kernels_match_unfused_ridge(fuse_scans):
    """Launch-level fusion (fuse_kernels) composes with scheduler-level
    shared-scan fusion (fuse_scans): every combination agrees with xla.
    Fused vs unfused pallas is allclose, not bitwise — the single fused dot
    reassociates fp32 sums differently than per-view launches."""
    ds, qs = _ridge_setup()
    outs, stats = {}, {}
    for be, fuse_kernels in [("xla", True), ("pallas", True),
                             ("pallas", False)]:
        eng = Engine(ds.schema, edges=ds.edges, sizes=ds.db.sizes())
        batch = eng.compile(qs, backend=be, fuse_scans=fuse_scans,
                            fuse_kernels=fuse_kernels)
        key = (be, fuse_kernels)
        outs[key] = {k: np.asarray(v, np.float64)
                     for k, v in batch(ds.db).items()}
        stats[key] = batch.stats
    for key in [("pallas", True), ("pallas", False)]:
        for k in outs[("xla", True)]:
            np.testing.assert_allclose(outs[key][k], outs[("xla", True)][k],
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"{key}/{k}")
    # xla has no pallas launch sites; fused pallas = 1 per scan step with
    # views; unfused = one per bucket/hist view, strictly more here
    assert stats[("xla", True)].n_kernel_launches == 0
    n_fused = stats[("pallas", True)].n_kernel_launches
    n_unfused = stats[("pallas", False)].n_kernel_launches
    assert 0 < n_fused < n_unfused
    assert n_fused <= stats[("pallas", True)].n_scan_steps


def test_fused_kernels_match_unfused_tree_frontier():
    """Frontier-batched node-histogram batch (the tree workload) under
    launch fusion: batched hists ride the same fused launch."""
    from repro.ml.trees import DecisionTree, stack_mask_params
    import repro
    ds = D.make("favorita", scale=0.02)
    rng = np.random.default_rng(11)
    outs, stats = {}, {}
    for key, cfg in {
            ("pallas", True): repro.ExecutionConfig(backend="pallas"),
            ("pallas", False): repro.ExecutionConfig(backend="pallas",
                                                     fuse_kernels=False),
            ("xla", True): repro.ExecutionConfig(backend="xla")}.items():
        dt = DecisionTree(ds, task="regression", max_depth=2,
                          min_instances=10, max_nodes=7, node_batch=True,
                          config=cfg)
        masks = [{f.attr: np.ones(f.domain, np.float32)
                  for f in dt.features} for _ in range(4)]
        out = dt.batch.run_batched(ds.db, stack_mask_params(dt.features,
                                                            masks))
        outs[key] = {k: np.asarray(v, np.float64) for k, v in out.items()}
        stats[key] = dt.batch.stats
    for key in [("pallas", True), ("pallas", False)]:
        for k in outs[("xla", True)]:
            np.testing.assert_allclose(outs[key][k], outs[("xla", True)][k],
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"{key}/{k}")
    assert stats[("xla", True)].n_kernel_launches == 0
    assert (0 < stats[("pallas", True)].n_kernel_launches
            < stats[("pallas", False)].n_kernel_launches)


def test_block_rows_threads_through_config():
    """block_rows reaches the pallas lowering via PlanConfig (no more
    backend class attribute) and any aligned value gives the same answer."""
    ds, qs = _ridge_setup()
    outs = []
    for br in (128, 512):
        eng = Engine(ds.schema, edges=ds.edges, sizes=ds.db.sizes())
        batch = eng.compile(qs, backend="pallas", block_rows=br)
        assert batch.plan.config.block_rows == br
        outs.append({k: np.asarray(v, np.float64)
                     for k, v in batch(ds.db).items()})
    for k in outs[0]:
        np.testing.assert_allclose(outs[0][k], outs[1][k],
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("field,bad", [
    ("block_rows", 0), ("block_rows", -8), ("block_rows", 7),
    ("block_rows", 129), ("block_rows", "biggish"),
    ("block_size", 0), ("block_size", -1), ("block_size", "large")])
def test_invalid_blocking_rejected(field, bad):
    import repro
    with pytest.raises(ValueError, match=field):
        repro.ExecutionConfig(backend="pallas", **{field: bad})


def test_autotuned_blocking_smoke(tmp_path):
    """block_size="auto" resolves per-step blockings at bind time, records
    them in plan.last_autotune, and matches the xla reference."""
    ds, qs = _ridge_setup()
    cache = str(tmp_path / "autotune.json")
    eng = Engine(ds.schema, edges=ds.edges, sizes=ds.db.sizes())
    batch = eng.compile(qs, backend="pallas", block_size="auto",
                        block_rows="auto", autotune_cache=cache)
    out = {k: np.asarray(v, np.float64) for k, v in batch(ds.db).items()}
    rep = batch.plan.last_autotune
    assert rep and all(isinstance(r["block_size"], int)
                       and r["block_rows"] % 8 == 0 for r in rep)
    eng2 = Engine(ds.schema, edges=ds.edges, sizes=ds.db.sizes())
    ref_out = eng2.compile(qs, backend="xla")(ds.db)
    for k in out:
        np.testing.assert_allclose(out[k], np.asarray(ref_out[k], np.float64),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# xla accumulate paths: a view with more segments than a block has rows sums
# each block into a compact partial over the segments it touches and
# scatter-adds it; the dense path zero-fills a partial over every segment.
# Each segment gets the same f32 adds in the same order, so the two agree
# bit for bit.

_BLOCK = 64
_A, _B = 40, 30                      # 1,200 (a, b) segments


def _compact_case(case):
    """(columns, n_valid, weights, batched, n_nodes) of one case."""
    rng = np.random.default_rng(14)
    n = 4 * _BLOCK
    a, b = rng.integers(0, _A, n), rng.integers(0, _B, n)
    n_valid, weights = n, None
    if case == "one_segment":
        a, b = np.full(n, 7), np.full(n, 11)
    elif case == "distinct_segments":
        code = rng.permutation(_A * _B)[:n]
        a, b = code // _B, code % _B
    elif case == "padded_last_block":
        n = 3 * _BLOCK + 21
        a, b, n_valid = a[:n], b[:n], n - 9
    elif case == "ivm_weights":
        weights = rng.choice(np.float32([1.0, -1.0, 0.0]), n)
    cols = {"a": a.astype(np.int32), "b": b.astype(np.int32),
            "u": rng.normal(size=len(a)).astype(np.float32)}
    return cols, n_valid, weights, case == "batched", 3


@pytest.mark.parametrize("case", ["unbatched", "batched", "ivm_weights",
                                  "padded_last_block", "one_segment",
                                  "distinct_segments"])
def test_compact_accumulate_matches_dense(case, monkeypatch):
    import jax
    import jax.numpy as jnp

    import repro
    from repro.core.aggregates import Param
    from repro.core.lowering import xla

    cols, n_valid, weights, batched, n_nodes = _compact_case(case)
    S = schema([("a", "categorical", _A), ("b", "categorical", _B),
                ("u", "continuous", 0)], [("R", ["a", "b", "u"])])
    aggs = [COUNT, sum_of("u"), agg(Pow("u", 2))]
    params = {}
    if batched:
        aggs.append(agg(Var("u"), Delta("b", "==",
                                        Param("t", batched=True))))
        params = {"t": jnp.arange(n_nodes, dtype=jnp.float32)}
    qs = [query("q_ab", ["a", "b"], aggs), query("q_a", ["a"], [COUNT])]
    db = from_numpy(S, {"R": cols})
    plan = repro.connect(db, config=repro.ExecutionConfig(
        block_size=_BLOCK)).views(qs).compiled.plan
    (prog,) = plan.step_programs
    segs = [vp.seg.n_segments for vp in prog.views if vp.seg is not None]
    # q_ab's view passes the threshold, q_a's does not
    bound = xla.COMPACT_SEGMENTS_PER_ROW * _BLOCK
    assert max(segs) > bound and min(segs) <= bound

    def run(segments_per_row):
        monkeypatch.setattr(xla, "COMPACT_SEGMENTS_PER_ROW",
                            segments_per_row)

        def step(cols, n_valid, weights):
            arrays = {}
            xla.XlaBackend().run_step(
                prog, cols, arrays, params, n_valid=n_valid, offset=0,
                config=plan.config, n_nodes=n_nodes if batched else None,
                weights=weights)
            return arrays

        w = None if weights is None else jnp.asarray(weights)
        # n_valid is traced: the dynamic valid-row count of resident
        # relations
        out = jax.jit(step)({k: jnp.asarray(v) for k, v in cols.items()},
                            jnp.int32(n_valid), w)
        return ({vid: np.asarray(v) for vid, v in out.items()},
                xla.XlaBackend.count_compact(prog, plan.config))

    compact, n_compact = run(xla.COMPACT_SEGMENTS_PER_ROW)
    dense, n_dense = run(10 ** 9)
    assert n_compact == 1 and n_dense == 0
    assert compact.keys() == dense.keys()
    for vid in dense:
        np.testing.assert_array_equal(compact[vid], dense[vid],
                                      err_msg=f"view {vid}")

    # and both are the sums: COUNT per (a, b) against numpy
    w = np.ones(len(cols["a"])) if weights is None else weights
    w = np.where(np.arange(len(w)) < n_valid, w, 0.0)
    expect = np.zeros((_A, _B))
    np.add.at(expect, (cols["a"], cols["b"]), w)
    (vp,) = [vp for vp in prog.views if vp.group_by == ("a", "b")]
    got = dense[vp.vid][..., 0]
    got = got[0] if batched else got
    np.testing.assert_allclose(got, expect, rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# clustered steps: a relation long against its widest compact key is sorted
# by that key once, and each block of a compact group is added into one
# window of the block's rows of segments (or, when its ids span more,
# through the compact scatter).  Sums run in another order than unsorted,
# so the two agree to float tolerance.

_ROWS = 80 * _BLOCK                  # 4.3 rows a (a, b) segment
_C = 2                               # (a, b, c): a nested compact key


def _clustered_case(case):
    """(columns, n_valid, offset, weights) of one case."""
    rng = np.random.default_rng(16)
    n = _ROWS
    a, b = rng.integers(0, _A, n), rng.integers(0, _B, n)
    n_valid, offset, weights = n, 0, None
    if case == "sorted":
        code = np.sort(a * _B + b)
        a, b = code // _B, code % _B
    elif case == "zipf":
        a, b = D.zipf_codes(rng, n, _A), D.zipf_codes(rng, n, _B)
    elif case == "span_exceeds_window":
        # two far runs of segments: the block where one ends and the other
        # starts spans more than a block's rows of segments
        a = np.where(rng.random(n) < 0.5, 0, _A - 1)
    elif case == "window_at_end":
        a, b = np.full(n, _A - 1), rng.integers(_B - 10, _B, n)
    elif case == "out_of_domain":
        a = np.where(rng.random(n) < 0.05, _A + 5, a)
        a = np.where(rng.random(n) < 0.05, -1, a)
    elif case == "offset_n_valid":
        offset, n_valid = 1000, 1000 + n - 100
    elif case == "ivm_weights":
        weights = rng.choice(np.float32([1.0, -1.0, 0.0]), n)
    cols = {"a": a.astype(np.int32), "b": b.astype(np.int32),
            "c": rng.integers(0, _C, n).astype(np.int32),
            "u": rng.normal(size=n).astype(np.float32)}
    return cols, n_valid, offset, weights


def _sorted_spans(cols):
    """The id span of each block of the rows sorted by (a, b)."""
    code = np.sort(cols["a"] * _B + cols["b"]).reshape(-1, _BLOCK)
    return code.max(axis=1) - code.min(axis=1), code.min(axis=1)


@pytest.mark.parametrize("case", [
    "sorted", "shuffled", "zipf", "span_exceeds_window", "window_at_end",
    "out_of_domain", "offset_n_valid", "ivm_weights", "batched",
    "nested_keys"])
def test_clustered_accumulate_matches_unclustered(case, monkeypatch):
    import jax
    import jax.numpy as jnp

    import repro
    from repro.core.aggregates import Param
    from repro.core.lowering import xla

    cols, n_valid, offset, weights = _clustered_case(case)
    batched, n_nodes = case == "batched", 3
    S = schema([("a", "categorical", _A), ("b", "categorical", _B),
                ("c", "categorical", _C), ("u", "continuous", 0)],
               [("R", ["a", "b", "c", "u"])])
    aggs = [COUNT, sum_of("u"), agg(Pow("u", 2))]
    params = {}
    if batched:
        aggs.append(agg(Var("u"), Delta("b", "==",
                                        Param("t", batched=True))))
        params = {"t": jnp.arange(n_nodes, dtype=jnp.float32)}
    qs = [query("q_ab", ["a", "b"], aggs), query("q_a", ["a"], [COUNT])]
    if case == "nested_keys":
        qs.append(query("q_abc", ["a", "b", "c"], [COUNT, sum_of("u")]))
    in_domain = dict(cols, a=np.clip(cols["a"], 0, _A - 1))
    plan = repro.connect(from_numpy(S, {"R": in_domain}),
                         config=repro.ExecutionConfig(block_size=_BLOCK)
                         ).views(qs).compiled.plan
    (prog,) = plan.step_programs
    groups = xla.accumulator_groups(prog)
    key = xla.cluster_key(groups, _ROWS, _BLOCK)
    assert key is not None and key.attrs == ("a", "b")
    compact = [g for g in groups if xla.takes_compact(g.seg, _BLOCK)]
    assert [g.batched for g in compact] == [batched] * (
        1 + (case == "nested_keys"))
    if case == "span_exceeds_window":
        assert (_sorted_spans(cols)[0] >= _BLOCK).any()
    if case == "window_at_end":
        assert _sorted_spans(cols)[1][-1] + _BLOCK > _A * _B

    def run(rows_per_segment):
        monkeypatch.setattr(xla, "CLUSTER_ROWS_PER_SEGMENT",
                            rows_per_segment)

        def step(cols, n_valid, offset, weights):
            arrays = {}
            xla.XlaBackend().run_step(
                prog, cols, arrays, params, n_valid=n_valid, offset=offset,
                config=plan.config, n_nodes=n_nodes if batched else None,
                weights=weights)
            return arrays

        w = None if weights is None else jnp.asarray(weights)
        # n_valid and offset are traced: a resident or sharded relation's
        out = jax.jit(step)({k: jnp.asarray(v) for k, v in cols.items()},
                            jnp.int32(n_valid), jnp.int32(offset), w)
        return ({vid: np.asarray(v) for vid, v in out.items()},
                xla.XlaBackend.count_clustered(prog, plan.config, _ROWS))

    clustered, n_clustered = run(xla.CLUSTER_ROWS_PER_SEGMENT)
    unclustered, n_unclustered = run(10 ** 9)
    assert (n_clustered, n_unclustered) == (1, 0)
    assert clustered.keys() == unclustered.keys()
    for vid in unclustered:
        np.testing.assert_allclose(clustered[vid], unclustered[vid],
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"view {vid}")

    # and both are the sums: COUNT per (a, b) against numpy
    w = np.ones(_ROWS) if weights is None else weights
    w = np.where(np.arange(_ROWS) < n_valid - offset, w, 0.0)
    ok = (cols["a"] >= 0) & (cols["a"] < _A)
    expect = np.zeros((_A, _B))
    np.add.at(expect, (cols["a"][ok], cols["b"][ok]), w[ok])
    (vp,) = [vp for vp in prog.views if vp.group_by == ("a", "b")]
    got = clustered[vp.vid][..., 0]
    got = got[0] if batched else got
    np.testing.assert_allclose(got, expect, rtol=0, atol=1e-6)


def _cell_batch(name):
    """The benchmark cell ``name``'s covar batch, compiled to a plan at its
    configuration's full size over shapes (no data), and the sizes."""
    import os
    import sys

    import jax
    import jax.numpy as jnp

    import repro
    from repro.core.schema import schema as make_schema
    from repro.data.datasets import Dataset
    from repro.data.relations import Database, Relation
    from repro.ml.covar import covar_queries

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import run as bench_run

    cfg = bench_run.Cell(name).cfg
    S = make_schema([tuple(a) for a in cfg.spec["attributes"]],
                    [(r, cfg.attrs[r]) for r in cfg.relations])
    cols = {r: {a: jax.ShapeDtypeStruct(
        (cfg.n_rows(r),), jnp.float32 if cfg.kinds[a] == "continuous"
        else jnp.int32) for a in cfg.attrs[r]} for r in cfg.relations}
    data = Database(S, {r: Relation(r, c) for r, c in cols.items()})
    ds = Dataset(cfg.name, S, {}, [tuple(e) for e in cfg.edges],
                 cfg.features_cont, cfg.features_cat, cfg.label, cfg.fact,
                 _db=data)
    qs, _ = covar_queries(ds)
    return repro.connect(ds).views(qs).compiled, data.sizes(), cfg.fact


@pytest.mark.parametrize("cell, key, short", [
    ("favorita.ridge", ("date", "store"), ["Items", "Transactions"]),
    ("retailer.ridge", ("date", "locn"), ["Items", "Weather"])])
def test_cluster_rule_picks_the_fact_scan(cell, key, short):
    """At the benchmark's full sizes the shape rule sorts exactly the first
    fact scan, by its widest compact key; no other step (those on the
    ``short`` relations have compact groups, but few rows against their
    keys) and no delta scan of a 0.1% update to the fact."""
    from repro.core.ivm import MaintainedBatch
    from repro.core.lowering import xla
    from repro.data.relations import next_pow2

    batch, sizes, fact = _cell_batch(cell)
    plan = batch.plan
    assert batch.stats.n_clustered_steps == 1
    assert "clustered=1" in batch.stats.summary()
    sorted_steps = [p for p in plan.step_programs
                    if xla.XlaBackend.count_clustered(p, plan.config,
                                                      sizes[p.rel])]
    assert [p.rel for p in sorted_steps] == [fact]
    (prog,) = sorted_steps
    assert prog is [p for p in plan.step_programs if p.rel == fact][0]
    groups = xla.accumulator_groups(prog)
    assert xla.cluster_key(groups, sizes[fact], 4096).attrs == key
    assert sorted({p.rel for p in plan.step_programs if p is not prog
                   and any(xla.takes_compact(g.seg, 4096)
                           for g in xla.accumulator_groups(p))}) == short

    delta = MaintainedBatch(batch).delta_program(fact)
    n_delta = next_pow2(sizes[fact] // 1000)
    scans = [st for st in delta.steps if st.scans_delta]
    assert scans
    assert not any(xla.XlaBackend.count_clustered(st.prog, plan.config,
                                                  n_delta) for st in scans)


# ---------------------------------------------------------------------------
# grouped accumulators: a step's views on one segment key (and node axis)
# share one accumulator, one partial and one accumulate per block

_NODES = np.int32([3, 7, 11])         # the batched param, one per node


def _grouped_case():
    """A two-relation batch rooted at the fact ``F`` whose ``F`` step mixes
    every kind of group: two compact views on (a, b), one with a pulled
    dimension; two dense views on (a); a pulled-only and a scalar view; a
    batched and an unbatched compact group on (a, b, k)."""
    import warnings

    S = schema([("a", "categorical", 40), ("b", "categorical", 30),
                ("k", "key", 6), ("c", "categorical", 5),
                ("u", "continuous", 0), ("v", "continuous", 0)],
               [("F", ["a", "b", "k", "u"]), ("D", ["k", "c", "v"])])
    rng = np.random.default_rng(15)
    n = 4 * _BLOCK + 23
    T = {"F": {"a": rng.integers(0, 40, n), "b": rng.integers(0, 30, n),
               "k": rng.integers(0, 6, n),
               "u": rng.normal(size=n).astype(np.float32)},
         "D": {"k": np.arange(6), "c": rng.integers(0, 5, 6),
               "v": rng.normal(size=6).astype(np.float32)}}
    t = Param("t", batched=True)
    qs = [query("q_ab", ["a", "b"], [COUNT, sum_of("u")]),
          query("q_abc", ["a", "b", "c"], [COUNT, agg(Var("u"), Var("v"))]),
          query("q_a", ["a"], [COUNT, agg(Pow("u", 2))]),
          query("q_ac", ["a", "c"], [sum_of("v")]),
          query("q_c", ["c"], [sum_of("u")]),
          query("q_all", [], [COUNT, sum_of("v")]),
          query("q_abk", ["a", "b", "k"], [agg(Var("u"), Delta("b", "==", t))]),
          query("q_abkc", ["a", "b", "k", "c"], [COUNT])]
    db = from_numpy(S, T)
    cols = {r: dict(rel.columns) for r, rel in db.relations.items()}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        batch = Engine(S, sizes=db.sizes()).compile(
            qs, block_size=_BLOCK, root_override={q.name: "F" for q in qs})
    return S, T, db, cols, qs, batch


def _oracle(S, T, q, weights):
    """``q`` over the materialized join, each row weighted by its fact
    row's weight; a leading node axis for the batched query."""
    from repro.core.plan import materialize_join

    tables = {r: dict(c) for r, c in T.items()}
    tables["F"]["row"] = np.arange(len(T["F"]["a"]))
    J = materialize_join(S, tables, order=["F", "D"])
    w = weights[J["row"]]
    batched = any(p.batched for a in q.aggregates for pr in a.products
                  for term in pr.terms for p in term.params())
    out = []
    for t in (_NODES if batched else [None]):
        cols = []
        for a in q.aggregates:
            val = np.zeros(len(w))
            for prod in a.products:
                v = np.ones(len(w))
                for term in prod.terms:
                    env = {at: J[at] for at in term.attrs()}
                    v = v * np.asarray(term.evaluate(env, {"t": t}),
                                       dtype=np.float64)
                val += v
            val = val * w
            if q.group_by:
                o = np.zeros([S.domain(g) for g in q.group_by])
                np.add.at(o, tuple(J[g] for g in q.group_by), val)
            else:
                o = val.sum()
            cols.append(o)
        out.append(np.stack(cols, axis=-1))
    return out[0] if len(out) == 1 else np.stack(out)


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "ivm_weights"])
def test_grouped_accumulate_matches_oracle(weighted, monkeypatch):
    """Every view of a step mixing compact, dense, scalar, pulled-only and
    batched groups, several views on one key, gives the join's sums; with
    IVM delta weights (±1, 0) on the fact's rows, the weighted sums."""
    import jax
    import jax.numpy as jnp

    from repro.core.lowering import xla

    S, T, db, cols, qs, batch = _grouped_case()
    n = len(T["F"]["a"])
    weights = (np.random.default_rng(16).choice(
        np.float32([1.0, -1.0, 0.0]), n) if weighted
        else np.ones(n, np.float32))
    if weighted:
        orig = xla.XlaBackend.run_step

        def run_step(self, prog, rel_cols, *args, **kw):
            if prog.rel == "F":
                kw["weights"] = jnp.asarray(weights)
            return orig(self, prog, rel_cols, *args, **kw)

        monkeypatch.setattr(xla.XlaBackend, "run_step", run_step)

    run = batch.plan.bind(db.sizes(), n_nodes=len(_NODES))
    out = jax.jit(run)(cols, {"t": jnp.asarray(_NODES)})
    for q in qs:
        np.testing.assert_allclose(np.asarray(out[q.name], np.float64),
                                   _oracle(S, T, q, weights),
                                   rtol=1e-5, atol=1e-4, err_msg=q.name)


def test_one_scatter_per_accumulator_group():
    """The ``F`` step's lowered HLO scatters onto each segmented group's
    ``(N?, n_segments, width)`` shape once, and onto no single view's:
    the dense groups' ``segment_sum`` and the compact groups' scatter into
    the accumulator.  ``n_accumulators`` counts the groups over steps;
    ``n_compact_views`` still counts views."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.core.lowering import xla

    _, _, db, cols, _, batch = _grouped_case()
    plan = batch.plan
    (prog,) = [p for p in plan.step_programs if p.rel == "F"]
    groups = xla.accumulator_groups(prog)
    shapes = [((g.seg is not None, g.batched,
                tuple(len(vp.pulled) > 0 for vp in g.views)))
              for g in groups]
    assert sorted(shapes) == sorted([
        (True, False, (False, True)),          # (a, b): two views
        (True, False, (False, True)),          # (a): two views
        (False, False, (True, False)),         # pulled-only and scalar
        (True, True, (False,)),                # (a, b, k), batched
        (True, False, (True,))])               # (a, b, k, c)
    compact = {g.seg.attrs: xla.takes_compact(g.seg, _BLOCK)
               for g in groups if g.seg is not None}
    assert compact == {("a", "b"): True, ("a",): False,
                       ("a", "b", "k"): True}

    N = len(_NODES)

    def step(cols, params):
        arrays = {}
        for p in plan.step_programs:
            xla.XlaBackend().run_step(
                p, cols[p.rel], arrays, params, n_valid=db.sizes()[p.rel],
                offset=0, config=plan.config, n_nodes=N)
        return arrays

    hlo = jax.jit(step).lower(cols, {
        "t": jnp.asarray(_NODES)}).compiler_ir("hlo").as_hlo_text()
    scattered = [tuple(int(d) for d in m.split(","))
                 for m in re.findall(r"= f32\[([\d,]+)\]\S* scatter\(", hlo)]
    for g in groups:
        if g.seg is None:
            continue
        lead = (N,) if g.batched else ()
        assert scattered.count(lead + (g.seg.n_segments, g.width)) == 1
        if len(g.views) > 1:
            for vp in g.views:
                w = xla._view_width(vp)
                assert lead + (g.seg.n_segments, w) not in scattered
    segs = {g.seg.n_segments for g in groups if g.seg is not None}
    onto_keys = [s for s in scattered if s[0] in segs
                 or (len(s) == 3 and s[0] == N and s[1] in segs)]
    assert len(onto_keys) == sum(g.seg is not None for g in groups)

    stats = batch.stats
    assert stats.n_accumulators == sum(
        len(xla.accumulator_groups(p)) for p in plan.step_programs)
    assert stats.n_accumulators == 2 + len(groups)   # D: (k) and (k, c)
    assert stats.n_compact_views == 4
    assert f"accumulators={stats.n_accumulators}" in stats.summary()
    # compact, but 279 rows against 1,200 (a, b) segments: no sort
    assert stats.n_clustered_steps == 0
    assert "clustered=0" in stats.summary()
