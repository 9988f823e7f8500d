"""Checkpoint store (``checkpoint/store.py``) over maintained view state:
a snapshot round-trips bit-exactly into a fresh batch, a corrupted leaf is
refused at restore, and old steps are collected past ``keep``."""

import os

import jax
import numpy as np
import pytest

import repro
from repro.checkpoint import store
from repro.core import COUNT, agg, Pow, query, schema, sum_of
from repro.data import DeltaBatchUpdate, from_numpy

S = schema([("k", "key", 6), ("c", "categorical", 4), ("u", "continuous", 0)],
           [("F", ["k", "u"]), ("D", ["k", "c"])])
QUERIES = [query("total", [], [COUNT, sum_of("u")]),
           query("by_c", ["c"], [COUNT, sum_of("u"), agg(Pow("u", 2))])]


def maintained(seed=0):
    """A maintained batch over a small two-relation star (not initialized)."""
    rng = np.random.default_rng(seed)
    n = 41
    db = from_numpy(S, {
        "F": {"k": rng.integers(0, 6, n),
              "u": rng.normal(size=n).astype(np.float32)},
        "D": {"k": np.arange(6), "c": rng.integers(0, 4, 6)}})
    session = repro.connect(db, config=repro.ExecutionConfig(block_size=8))
    return session.views(QUERIES, maintain=True).maintained, db


def tick(mb, rng):
    """One insert-and-delete update on the fact relation."""
    upd = (DeltaBatchUpdate()
           .insert("F", {"k": rng.integers(0, 6, 3),
                         "u": rng.normal(size=3).astype(np.float32)})
           .delete("F", np.array([0])))
    mb.apply(upd)


def test_checkpoint_roundtrip(tmp_path):
    mb, db = maintained()
    mb.init(db)
    tick(mb, np.random.default_rng(1))
    path = store.save_view_state(str(tmp_path), mb)
    assert os.path.exists(os.path.join(path, "manifest.json"))

    fresh, _ = maintained()
    assert store.restore_view_state(str(tmp_path), fresh) == 1
    want, got = mb.snapshot_state(), fresh.snapshot_state()
    assert (jax.tree_util.tree_structure(want)
            == jax.tree_util.tree_structure(got))
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for q in QUERIES:
        np.testing.assert_array_equal(np.asarray(fresh.results()[q.name]),
                                      np.asarray(mb.results()[q.name]))


def test_checkpoint_corruption_detected(tmp_path):
    mb, db = maintained()
    mb.init(db)
    tick(mb, np.random.default_rng(2))
    store.save_view_state(str(tmp_path), mb)
    d = os.path.join(str(tmp_path), "step_00000001")
    victim = sorted(f for f in os.listdir(d) if f.startswith("views")
                    and f.endswith(".npy"))[0]
    arr = np.load(os.path.join(d, victim))
    np.save(os.path.join(d, victim), arr + 1.0)
    fresh, _ = maintained()
    with pytest.raises(IOError):
        store.restore_view_state(str(tmp_path), fresh)


def test_checkpoint_gc_keeps_last_k(tmp_path):
    mb, db = maintained()
    mb.init(db)
    rng = np.random.default_rng(3)
    for _ in range(5):
        tick(mb, rng)
        store.save_view_state(str(tmp_path), mb, keep=2)
    steps = sorted(d for d in os.listdir(str(tmp_path))
                   if d.startswith("step_"))
    assert steps == ["step_00000004", "step_00000005"]
    assert store.latest_step(str(tmp_path)) == 5
