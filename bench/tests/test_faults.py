"""A run with the timed path broken underneath must come out not correct:
one case for each fault a cell can have."""

import jax
import pytest

from conftest import run_cell


def _half_rows(monkeypatch):
    """Every scan leaves out half of its rows."""
    from repro.core.lowering.xla import XlaBackend

    orig = XlaBackend.run_step

    def run_step(self, prog, rel_cols, arrays, params, *, n_valid, **kw):
        return orig(self, prog, rel_cols, arrays, params,
                    n_valid=n_valid // 2, **kw)

    monkeypatch.setattr(XlaBackend, "run_step", run_step)


def _altered_answer(monkeypatch):
    """One entry of the batch's first output is off by one, inside the
    compiled program."""
    from repro.core.plan import ExecutablePlan

    orig = ExecutablePlan.bind

    def bind(self, n_rows, n_nodes=None):
        run = orig(self, n_rows, n_nodes)

        def altered(*args, **kw):
            out = dict(run(*args, **kw))
            k = sorted(out)[0]
            out[k] = out[k].at[(0,) * out[k].ndim].add(1.0)
            return out

        return altered

    monkeypatch.setattr(ExecutablePlan, "bind", bind)


def _solver_unmoved(monkeypatch):
    """The solver returns the model it started from."""
    from repro.ml import ridge

    orig = ridge.bgd
    monkeypatch.setattr(ridge, "bgd", lambda C, N, layout, **kw: orig(
        C, N, layout, **dict(kw, max_iters=0)))


@pytest.mark.parametrize("name", ["favorita.ridge", "retailer.ridge"])
@pytest.mark.parametrize("fault", [_half_rows, _altered_answer,
                                   _solver_unmoved])
def test_fault_is_not_correct(monkeypatch, name, fault):
    fault(monkeypatch)
    jax.clear_caches()
    r = run_cell(name, seconds=0.5)
    assert not r["correct"], r["checks"]
