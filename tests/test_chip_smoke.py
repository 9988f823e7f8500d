"""``chip_smoke.py`` on the CPU: its phases at a small size, its sharded
phase on four virtual devices, and its refusal of a machine without a TPU.

The phases check themselves against the script's numpy reference and raise
on a mismatch, so a phase that returns has passed its checks.
"""

import importlib.util
import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")
FACT_ROWS = 60_000


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_pass_on_cpu(smoke, capsys):
    """Phases 2–6 at 60k fact rows, Pallas in interpret mode."""
    smoke.run_all(FACT_ROWS, seed=0, interpret=True)
    out = capsys.readouterr().out
    for name in ("covar[xla] vs numpy", "covar[pallas] vs numpy",
                 "covar[pallas] vs covar[xla]", "maintained epoch 3 vs numpy",
                 "query units_by_family vs numpy",
                 "query units_by_city_family vs numpy"):
        assert f"check {name}:" in out
    assert "FAIL" not in out
    assert "pallas interpret=True" in out
    assert "tier subsumed" in out and "tier compiled" in out


def test_smoke_reference_matches_row_level_sums(smoke):
    """The cell-level reference equals direct per-row numpy sums."""
    from repro.data import datasets as D

    ds = D.make_favorita(scale=0.05, seed=3)
    ref = smoke.Reference(ds, ds.tables[ds.fact])
    sales = ds.tables["Sales"]
    city = ds.tables["Stores"]["city"][sales["store"]]
    txns = ds.tables["Transactions"]["txns"][
        sales["date"] * ds.schema.domain("store") + sales["store"]]
    units = sales["units"].astype(np.float64)
    want = np.bincount(city, units * txns, ds.schema.domain("city"))
    got = np.bincount(ref.vals["city"], ref.moment("txns", "units"),
                      ds.schema.domain("city"))
    np.testing.assert_allclose(got, want, rtol=1e-12)
    np.testing.assert_allclose(ref.grouped(["city"], "units"),
                               np.bincount(city, units,
                                           ds.schema.domain("city")),
                               rtol=1e-12)


def test_smoke_check_rejects_a_wrong_answer(smoke):
    want = np.array([1.0e6, 2.0])
    smoke.check("ok", want * (1 + 1e-7), want, want, 1e-6)
    with pytest.raises(smoke.CheckFailed):
        smoke.check("off", want * (1 + 1e-5), want, want, 1e-6)


def test_smoke_sharded_phase_on_four_host_devices(subproc):
    """The ``--chips 4`` path on four forced host devices, in a fresh
    process (the device count is fixed when JAX starts)."""
    out = subproc(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        ds = smoke.make_data({FACT_ROWS // 4}, seed=0)
        smoke.phase_sharded(ds, 4, seed=0)
        print("SHARDED-OK")
        """, 4)
    assert "SHARDED-OK" in out
    assert "shard: devices=4" in out
    assert "FAIL" not in out


def test_smoke_main_refuses_a_cpu_device(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main(["--fact-rows", "1000"])
    assert e.value.code != 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out
