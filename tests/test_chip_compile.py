"""Compile the engine's kernels for a described v5e chip at deployment widths.

Interpret-mode tests cannot show a VMEM overflow or a tile misalignment;
the TPU compiler, which is installed even where no chip is attached, can.
The spec sets are the fused scan steps of the Favorita covar batch
(``(seg 54×88)``, ``(seg 33×6, 990×1, …)``, ``(seg 18036×79, 36072×1)``,
where 18,036 is 334 dates × 54 stores) and of a regression-tree batch, at
65,536 rows and the default ``block_rows=512``.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_scan import ReduceSpec, fused_scan_block_pallas
from repro.kernels.seg_aggregate import seg_aggregate_pallas
from repro.kernels.tree_hist import tree_hist_batched_pallas

N_ROWS = 65_536
BLOCK_ROWS = 512


def _seg_specs(pairs):
    """Seg reductions with consecutive code columns and payload offsets."""
    specs, off = [], 0
    for col, (n_seg, width) in enumerate(pairs):
        specs.append(ReduceSpec("seg", col, n_seg, width, off))
        off += width
    return tuple(specs), len(pairs), off


def _tree_specs():
    """One (date, store) seg reduction plus three single-node hists sharing
    one ``[1, y, y²]`` triple."""
    specs = [ReduceSpec("seg", 0, 18036, 3, 0)]
    conds, off = [], 3
    for col, n_buckets in enumerate((2, 334, 40), start=1):
        conds.append((col, off, n_buckets))
        off += 1
    yk_off = off
    specs += [ReduceSpec("hist", col, n_buckets, 3, pay_off, n_cond=1,
                         yk_off=yk_off) for col, pay_off, n_buckets in conds]
    return tuple(specs), 1 + len(conds), yk_off + 3


SPEC_SETS = {
    "seg54x88": _seg_specs([(54, 88)]),
    "seg33x6_990_66_30x6_60_2x6": _seg_specs(
        [(33, 6), (990, 1), (66, 1), (30, 6), (60, 1), (2, 6)]),
    "seg18036x79_36072": _seg_specs([(18036, 79), (36072, 1)]),
    "seg18036x3_hist2_334_40": _tree_specs(),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the persistent
    # cache without the chip, so keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("double_buffer", [True, False])
@pytest.mark.parametrize("spec_set", sorted(SPEC_SETS))
def test_fused_scan_block_compiles_for_v5e(one_chip, spec_set, double_buffer):
    specs, n_codes, n_fpay = SPEC_SETS[spec_set]
    fn = functools.partial(fused_scan_block_pallas, specs=specs,
                           block_rows=BLOCK_ROWS, double_buffer=double_buffer)
    _compile(fn, one_chip, ((N_ROWS, n_codes), jnp.int32),
             ((N_ROWS, n_fpay), jnp.float32))


def test_seg_aggregate_compiles_for_v5e_at_wide_segments(one_chip):
    fn = functools.partial(seg_aggregate_pallas, n_segments=21_780,
                           block_rows=BLOCK_ROWS)
    _compile(fn, one_chip, ((N_ROWS,), jnp.int32), ((N_ROWS, 4), jnp.float32))


def test_tree_hist_batched_compiles_for_v5e(one_chip):
    fn = functools.partial(tree_hist_batched_pallas, n_buckets=20,
                           block_rows=BLOCK_ROWS)
    _compile(fn, one_chip, ((N_ROWS,), jnp.int32), ((N_ROWS,), jnp.float32),
             ((N_ROWS, 16), jnp.float32))
