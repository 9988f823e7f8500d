"""Pallas TPU kernels for the compute hot-spots (DESIGN.md §2).

covar_xtx       masked blocked XtX (the covar-matrix batch on the MXU)
seg_aggregate   multi-aggregate segment reduction (the MOO scan)
tree_hist       fused decision-tree node histogram (RT-node workload)

Each kernel: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit wrappers,
padding, interpret switch), ref.py (pure-jnp oracles).
"""

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
