"""Domain parallelism for the aggregate engine (paper Fig. 1 layer 7).

LMFAO partitions the largest relation across threads and merges per-thread
view hashmaps.  On a TPU mesh we partition the relation's rows across the
``data`` axis with ``shard_map``; each device runs the same fused scan steps
(the scheduler's shared-scan schedule, DESIGN.md §4/§6) on its row shard and
the (small, dense) view tensors are ``psum``-combined immediately after their
step — the collective-friendly direction, since views are orders of magnitude
smaller than fact tables (paper Table 2).  Fusion is sound under sharding
because a view is psum'd before any later step gathers it.
"""

from __future__ import annotations

import functools
from typing import Dict, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.plan import ExecutablePlan, _ceil_to


def shard_columns(db, mesh: Mesh, axis: str, shard_rel: str):
    """Pad the sharded relation to a multiple of the axis size and place
    every relation on the mesh — ``shard_rel`` row-partitioned, the rest
    replicated — so a run reads each device's rows where they live.
    Returns the per-relation column pytree and its sharding specs."""
    ndev = mesh.shape[axis]
    cols = {}
    specs = {}
    for name, rel in db.relations.items():
        if name == shard_rel:
            n = rel.n_rows
            n_pad = _ceil_to(max(n, 1), ndev)
            cols[name] = {a: put_sharded(jnp.pad(v, (0, n_pad - n))
                                         if n_pad > n else v, mesh, axis)
                          for a, v in rel.columns.items()}
            specs[name] = {a: P(axis) for a in rel.columns}
        else:
            cols[name] = {a: put_replicated(v, mesh)
                          for a, v in rel.columns.items()}
            specs[name] = {a: P() for a in rel.columns}
    return cols, specs


def sharded_runner(plan: ExecutablePlan, db, mesh: Mesh, axis: str, shard_rel: str,
                   n_nodes=None):
    """Build a jitted shard_map runner. Returns (fn, cols).  ``n_nodes`` is
    the param-batch (node) axis size for plans with batched params
    (DESIGN.md §7.4); batched view tensors psum with the node axis intact."""
    ndev = mesh.shape[axis]
    n_rows = db.sizes()
    cols, specs = shard_columns(db, mesh, axis, shard_rel)
    run = plan.bind(n_rows, n_nodes=n_nodes)
    rows_per_shard = int(next(iter(cols[shard_rel].values())).shape[0]) // ndev

    def local(columns, params):
        off = jax.lax.axis_index(axis).astype(jnp.int32) * rows_per_shard
        return run(columns, params,
                   offsets={shard_rel: off},
                   psum_axes={shard_rel: axis})

    in_specs = (specs, P())
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=P(),
                       check_vma=False)
    return jax.jit(fn), cols


def lower_sharded(plan: ExecutablePlan, db, mesh: Mesh, axis: str, shard_rel: str):
    """Dry-run lowering of the sharded aggregate batch (no execution)."""
    fn, cols = sharded_runner(plan, db, mesh, axis, shard_rel)
    spec_cols = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cols)
    return fn.lower(spec_cols, {})


# ------------------------------------------------------------------ sharded IVM
# Building blocks for the sharded delta tick (core/ivm.py, DESIGN.md §8).
# Update staging is explicit device_put (allowed under the transfer guard);
# the per-shard delete/advance helpers run *inside* the tick's shard_map.

# Delete batches are padded with a gid no live row can hold.
GID_SENTINEL = np.iinfo(np.int32).max


def put_replicated(arr, mesh: Mesh):
    """Explicitly place a host array replicated across the mesh."""
    return jax.device_put(arr, NamedSharding(mesh, P()))


def put_sharded(arr, mesh: Mesh, axis: str):
    """Explicitly place a host array row-sharded over ``axis`` (leading dim
    must be a multiple of the axis size)."""
    return jax.device_put(arr, NamedSharding(mesh, P(axis)))


def strided_insert_layout(block: int, ndev: int):
    """Host permutation laying a padded insert batch out so that global
    insert rank ``j`` lands on shard ``j % ndev`` at local slot ``j // ndev``
    — round-robin keeps shards balanced under any update pattern, and shard
    ``s``'s valid inserts are the first ``ceil((n_ins - s) / ndev)`` rows of
    its contiguous block."""
    return np.arange(block * ndev).reshape(block, ndev).T.reshape(-1)


def local_insert_count(n_ins, shard, ndev: int, block: int):
    """Valid inserts owned by ``shard`` under the strided layout (traced)."""
    return jnp.clip((n_ins - shard + ndev - 1) // ndev, 0, block).astype(jnp.int32)


def local_delete(gids, live, del_gids, del_pad: int, capacity: int):
    """Route a replicated, sorted, sentinel-padded global delete batch to the
    rows this shard owns, by matching oracle positions (gids).

    Returns ``(hit, slots, n_del_local)``: a boolean mask over the shard's
    rows, the (sorted, ``del_pad``-sized, ``capacity``-filled) local slot
    indices of deleted rows, and their count.  All static-shape, so the
    delete batch size only enters the jit cache through its pow2 pad."""
    pos = jnp.searchsorted(del_gids, gids).astype(jnp.int32)
    match = jnp.take(del_gids, pos, mode="clip") == gids
    hit = match & (pos < del_pad) & live
    slots = jnp.nonzero(hit, size=del_pad, fill_value=capacity)[0].astype(jnp.int32)
    return hit, slots, jnp.sum(hit).astype(jnp.int32)


def local_advance(buffers, gids, n_valid, hit, del_gids, ins, gid_base,
                  shard, ndev: int, ins_block: int, n_ins_local, n_del_local,
                  *, compact: bool):
    """Shard-local epoch advance: compact deleted rows out (stable argsort,
    mirroring ``_resident_advance``), renumber surviving gids to the oracle's
    post-delete positions (``gid' = gid - #deleted_gids < gid``), then append
    this shard's insert block with fresh trailing gids
    ``gid_base + shard + ndev * arange`` (round-robin, matching the strided
    insert layout).  Everything indexes within the shard — no collectives."""
    cap = gids.shape[0]
    rows = jnp.arange(cap, dtype=jnp.int32)
    live = rows < n_valid
    if compact:
        gids = gids - jnp.searchsorted(del_gids, gids).astype(jnp.int32)
        order = jnp.argsort(hit | ~live)
        buffers = {a: c[order] for a, c in buffers.items()}
        gids = gids[order]
    n_after = n_valid - n_del_local
    if ins_block:
        pos = n_after + jnp.arange(ins_block, dtype=jnp.int32)
        pos = jnp.where(jnp.arange(ins_block) < n_ins_local, pos, cap)
        buffers = {a: c.at[pos].set(ins[a].astype(c.dtype), mode="drop")
                   for a, c in buffers.items()}
        new_gid = (gid_base + shard + ndev * jnp.arange(ins_block)).astype(jnp.int32)
        gids = gids.at[pos].set(new_gid, mode="drop")
    return buffers, gids, (n_after + n_ins_local).astype(jnp.int32)
