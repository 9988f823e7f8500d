"""Pallas TPU kernel: whole-step fused scan-block reduction.

One scheduler step may carry several *kernel-level* reductions per row block:
one segment sum per distinct local group-by key (bucket) plus one tree
histogram per histogram-pattern view.  Launching them separately re-reads
the row block from HBM once per reduction; this kernel fuses the **union of a
step's view buckets** into a single launch — every reduction is a one-hot
matmul against the same VMEM-resident row block, so the block is read once
and the MXU runs back-to-back contractions (DESIGN.md §10).  The single-
reduction kernels (``seg_aggregate``, ``tree_hist``, ``tree_hist_batched``)
are this kernel with one :class:`ReduceSpec`.

Inputs are packed by the lowering backend into two arrays:

  * ``codes``  (n, C) int32 — one column per reduction: the flattened
    segment id (bucket reductions) or the histogram bucket code (hist
    reductions);
  * ``fpay``   (n, W) f32  — all float payloads concatenated: bucket view
    payloads, the ``[1, y, y²]`` triples, and hist cond masks.  Static
    :class:`ReduceSpec` offsets say which slice belongs to whom, so the
    kernel never materializes a hist payload in HBM — ``cond ⊗ [1,y,y²]`` is
    formed in VMEM.

Each reduction ``r`` returns its own ``(n_segments_r, width_r)`` array.

Layout.  The kernel sees both inputs transposed, rows on the lane axis
(``(C, n)``, ``(W, n)``): C and W are a handful of columns, so a row-major
block would fill 1–6 of a vreg's 128 lanes and a DMA window of it cannot be
tiled.  Accumulators are kept as ``(width, segments)`` so that segments, the
long axis, are lane-dense too.

Segment tiles.  A one-hot over every segment of a wide view does not fit
VMEM (18,036 date×store segments × 512 rows is 37 MB in f32).  The grid's
leading axis walks segment tiles: tile ``t`` one-hots only segments
``[t·T, (t+1)·T)`` of each reduction and owns that slice of its output.
``T`` is the largest multiple of 128 whose buffers, counted from the shapes
(:func:`vmem_bytes`), fit :data:`VMEM_BUDGET_BYTES`.  Reductions with fewer
segments than ``T`` use one tile of their lane-rounded width and sit out
later tiles; the padding segments are dropped on return.

Precision.  The one-hot is exact in bf16, so the payload is split into
three bf16 terms whose sum is the f32 value (:func:`_split_bf16`) and each
term runs one single-pass bf16 matmul with f32 accumulation: the products
are exact, and the result does not depend on the default precision Mosaic
picks for an f32 contraction.

Two execution strategies (bit-identical):

  * **grid pipeline** (``double_buffer=False``): a ``(tiles, row blocks)``
    grid — the compiler's automatic pipelining streams row blocks;
  * **manual double buffering** (``double_buffer=True``): inputs stay in
    HBM (``memory_space=ANY``) and the kernel drives its own two-slot
    HBM→VMEM DMA pipeline over the row blocks of each tile — the copy of
    block ``i+1`` is started *before* the compute on block ``i``, so the
    MXU contractions overlap the next block's loads (DESIGN.md §10).

Row counts pad to a ``block_rows`` multiple with zeroed payload/cond (padded
rows contribute nothing — validity is already folded into the payloads by
``lowering/common.view_payload``), so any ``n`` works.  On the chip
``block_rows`` is a multiple of 128 (the lane tile).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.padding import pad_dim as _pad_dim
from repro.kernels.padding import pad_rows as _pad_rows

#: scoped VMEM the kernel asks Mosaic for (the v4/v5e default scoped limit)
VMEM_LIMIT_BYTES = 16 * 2**20
#: what the counted buffers may take of it; the rest is headroom for the
#: compiler's own scratch, which :func:`vmem_bytes` does not see
VMEM_BUDGET_BYTES = 12 * 2**20
_LANES = 128


@dataclasses.dataclass(frozen=True)
class ReduceSpec:
    """One fused reduction: ``kind`` "seg" sums ``fpay[:, pay_off:pay_off +
    width]`` into ``n_segments`` rows keyed by ``codes[:, code_col]``;
    ``kind`` "hist" builds the payload ``cond ⊗ [1, y, y²]`` in VMEM from
    ``n_cond`` mask columns at ``pay_off`` and the y-triple at ``yk_off``
    (output width is ``n_cond * 3``)."""

    kind: str
    code_col: int
    n_segments: int
    width: int
    pay_off: int
    n_cond: int = 0
    yk_off: int = 0

    def __post_init__(self):
        assert self.kind in ("seg", "hist"), self.kind
        if self.kind == "hist":
            assert self.width == self.n_cond * 3, (self.width, self.n_cond)


def _ceil(n: int, m: int) -> int:
    return -(-n // m) * m


def _tile_of(sp: ReduceSpec, tile: int) -> Tuple[int, int]:
    """(tile width, tile count) of one reduction under segment tile
    ``tile``: a reduction that fits one tile takes its width rounded up to
    whole lanes (Mosaic lowers narrower one-hot contractions through
    mixed-dtype broadcasts it then rejects)."""
    if sp.n_segments <= tile:
        return _ceil(sp.n_segments, _LANES), 1
    return tile, -(-sp.n_segments // tile)


def _out_rows(sp: ReduceSpec) -> Tuple[int, ...]:
    """Leading dims of a reduction's kernel output block: ``(width,)`` for
    seg, ``(3, n_cond)`` for hist (one slab per ``[1, y, y²]`` entry)."""
    return (sp.width,) if sp.kind == "seg" else (3, sp.n_cond)


def vmem_bytes(specs: Tuple[ReduceSpec, ...], n_codes: int, n_fpay: int,
               block_rows: int, tile: int) -> int:
    """VMEM the kernel's buffers take at segment tile ``tile``: both input
    row blocks (two slots each), every output tile (two pipeline buffers
    each), and the one-hot temporaries of the widest tile (int32 iota,
    compare mask, bf16 one-hot)."""
    sub = lambda r: _ceil(max(r, 1), 8)
    total = 2 * (sub(n_codes) + sub(n_fpay)) * block_rows * 4
    temp = 0
    for sp in specs:
        t, _ = _tile_of(sp, tile)
        rows = _out_rows(sp)
        slabs = rows[0] if len(rows) == 2 else 1
        total += 2 * slabs * sub(rows[-1]) * t * 4
        temp = max(temp, sub(t) * block_rows * (4 + 4 + 2))
    return total + temp


def segment_tile(specs: Tuple[ReduceSpec, ...], n_codes: int, n_fpay: int,
                 block_rows: int) -> int:
    """Largest segment tile (a multiple of 128) whose buffers fit
    :data:`VMEM_BUDGET_BYTES`."""
    tile = _ceil(max(sp.n_segments for sp in specs), _LANES)
    while (tile > _LANES and vmem_bytes(specs, n_codes, n_fpay, block_rows,
                                        tile) > VMEM_BUDGET_BYTES):
        tile -= _LANES
    need = vmem_bytes(specs, n_codes, n_fpay, block_rows, tile)
    if need > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"fused scan block needs {need} bytes of VMEM at the smallest "
            f"segment tile ({tile}) with block_rows={block_rows}; the budget "
            f"is {VMEM_BUDGET_BYTES} (lower block_rows or split the step)")
    return tile


def _split_bf16(x):
    """Three bf16 terms whose f32 sum is ``x`` (8 significand bits each)."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _onehot_dot(pay, onehot):
    """``pay (m, bm) f32 · onehot (t, bm)ᵀ -> (m, t) f32``, exact products."""
    out = None
    for part in _split_bf16(pay):
        d = jax.lax.dot_general(part, onehot, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        out = d if out is None else out + d
    return out


def _accumulate(specs, tiles, t, codes_ref, fpay_ref, o_refs):
    """Add one row block's contribution to each reduction's tile ``t``;
    ``codes_ref`` (C, bm) and ``fpay_ref`` (W, bm) are the block in VMEM."""
    bm = codes_ref.shape[1]
    for sp, (tw, nt), o in zip(specs, tiles, o_refs):

        def add(sp=sp, tw=tw, o=o):
            code = codes_ref[pl.ds(sp.code_col, 1), :]             # (1, bm)
            seg = t * tw + jax.lax.broadcasted_iota(jnp.int32, (tw, bm), 0)
            onehot = (seg == code).astype(jnp.bfloat16)            # (tw, bm)
            if sp.kind == "seg":
                pay = fpay_ref[pl.ds(sp.pay_off, sp.width), :]
                o[...] += _onehot_dot(pay, onehot)
                return
            cond = fpay_ref[pl.ds(sp.pay_off, sp.n_cond), :]
            for k in range(3):     # payload cond ⊗ yk[k], formed in VMEM
                yk = fpay_ref[pl.ds(sp.yk_off + k, 1), :]
                o[k] += _onehot_dot(cond * yk, onehot)

        _when_active(t, nt, add)


def _when_active(t, n_tiles: int, fn):
    """Run ``fn`` on the tiles a reduction owns (unconditionally when it
    owns every tile of the grid)."""
    if n_tiles is None:
        fn()
    else:
        pl.when(t < n_tiles)(fn)


def _zero_active(tiles, t, o_refs):
    for (_, nt), o in zip(tiles, o_refs):
        def zero(o=o):
            o[...] = jnp.zeros_like(o)
        _when_active(t, nt, zero)


def _grid_kernel(specs, tiles):

    def kernel(codes_ref, fpay_ref, *o_refs):
        t, i = pl.program_id(0), pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            _zero_active(tiles, t, o_refs)

        _accumulate(specs, tiles, t, codes_ref, fpay_ref, o_refs)

    return kernel


def _dbuf_kernel(specs, tiles, block_rows: int, n_blocks: int):

    def kernel(codes_hbm, fpay_hbm, *o_refs):
        t = pl.program_id(0)

        def body(codes_scr, fpay_scr, sem):
            _zero_active(tiles, t, o_refs)

            def dmas(slot, blk):
                rows = pl.ds(pl.multiple_of(blk * block_rows, block_rows),
                             block_rows)
                return (pltpu.make_async_copy(codes_hbm.at[:, rows],
                                              codes_scr.at[slot],
                                              sem.at[0, slot]),
                        pltpu.make_async_copy(fpay_hbm.at[:, rows],
                                              fpay_scr.at[slot],
                                              sem.at[1, slot]))

            for d in dmas(0, 0):        # warm-up: first block's copies
                d.start()

            def step(blk, carry):
                slot = jax.lax.rem(blk, 2)

                @pl.when(blk + 1 < n_blocks)
                def _prefetch():        # overlap: next block's HBM→VMEM copy
                    for d in dmas(jax.lax.rem(blk + 1, 2), blk + 1):
                        d.start()

                for d in dmas(slot, blk):
                    d.wait()
                _accumulate(specs, tiles, t, codes_scr.at[slot],
                            fpay_scr.at[slot], o_refs)
                return carry

            jax.lax.fori_loop(0, n_blocks, step, 0)

        pl.run_scoped(
            body,
            codes_scr=pltpu.VMEM((2, codes_hbm.shape[0], block_rows),
                                 jnp.int32),
            fpay_scr=pltpu.VMEM((2, fpay_hbm.shape[0], block_rows),
                                jnp.float32),
            sem=pltpu.SemaphoreType.DMA((2, 2)),
        )

    return kernel


def fused_scan_block_pallas(codes: jnp.ndarray, fpay: jnp.ndarray,
                            specs: Tuple[ReduceSpec, ...], *,
                            block_rows: int = 512, interpret: bool = False,
                            double_buffer: bool = True,
                            name: str = "fused_scan_block"):
    """Run every reduction of ``specs`` over the same row blocks in ONE
    kernel launch; returns a tuple of ``(n_segments_r, width_r)`` arrays
    aligned with ``specs``.  ``codes`` (n, C) int32, ``fpay`` (n, W) f32.
    ``name`` labels the kernel in profiles."""
    assert specs, "fused_scan_block needs at least one reduction"
    assert codes.ndim == 2 and fpay.ndim == 2, (codes.shape, fpay.shape)
    assert codes.shape[0] == fpay.shape[0], (codes.shape, fpay.shape)
    # rows pad to the row block; columns to the sublane tile, so that a DMA
    # window of the transposed arrays covers whole (8, 128) tiles
    codes_t = _pad_dim(_pad_rows(codes.astype(jnp.int32), block_rows),
                       1, 8).T
    fpay_t = _pad_dim(_pad_rows(fpay.astype(jnp.float32), block_rows),
                      1, 8).T
    (n_codes, n), n_fpay = codes_t.shape, fpay_t.shape[0]
    n_blocks = n // block_rows
    tile = segment_tile(specs, n_codes, n_fpay, block_rows)
    tiles = [_tile_of(sp, tile) for sp in specs]
    n_tiles = max(nt for _, nt in tiles)
    # a reduction that owns every tile needs no activity guard
    guarded = [(tw, None if nt == n_tiles else nt) for tw, nt in tiles]
    out_shapes = tuple(
        jax.ShapeDtypeStruct(_out_rows(sp) + (tw * nt,), jnp.float32)
        for sp, (tw, nt) in zip(specs, tiles))

    def out_spec(sp, tw, nt):
        lead = _out_rows(sp)
        zeros = (0,) * len(lead)
        return pl.BlockSpec(lead + (tw,), lambda t, *_: zeros + (
            jnp.minimum(t, nt - 1),))

    if double_buffer:
        kernel = _dbuf_kernel(specs, guarded, block_rows, n_blocks)
        grid = (n_tiles,)
        in_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
    else:
        kernel = _grid_kernel(specs, guarded)
        grid = (n_tiles, n_blocks)
        in_specs = [pl.BlockSpec((n_codes, block_rows), lambda t, i: (0, i)),
                    pl.BlockSpec((n_fpay, block_rows), lambda t, i: (0, i))]
    outs = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=tuple(out_spec(sp, tw, nt)
                        for sp, (tw, nt) in zip(specs, tiles)),
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name=name,
    )(codes_t, fpay_t)
    return tuple(_from_kernel_layout(sp, o) for sp, o in zip(specs, outs))


def _from_kernel_layout(sp: ReduceSpec, out: jnp.ndarray) -> jnp.ndarray:
    """Kernel output -> ``(n_segments, width)``: drop the tile padding and
    put segments first (hist columns ordered ``[cond j, stat k]``)."""
    out = out[..., :sp.n_segments]
    if sp.kind == "seg":
        return out.T
    return jnp.transpose(out, (2, 1, 0)).reshape(sp.n_segments, sp.width)
