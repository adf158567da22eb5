"""Block-sparse (BSR) prediction kernel — scores = x @ W_pruned^T.

The paper's Delta-pruning (§2.2) leaves W with >= 95% exact zeros. On CPU the
paper stores per-label sparse vectors; the TPU-native equivalent (DESIGN.md
§2) is *block* sparsity: W is tiled into MXU-aligned (bl, bd) blocks, all-zero
blocks are dropped at model-conversion time (core/pruning.to_block_sparse),
and this kernel iterates ONLY over surviving blocks — compute and HBM traffic
scale with block density, not with L x D.

Mechanics: one grid step per packed nonzero block, ordered row-major. The
block's (row, col) coordinates are scalar-prefetched so BlockSpec index_maps
can steer both the x-tile fetch (col) and the output-tile revisit (row).
Because blocks of one label-row are adjacent in the packing, the output tile
(n, bl) stays resident in VMEM for the whole row and is written back once.

VMEM (f32): x tile n*bd + W block bl*bd + out tile n*bl; for n = 256,
bl = bd = 128 that is 128 KB + 64 KB + 128 KB — far under budget, so wide
request batches are fine.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import resolve_interpret

DEFAULT_BLOCK = (128, 128)


def _bsr_kernel(rows_ref, cols_ref, x_ref, blk_ref, o_ref):
    """Grid step k: o[:, rows[k]] += x[:, cols[k]] @ blocks[k]^T."""
    del cols_ref
    k = pl.program_id(0)
    is_new_row = jnp.logical_or(
        k == 0, rows_ref[k] != rows_ref[jnp.maximum(k - 1, 0)])

    @pl.when(is_new_row)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), blk_ref[0].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def bsr_predict_pallas(x: jax.Array, blocks: jax.Array, block_rows: jax.Array,
                       block_cols: jax.Array, n_row_blocks: int,
                       *, interpret: bool | None = None) -> jax.Array:
    """x (n, Dp), blocks (nb, bl, bd) row-major packed -> scores (n, Lp).

    Row-blocks with no surviving blocks are never visited; ops.py masks them.
    """
    n = x.shape[0]
    nb, bl, bd = blocks.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[pl.BlockSpec((n, bd), lambda k, rows, cols: (0, cols[k])),
                  pl.BlockSpec((1, bl, bd), lambda k, rows, cols: (k, 0, 0))],
        out_specs=pl.BlockSpec((n, bl), lambda k, rows, cols: (0, rows[k])),
    )
    return pl.pallas_call(
        _bsr_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, n_row_blocks * bl), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(block_rows, block_cols, x, blocks)


def _bsr_int8_kernel(rows_ref, cols_ref, scales_ref, x_ref, blk_ref, o_ref):
    """Int8 variant of `_bsr_kernel`: the packed block arrives as int8,
    is widened to fp32 in-register, and the per-block scale is applied to
    the fp32 partial product — one scalar multiply per output tile instead
    of bl*bd dequant multiplies, with identical accumulation order to the
    gathered int8 kernel (the bit-for-bit full-coverage contract)."""
    del cols_ref
    k = pl.program_id(0)
    is_new_row = jnp.logical_or(
        k == 0, rows_ref[k] != rows_ref[jnp.maximum(k - 1, 0)])

    @pl.when(is_new_row)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += scales_ref[k] * jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), blk_ref[0].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def bsr_predict_int8_pallas(x: jax.Array, blocks: jax.Array,
                            scales: jax.Array, block_rows: jax.Array,
                            block_cols: jax.Array, n_row_blocks: int,
                            *, interpret: bool | None = None) -> jax.Array:
    """x (n, Dp), blocks (nb, bl, bd) int8 row-major packed, scales (nb,)
    fp32 -> scores (n, Lp) fp32. HBM traffic for the model payload is
    nb*bl*bd bytes + 4*nb scale bytes — ~0.25x the fp32 kernel's.

    The scales ride in scalar memory next to the block coordinates (both
    are scalar-prefetched), so each grid step reads one f32 alongside its
    int8 tile. Row-blocks with no surviving blocks are never visited;
    ops.py masks them, exactly like the fp32 path.
    """
    n = x.shape[0]
    nb, bl, bd = blocks.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb,),
        in_specs=[pl.BlockSpec((n, bd),
                               lambda k, rows, cols, scales: (0, cols[k])),
                  pl.BlockSpec((1, bl, bd),
                               lambda k, rows, cols, scales: (k, 0, 0))],
        out_specs=pl.BlockSpec((n, bl),
                               lambda k, rows, cols, scales: (0, rows[k])),
    )
    return pl.pallas_call(
        _bsr_int8_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, n_row_blocks * bl), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(block_rows, block_cols, scales, x, blocks)


def _bsr_gather_kernel(sel_ref, rptr_ref, cols_ref, x_ref, blk_ref, o_ref):
    """Grid step (i, j): j-th packed block of selected row block sel[i].

    o[:, i-th tile] += x[:, cols[ptr]] @ blocks[ptr]^T  for
    ptr = row_ptr[sel[i]] + j, gated on j < blocks-in-row — padding steps
    (rows shorter than the grid's max) fetch a clamped tile and add nothing.
    The output tile is zero-initialized at j == 0 unconditionally, so a
    selected row block with NO surviving blocks yields exact-zero scores —
    the same pruned-label convention as the exhaustive path.
    """
    del cols_ref
    i, j = pl.program_id(0), pl.program_id(1)
    r = sel_ref[i]

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(rptr_ref[r] + j < rptr_ref[r + 1])
    def _acc():
        o_ref[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), blk_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def bsr_predict_gather_pallas(x: jax.Array, blocks: jax.Array,
                              block_cols: jax.Array, row_ptr: jax.Array,
                              sel: jax.Array, max_blocks_per_row: int,
                              *, interpret: bool | None = None) -> jax.Array:
    """Gathered-block BSR predict: score only the row blocks listed in `sel`.

    x (n, Dp), blocks (nb, bl, bd) row-major packed, row_ptr (R + 1,),
    sel (B,) int32 row-block ids (any order, no duplicates) -> scores
    (n, B * bl), where columns [i*bl, (i+1)*bl) are the scores of row block
    sel[i]'s labels. `max_blocks_per_row` bounds the inner grid dimension
    (static: max(row_ptr[r+1] - row_ptr[r]) over all row blocks, >= 1).

    Both BlockSpec index maps clamp the packed pointer to nb - 1 so padding
    grid steps (j beyond a short row's block count) fetch a valid tile; the
    kernel body gates their accumulation off. Compute and HBM traffic scale
    with the selected blocks, not with L.
    """
    n = x.shape[0]
    nb, bl, bd = blocks.shape
    B = sel.shape[0]

    def _ptr(i, j, sel_a, rptr_a, cols_a):
        return jnp.minimum(rptr_a[sel_a[i]] + j, nb - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, max_blocks_per_row),
        in_specs=[
            pl.BlockSpec((n, bd),
                         lambda i, j, sel_a, rptr_a, cols_a:
                         (0, cols_a[_ptr(i, j, sel_a, rptr_a, cols_a)])),
            pl.BlockSpec((1, bl, bd),
                         lambda i, j, sel_a, rptr_a, cols_a:
                         (_ptr(i, j, sel_a, rptr_a, cols_a), 0, 0)),
        ],
        out_specs=pl.BlockSpec((n, bl),
                               lambda i, j, sel_a, rptr_a, cols_a: (0, i)),
    )
    return pl.pallas_call(
        _bsr_gather_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, B * bl), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(sel, row_ptr, block_cols, x, blocks)


def _bsr_gather_int8_kernel(sel_ref, rptr_ref, cols_ref, scales_ref,
                            x_ref, blk_ref, o_ref):
    """Int8 variant of `_bsr_gather_kernel`: same clamp/gate structure,
    with the clamped packed pointer also indexing the per-block scale and
    the scale applied to the fp32 partial product — the same in-register
    dequantization as the exhaustive int8 kernel, so full coverage is
    bit-for-bit identical."""
    del cols_ref
    i, j = pl.program_id(0), pl.program_id(1)
    r = sel_ref[i]

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(rptr_ref[r] + j < rptr_ref[r + 1])
    def _acc():
        ptr = rptr_ref[r] + j            # in-bounds inside the gate
        o_ref[...] += scales_ref[ptr] * jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), blk_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def bsr_predict_gather_int8_pallas(x: jax.Array, blocks: jax.Array,
                                   scales: jax.Array, block_cols: jax.Array,
                                   row_ptr: jax.Array, sel: jax.Array,
                                   max_blocks_per_row: int, *,
                                   interpret: bool | None = None,
                                   ) -> jax.Array:
    """Gathered-block int8 predict: the shortlist fine stage over int8
    tiles. Same contract as `bsr_predict_gather_pallas` with (blocks int8,
    scales fp32) replacing the fp32 blocks; padding grid steps fetch a
    clamped tile and add nothing, and the scale is read only inside the
    in-bounds gate."""
    n = x.shape[0]
    nb, bl, bd = blocks.shape
    B = sel.shape[0]

    def _ptr(i, j, sel_a, rptr_a, cols_a, scales_a):
        return jnp.minimum(rptr_a[sel_a[i]] + j, nb - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, max_blocks_per_row),
        in_specs=[
            pl.BlockSpec((n, bd),
                         lambda i, j, sel_a, rptr_a, cols_a, scales_a:
                         (0, cols_a[_ptr(i, j, sel_a, rptr_a, cols_a,
                                         scales_a)])),
            pl.BlockSpec((1, bl, bd),
                         lambda i, j, sel_a, rptr_a, cols_a, scales_a:
                         (_ptr(i, j, sel_a, rptr_a, cols_a, scales_a),
                          0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (n, bl),
            lambda i, j, sel_a, rptr_a, cols_a, scales_a: (0, i)),
    )
    return pl.pallas_call(
        _bsr_gather_int8_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, B * bl), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(sel, row_ptr, block_cols, scales, x, blocks)


def _bsr_gather_pq_kernel(sel_ref, rptr_ref, cols_ref, x_ref, blk_ref, o_ref):
    """Ragged per-query gather, grid step (q, i, j): j-th packed block of
    row block sel[q, i] — query q's OWN i-th selected block, scored against
    query q's single row.

    o[q-th row, i-th tile] += x[q, cols[ptr]] @ blocks[ptr]^T  for
    ptr = row_ptr[sel[q, i]] + j, gated on j < blocks-in-row exactly like
    the shared-selection kernel; the (1, bl) output tile is zero-initialized
    at j == 0. Each query walks its own block list, so a query whose
    selection hits sparse row blocks does strictly less accumulation work
    than one that hit dense rows — the shared-B union's worst-case cost is
    gone.
    """
    del cols_ref
    q, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    r = sel_ref[q, i]

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(rptr_ref[r] + j < rptr_ref[r + 1])
    def _acc():
        o_ref[0] += jax.lax.dot_general(
            x_ref[0].astype(jnp.float32), blk_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def bsr_predict_gather_pq_pallas(x: jax.Array, blocks: jax.Array,
                                 block_cols: jax.Array, row_ptr: jax.Array,
                                 sel: jax.Array, max_blocks_per_row: int,
                                 *, interpret: bool | None = None,
                                 ) -> jax.Array:
    """Per-query gathered-block BSR predict: row q scores only ITS row
    blocks `sel[q]`.

    x (n, Dp), blocks (nb, bl, bd) row-major packed, row_ptr (R + 1,),
    sel (n, B) int32 — row q's B selected row-block ids (sorted, no
    duplicates) -> scores (n, B * bl), where row q's columns
    [i*bl, (i+1)*bl) are the scores of row block sel[q, i]'s labels (a
    per-row ragged layout; ops.py owns the per-row label translation).

    The grid is (n, B, max_blocks_per_row) with j innermost, so each
    (1, bl) output tile stays resident across its row block's packed
    blocks. Both index maps clamp the packed pointer to nb - 1 so padding
    steps fetch a valid tile; the body gates their accumulation off. x and
    the scores travel as (n, 1, width) arrays: a block's last two dims are
    then (1, full) — a (1, bd) block of an (n, Dp) array breaks the TPU's
    (8, 128) tiling rule whenever n > 1.

    Numerics note: the per-query dot is (1, bd) @ (bd, bl) — NOT bitwise
    identical to one row of the shared kernel's (n, bd) @ (bd, bl) dot on
    every backend, which is why `ShortlistBackend` collapses B == R (where
    every per-query list provably equals the full sorted block list) to
    the shared kernel: the full-width bit-exactness contract rides on the
    proven path, and this kernel serves only genuinely ragged B < R work.
    At n == 1 the shapes coincide and the two kernels ARE bit-identical
    (tested).
    """
    n = x.shape[0]
    nb, bl, bd = blocks.shape
    B = sel.shape[1]

    def _ptr(q, i, j, sel_a, rptr_a, cols_a):
        return jnp.minimum(rptr_a[sel_a[q, i]] + j, nb - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n, B, max_blocks_per_row),
        in_specs=[
            pl.BlockSpec((1, 1, bd),
                         lambda q, i, j, sel_a, rptr_a, cols_a:
                         (q, 0, cols_a[_ptr(q, i, j, sel_a, rptr_a,
                                            cols_a)])),
            pl.BlockSpec((1, bl, bd),
                         lambda q, i, j, sel_a, rptr_a, cols_a:
                         (_ptr(q, i, j, sel_a, rptr_a, cols_a), 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bl), lambda q, i, j, sel_a, rptr_a, cols_a: (q, 0, i)),
    )
    out = pl.pallas_call(
        _bsr_gather_pq_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, B * bl), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(sel, row_ptr, block_cols, x[:, None, :], blocks)
    return out[:, 0, :]


def _bsr_gather_pq_int8_kernel(sel_ref, rptr_ref, cols_ref, scales_ref,
                               x_ref, blk_ref, o_ref):
    """Int8 variant of `_bsr_gather_pq_kernel`: identical clamp/gate
    structure, with the in-bounds packed pointer indexing the per-block
    scale and the scale applied to the fp32 partial product — the same
    in-register dequantization as every other int8 kernel in this file."""
    del cols_ref
    q, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    r = sel_ref[q, i]

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(rptr_ref[r] + j < rptr_ref[r + 1])
    def _acc():
        ptr = rptr_ref[r] + j            # in-bounds inside the gate
        o_ref[0] += scales_ref[ptr] * jax.lax.dot_general(
            x_ref[0].astype(jnp.float32), blk_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def bsr_predict_gather_pq_int8_pallas(x: jax.Array, blocks: jax.Array,
                                      scales: jax.Array,
                                      block_cols: jax.Array,
                                      row_ptr: jax.Array, sel: jax.Array,
                                      max_blocks_per_row: int, *,
                                      interpret: bool | None = None,
                                      ) -> jax.Array:
    """Per-query gathered-block int8 predict: same contract as
    `bsr_predict_gather_pq_pallas` with (blocks int8, scales fp32)
    replacing the fp32 blocks."""
    n = x.shape[0]
    nb, bl, bd = blocks.shape
    B = sel.shape[1]

    def _ptr(q, i, j, sel_a, rptr_a, cols_a, scales_a):
        return jnp.minimum(rptr_a[sel_a[q, i]] + j, nb - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n, B, max_blocks_per_row),
        in_specs=[
            pl.BlockSpec((1, 1, bd),
                         lambda q, i, j, sel_a, rptr_a, cols_a, scales_a:
                         (q, 0, cols_a[_ptr(q, i, j, sel_a, rptr_a, cols_a,
                                            scales_a)])),
            pl.BlockSpec((1, bl, bd),
                         lambda q, i, j, sel_a, rptr_a, cols_a, scales_a:
                         (_ptr(q, i, j, sel_a, rptr_a, cols_a, scales_a),
                          0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bl),
            lambda q, i, j, sel_a, rptr_a, cols_a, scales_a: (q, 0, i)),
    )
    out = pl.pallas_call(
        _bsr_gather_pq_int8_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, B * bl), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(sel, row_ptr, block_cols, scales, x[:, None, :], blocks)
    return out[:, 0, :]
