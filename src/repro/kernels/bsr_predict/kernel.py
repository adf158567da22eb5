"""Block-sparse (BSR) prediction kernel — scores = x @ W_pruned^T.

The paper's Delta-pruning (§2.2) leaves W with >= 95% exact zeros. On CPU the
paper stores per-label sparse vectors; the TPU-native equivalent (DESIGN.md
§2) is *block* sparsity: W is tiled into MXU-aligned (bl, bd) blocks, all-zero
blocks are dropped at model-conversion time (core/pruning.to_block_sparse),
and this kernel iterates ONLY over surviving blocks — compute and HBM traffic
scale with block density, not with L x D.

Mechanics: the packed blocks are read `GROUP` at a time (1 MiB of fp32
128 x 128 blocks), in packed order, and each block takes one
(rows, bd) x (bl, bd)^T dot added into its label row's scores in packed
order. What stays in VMEM is chosen from the launch's shapes alone
(`streams_scores`), by this rule:

  resident   x (n, Dp) and the scores (n, Lp) of all n rows fit
             `VMEM_BUDGET`: both stay resident while the grid walks every
             group, x fetched once, the scores written back once.
  streamed   else, where x of all n rows fits: x stays resident and the
             grid walks groups of `STEP_ROW_BLOCKS` label row blocks, each
             step writing its (n, STEP_ROW_BLOCKS * bl) score slice once.
             The packed blocks and their column ids are streamed in by
             DMA, double-buffered, every block once; `row_ptr` is
             scalar-prefetched. Each label row sums its dots in registers.
  row tiles  else: the resident layout over equal row tiles of x and the
             scores (`row_tile`), one pass over the blocks each.

Every layout adds the same per-block dots in the same order into zeroed
scores, so they return the same bits. `weight_passes` says how many
times a launch reads the blocks: one, except in row tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import resolve_interpret

DEFAULT_BLOCK = (128, 128)

# Bytes of VMEM a launch's resident arrays may fill, rows counted in whole
# sublane tiles of 8: x and the fp32 scores of a row tile in the resident
# layout, x and two score slices in the streamed one. Most of a TPU v5e
# core's 128 MiB, so that at Wiki10-31K half (Dp 102,016, Lp 15,488) one
# tile holds up to 208 rows and a 128-row launch reads the blocks once; at
# LF-AmazonTitles-1.3M (Dp 768, Lp 1,305,344) the scores of 16 rows fit,
# and wider launches stream them.
VMEM_BUDGET = 96 << 20
# Packed blocks per grid step: 1 MiB of fp32 128 x 128 blocks, the
# fastest of 4-64 at Wiki10-31K half on a TPU v5e.
GROUP = 16
# block_rows / block_cols reach the kernel as SMEM windows of this many
# entries (the 1-D int32 tiling XLA gives them); a group never straddles
# two windows because GROUP divides it.
_SMEM_WINDOW = 1024
# Label row blocks per grid step of the streamed layout: a 4,096-label
# score slice at 128-row blocks.
STEP_ROW_BLOCKS = 32
# The streamed layout takes a label row's blocks this many at a time, as
# independent dots. One at a time, each dot waits on the last: at
# LF-AmazonTitles-1.3M's 6-block rows a launch took 8.0 ms against 7.0 ms
# at 4-16 at a time on a TPU v5e (fetches one chunk ahead in both).
_ROW_UNROLL = 8
# Chunks (and column windows) the streamed layout keeps in flight ahead
# of the one it reads: a launch there took 5.47 / 5.34 / 5.34 ms at 1 / 2
# / 3 (n = 1; 9.12 / 8.12 / 7.62 ms at n = 256) against 4.90 ms for the
# blocks at 819 GB/s.
_STREAM_AHEAD = 2


def _resident_bytes(rows: int, Lp: int, Dp: int) -> int:
    """VMEM a row tile keeps resident: x and the fp32 scores, rows padded
    to the 8-row sublane tile."""
    return 4 * (-(-rows // 8) * 8) * (Dp + Lp)


def _streamed_bytes(n: int, Dp: int, bl: int) -> int:
    """VMEM the streamed layout keeps: x and two score slices in flight,
    rows padded to the 8-row sublane tile."""
    return 4 * (-(-n // 8) * 8) * (Dp + 2 * STEP_ROW_BLOCKS * bl)


def streams_scores(n: int, Lp: int, Dp: int, bl: int) -> bool:
    """Whether an n-row launch takes the streamed layout: the scores of all
    n rows do not fit `VMEM_BUDGET` beside x, and x alone (with two score
    slices) does."""
    return (_resident_bytes(n, Lp, Dp) > VMEM_BUDGET
            and _streamed_bytes(n, Dp, bl) <= VMEM_BUDGET)


def weight_passes(n: int, Lp: int, Dp: int, bl: int) -> int:
    """Passes an n-row launch makes over the packed blocks."""
    if streams_scores(n, Lp, Dp, bl):
        return 1
    return -(-n // row_tile(n, Lp, Dp))


def row_tile(n: int, Lp: int, Dp: int) -> int:
    """Rows of x and the scores held in VMEM during one pass over the
    packed blocks: all n where they fit `VMEM_BUDGET`, else the fewest
    passes of equal tiles, each a multiple of 8 rows within the budget."""
    if _resident_bytes(n, Lp, Dp) <= VMEM_BUDGET:
        return n
    most = max(8, VMEM_BUDGET // (4 * (Dp + Lp)) // 8 * 8)
    passes = -(-n // most)
    per_pass = -(-n // passes)
    return -(-per_pass // 8) * 8


def _bsr_kernel(rows_ref, cols_ref, x_ref, blk_ref, o_ref, *, n_blocks):
    """Grid step (i, k), row tile i: packed blocks [k*G, (k+1)*G), each
    added as o[:, rows[p]] += x[:, cols[p]] @ blocks[p]^T in packed order.

    rows_ref / cols_ref hold the SMEM window of entries the group lies in;
    o_ref is the tile's (rows, Lp) scores, zeroed at the first step. A
    group inside one label row sums its dots into one tile held in
    registers and stores it once: the same float32 additions, in the same
    order, as a load-add-store per block. Other groups, and the last,
    partial one, update their tiles block by block in a loop that stops
    at n_blocks.
    """
    k = pl.program_id(1)
    group, bl, bd = blk_ref.shape
    window = rows_ref.shape[0]
    base = (k * group) % window

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def dot(g):
        c = pl.multiple_of(cols_ref[base + g] * bd, bd)
        return jax.lax.dot_general(
            x_ref[:, pl.ds(c, bd)].astype(jnp.float32),
            blk_ref[g].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    def tile(g):
        return pl.ds(pl.multiple_of(rows_ref[base + g] * bl, bl), bl)

    last = jnp.minimum(base + group - 1, window - 1)
    one_row = jnp.logical_and(k < n_blocks // group,
                              rows_ref[base] == rows_ref[last])

    @pl.when(one_row)
    def _one_row():
        acc = o_ref[:, tile(0)]
        for g in range(group):
            acc += dot(g)
        o_ref[:, tile(0)] = acc

    @pl.when(jnp.logical_not(one_row))
    def _by_block():
        def add(g, carry):
            o_ref[:, tile(g)] += dot(g)
            return carry

        # A loop, not 16 gated copies: this path is rare on long label
        # rows, and each unrolled copy costs lowering time per bucket.
        jax.lax.fori_loop(0, jnp.minimum(group, n_blocks - k * group), add, 0)


def _bsr_stream_kernel(ptr_ref, x_ref, blk_hbm, cols_hbm, o_ref, blk_buf,
                       cols_buf, sems, chunk_ref, *, n_blocks, n_row_blocks):
    """Grid step i: label row blocks [i*S, (i+1)*S), S = STEP_ROW_BLOCKS.

    Each row block r sums x[:, cols[p]] @ blocks[p]^T over its packed
    blocks p in [row_ptr[r], row_ptr[r+1]) in registers, from zero, in
    packed order, and stores its (n, bl) scores once into the step's slice
    o_ref. Row blocks past the last (a partial final step) and row blocks
    with no packed block store zeros. A row's blocks are taken
    `_ROW_UNROLL` at a time: that many independent dots (the last of the
    row standing in past its end), then added in order, each past the
    end as an exact zero, so the MXUs overlap the dots of short rows.

    The packed blocks stream through blk_buf in chunks of `group`, chunk c
    holding blocks [c*group, (c+1)*group) (the last clamped to end at
    n_blocks), and their column ids through cols_buf in aligned windows.
    Each has `_STREAM_AHEAD` + 2 slots: the one read, the one before it (a
    run of a row's blocks may straddle the two), and `_STREAM_AHEAD` in
    flight; entering chunk c starts chunk c + _STREAM_AHEAD before it
    waits for c, so the next fetch is always under way. chunk_ref holds
    the chunk the walk is in: a chunk that straddles two steps is fetched
    and waited for once. The last step waits for any fetch the walk did
    not consume.
    """
    i = pl.program_id(0)
    step_rows = o_ref.shape[1] // blk_buf.shape[2]
    slots, group, bl, bd = blk_buf.shape
    window = cols_buf.shape[0] // slots
    n_chunks = pl.cdiv(n_blocks, group)
    n_windows = cols_hbm.shape[0] // window

    def first(c):
        return jnp.minimum(c * group, n_blocks - group)

    def chunk_copy(c):
        return pltpu.make_async_copy(blk_hbm.at[pl.ds(first(c), group)],
                                     blk_buf.at[c % slots],
                                     sems.at[0, c % slots])

    def window_copy(w):
        return pltpu.make_async_copy(
            cols_hbm.at[pl.ds(pl.multiple_of(w * window, window), window)],
            cols_buf.at[pl.ds(pl.multiple_of(w % slots * window, window),
                              window)],
            sems.at[1, w % slots])

    @pl.when(i == 0)
    def _start():
        chunk_ref[0] = -1
        for a in range(min(_STREAM_AHEAD, n_chunks)):
            chunk_copy(a).start()
        for a in range(min(_STREAM_AHEAD, n_windows)):
            window_copy(a).start()

    def enter(c):
        @pl.when(c > chunk_ref[0])
        def _enter():
            @pl.when(c + _STREAM_AHEAD < n_chunks)
            def _next_chunk():
                chunk_copy(c + _STREAM_AHEAD).start()

            chunk_copy(c).wait()
            w = c * group // window

            @pl.when(c * group % window == 0)
            def _window():
                @pl.when(w + _STREAM_AHEAD < n_windows)
                def _next_window():
                    window_copy(w + _STREAM_AHEAD).start()

                window_copy(w).wait()

            chunk_ref[0] = c

    def dot(p):
        c = p // group
        col = cols_buf[p // window % slots * window + p % window]
        c0 = pl.multiple_of(col * bd, bd)
        return jax.lax.dot_general(
            x_ref[:, pl.ds(c0, bd)].astype(jnp.float32),
            blk_buf[c % slots, p - first(c)].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    def row(t, carry):
        r = i * step_rows + t
        lo = ptr_ref[jnp.minimum(r, n_row_blocks)]
        hi = ptr_ref[jnp.minimum(r + 1, n_row_blocks)]

        def run(j, acc):
            base = lo + j * _ROW_UNROLL
            last = jnp.minimum(base + _ROW_UNROLL, hi) - 1
            enter(base // group)
            enter(last // group)
            dots = [dot(jnp.minimum(base + u, last))
                    for u in range(_ROW_UNROLL)]
            for u, d in enumerate(dots):
                acc = acc + jnp.where(base + u <= last, d, 0.0)
            return acc

        acc = jax.lax.fori_loop(
            0, pl.cdiv(hi - lo, _ROW_UNROLL), run,
            jnp.zeros((x_ref.shape[0], bl), jnp.float32))
        o_ref[:, pl.ds(pl.multiple_of(t * bl, bl), bl)] = acc
        return carry

    jax.lax.fori_loop(0, step_rows, row, 0)

    # A model whose row_ptr leaves packed blocks unvisited (the fully
    # pruned one keeps a sentinel block in no row) still has chunks and
    # windows in flight: wait for them before the kernel ends.
    @pl.when(i == pl.num_programs(0) - 1)
    def _drain():
        c = chunk_ref[0]
        w = jnp.where(c >= 0, c * group // window, -1)
        for a in range(1, _STREAM_AHEAD + 1):
            @pl.when(c + a < n_chunks)
            def _chunk():
                chunk_copy(c + a).wait()

            @pl.when(w + a < n_windows)
            def _window():
                window_copy(w + a).wait()


def _bsr_streamed(x, blocks, block_cols, row_ptr, n_row_blocks, interpret):
    """The streamed layout: x resident, score slices of STEP_ROW_BLOCKS
    label row blocks written once each, blocks and column ids by DMA."""
    n, Dp = x.shape
    nb, bl, bd = blocks.shape
    group = min(GROUP, nb)
    slots = _STREAM_AHEAD + 2
    cols = jnp.pad(block_cols, (0, -nb % _SMEM_WINDOW))
    w_bytes = group * bl * bd * blocks.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_bsr_stream_kernel, n_blocks=nb,
                          n_row_blocks=n_row_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(n_row_blocks, STEP_ROW_BLOCKS),),
            in_specs=[pl.BlockSpec((n, Dp), lambda i, ptr: (0, 0),
                                   pipeline_mode=pl.Buffered(1)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((n, STEP_ROW_BLOCKS * bl),
                                   lambda i, ptr: (0, i)),
            scratch_shapes=[
                pltpu.VMEM((slots, group, bl, bd), blocks.dtype),
                pltpu.SMEM((slots * _SMEM_WINDOW,), jnp.int32),
                pltpu.SemaphoreType.DMA((2, slots)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((n, n_row_blocks * bl), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(_streamed_bytes(n, Dp, bl)
                              + slots * w_bytes + (8 << 20))),
        interpret=resolve_interpret(interpret),
    )(row_ptr, x, blocks, cols)


def bsr_predict_pallas(x: jax.Array, blocks: jax.Array, block_rows: jax.Array,
                       block_cols: jax.Array, n_row_blocks: int,
                       *, row_ptr: jax.Array,
                       interpret: bool | None = None) -> jax.Array:
    """x (n, Dp), blocks (nb, bl, bd) row-major packed, with their label
    row block and column block ids and row_ptr (n_row_blocks + 1,) ->
    scores (n, Lp).

    The layout follows `streams_scores`. Resident and row tiles: grid (row
    tiles, groups of `GROUP` blocks; fewer where nb < GROUP), reading
    block_rows. Streamed: grid (groups of `STEP_ROW_BLOCKS` label row
    blocks,), reading row_ptr. Scores of row-blocks with no surviving
    blocks are zero; ops.py masks them all the same.
    """
    n, Dp = x.shape
    nb, bl, bd = blocks.shape
    Lp = n_row_blocks * bl
    if streams_scores(n, Lp, Dp, bl):
        return _bsr_streamed(x, blocks, block_cols, row_ptr, n_row_blocks,
                             interpret)
    rows = row_tile(n, Lp, Dp)
    passes = pl.cdiv(n, rows)
    if passes * rows != n:
        x = jnp.pad(x, ((0, passes * rows - n), (0, 0)))
    group = min(GROUP, nb)
    window = min(_SMEM_WINDOW, nb)
    meta = pl.BlockSpec((window,), lambda i, k: (k * group // window,),
                        memory_space=pltpu.SMEM)
    resident = pl.Buffered(1)
    w_bytes = group * bl * bd * blocks.dtype.itemsize
    scores = pl.pallas_call(
        functools.partial(_bsr_kernel, n_blocks=nb),
        grid=(passes, pl.cdiv(nb, group)),
        in_specs=[meta, meta,
                  pl.BlockSpec((rows, Dp), lambda i, k: (i, 0),
                               pipeline_mode=resident),
                  pl.BlockSpec((group, bl, bd), lambda i, k: (k, 0, 0))],
        out_specs=pl.BlockSpec((rows, Lp), lambda i, k: (i, 0),
                               pipeline_mode=resident),
        out_shape=jax.ShapeDtypeStruct((passes * rows, Lp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=(_resident_bytes(rows, Lp, Dp) + 2 * w_bytes
                              + (8 << 20))),
        interpret=resolve_interpret(interpret),
    )(block_rows, block_cols, x, blocks)
    return scores if passes * rows == n else scores[:n]


def _bsr_int8_kernel(rows_ref, cols_ref, scales_ref, x_ref, blk_ref, o_ref):
    """Int8 exhaustive kernel, one grid step per packed block (row, col
    scalar-prefetched, the output tile revisited along a label row): the
    packed block arrives as int8, is widened to fp32 in-register, and the
    per-block scale is applied to the fp32 partial product — one scalar
    multiply per output tile instead of bl*bd dequant multiplies, with
    identical accumulation order to the gathered int8 kernel (the
    bit-for-bit full-coverage contract)."""
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


def _bsr_gather_kernel(sel_ref, rptr_ref, cols_ref, *refs):
    """Grid step (i, j): j-th packed block of selected row block sel[i].

    o[:, i-th tile] += x[:, cols[ptr]] @ blocks[ptr]^T  for
    ptr = row_ptr[sel[i]] + j, gated on j < blocks-in-row — padding steps
    (rows shorter than the grid's max) fetch a clamped tile and add nothing.
    The output tile is zero-initialized at j == 0 unconditionally, so a
    selected row block with NO surviving blocks yields exact-zero scores —
    the same pruned-label convention as the exhaustive path.

    refs is (x, blocks, o), or (scales, x, blocks, o) for int8 blocks: the
    in-bounds ptr then also reads the block's scale, which multiplies its
    fp32 dot, as in the exhaustive int8 kernel (same bits at full
    coverage).
    """
    del cols_ref
    *scales_ref, x_ref, blk_ref, o_ref = refs
    i, j = pl.program_id(0), pl.program_id(1)
    r = sel_ref[i]

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(rptr_ref[r] + j < rptr_ref[r + 1])
    def _acc():
        d = jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), blk_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if scales_ref:
            d = scales_ref[0][rptr_ref[r] + j] * d
        o_ref[...] += d


def bsr_predict_gather_pallas(x: jax.Array, blocks: jax.Array,
                              block_cols: jax.Array, row_ptr: jax.Array,
                              sel: jax.Array, max_blocks_per_row: int,
                              *, scales: jax.Array | None = None,
                              interpret: bool | None = None) -> jax.Array:
    """Gathered-block BSR predict: score only the row blocks listed in `sel`.

    x (n, Dp), blocks (nb, bl, bd) row-major packed, row_ptr (R + 1,),
    sel (B,) int32 row-block ids (any order, no duplicates) -> scores
    (n, B * bl), where columns [i*bl, (i+1)*bl) are the scores of row block
    sel[i]'s labels. `max_blocks_per_row` bounds the inner grid dimension
    (static: max(row_ptr[r+1] - row_ptr[r]) over all row blocks, >= 1).
    Int8 blocks come with their (nb,) fp32 `scales`, scalar-prefetched
    beside the coordinates; fp32 blocks come without.

    Both BlockSpec index maps clamp the packed pointer to nb - 1 so padding
    grid steps (j beyond a short row's block count) fetch a valid tile; the
    kernel body gates their accumulation off. Compute and HBM traffic scale
    with the selected blocks, not with L.
    """
    n = x.shape[0]
    nb, bl, bd = blocks.shape
    B = sel.shape[0]
    prefetch = (sel, row_ptr, block_cols) + (() if scales is None
                                             else (scales,))

    def _ptr(i, j, sel_a, rptr_a, *_):
        return jnp.minimum(rptr_a[sel_a[i]] + j, nb - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, max_blocks_per_row),
        in_specs=[
            pl.BlockSpec((n, bd), lambda i, j, sel_a, rptr_a, cols_a, *_:
                         (0, cols_a[_ptr(i, j, sel_a, rptr_a)])),
            pl.BlockSpec((1, bl, bd), lambda i, j, *refs:
                         (_ptr(i, j, *refs), 0, 0)),
        ],
        out_specs=pl.BlockSpec((n, bl), lambda i, j, *_: (0, i)),
    )
    return pl.pallas_call(
        _bsr_gather_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, B * bl), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(*prefetch, x, blocks)


def _bsr_gather_pq_kernel(sel_ref, rptr_ref, cols_ref, *refs):
    """Ragged per-query gather, grid step (q, i, j): j-th packed block of
    row block sel[q, i] — query q's OWN i-th selected block, scored against
    query q's single row.

    o[q-th row, i-th tile] += x[q, cols[ptr]] @ blocks[ptr]^T  for
    ptr = row_ptr[sel[q, i]] + j, gated and scaled exactly like the
    shared-selection kernel (refs likewise); the (1, bl) output tile is
    zero-initialized at j == 0. Each query walks its own block list, so a
    query whose selection hits sparse row blocks does strictly less
    accumulation work than one that hit dense rows — the shared-B union's
    worst-case cost is gone.
    """
    del cols_ref
    *scales_ref, x_ref, blk_ref, o_ref = refs
    q, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    r = sel_ref[q, i]

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(rptr_ref[r] + j < rptr_ref[r + 1])
    def _acc():
        d = jax.lax.dot_general(
            x_ref[0].astype(jnp.float32), blk_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if scales_ref:
            d = scales_ref[0][rptr_ref[r] + j] * d
        o_ref[0] += d


def bsr_predict_gather_pq_pallas(x: jax.Array, blocks: jax.Array,
                                 block_cols: jax.Array, row_ptr: jax.Array,
                                 sel: jax.Array, max_blocks_per_row: int,
                                 *, scales: jax.Array | None = None,
                                 interpret: bool | None = None,
                                 ) -> jax.Array:
    """Per-query gathered-block BSR predict: row q scores only ITS row
    blocks `sel[q]`.

    x (n, Dp), blocks (nb, bl, bd) row-major packed, row_ptr (R + 1,),
    sel (n, B) int32 — row q's B selected row-block ids (sorted, no
    duplicates) -> scores (n, B * bl), where row q's columns
    [i*bl, (i+1)*bl) are the scores of row block sel[q, i]'s labels (a
    per-row ragged layout; ops.py owns the per-row label translation).
    `scales` as in `bsr_predict_gather_pallas`.

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
    prefetch = (sel, row_ptr, block_cols) + (() if scales is None
                                             else (scales,))

    def _ptr(q, i, j, sel_a, rptr_a, *_):
        return jnp.minimum(rptr_a[sel_a[q, i]] + j, nb - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(n, B, max_blocks_per_row),
        in_specs=[
            pl.BlockSpec((1, 1, bd),
                         lambda q, i, j, sel_a, rptr_a, cols_a, *_:
                         (q, 0, cols_a[_ptr(q, i, j, sel_a, rptr_a)])),
            pl.BlockSpec((1, bl, bd), lambda q, i, j, *refs:
                         (_ptr(q, i, j, *refs), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bl), lambda q, i, j, *_: (q, 0, i)),
    )
    out = pl.pallas_call(
        _bsr_gather_pq_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, B * bl), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(*prefetch, x[:, None, :], blocks)
    return out[:, 0, :]
