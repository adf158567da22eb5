"""Public wrapper: BSR prediction over a pruned DiSMEC model.

Every wrapper takes either packed model: a float32 `BlockSparseModel` or
the int8 `Int8BlockSparseModel`, whose per-block scales multiply each
block's fp32 dot. The kernel follows from the model's type, a fact of the
trace, so one jitted step compiles a separate program per dtype.

`bsr_predict` yields the dense (n, Lp) score matrix; `bsr_predict_topk`
reduces it to k candidates per row in the serving entry point used by
`repro.serve.xmc.BsrBackend` and `Int8Backend` — scores never leave the
padded block coordinate system before being reduced. The masks and the
top-k run under `jax.named_scope("xmc.topk")`, so a device trace tells
their time from the kernel's.

The top-k takes one of two forms, chosen from the shapes (`topk_grouped`):
`jax.lax.top_k` over the whole row, or, on rows at least
`GROUPED_TOPK_MIN_LP` wide, the grouped top-k (`grouped_top_k`). The
latter cuts the row into contiguous groups of `TOPK_GROUP` labels and a
short tail, reads the scores once in a max reduction over the groups,
takes the top k of the group maxima, gathers those k groups' scores and
takes the top k of them and the tail's. It returns `lax.top_k`'s values
and ids exactly, tie order included: every group whose maximum ranks above
the k-th group maximum t is selected, so every answer above t is among the
candidates; an element equal to t in an unselected group is beaten by k
elements of the selected ones, each above t or equal to t at a lower id,
since the groups are contiguous and `lax.top_k` prefers the lower index on
ties. The group maxima are taken over `lax.top_k`'s own order of floats
(-0.0 below +0.0), as integer keys.

`bsr_predict_gather` / `bsr_predict_gather_topk` are the shortlist-gated
variants (serve/shortlist.py): given a per-batch list of selected row
blocks they score ONLY those blocks' packed tiles, so per-query compute
scales with B * block_size instead of L. With the selection covering all
row blocks (sorted) they reproduce the exhaustive path bit-for-bit, in
either dtype. `bsr_predict_gather_pq` / `bsr_predict_gather_pq_topk` take
one selection per query row.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pruning import BlockSparseModel, Int8BlockSparseModel
from repro.kernels.bsr_predict.kernel import (bsr_predict_gather_pallas,
                                              bsr_predict_gather_pq_pallas,
                                              bsr_predict_int8_pallas,
                                              bsr_predict_pallas,
                                              weight_passes)

#: Either packed model: every wrapper below takes both, and picks the
#: kernel from the type, which is known when the wrapper is traced.
Model = BlockSparseModel | Int8BlockSparseModel

# Score of a masked (padding) label: loses to every real score, finite so
# no -inf reaches a comparison.
NEG_INF = float(-3.0e38)
# Labels per group of the grouped top-k: 16 tiles of 128. On a v5e at Lp
# 1,305,344, groups of 1,024-2,048 labels take a third of the time of groups
# of 128-256 at 64-256 rows, whose max pass reduces across lanes on every
# tile; 2,048 is the faster at 256 rows (PERF.md, section 3).
TOPK_GROUP = 2048
# Narrowest score row that takes the grouped top-k. On a v5e it wins at
# Lp 1,305,344 from 16 rows up and loses at Lp 15,488 from 128 rows up, so
# Wiki10-31K half (Lp 15,488) and EURLex-4K (4,096) stay on one
# `lax.top_k`.
GROUPED_TOPK_MIN_LP = 1 << 16


def _pad_features(x: jax.Array, model) -> jax.Array:
    """Pad x (n, D) to the model's padded feature width Dp.

    D > Dp is a hard error with both dims named: the old D < Dp branch
    silently fell through on oversized requests, which then shape-erred
    deep inside the kernel's BlockSpec machinery (or mis-scored under jit
    where the trace point is far from the caller).
    """
    Dp = model.shape[1]
    D = x.shape[1]
    if D > Dp:
        raise ValueError(
            f"request feature dim {D} exceeds the model's padded feature "
            f"dim {Dp} (true feature dim {model.n_features}); bsr_predict "
            "cannot score features the model never had — slice the request "
            "or rebuild the model with the wider feature space")
    if D < Dp:
        x = jnp.pad(x, ((0, 0), (0, Dp - D)))
    return x


def _live_labels(model) -> jax.Array:
    """(Lp,) bool: the label's row block holds a packed block."""
    counts = model.row_ptr[1:] - model.row_ptr[:-1]          # (Lp/bl,)
    return jnp.repeat(counts > 0, model.block_shape[0])


def _mask_empty_row_blocks(out: jax.Array, model) -> jax.Array:
    # Mask empty row-blocks (undefined memory in the kernel output -- may be
    # NaN in interpret mode, so select rather than multiply).
    return jnp.where(_live_labels(model)[None, :], out, 0.0)


def _kernel_scores(x: jax.Array, model: Model,
                   interpret: bool | None) -> jax.Array:
    """The kernel's (n, Lp) scores, x padded to the model's width; row
    blocks with no surviving block are not yet masked. Int8 blocks go
    through the int8 kernel, which applies each block's scale to its fp32
    dot."""
    Lp, bl = model.shape[0], model.block_shape[0]
    x = _pad_features(x, model)
    if isinstance(model, Int8BlockSparseModel):
        return bsr_predict_int8_pallas(x, model.blocks, model.scales,
                                       model.block_rows, model.block_cols,
                                       Lp // bl, interpret=interpret)
    return bsr_predict_pallas(x, model.blocks,
                              model.block_rows, model.block_cols, Lp // bl,
                              row_ptr=model.row_ptr, interpret=interpret)


def bsr_predict(x: jax.Array, model: Model,
                *, interpret: bool | None = None) -> jax.Array:
    """Scores (n, L) for a batch against a block-sparse model.

    Pads x's feature dim to the padded model shape (raising when the
    request is WIDER than the model) and zeroes out label row-blocks that
    have no surviving blocks (never visited by the kernel). Int8 scores
    match the fp32 path within the per-block quantization bound
    (|w - scale*q| <= scale/2 elementwise), at ~0.25x the weight traffic.
    """
    return _mask_empty_row_blocks(_kernel_scores(x, model, interpret), model)


def bsr_predict_topk(x: jax.Array, model: Model, k: int,
                     *, n_labels: int | None = None,
                     interpret: bool | None = None,
                     ) -> tuple[jax.Array, jax.Array]:
    """Fused predict -> top-k: (vals, idx) each (n, k), idx in true label ids.

    Padding label rows (id >= n_labels) are masked to NEG_INF before the
    top-k so a block-padded model never serves phantom labels. Fully
    pruned real labels keep their exact-zero score, matching the dense path
    (an all-zero int8 block quantizes to scale 0).
    """
    scores = _kernel_scores(x, model, interpret)
    with jax.named_scope("xmc.topk"):
        Lp = scores.shape[1]
        if topk_grouped(Lp, k):
            real = jnp.arange(Lp) < (Lp if n_labels is None else n_labels)
            return grouped_top_k(scores, k, TOPK_GROUP,
                                 _live_labels(model) & real,
                                 jnp.where(real, 0.0, NEG_INF))
        scores = _mask_empty_row_blocks(scores, model)
        if n_labels is not None and n_labels < Lp:
            ids = jnp.arange(Lp)
            scores = jnp.where(ids[None, :] < n_labels, scores, NEG_INF)
        return jax.lax.top_k(scores, k)


def topk_grouped(Lp: int, k: int) -> bool:
    """Whether `bsr_predict_topk` takes the grouped top-k over rows of Lp
    scores: rows at least `GROUPED_TOPK_MIN_LP` wide, of whole 128-label
    tiles, and k groups' candidates narrower than the row."""
    return (Lp >= GROUPED_TOPK_MIN_LP and Lp % 128 == 0
            and k * TOPK_GROUP < Lp)


def _order_keys(v: jax.Array) -> jax.Array:
    """int32 keys that order float32 values as `lax.top_k` does, -0.0
    below +0.0 (sign-magnitude to two's complement); applied twice, the
    identity."""
    i = jax.lax.bitcast_convert_type(v, jnp.int32)
    return i ^ ((i >> 31) & 0x7FFFFFFF)


def grouped_top_k(scores: jax.Array, k: int, c: int, keep: jax.Array,
                  fill: jax.Array) -> tuple[jax.Array, jax.Array]:
    """`jax.lax.top_k(jnp.where(keep, scores, fill), k)`, bit for bit, over
    (n, Lp) scores, Lp a multiple of 128, c one of 128 with k * c < Lp, and
    `fill` finite (module docstring). `keep` and `fill` are (Lp,).

    The row is cut into G = Lp // c contiguous groups of c labels and a
    tail of the Lp % c labels after them. One pass over the scores takes
    each group's maximum, the masks applied as it reads. The top k of
    those maxima choose k groups, put in ascending order. A gather takes
    each chosen group's c scores and its masks. The tail's scores join
    them as candidates outright, after them, so the candidates' ids ascend
    and the last top k breaks ties as `lax.top_k` does over the row.

    The scores are viewed as (n / r, Lp / 128, r, 128), r = gcd(n, 8): the
    (r, 128) tiles in which a TPU holds an (n, Lp) float32 array. The max
    pass reads them in place, across a group's tiles elementwise and
    across the 128 lanes once per group. A chosen group of a row is c / 128
    whole tiles of its r-row block, contiguous in memory, so the gather
    reads them in place too and keeps the row's sublane."""
    n, Lp = scores.shape
    nb, s, r = Lp // 128, c // 128, math.gcd(n, 8)
    G = nb // s
    tiles = scores.reshape(n // r, r, nb, 128).transpose(0, 2, 1, 3)
    keys = _order_keys(jnp.where(keep.reshape(nb, 1, 128), tiles,
                                 fill.reshape(nb, 1, 128)))
    low = jnp.iinfo(jnp.int32).min
    gmax = jax.lax.reduce_window(keys, low, jax.lax.max, (1, s, 1, 1),
                                 (1, s, 1, 1)).max(-1)       # (n/r, G, r)
    gmax = _order_keys(gmax.transpose(0, 2, 1).reshape(n, G))
    _, gid = jax.lax.top_k(jax.lax.bitcast_convert_type(gmax, jnp.float32),
                           k)
    # The chosen groups in ascending order, by rank.
    slot = jnp.arange(k)
    rank = (gid[:, None, :] < gid[:, :, None]).sum(-1)           # (n, k)
    gid = jnp.where(rank[:, :, None] == slot, gid[:, :, None], 0).sum(1)
    # Each chosen group's s tiles of the row's r-row block, one slice.
    row = jnp.arange(n)[:, None]
    cand = jax.lax.gather(
        tiles.reshape(n // r * nb, r, 128),
        (row // r * nb + gid * s)[..., None],
        jax.lax.GatherDimensionNumbers(offset_dims=(2, 3, 4),
                                       collapsed_slice_dims=(),
                                       start_index_map=(0,)),
        (s, r, 128), mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    mine = jnp.arange(r) == (row % r)[..., None]                  # (n, 1, r)
    cand = jnp.where(mine[:, :, None, :, None], cand, -jnp.inf).max(3)
    # Both masks in one (G, c) array, +inf where the score is kept; a
    # chosen group's row of it is whole tiles, gathered as one slice.
    mask = jnp.where(keep, jnp.inf, fill)[:G * c].reshape(G, s, 128)
    mask = mask[gid]                                         # (n, k, s, 128)
    cand = jnp.where(mask == jnp.inf, cand, mask).reshape(n, k * c)
    tail = jnp.where(keep[G * c:], scores[:, G * c:], fill[G * c:])
    vals, j = jax.lax.top_k(jnp.concatenate([cand, tail], axis=1), k)
    at = jnp.where(j[:, :, None] // c == slot, gid[:, None, :], 0).sum(-1)
    ids = jnp.where(j < k * c, at * c + j % c, j - k * c + G * c)
    return vals, ids


def max_blocks_per_row(model: BlockSparseModel) -> int:
    """Static bound on packed blocks per row block (>= 1) — the inner grid
    extent of the gathered-block kernel."""
    ptr = np.asarray(model.row_ptr)
    return max(1, int(np.max(ptr[1:] - ptr[:-1])))


def _scales(model: Model) -> jax.Array | None:
    """The per-block scales the gather kernels take for int8 blocks, None
    for fp32 ones."""
    return model.scales if isinstance(model, Int8BlockSparseModel) else None


def bsr_predict_gather(x: jax.Array, model: Model,
                       sel: jax.Array, *,
                       max_per_row: int | None = None,
                       interpret: bool | None = None) -> jax.Array:
    """Scores for ONLY the row blocks listed in `sel` (B,) int32.

    Returns (n, B * bl): columns [i*bl, (i+1)*bl) are row block sel[i]'s
    label scores. Pads x's feature dim like `bsr_predict`; a selected row
    block with no surviving blocks comes back exact-zero (the kernel
    zero-initializes every selected output tile), so pruned labels keep
    the dense path's score convention without any extra masking. At
    sel = every row block, sorted, the scores equal `bsr_predict`'s bit for
    bit, for int8 blocks too: the scale multiplies the same per-block fp32
    dot in the same order.
    """
    x = _pad_features(x, model)
    if max_per_row is None:
        max_per_row = max_blocks_per_row(model)
    return bsr_predict_gather_pallas(
        x, model.blocks, model.block_cols, model.row_ptr,
        jnp.asarray(sel, jnp.int32), max_per_row, scales=_scales(model),
        interpret=interpret)


def bsr_predict_gather_topk(x: jax.Array, model: Model,
                            sel: jax.Array, k: int, *,
                            n_labels: int | None = None,
                            max_per_row: int | None = None,
                            interpret: bool | None = None,
                            ) -> tuple[jax.Array, jax.Array]:
    """Fused gathered predict -> top-k over the shortlisted labels only.

    (vals, idx) each (n, k); idx in TRUE label ids (candidates translated
    back through `sel`). Padding labels (global id >= n_labels) are masked
    to NEG_INF before the top-k. With `sel` sorted ascending and covering
    every row block this reproduces `bsr_predict_topk` exactly, tie order
    included — the B-covers-all equivalence the shortlist backend tests
    gate on.
    """
    bl = model.block_shape[0]
    sel = jnp.asarray(sel, jnp.int32)
    scores = bsr_predict_gather(x, model, sel, max_per_row=max_per_row,
                                interpret=interpret)
    # Candidate column -> true label id, used both to mask block padding
    # and to translate the merged top-k back to label coordinates.
    label_ids = (sel[:, None] * bl + jnp.arange(bl)[None, :]).reshape(-1)
    if n_labels is not None:
        scores = jnp.where(label_ids[None, :] < n_labels, scores, NEG_INF)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, jnp.take(label_ids, idx)


def bsr_predict_gather_pq(x: jax.Array, model: Model,
                          sel: jax.Array, *,
                          max_per_row: int | None = None,
                          interpret: bool | None = None) -> jax.Array:
    """Per-query gathered scores: row q scores ONLY its blocks `sel[q]`.

    sel (n, B) int32 (each row sorted, no duplicates) -> (n, B * bl): row
    q's columns [i*bl, (i+1)*bl) are row block sel[q, i]'s label scores —
    a per-row ragged layout; the topk wrapper owns the per-row label
    translation. Same pad/zero-init conventions as `bsr_predict_gather`.
    """
    x = _pad_features(x, model)
    if max_per_row is None:
        max_per_row = max_blocks_per_row(model)
    return bsr_predict_gather_pq_pallas(
        x, model.blocks, model.block_cols, model.row_ptr,
        jnp.asarray(sel, jnp.int32), max_per_row, scales=_scales(model),
        interpret=interpret)


def bsr_predict_gather_pq_topk(x: jax.Array, model: Model,
                               sel: jax.Array, k: int, *,
                               n_labels: int | None = None,
                               max_per_row: int | None = None,
                               interpret: bool | None = None,
                               ) -> tuple[jax.Array, jax.Array]:
    """Fused per-query gathered predict -> top-k over each row's own
    shortlist. (vals, idx) each (n, k); idx in TRUE label ids (row q's
    candidates translated through sel[q]). Padding labels are masked to
    NEG_INF before the top-k, same as every other topk wrapper here."""
    bl = model.block_shape[0]
    sel = jnp.asarray(sel, jnp.int32)
    scores = bsr_predict_gather_pq(x, model, sel, max_per_row=max_per_row,
                                   interpret=interpret)
    # (n, B*bl): row q's candidate column c is label sel[q, c//bl]*bl + c%bl.
    label_ids = (sel[:, :, None] * bl
                 + jnp.arange(bl)[None, None, :]).reshape(sel.shape[0], -1)
    if n_labels is not None:
        scores = jnp.where(label_ids < n_labels, scores, NEG_INF)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, jnp.take_along_axis(label_ids, idx, axis=1)


def gather_flops(model: BlockSparseModel, n: int, sel: np.ndarray) -> int:
    """FLOPs the gathered fine stage actually executes for one batch:
    2 * n * bl * bd per surviving block of the selected row blocks."""
    bl, bd = model.block_shape
    ptr = np.asarray(model.row_ptr)
    sel = np.asarray(sel)
    n_sel_blocks = int((ptr[sel + 1] - ptr[sel]).sum())
    return 2 * n * bl * bd * n_sel_blocks


def gather_pq_flops(model: BlockSparseModel, sel: np.ndarray) -> int:
    """FLOPs of the per-query fine stage: 2 * bl * bd per surviving block
    of each ROW's selected row blocks — each query pays only for its own
    list (sel is (n, B)), which is the whole point of the ragged kernel."""
    bl, bd = model.block_shape
    ptr = np.asarray(model.row_ptr)
    sel = np.asarray(sel)
    n_sel_blocks = int((ptr[sel + 1] - ptr[sel]).sum())
    return 2 * bl * bd * n_sel_blocks


def model_flops(model: BlockSparseModel, n: int) -> int:
    """FLOPs actually executed: 2 * n * bl * bd per surviving block —
    the block-density speedup the kernel realizes over dense predict."""
    bl, bd = model.block_shape
    return 2 * n * bl * bd * model.n_blocks


def dense_flops(model: BlockSparseModel, n: int) -> int:
    Lp, Dp = model.shape
    return 2 * n * Lp * Dp


def predict_bytes(model: BlockSparseModel, n: int) -> int:
    """Bytes the exhaustive fp32 predict moves through HBM: every packed
    block once per pass the launch makes (`kernel.weight_passes`), x once,
    and the output once."""
    bl, bd = model.block_shape
    Lp, Dp = model.shape
    passes = weight_passes(n, Lp, Dp, bl)
    weights = 4 * model.n_blocks * bl * bd * passes
    return weights + 4 * n * Dp + 4 * n * Lp


def predict_bytes_int8(model, n: int) -> int:
    """Same traffic model for the int8 artifact: 1-byte blocks + 4-byte
    per-block scales; x and the fp32 output are unchanged."""
    bl, bd = model.block_shape
    Lp, Dp = model.shape
    weights = model.n_blocks * bl * bd + 4 * model.n_blocks
    x_bytes = 4 * n * Dp * (Lp // bl)
    out = 4 * n * Lp
    return weights + x_bytes + out
