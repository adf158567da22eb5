"""Public wrapper: BSR prediction over a pruned DiSMEC model.

`bsr_predict` yields the dense (n, Lp) score matrix; `bsr_predict_topk`
reduces it with `jax.lax.top_k` into the serving entry point used by
`repro.serve.xmc.BsrBackend` — scores never leave the padded block
coordinate system before being reduced to k candidates.

`bsr_predict_gather` / `bsr_predict_gather_topk` are the shortlist-gated
variants (serve/shortlist.py): given a per-batch list of selected row
blocks they score ONLY those blocks' packed tiles, so per-query compute
scales with B * block_size instead of L. With the selection covering all
row blocks (sorted) they reproduce the exhaustive path bit-for-bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pruning import BlockSparseModel, Int8BlockSparseModel
from repro.kernels.bsr_predict.kernel import (bsr_predict_gather_int8_pallas,
                                              bsr_predict_gather_pallas,
                                              bsr_predict_gather_pq_int8_pallas,
                                              bsr_predict_gather_pq_pallas,
                                              bsr_predict_int8_pallas,
                                              bsr_predict_pallas)

# Score of a masked (padding) label: loses to every real score, finite so
# no -inf reaches a comparison.
NEG_INF = float(-3.0e38)


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


def _mask_empty_row_blocks(out: jax.Array, model) -> jax.Array:
    # Mask empty row-blocks (undefined memory in the kernel output -- may be
    # NaN in interpret mode, so select rather than multiply).
    bl = model.block_shape[0]
    counts = model.row_ptr[1:] - model.row_ptr[:-1]          # (Lp/bl,)
    row_mask = jnp.repeat(counts > 0, bl)
    return jnp.where(row_mask[None, :], out, 0.0)


def bsr_predict(x: jax.Array, model: BlockSparseModel,
                *, interpret: bool | None = None) -> jax.Array:
    """Scores (n, L) for a batch against a block-sparse model.

    Pads x's feature dim to the padded model shape (raising when the
    request is WIDER than the model) and zeroes out label row-blocks that
    have no surviving blocks (never visited by the kernel).
    """
    Lp, Dp = model.shape
    bl, bd = model.block_shape
    x = _pad_features(x, model)
    out = bsr_predict_pallas(x, model.blocks, model.block_rows,
                             model.block_cols, Lp // bl, interpret=interpret)
    return _mask_empty_row_blocks(out, model)


def bsr_predict_int8(x: jax.Array, model: Int8BlockSparseModel,
                     *, interpret: bool | None = None) -> jax.Array:
    """Scores (n, L) against the int8 per-block-scaled artifact — same
    pad/mask conventions as `bsr_predict`, ~0.25x the model HBM traffic.
    Scores match the fp32 path within the per-block quantization bound
    (|w - scale*q| <= scale/2 elementwise)."""
    Lp, Dp = model.shape
    bl, bd = model.block_shape
    x = _pad_features(x, model)
    out = bsr_predict_int8_pallas(x, model.blocks, model.scales,
                                  model.block_rows, model.block_cols,
                                  Lp // bl, interpret=interpret)
    return _mask_empty_row_blocks(out, model)


def bsr_predict_topk(x: jax.Array, model: BlockSparseModel, k: int,
                     *, n_labels: int | None = None,
                     interpret: bool | None = None,
                     ) -> tuple[jax.Array, jax.Array]:
    """Fused predict -> top-k: (vals, idx) each (n, k), idx in true label ids.

    Padding label rows (id >= n_labels) are masked to NEG_INF before the
    top-k so a block-padded model never serves phantom labels. Fully
    pruned real labels keep their exact-zero score, matching the dense path.
    """
    scores = bsr_predict(x, model, interpret=interpret)
    Lp = scores.shape[1]
    if n_labels is not None and n_labels < Lp:
        ids = jnp.arange(Lp)
        scores = jnp.where(ids[None, :] < n_labels, scores, NEG_INF)
    return jax.lax.top_k(scores, k)


def bsr_predict_int8_topk(x: jax.Array, model: Int8BlockSparseModel, k: int,
                          *, n_labels: int | None = None,
                          interpret: bool | None = None,
                          ) -> tuple[jax.Array, jax.Array]:
    """Fused int8 predict -> top-k: (vals, idx) each (n, k), idx in true
    label ids — the `"int8"` backend's serving entry point. Padding labels
    are masked to NEG_INF before the top-k and fully pruned real labels
    keep their exact-zero score (an all-zero block quantizes to scale 0),
    matching the fp32 conventions."""
    scores = bsr_predict_int8(x, model, interpret=interpret)
    Lp = scores.shape[1]
    if n_labels is not None and n_labels < Lp:
        ids = jnp.arange(Lp)
        scores = jnp.where(ids[None, :] < n_labels, scores, NEG_INF)
    return jax.lax.top_k(scores, k)


def max_blocks_per_row(model: BlockSparseModel) -> int:
    """Static bound on packed blocks per row block (>= 1) — the inner grid
    extent of the gathered-block kernel."""
    ptr = np.asarray(model.row_ptr)
    return max(1, int(np.max(ptr[1:] - ptr[:-1])))


def bsr_predict_gather(x: jax.Array, model: BlockSparseModel,
                       sel: jax.Array, *,
                       max_per_row: int | None = None,
                       interpret: bool | None = None) -> jax.Array:
    """Scores for ONLY the row blocks listed in `sel` (B,) int32.

    Returns (n, B * bl): columns [i*bl, (i+1)*bl) are row block sel[i]'s
    label scores. Pads x's feature dim like `bsr_predict`; a selected row
    block with no surviving blocks comes back exact-zero (the kernel
    zero-initializes every selected output tile), so pruned labels keep
    the dense path's score convention without any extra masking.
    """
    x = _pad_features(x, model)
    if max_per_row is None:
        max_per_row = max_blocks_per_row(model)
    return bsr_predict_gather_pallas(
        x, model.blocks, model.block_cols, model.row_ptr,
        jnp.asarray(sel, jnp.int32), max_per_row, interpret=interpret)


def bsr_predict_gather_int8(x: jax.Array, model: Int8BlockSparseModel,
                            sel: jax.Array, *,
                            max_per_row: int | None = None,
                            interpret: bool | None = None) -> jax.Array:
    """Int8 scores for ONLY the row blocks listed in `sel` (B,) int32 —
    the shortlist fine stage over the quantized artifact. Same contract
    as `bsr_predict_gather` (exact-zero empty blocks included: their
    packed sentinel quantizes to zeros)."""
    x = _pad_features(x, model)
    if max_per_row is None:
        max_per_row = max_blocks_per_row(model)
    return bsr_predict_gather_int8_pallas(
        x, model.blocks, model.scales, model.block_cols, model.row_ptr,
        jnp.asarray(sel, jnp.int32), max_per_row, interpret=interpret)


def bsr_predict_gather_topk(x: jax.Array, model: BlockSparseModel,
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


def bsr_predict_gather_int8_topk(x: jax.Array, model: Int8BlockSparseModel,
                                 sel: jax.Array, k: int, *,
                                 n_labels: int | None = None,
                                 max_per_row: int | None = None,
                                 interpret: bool | None = None,
                                 ) -> tuple[jax.Array, jax.Array]:
    """Fused gathered int8 predict -> top-k: the shortlist backend's fine
    stage over the quantized artifact. Same contract as
    `bsr_predict_gather_topk` (idx in true label ids, padding masked, sorted
    full-coverage `sel` reproduces `bsr_predict_int8_topk` bit-for-bit —
    the scale multiplies the same per-block fp32 dot in the same order)."""
    bl = model.block_shape[0]
    sel = jnp.asarray(sel, jnp.int32)
    scores = bsr_predict_gather_int8(x, model, sel, max_per_row=max_per_row,
                                     interpret=interpret)
    label_ids = (sel[:, None] * bl + jnp.arange(bl)[None, :]).reshape(-1)
    if n_labels is not None:
        scores = jnp.where(label_ids[None, :] < n_labels, scores, NEG_INF)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, jnp.take(label_ids, idx)


def bsr_predict_gather_pq(x: jax.Array, model: BlockSparseModel,
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
        jnp.asarray(sel, jnp.int32), max_per_row, interpret=interpret)


def bsr_predict_gather_pq_int8(x: jax.Array, model: Int8BlockSparseModel,
                               sel: jax.Array, *,
                               max_per_row: int | None = None,
                               interpret: bool | None = None) -> jax.Array:
    """Per-query gathered int8 scores — `bsr_predict_gather_pq` over the
    quantized artifact."""
    x = _pad_features(x, model)
    if max_per_row is None:
        max_per_row = max_blocks_per_row(model)
    return bsr_predict_gather_pq_int8_pallas(
        x, model.blocks, model.scales, model.block_cols, model.row_ptr,
        jnp.asarray(sel, jnp.int32), max_per_row, interpret=interpret)


def _pq_translate_topk(scores: jax.Array, sel: jax.Array, bl: int, k: int,
                       n_labels: int | None,
                       ) -> tuple[jax.Array, jax.Array]:
    """Shared tail of the per-query topk wrappers: mask block padding per
    row and translate merged top-k back to true label ids via each row's
    own candidate list."""
    # (n, B*bl): row q's candidate column c is label sel[q, c//bl]*bl + c%bl.
    label_ids = (sel[:, :, None] * bl
                 + jnp.arange(bl)[None, None, :]).reshape(sel.shape[0], -1)
    if n_labels is not None:
        scores = jnp.where(label_ids < n_labels, scores, NEG_INF)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, jnp.take_along_axis(label_ids, idx, axis=1)


def bsr_predict_gather_pq_topk(x: jax.Array, model: BlockSparseModel,
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
    return _pq_translate_topk(scores, sel, bl, k, n_labels)


def bsr_predict_gather_pq_int8_topk(x: jax.Array,
                                    model: Int8BlockSparseModel,
                                    sel: jax.Array, k: int, *,
                                    n_labels: int | None = None,
                                    max_per_row: int | None = None,
                                    interpret: bool | None = None,
                                    ) -> tuple[jax.Array, jax.Array]:
    """Fused per-query gathered int8 predict -> top-k: same contract as
    `bsr_predict_gather_pq_topk` over the quantized artifact."""
    bl = model.block_shape[0]
    sel = jnp.asarray(sel, jnp.int32)
    scores = bsr_predict_gather_pq_int8(x, model, sel,
                                        max_per_row=max_per_row,
                                        interpret=interpret)
    return _pq_translate_topk(scores, sel, bl, k, n_labels)


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
    """Bytes the exhaustive fp32 predict must move through HBM: every
    packed block once, plus x streamed per row block, plus the output."""
    bl, bd = model.block_shape
    Lp, Dp = model.shape
    weights = 4 * model.n_blocks * bl * bd
    x_bytes = 4 * n * Dp * (Lp // bl)        # x re-read per row block
    out = 4 * n * Lp
    return weights + x_bytes + out


def predict_bytes_int8(model, n: int) -> int:
    """Same traffic model for the int8 artifact: 1-byte blocks + 4-byte
    per-block scales; x and the fp32 output are unchanged."""
    bl, bd = model.block_shape
    Lp, Dp = model.shape
    weights = model.n_blocks * bl * bd + 4 * model.n_blocks
    x_bytes = 4 * n * Dp * (Lp // bl)
    out = 4 * n * Lp
    return weights + x_bytes + out
