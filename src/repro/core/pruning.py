"""Model sparsity via restricted ambiguity (paper §2.2).

Weights with |w| < Delta carry "very little discriminative information"; the
paper sets them to exact zero after training (Algorithm 1, step 7), shrinking
models ~3 orders of magnitude (870 GB -> 3 GB on WikiLSHTC-325K) with no
accuracy loss at Delta = 0.01.

On TPU we additionally convert the pruned matrix to *block*-sparse form
(BSR with MXU-aligned blocks): zero blocks are skipped entirely by the
Pallas predict kernel (kernels/bsr_predict). This is the TPU-native analogue
of the paper's sparse per-batch model files (DESIGN.md §2).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def prune(W: Array, delta: float) -> Array:
    """Algorithm 1 step 7: zero all ambiguous weights |w| < delta."""
    return jnp.where(jnp.abs(W) < delta, 0.0, W)


def nnz(W: Array) -> Array:
    return jnp.sum((W != 0.0).astype(jnp.int32))


def sparsity(W: Array) -> Array:
    return 1.0 - nnz(W) / W.size


def ambiguous_fraction(W: Array, delta: float = 0.01) -> Array:
    """Fraction of weights in [-delta, delta] — paper reports 96% (Wiki-31K)
    and 99.5% (WikiLSHTC-325K)."""
    return jnp.mean((jnp.abs(W) < delta).astype(jnp.float32))


def weight_histogram(W: Array, bins: int = 81, lim: float = 0.2):
    """Histogram of learnt weights (paper Fig. 2a/2b)."""
    edges = jnp.linspace(-lim, lim, bins + 1)
    counts, _ = jnp.histogram(W.reshape(-1), bins=edges)
    return counts, edges


@dataclasses.dataclass
class BlockSparseModel:
    """Packed BSR representation of a pruned weight matrix.

    W (L, D) is tiled into (bl, bd) blocks; blocks that are entirely zero
    after Delta-pruning are dropped. The survivors are packed densely:

      blocks     : (n_blocks, bl, bd) packed nonzero blocks
      block_rows : (n_blocks,) label-block index of each packed block (sorted)
      block_cols : (n_blocks,) feature-block index of each packed block
      row_ptr    : (L/bl + 1,) CSR-style offsets into the packed arrays
      shape      : (Lp, Dp) block-padded shape of the packed matrix
      orig_shape : (L, D) true pre-padding shape — the labels/features that
                   actually exist; serving must never answer outside it

    A pytree: the arrays are its leaves and the three shapes its static
    part, so a jitted step takes the model whole and compiles once per
    geometry.
    """
    blocks: Array
    block_rows: Array
    block_cols: Array
    row_ptr: Array
    shape: tuple[int, int]
    block_shape: tuple[int, int]
    orig_shape: tuple[int, int] | None = None

    @property
    def n_labels(self) -> int:
        """True label count (pre-padding)."""
        return (self.orig_shape or self.shape)[0]

    @property
    def n_features(self) -> int:
        """True feature dim (pre-padding)."""
        return (self.orig_shape or self.shape)[1]

    @property
    def n_blocks(self) -> int:
        return int(self.blocks.shape[0])

    @property
    def density(self) -> float:
        bl, bd = self.block_shape
        total = (self.shape[0] // bl) * (self.shape[1] // bd)
        return self.n_blocks / max(total, 1)

    def to_dense(self) -> Array:
        # Host-side assembly into one numpy buffer: a single device transfer
        # instead of one functional full-matrix update per block (this is on
        # the dense/sharded backend load path).
        bl, bd = self.block_shape
        W = np.zeros(self.shape, np.asarray(self.blocks).dtype)
        rows = np.asarray(self.block_rows)
        cols = np.asarray(self.block_cols)
        blocks = np.asarray(self.blocks)
        for k in range(self.n_blocks):
            W[rows[k] * bl:(rows[k] + 1) * bl,
              cols[k] * bd:(cols[k] + 1) * bd] = blocks[k]
        return jnp.asarray(W)

    def quantize(self, *, device: bool = True) -> "Int8BlockSparseModel":
        """Symmetric per-block int8 artifact of this model (value payload
        ~0.25x, per-block fp32 scales riding alongside) — the `"int8"`
        serving backend's model. See `quantize_block_sparse`."""
        return quantize_block_sparse(self, device=device)

    def save(self, directory: str, *, meta: dict | None = None) -> None:
        """Persist as the serving checkpoint artifact (checkpoint/io.py) —
        the paper's offline model files, in packed BSR form."""
        from repro.checkpoint.io import save_block_sparse  # deferred: no cycle
        save_block_sparse(self, directory, meta=meta)

    @staticmethod
    def load(directory: str) -> tuple["BlockSparseModel", dict]:
        """Returns (model, meta). Inverse of `save`."""
        from repro.checkpoint.io import load_block_sparse
        return load_block_sparse(directory)


#: How both packed model classes split into pytree leaves and static
#: fields (`jax.tree_util.register_dataclass`, below each class).
_MODEL_ARRAYS = ("blocks", "block_rows", "block_cols", "row_ptr")
_MODEL_SHAPES = ("shape", "block_shape", "orig_shape")
jax.tree_util.register_dataclass(BlockSparseModel, data_fields=_MODEL_ARRAYS,
                                 meta_fields=_MODEL_SHAPES)


#: Symmetric int8 range: scale = max|block| / INT8_QMAX, values in
#: [-INT8_QMAX, INT8_QMAX]. -128 is never produced, so negation round-trips.
INT8_QMAX = 127


def quantize_blocks(blocks) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric per-block int8 quantization of packed (nb, bl, bd) blocks.

    Returns (q, scales): q int8 with q[k] ~= blocks[k] / scales[k], scales
    float32 (nb,) with scales[k] = max|blocks[k]| / 127. Round-to-nearest-
    even (np.rint) keeps the worst-case per-element error at scales[k] / 2.
    An all-zero block (the fully-pruned sentinel) gets scale 0 and exact
    int8 zeros. Deterministic in the fp32 blocks, so lazy quantization at
    load reproduces the persisted artifact bit-for-bit.
    """
    b = np.asarray(blocks, np.float32)
    amax = np.abs(b).max(axis=(1, 2))                       # (nb,)
    scales = (amax / INT8_QMAX).astype(np.float32)
    safe = np.where(scales > 0.0, scales, 1.0)[:, None, None]
    q = np.clip(np.rint(b / safe), -INT8_QMAX, INT8_QMAX).astype(np.int8)
    return q, scales


def dequantize_blocks(q, scales) -> np.ndarray:
    """Inverse of `quantize_blocks` up to the rounding error bound."""
    return (np.asarray(q, np.float32)
            * np.asarray(scales, np.float32)[:, None, None])


@dataclasses.dataclass
class Int8BlockSparseModel:
    """Packed BSR with symmetric per-block int8 values + fp32 scales.

    The serving-side compression artifact (paper §4.2's model-size lever,
    taken one step past (value, index) pairs): each surviving (bl, bd)
    block stores int8 values and ONE fp32 scale, quartering the dominant
    payload — the predict kernel is bandwidth-bound, so HBM traffic drops
    with it. Coordinates (`block_rows` / `block_cols` / `row_ptr`) and
    shapes are shared with the fp32 `BlockSparseModel` it was quantized
    from; the int8 Pallas kernels dequantize in-register against the
    per-block scale and accumulate in fp32. A pytree like the fp32 model,
    with `scales` one more leaf.
    """
    blocks: Array                     # (n_blocks, bl, bd) int8
    scales: Array                     # (n_blocks,) float32
    block_rows: Array
    block_cols: Array
    row_ptr: Array
    shape: tuple[int, int]
    block_shape: tuple[int, int]
    orig_shape: tuple[int, int] | None = None

    @property
    def n_labels(self) -> int:
        return (self.orig_shape or self.shape)[0]

    @property
    def n_features(self) -> int:
        return (self.orig_shape or self.shape)[1]

    @property
    def n_blocks(self) -> int:
        return int(self.blocks.shape[0])

    def payload_bytes(self) -> int:
        """Bytes of the quantized value payload (int8 blocks + scales) —
        what the predict kernel streams from HBM per full pass."""
        return (int(np.prod(self.blocks.shape))
                + 4 * int(self.scales.shape[0]))

    def dequantize(self) -> "BlockSparseModel":
        """Back to a float32 `BlockSparseModel` (within the rounding
        bound) — reference/debug path, never used by the serving kernels."""
        return BlockSparseModel(
            blocks=jnp.asarray(dequantize_blocks(self.blocks, self.scales)),
            block_rows=self.block_rows, block_cols=self.block_cols,
            row_ptr=self.row_ptr, shape=self.shape,
            block_shape=self.block_shape, orig_shape=self.orig_shape)


jax.tree_util.register_dataclass(Int8BlockSparseModel,
                                 data_fields=("blocks", "scales",
                                              *_MODEL_ARRAYS[1:]),
                                 meta_fields=_MODEL_SHAPES)


def quantize_block_sparse(model: "BlockSparseModel",
                          *, device: bool = True) -> Int8BlockSparseModel:
    """Quantize a packed fp32 model to the int8 serving artifact. The
    coordinate arrays are shared (not copied); `device=False` keeps the
    new arrays numpy for host-side checkpoint writers."""
    q, scales = quantize_blocks(model.blocks)
    put = jnp.asarray if device else np.asarray
    return Int8BlockSparseModel(
        blocks=put(q), scales=put(scales),
        block_rows=model.block_rows, block_cols=model.block_cols,
        row_ptr=model.row_ptr, shape=model.shape,
        block_shape=model.block_shape, orig_shape=model.orig_shape)


def to_block_sparse(W: Array, block_shape: tuple[int, int] = (128, 128),
                    pad_value: float = 0.0, *, row_block_offset: int = 0,
                    sentinel_if_empty: bool = True,
                    device: bool = True) -> BlockSparseModel:
    """Convert a (pruned) dense matrix to packed BSR. Host-side (numpy):
    model conversion happens once, offline, like the paper's model files.

    Append/row-offset form (streaming training, train/xmc.py): with
    `row_block_offset=k` the result describes rows [k*bl, k*bl + L) of a
    larger matrix — `block_rows` are offset into the enclosing matrix while
    `shape` and `row_ptr` stay local to this slice, so consecutive slices
    concatenate with `concat_block_sparse` without re-tiling any block.
    `sentinel_if_empty=False` lets an all-zero slice stay truly empty
    (0 packed blocks) instead of carrying the single-zero-block sentinel
    the standalone kernels expect.

    `device=False` keeps the packed arrays as numpy instead of jnp.
    The streaming checkpoint writer consumes them host-side immediately —
    and its background worker must not enqueue device puts that would
    contend with in-flight batch solves (train/xmc.py overlap mode); a
    serving-bound conversion should keep the default and land on device.
    """
    Wn = np.asarray(W)
    L, D = Wn.shape
    bl, bd = block_shape
    Lp = ((L + bl - 1) // bl) * bl
    Dp = ((D + bd - 1) // bd) * bd
    if (Lp, Dp) != (L, D):
        Wp = np.full((Lp, Dp), pad_value, Wn.dtype)
        Wp[:L, :D] = Wn
        Wn = Wp
    nbl, nbd = Lp // bl, Dp // bd
    tiles = Wn.reshape(nbl, bl, nbd, bd).transpose(0, 2, 1, 3)  # (nbl, nbd, bl, bd)
    nonzero = np.abs(tiles).max(axis=(2, 3)) > 0.0              # (nbl, nbd)
    rows, cols = np.nonzero(nonzero)                            # row-major sorted
    blocks = tiles[rows, cols]                                  # (n_blocks, bl, bd)
    counts = np.bincount(rows, minlength=nbl)
    row_ptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    if blocks.shape[0] == 0 and sentinel_if_empty:              # fully pruned
        blocks = np.zeros((1, bl, bd), Wn.dtype)
        rows = np.zeros((1,), np.int64)
        cols = np.zeros((1,), np.int64)
        row_ptr = np.zeros(nbl + 1, np.int32)
    put = jnp.asarray if device else np.asarray
    return BlockSparseModel(
        blocks=put(blocks),
        block_rows=put((rows + row_block_offset).astype(np.int32)),
        block_cols=put(cols.astype(np.int32)),
        row_ptr=put(row_ptr),
        shape=(Lp, Dp), block_shape=block_shape, orig_shape=(L, D))


def concat_block_sparse(parts: list[BlockSparseModel],
                        orig_shape: tuple[int, int]) -> BlockSparseModel:
    """Stack per-batch BSR slices (append form, consecutive row ranges) into
    one servable model without touching any packed block.

    Every part must have been produced by `to_block_sparse(...,
    row_block_offset=<its global start block>)` with the same block shape,
    the same (padded) feature width, and row-block-aligned starts — exactly
    what the streaming trainer emits. The merge is pure bookkeeping:
    blocks/rows/cols concatenate, and each part's local row_ptr is shifted
    by the packed-block count of everything before it.
    """
    if not parts:
        raise ValueError("concat_block_sparse needs at least one part")
    bl, bd = parts[0].block_shape
    Dp = parts[0].shape[1]
    blocks, rows, cols, row_ptr = [], [], [], [np.zeros(1, np.int32)]
    row_block_off = 0
    n_packed = 0
    for p in parts:
        if p.block_shape != (bl, bd) or p.shape[1] != Dp:
            raise ValueError("parts disagree on block shape / feature width")
        p_rows = np.asarray(p.block_rows, np.int64)
        p_ptr = np.asarray(p.row_ptr, np.int64)
        n_p = int(p_ptr[-1])            # packed blocks (0 for empty parts;
        if n_p:                         # the sentinel would report ptr[-1]=0)
            if p_rows[0] < row_block_off:
                raise ValueError("part rows overlap the previous part")
            blocks.append(np.asarray(p.blocks)[:n_p])
            rows.append(p_rows[:n_p])
            cols.append(np.asarray(p.block_cols, np.int64)[:n_p])
        row_ptr.append(p_ptr[1:] + n_packed)
        n_packed += n_p
        row_block_off += p.shape[0] // bl
    L, D = orig_shape
    Lp, Dp_full = row_block_off * bl, Dp
    if Lp < L or Dp_full < D:
        raise ValueError(f"parts cover ({Lp}, {Dp_full}), need {orig_shape}")
    if n_packed == 0:                                           # fully pruned
        return BlockSparseModel(
            blocks=jnp.zeros((1, bl, bd), jnp.float32),
            block_rows=jnp.zeros((1,), jnp.int32),
            block_cols=jnp.zeros((1,), jnp.int32),
            row_ptr=jnp.zeros(row_block_off + 1, jnp.int32),
            shape=(Lp, Dp_full), block_shape=(bl, bd), orig_shape=orig_shape)
    return BlockSparseModel(
        blocks=jnp.asarray(np.concatenate(blocks, axis=0)),
        block_rows=jnp.asarray(np.concatenate(rows), jnp.int32),
        block_cols=jnp.asarray(np.concatenate(cols), jnp.int32),
        row_ptr=jnp.asarray(np.concatenate(row_ptr), jnp.int32),
        shape=(Lp, Dp_full), block_shape=(bl, bd), orig_shape=orig_shape)
