"""Shortlist layer: pluggable coarse-stage scoring for sub-linear serving.

Every exhaustive `PredictBackend` scores all L labels per query — the wall
between this reproduction and the paper's 670k-label regime at production
traffic. Both XMC surveys in PAPERS.md document a candidate-selection stage
as the standard path to sub-linear inference; this module is that stage,
shaped for the packed BSR artifact the rest of the repo already serves:

  * The *unit of shortlisting is the BSR row block* (bl consecutive
    labels), because that is the granularity at which the fine stage —
    `kernels/bsr_predict.ops.bsr_predict_gather_topk` — can skip work
    without breaking the MXU-tiled matmul structure.
  * The coarse model is pluggable (`ShortlistArtifact.kind`):

      "centroid"  one (R, Dp) matrix of row-block centroids (R = Lp / bl):
                  row r is the mean of the bl label weight rows of block r,
                  computed directly from the packed blocks (never
                  densifying W). Unlearned, free to build, the v1 format.
      "learned"   a trained one-vs-rest linear meta-classifier over row
                  blocks: row r of the (R, Dp) matrix is the TRON-solved
                  weight vector of the binary problem "does this document
                  have a positive label inside block r?" — the same
                  `make_batch_solver` that trains the fine model, run once
                  over R block-membership problems at finalize time. Both
                  surveys report learned coarse stages dominating centroid
                  heuristics at equal recall; the serving benchmark gates
                  that here (strictly lower candidate fraction at
                  recall@5 >= 0.95).
      "tree"      a fixed-depth routing tree adapted from
                  `baselines/fastxml.py`'s node splitting: internal nodes
                  are mean-difference hyperplanes over the training
                  documents, leaves score row blocks by positive-block
                  frequency among the documents routed there. Routing a
                  query is `depth` dot products + one (R,) lookup —
                  O(depth * D + R) instead of O(R * D) coarse work.

    Either way coarse scoring stays one small dense op per query and the
    fine stage is unchanged.
  * Selection takes the top-B row blocks — shared across the micro-batch
    (max over per-query coarse scores: one selection, shapes static) or
    *per query* (`per_query=True` on the backend: each query gets its own
    top-B list, served by the ragged-gather kernel, so easy queries stop
    paying for the union's width). Compute scales with B * bl * D + R * D,
    not L * D.

The artifact is built at checkpoint-save/finalize time (`build_shortlist`
for centroids — free, always written) and optionally *upgraded* to a
learned/tree coarse stage by `fit()` once training data is still in hand
(`checkpoint.io.upgrade_shortlist`). It is persisted next to the BSR
arrays as `shortlist.npz` (v2 format: explicit `version`/`kind` keys;
v1 files — no version key — load as kind="centroid"). Checkpoints without
any artifact (written before PR 6) keep serving: the "shortlist" backend
falls back to exhaustive BSR scoring when `load_shortlist` finds nothing.

This module also owns the pack-time label-reorder policy
(`cooccurrence_label_order`): a deterministic co-occurrence clustering
permutation that makes real label spaces block-local the way the clustered
demo data already is — trained under `Y[:, order]`, recorded in the
manifest as `label_order`, unmapped exactly at serve time by `XMCEngine`.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

#: On-disk format version written by checkpoint/io.py::save_shortlist.
#: v1 (PR 6) had no version/kind keys and is always a centroid artifact.
SHORTLIST_VERSION = 2

SHORTLIST_KINDS = ("centroid", "learned", "tree")


@dataclasses.dataclass
class ShortlistArtifact:
    """The coarse stage of two-stage scoring, built from a packed BSR model.

    centroids   : (R, Dp) float32 coarse scoring matrix (block-padded
                  feature width). For kind="centroid" row r is the mean
                  weight vector of the bl labels in BSR row block r; for
                  kind="learned" it is the trained one-vs-rest weight
                  vector of block r's membership problem. For kind="tree"
                  it is the centroid fallback (kept so validation and
                  downgrades always work); routing uses the tree arrays.
    block_rows  : bl, the row-block height the coarse stage summarizes.
                  Must match the served model's block height.
    n_labels    : true (pre-padding) label count of the source model.
    stat        : reducer/trainer tag ("mean" for centroids, "ovr" for the
                  learned meta-classifier, "fastxml" for the tree).
    kind        : which coarse scorer this is ("centroid" | "learned" |
                  "tree"). v1 artifacts load as "centroid".
    tree_nodes  : (2^depth - 1, Dp) float32 — level-order internal-node
                  hyperplanes (kind="tree" only; node i's children are
                  2i+1 / 2i+2; x routes right iff x @ w >= 0).
    tree_leaf_scores : (2^depth, R) float32 — per-leaf row-block scores.
    tree_depth  : routing depth (0 when kind != "tree").
    """
    centroids: np.ndarray
    block_rows: int
    n_labels: int
    stat: str = "mean"
    kind: str = "centroid"
    tree_nodes: Optional[np.ndarray] = None
    tree_leaf_scores: Optional[np.ndarray] = None
    tree_depth: int = 0

    @property
    def n_row_blocks(self) -> int:
        return int(self.centroids.shape[0])

    def default_blocks(self) -> int:
        """Default shortlist width B when `ServeSpec.shortlist_blocks` is
        unset: 1/8 of the row blocks (12.5% candidate fraction), floored
        at 1 — comfortably inside the <25% regime the serving benchmark
        gates on while leaving recall headroom."""
        return max(1, -(-self.n_row_blocks // 8))

    def validate_against(self, model) -> "ShortlistArtifact":
        """Shape-check against the `BlockSparseModel` it will gate."""
        bl = model.block_shape[0]
        R = model.shape[0] // bl
        if self.block_rows != bl or self.centroids.shape != (R, model.shape[1]):
            raise ValueError(
                f"shortlist artifact ({self.centroids.shape} centroids, "
                f"block_rows={self.block_rows}) does not match model "
                f"(shape {model.shape}, block height {bl}); rebuild it with "
                "build_shortlist(model)")
        if self.kind not in SHORTLIST_KINDS:
            raise ValueError(f"unknown shortlist kind {self.kind!r}; "
                             f"expected one of {SHORTLIST_KINDS}")
        if self.kind == "tree":
            d = int(self.tree_depth)
            if (self.tree_nodes is None or self.tree_leaf_scores is None
                    or d < 1
                    or self.tree_nodes.shape != (2 ** d - 1,
                                                 model.shape[1])
                    or self.tree_leaf_scores.shape != (2 ** d, R)):
                raise ValueError(
                    "tree shortlist artifact is inconsistent: depth "
                    f"{self.tree_depth}, nodes "
                    f"{None if self.tree_nodes is None else self.tree_nodes.shape}, "
                    f"leaf_scores "
                    f"{None if self.tree_leaf_scores is None else self.tree_leaf_scores.shape}"
                    f" for model shape {model.shape}")
        return self


def build_shortlist(model) -> ShortlistArtifact:
    """Build the coarse centroid matrix from a packed `BlockSparseModel`.

    Works entirely on the packed arrays: each surviving (bl, bd) block
    contributes its column sums to its row block's centroid slice, then
    every centroid is divided by bl. Deterministic (packed blocks are
    row-major sorted), so cooperative multi-worker finalizes write
    byte-identical artifacts.
    """
    bl, bd = model.block_shape
    Lp, Dp = model.shape
    R = Lp // bl
    row_ptr = np.asarray(model.row_ptr)
    rows = np.asarray(model.block_rows)
    cols = np.asarray(model.block_cols)
    blocks = np.asarray(model.blocks, dtype=np.float32)
    C = np.zeros((R, Dp), np.float32)
    # row_ptr[-1] is the packed-block count; the all-pruned sentinel model
    # carries one zero block with row_ptr all zeros, which this skips.
    for k in range(int(row_ptr[-1])):
        r, c = int(rows[k]), int(cols[k])
        C[r, c * bd:(c + 1) * bd] += blocks[k].sum(axis=0)
    C /= float(bl)
    return ShortlistArtifact(centroids=C, block_rows=bl,
                             n_labels=model.n_labels, stat="mean")


def block_membership(Y, *, block_rows: int, n_row_blocks: int) -> np.ndarray:
    """(N, L) label matrix -> (N, R) 0/1 block-membership targets: document
    i is positive for row block r iff any of its positive labels lands in
    packed rows [r*bl, (r+1)*bl). Y must already be in *packed* label order
    (apply `label_order` first when the checkpoint was reordered)."""
    Yn = np.asarray(Y)
    N, L = Yn.shape
    Yb = np.zeros((N, n_row_blocks), np.float32)
    for r in range(n_row_blocks):
        lo, hi = r * block_rows, min((r + 1) * block_rows, L)
        if lo < L:
            Yb[:, r] = (Yn[:, lo:hi] > 0).any(axis=1)
    return Yb


def build_learned_shortlist(model, X, Y, *, C: float = 1.0,
                            max_newton: int = 20,
                            eps: float = 0.01) -> ShortlistArtifact:
    """Train the one-vs-rest coarse meta-classifier over row blocks.

    Reuses the fine model's TRON batch solver: R binary problems ("does
    this document hit block r?") solved as one batch, unpruned (delta=0 —
    the coarse matrix is (R, Dp) dense and tiny next to the fine model),
    then padded to the model's block-padded feature width. Deterministic
    for fixed (X, Y, model), so cooperative finalizers that race the
    upgrade write byte-identical artifacts.

    Y must be in *packed* label order (same convention as
    `block_membership`).
    """
    import jax.numpy as jnp
    from repro.core.dismec import DiSMECConfig, make_batch_solver

    bl = model.block_shape[0]
    Lp, Dp = model.shape
    R = Lp // bl
    Xn = np.asarray(X, np.float32)
    Yb = block_membership(Y, block_rows=bl, n_row_blocks=R)
    signs = (2.0 * Yb.T - 1.0).astype(np.float32)          # (R, N)
    cfg = DiSMECConfig(C=C, delta=0.0, eps=eps, max_newton=max_newton)
    solver = make_batch_solver(jnp.asarray(Xn), cfg)
    W = np.asarray(solver(jnp.asarray(signs), None).W)     # (R, D)
    Wp = np.zeros((R, Dp), np.float32)
    Wp[:, :W.shape[1]] = W
    return ShortlistArtifact(centroids=Wp, block_rows=bl,
                             n_labels=model.n_labels, stat="ovr",
                             kind="learned")


def build_tree_shortlist(model, X, Y, *, depth: int = 3,
                         seed: int = 0) -> ShortlistArtifact:
    """Build the fixed-depth routing tree coarse stage (fastxml-style).

    Adapts `baselines/fastxml.py`'s node splitting to the row-block
    targets: each internal node starts from a seeded random hyperplane and
    is refined by three mean-difference iterations (w = mu_right -
    mu_left over the node's documents); leaves score row blocks by the
    positive-block frequency of the documents routed there. The tree is
    complete (every query routes `depth` steps — jittable with static
    shapes); a leaf that receives no training documents inherits the
    nearest ancestor's scores so routing never hits an all-zero coarse
    row. Deterministic for fixed (X, Y, depth, seed).

    The returned artifact keeps the centroid matrix as `centroids` (the
    validation anchor and downgrade path); routing uses
    tree_nodes/tree_leaf_scores.
    """
    bl = model.block_shape[0]
    Lp, Dp = model.shape
    R = Lp // bl
    Xn = np.asarray(X, np.float32)
    N, D = Xn.shape
    Yb = block_membership(Y, block_rows=bl, n_row_blocks=R)
    rng = np.random.default_rng(seed)

    n_nodes = 2 ** depth - 1
    n_leaves = 2 ** depth
    nodes = np.zeros((n_nodes, Dp), np.float32)
    # node_scores[i] = block frequency over docs at node i (internal and
    # leaf level); leaves inherit from ancestors when empty.
    members: dict[int, np.ndarray] = {0: np.arange(N)}
    scores: dict[int, np.ndarray] = {}
    for i in range(n_nodes + n_leaves):
        idx = members.get(i, np.arange(0))
        if idx.size:
            freq = Yb[idx].sum(axis=0)
            scores[i] = (freq / max(float(freq.max()), 1.0)).astype(
                np.float32)
        else:
            # Inherit: parent of node i is (i - 1) // 2; node 0 always has
            # members, so the walk terminates.
            scores[i] = scores[(i - 1) // 2]
        if i >= n_nodes:
            continue                                   # leaf: no split
        w = rng.standard_normal(D).astype(np.float32)  # drawn per node, in
        if idx.size >= 2:                              # level order: stable
            for _ in range(3):                         # mean-difference
                side = Xn[idx] @ w >= 0.0              # refinement à la
                if side.all() or not side.any():       # fastxml
                    break
                w = (Xn[idx[side]].mean(axis=0)
                     - Xn[idx[~side]].mean(axis=0)).astype(np.float32)
            side = Xn[idx] @ w >= 0.0
            if side.all() or not side.any():
                w = np.zeros(D, np.float32)            # degenerate: all right
                side = np.ones(idx.size, bool)
            nodes[i, :D] = w
            members[2 * i + 1] = idx[~side]
            members[2 * i + 2] = idx[side]
        else:
            members[2 * i + 1] = np.arange(0)
            members[2 * i + 2] = idx                   # w = 0 routes right
    leaf_scores = np.stack([scores[n_nodes + j] for j in range(n_leaves)])
    base = build_shortlist(model)
    return ShortlistArtifact(centroids=base.centroids, block_rows=bl,
                             n_labels=model.n_labels, stat="fastxml",
                             kind="tree", tree_nodes=nodes,
                             tree_leaf_scores=leaf_scores.astype(np.float32),
                             tree_depth=int(depth))


def coarse_scores(artifact: ShortlistArtifact, x) -> np.ndarray:
    """(n, D*) queries -> (n, R) coarse row-block scores, host-side (the
    reference implementation the jitted serving paths mirror; used by
    tests and introspection). Pads/truncates x to the artifact's feature
    width."""
    xn = np.asarray(x, np.float32)
    Dp = artifact.centroids.shape[1]
    if xn.shape[1] < Dp:
        xn = np.concatenate(
            [xn, np.zeros((xn.shape[0], Dp - xn.shape[1]), np.float32)],
            axis=1)
    xn = xn[:, :Dp]
    if artifact.kind == "tree":
        idx = np.zeros(xn.shape[0], np.int64)
        for _ in range(int(artifact.tree_depth)):
            go_right = (xn * artifact.tree_nodes[idx]).sum(axis=1) >= 0.0
            idx = 2 * idx + 1 + go_right
        leaf = idx - (2 ** int(artifact.tree_depth) - 1)
        return artifact.tree_leaf_scores[leaf]
    return xn @ artifact.centroids.T


def cooccurrence_label_order(Y, *, block_rows: int) -> np.ndarray:
    """Deterministic co-occurrence clustering permutation over labels.

    Greedy block seriation: seed each row block with the most frequent
    unplaced label, then repeatedly append the unplaced label with the
    highest co-occurrence count against the block's current members
    (frequency, then smallest id, break ties) until the block holds
    `block_rows` labels. Co-occurring labels land in the same BSR row
    block, so a B-block shortlist covers correlated top-k sets — the
    locality the clustered demo data has by construction, manufactured
    for real label spaces at pack time.

    Returns `order` (L,) int64 with `order[packed_pos] = original_label`:
    train under `Y[:, order]`, serve packed top-k ids through
    `order[idx]`. O(L^2) memory/time — fine at the scales this repo
    trains; the docstring is the contract, the policy is replaceable.
    """
    Yn = (np.asarray(Y) > 0).astype(np.float32)
    L = Yn.shape[1]
    co = Yn.T @ Yn                                    # (L, L) co-occurrence
    freq = np.diag(co).copy()
    np.fill_diagonal(co, 0.0)
    placed = np.zeros(L, bool)
    order = np.empty(L, np.int64)
    pos = 0
    while pos < L:
        # Seed: most frequent unplaced label (smallest id on ties).
        seed_scores = np.where(placed, -1.0, freq)
        seed = int(np.argmax(seed_scores))
        order[pos] = seed
        placed[seed] = True
        pos += 1
        affinity = co[seed].copy()
        for _ in range(min(block_rows - 1, L - pos)):
            cand = np.where(placed, -1.0, affinity)
            if cand.max() <= 0.0:          # nothing co-occurs: next seed
                break
            nxt = int(np.argmax(cand))
            order[pos] = nxt
            placed[nxt] = True
            pos += 1
            affinity += co[nxt]
    return order
