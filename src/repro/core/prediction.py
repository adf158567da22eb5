"""Backend-agnostic XMC scoring + ranking metrics (paper §2.2.1, §3.2).

This module is the *scoring layer* of the serving subsystem: pure functions
from (X, W) to scores / top-k, with no request-side machinery. The serving
engine (`repro.serve.xmc`) wraps these behind a common `PredictBackend`
protocol — `predict_topk` backs the dense backend, `predict_topk_sharded`
backs the mesh-sharded backend, and the block-sparse Pallas path lives in
`repro.kernels.bsr_predict`.

The paper stores the per-batch block matrices W^1..W^B on separate nodes and
evaluates <w_l, x> for all blocks in parallel, then merges to a top-k. On the
mesh, W is label-sharded over `model`; each device computes its shard's
scores, takes a *local* top-k, and only the (k x n_shards) candidates are
gathered and merged — never the full L-dimensional score vector. That is the
collective-frugal form of the paper's distributed prediction.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


Array = jax.Array


def predict_scores(X: Array, W: Array) -> Array:
    """Dense score matrix (n, L) = X @ W^T."""
    return X @ W.T


def predict_topk(X: Array, W: Array, k: int = 5) -> tuple[Array, Array]:
    """Top-k labels per test instance. Returns (scores, indices), (n, k)."""
    return jax.lax.top_k(predict_scores(X, W), k)


def predict_topk_sharded(X: Array, W: Array, k: int, mesh: Mesh,
                         *, label_axis: str = "model",
                         n_labels: int | None = None) -> tuple[Array, Array]:
    """Label-sharded distributed prediction with local-topk + global merge.

    X : (n, D) replicated test batch, W : (L, D) with L divisible by shard
    count. `n_labels` masks padding rows (label id >= n_labels) out of the
    merge so a row-padded W never serves phantom labels.
    """
    n_shards = mesh.shape[label_axis]
    L = W.shape[0]
    assert L % n_shards == 0, "pad labels before sharding"
    shard_size = L // n_shards

    def shard_fn(X_sh, W_sh):
        scores = X_sh @ W_sh.T                             # (n, L/shard)
        offset = jax.lax.axis_index(label_axis) * shard_size
        if n_labels is not None and n_labels < L:
            local_ids = offset + jnp.arange(shard_size)
            scores = jnp.where(local_ids[None, :] < n_labels, scores,
                               jnp.float32(-3.0e38))
        s_loc, i_loc = jax.lax.top_k(scores, k)            # local top-k
        # Globalize label indices of this shard.
        i_loc = i_loc + offset
        # Merge across shards: gather k*n_shards candidates, re-top-k.
        s_all = jax.lax.all_gather(s_loc, label_axis, axis=1, tiled=True)
        i_all = jax.lax.all_gather(i_loc, label_axis, axis=1, tiled=True)
        s_top, pos = jax.lax.top_k(s_all, k)
        i_top = jnp.take_along_axis(i_all, pos, axis=1)
        return s_top, i_top

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(P(), P(label_axis, None)),
                       out_specs=(P(), P()), check_vma=False)
    return fn(X, W)


# ---------------------------------------------------------------------------
# Metrics (paper §3.2). Y_true is (n, L) multi-hot; topk_idx is (n, k).
# ---------------------------------------------------------------------------

def precision_at_k(Y_true: Array, topk_idx: Array, k: int) -> Array:
    """P@k = (1/k) sum_{l in rank_k(yhat)} y_l   (averaged over instances)."""
    hits = jnp.take_along_axis(Y_true, topk_idx[:, :k], axis=1)
    return jnp.mean(jnp.sum(hits, axis=1) / k)


def ndcg_at_k(Y_true: Array, topk_idx: Array, k: int) -> Array:
    """nDCG@k with the paper's normalization: DCG@k / sum_{l=1..min(k,|y|)} 1/log2(l+1)."""
    hits = jnp.take_along_axis(Y_true, topk_idx[:, :k], axis=1)     # (n, k)
    ranks = jnp.arange(1, k + 1, dtype=jnp.float32)
    dcg = jnp.sum(hits / jnp.log2(ranks + 1.0), axis=1)
    n_pos = jnp.sum(Y_true, axis=1)
    denom_terms = 1.0 / jnp.log2(ranks + 1.0)
    cum = jnp.cumsum(denom_terms)
    idx = jnp.clip(jnp.minimum(n_pos, k).astype(jnp.int32) - 1, 0, k - 1)
    norm = cum[idx]
    return jnp.mean(jnp.where(n_pos > 0, dcg / norm, 0.0))


def evaluate(Y_true: Array, topk_idx: Array,
             ks: tuple[int, ...] = (1, 3, 5)) -> dict[str, float]:
    out = {}
    for k in ks:
        out[f"P@{k}"] = float(precision_at_k(Y_true, topk_idx, k))
        out[f"nDCG@{k}"] = float(ndcg_at_k(Y_true, topk_idx, k))
    return out
