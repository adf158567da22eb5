"""Pure-jnp oracle for BSR prediction: dense matmul against the
densified block-sparse matrix."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.pruning import BlockSparseModel, Int8BlockSparseModel


def bsr_predict(x: jax.Array, model: BlockSparseModel) -> jax.Array:
    W = model.to_dense()
    return x.astype(jnp.float32) @ W.T.astype(jnp.float32)


def dense_topk(x: jax.Array, W: jax.Array, k: int):
    """The plain float32 reference of serving: scores = x @ W^T at the
    highest matmul precision over W's rows (the real labels), then their
    k best as (scores, label ids), each (n, k). No kernel, bucket or
    server."""
    scores = jnp.dot(x.astype(jnp.float32), W.astype(jnp.float32).T,
                     precision=jax.lax.Precision.HIGHEST)
    return jax.lax.top_k(scores, k)


def bsr_predict_int8(x: jax.Array, model: Int8BlockSparseModel) -> jax.Array:
    """Oracle for the int8 kernel: dequantize every block to fp32 (the
    exact values the kernel reconstructs in-register) then dense matmul."""
    return bsr_predict(x, model.dequantize())


def bsr_predict_gather_int8(x: jax.Array, model: Int8BlockSparseModel,
                            sel: jax.Array) -> jax.Array:
    return bsr_predict_gather(x, model.dequantize(), sel)


def bsr_predict_gather(x: jax.Array, model: BlockSparseModel,
                       sel: jax.Array) -> jax.Array:
    """Oracle for the gathered-block kernel: dense scores against the
    densified model, with the selected row blocks' label columns gathered
    into (n, B * bl) in `sel` order."""
    bl = model.block_shape[0]
    scores = bsr_predict(x, model)                       # (n, Lp)
    cols = (jnp.asarray(sel, jnp.int32)[:, None] * bl
            + jnp.arange(bl)[None, :]).reshape(-1)       # (B * bl,)
    return scores[:, cols]
