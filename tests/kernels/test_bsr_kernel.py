"""Block-sparse predict kernel vs ref oracle and dense matmul."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.pruning import (prune, quantize_block_sparse,
                                to_block_sparse)
from repro.kernels.bsr_predict import ops, ref


def _sparse_W(L, D, density, seed, block=16):
    rng = np.random.default_rng(seed)
    W = rng.normal(size=(L, D)).astype(np.float32)
    # Zero whole blocks to the target density.
    nbl, nbd = L // block, D // block
    keep = rng.random((nbl, nbd)) < density
    mask = np.kron(keep, np.ones((block, block)))
    return W * mask[:L, :D]


@pytest.mark.parametrize("L,D,density", [(64, 64, 0.3), (128, 256, 0.1),
                                         (256, 128, 0.6), (64, 64, 1.0)])
@pytest.mark.parametrize("n", [1, 8])
def test_bsr_predict_allclose(L, D, density, n):
    W = _sparse_W(L, D, density, seed=L + D)
    model = to_block_sparse(jnp.asarray(W), (16, 16))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(n, D)), jnp.float32)

    out_k = ops.bsr_predict(x, model)
    out_r = ref.bsr_predict(x, model)
    out_d = np.asarray(x) @ W.T
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out_k)[:, :L], out_d,
                               rtol=1e-3, atol=1e-3)


def test_bsr_flops_accounting():
    W = _sparse_W(128, 128, 0.25, seed=7)
    model = to_block_sparse(jnp.asarray(W), (16, 16))
    assert ops.model_flops(model, 4) < ops.dense_flops(model, 4)
    ratio = ops.model_flops(model, 4) / ops.dense_flops(model, 4)
    assert abs(ratio - model.density) < 1e-9


def test_fully_pruned_model_predicts_zero():
    W = jnp.zeros((32, 32), jnp.float32)
    model = to_block_sparse(W, (16, 16))
    x = jnp.ones((2, 32), jnp.float32)
    out = ops.bsr_predict(x, model)
    assert float(jnp.max(jnp.abs(out))) == 0.0


def test_pruned_dismec_model_end_to_end(dismec_model, xmc_small_jnp):
    """The paper's serving path: prune -> BSR -> predict == dense predict."""
    _, _, Xte, _ = xmc_small_jnp
    W = prune(dismec_model.W, 0.01)
    model = to_block_sparse(W, (32, 32))
    out = ops.bsr_predict(Xte, model)
    dense = Xte @ W.T
    np.testing.assert_allclose(np.asarray(out)[:, :W.shape[0]],
                               np.asarray(dense), rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("dtype", ["fp32", "int8"])
@pytest.mark.parametrize("n", [1, 5])
@pytest.mark.parametrize("k", [1, 5])
def test_bsr_predict_topk_masks_block_padding(dtype, n, k):
    """Every real score is negative and the padding rows of the last row
    block score exactly 0: the top-k must still be the real labels'
    (ids < L), equal to lax.top_k over the dense scores."""
    import jax

    L, D = 40, 64                      # L pads to 48 at 16-row blocks
    rng = np.random.default_rng(n * 10 + k)
    W = -np.abs(rng.normal(size=(L, D))).astype(np.float32) - 0.1
    x = jnp.asarray(np.abs(rng.normal(size=(n, D))) + 0.1, jnp.float32)
    model = to_block_sparse(jnp.asarray(W), (16, 16))
    if dtype == "int8":
        model = quantize_block_sparse(model)
        dense = np.asarray(model.dequantize().to_dense())[:L, :D]
        vals, ids = ops.bsr_predict_int8_topk(x, model, k, n_labels=L)
    else:
        dense = W
        vals, ids = ops.bsr_predict_topk(x, model, k, n_labels=L)
    v_ref, i_ref = jax.lax.top_k(jnp.asarray(np.asarray(x) @ dense.T), k)
    assert np.all(np.asarray(ids) < L)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(v_ref),
                               rtol=1e-4, atol=1e-4)
