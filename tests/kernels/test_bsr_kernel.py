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


# -- several packed blocks per grid step, row tiles resident in VMEM ----------

def _per_block_pallas(x, blocks, block_rows, block_cols, n_row_blocks):
    """The exhaustive kernel before blocks were grouped, kept here as the
    reference: one grid step per packed block, coordinates
    scalar-prefetched, the (n, bl) output tile revisited along a label
    row. Row blocks with no packed block are left unwritten."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(rows_ref, cols_ref, x_ref, blk_ref, o_ref):
        k = pl.program_id(0)
        is_new_row = jnp.logical_or(
            k == 0, rows_ref[k] != rows_ref[jnp.maximum(k - 1, 0)])

        @pl.when(is_new_row)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), blk_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    n = x.shape[0]
    nb, bl, bd = blocks.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(nb,),
        in_specs=[pl.BlockSpec((n, bd), lambda k, rows, cols: (0, cols[k])),
                  pl.BlockSpec((1, bl, bd), lambda k, rows, cols: (k, 0, 0))],
        out_specs=pl.BlockSpec((n, bl), lambda k, rows, cols: (0, rows[k])))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, n_row_blocks * bl), jnp.float32),
        interpret=True)(block_rows, block_cols, x, blocks)


def _block_pattern(case):
    """(row blocks, col blocks) keep-mask of 16 x 16 blocks for each shape
    the kernel must handle; the group size there is 16."""
    rng = np.random.default_rng(3)
    if case == "ragged":              # packed count not a multiple of 16
        keep = rng.random((8, 12)) < 0.4
        keep[5] = False
    elif case == "one_block_rows":    # every group crosses row boundaries
        keep = np.zeros((20, 4), bool)
        keep[np.arange(20), rng.integers(0, 4, 20)] = True
    elif case == "long_rows":         # rows spanning several groups
        keep = np.ones((3, 40), bool)
    elif case == "whole_groups":      # packed count a multiple of 16
        keep = np.ones((2, 16), bool)
    elif case == "empty_row_blocks":
        keep = rng.random((10, 8)) < 0.5
        keep[[0, 3, 4, 9]] = False
    elif case == "several_smem_windows":   # more than 1,024 packed blocks
        keep = np.ones((10, 110), bool)
    else:                             # "fully_pruned"
        keep = np.zeros((4, 4), bool)
    return keep


def _assert_bit_identical_to_per_block(case, n):
    """Scores of the kernel equal the per-block reference's bit for bit on
    every row block that holds a packed block, and are exact zeros
    elsewhere; `ops.bsr_predict` masks the rest to zero."""
    from repro.kernels.bsr_predict import kernel as K

    keep = _block_pattern(case)
    R, C = keep.shape
    rng = np.random.default_rng(R * C + n)
    W = rng.normal(size=(R * 16, C * 16)).astype(np.float32)
    W *= np.kron(keep, np.ones((16, 16), np.float32))
    model = to_block_sparse(jnp.asarray(W), (16, 16))
    x = jnp.asarray(rng.normal(size=(n, C * 16)), jnp.float32)
    if case in ("ragged", "long_rows", "several_smem_windows"):
        assert model.n_blocks % K.GROUP != 0
    args = (x, model.blocks, model.block_rows, model.block_cols, R)

    got = np.asarray(K.bsr_predict_pallas(*args, interpret=True))
    per_block = np.asarray(_per_block_pallas(*args))
    live = np.repeat(np.diff(np.asarray(model.row_ptr)) > 0, 16)
    np.testing.assert_array_equal(got[:, live], per_block[:, live])
    assert np.all(got[:, ~live] == 0.0)
    np.testing.assert_array_equal(
        np.asarray(ops.bsr_predict(x, model)),
        np.where(live[None, :], per_block, 0.0))


@pytest.mark.parametrize("n", [1, 8, 32])
@pytest.mark.parametrize("case", ["ragged", "one_block_rows", "long_rows",
                                  "whole_groups", "empty_row_blocks",
                                  "several_smem_windows", "fully_pruned"])
def test_grouped_path_is_bit_identical_to_per_block(case, n):
    """One row tile holds the whole launch: the kernel adds the same
    per-block dots in the same order as one grid step per block."""
    _assert_bit_identical_to_per_block(case, n)


@pytest.mark.parametrize("case,n", [("ragged", 24), ("long_rows", 20),
                                    ("several_smem_windows", 17),
                                    ("one_block_rows", 40),
                                    ("empty_row_blocks", 9)])
def test_row_tiles_are_bit_identical_to_per_block(case, n, monkeypatch):
    """With a VMEM budget of 8 rows the launch takes several row tiles, one
    pass over the blocks each, the last padded where 8 does not divide n;
    the scores still equal the per-block reference's bit for bit."""
    from repro.kernels.bsr_predict import kernel as K

    R, C = _block_pattern(case).shape
    Lp, Dp = R * 16, C * 16
    monkeypatch.setattr(K, "VMEM_BUDGET", K._resident_bytes(8, Lp, Dp))
    assert K.row_tile(n, Lp, Dp) == 8
    _assert_bit_identical_to_per_block(case, n)


@pytest.mark.parametrize("n,passes,rows", [
    (1, 1, 1), (64, 1, 64), (128, 1, 128), (208, 1, 208), (216, 2, 112),
    (256, 2, 128)])
def test_row_tiles_fit_vmem_budget(n, passes, rows):
    """At Wiki10-31K half (Lp 15,488, Dp 102,016) x and the scores fit the
    VMEM budget up to n = 208 rows; wider launches take equal row tiles
    within it, one pass over the 16-block groups each. Traced at full
    size, nothing runs."""
    import jax

    from repro.kernels.bsr_predict import kernel as K

    NB, LP, DP = 96437, 15488, 102016
    assert K.row_tile(n, LP, DP) == rows
    assert K._resident_bytes(rows, LP, DP) <= K.VMEM_BUDGET
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in [
        ((n, DP), jnp.float32), ((NB, 128, 128), jnp.float32),
        ((NB,), jnp.int32), ((NB,), jnp.int32)]]
    jaxpr = jax.make_jaxpr(lambda x, b, r, c: K.bsr_predict_pallas(
        x, b, r, c, LP // 128, interpret=False))(*shapes)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (passes, -(-NB // K.GROUP))
    assert jaxpr.out_avals[0].shape == (n, LP)
