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

    got = np.asarray(K.bsr_predict_pallas(*args, row_ptr=model.row_ptr,
                                          interpret=True))
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
    (1, 1, 1), (64, 1, 64), (128, 1, 128), (208, 1, 208), (216, 1, 216),
    (256, 2, 128)])
def test_row_tiles_fit_vmem_budget(n, passes, rows):
    """At Wiki10-31K half (Lp 15,488, Dp 102,016) x and the scores fit the
    VMEM budget up to n = 208 rows; at 216 the scores do not and x does,
    so the launch streams the scores and holds all its rows; at 256 x does
    not fit either, and the launch takes equal row tiles within the
    budget, one pass over the 16-block groups each. Traced at full size,
    nothing runs."""
    import jax

    from repro.kernels.bsr_predict import kernel as K

    NB, LP, DP = 96437, 15488, 102016
    streams = K.streams_scores(n, LP, DP, 128)
    assert (K._streamed_bytes(rows, DP, 128) if streams
            else K._resident_bytes(rows, LP, DP)) <= K.VMEM_BUDGET
    assert streams or K.row_tile(n, LP, DP) == rows
    assert K.weight_passes(n, LP, DP, 128) == passes
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in [
        ((n, DP), jnp.float32), ((NB, 128, 128), jnp.float32),
        ((NB,), jnp.int32), ((NB,), jnp.int32),
        ((LP // 128 + 1,), jnp.int32)]]
    jaxpr = jax.make_jaxpr(lambda x, b, r, c, p: K.bsr_predict_pallas(
        x, b, r, c, LP // 128, row_ptr=p, interpret=False))(*shapes)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (
        (-(-LP // 128 // K.STEP_ROW_BLOCKS),) if streams
        else (passes, -(-NB // K.GROUP)))
    assert jaxpr.out_avals[0].shape == (n, LP)


# -- the layout chosen from the shapes: resident, streamed, row tiles ---------

BUCKETS = (1, 2, 4, 8, 16, 32)


def _layout_model(case):
    """16 x 16 blocks over more than 64 label row blocks, so that a score
    slice pair of the streamed layout is narrower than the scores."""
    rng = np.random.default_rng(11)
    if case == "short_rows":          # 6 blocks a row: no group is one row
        keep = np.ones((70, 6), bool)
    elif case == "ragged_rows":       # a real pruned checkpoint's rows
        keep = rng.random((75, 9)) < 0.4
        keep[[3, 40, 74]] = False     # empty row blocks, the last included
    elif case == "fully_pruned":      # one sentinel block, in no row
        keep = np.zeros((70, 6), bool)
    else:                             # "one_step_partial": 65 row blocks,
        keep = rng.random((65, 4)) < 0.7      # a last step of one
    R, C = keep.shape
    W = rng.normal(size=(R * 16, C * 16)).astype(np.float32)
    W *= np.kron(keep, np.ones((16, 16), np.float32))
    return W, to_block_sparse(jnp.asarray(W), (16, 16))


def _scores_under(budget, x, model, monkeypatch):
    from repro.kernels.bsr_predict import kernel as K
    monkeypatch.setattr(K, "VMEM_BUDGET", budget)
    out = K.bsr_predict_pallas(x, model.blocks, model.block_rows,
                               model.block_cols, model.shape[0] // 16,
                               row_ptr=model.row_ptr, interpret=True)
    monkeypatch.undo()
    return np.asarray(out)


@pytest.mark.parametrize("n", BUCKETS)
@pytest.mark.parametrize("case", ["short_rows", "ragged_rows",
                                  "one_step_partial", "fully_pruned"])
def test_layouts_are_bit_identical(case, n, monkeypatch):
    """At every bucket of a small engine, the budget is shrunk so that the
    scores of all n rows do not fit and x does (streamed), and so that
    neither does (row tiles of 8, n > 8): both return the resident
    layout's scores bit for bit on live row blocks, exact zeros on empty
    ones. The streamed walk covers a last partial step of row blocks and
    a last partial chunk of packed blocks."""
    from repro.kernels.bsr_predict import kernel as K

    W, model = _layout_model(case)
    (Lp, Dp), bl = model.shape, 16
    assert model.n_blocks % K.GROUP != 0
    assert (Lp // bl) % K.STEP_ROW_BLOCKS != 0
    x = jnp.asarray(np.random.default_rng(n).normal(size=(n, Dp)),
                    jnp.float32)
    assert not K.streams_scores(n, Lp, Dp, bl)
    resident = _scores_under(K.VMEM_BUDGET, x, model, monkeypatch)

    streamed_budget = K._streamed_bytes(n, Dp, bl)
    monkeypatch.setattr(K, "VMEM_BUDGET", streamed_budget)
    assert K.streams_scores(n, Lp, Dp, bl)
    assert K.weight_passes(n, Lp, Dp, bl) == 1
    monkeypatch.undo()
    layouts = {"streamed": _scores_under(streamed_budget, x, model,
                                         monkeypatch)}
    if n > 8:
        tiles_budget = K._resident_bytes(8, Lp, Dp)
        monkeypatch.setattr(K, "VMEM_BUDGET", tiles_budget)
        assert not K.streams_scores(n, Lp, Dp, bl)
        assert K.weight_passes(n, Lp, Dp, bl) == n // 8
        monkeypatch.undo()
        layouts["row_tiles"] = _scores_under(tiles_budget, x, model,
                                             monkeypatch)
    live = np.repeat(np.diff(np.asarray(model.row_ptr)) > 0, bl)
    for name, got in layouts.items():
        np.testing.assert_array_equal(got[:, live], resident[:, live],
                                      err_msg=name)
        assert np.all(got[:, ~live] == 0.0), name
    np.testing.assert_allclose(resident[:, :W.shape[0]], np.asarray(x) @ W.T,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,layout", [(1, "resident"), (16, "resident"),
                                      (32, "streamed"), (256, "streamed")])
def test_layout_at_lf_amazontitles_1_3m(n, layout):
    """At LF-AmazonTitles-1.3M (Lp 1,305,344, Dp 768) the scores of 16 rows
    fit beside x; wider launches stream them, one weight pass each.
    Traced at full size, nothing runs."""
    import jax

    from repro.kernels.bsr_predict import kernel as K

    NRB, NCB = 10198, 6
    NB, LP, DP = NRB * NCB, NRB * 128, NCB * 128
    assert K.streams_scores(n, LP, DP, 128) == (layout == "streamed")
    assert K.weight_passes(n, LP, DP, 128) == 1
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in [
        ((n, DP), jnp.float32), ((NB, 128, 128), jnp.float32),
        ((NB,), jnp.int32), ((NB,), jnp.int32), ((NRB + 1,), jnp.int32)]]
    jaxpr = jax.make_jaxpr(lambda x, b, r, c, p: K.bsr_predict_pallas(
        x, b, r, c, NRB, row_ptr=p, interpret=False))(*shapes)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    grid = ((-(-NRB // K.STEP_ROW_BLOCKS),) if layout == "streamed"
            else (1, -(-NB // K.GROUP)))
    assert call.params["grid_mapping"].grid == grid
    assert jaxpr.out_avals[0].shape == (n, LP)


@pytest.mark.parametrize("n", [4, 8, 16])
def test_bsr_topk_matches_the_plain_reference(n):
    """`_bsr_topk` (kernel, masks, top-k) against the plain float32
    reference at the highest precision (`ref.dense_topk`), on a pruned
    model with a padded last row block. Scores may differ by one bfloat16
    pass, each operand rounded to 8 mantissa bits: at most 2^-8 of
    sum_j |x_j w_lj|. Ids must match at each rank whose reference score is
    further than both ranks' tolerances from the ranks above and below."""
    from repro.serve.xmc import BsrBackend

    L, D, k = 1150, 200, 5            # 72 row blocks, the last one padded
    rng = np.random.default_rng(n)
    W = np.asarray(prune(jnp.asarray(
        rng.normal(size=(L, D)).astype(np.float32) * 0.03), 0.01))
    model = to_block_sparse(jnp.asarray(W), (16, 16))
    x = rng.normal(size=(n, D)).astype(np.float32)
    vals, ids = BsrBackend(model, k, n_labels=L).topk(jnp.asarray(x))
    vals, ids = np.asarray(vals), np.asarray(ids)
    ref_vals, ref_ids = (np.asarray(a) for a in ref.dense_topk(
        jnp.asarray(x), jnp.asarray(W), k + 1))
    scale = np.abs(x) @ np.abs(W).T                       # (n, L)
    tol = 2.0 ** -8 * np.take_along_axis(scale, ids, 1)
    served_ref = np.take_along_axis(x.astype(np.float64) @ W.T, ids, 1)
    assert np.all(np.abs(vals - served_ref) <= tol)
    # Position j is untied where the reference's j-th score lies more than
    # both tolerances from its neighbours' (above and below).
    ref_tol = 2.0 ** -8 * np.take_along_axis(scale, ref_ids, 1)
    apart = -np.diff(ref_vals, axis=1) > ref_tol[:, :-1] + ref_tol[:, 1:]
    untied = np.concatenate([np.ones((n, 1), bool), apart[:, :-1]], 1) \
        & apart
    assert untied.any()
    np.testing.assert_array_equal(ids[untied], ref_ids[:, :k][untied])


@pytest.mark.parametrize("budget,passes", [("default", 1), ("streamed", 1),
                                           ("row_tiles", 2)])
def test_server_counts_weight_passes(budget, passes, monkeypatch):
    """`XMCServer.stats()["weight_passes"]` sums the passes each launch
    made over the blocks: one a launch wherever x fits, as many as row
    tiles otherwise. Buckets 4 and 16: 16 rows take two tiles of 8."""
    from repro.kernels.bsr_predict import kernel as K
    from repro.serve import XMCEngine, XMCServer
    from repro.serve.xmc import BsrBackend

    _, model = _layout_model("short_rows")
    (Lp, Dp), bl = model.shape, 16
    if budget == "streamed":
        monkeypatch.setattr(K, "VMEM_BUDGET", K._streamed_bytes(16, Dp, bl))
    elif budget == "row_tiles":
        monkeypatch.setattr(K, "VMEM_BUDGET", K._resident_bytes(8, Lp, Dp))
    engine = XMCEngine(BsrBackend(model, 3, n_labels=Lp), buckets=(4, 16),
                       warmup=False, n_features=Dp)
    server = XMCServer(engine, max_batch_delay_ms=120_000.0, start=False)
    for rows in (16, 3):              # one full bucket of 16, then 3 -> 4
        server.submit(np.ones((rows, Dp), np.float32))
    server.stop()
    st = server.stats()
    assert st["batches"] == 2
    assert st["weight_passes"] == passes + 1
