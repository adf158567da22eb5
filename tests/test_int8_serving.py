"""Int8 block-quantized serving: quantizer round-trip bounds, int8 kernel
parity (exhaustive + gathered) against the dequantize oracle, fp32/int8
top-k agreement across block densities, checkpoint persistence vs lazy
quantization (single-shard + stream), the ServeSpec knob, the D > Dp
guard on every predict wrapper, and warm-up ledger isolation."""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from repro.checkpoint.io import (BSR_ARRAYS, BlockSparseWriter,
                                 load_block_sparse, load_block_sparse_int8,
                                 save_block_sparse)
from repro.core.pruning import (INT8_QMAX, Int8BlockSparseModel,
                                dequantize_blocks, quantize_block_sparse,
                                quantize_blocks, to_block_sparse)
from repro.kernels.bsr_predict import ops as bsr_ops
from repro.kernels.bsr_predict import ref as bsr_ref
from repro.serve import (XMCEngine, build_shortlist, make_backend,
                         reset_warmup_cache, warmup_cache_stats)
from repro.specs import ServeSpec


def _block_sparse_W(L, D, density, seed, block=(16, 128),
                    guarantee_blocks=False):
    """Dense W whose zero pattern is aligned to the BSR block grid, with
    `density` the fraction of surviving blocks. `guarantee_blocks` pins
    two blocks on so low densities never zero the whole matrix."""
    bl, bd = block
    rng = np.random.default_rng(seed)
    W = rng.normal(size=(L, D)).astype(np.float32)
    keep = rng.random((L // bl + (L % bl > 0),
                       D // bd + (D % bd > 0))) < density
    if guarantee_blocks:
        keep[0, 0] = keep[-1, -1] = True
    mask = np.kron(keep, np.ones((bl, bd)))
    return W * mask[:L, :D]


# ---------------------------------------------------------------------------
# Quantizer: round-trip bound, zero-block convention, int8 range
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("density", [0.1, 0.5, 1.0])
def test_quantize_roundtrip_error_bound(density):
    """Symmetric per-block int8: |deq - orig| <= scale/2 elementwise (the
    rounding bound), all-zero blocks come back EXACTLY zero (scale 0, so a
    Delta-pruned label still scores a bit-exact 0.0), and no block value
    ever hits -128 (negation must round-trip)."""
    W = _block_sparse_W(96, 256, density, seed=int(density * 10))
    model = to_block_sparse(jnp.asarray(W), (16, 128))
    blocks = np.asarray(model.blocks)
    q, scales = quantize_blocks(blocks)
    assert q.dtype == np.int8 and scales.dtype == np.float32
    assert int(q.min()) >= -INT8_QMAX
    deq = dequantize_blocks(q, scales)
    bound = scales[:, None, None] / 2 + 1e-7
    assert np.all(np.abs(deq - blocks) <= bound)
    zero = np.all(blocks == 0.0, axis=(1, 2))
    if zero.any():
        assert np.all(scales[zero] == 0.0)
        assert np.all(deq[zero] == 0.0)


def test_model_quantize_method_matches_function():
    W = _block_sparse_W(64, 256, 0.5, seed=3)
    model = to_block_sparse(jnp.asarray(W), (16, 128))
    a = model.quantize()
    b = quantize_block_sparse(model)
    assert isinstance(a, Int8BlockSparseModel)
    assert np.array_equal(np.asarray(a.blocks), np.asarray(b.blocks))
    assert np.array_equal(np.asarray(a.scales), np.asarray(b.scales))
    assert a.payload_bytes() == b.payload_bytes()
    # int8 payload: 1 byte/value + one fp32 scale per block, vs 4 bytes/value.
    fp32 = 4 * int(np.prod(np.asarray(model.blocks).shape))
    assert a.payload_bytes() / fp32 < 0.55


# ---------------------------------------------------------------------------
# Int8 kernels vs oracle; full-coverage gather is bit-exact
# ---------------------------------------------------------------------------

_ORACLE = {"fp32": (bsr_ref.bsr_predict, bsr_ref.bsr_predict_gather),
           "int8": (bsr_ref.bsr_predict_int8, bsr_ref.bsr_predict_gather_int8)}


def _packed(W, dtype):
    """W packed at (16, 128) blocks, quantized for "int8"."""
    model = to_block_sparse(jnp.asarray(W), (16, 128))
    return model.quantize() if dtype == "int8" else model


@pytest.mark.parametrize("dtype", ["fp32", "int8"])
def test_int8_kernel_matches_oracle(dtype):
    """Pallas exhaustive scoring (`bsr_predict`, either dtype) ==
    dequantize-then-fp32 oracle, on a non-tile-aligned shape (row +
    feature padding both engaged)."""
    L, D = 100, 300
    W = _block_sparse_W(L, D, 0.6, seed=11)
    q = _packed(W, dtype)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, D)), jnp.float32)
    got = bsr_ops.bsr_predict(x, q)
    want = _ORACLE[dtype][0](jnp.pad(x, ((0, 0), (0, q.shape[1] - D))), q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", ["fp32", "int8"])
def test_int8_gather_kernel_matches_oracle_unsorted_sel(dtype):
    L, D = 100, 300
    W = _block_sparse_W(L, D, 0.6, seed=12)
    q = _packed(W, dtype)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(3, D)), jnp.float32)
    sel = jnp.asarray([5, 0, 3], jnp.int32)
    got = bsr_ops.bsr_predict_gather(x, q, sel)
    want = _ORACLE[dtype][1](jnp.pad(x, ((0, 0), (0, q.shape[1] - D))), q,
                             sel)
    assert got.shape == (3, 3 * 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", ["fp32", "int8"])
def test_full_coverage_gather_int8_bitwise_exhaustive(dtype):
    """sel = every row block: the gathered kernel must reproduce the
    exhaustive kernel BIT-FOR-BIT — for int8 both dequantize against the
    same per-block scale in the same fp32 accumulation order, so composing
    the shortlist gate with int8 adds no numerics of its own."""
    L, D = 128, 256
    W = _block_sparse_W(L, D, 0.5, seed=13)
    q = _packed(W, dtype)
    n_row_blocks = q.shape[0] // q.block_shape[0]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(4, D)), jnp.float32)
    sel = jnp.arange(n_row_blocks, dtype=jnp.int32)
    full = bsr_ops.bsr_predict_gather(x, q, sel)
    exhaustive = bsr_ops.bsr_predict(x, q)
    assert np.array_equal(np.asarray(full), np.asarray(exhaustive))
    sc_g, lb_g = bsr_ops.bsr_predict_gather_topk(x, q, sel, 5, n_labels=L)
    sc_e, lb_e = bsr_ops.bsr_predict_topk(x, q, 5, n_labels=L)
    assert np.array_equal(np.asarray(sc_g), np.asarray(sc_e))
    assert np.array_equal(np.asarray(lb_g), np.asarray(lb_e))


@pytest.mark.parametrize("density", [0.1, 0.5, 1.0])
def test_int8_topk_agreement_across_densities(density):
    """Serving-level acceptance: int8 top-5 label sets agree with fp32 on
    >= 99% of slots, at every block density — including through the
    shortlist backend at full coverage. Requests plant 5 labels per
    instance with unit score gaps (the decisive-margin regime real ranked
    retrieval lives in — fully random scores put rank-5 boundaries inside
    the quantization noise floor, which no 8-bit scheme can rank)."""
    L, D, k = 128, 256, 5
    seed = int(density * 100) + 7
    W = _block_sparse_W(L, D, density, seed=seed, guarantee_blocks=True)
    bsr = to_block_sparse(jnp.asarray(W), (16, 128))
    rng = np.random.default_rng(seed + 1)
    norms = np.linalg.norm(W, axis=1)
    live = np.flatnonzero(norms > 0)          # fully-pruned labels score 0
    coefs = np.arange(10, 10 - k, -1, dtype=np.float32)
    x = jnp.asarray(np.stack([
        (coefs[:, None] * W[labs] / (norms[labs, None] ** 2)).sum(0)
        for labs in (rng.choice(live, size=k, replace=False)
                     for _ in range(16))]), jnp.float32)
    _, lb_f = make_backend("bsr", bsr, k, n_labels=L).topk(x)
    _, lb_q = make_backend("int8", bsr, k, n_labels=L).topk(x)
    agree = np.mean([
        len(set(map(int, a)) & set(map(int, b))) / k
        for a, b in zip(np.asarray(lb_f), np.asarray(lb_q))])
    assert agree >= 0.99
    # Shortlist-composed int8 at B = n_row_blocks: bit-equal to Int8Backend.
    art = build_shortlist(bsr)
    n_row_blocks = bsr.shape[0] // bsr.block_shape[0]
    sl = make_backend("shortlist", bsr, k, n_labels=L, shortlist=art,
                      shortlist_blocks=n_row_blocks, int8=True)
    assert sl.int8
    sc_sl, lb_sl = sl.topk(x)
    sc_q, lb_q2 = make_backend("int8", bsr, k, n_labels=L).topk(x)
    assert np.array_equal(np.asarray(lb_sl), np.asarray(lb_q2))
    assert np.array_equal(np.asarray(sc_sl), np.asarray(sc_q))


# ---------------------------------------------------------------------------
# D > Dp guard: every wrapper, loud and early
# ---------------------------------------------------------------------------

def test_oversized_request_raises_on_all_wrappers():
    """A request wider than the model's padded feature dim must fail with
    a ValueError naming both dims — on every predict wrapper, exhaustive,
    gathered and per-query, scores and top-k, over the fp32 AND the int8
    model — not shape-err deep inside the kernel."""
    L, D = 64, 256
    W = _block_sparse_W(L, D, 0.5, seed=21)
    bsr = to_block_sparse(jnp.asarray(W), (16, 128))
    Dp = bsr.shape[1]
    x_wide = jnp.ones((2, Dp + 64), jnp.float32)
    sel = jnp.asarray([0], jnp.int32)
    sel_pq = jnp.zeros((2, 1), jnp.int32)
    pattern = rf"feature dim {Dp + 64}.*{Dp}"
    calls = [lambda m: bsr_ops.bsr_predict(x_wide, m),
             lambda m: bsr_ops.bsr_predict_topk(x_wide, m, 3),
             lambda m: bsr_ops.bsr_predict_gather(x_wide, m, sel),
             lambda m: bsr_ops.bsr_predict_gather_topk(x_wide, m, sel, 3),
             lambda m: bsr_ops.bsr_predict_gather_pq(x_wide, m, sel_pq),
             lambda m: bsr_ops.bsr_predict_gather_pq_topk(x_wide, m,
                                                          sel_pq, 3)]
    for model in (bsr, bsr.quantize()):
        for call in calls:
            with pytest.raises(ValueError, match=pattern):
                call(model)


# ---------------------------------------------------------------------------
# Checkpoint persistence: single-shard, stream, and legacy fallback
# ---------------------------------------------------------------------------

def test_single_shard_persists_int8_bit_identical_to_lazy(tmp_path):
    W = _block_sparse_W(64, 256, 0.5, seed=31)
    model = to_block_sparse(jnp.asarray(W), (16, 128))
    ckpt = str(tmp_path / "ck")
    save_block_sparse(model, ckpt)
    data = np.load(os.path.join(ckpt, BSR_ARRAYS))
    assert "blocks_int8" in data.files and "block_scales" in data.files
    loaded, _ = load_block_sparse_int8(ckpt)
    lazy = quantize_block_sparse(model)
    assert np.array_equal(np.asarray(loaded.blocks), np.asarray(lazy.blocks))
    assert np.array_equal(np.asarray(loaded.scales), np.asarray(lazy.scales))


def test_legacy_single_shard_falls_back_to_lazy_quantize(tmp_path):
    """A pre-int8 checkpoint (no blocks_int8 in the npz) still serves
    int8: the loader quantizes the fp32 blocks lazily, bit-identical to
    what a re-save would persist."""
    W = _block_sparse_W(64, 256, 0.5, seed=32)
    model = to_block_sparse(jnp.asarray(W), (16, 128))
    ckpt = str(tmp_path / "ck")
    save_block_sparse(model, ckpt)
    path = os.path.join(ckpt, BSR_ARRAYS)
    data = np.load(path)
    legacy = {k: data[k] for k in data.files
              if k not in ("blocks_int8", "block_scales")}
    np.savez(path, **legacy)
    loaded, _ = load_block_sparse_int8(ckpt)
    lazy = quantize_block_sparse(model)
    assert np.array_equal(np.asarray(loaded.blocks), np.asarray(lazy.blocks))
    assert np.array_equal(np.asarray(loaded.scales), np.asarray(lazy.scales))
    # And the engine serves it end-to-end, agreeing with in-memory int8.
    eng = XMCEngine.from_checkpoint(ckpt, backend="int8", k=5, warmup=False)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(4, 256)),
                    jnp.float32)
    _, lb = eng.backend.topk(x)
    _, lb_mem = make_backend("int8", model, 5, n_labels=64).topk(x)
    assert np.array_equal(np.asarray(lb), np.asarray(lb_mem))


def _write_stream_checkpoint(directory, W, *, block=(16, 128),
                             label_batch=32):
    bl, _ = block
    L, D = W.shape
    n_batches = L // label_batch
    w = BlockSparseWriter(directory, n_labels=L, n_features=D,
                          block_shape=block, label_batch=label_batch,
                          n_batches=n_batches)
    for b in range(n_batches):
        part = to_block_sparse(
            jnp.asarray(W[b * label_batch:(b + 1) * label_batch]), block,
            row_block_offset=b * label_batch // bl, device=False)
        w.write_batch(b, part, row_start=b * label_batch,
                      n_rows=label_batch)
    assert w.try_finalize() is not None


def test_stream_persists_int8_and_legacy_shards_fall_back(tmp_path):
    """Streamed multi-shard layout: per-shard blocks_int8 arrays stitch to
    the same bytes lazy quantization of the stitched fp32 model produces;
    stripping the int8 arrays from ANY shard flips the loader to the lazy
    path with identical results."""
    W = _block_sparse_W(64, 256, 0.5, seed=33)
    ckpt = str(tmp_path / "stream")
    _write_stream_checkpoint(ckpt, W)
    model, _ = load_block_sparse(ckpt)
    lazy = quantize_block_sparse(model)
    loaded, _ = load_block_sparse_int8(ckpt, model=model)
    assert np.array_equal(np.asarray(loaded.blocks), np.asarray(lazy.blocks))
    assert np.array_equal(np.asarray(loaded.scales), np.asarray(lazy.scales))
    # Legacy stream: rewrite one shard without the int8 arrays.
    shard = sorted(p for p in os.listdir(ckpt) if p.startswith("shard-"))[0]
    path = os.path.join(ckpt, shard)
    data = np.load(path)
    np.savez(path, **{k: data[k] for k in data.files
                      if k not in ("blocks_int8", "block_scales")})
    fell_back, _ = load_block_sparse_int8(ckpt, model=model)
    assert np.array_equal(np.asarray(fell_back.blocks),
                          np.asarray(lazy.blocks))
    assert np.array_equal(np.asarray(fell_back.scales),
                          np.asarray(lazy.scales))


# ---------------------------------------------------------------------------
# ServeSpec knob
# ---------------------------------------------------------------------------

def test_serve_spec_int8_roundtrip_and_legacy_default():
    spec = ServeSpec(backend="shortlist", int8=True)
    spec.validate()
    assert ServeSpec.from_dict(spec.to_dict()) == spec
    old = spec.to_dict()
    del old["int8"]          # manifest written before the int8 PR
    assert ServeSpec.from_dict(old).int8 is False


# ---------------------------------------------------------------------------
# Warm-up ledger: int8 never aliases fp32
# ---------------------------------------------------------------------------

def test_warmup_int8_does_not_alias_fp32():
    """An int8 backend over the SAME geometry as a fp32 bsr backend is a
    different executable: its warm-up must dispatch, not ride the fp32
    bucket's ledger entry — while two equal int8 backends do share."""
    L, D, k = 128, 256, 3
    W = _block_sparse_W(L, D, 0.5, seed=41)
    bsr = to_block_sparse(jnp.asarray(W), (16, 128))
    reset_warmup_cache()
    try:
        e_f = XMCEngine(make_backend("bsr", bsr, k, n_labels=L),
                        buckets=(1, 2), warmup=False, n_features=D)
        assert e_f.warmup() == 2
        assert warmup_cache_stats() == {"dispatches": 2, "shared_hits": 0}
        e_q = XMCEngine(make_backend("int8", bsr, k, n_labels=L),
                        buckets=(1, 2), warmup=False, n_features=D)
        assert e_q.warmup() == 2
        assert warmup_cache_stats() == {"dispatches": 4, "shared_hits": 0}
        e_q2 = XMCEngine(make_backend("int8", bsr, k, n_labels=L),
                         buckets=(1, 2), warmup=False, n_features=D)
        assert e_q2.warmup() == 2
        assert warmup_cache_stats() == {"dispatches": 4, "shared_hits": 2}
        # Shortlist with and without int8 are distinct computations too.
        art = build_shortlist(bsr)
        e_sf = XMCEngine(make_backend("shortlist", bsr, k, n_labels=L,
                                      shortlist=art, shortlist_blocks=2),
                         buckets=(1,), warmup=False, n_features=D)
        assert e_sf.warmup() == 1
        d_after_sl = warmup_cache_stats()["dispatches"]
        e_sq = XMCEngine(make_backend("shortlist", bsr, k, n_labels=L,
                                      shortlist=art, shortlist_blocks=2,
                                      int8=True),
                         buckets=(1,), warmup=False, n_features=D)
        assert e_sq.warmup() == 1
        assert warmup_cache_stats()["dispatches"] == d_after_sl + 1
    finally:
        reset_warmup_cache()
