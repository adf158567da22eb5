"""Shortlist-gated sub-linear serving: gathered-block kernel parity, the
two-stage backend's equivalence/recall/fallback contracts, the persisted
artifact, and the shared warm-up compile ledger."""

import os
import tempfile

import numpy as np
import pytest

import jax.numpy as jnp

from repro.checkpoint.io import (SHORTLIST_FILE, load_block_sparse,
                                 load_block_sparse_meta, load_shortlist,
                                 save_shortlist)
from repro.core.pruning import prune, to_block_sparse
from repro.data.xmc import make_xmc_dataset
from repro.kernels.bsr_predict import ops as bsr_ops
from repro.kernels.bsr_predict import ref as bsr_ref
from repro.serve import (ShortlistBackend, XMCEngine, build_shortlist,
                         make_backend, reset_warmup_cache,
                         warmup_cache_stats)
from repro.serve.shortlist import ShortlistArtifact
from repro.specs import ServeSpec


def _random_pruned_bsr(L, D, *, block=(16, 128), delta=0.05, seed=0,
                       zero_rows=()):
    rng = np.random.default_rng(seed)
    W = rng.normal(size=(L, D)).astype(np.float32) * 0.1
    W = np.array(prune(jnp.asarray(W), delta))
    for r in zero_rows:
        W[r] = 0.0
    return W, to_block_sparse(jnp.asarray(W), block)


# ---------------------------------------------------------------------------
# Gathered-block kernel
# ---------------------------------------------------------------------------

def _as(bsr, dtype):
    return bsr.quantize() if dtype == "int8" else bsr


@pytest.mark.parametrize("dtype", ["fp32", "int8"])
def test_gather_kernel_matches_ref_non_tile_aligned(dtype):
    """Pallas gathered-block scoring == dense-gather oracle on shapes that
    hit both row padding (L=100 -> Lp=112 with bl=16) and feature padding
    (D=300 -> Dp=384), with an UNSORTED selection, for either dtype."""
    L, D = 100, 300
    _, bsr = _random_pruned_bsr(L, D, seed=1)
    bsr = _as(bsr, dtype)
    oracle = (bsr_ref.bsr_predict_gather_int8 if dtype == "int8"
              else bsr_ref.bsr_predict_gather)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(4, D)).astype(np.float32))
    sel = jnp.asarray([5, 0, 3], jnp.int32)          # arbitrary order
    got = bsr_ops.bsr_predict_gather(x, bsr, sel)
    want = oracle(jnp.pad(x, ((0, 0), (0, bsr.shape[1] - D))), bsr, sel)
    assert got.shape == (4, 3 * 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", ["fp32", "int8"])
def test_gather_kernel_empty_row_block_is_exact_zero(dtype):
    """A selected row block whose labels were all Delta-pruned must come
    back EXACTLY 0.0 (the dense score of a pruned label), not garbage."""
    L, D = 64, 256
    zero_rows = list(range(16, 32))                  # kills row block 1
    _, bsr = _random_pruned_bsr(L, D, seed=3, zero_rows=zero_rows)
    bsr = _as(bsr, dtype)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(3, D)).astype(np.float32))
    out = np.asarray(bsr_ops.bsr_predict_gather(x, bsr, jnp.asarray([1, 2])))
    assert (out[:, :16] == 0.0).all()                # block 1: pruned
    assert (out[:, 16:] != 0.0).any()                # block 2: real scores


@pytest.mark.parametrize("dtype", ["fp32", "int8"])
def test_gather_topk_full_coverage_is_bit_exact(dtype):
    """sel = every row block (sorted) reproduces the exhaustive fused
    predict->topk bit-for-bit, tie order included, in either dtype."""
    L, D, k = 100, 300, 5
    _, bsr = _random_pruned_bsr(L, D, seed=5)
    bsr = _as(bsr, dtype)
    R = bsr.shape[0] // bsr.block_shape[0]
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(8, D)).astype(np.float32))
    v1, i1 = bsr_ops.bsr_predict_topk(x, bsr, k, n_labels=L)
    v2, i2 = bsr_ops.bsr_predict_gather_topk(x, bsr, jnp.arange(R), k,
                                             n_labels=L)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


# ---------------------------------------------------------------------------
# Shortlist backend
# ---------------------------------------------------------------------------

def test_shortlist_backend_full_width_equals_exhaustive():
    """B covering all row blocks == exhaustive BSR: identical scores AND
    identical label ids (the B-covers-all acceptance gate)."""
    L, D, k = 200, 300, 5
    _, bsr = _random_pruned_bsr(L, D, seed=7)
    art = build_shortlist(bsr)
    R = art.n_row_blocks
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(6, D)).astype(np.float32))
    sl = make_backend("shortlist", bsr, k, n_labels=L, shortlist=art,
                      shortlist_blocks=R)
    ex = make_backend("bsr", bsr, k, n_labels=L)
    v1, i1 = sl.topk(x)
    v2, i2 = ex.topk(x)
    assert isinstance(sl, ShortlistBackend) and sl.candidate_fraction == 1.0
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_shortlist_recall_gate_on_clustered_power_law():
    """On a cluster-ordered power-law label space (the regime candidate
    stages serve), a B = 3/16 shortlist recovers >= 95% of the exhaustive
    top-5 for single-query batches — at under 25% of the row blocks."""
    L, D, k = 128, 1024, 5
    data = make_xmc_dataset(n_train=8, n_test=48, n_features=D, n_labels=L,
                            pool_stride=2, label_locality=0.9,
                            multi_label_p=0.9, seed=9)
    # Analytic OvR weights from the generator's signature pools (training
    # would find ~these; the test needs the serving stack, not TRON).
    W = np.zeros((L, D), np.float32)
    for l in range(L):
        W[l, data.label_pools[l]] = 1.0
    bsr = to_block_sparse(jnp.asarray(W), (8, 128))
    art = build_shortlist(bsr)
    assert art.n_row_blocks == 16
    sl = make_backend("shortlist", bsr, k, n_labels=L, shortlist=art,
                      shortlist_blocks=3)
    ex = make_backend("bsr", bsr, k, n_labels=L)
    assert sl.candidate_fraction < 0.25

    hits = total = 0
    for q in np.asarray(data.X_test, np.float32):
        x = jnp.asarray(q[None, :])
        _, want = ex.topk(x)
        _, got = sl.topk(x)
        hits += len(set(np.asarray(want)[0].tolist())
                    & set(np.asarray(got)[0].tolist()))
        total += k
    assert hits / total >= 0.95, f"recall@{k} = {hits / total:.3f}"


def test_shortlist_spec_and_registry_fallback():
    """Without an artifact the "shortlist" kind degrades to exhaustive BSR
    (same results); old-style plugin factories without the shortlist
    kwargs still work through make_backend."""
    L, D, k = 100, 256, 3
    _, bsr = _random_pruned_bsr(L, D, seed=10)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(4, D)).astype(np.float32))
    fb = make_backend("shortlist", bsr, k, n_labels=L)       # no artifact
    ex = make_backend("bsr", bsr, k, n_labels=L)
    assert fb.name == "bsr"
    np.testing.assert_array_equal(np.asarray(fb.topk(x)[1]),
                                  np.asarray(ex.topk(x)[1]))

    from repro.serve import register_backend, unregister_backend

    @register_backend("_old_style")
    def _old_factory(bsr_, k_, *, n_labels, mesh, label_axis, interpret):
        return make_backend("dense", bsr_, k_, n_labels=n_labels)
    try:
        be = make_backend("_old_style", bsr, k, n_labels=L,
                          shortlist=build_shortlist(bsr), shortlist_blocks=2)
        assert be.name == "dense"                # kwargs filtered, no crash
    finally:
        unregister_backend("_old_style")


# ---------------------------------------------------------------------------
# Artifact persistence
# ---------------------------------------------------------------------------

def test_artifact_roundtrip_and_validation():
    L, D = 150, 300
    _, bsr = _random_pruned_bsr(L, D, seed=12)
    art = build_shortlist(bsr)
    assert art.centroids.shape == (bsr.shape[0] // 16, bsr.shape[1])
    assert art.validate_against(bsr) is art
    with tempfile.TemporaryDirectory() as d:
        entry = save_shortlist(d, art)
        assert entry["file"] == SHORTLIST_FILE
        back = load_shortlist(d)
    np.testing.assert_array_equal(back.centroids, art.centroids)
    assert (back.block_rows, back.n_labels, back.stat) == (16, L, "mean")
    _, other = _random_pruned_bsr(64, D, block=(32, 128), seed=13)
    with pytest.raises(ValueError, match="does not match"):
        back.validate_against(other)


def test_centroids_are_true_block_means():
    L, D = 96, 256
    W, bsr = _random_pruned_bsr(L, D, seed=14)
    art = build_shortlist(bsr)
    dense = np.asarray(bsr.to_dense())
    for r in range(art.n_row_blocks):
        np.testing.assert_allclose(art.centroids[r],
                                   dense[r * 16:(r + 1) * 16].mean(axis=0),
                                   rtol=1e-6, atol=1e-6)


def test_save_writes_artifact_and_legacy_checkpoint_falls_back():
    """`BlockSparseModel.save` persists the shortlist next to the BSR
    arrays; deleting it (a checkpoint from before this PR) must silently
    fall back to exhaustive scoring with identical results."""
    L, D, k = 140, 300, 5
    _, bsr = _random_pruned_bsr(L, D, seed=15)
    rng = np.random.default_rng(16)
    x = np.asarray(rng.normal(size=(3, D)), np.float32)
    with tempfile.TemporaryDirectory() as d:
        bsr.save(d, meta={"n_labels": L, "n_features": D})
        assert os.path.exists(os.path.join(d, SHORTLIST_FILE))
        index = load_block_sparse_meta(d)
        assert index["shortlist"]["n_row_blocks"] == bsr.shape[0] // 16
        art = load_shortlist(d)
        art.validate_against(load_block_sparse(d)[0])

        eng = XMCEngine.from_checkpoint(d, backend="shortlist", k=k,
                                        warmup=False, shortlist_blocks=2)
        assert isinstance(eng.backend, ShortlistBackend)
        got_sl = eng.serve([x])[0].labels

        os.remove(os.path.join(d, SHORTLIST_FILE))      # legacy checkpoint
        eng_fb = XMCEngine.from_checkpoint(d, backend="shortlist", k=k,
                                           warmup=False)
        assert eng_fb.backend.name == "bsr"
        got_fb = eng_fb.serve([x])[0].labels
        eng_ex = XMCEngine.from_checkpoint(d, backend="bsr", k=k,
                                           warmup=False)
        np.testing.assert_array_equal(got_fb, eng_ex.serve([x])[0].labels)
        assert got_sl.shape == got_fb.shape


# ---------------------------------------------------------------------------
# ServeSpec knob
# ---------------------------------------------------------------------------

def test_serve_spec_shortlist_blocks_roundtrip_and_validation():
    spec = ServeSpec(backend="shortlist", shortlist_blocks=4)
    assert ServeSpec.from_dict(spec.to_dict()) == spec
    # Manifests written before the knob existed deserialize to the default.
    old = spec.to_dict()
    del old["shortlist_blocks"]
    assert ServeSpec.from_dict(old).shortlist_blocks is None
    with pytest.raises(ValueError, match="shortlist_blocks"):
        ServeSpec(shortlist_blocks=0).validate()


# ---------------------------------------------------------------------------
# Shared warm-up compile ledger
# ---------------------------------------------------------------------------

def test_warmup_shared_across_equal_backends():
    """A second engine over an equal-shaped model must not repeat the
    first's warm-up dispatches: every bucket is a shared hit (the jitted
    scoring functions are module-level, so jax's compile cache is keyed on
    shapes/statics, not backend instances)."""
    L, D, k = 140, 256, 3
    _, bsr1 = _random_pruned_bsr(L, D, seed=17)
    _, bsr2 = _random_pruned_bsr(L, D, seed=18)     # same shapes, new values
    reset_warmup_cache()
    try:
        e1 = XMCEngine(make_backend("dense", bsr1, k, n_labels=L),
                       buckets=(2, 4), warmup=False, n_features=D)
        assert e1.warmup() == 2
        assert warmup_cache_stats() == {"dispatches": 2, "shared_hits": 0}
        e2 = XMCEngine(make_backend("dense", bsr2, k, n_labels=L),
                       buckets=(2, 4), warmup=False, n_features=D)
        assert e2.warmup() == 2                     # per-engine count stays
        assert warmup_cache_stats() == {"dispatches": 2, "shared_hits": 2}
        # A different k is a different computation: no false sharing.
        e3 = XMCEngine(make_backend("dense", bsr1, k + 1, n_labels=L),
                       buckets=(2,), warmup=False, n_features=D)
        assert e3.warmup() == 1
        assert warmup_cache_stats()["dispatches"] == 3
    finally:
        reset_warmup_cache()


def test_warmup_shared_across_bsr_and_shortlist_instances():
    """The bsr and shortlist backends share warm-up state per kind too —
    and the two kinds never collide with each other."""
    L, D, k = 100, 256, 3
    _, bsr = _random_pruned_bsr(L, D, seed=19)
    art = build_shortlist(bsr)
    reset_warmup_cache()
    try:
        for expected_hits, make in ((0, lambda: make_backend(
                "bsr", bsr, k, n_labels=L)),
                (0, lambda: make_backend(
                    "shortlist", bsr, k, n_labels=L, shortlist=art,
                    shortlist_blocks=2)),
                (2, lambda: make_backend("bsr", bsr, k, n_labels=L)),
                (4, lambda: make_backend(
                    "shortlist", bsr, k, n_labels=L, shortlist=art,
                    shortlist_blocks=2))):
            eng = XMCEngine(make(), buckets=(1, 2), warmup=False,
                            n_features=D)
            assert eng.warmup() == 2
            assert warmup_cache_stats()["shared_hits"] == expected_hits
    finally:
        reset_warmup_cache()


# ---------------------------------------------------------------------------
# v2 coarse stages: learned / tree artifacts, per-query selection
# ---------------------------------------------------------------------------

def _clustered_model_and_data(seed=11):
    """Small clustered problem + analytic OvR weights (shared by the v2
    coarse-stage tests): pool_stride/label_locality put co-occurring labels
    in adjacent ids, the regime every coarse stage targets."""
    L, D = 96, 768
    data = make_xmc_dataset(n_train=48, n_test=16, n_features=D, n_labels=L,
                            pool_stride=2, label_locality=0.9,
                            multi_label_p=0.9, seed=seed)
    W = np.zeros((L, D), np.float32)
    for l in range(L):
        W[l, data.label_pools[l]] = 1.0
    return data, W, to_block_sparse(jnp.asarray(W), (8, 128))


def test_v2_artifact_roundtrip_learned_and_tree(tmp_path):
    """save_shortlist/load_shortlist preserve the v2 payload exactly for
    both new kinds — including the tree arrays, which v1 never had."""
    from repro.serve.shortlist import (build_learned_shortlist,
                                       build_tree_shortlist)
    data, _, bsr = _clustered_model_and_data()
    X, Y = np.asarray(data.X_train), np.asarray(data.Y_train)
    for art in (build_learned_shortlist(bsr, X, Y, max_newton=3),
                build_tree_shortlist(bsr, X, Y, depth=2)):
        d = str(tmp_path / art.kind)
        os.makedirs(d)
        save_shortlist(d, art)
        back = load_shortlist(d)
        assert (back.kind, back.stat, back.block_rows, back.n_labels) == \
            (art.kind, art.stat, art.block_rows, art.n_labels)
        np.testing.assert_array_equal(back.centroids, art.centroids)
        if art.kind == "tree":
            assert back.tree_depth == art.tree_depth
            np.testing.assert_array_equal(back.tree_nodes, art.tree_nodes)
            np.testing.assert_array_equal(back.tree_leaf_scores,
                                          art.tree_leaf_scores)
        else:
            assert back.tree_depth == 0 and back.tree_nodes is None
        back.validate_against(bsr)                    # loads stay servable


def test_learned_and_tree_full_width_equal_exhaustive():
    """B = R with a learned or tree coarse stage is still exhaustive
    scoring: identical scores AND ids vs the plain BSR backend (the coarse
    stage may only ever RANK blocks, never perturb fine scores)."""
    from repro.serve.shortlist import (build_learned_shortlist,
                                       build_tree_shortlist)
    data, W, bsr = _clustered_model_and_data(seed=12)
    L, k = W.shape[0], 5
    X, Y = np.asarray(data.X_train), np.asarray(data.Y_train)
    R = bsr.shape[0] // bsr.block_shape[0]
    x = jnp.asarray(np.asarray(data.X_test[:4], np.float32))
    ex = make_backend("bsr", bsr, k, n_labels=L)
    v2, i2 = ex.topk(x)
    for art in (build_learned_shortlist(bsr, X, Y, max_newton=3),
                build_tree_shortlist(bsr, X, Y, depth=3)):
        sl = make_backend("shortlist", bsr, k, n_labels=L, shortlist=art,
                          shortlist_blocks=R)
        assert sl.kind == art.kind
        v1, i1 = sl.topk(x)
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_per_query_selection_is_per_row_topB():
    """per_query=True: `select_blocks` returns each ROW's own top-B coarse
    blocks (sorted), matching the host-side reference selection — not the
    batch-union the shared path uses."""
    from repro.serve.shortlist import coarse_scores
    _, W, bsr = _clustered_model_and_data(seed=13)
    L, k, B = W.shape[0], 5, 3
    rng = np.random.default_rng(14)
    x = rng.normal(size=(5, W.shape[1])).astype(np.float32)
    art = build_shortlist(bsr)
    sl = make_backend("shortlist", bsr, k, n_labels=L, shortlist=art,
                      shortlist_blocks=B, shortlist_per_query=True)
    assert sl.per_query is True
    sel = sl.select_blocks(jnp.asarray(x))
    want = np.sort(np.argsort(-coarse_scores(art, x), axis=1)[:, :B], axis=1)
    assert sel.shape == (5, B)
    np.testing.assert_array_equal(np.sort(sel, axis=1), want)


def test_per_query_single_row_matches_shared():
    """For n = 1 the per-query selection IS the shared union, so the ragged
    path must be bit-identical to the shared gather on single-row batches
    (the equivalence the serving benchmark's per-query gate leans on)."""
    _, W, bsr = _clustered_model_and_data(seed=15)
    L, k, B = W.shape[0], 5, 4
    art = build_shortlist(bsr)
    shared = make_backend("shortlist", bsr, k, n_labels=L, shortlist=art,
                          shortlist_blocks=B)
    pq = make_backend("shortlist", bsr, k, n_labels=L, shortlist=art,
                      shortlist_blocks=B, shortlist_per_query=True)
    rng = np.random.default_rng(16)
    for _ in range(4):
        x = jnp.asarray(rng.normal(size=(1, W.shape[1])).astype(np.float32))
        vs, ls = shared.topk(x)
        vp, lp = pq.topk(x)
        np.testing.assert_array_equal(np.asarray(vs), np.asarray(vp))
        np.testing.assert_array_equal(np.asarray(ls), np.asarray(lp))


def test_per_query_collapses_to_shared_at_full_width():
    """B = R per-query collapses to the shared executable (the ragged
    kernel never sees a full-width request)."""
    _, W, bsr = _clustered_model_and_data(seed=17)
    L = W.shape[0]
    R = bsr.shape[0] // bsr.block_shape[0]
    art = build_shortlist(bsr)
    pq = make_backend("shortlist", bsr, 5, n_labels=L, shortlist=art,
                      shortlist_blocks=R, shortlist_per_query=True)
    assert pq.per_query is False


def test_validate_rejects_inconsistent_artifacts():
    """validate_against: unknown kinds and torn tree payloads must fail
    loudly at load, not at first query."""
    _, _, bsr = _clustered_model_and_data(seed=18)
    base = build_shortlist(bsr)
    bad_kind = ShortlistArtifact(centroids=base.centroids,
                                 block_rows=base.block_rows,
                                 n_labels=base.n_labels, kind="ann")
    with pytest.raises(ValueError, match="unknown shortlist kind"):
        bad_kind.validate_against(bsr)
    torn_tree = ShortlistArtifact(centroids=base.centroids,
                                  block_rows=base.block_rows,
                                  n_labels=base.n_labels, kind="tree",
                                  tree_nodes=None, tree_leaf_scores=None,
                                  tree_depth=3)
    with pytest.raises(ValueError, match="tree shortlist artifact"):
        torn_tree.validate_against(bsr)


def test_per_query_int8_single_row_matches_shared_int8():
    """The ragged int8 fine stage: single-row batches must be bit-identical
    to the shared gathered-int8 path (same collapse argument as fp32)."""
    _, W, bsr = _clustered_model_and_data(seed=19)
    L, k, B = W.shape[0], 5, 4
    art = build_shortlist(bsr)
    shared = make_backend("shortlist", bsr, k, n_labels=L, shortlist=art,
                          shortlist_blocks=B, int8=True)
    pq = make_backend("shortlist", bsr, k, n_labels=L, shortlist=art,
                      shortlist_blocks=B, int8=True,
                      shortlist_per_query=True)
    assert shared.int8 and pq.int8 and pq.per_query
    rng = np.random.default_rng(20)
    for _ in range(3):
        x = jnp.asarray(rng.normal(size=(1, W.shape[1])).astype(np.float32))
        vs, ls = shared.topk(x)
        vp, lp = pq.topk(x)
        np.testing.assert_array_equal(np.asarray(vs), np.asarray(vp))
        np.testing.assert_array_equal(np.asarray(ls), np.asarray(lp))


def test_tree_backend_selection_matches_host_reference():
    """The jitted tree routing (`_tree_coarse`) agrees with the host-side
    `coarse_scores` reference: the backend's shared B-block selection is
    exactly the reference's top-B of the batch-max leaf scores."""
    from repro.serve.shortlist import build_tree_shortlist, coarse_scores
    data, W, bsr = _clustered_model_and_data(seed=21)
    L, k, B = W.shape[0], 5, 3
    X, Y = np.asarray(data.X_train), np.asarray(data.Y_train)
    art = build_tree_shortlist(bsr, X, Y, depth=3)
    sl = make_backend("shortlist", bsr, k, n_labels=L, shortlist=art,
                      shortlist_blocks=B)
    assert sl.kind == "tree"
    x = np.asarray(data.X_test[:6], np.float32)
    sel = np.sort(np.asarray(sl.select_blocks(jnp.asarray(x))))
    coarse = coarse_scores(art, x)                    # host tree routing
    want = np.sort(np.argsort(-coarse.max(axis=0))[:B])
    np.testing.assert_array_equal(sel, want)


def test_fit_reorder_learned_per_query_end_to_end(tmp_path):
    """The fit-time tentpole path in one session: scrambled-label data +
    `ScheduleSpec(reorder_labels=True)` + `ServeSpec(shortlist_kind=
    "learned", shortlist_per_query=True)` -> the checkpoint persists a
    nontrivial `label_order` and a learned artifact, and the served
    full-width top-k ids are EXACTLY the dense reference of the packed
    model unmapped through that order (ids out are original label ids)."""
    from repro.serve.xmc import DenseBackend
    from repro.specs import ScheduleSpec, ServeSpec
    from repro.xmc_api import XMCSpec, fit

    L, D = 64, 1024
    data = make_xmc_dataset(n_train=160, n_test=24, n_features=D,
                            n_labels=L, pool_stride=2, label_locality=0.9,
                            multi_label_p=0.9, scramble_labels=True, seed=23)
    spec = XMCSpec(
        schedule=ScheduleSpec(label_batch=32, block_shape=(8, 128),
                              reorder_labels=True),
        serve=ServeSpec(backend="shortlist", k=5, shortlist_kind="learned",
                        shortlist_per_query=True, warmup=False))
    out = str(tmp_path / "ck")
    handle = fit(jnp.asarray(data.X_train), jnp.asarray(data.Y_train),
                 spec, out)
    assert handle.result.complete

    order = load_block_sparse_meta(out).get("label_order")
    assert order is not None
    order = np.asarray(order)
    assert sorted(order.tolist()) == list(range(L))        # permutation
    assert not np.array_equal(order, np.arange(L))         # and nontrivial
    assert load_shortlist(out).kind == "learned"           # fit upgraded it

    model, _ = load_block_sparse(out)
    R = model.shape[0] // model.block_shape[0]
    eng = handle.engine(ServeSpec(backend="shortlist", k=5,
                                  shortlist_kind="learned",
                                  shortlist_blocks=R, warmup=False))
    x = np.asarray(data.X_test[:6], np.float32)
    res = eng.serve([x])[0]

    Wp = np.asarray(model.to_dense())[:L, :D]              # packed order
    _, packed_ids = DenseBackend(jnp.asarray(Wp), 5, n_labels=L).topk(
        jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(res.labels),
                                  order[np.asarray(packed_ids)])
