"""The grouped top-k (`ops.grouped_top_k`) against `jax.lax.top_k`: values
and ids bit for bit, tie order included, and the shape rule that chooses
between them in `bsr_predict_topk`."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pruning import to_block_sparse
from repro.kernels.bsr_predict import ops

GROUPS = 12


def _rows(kind, n, Lp, c, seed):
    rng = np.random.default_rng(seed)
    if kind == "random":
        return rng.normal(size=(n, Lp))
    if kind == "small_int":                   # ties everywhere
        return rng.integers(-2, 3, size=(n, Lp)).astype(float)
    if kind == "all_zero":
        return np.zeros((n, Lp))
    if kind == "signed_zero":                 # -0.0 and +0.0 only
        return np.where(rng.random((n, Lp)) < 0.9, -0.0, 0.0)
    if kind == "one_hot":
        s = np.zeros((n, Lp))
        s[np.arange(n), rng.integers(0, Lp, size=n)] = 1.0
        return s
    # "straddle": every group's maximum is 1, the k-th group maximum too;
    # group j holds j % 3 + 1 ones, and a few later groups a 2 above it.
    s = np.zeros((n, Lp))
    for g in range(-(-Lp // c)):
        width = min(c, Lp - g * c)
        for row in range(n):
            at = rng.choice(width, size=min(width, g % 3 + 1), replace=False)
            s[row, g * c + at] = 1.0
    s[:, rng.integers(Lp // 2, Lp, size=3)] = 2.0
    return s


@functools.partial(jax.jit, static_argnames=("k", "c"))
def _grouped(s, keep, fill, *, k, c):
    return ops.grouped_top_k(s, k, c, keep, fill)


@functools.partial(jax.jit, static_argnames=("k",))
def _plain(s, keep, fill, *, k):
    return jax.lax.top_k(jnp.where(keep, s, fill), k)


def _assert_same(got, want):
    (gv, gi), (wv, wi) = ((np.asarray(a) for a in p) for p in (got, want))
    np.testing.assert_array_equal(gi, wi)
    np.testing.assert_array_equal(gv.view(np.int32), wv.view(np.int32))


@pytest.mark.parametrize("c", [128, 256, 2048])
@pytest.mark.parametrize("k", [1, 5, 10])
@pytest.mark.parametrize("n", [1, 8, 64])
@pytest.mark.parametrize("kind", ["random", "small_int", "all_zero",
                                  "signed_zero", "one_hot", "straddle"])
def test_grouped_top_k_equals_lax_top_k(kind, n, k, c):
    """Values and ids equal `lax.top_k`'s bit for bit, with and without
    the masks, where ties fall inside and across the k-th group maximum;
    groups of one, two and 16 128-label tiles, the last two with a tail
    of half a group after the whole groups."""
    Lp = GROUPS * c + c // 256 * 128
    s = jnp.asarray(_rows(kind, n, Lp, c, seed=n * 100 + k), jnp.float32)
    rng = np.random.default_rng(k)
    keep = jnp.asarray(rng.random(Lp) < 0.9)
    fill = jnp.asarray(np.where(rng.random(Lp) < 0.5, 0.0, ops.NEG_INF),
                       jnp.float32)
    _assert_same(_grouped(s, keep, fill, k=k, c=c),
                 _plain(s, keep, fill, k=k))
    everything = jnp.ones(Lp, bool)
    _assert_same(_grouped(s, everything, fill, k=k, c=c),
                 jax.lax.top_k(s, k))


# Groups of three 128-label tiles over the model below: six whole groups
# and a tail of two tiles.
WIDE_GROUP = 384


def _wide_model(case):
    """16 x 16 blocks over 160 label row blocks (Lp 2,560, twenty tiles of
    128 labels), features 48; 2,550 real labels, so the last row block
    pads."""
    rng = np.random.default_rng(5)
    L, D = 2550, 48
    if case == "fully_pruned":
        W = np.zeros((L, D), np.float32)
    else:
        keep = rng.random((160, 3)) < 0.5
        keep[[0, 9, 10, 11, 40, 150, 159]] = False     # empty row blocks
        W = rng.normal(size=(160 * 16, 3 * 16)).astype(np.float32)
        W = (W * np.kron(keep, np.ones((16, 16), np.float32)))[:L]
    return to_block_sparse(jnp.asarray(W), (16, 16)), L, D


@pytest.mark.parametrize("k", [1, 5])
@pytest.mark.parametrize("n", [1, 8, 24])
@pytest.mark.parametrize("case", ["ragged", "fully_pruned"])
def test_bsr_predict_topk_grouped_equals_the_lax_path(case, n, k,
                                                      monkeypatch):
    """`bsr_predict_topk` on a model wide enough for the grouped form
    (the rule's width and group lowered to the model's), with empty row
    blocks and padding labels, returns what its `lax.top_k` path returns,
    the fully pruned model's zeros included."""
    model, L, D = _wide_model(case)
    Lp = model.shape[0]
    x = jnp.asarray(np.random.default_rng(n).normal(size=(n, D)),
                    jnp.float32)
    monkeypatch.setattr(ops, "TOPK_GROUP", WIDE_GROUP)
    assert not ops.topk_grouped(Lp, k)
    plain = ops.bsr_predict_topk(x, model, k, n_labels=L)
    monkeypatch.setattr(ops, "GROUPED_TOPK_MIN_LP", Lp)
    assert ops.topk_grouped(Lp, k)
    grouped = ops.bsr_predict_topk(x, model, k, n_labels=L)
    _assert_same(grouped, plain)
    assert np.all(np.asarray(grouped[1]) < L)
    if case == "fully_pruned":
        np.testing.assert_array_equal(np.asarray(grouped[0]), 0.0)
        np.testing.assert_array_equal(np.asarray(grouped[1]),
                                      np.tile(np.arange(k), (n, 1)))


@pytest.mark.parametrize("Lp,k,grouped", [
    (4096, 5, False),          # EURLex-4K
    (15488, 5, False),         # Wiki10-31K half
    (1305344, 5, True),        # LF-AmazonTitles-1.3M
    (1305344, 10198, False),   # k groups' candidates cover the row
    (ops.GROUPED_TOPK_MIN_LP + 64, 5, False),   # no whole groups
])
def test_rule_keeps_lax_top_k_on_narrow_rows(Lp, k, grouped):
    assert ops.topk_grouped(Lp, k) is grouped


def test_rule_keeps_lax_top_k_where_candidates_cover_the_row(monkeypatch):
    monkeypatch.setattr(ops, "GROUPED_TOPK_MIN_LP", 128)
    c = ops.TOPK_GROUP
    assert ops.topk_grouped(5 * c + 128, 5)
    assert not ops.topk_grouped(5 * c, 5)        # 5 groups cover the row
    assert not ops.topk_grouped(5 * c, 6)


@pytest.mark.parametrize("min_lp,grouped", [(None, 0), (2560, 2)])
def test_server_counts_topk_grouped(min_lp, grouped, monkeypatch):
    """`XMCServer.stats()["topk_grouped"]` counts the launches whose top-k
    took the grouped form: every launch where the model's score row is as
    wide as the rule asks, none below it."""
    from repro.serve import XMCEngine, XMCServer
    from repro.serve.xmc import BsrBackend

    model, L, D = _wide_model("ragged")
    monkeypatch.setattr(ops, "TOPK_GROUP", WIDE_GROUP)
    if min_lp is not None:
        monkeypatch.setattr(ops, "GROUPED_TOPK_MIN_LP", min_lp)
    backend = BsrBackend(model, 3, n_labels=L)
    assert backend.topk_grouped(4) == bool(grouped)
    engine = XMCEngine(backend, buckets=(4, 16), warmup=False,
                       n_features=D)
    server = XMCServer(engine, max_batch_delay_ms=120_000.0, start=False)
    for rows in (16, 3):              # one full bucket of 16, then 3 -> 4
        server.submit(np.ones((rows, D), np.float32))
    server.stop()
    st = server.stats()
    assert st["batches"] == 2
    assert st["topk_grouped"] == grouped


def test_topk_grouped_share_reads_the_servers_counter():
    """The benchmark's `serve.topk_grouped_share` reader: `topk_grouped`
    over `batches` in percent, nothing where the server has no counter
    (a program without the grouped form) or launched nothing."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks",
                        "chip", "layers", "serve.topk_grouped_share.py")
    spec = importlib.util.spec_from_file_location("topk_grouped_share", path)
    layer = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(layer)
    read = layer.read
    assert read({"server_stats": {"topk_grouped": 6, "batches": 8}}) == 75.0
    assert read({"server_stats": {"topk_grouped": 0, "batches": 8}}) == 0.0
    assert read({"server_stats": {"batches": 8}}) is None
    assert read({"server_stats": {"topk_grouped": 0, "batches": 0}}) is None
    assert read({}) is None
