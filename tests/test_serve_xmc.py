"""XMC serving subsystem: backend equivalence, sparse checkpoint round-trip,
and micro-batch queue/bucketing semantics."""

import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.pruning import BlockSparseModel, prune, to_block_sparse
from repro.serve import BACKENDS, XMCEngine, make_backend
from repro.serve.batching import (LATENCY_SAMPLES, LatencyStats,
                                  MicroBatchQueue, pad_rows, pick_bucket)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _random_pruned_bsr(L, D, *, delta=0.05, seed=0, zero_rows=()):
    """A pruned weight matrix in both dense and packed-BSR form."""
    rng = np.random.default_rng(seed)
    W = rng.normal(size=(L, D)).astype(np.float32) * 0.1
    W = np.array(prune(jnp.asarray(W), delta))   # writable copy
    for r in zero_rows:
        W[r] = 0.0                       # fully pruned label
    return W, to_block_sparse(jnp.asarray(W), (128, 128))


# ---------------------------------------------------------------------------
# Backend equivalence
# ---------------------------------------------------------------------------

def test_backends_agree_on_topk():
    """dense / bsr / sharded must return identical top-k label ids for the
    same pruned model (the acceptance criterion of the serving refactor)."""
    L, D, k = 200, 512, 5
    W, bsr = _random_pruned_bsr(L, D, seed=1)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(16, D)).astype(np.float32))

    out = {}
    for kind in BACKENDS:
        be = make_backend(kind, bsr, k, n_labels=L)
        vals, idx = be.topk(x)
        assert vals.shape == (16, k) and idx.shape == (16, k)
        out[kind] = np.asarray(idx)
        assert out[kind].max() < L, f"{kind} served a padding label"
    np.testing.assert_array_equal(out["dense"], out["bsr"])
    np.testing.assert_array_equal(out["dense"], out["sharded"])


def test_backends_agree_with_fully_pruned_rows():
    """Labels whose entire weight row was Delta-pruned score exactly 0 in
    every backend (BSR's skipped empty row-blocks included), so the top-k
    sets still agree even when 0.0 lands inside the top-k."""
    L, D, k = 130, 256, 5
    zero_rows = list(range(120, 130))    # kills the whole 2nd 128-row block
    W, bsr = _random_pruned_bsr(L, D, seed=3, zero_rows=zero_rows)
    # With few labels and negative-leaning x@W.T, zeros enter the top-k.
    rng = np.random.default_rng(4)
    x = jnp.asarray(-np.abs(rng.normal(size=(8, D))).astype(np.float32))

    out = {}
    for kind in BACKENDS:
        be = make_backend(kind, bsr, k, n_labels=L)
        _, idx = be.topk(x)
        out[kind] = np.asarray(idx)
        assert out[kind].max() < L, f"{kind} served a padding label"
    np.testing.assert_array_equal(out["dense"], out["bsr"])
    np.testing.assert_array_equal(out["dense"], out["sharded"])


def test_default_n_labels_never_serves_padding():
    """Without an explicit n_labels, backends must fall back to the true
    pre-padding label count (orig_shape), not the block-padded shape —
    zero-score padding rows would otherwise beat negative real scores."""
    L, D, k = 200, 512, 5
    _, bsr = _random_pruned_bsr(L, D, seed=11)
    assert bsr.orig_shape == (L, D) and bsr.shape[0] > L
    rng = np.random.default_rng(12)
    x = jnp.asarray(-np.abs(rng.normal(size=(4, D))).astype(np.float32))
    for kind in BACKENDS:
        be = make_backend(kind, bsr, k)          # no n_labels passed
        _, idx = be.topk(x)
        assert np.asarray(idx).max() < L, f"{kind} served a padding label"


def test_backends_handle_non_block_multiple_features():
    """D not divisible by the block width: dense/sharded must slice the
    densified model back to (L, D) so (n, D) requests work everywhere."""
    L, D, k = 100, 300, 3
    W, bsr = _random_pruned_bsr(L, D, seed=13)
    assert bsr.shape[1] > D                      # feature dim was padded
    rng = np.random.default_rng(14)
    x = jnp.asarray(rng.normal(size=(4, D)).astype(np.float32))
    out = {}
    for kind in BACKENDS:
        be = make_backend(kind, bsr, k)
        _, idx = be.topk(x)
        out[kind] = np.asarray(idx)
    np.testing.assert_array_equal(out["dense"], out["bsr"])
    np.testing.assert_array_equal(out["dense"], out["sharded"])


def test_engine_rejects_mismatched_request_dim():
    L, D = 140, 256
    _, bsr = _random_pruned_bsr(L, D, seed=15)
    be = make_backend("dense", bsr, 3)
    engine = XMCEngine(be, buckets=(2, 4), warmup=False, n_features=D)
    with pytest.raises(ValueError, match="feature dim"):
        engine.submit(np.zeros((2, D + 1), np.float32))


def test_sharded_backend_masks_shard_padding():
    """L not divisible by the shard count: the row padding the backend adds
    must never appear in served results (subprocess with 8 devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    code = """
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.core.pruning import prune, to_block_sparse
        from repro.serve import make_backend
        mesh = jax.make_mesh((1, 8), ("data", "model"))
        rng = np.random.default_rng(0)
        L, D, k = 50, 256, 5
        W = prune(jnp.asarray(rng.normal(size=(L, D)), jnp.float32) * 0.1,
                  0.05)
        bsr = to_block_sparse(W, (128, 128))
        dense = make_backend("dense", bsr, k, n_labels=L)
        sharded = make_backend("sharded", bsr, k, n_labels=L, mesh=mesh)
        x = jnp.asarray(-np.abs(rng.normal(size=(4, D))), jnp.float32)
        _, i1 = dense.topk(x)
        _, i2 = sharded.topk(x)
        assert np.asarray(i2).max() < L, "padding label served"
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        print("OK")
    """
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "OK" in r.stdout


def test_packed_models_are_pytrees_sharing_one_executable_per_bucket():
    """Both packed model classes flatten to their arrays and unflatten with
    shape, block_shape and orig_shape intact. `_bsr_topk` takes the model
    whole: two bsr backends over distinct models of equal geometry reuse
    one executable per bucket, and the int8 model of the same geometry
    compiles its own."""
    from repro.serve.xmc import BsrBackend, Int8Backend, _bsr_topk

    L, D, k = 72, 80, 3               # a geometry no other test serves
    rng = np.random.default_rng(11)
    m1, m2 = (to_block_sparse(jnp.asarray(
        rng.normal(size=(L, D)).astype(np.float32)), (16, 16))
        for _ in range(2))
    assert m1.blocks.shape == m2.blocks.shape
    for m, n_leaves in ((m1, 4), (m1.quantize(), 5)):
        leaves, treedef = jax.tree_util.tree_flatten(m)
        assert len(leaves) == n_leaves
        assert all(isinstance(a, jax.Array) for a in leaves)
        back = jax.tree_util.tree_unflatten(treedef, leaves)
        assert type(back) is type(m)
        assert (back.shape, back.block_shape, back.orig_shape) == \
            (m.shape, m.block_shape, m.orig_shape) == \
            ((80, 80), (16, 16), (L, D))
        assert all(a is b for a, b in
                   zip(jax.tree_util.tree_leaves(back), leaves))

    def compiles(backend, n):
        before = _bsr_topk._cache_size()
        jax.block_until_ready(backend.topk(jnp.ones((n, D), jnp.float32)))
        return _bsr_topk._cache_size() - before

    b1, b2 = BsrBackend(m1, k), BsrBackend(m2, k)
    q1 = Int8Backend(m1, k)
    for n in (2, 4):
        assert compiles(b1, n) == 1
        assert compiles(b2, n) == 0
        assert compiles(q1, n) == 1
        assert compiles(Int8Backend(m2, k), n) == 0
    assert b1.warmup_key()[0] == "bsr" and q1.warmup_key()[0] == "int8"


# ---------------------------------------------------------------------------
# Sparse checkpoint round-trip
# ---------------------------------------------------------------------------

def test_block_sparse_checkpoint_roundtrip():
    """blocks / block_rows / block_cols / row_ptr / shapes / meta all
    survive save -> load exactly; the loaded model serves identically."""
    L, D = 200, 512
    W, bsr = _random_pruned_bsr(L, D, seed=5)
    meta = {"n_labels": L, "n_features": D, "delta": 0.05}
    with tempfile.TemporaryDirectory() as d:
        bsr.save(d, meta=meta)
        loaded, meta2 = BlockSparseModel.load(d)
    assert meta2 == meta
    assert loaded.shape == bsr.shape
    assert loaded.block_shape == bsr.block_shape
    np.testing.assert_array_equal(np.asarray(loaded.blocks),
                                  np.asarray(bsr.blocks))
    np.testing.assert_array_equal(np.asarray(loaded.block_rows),
                                  np.asarray(bsr.block_rows))
    np.testing.assert_array_equal(np.asarray(loaded.block_cols),
                                  np.asarray(bsr.block_cols))
    np.testing.assert_array_equal(np.asarray(loaded.row_ptr),
                                  np.asarray(bsr.row_ptr))
    np.testing.assert_array_equal(np.asarray(loaded.to_dense())[:L, :D], W)


def test_engine_from_checkpoint_serves():
    """End-to-end: save sparse artifact, load an engine, serve a ragged
    stream, get per-request results in submission order."""
    L, D = 140, 256
    _, bsr = _random_pruned_bsr(L, D, seed=6)
    rng = np.random.default_rng(7)
    requests = [rng.normal(size=(int(n), D)).astype(np.float32)
                for n in rng.integers(1, 6, size=9)]
    with tempfile.TemporaryDirectory() as d:
        bsr.save(d, meta={"n_labels": L, "n_features": D})
        engine = XMCEngine.from_checkpoint(d, backend="dense", k=3,
                                           warmup=False)
        results = engine.serve(requests)
    assert [r.request_id for r in results] == list(range(9))
    for req, res in zip(requests, results):
        assert res.labels.shape == (req.shape[0], 3)
        assert res.scores.shape == (req.shape[0], 3)
        assert res.labels.max() < L
    stats = engine.latency_summary()
    assert stats["count"] == 9 and stats["p99_ms"] >= stats["p50_ms"]


# ---------------------------------------------------------------------------
# Queue / bucketing
# ---------------------------------------------------------------------------

def test_pick_bucket_and_pad_rows():
    assert pick_bucket(1, (1, 4, 16)) == 1
    assert pick_bucket(3, (1, 4, 16)) == 4
    assert pick_bucket(16, (1, 4, 16)) == 16
    with pytest.raises(ValueError):
        pick_bucket(17, (1, 4, 16))
    x = np.ones((3, 5), np.float32)
    p = pad_rows(x, 8)
    assert p.shape == (8, 5)
    np.testing.assert_array_equal(p[:3], x)
    assert (p[3:] == 0).all()


def test_micro_batch_queue_coalesces_and_unpads():
    """Ragged requests coalesce FIFO into bucket-padded batches and split
    back to per-request rows without loss or reordering."""
    q = MicroBatchQueue(buckets=(2, 4, 8))
    sizes = [3, 2, 1, 5, 8, 1]
    reqs = [np.full((n, 4), i, np.float32)
            for i, n in enumerate(sizes)]
    rids = [q.submit(r) for r in reqs]
    assert rids == list(range(6))

    got: dict[int, list[np.ndarray]] = {}
    for mb in q.drain():
        assert mb.bucket in (2, 4, 8)
        assert mb.x.shape[0] == mb.bucket
        assert sum(mb.row_counts) <= mb.bucket
        for rid, rows in mb.split(mb.x):
            got.setdefault(rid, []).append(rows)
    assert len(q) == 0
    for i, n in enumerate(sizes):
        rows = np.concatenate(got[i], axis=0)
        assert rows.shape == (n, 4)
        assert (rows == i).all()         # request identity preserved


def test_micro_batch_queue_splits_oversize_requests():
    q = MicroBatchQueue(buckets=(2, 4))
    rid = q.submit(np.ones((10, 3), np.float32))
    batches = list(q.drain())
    assert all(mb.bucket <= 4 for mb in batches)
    total = sum(sum(mb.row_counts) for mb in batches)
    assert total == 10
    assert all(set(mb.request_ids) == {rid} for mb in batches)


def test_queue_rejects_empty_request():
    q = MicroBatchQueue(buckets=(2, 4))
    with pytest.raises(ValueError, match="empty request"):
        q.submit(np.zeros((0, 3), np.float32))


def test_split_request_counts_once_in_latency_stats():
    """A request split across micro-batches is one request: one latency
    sample (the sum of its dispatches), one result."""
    L, D = 140, 256
    _, bsr = _random_pruned_bsr(L, D, seed=9)
    be = make_backend("dense", bsr, 3, n_labels=L)
    engine = XMCEngine(be, buckets=(2, 4), warmup=False, n_features=D)
    rng = np.random.default_rng(10)
    results = engine.serve([rng.normal(size=(10, D)).astype(np.float32)])
    assert len(results) == 1
    assert results[0].labels.shape == (10, 3)
    assert engine.latency_summary()["count"] == 1


def test_next_batch_launch_policy():
    """Continuous-batching launch decision, with an injected clock: no
    launch before the deadline, launch at the deadline, immediate launch
    when the largest bucket fills, and force for drain/shutdown; each
    batch names which of the three launched it."""
    q = MicroBatchQueue(buckets=(2, 4))
    assert q.next_batch(force=True) is None          # empty queue
    q.submit(np.ones((1, 3), np.float32), arrival=100.0)
    # Partially filled, deadline not reached: hold.
    assert q.next_batch(now=100.001, max_delay_s=0.002) is None
    # No deadline configured at all: hold until full.
    assert q.next_batch(now=999.0) is None
    # Deadline expired: ship the partial bucket.
    mb = q.next_batch(now=100.01, max_delay_s=0.002)
    assert mb is not None and mb.bucket == 2 and mb.row_counts == [1]
    assert mb.arrivals == [100.0] and mb.launch == "deadline"
    # Fill launch: 4 rows >= largest bucket ships with no deadline check.
    for i in range(4):
        q.submit(np.ones((1, 3), np.float32), arrival=200.0 + i)
    mb = q.next_batch(now=200.0)                     # zero elapsed time
    assert mb is not None and mb.bucket == 4
    assert mb.arrivals == [200.0, 201.0, 202.0, 203.0] and mb.launch == "full"
    assert q.next_batch(now=200.0) is None
    # Force drains regardless of clock or fill.
    q.submit(np.ones((1, 3), np.float32), arrival=300.0)
    assert q.next_batch(force=True).launch == "forced"


def test_queue_pending_and_arrival_accounting():
    """pending_requests counts distinct requests (a split request once),
    pending_rows counts instances, oldest_arrival tracks head-of-line —
    the three quantities the server's launch/admission decisions read."""
    q = MicroBatchQueue(buckets=(2, 4))
    assert q.pending_requests() == 0 and q.pending_rows() == 0
    assert q.oldest_arrival() is None
    q.submit(np.ones((10, 3), np.float32), arrival=5.0)   # 3 pieces, 1 req
    q.submit(np.ones((1, 3), np.float32), arrival=6.0)
    assert q.pieces_of(10) == 3 and q.pieces_of(4) == 1
    assert q.pending_requests() == 2
    assert q.pending_rows() == 11
    assert q.oldest_arrival() == 5.0
    q.next_batch(force=True)                         # first 4-row piece out
    assert q.pending_requests() == 2                 # split req still queued
    assert q.pending_rows() == 7
    list(q.drain())
    assert q.pending_requests() == 0 and q.pending_rows() == 0


def test_reserve_id_shares_namespace_with_submit():
    q = MicroBatchQueue(buckets=(2,))
    a = q.submit(np.ones((1, 3), np.float32))
    b = q.reserve_id()                               # e.g. a rejected request
    c = q.submit(np.ones((1, 3), np.float32))
    assert [a, b, c] == [0, 1, 2]
    assert q.pending_requests() == 2                 # reserve queues nothing


def test_latency_stats_percentiles():
    s = LatencyStats()
    for ms in [1, 2, 3, 4, 100]:
        s.record_span(10.0, 10.0 + ms / 1e3)
    out = s.summary()
    assert out["count"] == 5
    assert out["p50_ms"] == pytest.approx(3.0)
    assert out["p99_ms"] > out["p50_ms"]


def test_latency_stats_record_span_and_aggregate_wrapper():
    """record_span is the per-request primitive (enqueue -> completion
    timestamps); the summary aggregates only the most recent
    LATENCY_SAMPLES of them, so a long-lived server's stats stay bounded
    and the oldest samples fall out first."""
    s = LatencyStats()
    for _ in range(3):
        s.record_span(0.0, 1.0)                      # 1,000 ms, oldest
    for _ in range(LATENCY_SAMPLES):
        s.record_span(10.0, 10.004)                  # 4 ms
    out = s.summary()
    assert s.count == LATENCY_SAMPLES and out["count"] == LATENCY_SAMPLES
    assert out["p99_ms"] == pytest.approx(4.0)
    assert out["mean_ms"] == pytest.approx(4.0)


def test_step_latency_includes_queue_wait():
    """Per-request spans start at enqueue, not at dispatch: a request that
    sat in the queue before step() ran reports that wait in its latency."""
    import time
    L, D = 140, 256
    _, bsr = _random_pruned_bsr(L, D, seed=16)
    be = make_backend("dense", bsr, 3, n_labels=L)
    engine = XMCEngine(be, buckets=(2,), warmup=False, n_features=D)
    engine.submit(np.zeros((1, D), np.float32))
    time.sleep(0.05)
    engine.step()
    stats = engine.latency_summary()
    assert stats["count"] == 1
    assert stats["p50_ms"] >= 50.0                   # the queue wait is real


def test_engine_bucket_warmup_counts():
    """warmup compiles each bucket once and never recompiles it."""
    L, D = 140, 256
    _, bsr = _random_pruned_bsr(L, D, seed=8)
    be = make_backend("dense", bsr, 3, n_labels=L)
    engine = XMCEngine(be, buckets=(2, 4), warmup=False, n_features=D)
    assert engine.warmup() == 2
    assert engine.warmup() == 0          # idempotent
