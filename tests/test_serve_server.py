"""Continuous-batching async server: deadline launch, admission control,
multi-model routing, and sync-vs-async bit-identity per backend."""

import os
import sys
import tempfile
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.pruning import prune, to_block_sparse
from repro.serve import (BACKENDS, ModelRouter, Rejected, XMCEngine,
                         XMCResult, XMCServer, build_shortlist, make_backend)
from repro.specs import ServeSpec
from repro.xmc_api import CheckpointHandle


def _pruned_bsr(L, D, *, seed=0, delta=0.05):
    rng = np.random.default_rng(seed)
    W = rng.normal(size=(L, D)).astype(np.float32) * 0.1
    return to_block_sparse(prune(jnp.asarray(W), delta), (128, 128))


def _engine(kind="dense", *, L=96, D=128, k=3, buckets=(2, 4, 8), seed=0,
            backend=None):
    bsr = _pruned_bsr(L, D, seed=seed)
    be = backend if backend is not None else make_backend(
        kind, bsr, k, n_labels=L, shortlist=build_shortlist(bsr))
    return XMCEngine(be, buckets=buckets, warmup=False, n_features=D)


def _requests(n, D, *, seed=0, max_rows=5):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=(int(r), D)).astype(np.float32)
            for r in rng.integers(1, max_rows + 1, size=n)]


# ---------------------------------------------------------------------------
# Launch policy
# ---------------------------------------------------------------------------

def test_deadline_launches_partially_filled_bucket():
    """One lone request must ship once its deadline expires — it can never
    fill the largest bucket, so a fill-only policy would hang forever."""
    engine = _engine(buckets=(8, 16))
    server = XMCServer(engine, max_batch_delay_ms=5.0)
    x = np.random.default_rng(1).normal(size=(1, 128)).astype(np.float32)
    t0 = time.monotonic()
    res = server.submit(x).result(timeout=30)
    waited = time.monotonic() - t0
    server.stop()
    assert isinstance(res, XMCResult)
    assert res.labels.shape == (1, 3)
    assert waited < 25, "deadline launch took implausibly long"
    assert server.counters["completed"] == 1


def test_full_bucket_launches_before_deadline():
    """Enough queued rows to fill the largest bucket launch immediately —
    with a deadline much longer than the test timeout, only fill-launch
    can resolve these futures in time."""
    engine = _engine(buckets=(2, 4, 8))
    server = XMCServer(engine, max_batch_delay_ms=120_000.0)
    reqs = _requests(8, 128, seed=2, max_rows=1)     # 8 rows = largest bucket
    futures = [server.submit(x) for x in reqs]
    results = [f.result(timeout=60) for f in futures]
    server.stop()
    assert all(isinstance(r, XMCResult) for r in results)
    assert server.counters["completed"] == 8


def test_fifo_order_is_preserved_across_batches():
    """Mixed-size requests pre-queued then drained: request ids complete in
    submission order batch by batch (FIFO fairness — no size-based
    reordering), and every request keeps its own rows."""
    engine = _engine(buckets=(2, 4))
    server = XMCServer(engine, start=False)
    sizes = [3, 1, 4, 2, 1, 5]
    reqs = [np.full((n, 128), i, np.float32) for i, n in enumerate(sizes)]
    futures = [server.submit(x) for x in reqs]
    server.stop()                                    # inline force-drain
    results = [f.result(timeout=0) for f in futures]
    for i, (n, res) in enumerate(zip(sizes, results)):
        assert res.request_id == i
        assert res.labels.shape == (n, 3)
    # Dispatch order == submission order: later requests never complete in
    # an earlier batch than earlier ones (head-of-line pieces go first).
    assert server.counters["batches"] >= 2


def test_stats_count_launch_reasons_and_padded_rows():
    """Each batch counts under the one reason it launched for (the three
    sum to `batches`); the requests' rows and the padding added to them
    sum to the buckets launched."""
    engine = _engine(buckets=(2, 4))
    server = XMCServer(engine, max_batch_delay_ms=120_000.0, start=False)
    for n in (3, 2):                  # 5 rows: [3] pads to 4, then [2]
        server.submit(np.ones((n, 128), np.float32))
    server.stop()                                    # inline force-drain
    st = server.stats()
    assert (st["batches"], st["launched_full"], st["launched_deadline"],
            st["launched_forced"]) == (2, 1, 0, 1)
    assert (st["rows_launched"], st["rows_padded"]) == (5, 1)

    server = XMCServer(engine, max_batch_delay_ms=1.0)
    server.submit(np.ones((1, 128), np.float32)).result(timeout=30)
    server.stop()
    st = server.stats()
    assert (st["batches"], st["launched_deadline"]) == (1, 1)
    assert (st["rows_launched"], st["rows_padded"]) == (1, 1)


# ---------------------------------------------------------------------------
# Device slots: max_inflight bounds the launches outstanding on the device
# ---------------------------------------------------------------------------

class _Launch:
    """One launch on `_HeldBackend`'s device: unfinished until released."""

    def __init__(self, backend, x):
        self.backend, self.x = backend, x
        self.done = threading.Event()
        self.waited = False

    def wait(self):
        self.done.wait()
        with self.backend.cv:
            if not self.waited:
                self.waited = True
                self.backend.outstanding -= 1
                self.backend.cv.notify_all()


class _Held:
    """A result leaf of a `_Launch`: `jax.block_until_ready` waits for the
    launch, `np.asarray` reads its value."""

    def __init__(self, value, launch):
        self.value, self.launch = value, launch

    def block_until_ready(self):
        self.launch.wait()
        return self

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.value, dtype)


class _HeldBackend:
    """A fake device whose launches stay unfinished until the test releases
    them (`hold`), as an asynchronous dispatch does while the chip is busy.
    A launch is outstanding from `topk` until a wait on it returns; `peak`
    is the most outstanding at once. Scores carry each row's first k
    features, so a test can tell which rows a launch took."""

    name = "held"

    def __init__(self, k=3, n_labels=96):
        self.k, self.n_labels = k, n_labels
        self.cv = threading.Condition()
        self.hold = False
        self.fail = False
        self.launches: list[_Launch] = []
        self.outstanding = self.peak = 0

    def topk(self, x):
        if self.fail:
            self.fail = False
            raise RuntimeError("launch failed")
        launch = _Launch(self, np.asarray(x))
        if not self.hold:
            launch.done.set()
        with self.cv:
            self.launches.append(launch)
            self.outstanding += 1
            self.peak = max(self.peak, self.outstanding)
            self.cv.notify_all()
        n = launch.x.shape[0]
        labels = np.tile(np.arange(self.k, dtype=np.int32), (n, 1))
        return (_Held(launch.x[:, :self.k], launch),
                _Held(labels, launch))

    def release(self, i):
        self.launches[i].done.set()

    def release_all(self):
        self.hold = False
        with self.cv:
            for launch in self.launches:
                launch.done.set()


def _held_server(max_inflight, *, buckets=(1, 2, 4, 8), delay_ms=0.0,
                 start=True):
    """A server over a `_HeldBackend`, warmed with its launches ready and
    then set to hold every launch."""
    be = _HeldBackend()
    engine = XMCEngine(be, buckets=buckets, warmup=True, n_features=128)
    be.launches.clear()
    be.outstanding = be.peak = 0
    be.hold = True
    server = XMCServer(engine, max_batch_delay_ms=delay_ms,
                       max_inflight=max_inflight, start=start)
    return server, be


def _until(pred, timeout=30.0):
    t_end = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < t_end, "condition not reached in time"
        time.sleep(0.001)


def _row(i):
    return np.full((1, 128), float(i), np.float32)


@pytest.mark.parametrize("max_inflight", [1, 2, 3])
def test_launches_outstanding_never_exceed_max_inflight(max_inflight):
    """`max_inflight` launches fill the device, the one the completion
    thread waits on among them; the next launchable batch waits for one to
    finish and is not launched behind them."""
    server, be = _held_server(max_inflight)
    futures = []
    for i in range(max_inflight):
        futures.append(server.submit(_row(i)))
        _until(lambda: len(be.launches) == i + 1)
    futures.append(server.submit(_row(max_inflight)))
    _until(lambda: server.stats()["slot_waits"] == 1)
    time.sleep(0.05)
    assert len(be.launches) == max_inflight
    be.release(0)
    _until(lambda: len(be.launches) == max_inflight + 1)
    done = threading.Event()

    def finish_in_order():          # the device ends one launch at a time
        i = 1
        while not done.is_set():
            if i < len(be.launches):
                be.release(i)
                i += 1
            time.sleep(0.002)

    device = threading.Thread(target=finish_in_order)
    device.start()
    for i in range(max_inflight + 1, max_inflight + 40):
        futures.append(server.submit(_row(i)))
        time.sleep(0.001)
    for f in futures:
        assert isinstance(f.result(timeout=30), XMCResult)
    done.set()
    device.join()
    server.stop()
    assert be.peak == max_inflight


def test_rows_queued_while_the_device_is_full_go_out_together():
    """The batch is formed once a slot frees, so it takes every row that
    arrived while the device was full, in arrival order."""
    server, be = _held_server(1)
    first = server.submit(_row(0))
    _until(lambda: len(be.launches) == 1)
    rest = [server.submit(_row(i)) for i in range(1, 5)]
    _until(lambda: server.stats()["slot_waits"] == 1)
    be.release(0)
    _until(lambda: len(be.launches) == 2)
    np.testing.assert_array_equal(be.launches[1].x[:, 0], [1, 2, 3, 4])
    be.release_all()
    for i, f in enumerate([first, *rest]):
        np.testing.assert_array_equal(f.result(timeout=30).scores, [[i] * 3])
    server.stop()
    st = server.stats()
    assert (st["batches"], st["rows_launched"], st["slot_waits"]) == (2, 5, 1)


def test_slot_waits_count_only_launchable_batches_that_found_no_slot():
    """Rows held by the batching rule wait for their fill or deadline, not
    for a slot, and count nothing; a launchable batch that finds the device
    free counts nothing either."""
    server, be = _held_server(1, buckets=(2,), delay_ms=120_000.0)
    futures = [server.submit(_row(0))]              # held: bucket not full
    time.sleep(0.05)
    assert (len(be.launches), server.stats()["slot_waits"]) == (0, 0)
    futures.append(server.submit(_row(1)))          # full: launches
    _until(lambda: len(be.launches) == 1)
    futures.append(server.submit(_row(2)))          # held, device full
    time.sleep(0.05)
    assert server.stats()["slot_waits"] == 0
    futures.append(server.submit(_row(3)))          # full, device full
    _until(lambda: server.stats()["slot_waits"] == 1)
    be.release_all()
    futures.append(server.submit(_row(4)))          # held until stop
    _until(lambda: futures[3].done())
    server.stop()                                   # forced, slot free
    for f in futures:
        assert isinstance(f.result(timeout=0), XMCResult)
    st = server.stats()
    assert (st["batches"], st["launched_full"], st["launched_forced"],
            st["slot_waits"]) == (3, 2, 1, 1)


def test_a_launch_that_raises_gives_its_slot_back():
    server, be = _held_server(1, start=False)
    be.hold = False
    server.submit(_row(0))
    be.fail = True
    with pytest.raises(RuntimeError, match="launch failed"):
        server._dispatch_once(force=True)
    fut = server.submit(_row(1))
    # stop() drains inline and needs the one slot.
    stopper = threading.Thread(target=server.stop, daemon=True)
    stopper.start()
    stopper.join(timeout=30)
    assert not stopper.is_alive(), "the failed launch kept its slot"
    assert isinstance(fut.result(timeout=0), XMCResult)
    assert server.stats()["slot_waits"] == 0


def test_slots_hold_under_many_submitters():
    """Stress: more submitting threads than cores, a thread switch every
    microsecond, and a device that ends each launch in order at once: no
    more than `max_inflight` launches are ever outstanding, and every
    request resolves to its own row."""
    server, be = _held_server(2, buckets=(1, 2, 4, 8, 16))
    done = threading.Event()

    def finish_in_order():
        i = 0
        while not done.is_set() or i < len(be.launches):
            if i < len(be.launches):
                be.release(i)
                i += 1
            else:
                time.sleep(0.0002)

    n_threads, per_thread = 2 * (os.cpu_count() or 1) + 4, 25
    futures = [[] for _ in range(n_threads)]

    def submit_rows(t):
        for j in range(per_thread):
            futures[t].append(server.submit(_row(t * per_thread + j)))

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        device = threading.Thread(target=finish_in_order)
        device.start()
        submitters = [threading.Thread(target=submit_rows, args=(t,))
                      for t in range(n_threads)]
        for th in submitters:
            th.start()
        for th in submitters:
            th.join(timeout=60)
            assert not th.is_alive()
        for t, fs in enumerate(futures):
            for j, f in enumerate(fs):
                np.testing.assert_array_equal(
                    f.result(timeout=60).scores, [[t * per_thread + j] * 3])
        server.stop()
    finally:
        sys.setswitchinterval(switch)
        done.set()
        device.join(timeout=60)
    assert not device.is_alive()
    assert be.peak <= 2
    assert server.stats()["completed"] == n_threads * per_thread


@pytest.mark.parametrize("started", [True, False])
def test_stop_resolves_every_request_through_the_slots(started):
    """stop() flushes the queue through the slots: with the server started,
    batches launched behind held ones finish once the device does; never
    started, the inline drain completes each launch before the next."""
    server, be = _held_server(1, buckets=(1, 2), start=started)
    if not started:
        be.hold = False
    futures = [server.submit(_row(i)) for i in range(7)]
    if started:
        _until(lambda: len(be.launches) == 1)
        releaser = threading.Thread(target=be.release_all)
        threading.Timer(0.05, releaser.start).start()
    server.stop()
    for i, f in enumerate(futures):
        np.testing.assert_array_equal(f.result(timeout=0).scores, [[i] * 3])
    assert server.stats()["completed"] == 7
    assert be.peak == 1


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------

def test_admission_rejects_past_max_queue_then_recovers():
    engine = _engine()
    server = XMCServer(engine, max_queue=2, start=False)
    reqs = _requests(6, 128, seed=3, max_rows=1)
    futures = [server.submit(x) for x in reqs]
    rejected = [f for f in futures if f.done()
                and isinstance(f.result(0), Rejected)]
    assert len(rejected) == 4                        # first 2 queued, rest shed
    for f in rejected:
        r = f.result(0)
        assert r.reason == "queue_full"
        assert r.request_id >= 0
    server.start()
    server.stop()
    completed = [f.result(5) for f in futures
                 if not isinstance(f.result(5), Rejected)]
    assert len(completed) == 2
    st = server.stats()
    assert st["rejected"] == 4 and st["completed"] == 2
    assert st["reject_rate"] == pytest.approx(4 / 6)
    # Queue drained: a fresh request is admitted again.
    server2 = XMCServer(_engine(), max_queue=2, start=False)
    f = server2.submit(reqs[0])
    assert not f.done()
    server2.stop()
    assert isinstance(f.result(0), XMCResult)


def test_rejected_requests_do_not_lose_ids():
    """Rejections consume an id from the same namespace as accepted
    requests — no two responses ever share an id."""
    server = XMCServer(_engine(), max_queue=1, start=False)
    reqs = _requests(5, 128, seed=4, max_rows=1)
    futures = [server.submit(x) for x in reqs]
    server.stop()
    ids = [f.result(5).request_id for f in futures]
    assert len(set(ids)) == len(ids)


def test_submit_after_stop_raises():
    server = XMCServer(_engine())
    server.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        server.submit(np.zeros((1, 128), np.float32))


def test_server_checks_feature_dim_at_submit():
    server = XMCServer(_engine(), start=False)
    with pytest.raises(ValueError, match="feature dim"):
        server.submit(np.zeros((1, 64), np.float32))
    server.stop()


# ---------------------------------------------------------------------------
# Oversize requests (regression: one request id -> exactly one result)
# ---------------------------------------------------------------------------

def test_oversize_request_coalesces_to_one_result_sync():
    """A request split across micro-batches by the queue must return as ONE
    XMCResult with its rows in order — never several partial results
    sharing the request id."""
    L, D, k = 96, 128, 3
    bsr = _pruned_bsr(L, D, seed=5)
    be = make_backend("dense", bsr, k, n_labels=L)
    engine = XMCEngine(be, buckets=(2, 4), warmup=False, n_features=D)
    rng = np.random.default_rng(6)
    x = rng.normal(size=(11, D)).astype(np.float32)  # 11 rows >> bucket 4
    results = engine.serve([x])
    assert len(results) == 1                          # one id, one result
    assert results[0].labels.shape == (11, k)
    # Row order survives the split: the first piece is exactly x[:4] at
    # bucket 4 (no padding), so the direct backend call is the reference.
    ref_scores, ref_labels = be.topk(jnp.asarray(x[:4]))
    np.testing.assert_array_equal(results[0].labels[:4],
                                  np.asarray(ref_labels))
    np.testing.assert_array_equal(results[0].scores[:4],
                                  np.asarray(ref_scores))


def test_oversize_request_coalesces_to_one_result_async():
    engine = _engine(buckets=(2, 4))
    server = XMCServer(engine, max_batch_delay_ms=1.0)
    rng = np.random.default_rng(7)
    x = rng.normal(size=(11, 128)).astype(np.float32)
    fut = server.submit(x)
    res = fut.result(timeout=60)
    server.stop()
    assert isinstance(res, XMCResult)
    assert res.labels.shape == (11, 3)
    assert server.counters["completed"] == 1          # one future, once
    assert server.latency.count == 1                  # one latency sample


# ---------------------------------------------------------------------------
# Sync-vs-async bit-identity per backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", BACKENDS)
def test_async_results_bit_identical_to_sync(kind):
    """The async loop changes WHEN batches launch, never WHAT they compute:
    with the same pre-queued request stream (same grouping), every backend
    returns bit-identical scores and labels through both paths."""
    L, D, k = 96, 128, 3
    bsr = _pruned_bsr(L, D, seed=8)
    be = make_backend(kind, bsr, k, n_labels=L,
                      shortlist=build_shortlist(bsr))
    reqs = _requests(9, D, seed=9)
    sync_engine = XMCEngine(be, buckets=(2, 4, 8), warmup=False,
                            n_features=D)
    sync = sync_engine.serve(reqs)
    async_engine = XMCEngine(be, buckets=(2, 4, 8), warmup=False,
                             n_features=D)
    server = XMCServer(async_engine, start=False)     # pre-queue everything
    futures = [server.submit(x) for x in reqs]
    server.stop()
    for s, f in zip(sync, futures):
        a = f.result(timeout=0)
        assert a.request_id == s.request_id
        np.testing.assert_array_equal(s.scores, a.scores)
        np.testing.assert_array_equal(s.labels, a.labels)


# ---------------------------------------------------------------------------
# Multi-model routing
# ---------------------------------------------------------------------------

def test_router_dispatches_across_two_checkpoints():
    """Two checkpoints with distinct ServeSpecs behind one router: each
    model answers with its own backend/k, results match that model's own
    synchronous engine, and unknown names fail loudly."""
    rng = np.random.default_rng(10)
    with tempfile.TemporaryDirectory() as da, \
            tempfile.TemporaryDirectory() as db:
        for d, L, seed in ((da, 96, 11), (db, 160, 12)):
            _pruned_bsr(L, 128, seed=seed).save(
                d, meta={"n_labels": L, "n_features": 128})
        ha, hb = CheckpointHandle.open(da), CheckpointHandle.open(db)
        spec_a = ServeSpec(backend="dense", k=3, buckets=(2, 4),
                           warmup=False, max_batch_delay_ms=1.0)
        spec_b = ServeSpec(backend="bsr", k=5, buckets=(2, 4),
                           warmup=False, max_batch_delay_ms=1.0)
        router = ModelRouter({"a": ha.server(spec_a, start=False),
                              "b": hb.server(spec_b, start=False)})
        assert router.models() == ("a", "b")
        xa = rng.normal(size=(2, 128)).astype(np.float32)
        xb = rng.normal(size=(3, 128)).astype(np.float32)
        fa = router.submit("a", xa)
        fb = router.submit("b", xb)
        with pytest.raises(ValueError, match="unknown model"):
            router.submit("nope", xa)
        router.stop()
        ra, rb = fa.result(5), fb.result(5)
        assert ra.labels.shape == (2, 3)              # model a's k
        assert rb.labels.shape == (3, 5)              # model b's k
        np.testing.assert_array_equal(
            ra.labels, ha.engine(spec_a).serve([xa])[0].labels)
        np.testing.assert_array_equal(
            rb.labels, hb.engine(spec_b).serve([xb])[0].labels)
        assert router.stats()["a"]["completed"] == 1
        assert router.stats()["b"]["completed"] == 1


def test_router_rejects_duplicate_model_name():
    router = ModelRouter()
    server = XMCServer(_engine(), start=False, name="m")
    router.add("m", server)
    with pytest.raises(ValueError, match="already routed"):
        router.add("m", server)
    server.stop()


# ---------------------------------------------------------------------------
# ServeSpec plumbing
# ---------------------------------------------------------------------------

def test_servespec_server_fields_roundtrip_and_validate():
    spec = ServeSpec(max_batch_delay_ms=7.5, max_queue=32)
    assert ServeSpec.from_dict(spec.to_dict()) == spec
    # Manifests written before these fields existed deserialize to defaults.
    old = {k: v for k, v in spec.to_dict().items()
           if k not in ("max_batch_delay_ms", "max_queue")}
    assert ServeSpec.from_dict(old) == ServeSpec()
    with pytest.raises(ValueError, match="max_batch_delay_ms"):
        ServeSpec(max_batch_delay_ms=-1.0).validate()
    with pytest.raises(ValueError, match="max_queue"):
        ServeSpec(max_queue=0).validate()


def test_handle_server_uses_spec_knobs():
    with tempfile.TemporaryDirectory() as d:
        _pruned_bsr(96, 128, seed=13).save(
            d, meta={"n_labels": 96, "n_features": 128})
        handle = CheckpointHandle.open(d)
        server = handle.server(
            ServeSpec(backend="dense", k=3, buckets=(2, 4), warmup=False,
                      max_batch_delay_ms=9.0, max_queue=7), start=False)
        assert server.max_batch_delay_ms == 9.0
        assert server.max_queue == 7
        server.stop()
