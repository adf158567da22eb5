"""Continuous-batching async XMC server: the real request path.

`XMCEngine.step()` drains a static queue synchronously — fine for batch
scoring, wrong for production traffic, where requests ARRIVE over time and
host-side batching must not serialize with device compute. This module
wraps an engine in an arrival-time-aware serving loop:

  * **Deadline-launched buckets** — a micro-batch launches the moment the
    largest bucket fills, OR when the oldest queued request has waited
    `max_batch_delay_ms` (continuous batching). Low traffic never waits for
    a bucket to fill; high traffic always ships full buckets.
  * **Double-buffered dispatch** — `max_inflight` bounds the launches
    outstanding on the device (dispatched, not yet finished, counting the
    one the completion thread waits on). The dispatcher takes a device slot
    before it forms a batch, so a batch formed while the device is full
    takes every row that arrived meanwhile instead of queueing behind
    other launches; a slot comes back as soon as its launch finishes on
    the device. At the default 2 one launch runs while the next waits
    behind it, so the device never waits on the host, and the completion
    thread fetches and resolves batch b while b+1 computes.
  * **Admission control** — past `max_queue` pending requests, `submit`
    resolves the future immediately with a `Rejected` result instead of
    growing the queue without bound: under overload, queue wait stays
    bounded and the caller learns it must shed or retry.
  * **Futures** — `submit` returns an `XMCFuture`; `result()` blocks for
    that one request only. Oversize requests (split into several
    micro-batches by the queue) resolve exactly once, with their rows
    re-coalesced in order.
  * **Multi-model routing** — `ModelRouter` holds several named servers
    (one `CheckpointHandle` + `ServeSpec` each) in one process and
    dispatches by model name. Bucket warm-up compiles are shared
    process-wide for equal compile keys, so N models over equal-shaped
    checkpoints cost one compile set per (shape, k).
  * **Zero-downtime hot swap** — `swap(engine)` replaces the serving model
    between micro-batches: the new engine is warmed for this server's
    buckets OFF the dispatcher thread (old model keeps serving through the
    compiles), then the reference flips atomically under the server lock.
    Micro-batches formed before the flip finish on the old model; requests
    batched after it score on the new one — no accepted request is ever
    dropped or re-queued. The previous engine is retained
    (`previous_engine`) so rollback is just `swap` back.
    `ModelRouter.refresh(name, dir)` is the checkpoint-level form, and
    `lifecycle.refresh.CheckpointWatcher` (`ModelRouter.watch`) drives it
    from a generation counter on disk.

Tracing: the worker threads open `jax.profiler.TraceAnnotation` spans
around each step of a micro-batch, so a profiler trace puts the host's
work on the device's clock (free when no profiler runs). Dispatcher:
`xmc.dispatch.idle` (queue empty), `xmc.dispatch.hold` (requests queued,
neither fill nor deadline reached), `xmc.dispatch.slot` (a batch is
launchable and all `max_inflight` launches are on the device), then per
batch `xmc.dispatch.form`, `.put` and `.launch`. Completion thread, per
batch: `xmc.complete.device`, `.fetch`, `.resolve`. `stats()` counts
launches by reason, the launches that waited for a slot, the rows launched
and padded, and the passes the launches made over the weights.

The batching policy itself lives in `serve.batching.MicroBatchQueue`
(`next_batch`); the engine's synchronous `step()` path is untouched and
remains bit-identical to this loop — same queue, same grouping, same
backend math (`tests/test_serve_server.py` holds that invariant per
registered backend).

Spec plumbing: `ServeSpec.max_batch_delay_ms` / `max_queue` configure the
server a checkpoint wants; `CheckpointHandle.server()` (repro.xmc_api)
builds one, and `launch/serve.py --server` runs a multi-model process from
the CLI.
"""

from __future__ import annotations

import dataclasses
import queue as queue_mod
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.serve.batching import LAUNCH_REASONS, LatencyStats
from repro.serve.xmc import XMCEngine, XMCResult


@dataclasses.dataclass
class Rejected:
    """Explicit load-shed answer: the request was NOT queued.

    Returned (through the future, immediately resolved) when admission
    control found `max_queue` requests already waiting. The caller decides
    to retry, back off, or route elsewhere — the server never buffers past
    its bound.
    """
    request_id: int
    reason: str = "queue_full"


class XMCFuture:
    """Hand-rolled future for one submitted request (stdlib-free on purpose:
    no executor semantics, just an event + value resolved by the server's
    completion thread — or instantly, for rejections)."""

    def __init__(self, request_id: int):
        self.request_id = request_id
        self._done = threading.Event()
        self._value: XMCResult | Rejected | None = None

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> XMCResult | Rejected:
        """Block until this request's answer (or `Rejected`) is ready."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not completed in {timeout}s")
        return self._value

    def _resolve(self, value: XMCResult | Rejected) -> None:
        self._value = value
        self._done.set()


@dataclasses.dataclass
class _Assembly:
    """Per-request completion state: parts arrive in dispatch order (the
    hand-off queue is FIFO), the future resolves when the last piece
    lands."""
    future: XMCFuture
    arrival: float
    pieces_left: int
    scores: list[np.ndarray] = dataclasses.field(default_factory=list)
    labels: list[np.ndarray] = dataclasses.field(default_factory=list)


_STOP = object()          # completion-thread sentinel


class _WaitSpan:
    """The dispatcher's wait as profiler spans: one span per stretch of
    one state (`xmc.dispatch.idle` / `.hold`), not one per wake-up."""

    def __init__(self):
        self._name: Optional[str] = None
        self._span: Optional[TraceAnnotation] = None

    def set(self, name: Optional[str]) -> None:
        """Enter state `name` (None: stop waiting); a new span opens only
        when the state changes."""
        if name == self._name:
            return
        if self._span is not None:
            self._span.__exit__(None, None, None)
        self._name, self._span = name, None
        if name is not None:
            self._span = TraceAnnotation(name)
            self._span.__enter__()


class XMCServer:
    """Arrival-time-aware continuous-batching loop over one `XMCEngine`.

    Request lifecycle (the backpressure state machine)::

        submit(x) --admission--> QUEUED --launch--> DISPATCHED --> COMPLETED
                      |            (fill or deadline)   (device)    (future
                      +--> REJECTED (pending_requests >= max_queue)  resolves)

    max_batch_delay_ms : launch deadline — a partially filled bucket ships
        after the oldest queued request has waited this long. 0 launches
        every submit immediately (pure latency mode); large values
        approximate drain-on-full batching (pure throughput mode).
    max_queue : admission bound on requests waiting for launch (dispatched/
        in-flight work does not count). None = unbounded (closed-loop /
        trusted callers only).
    max_inflight : launches outstanding on the device, dispatched and not
        yet finished; a launchable batch is formed only once one of them
        finishes. 2 = double-buffering (batch b+1 queued on the device
        while batch b computes).
    start : spawn the worker threads now. Pass False to pre-load requests
        and start later — with everything queued up front the launch
        grouping is identical to `engine.step()`'s drain, which is how the
        sync-vs-async bit-identity tests pin the loop.
    """

    def __init__(self, engine: XMCEngine, *,
                 max_batch_delay_ms: float = 2.0,
                 max_queue: Optional[int] = None,
                 max_inflight: int = 2,
                 name: Optional[str] = None,
                 start: bool = True):
        if max_batch_delay_ms < 0:
            raise ValueError(f"max_batch_delay_ms must be >= 0, got "
                             f"{max_batch_delay_ms}")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1 (or None for "
                             f"unbounded), got {max_queue}")
        if max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1, got {max_inflight}")
        self.engine = engine
        self.name = name
        self.max_batch_delay_ms = float(max_batch_delay_ms)
        self.max_queue = max_queue
        self.queue = engine.queue
        self.latency = LatencyStats()        # arrival -> completion
        self.queue_wait = LatencyStats()     # arrival -> device dispatch
        self.counters = {"accepted": 0, "rejected": 0, "completed": 0,
                         "batches": 0, "slot_waits": 0, "swaps": 0,
                         **{f"launched_{r}": 0 for r in LAUNCH_REASONS},
                         "rows_launched": 0, "rows_padded": 0,
                         "weight_passes": 0, "topk_grouped": 0}
        self.previous_engine: Optional[XMCEngine] = None  # rollback target
        self.last_swap: Optional[dict] = None   # timing of the latest swap
        self._cv = threading.Condition()
        self._by_rid: dict[int, _Assembly] = {}
        self._slots = threading.Semaphore(max_inflight)   # device slots
        self._inflight: queue_mod.Queue = queue_mod.Queue()  # FIFO hand-off
        self._stopping = False
        self._started = False
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name=f"xmc-dispatch-{name}",
            daemon=True)
        self._completer = threading.Thread(
            target=self._completion_loop, name=f"xmc-complete-{name}",
            daemon=True)
        if start:
            self.start()

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "XMCServer":
        if not self._started:
            self._started = True
            self._completer.start()
            self._dispatcher.start()
        return self

    def stop(self) -> None:
        """Flush and shut down: every accepted request still resolves (the
        dispatcher force-drains the queue on its way out), then both worker
        threads exit. Idempotent; `submit` after stop raises."""
        with self._cv:
            if self._stopping:
                self._started or self._drain_unstarted()
                return
            self._stopping = True
            self._cv.notify_all()
        if self._started:
            self._dispatcher.join()
            self._completer.join()
        else:
            self._drain_unstarted()

    def __enter__(self) -> "XMCServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _drain_unstarted(self) -> None:
        """A never-started server still owes answers on stop: run the loop
        body inline, completing after every dispatch so its device slot is
        back before the next one is taken (tests build servers with
        start=False)."""
        while self._dispatch_once(force=True):
            self._complete_pending()
        self._complete_pending()

    # -- hot swap -----------------------------------------------------------

    def swap(self, engine: XMCEngine) -> XMCEngine:
        """Replace the serving model with `engine`, zero downtime.

        The swap state machine::

            VALIDATE --> WARM (off-thread, old model still serving)
                     --> FLIP (atomic, under the server lock, between
                               micro-batches)

        VALIDATE raises before anything changes: a feature-dim mismatch
        (requests already accepted for D_old could never score on D_new)
        or a stopped server. WARM compiles the new engine's top-k for THIS
        server's buckets on the calling thread — the dispatcher keeps
        serving the old model throughout, so warm-up cost never shows up
        as request latency (equal-shaped models share compiles via the
        process-wide warm-up ledger and pay ~nothing here). FLIP takes the
        lock and replaces the engine reference: micro-batches already
        formed (they captured the old engine in `_dispatch_once`) complete
        on the old model; everything batched after the flip scores on the
        new one. No accepted request is dropped or re-queued.

        Returns the previous engine (also retained as `previous_engine`),
        so rollback is `server.swap(server.previous_engine)`.
        """
        with self._cv:
            if self._stopping:
                raise RuntimeError("cannot swap on a stopped server")
            old = self.engine
        nf_old, nf_new = old.n_features, engine.n_features
        if nf_new is None:
            nf_new = nf_old
            if nf_old is not None:
                engine.adopt_n_features(nf_old)
        if nf_old is not None and nf_new != nf_old:
            raise ValueError(
                f"cannot swap: new engine serves feature dim {nf_new}, "
                f"server accepts feature dim {nf_old}")
        t0 = time.monotonic()
        if engine.n_features is not None:       # warm outside the lock
            engine.warmup(self.queue.buckets)
        t_warm = time.monotonic()
        with self._cv:
            if self._stopping:
                raise RuntimeError("cannot swap on a stopped server")
            prev = self.engine
            self.engine = engine
            self.previous_engine = prev
            self.counters["swaps"] += 1
            t_flip = time.monotonic()
            self.last_swap = {"warm_ms": (t_warm - t0) * 1e3,
                              "flip_ms": (t_flip - t_warm) * 1e3,
                              "t_flip": t_flip}
            self._cv.notify_all()
        return prev

    # -- request path -------------------------------------------------------

    def submit(self, x: np.ndarray) -> XMCFuture:
        """Enqueue one (n_i, D) request; returns its future immediately.

        The future resolves to an `XMCResult` (top-k per instance, split
        requests re-coalesced) — or to `Rejected`, already resolved at
        return, when admission control sheds the request.
        """
        x = np.asarray(x, np.float32)
        assert x.ndim == 2, "a request is an (n_i, D) feature batch"
        nf = self.engine.n_features
        if nf is not None and x.shape[1] != nf:
            raise ValueError(f"request feature dim {x.shape[1]} != engine "
                             f"feature dim {nf}")
        with self._cv:
            if self._stopping:
                raise RuntimeError("server is stopped")
            if self.max_queue is not None and \
                    self.queue.pending_requests() >= self.max_queue:
                fut = XMCFuture(self.queue.reserve_id())
                fut._resolve(Rejected(fut.request_id))
                self.counters["rejected"] += 1
                return fut
            arrival = time.monotonic()
            rid = self.queue.submit(x, arrival=arrival)
            fut = XMCFuture(rid)
            self._by_rid[rid] = _Assembly(
                future=fut, arrival=arrival,
                pieces_left=self.queue.pieces_of(x.shape[0]))
            self.counters["accepted"] += 1
            self._cv.notify_all()
        return fut

    # -- worker loops -------------------------------------------------------

    def _dispatch_once(self, *, force: bool = False) -> bool:
        """Take a device slot, then form one micro-batch if launchable,
        dispatch it to the device, and hand it to the completion side.
        Returns False when nothing was launchable."""
        delay_s = self.max_batch_delay_ms / 1e3
        with self._cv:
            if not self.queue:
                return False
        # Wait outside the lock: submit() keeps queueing rows meanwhile,
        # and the batch formed below takes them all.
        if not self._slots.acquire(blocking=False):
            self.counters["slot_waits"] += 1
            with TraceAnnotation("xmc.dispatch.slot"):
                self._slots.acquire()
        launched = False
        try:
            with self._cv:
                with TraceAnnotation("xmc.dispatch.form"):
                    mb = self.queue.next_batch(max_delay_s=delay_s,
                                               force=force)
                engine = self.engine     # captured with the batch: a
                # concurrent swap() must not tear one micro-batch across
                # two models
            if mb is None:
                return False
            engine.ensure_warm(mb.bucket)
            with TraceAnnotation("xmc.dispatch.put"):
                xb = jnp.asarray(mb.x)               # host pack -> device put
            t_dispatch = time.monotonic()
            with TraceAnnotation("xmc.dispatch.launch"):
                scores, labels = engine.backend.topk(xb)    # async dispatch
            launched = True
        finally:
            if not launched:
                self._slots.release()
        rows = sum(mb.row_counts)
        c = self.counters
        c["batches"] += 1
        c[f"launched_{mb.launch}"] += 1
        c["rows_launched"] += rows
        c["rows_padded"] += mb.bucket - rows
        passes = getattr(engine.backend, "weight_passes", None)
        if passes is not None:
            c["weight_passes"] += passes(mb.bucket)
        grouped = getattr(engine.backend, "topk_grouped", None)
        if grouped is not None:
            c["topk_grouped"] += grouped(mb.bucket)
        self._inflight.put((mb, scores, labels, t_dispatch))
        return True

    def _dispatch_loop(self) -> None:
        delay_s = self.max_batch_delay_ms / 1e3
        cap = self.queue.buckets[-1]
        waiting = _WaitSpan()
        while True:
            with self._cv:
                while True:
                    if self._stopping:
                        break
                    now = time.monotonic()
                    if self.queue.pending_rows() >= cap:
                        break                    # bucket full: launch now
                    oldest = self.queue.oldest_arrival()
                    if oldest is not None and now - oldest >= delay_s:
                        break                    # deadline expired: launch
                    if oldest is None:
                        waiting.set("xmc.dispatch.idle")
                        wait = None
                    else:
                        waiting.set("xmc.dispatch.hold")
                        wait = max(delay_s - (now - oldest), 0.0)
                    self._cv.wait(timeout=wait)
                waiting.set(None)
                stopping = self._stopping
            if not self._dispatch_once(force=stopping) and stopping:
                break
        self._inflight.put(_STOP)

    def _complete_batch(self, mb, scores, labels, t_dispatch: float) -> None:
        try:
            with TraceAnnotation("xmc.complete.device"):
                jax.block_until_ready(labels)
        finally:
            self._slots.release()      # the device is done with this launch
        with TraceAnnotation("xmc.complete.fetch"):
            scores, labels = np.asarray(scores), np.asarray(labels)
        t_done = time.monotonic()
        with TraceAnnotation("xmc.complete.resolve"):
            self._resolve_batch(mb, scores, labels, t_dispatch, t_done)

    def _resolve_batch(self, mb, scores: np.ndarray, labels: np.ndarray,
                       t_dispatch: float, t_done: float) -> None:
        """Split a finished batch by request, assemble each request's
        pieces, record its latency and queue wait, and resolve its
        future once its last piece lands."""
        resolved = []
        with self._cv:
            for (rid, s), (_, l) in zip(mb.split(scores), mb.split(labels)):
                asm = self._by_rid.get(rid)
                if asm is None:     # enqueued via engine.submit, not ours
                    continue
                asm.scores.append(s)
                asm.labels.append(l)
                asm.pieces_left -= 1
                if asm.pieces_left == 0:
                    del self._by_rid[rid]
                    self.latency.record_span(asm.arrival, t_done)
                    self.queue_wait.record_span(asm.arrival, t_dispatch)
                    self.counters["completed"] += 1
                    resolved.append((asm.future, XMCResult(
                        request_id=rid,
                        scores=np.concatenate(asm.scores, axis=0),
                        labels=np.concatenate(asm.labels, axis=0))))
        for fut, res in resolved:        # wake waiters outside the lock
            fut._resolve(res)

    def _complete_pending(self) -> None:
        while True:
            try:
                item = self._inflight.get_nowait()
            except queue_mod.Empty:
                return
            if item is not _STOP:
                self._complete_batch(*item)

    def _completion_loop(self) -> None:
        while True:
            item = self._inflight.get()
            if item is _STOP:
                return
            self._complete_batch(*item)

    # -- introspection ------------------------------------------------------

    def stats(self) -> dict:
        """Counters + latency percentiles: `latency` is per-request
        arrival->completion, `queue_wait` is arrival->device-dispatch (what
        admission control bounds), each over the most recent
        `LATENCY_SAMPLES` requests. `launched_full` / `_deadline` /
        `_forced` count batches by why they launched (they sum to
        `batches`); `slot_waits` counts the batches that were launchable
        but found all `max_inflight` device slots taken; `rows_launched`
        counts the requests' rows and `rows_padded` the bucket rows added
        to them. `weight_passes` sums the passes each launch made over the
        weights, as a backend with a `weight_passes(bucket)` reports them
        (the BSR backend does); other backends add nothing. `topk_grouped` counts the launches whose
        top-k took the grouped form, as a backend with a
        `topk_grouped(bucket)` reports it (the BSR backend does)."""
        out = dict(self.counters)
        out["pending_requests"] = self.queue.pending_requests()
        accepted = out["accepted"] + out["rejected"]
        out["reject_rate"] = (out["rejected"] / accepted) if accepted else 0.0
        out["latency"] = self.latency.summary()
        out["queue_wait"] = self.queue_wait.summary()
        return out


class ModelRouter:
    """Several named `XMCServer`s in one process; requests dispatch by model
    name. Pure routing — each server keeps its own queue, deadline, and
    admission bound (its model's `ServeSpec`), and bucket warm-up compiles
    for equal (shape, dtype, k) keys are already shared process-wide by the
    engines, so co-hosting N equal-shaped models costs one compile set.

        router = ModelRouter({"wiki": handle_a.server(),
                              "amazon": handle_b.server(ServeSpec(k=10))})
        fut = router.submit("wiki", x)
    """

    def __init__(self, servers: Optional[dict[str, XMCServer]] = None):
        self._servers: dict[str, XMCServer] = {}
        self._watchers: list = []            # CheckpointWatchers we own
        for name, srv in (servers or {}).items():
            self.add(name, srv)

    def add(self, name: str, server: XMCServer) -> "ModelRouter":
        if name in self._servers:
            raise ValueError(f"model {name!r} already routed")
        if server.name is None:
            server.name = name
        self._servers[name] = server
        return self

    def models(self) -> tuple[str, ...]:
        return tuple(sorted(self._servers))

    def __getitem__(self, name: str) -> XMCServer:
        return self._servers[name]

    def __len__(self) -> int:
        return len(self._servers)

    def submit(self, model: str, x: np.ndarray) -> XMCFuture:
        try:
            server = self._servers[model]
        except KeyError:
            raise ValueError(f"unknown model {model!r}; routed models: "
                             f"{self.models()}") from None
        return server.submit(x)

    def refresh(self, name: str, directory: str, *,
                serve_override=None, mesh=None):
        """Hot-swap the named server onto the checkpoint in `directory`.

        Opens the checkpoint strictly (a still-streaming directory raises
        — see `CheckpointHandle.open`), builds the engine its spec (or
        `serve_override`) describes, and `swap`s it in: the server keeps
        answering on the old model until the new one is warm, then flips
        between micro-batches. Returns the previous engine (kept on the
        server as `previous_engine`) for rollback.
        """
        try:
            server = self._servers[name]
        except KeyError:
            raise ValueError(f"unknown model {name!r}; routed models: "
                             f"{self.models()}") from None
        from repro.xmc_api import CheckpointHandle      # deferred: no cycle
        handle = CheckpointHandle.open(directory)
        serve = (serve_override or handle.spec.serve).validate()
        # swap() warms for the SERVER's buckets — skip the engine's own
        # construction-time warm-up so nothing compiles twice.
        engine = handle.engine(serve.replace(warmup=False), mesh=mesh)
        return server.swap(engine)

    def watch(self, name: str, directory: str, *, serve_override=None,
              mesh=None, poll_interval_s: float = 2.0, on_swap=None):
        """Attach a `lifecycle.refresh.CheckpointWatcher` that polls
        `directory`'s generation counter and `refresh`es the named server
        whenever a newer finalized checkpoint lands. The watcher thread is
        owned by the router and joined by `stop()`. Returns the watcher
        (use its `poll_once()` for deterministic tests)."""
        if name not in self._servers:
            raise ValueError(f"unknown model {name!r}; routed models: "
                             f"{self.models()}")
        from repro.lifecycle.refresh import CheckpointWatcher  # no cycle
        watcher = CheckpointWatcher(
            directory, self._servers[name], serve_override=serve_override,
            mesh=mesh, poll_interval_s=poll_interval_s, on_swap=on_swap)
        self._watchers.append(watcher)
        watcher.start()
        return watcher

    def start(self) -> "ModelRouter":
        for srv in self._servers.values():
            srv.start()
        return self

    def stop(self) -> None:
        for w in self._watchers:     # watchers first: no swap mid-drain
            w.stop()
        for srv in self._servers.values():
            srv.stop()

    def __enter__(self) -> "ModelRouter":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def stats(self) -> dict[str, dict]:
        return {name: srv.stats() for name, srv in self._servers.items()}
