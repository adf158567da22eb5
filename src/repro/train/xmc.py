"""Streaming label-batch training engine for DiSMEC (Algorithm 1 at scale).

This module is the *engine* under the declarative session API: the public
way to train is

    from repro.xmc_api import XMCSpec, fit
    handle = fit(X, Y, XMCSpec(...), out_dir)        # -> CheckpointHandle
    engine = handle.engine()                          # -> serving XMCEngine

`fit()` builds an `XMCTrainJob` from the spec's `SolverSpec`/`ScheduleSpec`
and runs it here; the spec is embedded in the checkpoint manifest (both as
the resume fingerprint and as recoverable metadata), so the checkpoint
alone reproduces the experiment. `init_from=` warm-starts every batch's
TRON from a prior checkpoint's rows mapped back to label ranges.
`train_streaming` below is the deprecated pre-spec shim over the same
engine; `core.dismec.train/train_sharded` are the in-memory adapters.

The paper's model never exists dense — 870 GB of OvR weights become 3 GB of
(value, index) pairs via Delta-pruning (§2.2) — and this pipeline makes the
*training* side honor that: device memory is O(label_batch x D), the servable
artifact is written incrementally, and a killed job resumes where it stopped.

`XMCTrainJob` composes the two layers of Algorithm 1 with the streaming
writer; the mapping to the algorithm's steps 3-11:

  step 3    `for b in 0..B` over label batches   -> the host-side scheduler
            loop in `run()`. Batches are contiguous label ranges of size
            `cfg.label_batch` so the checkpoint streams in label order; the
            last partial batch is padded with all-negative sign rows so every
            batch shares one compiled solver executable.
  steps 4-6 dispatch batch b to a node, train its binary problems in
            parallel -> one mesh-sharded batched-TRON call
            (`core.dismec.make_batch_solver`): labels sharded over the mesh
            `model` axis (optionally instances over `data` with psum'd
            grad/Hv), each shard solved by one SIMT-style TRON loop.
            `balance=True` deals a batch's labels to shards with the
            frequency-balanced `balance_permutation` (the un-permutation is
            host-side, per batch), equalizing shard wall times.
  step 7    prune ambiguous weights  -> `prune` runs inside the jitted solve,
            on device, before the block ever travels to the host.
  steps 8-10 write batch b's sparse model file -> the pruned block lands on
            the host, is packed to append-form BSR
            (`to_block_sparse(row_block_offset=...)`) and appended to the
            multi-shard checkpoint by `checkpoint.io.BlockSparseWriter`
            (one shard .npz per batch + an atomically rewritten manifest).
            With `overlap=True` (default) this host leg runs on a bounded
            background worker: the scheduler dispatches batch b+1's solver
            (jax dispatch is asynchronous) before batch b's result has even
            left the device, so the device->host transfer + BSR pack +
            compressed shard write of batch b hide behind batch b+1's
            compute. `max_inflight` bounds how many un-drained device
            results may exist at once (device memory stays
            O(max_inflight x label_batch x D)); the single worker drains
            them strictly in dispatch order, so the manifest grows in
            exactly the sequential order and every crash/resume/manifest
            invariant below is unchanged (`overlap=False` restores the
            fully sequential scheduler).
  step 11   assemble W  -> never materialized during training. The manifest
            IS the model: `checkpoint.io.load_block_sparse` stitches the
            shards by row_ptr bookkeeping and PR 1's `XMCEngine` serves the
            result unchanged. (`materialize=True`, used by the in-memory
            `core.dismec.train` wrapper, assembles W host-side instead.)

Resume: the manifest lists finished batches; a restarted job skips them and
solves only the rest. A crash between a shard write and its manifest update
orphans one shard file, which the next run simply re-solves and overwrites.

Multi-host layer 1: with `ScheduleSpec(workers=N)` (or an explicit
`worker=` id), step 3's loop claims batches through the manifest's lease
table instead of walking them statically — N independent `fit()` processes
pointed at one `out_dir` cooperatively drain the label-batch queue into a
single checkpoint, exactly the paper's dispatch of batches to nodes. The
manifest's solver/schedule/data fingerprint gates every joiner, so
co-workers running a different spec (or different data) are rejected; a
worker that dies mid-batch is recovered by lease expiry (`lease_ttl`).
"""

from __future__ import annotations

import dataclasses
import os
import queue
import socket
import threading
import time
from typing import Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.checkpoint.io import (BSR_ARRAYS, BlockSparseWriter,
                                 has_block_sparse_checkpoint,
                                 label_range_reader, load_block_sparse_meta)
from repro.core.dismec import (DiSMECConfig, DiSMECModel, balance_permutation,
                               make_batch_solver)
from repro.core.pruning import to_block_sparse
from repro.specs import ScheduleSpec, ServeSpec, SolverSpec

Array = jax.Array


def default_worker_id() -> str:
    """Identity of this trainer process in a cooperative multi-worker
    drain: unique per (host, process), stable for the process lifetime —
    what a batch lease records as its holder when the user does not pass
    an explicit `--worker-id`."""
    return f"{socket.gethostname()}-{os.getpid()}"


def _init_fingerprint(init_from: str) -> dict:
    """Content identity of a warm-start source. The solved weights depend
    on W0 (truncated Newton stops early), so a resumed warm run must not
    stitch shards seeded from a *different* prior model. A streamed source
    carries its own solver+data fingerprint in its manifest; a one-shot
    artifact has none, so its packed values are digested directly."""
    index = load_block_sparse_meta(init_from)
    if index.get("layout") == "stream":
        return {"solver": index["manifest"].get("solver"),
                "n_blocks": index["n_blocks"]}
    blocks = np.load(os.path.join(init_from, BSR_ARRAYS))["blocks"]
    return {"shape": list(index["shape"]), "n_blocks": index["n_blocks"],
            "nnz": int(np.count_nonzero(blocks)),
            "sum": float(blocks.sum()),
            "abs_sum": float(np.abs(blocks).sum())}


@dataclasses.dataclass
class XMCTrainResult:
    """What one `XMCTrainJob.run` did (and, if materialized, the model)."""
    model: Optional[DiSMECModel]   # only when materialize=True and complete
    out_dir: Optional[str]         # streamed checkpoint directory (if any)
    n_batches: int                 # total label batches of the job
    solved: list[int]              # batch ids solved by THIS run
    skipped: list[int]             # batch ids resumed from the manifest
    complete: bool                 # all batches present (checkpoint servable)
    manifest: Optional[dict]       # final manifest when streamed + complete
    # One dict per batch THIS run solved, in solve order: label count,
    # TRON's Newton and CG iterations (max and mean over the batch's
    # labels), `wall_s` — host-clock seconds from when the batch could
    # start (its submission to the solver, or the previous batch's
    # arrival) to when its weights reached the host, i.e. the pipeline's
    # period, set by the slower of the device solve and the previous
    # batch's pack + write; the first batch's includes the solver compile
    # — and `write_s`, this batch's BSR pack + shard write on the host.
    batch_stats: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass(frozen=True)
class XMCTrainJob:
    """Algorithm 1's outer loop as a restartable streaming pipeline.

    cfg.label_batch sets the layer-1 batch size (the paper's per-node label
    count); when streaming to a checkpoint it must be a multiple of the BSR
    block height so per-batch blocks append without re-tiling. `mesh` turns
    on layer-2 mesh sharding for each batch's solve; `balance` deals each
    batch's labels to mesh shards frequency-balanced (no-op without a mesh).

    `overlap` double-buffers the loop: batch b+1's solve is dispatched
    before batch b's result is pulled off the device, and the
    transfer/pack/write leg runs on a background worker; a semaphore
    acquired before dispatch and released after the drain caps un-drained
    device results at `max_inflight` (see the module docstring). The
    produced checkpoint is byte-identical to a sequential
    (`overlap=False`) run.

    `workers > 1` (or an explicit `worker=` id to `run`) turns the static
    skip-finished loop into a lease-aware iterator over the shared
    manifest: each batch is atomically claimed before dispatch
    (`BlockSparseWriter.claim_next_batch`), held alive by a heartbeat
    thread while it solves, and released by its shard's manifest commit —
    so N independent processes pointed at the same `out_dir` drain one
    queue into one checkpoint (the paper's layer 1 over real nodes). A
    worker killed mid-batch is recovered when its lease outlives
    `lease_ttl`; a worker that exits cleanly (error, `max_batches`)
    releases its leases so co-workers reclaim immediately. Per-batch
    solves are deterministic, so the cooperative checkpoint is
    bit-identical to a single-worker one.
    """
    cfg: DiSMECConfig
    mesh: Optional[Mesh] = None
    label_axis: str = "model"
    data_axis: str = "data"
    shard_data: bool = False
    balance: bool = False
    block_shape: tuple[int, int] = (128, 128)
    overlap: bool = True
    max_inflight: int = 2
    workers: int = 1
    lease_ttl: float = 300.0

    def label_batches(self, n_labels: int) -> list[tuple[int, int]]:
        """Contiguous [start, stop) label ranges of the scheduler loop."""
        lb = min(self.cfg.label_batch, n_labels)
        return [(s, min(s + lb, n_labels)) for s in range(0, n_labels, lb)]

    def specs(self) -> tuple[SolverSpec, ScheduleSpec]:
        """This job as (SolverSpec, ScheduleSpec) — the adapter that lets
        every entry point (spec-driven or legacy) write one manifest
        format."""
        return SolverSpec.from_config(self.cfg), ScheduleSpec.from_job(self)

    def run(self, X: Array, Y: Array, out_dir: Optional[str] = None, *,
            resume: bool = True, materialize: Optional[bool] = None,
            max_batches: Optional[int] = None, meta: Optional[dict] = None,
            on_batch: Optional[Callable[[int, int], None]] = None,
            init_from: Optional[str] = None, worker: Optional[str] = None,
            label_order=None) -> XMCTrainResult:
        """Train X (N, D), Y (N, L) into `out_dir` (streamed multi-shard
        checkpoint) and/or an in-memory model.

        resume       : skip batches already listed in out_dir's manifest
                       (False starts the checkpoint fresh).
        materialize  : assemble the dense W host-side and return a
                       DiSMECModel; defaults to True only when not streaming.
        max_batches  : stop after solving this many new batches (the
                       checkpoint is left incomplete — the crash/preemption
                       story, used by tests and the resume benchmark).
        on_batch     : callback (batch_id, n_batches) after each solved
                       batch — progress reporting / instrumentation hooks.
                       With overlap=True it fires on the background writer
                       thread, still in batch order and still after that
                       batch's shard write; an exception it raises aborts
                       the run like a write failure would.
        init_from    : warm start — a prior block-sparse checkpoint whose
                       rows seed each batch's TRON as W0 (label ranges are
                       read shard-by-shard, never the full matrix; labels
                       past the prior model's L cold-start at zero). The
                       stopping tolerance stays anchored at the cold-start
                       gradient, so a converged same-spec source is a fixed
                       point: the solver accepts it unchanged.
        worker       : this process's identity in a cooperative multi-worker
                       drain (defaults to host-pid via `default_worker_id`).
                       Passing it — or setting `workers > 1` on the job —
                       switches the scheduler to lease-based batch claiming
                       over the shared manifest; `solved`/`on_batch` then
                       cover only the batches THIS worker claimed. A worker
                       with nothing left to claim sees the job through: it
                       polls (bounded ~1 s sleeps) until co-workers commit
                       their leases — or reclaims them when they expire, so
                       a dead co-worker's batches recover with no manual
                       step. `complete` is therefore True on every normal
                       cooperative return; False only when `max_batches`
                       cut this worker short or an error aborted the run.
                       Liveness caveat: a co-worker that is stuck alive
                       (still heartbeating, never committing) blocks
                       completion until an operator kills it and its lease
                       expires.
        label_order  : pack-time label permutation (len L): the run trains
                       and streams `Y[:, label_order]`, so packed row j of
                       the checkpoint holds original label label_order[j].
                       Recorded in the manifest (identity-checked on
                       resume, both directions) and unmapped exactly by
                       the serving engine. `fit()` computes it from
                       `ScheduleSpec.reorder_labels` via
                       `serve.shortlist.cooccurrence_label_order`.
        """
        Yn = np.asarray(Y)
        if label_order is not None:
            label_order = np.asarray(label_order, np.int64).reshape(-1)
            Yn = Yn[:, label_order]       # train/pack in permuted order
        N, L = Yn.shape
        D = int(X.shape[1])
        batches = self.label_batches(L)
        lb = batches[0][1] - batches[0][0]
        n_shards = self.mesh.shape[self.label_axis] if self.mesh else 1
        # Every batch is padded to one shape: lb rounded up to the label-shard
        # count, so the whole run compiles the solver exactly once.
        lb_solve = -(-lb // n_shards) * n_shards
        bl, bd = self.block_shape
        if materialize is None:
            materialize = out_dir is None
        init_read = None
        if init_from is not None:
            init_index = load_block_sparse_meta(init_from)
            init_D = init_index["orig_shape"][1]
            if init_D != D:
                raise ValueError(
                    f"init_from checkpoint has feature dim {init_D}, "
                    f"dataset has {D}; warm start needs matching features")
            # Built once: a one-shot source is densified a single time and
            # sliced per batch; a streamed source reads only the shards
            # each batch's range overlaps.
            init_read = label_range_reader(init_from)

        solver_spec, schedule_spec = self.specs()
        writer = None
        done: set[int] = set()
        if out_dir is not None:
            if lb % bl != 0 and len(batches) > 1:
                raise ValueError(
                    f"label_batch={lb} must be a multiple of the BSR block "
                    f"height {bl} to stream batches without re-tiling "
                    "(round label_batch up, or shrink block_shape — the "
                    "spec path, repro.xmc_api.fit, normalizes this "
                    "automatically)")
            # The solved weights depend on the full solver/schedule spec,
            # the dataset, and any warm-start source: record them so a
            # resumed run cannot silently mix shards trained under
            # different settings into one checkpoint.
            solver_id = {
                "spec": {"solver": solver_spec.fingerprint(),
                         "schedule": schedule_spec.fingerprint()},
                "init": (None if init_from is None
                         else _init_fingerprint(init_from)),
                "data": [int(N), int(D), float(np.asarray(X).sum()),
                         int(Yn.sum())]}
            # Full recoverable experiment description (adds the knobs the
            # fingerprint deliberately drops); fit() overrides this with
            # the user's spec, serve section included.
            meta_full = {"n_labels": L, "n_features": D,
                         "delta": self.cfg.delta, **(meta or {})}
            meta_full.setdefault("xmc_spec", {
                "solver": solver_spec.to_dict(),
                "schedule": schedule_spec.canonical().to_dict(),
                "serve": ServeSpec().to_dict()})
            writer = BlockSparseWriter(
                out_dir, n_labels=L, n_features=D,
                block_shape=self.block_shape, label_batch=lb,
                n_batches=len(batches), resume=resume, solver=solver_id,
                meta=meta_full, label_order=label_order)
            done = writer.done_batches

        solver = make_batch_solver(X, self.cfg, self.mesh,
                                   label_axis=self.label_axis,
                                   data_axis=self.data_axis,
                                   shard_data=self.shard_data,
                                   warm=init_from is not None)

        host_blocks: dict[int, np.ndarray] = {}
        solved: list[int] = []
        skipped: list[int] = []
        batch_stats: list[dict] = []
        last_ready = [0.0]

        # Multi-host layer 1: with a worker identity (explicit, or implied
        # by workers > 1) batches are claimed from the shared manifest's
        # lease table instead of walked statically.
        coordinate = writer is not None and (self.workers > 1
                                             or worker is not None)
        worker_id = worker or default_worker_id()
        held: set[int] = set()               # leases this worker holds now
        held_lock = threading.Lock()
        # First failure from the background drain worker (overlap mode).
        # Shared with leased_batches: the claim-wait loop must abort on it,
        # or a failed batch's still-held (and heartbeated) lease would keep
        # the loop waiting forever — wedging this worker AND every
        # co-worker behind the never-released lease.
        failed: list[BaseException] = []

        def dispatch(b: int, start: int, stop: int):
            """Host-side prep + asynchronous device dispatch of one batch."""
            rows = stop - start
            signs = (2.0 * Yn[:, start:stop].T - 1.0).astype(np.float32)
            perm = None
            if self.balance and self.mesh is not None and rows > n_shards:
                perm = balance_permutation(Yn[:, start:stop], n_shards)
                signs = signs[perm]
            W0 = None
            if init_read is not None:
                W0r = init_read(start, stop)
                if perm is not None:       # W0 rows follow the shard dealing
                    W0r = W0r[perm]
                if rows < lb_solve:
                    W0r = np.concatenate(
                        [W0r, np.zeros((lb_solve - rows, D), np.float32)])
                W0 = jnp.asarray(W0r)
            if rows < lb_solve:                           # shape-constant pad
                signs = np.concatenate(
                    [signs, -np.ones((lb_solve - rows, N), np.float32)])
            t_submit = time.time()
            sol = solver(jnp.asarray(signs), W0)
            return (b, start, rows, perm, t_submit, sol.W[:rows],
                    sol.n_newton[:rows], sol.n_cg[:rows])

        def drain(item) -> None:
            """Device->host transfer + BSR pack + shard write of one solved
            batch (paper's steps 8-10) — the leg that overlaps batch b+1's
            device compute when `overlap=True`."""
            b, start, rows, perm, t_submit, W_dev, newton, cg = item
            W_b = np.asarray(W_dev)
            t_ready = time.time()
            newton, cg = np.asarray(newton), np.asarray(cg)
            stats = {"batch": b, "labels": rows,
                     "newton_max": int(newton.max()),
                     "newton_mean": float(newton.mean()),
                     "cg_max": int(cg.max()), "cg_mean": float(cg.mean()),
                     "wall_s": t_ready - max(t_submit, last_ready[0])}
            last_ready[0] = t_ready
            if perm is not None:
                W_b = W_b[np.argsort(perm)]               # undo shard dealing
            if writer is not None:
                # device=False: the pack stays numpy end-to-end — a device
                # put here would queue behind the in-flight batch solves
                # this worker is meant to overlap.
                part = to_block_sparse(W_b, self.block_shape,
                                       row_block_offset=start // bl,
                                       sentinel_if_empty=False, device=False)
                # The manifest commit inside write_batch also releases
                # this batch's lease.
                writer.write_batch(b, part, row_start=start, n_rows=rows)
            stats["write_s"] = time.time() - t_ready
            batch_stats.append(stats)
            with held_lock:
                held.discard(b)
            if materialize:
                host_blocks[b] = W_b
            solved.append(b)
            if on_batch is not None:
                on_batch(b, len(batches))

        def leased_batches() -> Iterable[tuple[int, int, int]]:
            """Lease-aware layer-1 iterator: claim the next unleased (or
            expired) batch from the shared manifest right before
            dispatching it; when everything left is leased by live
            co-workers, back off until the earliest lease could expire —
            normally its commit lands first and the queue reads drained,
            but a dead worker's batch is reclaimed here with no manual
            cleanup."""
            n_claimed = 0
            while max_batches is None or n_claimed < max_batches:
                if failed:                      # drain died: stop claiming
                    return
                with held_lock:
                    in_flight = set(held)
                b = writer.claim_next_batch(worker_id, ttl=self.lease_ttl,
                                            exclude=in_flight)
                if b is None:
                    wait = writer.claim_wait_seconds()
                    if wait is None:            # every batch is written
                        return
                    time.sleep(min(max(wait, 0.05), 1.0))
                    continue
                with held_lock:
                    held.add(b)
                n_claimed += 1
                yield (b, *batches[b])

        if coordinate:
            skipped.extend(sorted(done))                  # done before we ran
            if materialize:
                for b in skipped:
                    host_blocks[b] = writer.read_batch_dense(b)
            work_iter: Iterable[tuple[int, int, int]] = leased_batches()
        else:
            to_solve: list[tuple[int, int, int]] = []
            for b, (start, stop) in enumerate(batches):   # paper's step 3
                if b in done:
                    skipped.append(b)
                    if materialize:
                        host_blocks[b] = writer.read_batch_dense(b)
                    continue
                if max_batches is not None and len(to_solve) >= max_batches:
                    break
                to_solve.append((b, start, stop))
            work_iter = to_solve

        hb_stop = threading.Event()
        hb_thread = None
        if coordinate:
            # Leases must outlive arbitrarily long solves: refresh every
            # currently-held one well inside the TTL.
            def _heartbeat():
                interval = max(0.05, self.lease_ttl / 4.0)
                while not hb_stop.wait(interval):
                    with held_lock:
                        current = sorted(held)
                    try:
                        writer.heartbeat(worker_id, current)
                    except OSError:       # transient fs hiccup: next tick
                        pass
            hb_thread = threading.Thread(target=_heartbeat, daemon=True,
                                         name="xmc-lease-heartbeat")
            hb_thread.start()

        try:
            if not self.overlap:
                for item in work_iter:
                    drain(dispatch(*item))
            else:
                # Double-buffered: the main thread keeps dispatching solves;
                # a single background worker drains results in dispatch
                # order. A slot must be acquired BEFORE a batch is claimed
                # and dispatched, and is released only once its result is
                # fully drained, so at most max_inflight un-drained device
                # results (and held leases) exist at any moment.
                slots = threading.Semaphore(max(1, self.max_inflight))
                inflight: queue.Queue = queue.Queue()

                def _drain_loop():
                    while True:
                        item = inflight.get()
                        if item is None:
                            return
                        try:
                            if not failed:
                                drain(item)
                        except BaseException as e:   # propagate to main loop
                            failed.append(e)
                        finally:
                            slots.release()

                it = iter(work_iter)
                t = threading.Thread(target=_drain_loop, daemon=True,
                                     name="xmc-checkpoint-writer")
                t.start()
                try:
                    while True:
                        slots.acquire()
                        if failed:
                            slots.release()
                            break
                        item = next(it, None)
                        if item is None:
                            slots.release()
                            break
                        inflight.put(dispatch(*item))
                finally:
                    inflight.put(None)
                    t.join()
                if failed:
                    raise failed[0]
        finally:
            if coordinate:
                hb_stop.set()
                hb_thread.join()
                # Exit (clean or not) releases whatever is still held, so
                # co-workers reclaim now instead of waiting out the TTL.
                with held_lock:
                    leftover = sorted(held)
                writer.release_leases(worker_id, leftover)

        if coordinate:
            # Cooperative completion is a property of the shared manifest,
            # not of this worker's batches: whoever drains the last batch
            # finalizes (try_finalize is idempotent under the lock).
            manifest = writer.try_finalize()
            complete = manifest is not None
            if materialize and complete:
                for b in range(len(batches)):     # co-workers' batches
                    if b not in host_blocks:
                        host_blocks[b] = writer.read_batch_dense(b)
        else:
            complete = len(solved) + len(skipped) == len(batches)
            manifest = writer.finalize() if (writer and complete) else None
        model = None
        if materialize and complete:
            W = np.concatenate([host_blocks[b] for b in range(len(batches))])
            model = DiSMECModel(W=jnp.asarray(W), delta=self.cfg.delta,
                                n_labels=L)
        return XMCTrainResult(model=model, out_dir=out_dir,
                              n_batches=len(batches), solved=solved,
                              skipped=skipped, complete=complete,
                              manifest=manifest, batch_stats=batch_stats)


def train_streaming(X: Array, Y: Array, cfg: DiSMECConfig, out_dir: str,
                    **job_kwargs) -> XMCTrainResult:
    """DEPRECATED shim: stream-train into a servable multi-shard checkpoint.

    Use the declarative session API instead::

        from repro.xmc_api import XMCSpec, fit
        handle = fit(X, Y, XMCSpec(...), out_dir)

    This shim drives the exact same engine (`XMCTrainJob.run`), so the
    checkpoints it writes are bit-identical to `fit()`'s for an equivalent
    spec (tested in tests/test_xmc_api.py).
    """
    import warnings
    warnings.warn(
        "train_streaming is deprecated; build an XMCSpec and call "
        "repro.xmc_api.fit(X, Y, spec, out_dir) instead",
        DeprecationWarning, stacklevel=2)
    run_kwargs = {k: job_kwargs.pop(k)
                  for k in ("resume", "materialize", "max_batches", "meta",
                            "on_batch", "init_from") if k in job_kwargs}
    return XMCTrainJob(cfg=cfg, **job_kwargs).run(X, Y, out_dir, **run_kwargs)


def train_demo_checkpoint(ckpt_dir: str, *, n_train: int = 800,
                          n_test: int = 512, n_features: int = 4096,
                          n_labels: int = 256, label_batch: int = 128,
                          block_shape: tuple[int, int] = (128, 128),
                          data_kwargs: dict | None = None,
                          C: float = 1.0, delta: float = 0.01,
                          seed: int = 0, reuse: bool = True,
                          verbose: bool = True):
    """Train-and-checkpoint a small DiSMEC model for demos/benchmarks.

    The one shared setup behind `launch/serve.py --xmc`,
    `examples/serve_xmc.py` and `benchmarks/serve_latency.py`: builds the
    synthetic dataset, streams a model into `ckpt_dir` through `XMCTrainJob`
    (unless a servable checkpoint is already there and `reuse`), and returns
    `(dataset, index)` where `index` is the checkpoint's pre-flight metadata
    (`checkpoint.io.load_block_sparse_meta`). `block_shape` sets the BSR
    tile — the shortlist serving benchmark passes a finer block height so
    the demo model has enough row blocks for a meaningful candidate stage.
    `data_kwargs` forwards extra knobs to `make_xmc_dataset` (e.g.
    pool_stride / label_locality for a cluster-ordered label space).
    """
    from repro.data.xmc import make_xmc_dataset       # deferred: keep light
    data = make_xmc_dataset(n_train=n_train, n_test=n_test,
                            n_features=n_features, n_labels=n_labels,
                            seed=seed, **(data_kwargs or {}))
    if not (reuse and has_block_sparse_checkpoint(ckpt_dir)):
        if verbose:
            print(f"[xmc] no servable checkpoint at {ckpt_dir}; streaming a "
                  f"{n_labels}-label model in batches of {label_batch}...")
        from repro.xmc_api import XMCSpec, fit            # deferred: no cycle
        spec = XMCSpec(solver=SolverSpec(C=C, delta=delta),
                       schedule=ScheduleSpec(label_batch=label_batch,
                                             block_shape=tuple(block_shape)))
        fit(jnp.asarray(data.X_train), jnp.asarray(data.Y_train), spec,
            ckpt_dir)
        if verbose:
            index = load_block_sparse_meta(ckpt_dir)
            print(f"[xmc] saved sparse checkpoint: {index['n_blocks']} "
                  "blocks across "
                  f"{len(index['manifest']['shards'])} shards")
    return data, load_block_sparse_meta(ckpt_dir)
