"""Distributed training example: the paper's double parallelization, for
real this time.

Layer 1 (paper: label batches -> nodes)  = N independent worker PROCESSES
    cooperatively draining one label-batch queue through the checkpoint
    manifest's lease table. Each worker runs the same `fit(X, Y, spec,
    out_dir, worker=...)`; batches are claimed atomically, a worker killed
    mid-batch is recovered by lease expiry, and the finished checkpoint is
    bit-identical to a single-worker run. On a cluster you'd launch the
    same thing with plain process spawning on each node
    (`python -m repro.launch.train --xmc --worker-id $HOSTNAME ...`)
    against a shared filesystem — nothing here is multiprocessing-specific.

Layer 2 (paper: one label per core)      = the batched TRON solve inside
    each worker; add `ScheduleSpec(mesh=(d, m))` to also shard every
    batch's solve over an in-process device mesh (see docs/architecture.md
    — the two layers compose).

Run: PYTHONPATH=src python examples/distributed_dismec.py
"""

import json
import multiprocessing as mp
import os
import tempfile
import time

N_WORKERS = 2
DATA = dict(n_train=512, n_test=128, n_features=2048, n_labels=192, seed=0)
LABEL_BATCH = 32                       # 6 batches -> a real queue to deal
BLOCK = (32, 128)


def build_spec():
    from repro.specs import ScheduleSpec, SolverSpec
    from repro.xmc_api import XMCSpec

    # Every worker must build the SAME canonical spec — the manifest
    # fingerprint rejects a joiner whose spec (or data) disagrees.
    return XMCSpec(
        solver=SolverSpec(C=1.0, delta=0.01, eps=1e-2),
        schedule=ScheduleSpec(label_batch=LABEL_BATCH, block_shape=BLOCK,
                              workers=N_WORKERS, lease_ttl=60.0))


def worker_main(worker_id: str, out_dir: str, queue) -> None:
    """One layer-1 node: same data, same spec, shared out_dir."""
    import jax.numpy as jnp

    from repro.data.xmc import make_xmc_dataset
    from repro.xmc_api import fit

    data = make_xmc_dataset(**DATA)              # deterministic per seed
    t0 = time.time()
    handle = fit(jnp.asarray(data.X_train), jnp.asarray(data.Y_train),
                 build_spec(), out_dir, worker=worker_id)
    res = handle.result
    queue.put({"worker": worker_id, "solved": res.solved,
               "complete": res.complete, "wall_s": time.time() - t0})


def main():
    import numpy as np
    import jax.numpy as jnp

    from repro.checkpoint.io import BSR_MANIFEST, load_block_sparse
    from repro.compat import refuse_shared_accelerator
    from repro.core.prediction import evaluate
    from repro.data.xmc import make_xmc_dataset
    from repro.xmc_api import CheckpointHandle, fit

    refuse_shared_accelerator(N_WORKERS, "this example")
    ctx = mp.get_context("spawn")                # fresh jax per worker
    with tempfile.TemporaryDirectory() as root:
        coop = os.path.join(root, "coop")

        print(f"layer 1: {N_WORKERS} worker processes draining "
              f"{DATA['n_labels'] // LABEL_BATCH} label batches -> {coop}")
        q = ctx.Queue()
        procs = [ctx.Process(target=worker_main, args=(f"node{i}", coop, q))
                 for i in range(N_WORKERS)]
        t0 = time.time()
        for p in procs:
            p.start()
        # Collect with a timeout + liveness check: a worker that dies
        # before reporting must fail the demo, not hang it on q.get() —
        # and on failure the survivors are terminated first, so the demo
        # exits promptly instead of blocking on multiprocessing's atexit
        # join while tempdir cleanup races their in-flight writes.
        import queue as queue_mod
        reports, deadline = [], time.time() + 600.0
        try:
            while len(reports) < len(procs):
                try:
                    reports.append(q.get(timeout=5.0))
                except queue_mod.Empty:
                    dead = [p for p in procs
                            if not p.is_alive()
                            and p.exitcode not in (0, None)]
                    if dead:
                        raise RuntimeError(
                            f"worker(s) died with exit codes "
                            f"{[p.exitcode for p in dead]}")
                    if time.time() > deadline:
                        raise RuntimeError("timed out waiting for workers")
        except BaseException:
            for p in procs:
                if p.is_alive():
                    p.terminate()
            for p in procs:
                p.join()
            raise
        for p in procs:
            p.join()
        wall = time.time() - t0
        for r in sorted(reports, key=lambda r: r["worker"]):
            print(f"  {r['worker']}: solved batches {r['solved']} "
                  f"in {r['wall_s']:.1f}s (complete={r['complete']})")
        assert any(r["complete"] for r in reports)

        # The cooperative checkpoint must be bit-identical to one worker
        # doing everything alone.
        data = make_xmc_dataset(**DATA)
        single = os.path.join(root, "single")
        fit(jnp.asarray(data.X_train), jnp.asarray(data.Y_train),
            build_spec(), single)
        with open(os.path.join(coop, BSR_MANIFEST)) as f:
            m_coop = json.load(f)
        with open(os.path.join(single, BSR_MANIFEST)) as f:
            m_single = json.load(f)
        assert m_coop == m_single
        np.testing.assert_array_equal(
            np.asarray(load_block_sparse(coop)[0].to_dense()),
            np.asarray(load_block_sparse(single)[0].to_dense()))
        print(f"cooperative checkpoint bit-identical to single-worker run "
              f"({wall:.1f}s wall incl. process spawn)")

        # Serve the cooperative checkpoint: the manifest alone carries the
        # spec, so any process can re-open and serve it.
        engine = CheckpointHandle.open(coop).engine()
        results = engine.serve([np.asarray(data.X_test, np.float32)])
        print("served metrics:", evaluate(jnp.asarray(data.Y_test),
                                          jnp.asarray(results[0].labels)))


if __name__ == "__main__":
    main()
