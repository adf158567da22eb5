"""Streaming label-batch training pipeline: throughput, memory, resume.

Compares three ways of training the same DiSMEC model (train/xmc.py):

  one_shot — a single label batch covering all L labels: the whole (L, D)
             problem (and its TRON state) lives on device at once. This is
             what the paper says does NOT scale (870 GB dense).
  streamed — `XMCTrainJob` with label_batch << L: batches stream through one
             compiled solver, each pruned block is packed to BSR on the host
             and appended to the multi-shard checkpoint. Peak device memory
             is O(label_batch x D).
  resume   — kill the streamed job halfway (max_batches), then resume from
             the manifest; the overhead over an uninterrupted run is the
             price of crash tolerance.
  multiworker — the paper's layer 1 over real processes: N worker
             subprocesses each run `fit(..., worker=...)` against ONE
             shared out_dir and cooperatively drain the label-batch queue
             through the manifest lease table. Reports per-worker and
             cooperative batch throughput (the scaling is near-linear
             when workers have cores of their own; on one shared CPU the
             workers contend and the number says how much), and keeps the
             bit-identity gate live: the cooperative manifest and stitched
             weights must equal the single-worker streamed run's exactly.

Device memory is sampled between batches as the total bytes of live jax
arrays (plus the analytic TRON working set ~9 arrays of the solve shape,
which bounds the in-solve peak). Each record also carries the runtime
allocator's true per-device peaks (`device_peak_mb`, from
`device.memory_stats()["peak_bytes_in_use"]`) — on accelerators these see
the transient in-solve allocations live-array sampling cannot; on CPU the
allocator exposes no stats and the field is None per device. Emits one
BENCH_train.json line per mode.

Usage: PYTHONPATH=src python -m benchmarks.train_pipeline
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks._common import emit_json, print_table
from repro.checkpoint.io import BSR_MANIFEST, load_block_sparse
from repro.compat import refuse_shared_accelerator
from repro.core.dismec import DiSMECConfig
from repro.data.xmc import make_xmc_dataset
from repro.train.xmc import XMCTrainJob

OUT_JSON = "BENCH_train.json"
N_WORKERS = 2
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_TRAIN, N_FEATURES, N_LABELS = 500, 4096, 640
LABEL_BATCH = 128                      # L = 5 x label_batch
BLOCK = (128, 128)
# --smoke (tools/verify.sh / CI): same pipeline, tiny shapes — keeps the
# benchmark entrypoint exercised without the full CPU cost.
SMOKE_DIMS = dict(n_train=160, n_features=1024, n_labels=64,
                  label_batch=16, block=(16, 128))
# TRON working set per solve: W, f/g/gnorm/delta vectors, CG d/r/p/Hp and
# the W_try/g_try pair — ~9 (rows, D) arrays dominate.
TRON_ARRAYS = 9


def live_mb() -> float:
    return sum(b.nbytes for b in jax.live_arrays()) / 1e6


def device_peak_mb() -> list[dict]:
    """True per-device peak memory from the runtime allocator, one entry
    per jax device. `live_mb` sums the bytes of currently-live arrays —
    it cannot see transient allocations inside a jitted solve; the
    allocator's `peak_bytes_in_use` can. The peak is cumulative over the
    process (allocators don't rewind), so per-mode rows report the peak
    AS OF that mode's end. Backends without allocator stats (CPU) report
    `peak_mb: None` — the analytic `solve_working_set_mb` remains the
    bound there."""
    out = []
    for d in jax.devices():
        try:
            stats = d.memory_stats()
        except Exception:                 # backend without allocator stats
            stats = None
        peak = (stats or {}).get("peak_bytes_in_use")
        out.append({"device": str(d),
                    "peak_mb": None if peak is None else peak / 1e6})
    return out


def solve_peak_mb(rows: int, d: int) -> float:
    return TRON_ARRAYS * rows * d * 4 / 1e6


def run_job(job: XMCTrainJob, X, Y, out_dir, **kw):
    """Run one pipeline pass, sampling live device bytes and the completion
    timestamp after each batch."""
    samples, batch_ts = [], []

    def on_batch(b, n):
        samples.append(live_mb())
        batch_ts.append(time.time())

    t0 = time.time()
    res = job.run(X, Y, out_dir, on_batch=on_batch, **kw)
    wall = time.time() - t0
    peak = max(samples) if samples else live_mb()
    return res, wall, peak, batch_ts


def steady_labels_per_s(batch_ts: list[float], label_batch: int) -> float:
    """Post-warmup batch throughput: batches completed per second after the
    first completion (the first batch carries the solver compile)."""
    if len(batch_ts) < 2 or batch_ts[-1] <= batch_ts[0]:
        return float("inf")
    return (len(batch_ts) - 1) * label_batch / (batch_ts[-1] - batch_ts[0])


def main(smoke: bool = False):
    if smoke:
        n_train, n_features, n_labels = (SMOKE_DIMS["n_train"],
                                         SMOKE_DIMS["n_features"],
                                         SMOKE_DIMS["n_labels"])
        label_batch, block = SMOKE_DIMS["label_batch"], SMOKE_DIMS["block"]
    else:
        n_train, n_features, n_labels = N_TRAIN, N_FEATURES, N_LABELS
        label_batch, block = LABEL_BATCH, BLOCK
    data = make_xmc_dataset(n_train=n_train, n_test=64,
                            n_features=n_features, n_labels=n_labels, seed=0)
    X = jnp.asarray(data.X_train)
    Y = jnp.asarray(data.Y_train)
    base_mb = live_mb()                # X/Y and friends, common to all modes

    rows_out = []

    def record(mode, wall, peak_sampled, rows_solve, n_batches, extra=None,
               labels_solved=None):
        if labels_solved is None:
            labels_solved = n_labels
        rec = {"bench": "train_pipeline", "mode": mode, "smoke": smoke,
               "n_labels": n_labels, "n_features": n_features,
               "label_batch": rows_solve, "n_batches": n_batches,
               "wall_s": wall,
               "labels_per_s": labels_solved / wall,
               "peak_live_mb": peak_sampled,
               "solve_working_set_mb": solve_peak_mb(rows_solve, n_features),
               "baseline_live_mb": base_mb,
               "device_peak_mb": device_peak_mb()}
        rec.update(extra or {})
        emit_json(OUT_JSON, rec)
        rows_out.append({"mode": mode, "wall_s": wall,
                         "peak_live_mb": peak_sampled,
                         "solve_mb": rec["solve_working_set_mb"],
                         "labels/s": rec["labels_per_s"]})
        return rec

    cfg_stream = DiSMECConfig(delta=0.01, label_batch=label_batch)
    cfg_oneshot = DiSMECConfig(delta=0.01, label_batch=n_labels)

    # one_shot: all L labels in a single device solve (the non-scaling path).
    with tempfile.TemporaryDirectory() as d:
        res, wall, peak, _ = run_job(
            XMCTrainJob(cfg=cfg_oneshot, block_shape=block), X, Y, d)
        assert res.complete
        record("one_shot", wall, peak, n_labels, res.n_batches)

    # streamed: label batches through one compiled solver, BSR appended.
    with tempfile.TemporaryDirectory() as d:
        res, wall_streamed, peak_streamed, ts_streamed = run_job(
            XMCTrainJob(cfg=cfg_stream, block_shape=block), X, Y, d)
        assert res.complete and res.n_batches == n_labels // label_batch
        nnz = sum(s["nnz"] for s in res.manifest["shards"].values())
        record("streamed", wall_streamed, peak_streamed, label_batch,
               res.n_batches,
               {"model_nnz": nnz,
                "steady_labels_per_s": steady_labels_per_s(ts_streamed,
                                                           label_batch)})
        # Reference for the multiworker bit-identity gate below.
        with open(os.path.join(d, BSR_MANIFEST)) as f:
            manifest_single = json.load(f)
        W_single = np.asarray(load_block_sparse(d)[0].to_dense())

    # multiworker: N subprocesses cooperatively drain one shared out_dir
    # through the manifest lease table (layer 1 over real processes). The
    # reference is a SOLO subprocess measured the same way (its own
    # interpreter + compile inside its fit window), and co-workers
    # synchronize on a start barrier so their windows are concurrent —
    # scaling = solo window / cooperative window. On a box where each
    # worker gets its own cores this approaches the worker count as the
    # batch count grows; with all workers packed on one small CPU the
    # number reports the contention honestly.
    refuse_shared_accelerator(N_WORKERS, "the multiworker mode")
    with tempfile.TemporaryDirectory() as d:
        env = {**os.environ,
               "PYTHONPATH": "src" + (os.pathsep + os.environ["PYTHONPATH"]
                                      if os.environ.get("PYTHONPATH") else "")}

        def launch(worker_id, out_dir, workers, barrier=None):
            cmd = [sys.executable, "-m", "benchmarks.train_pipeline",
                   "--drain-worker", out_dir, "--workers", str(workers),
                   "--worker-id", worker_id]
            if barrier:
                cmd += ["--barrier", barrier]
            if smoke:
                cmd.append("--smoke")
            return subprocess.Popen(cmd, cwd=REPO_ROOT, env=env,
                                    stdout=subprocess.PIPE, text=True)

        def wait(proc):
            out, _ = proc.communicate()
            assert proc.returncode == 0, f"worker failed:\n{out}"
            return json.loads(out.strip().splitlines()[-1])

        solo = wait(launch("solo", os.path.join(d, "solo"), 1))
        solo_wall = solo["t_fit_end"] - solo["t_fit_start"]

        coop_dir = os.path.join(d, "coop")
        t0 = time.time()
        procs = [launch(f"w{i}", coop_dir, N_WORKERS,
                        barrier=os.path.join(d, "barrier"))
                 for i in range(N_WORKERS)]
        reports = [wait(p) for p in procs]
        wall_spawn = time.time() - t0
        coop_wall = (max(r["t_fit_end"] for r in reports)
                     - min(r["t_fit_start"] for r in reports))
        assert any(r["complete"] for r in reports)
        assert sum(r["n_solved"] for r in reports) == n_labels // label_batch
        with open(os.path.join(coop_dir, BSR_MANIFEST)) as f:
            manifest_coop = json.load(f)
        assert manifest_coop == manifest_single          # bit-identity gate
        np.testing.assert_array_equal(
            np.asarray(load_block_sparse(coop_dir)[0].to_dense()), W_single)
        # Peak device memory lives in the worker subprocesses (each is the
        # streamed profile), not in this parent: report None.
        record("multiworker", coop_wall, None, label_batch,
               n_labels // label_batch,
               {"workers": N_WORKERS,
                "batches_per_worker": [r["n_solved"] for r in reports],
                "wall_s_incl_spawn": wall_spawn,
                "fit_window_s_solo": solo_wall,
                "fit_window_scaling": solo_wall / coop_wall,
                "manifest_identical": True})
        print(f"multiworker: {N_WORKERS} workers drained "
              f"{n_labels // label_batch} batches in {coop_wall:.1f}s vs "
              f"{solo_wall:.1f}s solo ({solo_wall / coop_wall:.2f}x; "
              f"batches/worker {[r['n_solved'] for r in reports]})")

    # resume: kill halfway, restart from the manifest.
    with tempfile.TemporaryDirectory() as d:
        job = XMCTrainJob(cfg=cfg_stream, block_shape=block)
        half = (n_labels // label_batch) // 2
        res1, wall_partial, _, _ = run_job(job, X, Y, d, max_batches=half)
        assert not res1.complete
        res2, wall_resume, peak, _ = run_job(job, X, Y, d)
        assert res2.complete and len(res2.skipped) == half
        overhead = wall_partial + wall_resume - wall_streamed
        record("resume", wall_resume, peak, label_batch, res2.n_batches,
               {"resumed_batches": len(res2.skipped),
                "resume_overhead_s": overhead,
                "resume_overhead_frac": overhead / wall_streamed},
               # The resume leg only re-solved the non-skipped batches.
               labels_solved=len(res2.solved) * label_batch)

    print_table(
        f"streaming train pipeline (L={n_labels}, D={n_features}, "
        f"label_batch={label_batch})",
        rows_out, ["mode", "wall_s", "peak_live_mb", "solve_mb", "labels/s"])

    one_shot_mb = solve_peak_mb(n_labels, n_features)
    streamed_mb = solve_peak_mb(label_batch, n_features)
    print(f"\nsolver working set: one_shot {one_shot_mb:.0f} MB vs streamed "
          f"{streamed_mb:.0f} MB ({one_shot_mb / streamed_mb:.1f}x — scales "
          "with label_batch, not L)")
    print(f"wrote {OUT_JSON}")


def drain_worker(out_dir: str, worker_id: str, workers: int, smoke: bool,
                 barrier: str | None = None) -> None:
    """Subprocess entry for the multiworker mode: one cooperative worker.

    Builds the SAME dataset and canonical spec as the in-process modes (so
    the manifest fingerprint admits it and bit-identity vs `streamed`
    holds) and emits one JSON report line on stdout for the parent.
    `barrier` is a path prefix co-workers rendezvous on right before
    `fit`, so their measured fit windows are concurrent rather than
    staggered by process startup.
    """
    import glob

    from repro.specs import ScheduleSpec, SolverSpec
    from repro.xmc_api import XMCSpec, fit

    if smoke:
        n_train, n_features, n_labels = (SMOKE_DIMS["n_train"],
                                         SMOKE_DIMS["n_features"],
                                         SMOKE_DIMS["n_labels"])
        label_batch, block = SMOKE_DIMS["label_batch"], SMOKE_DIMS["block"]
    else:
        n_train, n_features, n_labels = N_TRAIN, N_FEATURES, N_LABELS
        label_batch, block = LABEL_BATCH, BLOCK
    data = make_xmc_dataset(n_train=n_train, n_test=64,
                            n_features=n_features, n_labels=n_labels, seed=0)
    X = jnp.asarray(data.X_train)
    Y = jnp.asarray(data.Y_train)
    spec = XMCSpec(solver=SolverSpec(delta=0.01),
                   schedule=ScheduleSpec(label_batch=label_batch,
                                         block_shape=block, workers=workers,
                                         lease_ttl=60.0))
    if barrier is not None:
        open(f"{barrier}.{worker_id}", "w").close()
        deadline = time.time() + 300.0
        while len(glob.glob(f"{barrier}.*")) < workers:
            if time.time() > deadline:
                raise RuntimeError("start-barrier timeout")
            time.sleep(0.02)
    t_start = time.time()
    handle = fit(X, Y, spec, out_dir, worker=worker_id)
    res = handle.result
    print(json.dumps({"worker": worker_id, "n_solved": len(res.solved),
                      "complete": res.complete, "t_fit_start": t_start,
                      "t_fit_end": time.time()}))


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--drain-worker", default=None, metavar="OUT_DIR",
                    help="internal: run as one cooperative worker draining "
                         "OUT_DIR (used by the multiworker mode)")
    ap.add_argument("--worker-id", default=None)
    ap.add_argument("--workers", type=int, default=N_WORKERS)
    ap.add_argument("--barrier", default=None,
                    help="internal: path prefix for the co-worker start "
                         "rendezvous")
    args = ap.parse_args()
    if args.drain_worker:
        drain_worker(args.drain_worker, args.worker_id or "w0",
                     args.workers, args.smoke, barrier=args.barrier)
    else:
        main(smoke=args.smoke)
