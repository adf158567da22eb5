"""Training launcher: LM train loop or streaming XMC pipeline.

LM mode:
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke \
      --steps 100 --seq-len 128 --batch 8
  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --smoke \
      --mesh 1x1 --head softmax

XMC mode (flags -> XMCSpec -> repro.xmc_api.fit: streaming label-batch
pipeline -> servable sparse checkpoint with the spec in its manifest;
re-running with the same --out resumes a killed job, --init-from warm
starts from a prior checkpoint's weights):
  PYTHONPATH=src python -m repro.launch.train --xmc --labels 512 \
      --label-batch 128 --out /tmp/xmc_ckpt
  PYTHONPATH=src python -m repro.launch.train --xmc --labels 512 \
      --delta 0.02 --out /tmp/xmc_d02 --init-from /tmp/xmc_ckpt
  PYTHONPATH=src python -m repro.launch.serve --xmc --ckpt /tmp/xmc_ckpt

Multi-host XMC (paper layer 1 over real nodes): launch the SAME command on
N hosts/processes sharing --out — each worker claims label batches through
the manifest's lease table and they drain one queue into one checkpoint
(bit-identical to a single-worker run; a worker killed mid-batch is
recovered by lease expiry):
  PYTHONPATH=src python -m repro.launch.train --xmc --labels 512 \
      --out /shared/xmc_ckpt --workers 2 --worker-id node0 &
  PYTHONPATH=src python -m repro.launch.train --xmc --labels 512 \
      --out /shared/xmc_ckpt --workers 2 --worker-id node1 &
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCH_IDS, get_config
from repro.compat import enable_compile_cache
from repro.data.lm import make_lm_batch_iterator
from repro.launch.mesh import make_host_mesh
from repro.models.model import build_model
from repro.models import sharding as shd
from repro.train.trainer import train_loop


def train_xmc(args) -> None:
    """--xmc: one declarative session — args become an XMCSpec, `fit()`
    streams the checkpoint, the handle quick-evals it."""
    from repro.core.prediction import evaluate, predict_topk
    from repro.data.xmc import make_xmc_dataset
    from repro.specs import ScheduleSpec, SolverSpec
    from repro.xmc_api import XMCSpec, fit

    if args.out is None:
        args.out = "/tmp/repro_xmc_train_ckpt"
    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = (d, m)

    data = make_xmc_dataset(n_train=args.train_n, n_test=args.test_n,
                            n_features=args.features, n_labels=args.labels,
                            seed=args.seed)
    # fit() normalizes the spec: a label batch that is not a multiple of the
    # BSR block height is rounded up with a warning (the old CLI shrank the
    # block to gcd(label_batch, 128) instead, which could degrade streamed
    # blocks all the way to 1-row tiles).
    spec = XMCSpec(
        solver=SolverSpec(C=args.C, delta=args.delta),
        schedule=ScheduleSpec(label_batch=args.label_batch, mesh=mesh,
                              shard_data=args.shard_data,
                              balance=args.balance, workers=args.workers,
                              lease_ttl=args.lease_ttl))

    t0 = time.time()
    handle = fit(jnp.asarray(data.X_train), jnp.asarray(data.Y_train),
                 spec, args.out, resume=not args.fresh,
                 init_from=args.init_from, worker=args.worker_id,
                 on_batch=lambda b, n: print(
                     f"[xmc] batch {b + 1}/{n} done "
                     f"({time.time() - t0:.1f}s)"))
    wall = time.time() - t0
    res = handle.result
    print(f"[xmc] {len(res.solved)} batches solved, {len(res.skipped)} "
          f"resumed from manifest in {wall:.1f}s -> {args.out}"
          + (f" (warm-started from {args.init_from})"
             if args.init_from else ""))

    if not res.complete:
        # Defensive: a normal run (cooperative or not) returns complete —
        # workers wait out co-worker leases. Reaching here means the run
        # was cut short; re-running the same command resumes it.
        print(f"[xmc] checkpoint not complete ({len(res.solved)} batches "
              f"by this worker); re-run this command to finish {args.out}")
        return

    nnz = sum(s["nnz"] for s in res.manifest["shards"].values())
    total = args.labels * args.features
    print(f"[xmc] model: {nnz} nonzeros / {total} "
          f"({100.0 * nnz / total:.2f}% dense)")

    # Quick-eval only at smoke scale: to_dense() would rebuild the full
    # (L, D) matrix the streaming pipeline just avoided materializing.
    if args.labels * args.features <= 50_000_000:
        model, _ = handle.model()
        W = model.to_dense()[:args.labels, :args.features]
        _, idx = predict_topk(jnp.asarray(data.X_test), W, 5)
        ev = evaluate(jnp.asarray(data.Y_test), idx)
        print(f"[xmc] test P@1={ev['P@1']:.3f} P@5={ev['P@5']:.3f}")
    else:
        print("[xmc] model too large for dense quick-eval; serve it with "
              "the bsr backend instead")
    print(f"[xmc] serve it: PYTHONPATH=src python -m repro.launch.serve "
          f"--xmc --ckpt {args.out} --features {args.features} "
          f"--labels {args.labels}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--xmc", action="store_true",
                    help="run the streaming XMC pipeline instead of LM train")
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS),
                    help="LM mode: architecture to train")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--head", choices=["dismec", "softmax"], default=None)
    ap.add_argument("--mesh", default=None, help="e.g. 2x4 (data x model)")
    ap.add_argument("--out", default=None, help="checkpoint directory")
    # XMC-mode knobs (streaming label-batch pipeline).
    ap.add_argument("--labels", type=int, default=512)
    ap.add_argument("--features", type=int, default=4096)
    ap.add_argument("--train-n", type=int, default=1000)
    ap.add_argument("--test-n", type=int, default=300)
    ap.add_argument("--label-batch", type=int, default=128)
    ap.add_argument("--C", type=float, default=1.0)
    ap.add_argument("--delta", type=float, default=0.01)
    ap.add_argument("--balance", action="store_true",
                    help="frequency-balanced label->shard dealing per batch")
    ap.add_argument("--shard-data", action="store_true",
                    help="also shard instances over the mesh data axis")
    ap.add_argument("--fresh", action="store_true",
                    help="ignore any existing manifest (no resume)")
    ap.add_argument("--init-from", default=None,
                    help="warm start: prior sparse checkpoint whose rows "
                         "seed each batch's TRON as W0")
    ap.add_argument("--workers", type=int, default=1,
                    help="cooperative worker count: >1 claims label batches "
                         "via the manifest lease table, so N processes "
                         "sharing --out drain one queue into one checkpoint")
    ap.add_argument("--worker-id", default=None,
                    help="stable identity of this worker in a multi-host "
                         "drain (default: hostname-pid); implies lease-"
                         "based claiming even with --workers 1")
    ap.add_argument("--lease-ttl", type=float, default=300.0,
                    help="seconds before an unrefreshed batch lease expires "
                         "and the batch is re-dealt (crash recovery)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.xmc:
        train_xmc(args)
        return
    if args.arch is None:
        ap.error("--arch is required in LM mode (or pass --xmc)")

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.head:
        cfg = dataclasses.replace(cfg, head_type=args.head)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    mesh = None
    batch_axes = ()
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_host_mesh(d, m)
        batch_axes = ("data",)

    def batches():
        it = make_lm_batch_iterator(cfg.vocab, args.seq_len, args.batch)
        for b in it:
            if cfg.n_prefix:
                b["prefix"] = jnp.ones(
                    (args.batch, cfg.n_prefix, cfg.d_model),
                    jnp.float32) * 0.01
            yield b

    t0 = time.time()
    params, hist = train_loop(model, params, batches(), steps=args.steps,
                              lr=args.lr, mesh=mesh, batch_axes=batch_axes)
    for h in hist:
        print(json.dumps(h))
    print(f"# trained {args.steps} steps in {time.time() - t0:.1f}s; "
          f"loss {hist[0]['loss']:.2f} -> {hist[-1]['loss']:.2f}")
    if args.out:
        from repro.checkpoint import save_pytree
        save_pytree(params, args.out)
        print(f"# checkpoint saved to {args.out}")


if __name__ == "__main__":
    main()
