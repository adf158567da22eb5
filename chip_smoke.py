#!/usr/bin/env python3
"""DiSMEC's main path, end to end, on the TPU: the quickest proof that the
system still starts on the chip.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the label-sharded paths, 4 chips

One chip: generate a Wiki10-31K-shaped problem from `--seed`, train it with
`xmc_api.fit` into a streamed BSR checkpoint, reopen it with
`CheckpointHandle.open`, and serve a few dozen requests of 1-8 test rows
through `XMCServer` with the `bsr`, `int8` and `shortlist` backends. Every
answer is checked against a float32 numpy reference computed from the
checkpoint's dense W, and test P@1 against a floor.

Four chips (`--chips 4`): fit the same data on one device (at the default
and at the highest f32 matmul precision), with `ScheduleSpec(mesh=(1, 4))`,
and with `(2, 2)` plus `shard_data=True`, unpruned. Every fit must meet
TRON's stopping rule on the whole of the data, checked on the host in
float64, and every two fits must lie within the distance their gradients
allow. Labels are cut to one batch of 1,024 there, for run time. Then
serve the one-device checkpoint with the `sharded` backend over the four
chips and with `bsr` on one, each checked against the reference, and
require real memory use on all four devices.

The deployment: Wiki10-31K from the Extreme Classification Repository
(paper Table 1): 14,146 train and 6,616 test instances, D = 101,938
features. Labels are cut from 30,938 to 4,096 (4 label batches of 1,024)
for run time; every width is kept. Data is generated, never downloaded.

Any failed check raises. The last line of stdout is one JSON object,
`{"ok": true, "device": {...}}`, printed only when every check passed on a
TPU. Without a TPU the script exits with status 2 before doing any work.
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402

from repro.compat import enable_compile_cache, make_mesh  # noqa: E402
from repro.data.xmc import make_xmc_dataset  # noqa: E402
from repro.serve.batching import DEFAULT_BUCKETS  # noqa: E402
from repro.specs import ScheduleSpec, ServeSpec, SolverSpec  # noqa: E402
from repro.xmc_api import CheckpointHandle, XMCSpec, fit  # noqa: E402

K = 5
# Kernels and the TPU's default f32 matmul may round operands to bf16
# (8 significant bits) before an f32-accumulated dot, so a served score may
# differ from the float32 reference by up to 2**-8 * sum_j |x_j w_j|. The
# tolerance allows twice that, per (row, label).
SCORE_TOL_FRAC = 2.0 ** -7
# TRON stops on its own gradient, computed from those bf16-rounded
# operands: an error of about 2**-8 per product, far under eps * ||g(0)||
# when summed over the training rows. The float64 check allows twice eps.
STOP_SLACK = 2.0


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """Shapes of one smoke run; the default is the Wiki10-31K cut."""
    n_train: int = 14146
    n_test: int = 6616
    n_features: int = 101938
    n_labels: int = 4096            # cut from 30,938
    label_batch: int = 1024
    n_requests: int = 48
    max_rows: int = 8
    buckets: tuple[int, ...] = DEFAULT_BUCKETS
    p1_floor: float = 0.9


WIKI10_31K = SmokeConfig()
WIKI10_31K_4CHIP = dataclasses.replace(WIKI10_31K, n_labels=1024)


class CheckFailed(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))


# -- phases -----------------------------------------------------------------

def make_data(cfg: SmokeConfig, seed: int):
    t0 = time.time()
    data = make_xmc_dataset(n_train=cfg.n_train, n_test=cfg.n_test,
                            n_features=cfg.n_features, n_labels=cfg.n_labels,
                            seed=seed, name="wiki10-31k-cut")
    log(f"data: generated in {time.time() - t0:.2f}s "
        f"(X_train {data.X_train.nbytes / 1e9:.2f} GB)")
    return data


def train(data, spec: XMCSpec, out_dir: str, tag: str):
    """fit() into out_dir; prints wall and per-batch TRON counts."""
    t0 = time.time()
    handle = fit(data.X_train, data.Y_train, spec, out_dir)
    wall = time.time() - t0
    res = handle.result
    check(res.complete, f"{tag}: checkpoint incomplete")
    for st in res.batch_stats:
        log(f"{tag}: batch {st['batch']} labels={st['labels']} "
            f"wall_s={st['wall_s']:.3f} write_s={st['write_s']:.3f} "
            f"newton_max={st['newton_max']} "
            f"newton_mean={st['newton_mean']:.2f} cg_max={st['cg_max']} "
            f"cg_mean={st['cg_mean']:.2f}")
    handle = CheckpointHandle.open(out_dir)
    index = handle.index()
    n_grid = index["shape"][0] * index["shape"][1] // (128 * 128)
    log(f"{tag}: train wall {wall:.2f}s for {res.n_batches} batches; "
        f"{index['n_blocks']} of {n_grid} 128x128 blocks survive pruning")
    return handle


def make_requests(cfg: SmokeConfig, X_test: np.ndarray, seed: int):
    rng = np.random.default_rng(seed + 1)
    sizes = rng.integers(1, cfg.max_rows + 1, size=cfg.n_requests)
    rows = [rng.choice(len(X_test), size=int(n), replace=False)
            for n in sizes]
    return [np.ascontiguousarray(X_test[r]) for r in rows]


def serve(handle, serve_spec: ServeSpec, requests, tag: str, *, mesh=None):
    """Submit every request to an XMCServer and wait on every future.
    Returns (labels, scores) stacked over all request rows."""
    t0 = time.time()
    server = handle.server(serve_spec, mesh=mesh)
    setup = time.time() - t0
    try:
        futures = []
        for x in requests:
            futures.append(server.submit(x))
            time.sleep(0.002)
        results = [f.result(timeout=600.0) for f in futures]
        stats = server.stats()
    finally:
        server.stop()
    for r, x in zip(results, requests):
        check(hasattr(r, "labels") and r.labels.shape == (len(x), K),
              f"{tag}: request {getattr(r, 'request_id', '?')} "
              f"answered {r!r}")
    lat = stats["latency"]
    log(f"{tag}: setup (load + bucket compiles) {setup:.2f}s, "
        f"{stats['completed']} requests in {stats['batches']} batches, "
        f"p50 {lat['p50_ms']:.3f} ms p99 {lat['p99_ms']:.3f} ms")
    return (np.concatenate([r.labels for r in results]),
            np.concatenate([r.scores for r in results]), server.engine)


def dense_w(model) -> np.ndarray:
    L, D = model.orig_shape
    return np.asarray(model.to_dense())[:L, :D]


class Reference:
    """float32 numpy scores of the request rows against a dense W, with a
    per-(row, label) tolerance from sum_j |x_j w_j| (see SCORE_TOL_FRAC)."""

    def __init__(self, X: np.ndarray, W: np.ndarray):
        self.scores = X @ W.T
        self.tol = SCORE_TOL_FRAC * (np.abs(X) @ np.abs(W).T) + 1e-6
        self.top = np.argsort(-self.scores, axis=1, kind="stable")[:, :K]

    def _pairs(self, tag, i, ids, vals):
        L = self.scores.shape[1]
        check(len(set(ids.tolist())) == K and ids.min() >= 0
              and ids.max() < L, f"{tag}: row {i} ids {ids}")
        err = np.abs(vals - self.scores[i, ids])
        check(np.all(err <= self.tol[i, ids]),
              f"{tag}: row {i} score error {err.max():.3e} over tolerance "
              f"{self.tol[i, ids].min():.3e}")
        return float(err.max())

    def check_exact(self, tag, labels, scores) -> dict:
        """Top-k ids equal the reference's, except labels whose reference
        score is within tolerance of the reference's k-th score."""
        max_err, n_tie_swaps = 0.0, 0
        for i in range(len(labels)):
            ids, ref_top = labels[i], self.top[i]
            max_err = max(max_err, self._pairs(tag, i, ids, scores[i]))
            kth = ref_top[-1]
            for lab in set(ids.tolist()) ^ set(ref_top.tolist()):
                gap = abs(self.scores[i, lab] - self.scores[i, kth])
                check(gap <= self.tol[i, lab] + self.tol[i, kth],
                      f"{tag}: row {i} returned {sorted(ids.tolist())}, "
                      f"reference top-{K} {sorted(ref_top.tolist())}; label "
                      f"{lab} is {gap:.3e} from the k-th score")
                n_tie_swaps += 1
        log(f"{tag}: {len(labels)} rows match the reference "
            f"(max score error {max_err:.3e}, {n_tie_swaps} labels swapped "
            "at a k-th-score tie)")
        return {"max_err": max_err, "tie_swaps": n_tie_swaps}

    def check_candidates(self, tag, labels, scores, block_rows: int) -> dict:
        """Shortlist answers: every (label, score) pair is right, and no
        label of a row block the answer drew from beats its k-th score —
        the fine stage is exact over the blocks it scored. Recall@k
        against the exhaustive reference is reported."""
        L = self.scores.shape[1]
        max_err, hits = 0.0, 0
        for i in range(len(labels)):
            ids = labels[i]
            max_err = max(max_err, self._pairs(tag, i, ids, scores[i]))
            last = ids[-1]
            for blk in set((ids // block_rows).tolist()):
                cand = np.arange(blk * block_rows,
                                 min((blk + 1) * block_rows, L))
                beat = cand[self.scores[i, cand] > self.scores[i, last]
                            + self.tol[i, cand] + self.tol[i, last]]
                missed = set(beat.tolist()) - set(ids.tolist())
                check(not missed, f"{tag}: row {i} skipped labels {missed} "
                      f"of scored block {blk}")
            hits += len(set(ids.tolist()) & set(self.top[i].tolist()))
        recall = hits / (K * len(labels))
        log(f"{tag}: {len(labels)} rows exact over their scored blocks "
            f"(max score error {max_err:.3e}); recall@{K} vs exhaustive "
            f"{recall:.4f}")
        return {"max_err": max_err, "recall": recall}


def stopping_ratios(X_csr, Y: np.ndarray, W: np.ndarray, C: float):
    """Per label, ||g(w)|| and ||g(w)|| / ||g(0)|| for DiSMEC's objective
    f(w) = ||w||^2 + C sum_i max(0, 1 - s_i <w, x_i>)^2, s_i = 2y_i - 1.
    TRON stops once its own ||g|| <= eps ||g(0)||. Computed on the host in
    float64 over a sparse X, independently of the solver and the chip."""
    S = 2.0 * Y.T.astype(np.float64) - 1.0                  # (L, N)
    Wd = W.astype(np.float64)
    scores = (X_csr @ Wd.T).T                                # (L, N)
    resid = np.where(1.0 - S * scores > 0.0, scores - S, 0.0)
    g = 2.0 * Wd + 2.0 * C * (X_csr.T @ resid.T).T
    g0 = -2.0 * C * (X_csr.T @ S.T).T
    gnorm = np.linalg.norm(g, axis=1)
    return gnorm, gnorm / np.linalg.norm(g0, axis=1)


def check_solutions(data, Ws: dict, solver: SolverSpec, ref_tag: str):
    """Every W must meet TRON's stopping rule on the whole of the data, in
    float64: ||g(w)|| <= STOP_SLACK * eps * ||g(0)|| per label. A solve on
    part of the data, or no solve, fails it. Then, f being 2-strongly
    convex, ||w - w*|| <= ||g(w)|| / 2, so two solutions of one label lie
    within (||g_a|| + ||g_b||) / 2 of each other: every pair's distance is
    checked against that, from the measured gradients, and the bound is
    logged against the reference's ||w||."""
    import scipy.sparse

    X_csr = scipy.sparse.csr_matrix(data.X_train, dtype=np.float64)
    limit = STOP_SLACK * solver.eps
    gnorm, worst_ratio = {}, {}
    for tag, W in Ws.items():
        gnorm[tag], ratio = stopping_ratios(X_csr, data.Y_train, W,
                                            solver.C)
        worst = int(np.argmax(ratio))
        worst_ratio[tag] = (worst, float(ratio[worst]))
        log(f"{tag}: float64 ||g(w)|| / ||g(0)|| per label: max "
            f"{ratio[worst]:.5f} (label {worst}), p99 "
            f"{np.quantile(ratio, 0.99):.5f}, median "
            f"{np.median(ratio):.5f}; limit {limit:.5f} (eps {solver.eps})")
    w_norm = np.linalg.norm(Ws[ref_tag], axis=1)
    far = []
    for a, b in itertools.combinations(Ws, 2):
        dist = np.linalg.norm(Ws[a] - Ws[b], axis=1)
        bound = (gnorm[a] + gnorm[b]) / 2.0
        worst = int(np.argmax(dist / bound))
        log(f"{a} vs {b}: max |dW| {np.abs(Ws[a] - Ws[b]).max():.3e}; "
            f"||w_a - w_b|| / bound at most {dist[worst] / bound[worst]:.4f}"
            f" (label {worst}: {dist[worst]:.3e} vs {bound[worst]:.3e}); "
            f"bound / ||w_ref|| at most {np.max(bound / w_norm):.4f}")
        if dist[worst] > bound[worst]:
            far.append(f"{a} vs {b}: label {worst} solutions lie "
                       f"{dist[worst]:.3e} apart, over {bound[worst]:.3e}")
    for tag, (worst, r) in worst_ratio.items():
        check(r <= limit, f"{tag}: label {worst} misses the stopping rule: "
              f"||g|| / ||g(0)|| = {r:.5f}")
    check(not far, "; ".join(far))


def precision_at_1(engine, X_test, Y_test) -> float:
    res = engine.serve([X_test])[0]
    return float(np.mean(Y_test[np.arange(len(X_test)), res.labels[:, 0]]))


# -- the two runs -----------------------------------------------------------

def run_one_chip(cfg: SmokeConfig, seed: int, work: str) -> None:
    data = make_data(cfg, seed)
    spec = XMCSpec(solver=SolverSpec(),
                   schedule=ScheduleSpec(label_batch=cfg.label_batch,
                                         block_shape=(128, 128)))
    handle = train(data, spec, os.path.join(work, "ckpt"), "train")
    log(f"device peak after train: {peak_bytes(jax.devices()[0])} bytes")

    requests = make_requests(cfg, data.X_test, seed)
    Xr = np.concatenate(requests)
    model, _ = handle.model()
    ref = Reference(Xr, dense_w(model))
    from repro.checkpoint.io import load_block_sparse_int8
    ref_int8 = Reference(Xr, dense_w(
        load_block_sparse_int8(handle.directory, model=model)[0]
        .dequantize()))
    del model

    base = dict(k=K, buckets=cfg.buckets)
    labels, scores, engine = serve(handle, ServeSpec(backend="bsr", **base),
                                   requests, "serve bsr")
    ref.check_exact("serve bsr", labels, scores)
    p1 = precision_at_1(engine, data.X_test, data.Y_test)
    log(f"test P@1 {p1:.4f} over {len(data.X_test)} rows "
        f"(floor {cfg.p1_floor})")
    check(p1 >= cfg.p1_floor, f"test P@1 {p1:.4f} under {cfg.p1_floor}")
    del engine

    labels, scores, _ = serve(handle, ServeSpec(backend="int8", **base),
                              requests, "serve int8")
    ref_int8.check_exact("serve int8", labels, scores)

    labels, scores, _ = serve(handle,
                              ServeSpec(backend="shortlist", **base),
                              requests, "serve shortlist")
    ref.check_candidates("serve shortlist", labels, scores, block_rows=128)
    log(f"device peak: {peak_bytes(jax.devices()[0])} bytes")


def run_four_chips(cfg: SmokeConfig, seed: int, work: str) -> None:
    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found "
          f"{len(devices)}")
    data = make_data(cfg, seed)
    # delta = 0: each checkpoint holds TRON's own solution, unpruned, so
    # check_solutions tests the stopping rule on exactly what it returned.
    solver = SolverSpec(delta=0.0)
    schedule = ScheduleSpec(label_batch=cfg.label_batch,
                            block_shape=(128, 128))
    spec = XMCSpec(solver=solver, schedule=schedule)
    one = train(data, spec, os.path.join(work, "one"), "train 1 device")
    Ws = {"train 1 device": dense_w(one.model()[0])}
    # The same solve with every f32 matmul at full precision: how far the
    # default precision's rounding alone moves TRON's solution.
    with jax.default_matmul_precision("highest"):
        h = train(data, spec, os.path.join(work, "highest"),
                  "train 1 device highest")
    Ws["train 1 device highest"] = dense_w(h.model()[0])
    for tag, sch in (
            ("train mesh (1,4)", dataclasses.replace(schedule, mesh=(1, 4))),
            ("train mesh (2,2) shard_data",
             dataclasses.replace(schedule, mesh=(2, 2), shard_data=True))):
        h = train(data, XMCSpec(solver=solver, schedule=sch),
                  os.path.join(work, tag.split()[-1]), tag)
        Ws[tag] = dense_w(h.model()[0])
    # Every device held at least its half of X under (2,2) shard_data (all
    # of X under (1,4)): a device under that bound did not take part.
    peaks = [peak_bytes(d) for d in devices]
    floor = data.X_train.nbytes // 2
    log(f"per-device peak bytes after the sharded fits: {peaks} "
        f"(floor {floor})")
    check(min(peaks) >= floor, f"a device stayed under {floor} bytes peak")
    check_solutions(data, Ws, solver, "train 1 device")
    W_one = Ws.pop("train 1 device")
    del Ws, h

    requests = make_requests(cfg, data.X_test, seed)
    ref = Reference(np.concatenate(requests), W_one)
    base = dict(k=K, buckets=cfg.buckets)
    sh_labels, sh_scores, _ = serve(
        one, ServeSpec(backend="sharded", **base), requests,
        "serve sharded (1,4)", mesh=make_mesh((1, 4), ("data", "model")))
    ref.check_exact("serve sharded (1,4)", sh_labels, sh_scores)
    labels, scores, _ = serve(one, ServeSpec(backend="bsr", **base),
                              requests, "serve bsr")
    ref.check_exact("serve bsr", labels, scores)
    same = float(np.mean(np.sort(labels, 1) == np.sort(sh_labels, 1)))
    log(f"sharded vs bsr: {same:.4f} of top-{K} ids identical")
    log(f"per-device peak bytes: {[peak_bytes(d) for d in devices]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing to "
              "run", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    cfg = WIKI10_31K_4CHIP if args.chips == 4 else WIKI10_31K
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {cache}")
    log(f"deployment: Wiki10-31K shapes (N_train={cfg.n_train}, "
        f"N_test={cfg.n_test}, D={cfg.n_features}); labels cut 30938 -> "
        f"{cfg.n_labels} ({cfg.n_labels // cfg.label_batch} batch(es) of "
        f"{cfg.label_batch}); seed {args.seed}")
    t0 = time.time()
    with tempfile.TemporaryDirectory(dir=REPO, prefix=".chip_smoke_") as d:
        if args.chips == 4:
            run_four_chips(cfg, args.seed, d)
        else:
            run_one_chip(cfg, args.seed, d)
    log(f"total {time.time() - t0:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
