"""Driver of the serving cells: an open-loop client against `XMCServer`.

Set-up: the BSR weights are made on the device from the seed
(`gen.make_serving_blocks`), wrapped in the program's `BlockSparseModel`,
served through the registered backend the traffic names, and every bucket
of the engine is warmed; a short burst through a throwaway server warms
the request path. The window then offers single-row requests to a fresh
server at the traffic's fixed rate, on arrival times fixed by the seed,
and times each from its due time to its answer (`client`).

Check: after the window, with the program's state freed, a sample of the
window's requests drawn from the seed is scored by the reference from
weights it regenerates itself, and each served label and score is judged
(`reference.judge_answers`).
"""

from __future__ import annotations

import contextlib
import gc
import os
import threading
import time

import numpy as np

import client
import gen
import reference
import tracing
from harness import Outcome, log


def build(ctx):
    """Set-up shared by a run and a sweep: weights on the device, the
    program's model, backend and engine with every bucket warmed, and the
    request pool. Returns (engine, pool, geometry, device arrays)."""
    import jax
    from repro.core.pruning import BlockSparseModel
    from repro.serve.xmc import XMCEngine, make_backend

    cfg, tf = ctx.cfg, ctx.traffic
    g = gen.bsr_geometry(cfg)
    with tracing.span("bench.setup.weights"):
        arrays = gen.make_serving_blocks(cfg, ctx.seed)
        jax.block_until_ready(arrays)
    blocks, rows, cols, ptr = arrays
    model = BlockSparseModel(blocks=blocks, block_rows=rows, block_cols=cols,
                             row_ptr=ptr, shape=(g["Lp"], g["Dp"]),
                             block_shape=(g["bl"], g["bd"]),
                             orig_shape=(g["L"], g["D"]))
    backend = make_backend(tf["backend"], model, tf["k"], n_labels=g["L"])
    with tracing.span("bench.setup.warmup"):
        t = time.monotonic()
        engine = XMCEngine(backend, tuple(tf["buckets"]), warmup=True,
                           n_features=g["D"])
        log(f"setup: {len(tf['buckets'])} buckets warmed in "
            f"{time.monotonic() - t:.2f}s")
    pool = gen.make_request_pool(cfg, tf["pool_rows"], ctx.seed)
    return engine, pool, g, arrays


def new_server(engine, tf):
    from repro.serve.server import XMCServer
    return XMCServer(engine, max_batch_delay_ms=tf["max_batch_delay_ms"],
                     max_queue=tf.get("max_queue"))


def drive(ctx) -> Outcome:
    from repro.serve.server import Rejected

    cfg, tf = ctx.cfg, ctx.traffic
    k = tf["k"]
    engine, pool, g, arrays = build(ctx)
    rng = np.random.default_rng([ctx.seed, 3])
    rate = tf["rate_rps"]
    gaps = gen.arrival_gaps(rate, ctx.seconds, rng)
    req_rows = rng.integers(0, len(pool), size=len(gaps))

    with tracing.span("bench.setup.warm_requests"):
        warm = new_server(engine, tf)
        n_warm = int(rate * tf["warm_seconds"])
        client.run_window(warm.submit, pool,
                          gen.arrival_gaps(rate, tf["warm_seconds"], rng),
                          rng.integers(0, len(pool), size=n_warm))
        warm.stop()

    srv = new_server(engine, tf)
    trace_dir = os.path.join(ctx.work_dir, "trace")
    timers = []

    def schedule_trace(t0):
        # The profiler starts in set-up (starting it stalls the host for
        # up to seconds); the traced span is a stretch of steady load.
        a = tf["trace_from_s"]
        span = {}

        def begin():
            span["s"] = tracing.span("bench.window")
            span["s"].__enter__()

        timers.append(threading.Timer(max(0.0, t0 + a - time.monotonic()),
                                      begin))
        timers.append(threading.Timer(
            max(0.0, t0 + a + tf["trace_seconds"] - time.monotonic()),
            lambda: span["s"].__exit__(None, None, None)))
        for tm in timers:
            tm.start()

    def opened(t0):
        ctx.window_open(t0)
        if ctx.trace:
            schedule_trace(t0)

    # The answers compared with the reference: a sample of the window's
    # requests drawn from the seed before it opens.
    pick = np.sort(np.random.default_rng([ctx.seed, 5]).choice(
        len(gaps), size=min(tf["check_requests"], len(gaps)), replace=False))
    with (tracing.capture(trace_dir) if ctx.trace
          else contextlib.nullcontext()):
        win = client.run_window(srv.submit, pool, gaps, req_rows, keep=pick,
                                rejected_type=Rejected, span=tracing.span,
                                on_start=opened)
        ctx.window_close()
        for tm in timers:
            tm.join()
    srv.stop()
    stats = srv.stats()
    memory = ctx.memory_peak_bytes()

    lat = win.latency_ms
    rejected = int(win.rejected.sum())
    unanswered = int(np.isnan(win.done).sum())
    late = win.lateness_ms
    log(f"window: {len(lat)} requests at {rate} req/s over "
        f"{win.due[-1] - win.t0:.3f}s; {stats['batches']} micro-batches, "
        f"{unanswered} unanswered, {rejected} rejected")
    log(f"generator lateness ms: p50 {np.percentile(late, 50):.3f} p99 "
        f"{np.percentile(late, 99):.3f} max {late.max():.3f}")
    log(f"latency ms (from due time): p50 {np.percentile(lat, 50):.3f} "
        f"p95 {np.percentile(lat, 95):.3f} p99 {np.percentile(lat, 99):.3f} "
        f"max {lat.max():.3f}; server queue_wait {stats['queue_wait']}")

    # -- check against the reference, with the program's state freed -------
    # A sampled request never answered, or rejected, is already counted.
    pick = np.array([i for i in pick if i in win.answers
                     and not win.rejected[i]], int)
    labels = [win.answers[i].labels for i in pick]
    scores = [win.answers[i].scores for i in pick]
    x = pool[req_rows[pick]]
    del srv, warm, engine, arrays, win
    gc.collect()
    t = time.monotonic()
    if len(pick):
        ref = reference.serve_reference(cfg, ctx.seed, x)
        judged = reference.judge_answers(np.concatenate(labels),
                                         np.concatenate(scores), ref, k)
    else:
        judged = {"bad": 0, "score_err": float("inf"),
                  "score_err_mean": float("inf"), "rank_gap": float("inf")}
    log(f"check: {len(pick)} answers against the reference in "
        f"{time.monotonic() - t:.2f}s: {judged}")

    trace = tracing.load(trace_dir, "bench.window") if ctx.trace else None
    return Outcome(
        end_to_end={"serve_p50_ms": client.percentile_ms(lat, 50),
                    "serve_p95_ms": client.percentile_ms(lat, 95),
                    "setup_s": ctx.setup_s},
        attempted=len(lat), failed=unanswered + rejected,
        checks={"unanswered": unanswered + rejected,
                "bad_answers": judged["bad"],
                "score_err": judged["score_err"],
                "score_err_mean": judged["score_err_mean"],
                "rank_gap": judged["rank_gap"]},
        layer_ctx={"server_stats": stats, "geometry": g, "latency_ms": lat},
        trace=trace, memory_peak_bytes=memory)
