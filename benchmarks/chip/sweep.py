#!/usr/bin/env python3
"""Find a serving cell's knee: the highest offered rate it sustains.

    python3 benchmarks/chip/sweep.py --workload eurlex-4k.serve-bsr \
        --rates 1000,2000,4000 --seconds 8 --seed 3

One process: the cell's set-up once, then for each rate, lowest first, a
fresh server and an open-loop window of single-row requests. A rate is
sustained when every request due in the window is answered within a
second of the window closing, the median latency of the window's second
half is no more than twice that of its first half (the queue does not
grow), and the 95th percentile is no more than twice the median of the
lowest rate (the tail stays with the body). The sweep stops at the first
rate not sustained. The cell's traffic file then fixes its rate at about
four fifths of the knee.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import client  # noqa: E402
import gen  # noqa: E402
import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    from repro.compat import enable_compile_cache
    enable_compile_cache()
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    devices = harness.check_devices(jax.devices(), cell["chips"])
    cfg = harness.load_json(HERE, "configs", cell["config"] + ".json")
    tf = harness.load_json(HERE, "traffic", cell["traffic"] + ".json")
    driver = harness.load_module(os.path.join(HERE, "drivers",
                                              tf["kind"] + ".py"), "driver")
    ctx = harness.Context(workload=args.workload, cfg=cfg, traffic=tf,
                          seed=args.seed, seconds=args.seconds, trace=False,
                          devices=devices, peak={}, t_start=time.monotonic(),
                          work_dir="", compiles=harness.CompileCounter())
    engine, pool, _, _ = driver.build(ctx)
    rng = np.random.default_rng(args.seed)
    knee = base = None
    for rate in [float(r) for r in args.rates.split(",")]:
        srv = driver.new_server(engine, tf)
        gaps = gen.arrival_gaps(rate, args.seconds, rng)
        win = client.run_window(srv.submit, pool, gaps,
                                rng.integers(0, len(pool), len(gaps)),
                                grace_s=30.0)
        srv.stop()
        st = srv.stats()
        lat = win.latency_ms
        h = len(lat) // 2
        first, second = np.median(lat[:h]), np.median(lat[h:])
        late_done = np.nanmax(win.done) - win.due[-1]
        base = np.median(lat) if base is None else base
        ok = (np.isfinite(lat).all() and late_done <= 1.0
              and second <= 2.0 * first
              and np.percentile(lat, 95) <= 2.0 * base)
        knee = rate if ok else knee
        print(f"rate {rate:g} req/s: {len(lat)} requests, "
              f"{st['batches']} micro-batches, p50 {np.median(lat):.3f} "
              f"p95 {np.percentile(lat, 95):.3f} p99 "
              f"{np.percentile(lat, 99):.3f} ms; median first half "
              f"{first:.3f} second half {second:.3f} ms; last answer "
              f"{late_done:.3f}s after the last due time; generator p99 "
              f"late {np.percentile(win.lateness_ms, 99):.3f} ms; "
              f"{'sustained' if ok else 'NOT sustained'}", flush=True)
        if not ok:
            break
    print(f"knee: {knee} req/s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
