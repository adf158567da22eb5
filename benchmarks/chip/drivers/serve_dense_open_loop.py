"""Driver of the dense-query serving cells: `serve_open_loop`'s open loop,
with dense query rows (`gen_dense`) and the streamed reference
(`reference_dense`).

The window, the client, the server and the check are
`serve_open_loop.drive` itself, run from a private copy of that module
whose `build` and `reference` are this module's: set-up makes the same
BSR weights on the device from the seed (`gen.make_serving_blocks`) and
warms every bucket the same way, but draws the request pool from
`gen_dense`; the check judges the sampled answers with
`reference_dense`, which never holds the (n, L) scores of a million
labels.
"""

from __future__ import annotations

import os
import time

import gen
import gen_dense
import reference_dense
import tracing
from harness import HERE, load_module, log

_base = load_module(os.path.join(HERE, "drivers", "serve_open_loop.py"),
                    "serve_open_loop_for_dense")


def build(ctx):
    """Set-up shared by a run and a sweep: weights on the device, the
    program's model, backend and engine with every bucket warmed, and the
    dense request pool. Returns (engine, pool, geometry, device arrays)."""
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
    pool = gen_dense.make_request_pool(cfg, tf["pool_rows"], ctx.seed)
    return engine, pool, g, arrays


_base.build = build
_base.reference = reference_dense
new_server = _base.new_server
drive = _base.drive
