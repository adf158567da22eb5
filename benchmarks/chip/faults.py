#!/usr/bin/env python3
"""The control and the faults, planted under the timed path, to show that
`correct` catches them.

    python3 benchmarks/chip/faults.py --workload eurlex-4k.serve-bsr \
        --fault control_int8 --seeds 7,8,9 --seconds 10

Each entry replaces the serving backend's `topk` for the length of a run,
through `patch(owner, name, value)`: the test suite passes pytest's
monkeypatch, the command line a plain setattr undone afterwards. The run
is the harness's own, comparison included. The benchmark's own runs never
plant one.

  control_int8         the control: the plain product in the program's
                       place, one precision below the configuration's
                       bfloat16 products: W rounded to int8 with one scale
                       per 128 x 128 block (max |w| / 127), x rounded to
                       bfloat16, float32 sums, then top-k
  control_fp8          the same with W rounded to float8 e4m3 (one scale per
                       block, max |w| / 448)
  program_int8         the program's own int8 path switched on
                       (`Int8Backend`: the same int8 blocks, dequantised in
                       the kernel); it compiles up to ~87,000 blocks
  answer_altered       a served label id changed where it is produced
  half_batch_left_out  half the rows of each micro-batch scored as zeros

Two entries plant no fault, to read what sound runs read on many seeds in
one process: `none` (the program as it is) and `reference_bf16` (the
plain product at the configuration's own precision, x and W rounded to
bfloat16, one pass, float32 sums, in the program's place).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import harness  # noqa: E402


# Per-block quantisers of the controls: the largest |w| of each block maps
# to the format's largest value. The rounded values are held exactly in
# bfloat16.
_LARGEST = {"int8": 127.0, "fp8": 448.0}


def _quantised(w, fmt):
    """Blocks (..., bl, bd) rounded to int8 or float8 e4m3 with one scale
    per block, as bfloat16, and the scales."""
    amax = jnp.max(jnp.abs(w), axis=(-2, -1), keepdims=True)
    scale = jnp.where(amax > 0, amax / _LARGEST[fmt], 1.0)
    v = w / scale
    v = jnp.round(v) if fmt == "int8" else v.astype(jnp.float8_e4m3fn)
    return v.astype(jnp.bfloat16), scale[..., 0, 0]


@functools.partial(jax.jit, static_argnames=("nrb", "k", "n_labels", "fmt"))
def _plain_topk(x, blocks, *, nrb, k, n_labels, fmt):
    """x (n, D) against the row-major blocks (nrb * ncb, bl, bd) of a model
    with every block present, one row block at a time, x rounded to
    bfloat16, float32 sums; W in bfloat16 (fmt "bf16") or rounded to int8
    or float8 e4m3 with a scale per block. Then the top k of the real
    labels."""
    nb, bl, bd = blocks.shape
    ncb = nb // nrb
    xp = jnp.zeros((x.shape[0], ncb * bd), jnp.float32)
    xh = xp.at[:, :x.shape[1]].set(x).reshape(-1, ncb, bd).astype(
        jnp.bfloat16)

    def row_block(w):                                   # (ncb, bl, bd)
        if fmt == "bf16":
            return jnp.einsum("ncd,cld->nl", xh, w.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        q, scale = _quantised(w, fmt)
        part = jnp.einsum("ncd,cld->ncl", xh, q,
                          preferred_element_type=jnp.float32)
        return jnp.einsum("ncl,c->nl", part, scale,
                          precision=jax.lax.Precision.HIGHEST)

    s = jax.lax.map(row_block, blocks.reshape(nrb, ncb, bl, bd))
    s = s.transpose(1, 0, 2).reshape(x.shape[0], nrb * bl)
    return jax.lax.top_k(s[:, :n_labels], k)


def _plant_plain(patch, fmt):
    from repro.serve import xmc

    def topk(self, x):
        m = self.model
        nrb = m.shape[0] // m.block_shape[0]
        if m.blocks.shape[0] != nrb * (m.shape[1] // m.block_shape[1]):
            raise ValueError("the control needs every block present")
        return _plain_topk(x, m.blocks, nrb=nrb, k=self.k,
                           n_labels=self.n_labels, fmt=fmt)
    patch(xmc.BsrBackend, "topk", topk)


def control_int8(patch):
    _plant_plain(patch, "int8")


def control_fp8(patch):
    _plant_plain(patch, "fp8")


def reference_bf16(patch):
    _plant_plain(patch, "bf16")


def none(patch):
    del patch


def program_int8(patch):
    from repro.serve import xmc
    made = {}

    def topk(self, x):
        if id(self) not in made:
            made[id(self)] = xmc.Int8Backend(self.model, self.k,
                                             n_labels=self.n_labels,
                                             interpret=self._interpret)
        return made[id(self)].topk(x)
    patch(xmc.BsrBackend, "topk", topk)


def answer_altered(patch):
    from repro.serve import xmc
    orig = xmc.BsrBackend.topk

    def topk(self, x):
        s, lab = orig(self, x)
        return s, lab.at[:, 0].set((lab[:, 0] + 1) % self.n_labels)
    patch(xmc.BsrBackend, "topk", topk)


def half_batch_left_out(patch):
    from repro.serve import xmc
    orig = xmc.BsrBackend.topk

    def topk(self, x):
        half = (x.shape[0] + 1) // 2 if x.shape[0] > 1 else 0
        return orig(self, x.at[half:].set(0.0))
    patch(xmc.BsrBackend, "topk", topk)


FAULTS = {f.__name__: f for f in (control_int8, control_fp8, program_int8,
                                  answer_altered, half_batch_left_out)}
# The controls fail on the precision of their scores.
CONTROLS = ("control_int8", "control_fp8", "program_int8")
WITNESSES = {f.__name__: f for f in (none, reference_bf16)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS) + sorted(WITNESSES))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from repro.compat import enable_compile_cache
    enable_compile_cache()
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    devices = harness.check_devices(jax.devices(), cell["chips"])
    undo = []

    def patch(owner, name, value):
        undo.append((owner, name, getattr(owner, name)))
        setattr(owner, name, value)

    for seed in (int(s) for s in args.seeds.split(",")):
        {**FAULTS, **WITNESSES}[args.fault](patch)
        try:
            res = harness.run_cell(args.workload, seed, args.seconds, False,
                                   devices=devices, t_start=time.monotonic(),
                                   bench=bench)
        finally:
            while undo:
                owner, name, value = undo.pop()
                setattr(owner, name, value)
        print(f"fault {args.fault} {args.workload} seed {seed}: "
              f"{json.dumps(res)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
