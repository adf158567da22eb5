import os
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes, print memory_analysis / cost_analysis, dump roofline inputs.

MUST be run as a module:  PYTHONPATH=src python -m repro.launch.dryrun \
    --arch chatglm3-6b --shape train_4k [--multi-pod] [--json out.json]

The XLA_FLAGS line above executes before ANY jax import (jax locks the device
count at first init); 512 placeholder host devices back the (2,16,16) mesh.
"""

import argparse
import json
import re
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES
from repro.configs.registry import ARCH_IDS, SKIPS, get_config
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.models import sharding as shd
from repro.models.model import build_model
from repro.optim import adamw_init
from repro.train.trainer import make_train_step

# TPU v5e hardware constants (roofline denominators).
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^\n(]*\(([^\n]*)\)")
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s32|u32|s8|u8|pred|s64|u64)"
                       r"\[([0-9,]*)\]")
_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
          "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8}


def collective_bytes(hlo_text: str) -> dict:
    """Per-device operand bytes of every collective in the optimized HLO."""
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    for m in _COLL_RE.finditer(hlo_text):
        kind, operands = m.group(1), m.group(2)
        total = 0
        for sm in _SHAPE_RE.finditer(operands):
            dt, dims = sm.group(1), sm.group(2)
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _BYTES[dt]
        out[kind] += total
    return out


def build_lowerable(arch: str, shape_name: str, mesh, *, smoke: bool = False):
    """Returns (fn, args) ready for jax.jit(fn).lower(*args)."""
    cfg = get_config(arch, smoke=smoke)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    ms = dict(zip(mesh.axis_names, mesh.devices.shape))
    baxes = shd.batch_axes(ms, cfg)
    use_swa = S.use_swa_for(cfg, shape_name)

    params_shape = S.abstract_params(model)
    params_sds = S.params_specs(cfg, params_shape, mesh)

    if shape.kind == "train":
        accum = S.TRAIN_ACCUM.get(arch, 1) if not smoke else 1
        batch_sds = S.train_batch_specs(cfg, shape, mesh, accum)
        opt_shape = jax.eval_shape(adamw_init, params_shape)
        opt_sds = jax.tree.map(
            lambda sds, ref: jax.ShapeDtypeStruct(
                sds.shape, sds.dtype, sharding=ref.sharding)
            if sds.shape else jax.ShapeDtypeStruct(sds.shape, sds.dtype),
            opt_shape,
            type(opt_shape)(step=opt_shape.step, mu=params_sds,
                            nu=params_sds),
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        from repro.optim.schedules import linear_warmup_cosine
        lr_fn = linear_warmup_cosine(3e-4, 100, 10000)
        step_fn = make_train_step(model, lr_fn=lr_fn, mesh=mesh,
                                  batch_axes=baxes, accum=accum)
        step_sds = jax.ShapeDtypeStruct((), jnp.int32)
        return step_fn, (params_sds, opt_sds, step_sds, batch_sds)

    if shape.kind == "prefill":
        batch_sds = S.serve_batch_specs(cfg, shape, mesh)

        def prefill_fn(params, batch):
            return model.prefill(params, batch, mesh=mesh, batch_axes=baxes,
                                 use_swa=use_swa)
        return prefill_fn, (params_sds, batch_sds)

    # decode: ONE token against a seq_len cache
    cache_sds = S.cache_specs(cfg, model, shape, mesh, use_swa)
    batch_sds = S.serve_batch_specs(cfg, shape, mesh)

    def decode_fn(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos, mesh=mesh,
                                 batch_axes=baxes, use_swa=use_swa)
    return decode_fn, (params_sds, cache_sds, batch_sds["tokens"],
                       batch_sds["pos"])


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            smoke: bool = False) -> dict:
    if (arch, shape_name) in SKIPS:
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": SKIPS[(arch, shape_name)]}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    fn, args = build_lowerable(arch, shape_name, mesh, smoke=smoke)
    with mesh:
        lowered = jax.jit(fn).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    coll = collective_bytes(hlo_text)
    from repro.launch import hlo_cost
    corrected = hlo_cost.summarize(hlo_text)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        # XLA HloCostAnalysis (counts while bodies ONCE — undercounts scans):
        "flops_per_device": float(cost.get("flops", 0.0)),
        "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes_per_device": coll,
        # Trip-count-corrected call-graph model (launch/hlo_cost.py):
        "flops_corrected": corrected["flops"],
        "hbm_bytes_corrected": corrected["hbm_bytes"],         # upper bound
        "hbm_bytes_fused": corrected["hbm_bytes_fused"],       # TPU fusion model
        "collective_bytes_corrected": corrected["collectives"],
        "collective_bytes_f32": corrected["collective_bytes_f32"],
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "peak_bytes": int(mem.argument_size_in_bytes +
                          mem.temp_size_in_bytes),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    }
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    choices=list(SHAPES) + ["all"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", default=None, help="append results to file")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    pods = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    failed = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                try:
                    rec = run_one(arch, shape, multi_pod=mp,
                                  smoke=args.smoke)
                except Exception as e:  # a dry-run failure IS a bug
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "error": f"{type(e).__name__}: {e}"}
                    failed += 1
                results.append(rec)
                print(json.dumps(rec), flush=True)
    if args.json:
        with open(args.json, "a") as f:
            for rec in results:
                f.write(json.dumps(rec) + "\n")
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
