"""Benchmark harness entry point: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig5_delta_sweep
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI: pipeline benches
                                                     # on tiny shapes

Modules (deliverable d):
  table2_accuracy        Table 2 + Fig 3 (P@k / nDCG@k vs baselines)
  fig2_weight_hist       Fig 2 (weight distribution pre/post prune)
  fig4_l1_vs_l2          Fig 4 (l1 underfits vs l2+prune)
  fig5_delta_sweep       Fig 5 (Delta vs size vs accuracy)
  table3_scaling         SS4.3 (double-parallelization scaling)
  table_model_size       SS4.2 (model size accounting + paper-scale check)
  table_prediction_speed SS4.3 (prediction latency + BSR flops ratio)
  c_validation_sweep     SS3.3 (C tuned on validation) + shard balance
  train_pipeline         streaming label-batch training: throughput/mem/resume
                         (+ per-device peak-memory counters)
  tron_hotpath           CG matmul accounting + scheduler-overlap wall clock
  serve_latency          serving-engine p50/p99 per predict backend, the
                         shortlist-vs-exhaustive sub-linear gate (candidate
                         fraction < 25% at recall@5 >= 0.95), the
                         open-loop Poisson server benchmark (deadline beats
                         drain-on-full on p99; overload sheds with bounded
                         queue wait), and the zero-downtime refresh gate
                         (hot swap under load: zero drops, swap-window p99
                         <= 2x steady state), and the coarse-stage gates
                         (learned one-vs-rest coarse stage reaches the
                         recall gate at strictly fewer candidate blocks
                         than centroids; per-query ragged gather bit-exact
                         at full width; legacy/v1 artifact fallback) — all
                         live in --smoke, so tools/verify.sh gates them
  lifecycle_sweep        warm-start Delta sweep driver smoke: unchanged-spec
                         arm bit-identical to its warm-start source, model
                         size monotone in Delta, size-budget policy picks a
                         feasible arm — live in --smoke
  roofline               deliverable (g): 3-term roofline from the dry-run
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import sys
import time
import traceback

from repro.compat import enable_compile_cache

MODULES = [
    "table2_accuracy",
    "fig2_weight_hist",
    "fig4_l1_vs_l2",
    "fig5_delta_sweep",
    "table3_scaling",
    "table_model_size",
    "table_prediction_speed",
    "c_validation_sweep",
    "train_pipeline",
    "tron_hotpath",
    "serve_latency",
    "lifecycle_sweep",
    "roofline",
]

# --smoke: the pipeline benchmarks (train / hot path / serve) on tiny
# shapes — a CI gate (tools/verify.sh) that keeps every benchmark
# entrypoint importable and runnable without the full CPU cost.
SMOKE_MODULES = ["train_pipeline", "tron_hotpath", "serve_latency",
                 "lifecycle_sweep"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset")
    ap.add_argument("--smoke", action="store_true",
                    help=f"tiny-shape pass over {SMOKE_MODULES}")
    args = ap.parse_args()
    enable_compile_cache()
    mods = (args.only.split(",") if args.only
            else SMOKE_MODULES if args.smoke else MODULES)

    failures = []
    for name in mods:
        print(f"\n{'=' * 72}\n== benchmarks.{name}"
              f"{' (smoke)' if args.smoke else ''}\n{'=' * 72}")
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            if name == "roofline":
                sys.argv = ["roofline"]          # default args
            kwargs = {}
            if args.smoke:
                if "smoke" not in inspect.signature(mod.main).parameters:
                    raise TypeError(f"benchmarks.{name}.main has no smoke "
                                    "mode; drop it from SMOKE_MODULES or "
                                    "add the parameter")
                kwargs["smoke"] = True
            mod.main(**kwargs)
            print(f"\n[benchmarks.{name} done in {time.time() - t0:.1f}s]")
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        print(f"\nFAILED benchmarks: {failures}")
        sys.exit(1)
    print(f"\nAll {len(mods)} benchmarks completed.")


if __name__ == "__main__":
    main()
