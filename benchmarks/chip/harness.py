"""Runs one cell of the chip benchmark: the parts every cell shares.

A cell names a configuration and a traffic mix in `BENCHMARK.json`. Both
are data files found by name:

    configs/<config>.json      sizes, source, reduced, assumed
    traffic/<traffic>.json     the mix; its "kind" names the driver
    drivers/<kind>.py          drive(ctx) -> Outcome: set-up, window, check
    checks/<workload>.json     the numbers `correct` compares, each with its limit
    layers/<metric>.py         read(ctx) -> value or None, per-layer metric

The harness checks the device, turns on the compile cache, counts compiles
inside the window, hands the driver a context, picks the cell's metrics,
and prints the result: each compared number beside its limit as the last
lines of standard error, and one JSON object as the last line of standard
output.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Optional

import counts
import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Outcome:
    """What a driver hands back."""
    end_to_end: dict            # metric name -> value (the cell picks)
    attempted: int
    failed: int
    checks: dict                # number name -> value, judged by limits
    layer_ctx: dict = dataclasses.field(default_factory=dict)
    trace: Optional[tracing.Trace] = None
    memory_peak_bytes: int = 0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str) -> tuple[list, list]:
    """(end-to-end, per-layer) metric entries that this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def check_devices(devices, chips: int):
    """The devices the cell runs on; raises NoChip off a TPU or short of
    chips. Never falls back to the CPU."""
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "nothing"
        raise NoChip(f"this benchmark runs on a TPU; JAX found {found}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices[:chips]


class GcPauses:
    """Pauses of Python's cyclic collector of the oldest generation while
    the window is open: a stall of the whole process."""

    def __init__(self):
        self.pauses = []
        self._t = None

    def start(self):
        gc.callbacks.append(self._on)

    def stop(self):
        if self._on in gc.callbacks:
            gc.callbacks.remove(self._on)

    def _on(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append(time.perf_counter() - self._t)


class CompileCounter:
    """Counts executables obtained (compiled or read from the cache)
    between window_open and window_close."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.total = 0
        self.in_window = 0
        self.open = False
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.total += 1
            self.in_window += self.open


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell's data files and the run's knobs."""
    workload: str
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    peak: dict
    t_start: float
    work_dir: str
    compiles: CompileCounter
    gc_pauses: GcPauses = dataclasses.field(default_factory=GcPauses)
    t_window: Optional[float] = None

    def window_open(self, t: Optional[float] = None) -> None:
        self.t_window = time.monotonic() if t is None else t
        self.compiles.open = True
        self.gc_pauses.start()

    def window_close(self) -> None:
        self.compiles.open = False
        self.gc_pauses.stop()

    @property
    def setup_s(self) -> float:
        return self.t_window - self.t_start

    def memory_peak_bytes(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             devices, t_start: float, bench: Optional[dict] = None,
             cfg: Optional[dict] = None, traffic: Optional[dict] = None,
             limits: Optional[dict] = None,
             peak: Optional[dict] = None) -> dict:
    """Run the cell once and return the result object. The keyword
    overrides let a test run the real path at a size a CPU can hold."""
    bench = bench or load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    cell = cells[workload]
    cfg = cfg or load_json(HERE, "configs", cell["config"] + ".json")
    traffic = traffic or load_json(HERE, "traffic",
                                   cell["traffic"] + ".json")
    limits = limits or load_json(HERE, "checks", workload + ".json")
    peak = peak or counts.peaks(devices[0].device_kind)
    e2e_spec, layer_spec = cell_metrics(bench, workload)
    driver = load_module(os.path.join(HERE, "drivers",
                                      traffic["kind"] + ".py"),
                         "driver_" + traffic["kind"])
    work = tempfile.mkdtemp(prefix="chipbench-")
    try:
        ctx = Context(workload=workload, cfg=cfg, traffic=traffic,
                      seed=seed, seconds=seconds, trace=trace,
                      devices=devices, peak=peak, t_start=t_start,
                      work_dir=work, compiles=CompileCounter())
        out = driver.drive(ctx)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    log(f"compiles: {ctx.compiles.total} in all, "
        f"{ctx.compiles.in_window} inside the window")
    p = ctx.gc_pauses.pauses
    log(f"gc: {len(p)} full collections inside the window, longest "
        f"{1e3 * max(p, default=0.0):.1f} ms")

    metrics = {}
    if not trace:
        for m in e2e_spec:
            metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    else:
        rctx = dict(out.layer_ctx, trace=out.trace, peak=peak, cfg=cfg,
                    traffic=traffic, chips=len(devices))
        for m in layer_spec:
            reader = load_module(os.path.join(HERE, "layers",
                                              m["name"] + ".py"),
                                 "layer_" + m["name"].replace(".", "_"))
            value = reader.read(rctx)
            if value is None:
                log(f"per-layer {m['name']}: nothing to read")
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # The checks file names the numbers compared; the driver's other
    # readings are printed beside them and judge nothing.
    checks = {name: {"value": out.checks[name], "limit": limit}
              for name, limit in limits.items()}
    for name in sorted(set(out.checks) - set(limits)):
        log(f"reading {name}: {out.checks[name]!r} (not compared)")
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device}
    if trace and out.trace is not None:
        busy = tracing.busy_s(out.trace)
        if busy is not None:
            device["busy_s"] = busy
        device["window_s"] = out.trace.window_s
        result["breakdown"] = {"device_ops": tracing.top_ops(out.trace),
                               "idle_gaps": tracing.idle_gaps(out.trace)}
    result["checks"] = checks
    return result


def emit(result: dict) -> None:
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        log(f"check {name}: {c['value']!r} limit {c['limit']!r} {ok}")
    print(json.dumps(result), flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(
        description="Run one cell of the chip benchmark once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_start: float,
         devices_fn: Optional[Callable] = None) -> int:
    args = parse_args(argv)
    try:
        bench = load_json(ROOT, "BENCHMARK.json")
        chips = {w["name"]: w for w in bench["workloads"]}[args.workload][
            "chips"]
    except (OSError, KeyError) as e:
        log(f"chip benchmark: cannot find workload {args.workload!r}: {e}")
        return 2
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        log(f"chip benchmark: the program is not importable here: {e}")
        return 2
    import jax
    try:
        devices = check_devices((devices_fn or jax.devices)(), chips)
        counts.peaks(devices[0].device_kind)
    except (NoChip, KeyError) as e:
        log(f"chip benchmark: {e}")
        return 3
    from repro.compat import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), devices=devices, t_start=t_start,
                      bench=bench)
    emit(result)
    return 0
