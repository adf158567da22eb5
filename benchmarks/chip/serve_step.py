"""Roofline shares of the serving step, from the trace and the counts.

Each launch of the predict step scores one micro-batch padded to a bucket
of n rows. Its least time is the larger of `counts.bsr_flops` over the
chip's bf16 peak and `counts.bsr_min_bytes` over its HBM bandwidth. A
share is the least time summed over the launches in the traced window,
over the device time those launches took: the kernel's own events for
serve.bsr_roofline, the step's whole programs for serve.step_mfu.
"""

from __future__ import annotations

import re
from typing import Optional

import counts
import tracing

# The exhaustive fp32 BSR kernel (kernels/bsr_predict `_bsr_kernel`): the
# one Pallas call (a `tpu_custom_call`, op name `jit(_bsr_topk)/pallas_call`)
# inside the predict step. Other Pallas kernels (int8, gathered, shortlist)
# run inside other steps and do not match.
KERNEL = "kernel"
# The jitted predict step (`serve/xmc.py::_bsr_topk`), one program per
# bucket.
STEP = "step"
PATTERNS = {KERNEL: r"(?s)^(?=.*(?:tpu_custom_call|pallas_call))(?=.*_bsr_topk\b)",
            STEP: r"_bsr_topk\b"}

_SHAPE = re.compile(r"f32\[(\d+),(\d+)\]")


def rows_of(event: tracing.Event, g: dict) -> Optional[int]:
    """The micro-batch rows n of a launch, from an (n, Lp), (n, Dp) or
    (n, D) f32 shape in its HLO text; None where the text shows none."""
    for m in _SHAPE.finditer(event.name + " " + event.text):
        if int(m.group(2)) in (g["Lp"], g["Dp"], g["D"]):
            return int(m.group(1))
    return None


def _rows_of_program(trace: tracing.Trace, module: tracing.Event,
                     g: dict) -> Optional[int]:
    """The rows of a step program's launch, read from the ops it ran (a
    program's own event names no shapes)."""
    for e in trace.ops(module.device):
        if module.start_ns <= e.start_ns and e.end_ns <= module.end_ns:
            n = rows_of(e, g)
            if n is not None:
                return n
    return None


def roofline_share(ctx, which: str):
    trace = ctx.get("trace")
    g = ctx.get("geometry")
    if trace is None or g is None:
        return None
    lo, hi = trace.window
    events = (trace.ops() if which == KERNEL else trace.modules())
    hits = [e for e in tracing.matching(events, PATTERNS[which])
            if e.start_ns >= lo and e.end_ns <= hi]
    least, took = 0.0, 0.0
    for e in hits:
        n = (rows_of(e, g) if which == KERNEL
             else _rows_of_program(trace, e, g))
        if n is None:           # a launch the counts cannot size
            return None
        least += counts.least_time_s(
            counts.bsr_flops(g["n_blocks"], (g["bl"], g["bd"]), n),
            counts.bsr_min_bytes(g["n_blocks"], (g["bl"], g["bd"]),
                                 g["Lp"], g["Dp"], n),
            ctx["peak"])
        took += e.dur_ns / 1e9
    return least / took if took > 0 else None
