"""The server's own spans, read against the device trace.

`XMCServer` (`src/repro/serve/server.py`) opens `jax.profiler
.TraceAnnotation` spans named `xmc.*` on its worker threads. They land in
the profiler's host plane, on the clock of the device events, beside the
benchmark's own `bench.*` spans. Every host thread comes back under one
line name, so spans are found by name, never by thread. The reductions:

  idle parts   device 0's idle time in the traced window, split by the
               dispatcher's state then: waiting on an empty queue
               (`xmc.dispatch.idle`), holding queued requests for the
               batching deadline (`xmc.dispatch.hold`), or neither (the
               host path itself: forming, putting, launching, handing a
               batch off). The three parts sum to device 0's idle share.
  per batch    the summed durations of one batch's spans, taken in order
               (form, put, launch on the dispatcher; fetch, resolve on the
               completion thread), for the batches wholly in the window.

A trace with no `xmc.*` span (a program without them) reads None.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import tracing

PREFIX = "xmc."
IDLE = "xmc.dispatch.idle"
HOLD = "xmc.dispatch.hold"
DISPATCH = ("xmc.dispatch.form", "xmc.dispatch.put", "xmc.dispatch.launch")
COMPLETE = ("xmc.complete.fetch", "xmc.complete.resolve")


def spans(trace: Optional[tracing.Trace]) -> list:
    """The program's spans, by start; empty where there are none."""
    if trace is None:
        return []
    return sorted((e for e in trace.host() if e.name.startswith(PREFIX)),
                  key=lambda e: e.start_ns)


def _overlap_ns(a: list, b: list) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_parts(trace: Optional[tracing.Trace]) -> Optional[dict]:
    """{"host", "hold", "empty"}: the shares of the traced window in which
    device 0 runs no op while the dispatcher is in neither wait span, in
    `xmc.dispatch.hold`, and in `xmc.dispatch.idle`. None without program
    spans or without a device op in the window."""
    evs = spans(trace)
    devs = trace.device_ids() if evs else []
    if not devs:
        return None
    lo, hi = trace.window
    busy = tracing._merged(((e.start_ns, e.end_ns)
                            for e in trace.ops(devs[0])), lo, hi)
    if not busy:
        return None
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]

    def covered(*names):
        return _overlap_ns(idle, tracing._merged(
            ((e.start_ns, e.end_ns) for e in evs if e.name in names),
            lo, hi))

    idle_ns = sum(e - s for s, e in idle)
    hold, empty = covered(HOLD), covered(IDLE)
    win = hi - lo
    return {"host": (idle_ns - hold - empty) / win, "hold": hold / win,
            "empty": empty / win}


def per_batch_ms(trace: Optional[tracing.Trace], names: tuple) -> list:
    """Milliseconds of each batch's spans `names`, summed: one batch is
    the spans in that order, each following the last. Only batches whose
    spans all lie in the traced window count."""
    evs = [e for e in spans(trace) if e.name in names]
    if not evs:
        return []
    lo, hi = trace.window
    out, group = [], []
    for e in evs:
        if e.name == names[0]:
            group = [e]
        elif group and e.name == names[len(group)]:
            group.append(e)
        else:
            group = []
        if len(group) == len(names):
            if group[0].start_ns >= lo and group[-1].end_ns <= hi:
                out.append(sum(g.dur_ns for g in group) / 1e6)
            group = []
    return out


def p90_ms(trace: Optional[tracing.Trace], names: tuple) -> Optional[float]:
    """The 90th percentile of `per_batch_ms`; None where no batch is."""
    ms = per_batch_ms(trace, names)
    return float(np.percentile(ms, 90)) if ms else None
