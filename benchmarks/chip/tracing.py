"""Device traces: capture, and the reduction from trace to metrics.

The benchmark brackets its own calls with host spans
(`jax.profiler.TraceAnnotation`, names starting "bench.") and, in a
`--trace 1` run, records a profiler trace of part of its window. The
reduction below is the one every PR is measured with:

  busy      union of the intervals in which an operation ran on a device,
            clipped to the traced window, averaged over the devices used;
  idle      1 - busy / window;
  matching  the events whose name or HLO text matches a pattern (a
            kernel, or a jitted step's programs);
  top ops   device seconds per operation name;
  gaps      the longest idle stretches on device 0, each named by the
            benchmark span (or host thread) that was open at its middle.

A pattern that matches no event is an error the caller sees as None: the
metric is left out, never read as 0.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
from typing import Iterable, Optional

# Lines of a TPU device plane that hold one event per executed operation.
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    device: Optional[int]     # device index, None for a host event
    line: str
    text: str = ""            # HLO text / long name, when the trace has it

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    events: list              # list[Event]
    window: tuple             # (start_ns, end_ns) of the traced window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def device_ids(self) -> list:
        return sorted({e.device for e in self.events if e.device is not None})

    def ops(self, device=None) -> list:
        return [e for e in self.events if e.device is not None
                and e.line in OP_LINES
                and (device is None or e.device == device)]

    def modules(self) -> list:
        return [e for e in self.events if e.device is not None
                and e.line in MODULE_LINES]

    def host(self) -> list:
        return [e for e in self.events if e.device is None]


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile everything between enter and exit into log_dir: device ops
    and host spans, without the Python tracer (an event per Python call,
    millions over a serving window, which the reduction does not read)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def load(log_dir: str, window_span: str) -> Trace:
    """Read the newest xplane under log_dir. The window is the extent of
    the host span named `window_span`."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    events = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        dev = int(m.group(1)) if m else None
        if dev is None and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if dev is not None and line.name not in OP_LINES + MODULE_LINES:
                continue
            for ev in line.events:
                text = ""
                if dev is not None:
                    for key, val in ev.stats:
                        if key in ("long_name", "hlo_op", "tf_op"):
                            text += f" {val}"
                events.append(Event(ev.name, float(ev.start_ns),
                                    float(ev.duration_ns), dev,
                                    line.name if dev is not None
                                    else f"host:{line.name}", text))
    wins = [e for e in events if e.device is None and e.name == window_span]
    if not wins:
        raise ValueError(f"no host span {window_span!r} in the trace")
    w = max(wins, key=lambda e: e.dur_ns)
    return Trace(events=events, window=(w.start_ns, w.end_ns))


# -- reduction ---------------------------------------------------------------

def _merged(intervals: Iterable[tuple], lo: float, hi: float) -> list:
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(trace: Trace) -> Optional[float]:
    """Seconds in which some operation ran, averaged over the devices that
    ran any; None when no device ran anything in the window."""
    lo, hi = trace.window
    per = []
    for dev in trace.device_ids():
        merged = _merged(((e.start_ns, e.end_ns) for e in trace.ops(dev)),
                         lo, hi)
        if merged:
            per.append(sum(e - s for s, e in merged) / 1e9)
    return sum(per) / len(per) if per else None


def idle_share(trace: Trace) -> Optional[float]:
    b = busy_s(trace)
    return None if b is None else 1.0 - b / trace.window_s


def matching(events: Iterable[Event], pattern: str) -> list:
    """The events whose name and text, read together, match."""
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name + " " + e.text)]


def top_ops(trace: Trace, n: int = 10) -> list:
    """[name, device seconds] of the operations that took most time."""
    lo, hi = trace.window
    tot: dict = {}
    for e in trace.ops():
        d = min(e.end_ns, hi) - max(e.start_ns, lo)
        if d > 0:
            tot[e.name] = tot.get(e.name, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """[label, seconds] of the longest idle stretches of the first device,
    each labelled by the innermost benchmark span open at its middle (or
    the host thread busiest then, or "host")."""
    lo, hi = trace.window
    devs = trace.device_ids()
    if not devs:
        return []
    merged = _merged(((e.start_ns, e.end_ns) for e in trace.ops(devs[0])),
                     lo, hi)
    edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = trace.host()
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) / 2
        open_ = [h for h in host if h.start_ns <= mid <= h.end_ns]
        bench = [h for h in open_ if h.name.startswith("bench.")]
        pick = (min(bench, key=lambda h: h.dur_ns) if bench else
                min(open_, key=lambda h: h.dur_ns) if open_ else None)
        out.append([pick.name if pick else "host", (e - s) / 1e9])
    return out
