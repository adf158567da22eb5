"""Open-loop client: requests go out on a schedule fixed in advance.

Adapted from `benchmarks/serve_latency.py::run_open_loop`, with its clock
fixed: each request is timed from when it was DUE, not from when the
generator got round to submitting it, so a stall in the generator or the
server shows up in the latency of every request it delays. How late the
generator ran is reported beside it.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np


@dataclasses.dataclass
class Window:
    """What one open-loop window offered and got back."""
    due: np.ndarray          # (n,) due time, monotonic seconds
    sent: np.ndarray         # (n,) when submit() was called
    done: np.ndarray         # (n,) when the answer was seen (nan: never)
    rows: np.ndarray         # (n,) pool row of each request
    rejected: np.ndarray     # (n,) the answer was a rejection
    answers: dict            # request index -> answer, for the kept ones
    t0: float                # window start

    @property
    def latency_ms(self) -> np.ndarray:
        """Due time to answer, ms; inf for a request never answered."""
        lat = (self.done - self.due) * 1e3
        return np.where(np.isnan(lat), np.inf, lat)

    @property
    def lateness_ms(self) -> np.ndarray:
        return (self.sent - self.due) * 1e3


def run_window(submit, pool: np.ndarray, gaps: np.ndarray,
               rows: np.ndarray, *, keep=(), rejected_type=(), span=None,
               grace_s: float = 60.0, on_start=None) -> Window:
    """Submit pool[rows[i]] (one row each) at the due times the gaps give,
    from the calling thread, while a collector thread records when each
    answer resolves (answers resolve in submission order on the server, so
    waiting on them in that order sees each within a scheduler tick).
    Waits up to `grace_s` past the last due time for stragglers.

    Only the answers of the requests in `keep` are held; every other
    future is dropped once resolved, so the client leaves no per-request
    objects for Python's collector to sweep while the window runs."""
    n = len(gaps)
    keep = set(int(i) for i in keep)
    futures = [None] * n
    due = np.empty(n)
    sent = np.empty(n)
    done = np.full(n, np.nan)
    rejected = np.zeros(n, bool)
    answers = {}
    have = threading.Semaphore(0)
    deadline = [None]

    def collect():
        for i in range(n):
            have.acquire()
            fut = futures[i]
            while True:
                remaining = (None if deadline[0] is None
                             else deadline[0] - time.monotonic())
                if remaining is not None and remaining <= 0:
                    return
                try:
                    ans = fut.result(timeout=0.5 if remaining is None
                                     else min(0.5, remaining))
                except TimeoutError:
                    continue
                done[i] = time.monotonic()
                rejected[i] = isinstance(ans, rejected_type)
                if i in keep:
                    answers[i] = ans
                futures[i] = None
                break

    collector = threading.Thread(target=collect, name="bench-collector",
                                 daemon=True)
    collector.start()
    t0 = time.monotonic()
    if on_start is not None:
        on_start(t0)
    t_due = t0
    for i in range(n):
        t_due += gaps[i]
        due[i] = t_due
        wait = t_due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        x = pool[rows[i]:rows[i] + 1]
        sent[i] = time.monotonic()
        if span is not None:
            with span("bench.submit"):
                futures[i] = submit(x)
        else:
            futures[i] = submit(x)
        have.release()
    deadline[0] = due[-1] + grace_s
    collector.join(timeout=grace_s + 5.0)
    return Window(due=due, sent=sent, done=done, rows=np.asarray(rows),
                  rejected=rejected, answers=answers, t0=t0)


def percentile_ms(lat_ms: np.ndarray, q: float) -> float:
    """The q-th percentile (0-100) of all requests' latencies, the
    unanswered counted as infinitely late."""
    return float(np.percentile(lat_ms, q, method="higher"))
