"""serve.topk_share: the share of the predict step's device time spent
outside the BSR kernel: in the masks and `lax.top_k` over the scores
(`kernels/bsr_predict/ops.py` `bsr_predict_topk`, under the program's
`xmc.topk` scope), and the feature and column-id pads (microseconds).
The device time of the ops inside the `_bsr_topk` programs that are not
the kernel, over the device time of those programs, in the traced
window. The profiler's op events carry the HLO text and not the scope,
so the ops are told apart by that text. Reads nothing where no step
program or no kernel is found."""

import serve_step
import tracing


def read(ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    lo, hi = trace.window
    steps = sorted((m for m in tracing.matching(
        trace.modules(), serve_step.PATTERNS[serve_step.STEP])
        if m.start_ns >= lo and m.end_ns <= hi), key=lambda m: m.start_ns)
    kernel = tracing.matching(trace.ops(),
                              serve_step.PATTERNS[serve_step.KERNEL])
    if not steps or not kernel:
        return None
    kernel_ids = {id(e) for e in kernel}
    ops = sorted(trace.ops(), key=lambda e: e.start_ns)
    outside, j = 0.0, 0
    for m in steps:
        while j < len(ops) and ops[j].start_ns < m.start_ns:
            j += 1
        k = j
        while k < len(ops) and ops[k].start_ns < m.end_ns:
            e = ops[k]
            if (e.device == m.device and e.end_ns <= m.end_ns
                    and id(e) not in kernel_ids):
                outside += e.dur_ns
            k += 1
    step = sum(m.dur_ns for m in steps)
    return 100.0 * outside / step if step > 0 else None
