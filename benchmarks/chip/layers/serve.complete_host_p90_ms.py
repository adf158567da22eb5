"""serve.complete_host_p90_ms: the 90th percentile, over the batches in
the traced window, of the completion thread's host time per batch: its
`xmc.complete.fetch` (device to host) and `xmc.complete.resolve` (split,
assemble, record, resolve the futures) spans, summed. Reads nothing
where the trace holds no `xmc.*` span."""

import program_spans


def read(ctx):
    return program_spans.p90_ms(ctx.get("trace"), program_spans.COMPLETE)
