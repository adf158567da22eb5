"""serve.dispatch_host_p90_ms: the 90th percentile, over the launches in
the traced window, of the dispatcher's host time per launch: its
`xmc.dispatch.form` (coalesce and pad), `xmc.dispatch.put` (host to
device) and `xmc.dispatch.launch` (enqueue the predict step) spans,
summed. Reads nothing where the trace holds no `xmc.*` span."""

import program_spans


def read(ctx):
    return program_spans.p90_ms(ctx.get("trace"), program_spans.DISPATCH)
