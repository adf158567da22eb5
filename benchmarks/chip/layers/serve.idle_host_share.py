"""serve.idle_host_share: percent of the traced window in which device 0
runs no op and the server's dispatcher is neither waiting on an empty
queue (`xmc.dispatch.idle`) nor holding queued requests for the batching
deadline (`xmc.dispatch.hold`): idle time the server's host path causes,
by its own work (form, put, launch) or by back-pressure (handoff).
Reads nothing where the trace holds no `xmc.*` span."""

import program_spans


def read(ctx):
    parts = program_spans.idle_parts(ctx.get("trace"))
    return None if parts is None else 100.0 * parts["host"]
