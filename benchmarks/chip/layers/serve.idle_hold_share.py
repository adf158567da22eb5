"""serve.idle_hold_share: percent of the traced window in which device 0
runs no op while the server's dispatcher is in `xmc.dispatch.hold`:
requests are queued, and the batching policy (`serve/batching.py`,
launch on fill or deadline) holds them. Reads nothing where the trace
holds no `xmc.*` span."""

import program_spans


def read(ctx):
    parts = program_spans.idle_parts(ctx.get("trace"))
    return None if parts is None else 100.0 * parts["hold"]
