"""serve.device_idle_share: percent of the traced window in which no operation ran
on the device (1 - union of device op intervals / window, in %),
over a trace of a few seconds of steady load."""

import tracing


def read(ctx):
    trace = ctx.get("trace")
    share = None if trace is None else tracing.idle_share(trace)
    return None if share is None else 100.0 * share
