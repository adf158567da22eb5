"""serve.client_p95_ms: the 95th percentile of the traced run's request
latencies, from each request's due time to its answer, on the client.
End to end it is too bimodal on a one-chip host: a stall of the host of
a tenth of a second to seconds, in some runs and not others, leaves a
backlog that drains slowly and moves the tail of a whole run."""

import client


def read(ctx):
    lat = ctx.get("latency_ms")
    return None if lat is None or not len(lat) else \
        client.percentile_ms(lat, 95)
