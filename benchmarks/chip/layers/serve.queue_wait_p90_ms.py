"""serve.queue_wait_p90_ms: the window's server, arrival to device
dispatch, 90th percentile (`XMCServer.stats()["queue_wait"]`)."""


def read(ctx):
    qw = (ctx.get("server_stats") or {}).get("queue_wait") or {}
    return qw.get("p90_ms")
