"""serve.slot_wait_share: the share of the window's launches that found
the device full, in percent: `XMCServer.stats()` `slot_waits` (batches
that were launchable while all `max_inflight` launches were still on the
device, so the dispatcher waited in `xmc.dispatch.slot` before forming
them) over `batches`, of the window's server. Near 100% where the device
sets the pace, near 0 where each launch ends before the next batch is due.
Reads nothing where the server does not count them."""


def read(ctx):
    st = ctx.get("server_stats") or {}
    if "slot_waits" not in st or not st.get("batches"):
        return None
    return 100.0 * st["slot_waits"] / st["batches"]
