"""serve.weight_passes_per_launch: the passes the window's launches made
over the packed BSR blocks, per launch (`XMCServer.stats()`
`weight_passes` over `batches`). One where every launch reads the blocks
once, whatever its bucket. Reads nothing where the server does not count
them."""


def read(ctx):
    st = ctx.get("server_stats") or {}
    if "weight_passes" not in st or not st.get("batches"):
        return None
    return st["weight_passes"] / st["batches"]
