"""serve.topk_grouped_share: the share of the window's launches whose
top-k took the grouped form (`kernels/bsr_predict/ops.py`
`grouped_top_k`: one max pass over groups of labels, then a top-k over
the group maxima and over the chosen groups' scores), in percent:
`XMCServer.stats()` `topk_grouped` over `batches`, of the window's
server. Reads nothing where the server does not count them."""


def read(ctx):
    st = ctx.get("server_stats") or {}
    if "topk_grouped" not in st or not st.get("batches"):
        return None
    return 100.0 * st["topk_grouped"] / st["batches"]
