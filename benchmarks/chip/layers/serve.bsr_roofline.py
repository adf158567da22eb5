"""serve.bsr_roofline: the exhaustive fp32 BSR kernel's share of its
roofline. For every kernel launch in the trace, the least time is the
larger of its FLOPs over the bf16 peak and its least bytes over HBM
bandwidth (`counts.bsr_flops`, `counts.bsr_min_bytes`, for the launch's
rows); the share is their sum over the kernel's summed device time.
Reads nothing where no kernel event is found."""

import serve_step


def read(ctx):
    share = serve_step.roofline_share(ctx, serve_step.KERNEL)
    return None if share is None else 100.0 * share
