"""serve.step_mfu: the whole predict step's share of the chip's peak: the
same least time as serve.bsr_roofline over the device time of the step's
programs (kernel, padding, masking, top-k), not of one kernel. It still
bounds a gain after a later change fuses or removes the kernel."""

import serve_step


def read(ctx):
    share = serve_step.roofline_share(ctx, serve_step.STEP)
    return None if share is None else 100.0 * share
