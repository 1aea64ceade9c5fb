"""Percent of the deposition roofline: the least time the chip could take for
the algorithm's deposition work (bench/counts.py), the larger of FLOPs over
peak FLOP/s and bytes over peak bandwidth, over the device time the
trace attributes to the deposition layer."""

import counts


def read(ctx):
    seconds = ctx.trace.layer_s.get("deposition", 0.0)
    if seconds <= 0 or ctx.steps <= 0 or ctx.n_weighted <= 0:
        return None
    flops, nbytes = counts.deposition_work(ctx.order, ctx.n_weighted, ctx.n_cells)
    share, _bound = counts.roofline_share(flops, nbytes, seconds / ctx.steps, ctx.peak)
    return share
