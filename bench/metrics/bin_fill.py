"""Percent of bin slots that hold a particle carrying weight, in the state
after the window: weighted particles / (cells x capacity). The rest is
padding that every contraction over the bins pays for."""


def read(ctx):
    slots = ctx.n_cells * ctx.capacity
    if slots <= 0 or ctx.n_weighted <= 0:
        return None
    return 100.0 * ctx.n_weighted / slots
