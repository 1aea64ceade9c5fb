"""Device milliseconds per step in the sort layer, attributed from the
trace by bench/layers/sort.json."""


def read(ctx):
    seconds = ctx.trace.layer_s.get("sort", 0.0)
    if seconds <= 0 or ctx.steps <= 0:
        return None
    return 1e3 * seconds / ctx.steps
