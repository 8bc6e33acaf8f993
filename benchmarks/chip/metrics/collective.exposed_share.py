"""collective.exposed_share: the share of the traced window in which an
all-gather, all-reduce or reduce-scatter runs on a chip and no other op
does, averaged over the cell's chips.  Reads nothing where no collective
ran."""
from bench import trace as T


def read(ctx):
    chips = sorted(ctx.trace["devices"])
    if not any(T.collective_intervals(ctx.trace, c) for c in chips):
        return None
    exposed = sum(T.exposed_collective_s(ctx.trace, c) for c in chips)
    return 100.0 * exposed / len(chips) / T.window_s(ctx.trace)
