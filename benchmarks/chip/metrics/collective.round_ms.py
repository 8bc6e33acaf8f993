"""collective.round_ms: device time per round in which an all-gather,
all-reduce, reduce-scatter, all-to-all or collective-permute runs on a
chip, whether compute overlaps it or not: the union of the collectives'
intervals (``bench/trace.py`` ``collective_intervals``, an async one from
its start to its done) inside the traced window, averaged over the cell's
chips, over the window's rounds.  Reads nothing where no collective ran.
It does not use ``leaves()``, so it is free of the defect that
``bench/phases.py`` describes."""
from bench import phases as P
from bench import trace as T


def read(ctx):
    chips = sorted(ctx.trace["devices"])
    per_chip = [T.collective_intervals(ctx.trace, c) for c in chips]
    if not any(per_chip):
        return None
    lo, hi = ctx.trace["window"]
    ns = sum(T.length(T.clip(iv, lo, hi)) for iv in per_chip)
    return 1e-6 * ns / len(chips) / P.rounds_in_window(ctx)
