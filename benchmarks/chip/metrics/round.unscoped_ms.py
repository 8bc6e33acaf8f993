"""round.unscoped_ms: device time per round of the ops in no phase, most
of them copies and loop bookkeeping the compiler inserted, averaged over
the cell's chips.  With the five phase metrics it adds up to the device's
busy time per round.  Reads nothing where the trace names no phase.
It carries the ``leaves()`` defect that ``bench/phases.py`` describes."""
from bench import phases as P


def read(ctx):
    return P.per_round_ms(ctx, {None})
