"""round.local_update_ms: device time per round of the clients' local
updates (scope ``fedadc.local_update``: the Nesterov half-step and the SGD
step, the gradient not included), averaged over the cell's chips.  Reads
nothing where the trace names no phase.
It carries the ``leaves()`` defect that ``bench/phases.py`` describes."""
from bench import phases as P


def read(ctx):
    return P.per_round_ms(ctx, {"local_update"})
