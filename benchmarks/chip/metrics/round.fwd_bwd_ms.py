"""round.fwd_bwd_ms: device time per round of the clients' forward and
backward passes (scope ``fedadc.fwd_bwd``), remat's recompute included,
averaged over the cell's chips.  Reads nothing where the trace names no
phase.  It leaves out the ops ``bench/trace.py`` ``leaves()`` misreads as
idle, most of them in this phase (``bench/phases.py``): size no claim
from it until that is repaired."""
from bench import phases as P


def read(ctx):
    return P.per_round_ms(ctx, {"fwd_bwd"})
