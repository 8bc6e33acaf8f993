"""round.recompute_ms: the part of ``round.fwd_bwd_ms`` whose ops were
traced inside a rematerialised computation, the forward that remat runs
again in the backward.  Reads nothing where the trace names no phase.
It carries the ``leaves()`` defect that ``bench/phases.py`` describes."""
from bench import phases as P


def read(ctx):
    return P.per_round_ms(ctx, {"fwd_bwd"}, recompute=True)
