"""round.server_ms: device time per round of the server's side of the
round, the broadcast (scope ``fedadc.broadcast``), the aggregation
(``fedadc.aggregate``) and the momentum and model update
(``fedadc.server_update``), averaged over the cell's chips.  Reads
nothing where the trace names no phase.
It carries the ``leaves()`` defect that ``bench/phases.py`` describes."""
from bench import phases as P


def read(ctx):
    return P.per_round_ms(ctx, {"broadcast", "aggregate", "server_update"})
