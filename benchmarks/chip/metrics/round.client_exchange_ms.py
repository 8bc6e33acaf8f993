"""round.client_exchange_ms: device time per round of each client's delta
and uplink (scope ``fedadc.uplink``) and of its weighted accumulation
(``fedadc.accumulate``), averaged over the cell's chips.  Reads nothing
where the trace names no phase.
It carries the ``leaves()`` defect that ``bench/phases.py`` describes."""
from bench import phases as P


def read(ctx):
    return P.per_round_ms(ctx, {"uplink", "accumulate"})
