"""device.idle_in_loop_share: the share of the traced window in which the
device idled inside an op that holds others (the round's loops), averaged
over the cell's chips.  The rest of ``device.idle_share`` lies between
programs.  Reads nothing where the trace names no phase.  Until
``bench/trace.py`` ``leaves()`` stops taking an op with an event of zero
length in its span for a holder, this reads mostly such ops' time, not
real idle (``bench/phases.py``): size no claim from it until then."""
from bench import phases as P
from bench import trace as T


def read(ctx):
    if not P.has_phases(ctx.trace):
        return None
    chips = sorted(ctx.trace["devices"])
    idle = sum(P.idle_in_loop_s(ctx.trace, c) for c in chips) / len(chips)
    return 100.0 * idle / T.window_s(ctx.trace)
