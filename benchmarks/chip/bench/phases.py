"""The FedADC round's phases in a device trace.

The program names each phase of its round with ``jax.named_scope``
(``launch/train.py``): ``fedadc.broadcast``, ``fedadc.fwd_bwd``,
``fedadc.local_update``, ``fedadc.uplink``, ``fedadc.accumulate``,
``fedadc.aggregate`` and ``fedadc.server_update``.  A scope adds no
operation: it writes the ``op_name`` metadata of each HLO instruction
traced inside it, and that name survives ``scan``, ``grad``, ``remat``
and fusion into the compiled program.  ``hlo_phases`` reads it from the
compiled HLO text, so each leaf op of a reduced trace (``bench/trace.py``)
can be put in its phase.

A reduced trace that carries phases has one more key:

* ``"phases"``: ``{op: {"phase": name | None, "recompute": bool}}``, as
  ``hlo_phases`` returns it.  The name is the scope's without
  ``fedadc.``; an op that is not in the map has no phase.

Known defect, shared with ``device.idle_share`` and ``device.busy_s``:
every reduction here starts from ``bench/trace.py`` ``leaves()``, which
takes an op for a holder, not a leaf, when an event of zero length lies
in its span (the profiler stamps some ``custom-call`` ops so, at the op's
start).  That op's time then reads as idle, inside the loop that runs it.
So ``idle_in_loop_s`` reads mostly that misread time, not real in-loop
idle, and ``phase_busy_s`` leaves it out of the op's phase (on a TPU v5e
mostly ``fwd_bwd``, about 2% of a ``qwen3-4b.silo`` round).
``zero_length_holders`` lists those ops.  Size no claim from
``device.idle_in_loop_share`` or ``round.fwd_bwd_ms`` until ``leaves()``
stops making such an op a holder (PERF.md §7); then
``zero_length_holders`` can go.
"""
from __future__ import annotations

import bisect
import re

from bench import trace as T

PHASES = ("broadcast", "fwd_bwd", "local_update", "uplink", "accumulate",
          "aggregate", "server_update")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_SCOPE = re.compile(r"fedadc\.(\w+)")
_ASYNC = re.compile(r"-(start|done)(\.\d+)?$")
REMAT = "rematted_computation"


def phase_of(op_name: str):
    """The innermost ``fedadc.<phase>`` scope of an ``op_name`` path, its
    token bare (``fedadc.fwd_bwd/...``) or wrapped by a transformation
    (``transpose(jvp(fedadc.fwd_bwd))``); None where it has none."""
    found = [p for p in _SCOPE.findall(op_name) if p in PHASES]
    return found[-1] if found else None


def hlo_phases(hlo_text: str) -> dict:
    """{instruction: {"phase", "recompute"}} for every instruction of a
    compiled program's HLO text.  ``recompute`` is true where the op was
    traced inside a rematerialised computation.  An instruction the
    compiler inserted has no ``op_name`` and no phase."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        meta = _OP_NAME.search(line)
        path = meta.group(1) if meta else ""
        out[m.group(1)] = {"phase": phase_of(path),
                           "recompute": REMAT in path}
    return out


def has_phases(trace) -> bool:
    """Whether the trace's program names any phase (a program without the
    scopes, or a trace without ``"phases"``, reads no phase metric)."""
    return any(v["phase"] for v in trace.get("phases", {}).values())


def op_phase(trace, op):
    """The phase of `op` in a trace that carries phases (None: none)."""
    return trace["phases"].get(op, {}).get("phase")


# ---------------------------------------------------------------------------
# reductions, per chip and over the window
# ---------------------------------------------------------------------------
def phase_busy_s(trace, chip, phases, recompute=None) -> float:
    """Seconds of the window in which a leaf op of one of `phases` (names,
    None for an op with no phase) ran on `chip`; with `recompute` given,
    only the ops whose recompute flag equals it."""
    lo, hi = trace["window"]
    info = trace["phases"]
    spans = []
    for op, s, e in T.leaves(trace["devices"][chip]["ops"]):
        entry = info.get(op, {"phase": None, "recompute": False})
        if entry["phase"] in phases and (
                recompute is None or entry["recompute"] == recompute):
            spans.append((s, e))
    return T.length(T.clip(spans, lo, hi)) / 1e9


def idle_in_loop_s(trace, chip) -> float:
    """Seconds of the window's idle time that lie inside the span of an op
    that holds other ops (a ``while``, ``call`` or ``conditional``): idle
    inside the round's loops, as against idle between programs."""
    lo, hi = trace["window"]
    ops = trace["devices"][chip]["ops"]
    leaf = {id(o) for o in T.leaves(ops)}
    holders = T.clip([(o[1], o[2]) for o in ops if id(o) not in leaf],
                     lo, hi)
    idle = T.idle_gaps(trace, chip)
    return (T.length(idle) - T.length(T.subtract(idle, holders))) / 1e9


def gap_phases(trace, chip, top: int = 10):
    """The `top` longest idle gaps of `chip` in the window, longest first,
    each ``[<phase of the op before>→<phase of the op after>, seconds]``;
    an op with no phase reads ``unscoped``, a window edge ``window``.  An
    event of zero length and an async op's start or done marker are no op
    on either side."""
    ops = sorted((s, e, op) for op, s, e in T.leaves(
        trace["devices"][chip]["ops"]) if e > s and not _ASYNC.search(op))
    by_end = sorted((e, op) for s, e, op in ops)
    ends = [e for e, _ in by_end]
    starts = [s for s, _, _ in ops]

    def name(op):
        return op_phase(trace, op) or "unscoped"
    gaps = sorted(T.idle_gaps(trace, chip), key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        i = bisect.bisect_right(ends, s) - 1
        j = bisect.bisect_left(starts, e)
        before = name(by_end[i][1]) if i >= 0 else "window"
        after = name(ops[j][2]) if j < len(ops) else "window"
        out.append([f"{before}→{after}", (e - s) / 1e9])
    return out


def zero_length_holders(trace, chip):
    """[(op, start_ns, end_ns)] of the ops that ``T.leaves`` takes for
    holders only because an event of zero length (a custom-call the
    profiler stamps at the op's start) lies in their span.  Busy time
    leaves such an op out, so its time reads as idle, inside the loop
    that runs it."""
    ops = trace["devices"][chip]["ops"]
    timed = [o for o in ops if o[2] > o[1]]
    leaf = {id(o) for o in T.leaves(ops)}
    return [tuple(o) for o in T.leaves(timed) if id(o) not in leaf]


def top_ops(trace, chip, phase, top: int = 5):
    """[[op, seconds]] of the `top` leaf ops of `phase` (None: no phase)
    that took most of the window on `chip`."""
    lo, hi = trace["window"]
    total = {}
    for op, s, e in T.leaves(trace["devices"][chip]["ops"]):
        if op_phase(trace, op) == phase and min(e, hi) > max(s, lo):
            total[op] = total.get(op, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
    return [[op, t] for op, t in sorted(total.items(),
                                        key=lambda kv: -kv[1])[:top]]


# ---------------------------------------------------------------------------
# per round, for the metrics' readers
# ---------------------------------------------------------------------------
def rounds_in_window(ctx) -> float:
    """The rounds the traced window holds: its tokens over a round's."""
    rnd = ctx.workload["round"]
    per_round = (rnd["clients"] * rnd["local_steps"] * rnd["rows"]
                 * rnd["seq_len"])
    return ctx.tokens_per_s * T.window_s(ctx.trace) / per_round


def per_round_ms(ctx, phases, recompute=None):
    """Milliseconds per round in which a leaf op of `phases` ran, averaged
    over the chips; None where the trace names no phase."""
    if not has_phases(ctx.trace):
        return None
    chips = sorted(ctx.trace["devices"])
    busy = sum(phase_busy_s(ctx.trace, c, phases, recompute)
               for c in chips) / len(chips)
    return 1e3 * busy / rounds_in_window(ctx)
