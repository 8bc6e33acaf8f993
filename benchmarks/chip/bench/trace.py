"""From a profiler trace to busy time, idle gaps, kernel time and exposed
collective time.

The reduced trace is a plain dict, so a test can build one by hand or
load a recorded one from JSON:

* ``"window"``: ``[start_ns, end_ns]`` of the host's ``bench.window``
  span, on the trace's clock;
* ``"host"``: ``[[name, start_ns, end_ns], ...]``, the host spans the
  benchmark recorded (``bench.*``);
* ``"devices"``: ``{chip: {"ops": [[op, start_ns, end_ns], ...],
  "async": [[op, start_ns, end_ns], ...]}}``: the ``XLA Ops`` line of
  each ``/device:TPU:<chip>`` plane (the ops the core runs, nested:
  a ``while`` holds its body's ops) and its ``Async XLA Ops`` line
  (a copy or collective from its start to its done);
* ``"kernels"``: ``{op: {"kernel": name, "result": [dtype, shape],
  "operands": [[dtype, shape], ...]}}`` for every Mosaic kernel of the
  compiled program (see ``hlo_kernels``).

``op`` is the HLO instruction's name (``fusion.12``, ``closed_call.159``).
"""
from __future__ import annotations

import base64
import re

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
_BODY = re.compile(r'"body":"([^"]*)"')
_KERNEL_NAME = re.compile(rb"[A-Za-z_][A-Za-z0-9_]*_kernel")


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------
def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def _array(text):
    dtype, dims = text
    return [dtype, [int(d) for d in dims.split(",") if d]]


def _braced(text: str, key: str) -> str:
    """The balanced ``{...}`` that follows `key` in `text` ("" if none)."""
    start = text.find(key + "{")
    if start < 0:
        return ""
    depth, i = 0, start + len(key)
    for j in range(i, len(text)):
        depth += {"{": 1, "}": -1}.get(text[j], 0)
        if depth == 0:
            return text[i + 1:j]
    return ""


def hlo_kernels(hlo_text: str) -> dict:
    """Every ``tpu_custom_call`` of a compiled program's HLO text: its
    kernel's name (the Pallas kernel function, read from the serialized
    Mosaic module), its result and its operands' shapes."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m, body = _INSTR.match(line), _BODY.search(line)
        if not m or not body:
            continue
        names = _KERNEL_NAME.findall(base64.b64decode(body.group(1)))
        head, _, rest = line.partition("custom-call(")
        out[m.group(1)] = {
            "kernel": names[0].decode() if names else "unknown",
            "result": _array(_ARRAY.findall(head.split("=", 1)[1])[0]),
            "operands": [_array(a) for a in _ARRAY.findall(
                _braced(rest, "operand_layout_constraints="))],
        }
    return out


def load_xplane(path: str, kernels: dict) -> dict:
    """The reduced trace of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1])
            lines = {"ops": [], "async": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "Async XLA Ops": "async"}.get(
                    line.name)
                if key is None:
                    continue
                lines[key] = [[op_name(e.name), e.start_ns, e.end_ns]
                              for e in line.events]
            devices[chip] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.end_ns] for e in line.events
                         if e.name.startswith("bench.")]
    window = [[s, e] for n, s, e in host if n == "bench.window"]
    if len(window) != 1:
        raise ValueError(f"expected one bench.window span, found "
                         f"{len(window)}")
    return {"window": window[0], "host": host, "devices": devices,
            "kernels": kernels}


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------
def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(a, b):
    """The points of `a` not in `b` (both any intervals) -> disjoint."""
    out, b = [], union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def leaves(ops):
    """The ops that hold no other op (a ``while`` or a ``call`` holds its
    body's ops; its own span is not work)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    holder = [False] * len(ops)
    stack = []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            holder[stack[-1]] = True
        stack.append(i)
    return [op for op, h in zip(ops, holder) if not h]


# ---------------------------------------------------------------------------
# reductions, per chip and over the window
# ---------------------------------------------------------------------------
def _window(trace):
    return trace["window"][0], trace["window"][1]


def window_s(trace) -> float:
    lo, hi = _window(trace)
    return (hi - lo) / 1e9


def busy_s(trace, chip) -> float:
    """Seconds of the window in which some op ran on `chip`."""
    lo, hi = _window(trace)
    ops = leaves(trace["devices"][chip]["ops"])
    return length(clip([(s, e) for _, s, e in ops], lo, hi)) / 1e9


def mean_busy_s(trace) -> float:
    chips = sorted(trace["devices"])
    return sum(busy_s(trace, c) for c in chips) / len(chips)


def idle_gaps(trace, chip):
    """[(start_ns, end_ns)] of the window in which no op ran on `chip`."""
    lo, hi = _window(trace)
    ops = leaves(trace["devices"][chip]["ops"])
    return subtract([(lo, hi)], [(s, e) for _, s, e in ops])


def kernel_events(trace, chip, kernel: str):
    """[(op, start_ns, end_ns)] of `kernel`'s calls inside the window."""
    lo, hi = _window(trace)
    names = {op for op, k in trace["kernels"].items()
             if k["kernel"] == kernel}
    return [(op, s, e) for op, s, e in trace["devices"][chip]["ops"]
            if op in names and s >= lo and e <= hi]


def collective_intervals(trace, chip):
    dev = trace["devices"][chip]
    return [(s, e) for op, s, e in dev["ops"] + dev["async"]
            if COLLECTIVE.match(op)]


def exposed_collective_s(trace, chip) -> float:
    """Seconds of the window in which a collective runs on `chip` and no
    other op does."""
    lo, hi = _window(trace)
    compute = [(s, e) for op, s, e in leaves(trace["devices"][chip]["ops"])
               if not COLLECTIVE.match(op)]
    coll = clip(collective_intervals(trace, chip), lo, hi)
    return length(subtract(coll, compute)) / 1e9


def self_times(trace, chip):
    """{op: seconds} of each op's own time in the window: its span less
    the spans of the ops it holds."""
    lo, hi = _window(trace)
    ops = sorted(((op, s, e) for op, s, e in trace["devices"][chip]["ops"]
                  if s >= lo and e <= hi), key=lambda o: (o[1], -o[2]))
    out = {}
    stack = []          # [op, end, own time so far]
    for op, s, e in ops:
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out[done[0]] = out.get(done[0], 0.0) + done[2]
        if stack:
            stack[-1][2] -= e - s
        stack.append([op, e, float(e - s)])
    for done in stack:
        out[done[0]] = out.get(done[0], 0.0) + done[2]
    return {op: t / 1e9 for op, t in out.items()}


def breakdown(trace, top: int = 10):
    """The device ops that took most of the window (own time, averaged
    over chips, named with their kernel where they are one) and the
    longest idle gaps of chip 0, named by the host spans they overlap."""
    chips = sorted(trace["devices"])
    total = {}
    for c in chips:
        for op, t in self_times(trace, c).items():
            k = trace["kernels"].get(op)
            name = f"{op} ({k['kernel']})" if k else op
            total[name] = total.get(name, 0.0) + t / len(chips)
    ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace, chips[0]), key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:top]:
        host = sorted({n for n, hs, he in trace["host"]
                       if n != "bench.window" and hs < e and he > s})
        named.append(["+".join(host) if host else "no host span",
                      (e - s) / 1e9])
    return {"device_ops": [[n, t] for n, t in ops], "idle_gaps": named}
