"""One traced run of a cell, read by the round's phases.

    python3 benchmarks/chip/trace_phases.py --workload <cell> --seed <n> \
        --seconds <s> [--record <path> --record-seconds 0.2]

Runs the cell as ``run.py --trace 1`` does, on the same chips, and reads
the phase metrics (``PHASE_METRICS``, readers in ``metrics/``) from the
same device trace, with the compiled round's phases (``bench/phases.py``)
added to the reduced trace.  The last line of standard output is
``run.py``'s result object, its ``metrics`` holding the phase metrics
too, and a ``phases`` object: the traced window's rounds and rate, busy
time per round, the ten longest idle gaps of chip 0 named by the phases
on either side, the top five ops of each phase and of no phase, and the
ops whose time reads as idle because an event of zero length lies in
their span (``bench/phases.py`` ``zero_length_holders``).

``--record`` writes the first ``--record-seconds`` of the window as a
reduced trace (JSON, with its phases and the run's traced rate), for the
tests' recorded traces.

Temporary: the harness's trace reader has no phases, so while the run
lasts this script swaps ``bench.harness.read_trace`` and
``bench.trace.load_xplane`` for wrappers, and depends on their
signatures; it fails with exit code 2 where the harness no longer calls
them.  Once ``read_trace`` adds ``hlo_phases`` to the trace and the phase
metrics are in BENCHMARK.json (PERF.md §7), ``run.py --trace 1`` prints
them, and this script keeps only ``--record`` and the diagnostics.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]
sys.path.insert(0, str(CHIP_DIR))
sys.path.insert(0, str(ROOT / "src"))

from run import use_compile_cache  # noqa: E402

PHASE_METRICS = {"round.fwd_bwd_ms": "ms", "round.recompute_ms": "ms",
                 "round.local_update_ms": "ms",
                 "round.client_exchange_ms": "ms", "round.server_ms": "ms",
                 "round.unscoped_ms": "ms", "device.idle_in_loop_share": "%"}


def record(trace, seconds, tokens_per_s, about):
    """The first `seconds` of the window: every op, host span and async op
    that overlaps it, the kernels and phases of those ops."""
    lo = trace["window"][0]
    hi = lo + int(seconds * 1e9)

    def cut(events):
        return [list(ev) for ev in events if ev[1] < hi and ev[2] > lo]
    devices = {c: {"ops": cut(d["ops"]), "async": cut(d["async"])}
               for c, d in trace["devices"].items()}
    names = {op for d in devices.values() for op, _, _ in d["ops"]}
    return {"about": about, "window": [lo, hi], "host": cut(trace["host"]),
            "devices": devices,
            "kernels": {k: v for k, v in trace["kernels"].items()
                        if k in names},
            "phases": {k: v for k, v in trace["phases"].items()
                       if k in names},
            "tokens_per_s": tokens_per_s}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", type=Path)
    ap.add_argument("--record-seconds", type=float, default=0.2)
    args = ap.parse_args(argv)

    from bench.registry import BenchmarkError, Registry
    registry = Registry(CHIP_DIR, ROOT / "BENCHMARK.json")
    try:
        registry.cell(args.workload)
    except BenchmarkError as e:
        print(f"trace_phases: {e}", file=sys.stderr, flush=True)
        return 2
    use_compile_cache()
    from bench import harness as H
    from bench import phases as P
    from bench import trace as T

    kept = {}
    load_xplane, read_trace = T.load_xplane, H.read_trace

    def load_and_keep(path, kernels):
        kept["trace"] = load_xplane(path, kernels)
        return kept["trace"]

    def read_with_phases(tdir, hlo, cell, peak, registry, rounds, elapsed):
        metrics, busy, span, brk = read_trace(tdir, hlo, cell, peak,
                                              registry, rounds, elapsed)
        if "trace" not in kept:
            raise BenchmarkError("the harness read its trace without "
                                 "bench.trace.load_xplane")
        tr = kept["trace"]
        tr["phases"] = P.hlo_phases(hlo)
        rate = rounds * cell.tokens_per_round / T.window_s(tr)
        ctx = H.Ctx(trace=tr, config=cell.config, workload=cell.workload,
                    peak=peak, chips=len(cell.devices), tokens_per_s=rate,
                    registry=registry)
        for name, unit in PHASE_METRICS.items():
            value = registry.metric(name).read(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": unit}
        chip0 = sorted(tr["devices"])[0]
        n = P.rounds_in_window(ctx)
        lo, hi = tr["window"]
        held = {}
        for op, s, e in P.zero_length_holders(tr, chip0):
            held[op] = held.get(op, 0.0) + max(min(e, hi) - max(s, lo),
                                               0) / 1e9
        kept["phases"] = {
            "rounds": rounds, "traced_tokens_per_s": rate,
            "busy_ms_per_round": 1e3 * busy / n,
            "gap_phases": P.gap_phases(tr, chip0, top=10),
            "top_ops": {str(p): P.top_ops(tr, chip0, p)
                        for p in P.PHASES + (None,)},
            "zero_length_holders": {
                "ms_per_round": 1e3 * sum(held.values()) / n,
                "top": [[op, P.op_phase(tr, op), t] for op, t in sorted(
                    held.items(), key=lambda kv: -kv[1])[:5]]}}
        H.log("gap_phases " + json.dumps(kept["phases"]["gap_phases"]))
        if args.record is not None:
            args.record.parent.mkdir(parents=True, exist_ok=True)
            args.record.write_text(json.dumps(record(
                tr, args.record_seconds, rate,
                f"The first {args.record_seconds} s of a {args.workload} "
                f"--trace 1 window (seed {args.seed}) on a "
                f"{cell.devices[0].device_kind}, reduced by "
                f"bench/trace.py, with the round's phases.")))
        return metrics, busy, span, brk

    T.load_xplane, H.read_trace = load_and_keep, read_with_phases
    try:
        result = H.run_cell(registry, args.workload, args.seed, args.seconds,
                            True, T_START)
    except BenchmarkError as e:
        print(f"trace_phases: {e}", file=sys.stderr, flush=True)
        return 2
    finally:
        T.load_xplane, H.read_trace = load_xplane, read_trace
    if "phases" not in kept:
        print("trace_phases: the harness read its trace without "
              "bench.harness.read_trace", file=sys.stderr, flush=True)
        return 2
    result["phases"] = kept["phases"]
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
