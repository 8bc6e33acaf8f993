"""Readings that the correctness limits of a cell are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,...,12 --control-seeds 1,2,3 \
        --faults half_clients,half_rows

In one process, on the cell's chips and at its sizes: the program's first
rounds from each seed against the reference's (the lower readings); the
program's own lower-precision path, the workload's ``control`` settings
(``run.compute_dtype`` bfloat16 where the cell states float32), from each
control seed (the control); and the reference with each planted fault
(see ``reference/fedadc.py``) against the clean reference of the same
seed (the upper readings).  Each
reading is one JSON line on standard output, with ``correct`` as the
run's own check would decide it under the workload's limits; the last
line sums them up: the largest reading of the program and the smallest of
the control and of each fault, for every number compared.  A step that
returns its state unchanged reads 1 on ``update_norm_gap`` by its
definition and needs no run.

Limits are set from these readings, so it runs on a TPU only, as
``run.py`` does: it exits 2 where JAX finds no TPU or fewer chips than
the cell needs.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]
sys.path.insert(0, str(CHIP_DIR))
sys.path.insert(0, str(ROOT / "src"))

from run import use_compile_cache  # noqa: E402


def seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    use_compile_cache()
    import jax
    import numpy as np

    from bench import compare as C
    from bench.harness import Cell, first_rounds, log_readings
    from bench.registry import Registry

    registry = Registry(CHIP_DIR, ROOT / "BENCHMARK.json")
    devices = jax.devices()
    platform = devices[0].platform
    chips = registry.cell(args.workload)["chips"]
    if platform != "tpu" or len(devices) < chips:
        print(f"calibrate: cell {args.workload} needs {chips} TPU chips, "
              f"JAX sees {len(devices)} {platform!r} devices",
              file=sys.stderr, flush=True)
        return 2
    cell = Cell(registry, args.workload, devices, platform)
    control = Cell(registry, args.workload, devices, platform,
                   run_override=cell.workload["control"]["run"])

    def program(c, seeds):
        out = {}
        mesh_ctx = jax.set_mesh(c.mesh) if c.mesh is not None \
            else contextlib.nullcontext()
        with mesh_ctx:
            compiled, state, key = c.build(seeds[0])
            for i, seed in enumerate(seeds):
                if i:
                    state, key = c.new_state(seed)
                state, out[seed], _ = first_rounds(c, compiled, state, key,
                                                   seed)
                del state
        return out
    prog = program(cell, args.seeds) if args.seeds else {}
    ctl = program(control, args.control_seeds) if args.control_seeds \
        else {}

    limits = cell.workload["limits"]

    def emit(kind, seed, values, worst, names):
        correct = C.passed({n: {"value": values[n], "limit": limits[n]}
                            for n in C.NAMES})
        print(json.dumps({"kind": kind, "seed": seed, "values": values,
                          "correct": correct,
                          "worst_leaf": {k: names[v]
                                         for k, v in worst.items()}}),
              flush=True)
        return values

    refs, table = {}, {}
    for seed in args.seeds:
        refs[seed] = cell.reference(seed)
        if seed == args.seeds[0]:
            log_readings(prog[seed], refs[seed])
        values, worst = C.readings(prog[seed], refs[seed])
        table.setdefault("program", []).append(
            emit("program", seed, values, worst, refs[seed]["names"]))
    for seed in args.control_seeds:
        if seed not in refs:
            refs[seed] = cell.reference(seed)
        if seed == args.control_seeds[0]:
            log_readings(ctl[seed], refs[seed])
        values, worst = C.readings(ctl[seed], refs[seed])
        table.setdefault("control", []).append(
            emit("control", seed, values, worst, refs[seed]["names"]))
    for fault in [f for f in args.faults.split(",") if f]:
        for seed in args.control_seeds:
            planted = cell.reference(seed, fault=fault)
            values, worst = C.readings(planted, refs[seed])
            table.setdefault(fault, []).append(
                emit(fault, seed, values, worst, refs[seed]["names"]))
    summary = {}
    for kind, rows in table.items():
        pick = np.max if kind == "program" else np.min
        summary[kind] = {n: float(pick([r[n] for r in rows]))
                         for n in C.NAMES}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
