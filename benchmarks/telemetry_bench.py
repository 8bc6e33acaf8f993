"""Telemetry bench: the enabled path leaves the numerics alone.

Runs the same synchronous FedADC configuration twice — telemetry disabled
(the default) and enabled with in-jit drift diagnostics + span tracing —
and checks that both produce the identical final accuracy: the
observability path is not allowed to touch the numerics.  Emits
``BENCH_telemetry.json`` for the CI bench-smoke gate
(``enabled_acc_identical`` is the committed boolean).

What the enabled path costs is a time, and so is left to a run on the
chip (DESIGN.md §Telemetry).
"""
from __future__ import annotations

import argparse
import json

from benchmarks.common import dataset, emit, partitions, run_fl
from repro.telemetry import Telemetry


def _run(parts, data, rounds, telemetry):
    return run_fl("fedadc", parts, data, rounds=rounds, n_clients=20, seed=0,
                  telemetry=Telemetry(engine="sim") if telemetry else None)


def main(rows=None, rounds=40, out_json="BENCH_telemetry.json"):
    rows = rows if rows is not None else []
    data = dataset()
    parts = partitions(data[1], 20, "sort", 2, seed=0)
    r_off = _run(parts, data, rounds, False)
    r_on = _run(parts, data, rounds, True)
    identical = bool(r_on["acc"] == r_off["acc"])
    rows.append(emit("telemetry.enabled_acc_identical", 0, identical))
    report = {
        "rounds": rounds,
        "enabled_acc_identical": identical,
    }
    with open(out_json, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {out_json}")
    assert identical, "telemetry-enabled run changed the accuracy"
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--out", default="BENCH_telemetry.json")
    args = ap.parse_args()
    main(rounds=args.rounds, out_json=args.out)
