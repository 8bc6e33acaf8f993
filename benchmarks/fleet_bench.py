"""Fleet-scale aggregation bench: flat vs two-tier hierarchical, K ∈
{10^3, 10^4, 10^5} simulated clients (DESIGN.md §Fleet; emits
BENCH_fleet.json).

Substrate-level on purpose: no model training, just the round substrate
the fleet subsystem changes — a ``FleetScheduler`` cohort, a
``PagedClientStore`` EF gather/scatter per round, seeded synthetic
deltas, and the repo's real ``weighted_mean`` reduction — so the
K=10^5 cell costs seconds, not hours.  Per (fleet, mode) cell:

* **flat** stages the whole cohort's wires as one (C, d) block and runs
  one global ``weighted_mean`` — the O(C·d) server staging footprint the
  ROADMAP flagged.
* **hier** walks the cohort's regions sequentially: each regional block
  (k_r, d) is staged, reduced to a partial, and FREED before the next
  region is built; the global combine then reduces the (R, d) partial
  stack — exactly ``hierarchical_aggregate``'s split, so peak staging
  drops from O(C·d) to O((C/R)·d + R·d).

Peak host bytes are measured from the actual ``.nbytes`` of live staged
blocks plus the store's resident high-water mark — deterministic given
the seed, so the CI gate compares them within tolerance, as it does the
store's page traffic (``spills_per_round``, ``loads_per_round``).  The
headline booleans (``hier_le_flat_peak_at_1e5``, ``budget_ok_at_1e5``)
and the byte ratio are the gated claims.  No field is a time.

``--smoke`` keeps all three K cells (the committed JSON's list lengths
must match CI's fresh run) and only trims the round count; every gated
field is round-count invariant — staging peaks repeat identically each
round, and the store peak hits its budget-bound ceiling during the first
round's scatter because the cohort's pages exceed the budget.
"""
import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.configs.base import FedConfig
from repro.federated import aggregation as A
from repro.federated.fleet import FleetScheduler, PagedClientStore
from repro.telemetry.tracer import Counters

FLEETS = (1_000, 10_000, 100_000)
COHORT = 256
REGIONS = 8
DIM = 8192                      # 32 KiB fp32 page per client
BUDGET_PAGES = 64               # < COHORT pages -> steady-state spilling


def _run_mode(fleet: int, hierarchical: bool, rounds: int, seed: int = 0):
    """Drive `rounds` substrate rounds; returns the cell dict."""
    d = DIM
    budget = BUDGET_PAGES * d * 4
    fed = FedConfig(n_clients=fleet, clients_per_round=COHORT,
                    fleet_regions=REGIONS if hierarchical else 0)
    counters = Counters()
    store = PagedClientStore(budget_bytes=budget, counters=counters)
    store.register("ef", lambda: np.zeros((d,), np.float32))
    sched = FleetScheduler(fed, n_regions=REGIONS if hierarchical else 1,
                           seed=seed)
    rng = np.random.RandomState(seed)
    peak_staging = 0
    for _ in range(rounds):
        cohort = sched.sample_cohort()
        groups = (cohort.region_slices() if hierarchical
                  else ((0, len(cohort.clients)),))
        partials, gw = [], []
        for start, size in groups:
            ids = cohort.clients[start:start + size]
            efs = store.gather("ef", ids)                    # (size, d)
            deltas = jnp.asarray(rng.randn(size, d).astype(np.float32))
            wires = deltas + efs
            w = jnp.ones((size,), jnp.float32)
            m = A.weighted_mean(wires, w)
            jax.block_until_ready(m)
            staged = (int(efs.nbytes + deltas.nbytes + wires.nbytes)
                      + sum(int(p.nbytes) for p in partials))
            peak_staging = max(peak_staging, staged)
            partials.append(m)
            gw.append(jnp.sum(w))
            store.scatter("ef", ids, wires * 0.5)            # EF update
            del efs, deltas, wires                           # free the block
        if hierarchical:
            gmean = A.weighted_mean(jnp.stack(partials), jnp.stack(gw))
        else:
            gmean = partials[0]
        jax.block_until_ready(gmean)
    snap = counters.snapshot()
    peak_store = int(store.peak_resident_bytes)
    return {
        "fleet": fleet,
        "mode": "hier" if hierarchical else "flat",
        "regions": REGIONS if hierarchical else 0,
        "cohort": COHORT,
        "d": d,
        "store_budget_bytes": budget,
        "peak_staging_bytes": int(peak_staging),
        "peak_store_bytes": peak_store,
        "peak_host_bytes": int(peak_staging) + peak_store,
        "budget_ok": bool(peak_store <= budget),
        "spills_per_round": round(snap.get("store.spills", 0) / rounds, 1),
        "loads_per_round": round(snap.get("store.loads", 0) / rounds, 1),
    }


def main(rows=None, out_json="BENCH_fleet.json", smoke=False):
    rows = rows if rows is not None else []
    rounds = 2 if smoke else 3
    cells = []
    for fleet in FLEETS:
        for hierarchical in (False, True):
            cell = _run_mode(fleet, hierarchical, rounds)
            cells.append(cell)
            rows.append(emit(
                f"fleet.K{fleet}.{cell['mode']}", 0,
                f"peak_host_mb={cell['peak_host_bytes'] / 2**20:.1f};"
                f"spills_per_round={cell['spills_per_round']}"))
    at_1e5 = {c["mode"]: c for c in cells if c["fleet"] == 100_000}
    report = {
        "cohort": COHORT,
        "regions": REGIONS,
        "d": DIM,
        "rounds_per_cell": rounds,
        "cells": cells,
        "headline": {
            "hier_le_flat_peak_at_1e5": bool(
                at_1e5["hier"]["peak_host_bytes"]
                <= at_1e5["flat"]["peak_host_bytes"]),
            "budget_ok_at_1e5": bool(at_1e5["hier"]["budget_ok"]
                                     and at_1e5["flat"]["budget_ok"]),
            "peak_host_hier_over_flat_at_1e5": round(
                at_1e5["hier"]["peak_host_bytes"]
                / at_1e5["flat"]["peak_host_bytes"], 4),
        },
    }
    with open(out_json, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {out_json}")
    assert report["headline"]["budget_ok_at_1e5"], (
        "paged store exceeded its resident-bytes budget at K=1e5")
    assert report["headline"]["hier_le_flat_peak_at_1e5"], (
        "hierarchical peak host bytes no longer <= flat at K=1e5")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_fleet.json")
    ap.add_argument("--smoke", action="store_true",
                    help="fewer rounds per cell; all K cells kept")
    args = ap.parse_args()
    main(out_json=args.out, smoke=args.smoke)
