"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Modules:
  fig1_acceleration  — Fig. 1 a-c  (FedADC vs FedAvg vs SlowMo, s=2,3,4)
  fig2_robustness    — Fig. 2      (FedADC robustness to skew; red vs blue)
  table1_sota        — Table I     (vs MOON/FedGKD/FedNTD/FedDyn/FedProx/
                                     SCAFFOLD/FedRS, 2 regimes)
  fig5_scale         — Fig. 5/6    (low participation, many clients)
  fig7_personalization — Fig. 7    (classifier calibration, 3 regularisers)
  clustering         — Sec. IV-E   (class-coverage client selection)
  straggler_bench    — wall-clock-to-accuracy, sync vs semi-async FedADC
                       under a 4× straggler fleet (DESIGN.md §Heterogeneity)
  serving_bench      — continuous batching vs serial decode: step counts
                       and greedy identity (DESIGN.md §Serving; emits
                       BENCH_serving_smoke.json)
  comm_load          — Sec. II-A   analytic bytes/round per strategy, side by
                       side with measured per-client wire bytes through each
                       compressor (DESIGN.md §Compression)
  comm_sweep         — accuracy-vs-uplink-bytes frontier, strategy ×
                       compressor on the non-IID benchmark (emits
                       BENCH_comm.json)
  telemetry_bench    — telemetry-enabled vs disabled sync rounds: the
                       enabled run leaves the accuracy unchanged (emits
                       BENCH_telemetry.json)
  fleet_bench        — flat vs two-tier hierarchical aggregation at
                       K ∈ {1e3,1e4,1e5} simulated clients: peak host
                       bytes and page traffic with a paged, budget-bounded
                       client store (DESIGN.md §Fleet; emits
                       BENCH_fleet.json)
"""
import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args()

    from benchmarks import (ablation_beta, clustering, comm_load, comm_sweep,
                            fig1_acceleration, fig2_robustness, fig5_scale,
                            fig7_personalization, fleet_bench, lm_round,
                            serving_bench, straggler_bench, table1_sota,
                            telemetry_bench)
    mods = {
        "comm_load": comm_load,
        "comm_sweep": comm_sweep,
        "fig1_acceleration": fig1_acceleration,
        "fig2_robustness": fig2_robustness,
        "table1_sota": table1_sota,
        "fig5_scale": fig5_scale,
        "fig7_personalization": fig7_personalization,
        "clustering": clustering,
        "lm_round": lm_round,
        "ablation_beta": ablation_beta,
        "straggler_bench": straggler_bench,
        "serving_bench": serving_bench,
        "telemetry_bench": telemetry_bench,
        "fleet_bench": fleet_bench,
    }
    picked = (args.only.split(",") if args.only else list(mods))
    print("name,us_per_call,derived")
    rows = []
    failed = []
    for name in picked:
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        try:
            mods[name].main(rows)
        except Exception as e:
            # run the remaining benchmarks, then fail the harness
            traceback.print_exc()
            print(f"{name},0,ERROR:{e!r}", flush=True)
            failed.append(name)
        print(f"# {name} took {time.time()-t0:.1f}s", flush=True)
    print(f"# total rows: {len(rows)}")
    if failed:
        sys.exit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
