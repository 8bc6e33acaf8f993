"""The on-chip benchmark: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs from the root of a checkout, on a machine that holds the chips the
cell asks for (``BENCHMARK.json``).  The last line of standard output is
one JSON object: ``correct``, ``attempted`` and ``failed`` (window
rounds, and those whose loss is not finite), ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``, each
number the correctness check compared beside its limit.  The same checks
are the last lines of standard error.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell needs, where the chip's ``device_kind`` has no peaks in
``peaks.json``, or where the compiled round holds no Mosaic kernel.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]
sys.path.insert(0, str(CHIP_DIR))
sys.path.insert(0, str(ROOT / "src"))


def use_compile_cache():
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else at the fixed path <checkout>/.jax_cache.  Every program is
    cached, however quickly it compiled, so a warm run compiles nothing."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    from bench.registry import BenchmarkError, Registry
    try:
        registry = Registry(CHIP_DIR, ROOT / "BENCHMARK.json")
        registry.cell(args.workload)
        use_compile_cache()
        from bench.harness import log, run_cell
        result = run_cell(registry, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
