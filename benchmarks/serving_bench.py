"""Serving bench: continuous batching vs serial one-at-a-time decode.

8 requests (random prompt lengths, fixed generation budget) go through the
ServingEngine at 4 slots and at 1 slot, the serial baseline: one request
at a time through the identical prefill-chunk + decode-step path.  Greedy
outputs must be identical; the engine-step counts of the two runs show
the scheduler's batching.  Emits ``BENCH_serving_smoke.json`` (counts, no
timings) for the CI bench-smoke gate, and one ``name,us_per_call,derived``
row under ``benchmarks/run.py``.
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from benchmarks.common import emit
from repro.configs.base import ModelConfig
from repro.models.registry import get_model
from repro.serving import SchedulerConfig, ServingEngine

TINY = ModelConfig(arch_id="serving-bench-tiny", n_layers=2, d_model=128,
                   n_heads=4, n_kv_heads=4, d_ff=256, vocab_size=512,
                   max_seq_len=512)
MAX_LEN = 128
GEN = 48


def make_requests(n, seed=0, lo=6, hi=17):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, TINY.vocab_size, rng.randint(lo, hi)).tolist()
            for _ in range(n)]


def run_level(params, prompts, n_slots, prefill_chunk=16):
    eng = ServingEngine(TINY, params=params, sched=SchedulerConfig(
        n_slots=n_slots, max_len=MAX_LEN, prefill_chunk=prefill_chunk,
        page_size=32))
    for p in prompts:
        eng.add_request(p, max_new_tokens=GEN)
    outs = eng.run()
    return {
        "n_slots": n_slots,
        "n_requests": len(prompts),
        "gen_tokens": sum(len(o.tokens) for o in outs),
        "engine_steps": eng.n_steps,
    }, outs


def main(rows=None, out_json="BENCH_serving_smoke.json"):
    """8 requests through the 4-slot scheduler, greedy outputs
    bit-identical to the serial engine."""
    rows = rows if rows is not None else []
    model = get_model(TINY)
    params = model.init(jax.random.PRNGKey(0), TINY)
    prompts = make_requests(8)
    res_b, batched = run_level(params, prompts, n_slots=4)
    res_s, serial = run_level(params, prompts, n_slots=1)
    assert [o.tokens for o in batched] == [o.tokens for o in serial], \
        "batched greedy output diverged from serial"
    report = {
        "n_requests": len(prompts),
        "gen_tokens": res_b["gen_tokens"],
        "engine_steps_batched": res_b["engine_steps"],
        "engine_steps_serial": res_s["engine_steps"],
        "batched_equals_serial": True,
    }
    with open(out_json, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {out_json}")
    rows.append(emit("serving.batched_equals_serial", 0,
                     f"steps_batched={res_b['engine_steps']};"
                     f"steps_serial={res_s['engine_steps']}"))
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", required=True,
                    help="8 requests through the scheduler + identity "
                         "check vs serial (the bench's one run)")
    ap.add_argument("--out", default="BENCH_serving_smoke.json")
    args = ap.parse_args()
    main(out_json=args.out)
