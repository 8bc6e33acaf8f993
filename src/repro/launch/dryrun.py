import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init).  This module is the ONLY place the 512 placeholder
# devices exist; tests and benches see the single real CPU device.

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import (ARCHS, SHAPES, get_arch, long_context_variant,
                           shape_applicable)
from repro.configs.base import FedConfig, RunConfig
from repro.launch import inputs as I
from repro.launch import roofline as R
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.serve import make_prefill_step, make_serve_step
from repro.launch.train import make_train_step


def _fed_for(shape, arch_id):
    """Round decomposition per shape: clients × H × b = global_batch."""
    return FedConfig(strategy="fedadc", clients_per_round=4, local_steps=4,
                     eta=0.05, beta_global=0.8, beta_local=0.8)


def _run_for(arch_id):
    # bf16 params for the huge archs (FL aggregation precision note in
    # DESIGN.md); fp32 otherwise.
    big = {"mistral-large-123b", "deepseek-v3-671b", "llama4-scout-17b-a16e",
           "internvl2-26b", "qwen1.5-32b"}
    return RunConfig(param_dtype="bfloat16" if arch_id in big else "float32",
                     remat="full")


def lower_one(arch_id: str, shape_name: str, multi_pod: bool,
              client_parallel: int = 1, fed=None, run=None,
              donate: bool = True, verbose: bool = True,
              serve_sharding: str = "serve", mesh_override=None,
              fsdp_over_pod: bool = False):
    """Lower + compile one (arch × shape × mesh) combination.
    Returns a result dict with the compiled program's counts."""
    shape = SHAPES[shape_name]
    mcfg = get_arch(arch_id)
    if shape_name == "long_500k":
        mcfg = long_context_variant(mcfg)
        if mcfg is None:
            return {"arch": arch_id, "shape": shape_name,
                    "multi_pod": multi_pod, "status": "skipped",
                    "reason": "no sub-quadratic decode path (DESIGN.md)"}
    fed = fed or _fed_for(shape, arch_id)
    run = run or _run_for(arch_id)
    if mesh_override is not None:
        mesh = make_mesh(mesh_override,
                         ("data", "model") if len(mesh_override) == 2
                         else ("pod", "data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            # NOTE (§Perf iteration 11, refuted): turning TP off for sub-1B
            # archs idles the model axis at this round decomposition
            # (b=16 ⇒ 1 seq per data shard already) — kept as an explicit
            # knob (tp_off) only.
            state_sds = I.state_inputs(mcfg, fed, run, mesh,
                                       fsdp_over_pod=fsdp_over_pod)
            batch_sds = I.train_inputs(mcfg, shape, fed, mesh, multi_pod)
            cp = mesh.shape.get("pod", 1) if multi_pod else client_parallel
            step = make_train_step(mcfg, fed, run, client_parallel=cp)
            out_sh = jax.tree.map(lambda s: s.sharding, state_sds)
            jitted = jax.jit(step,
                             out_shardings=(out_sh, None),
                             donate_argnums=(0,) if donate else ())
            lowered = jitted.lower(state_sds, batch_sds)
        elif shape.kind == "prefill":
            if serve_sharding == "serve":
                from dataclasses import replace as _rep
                mcfg = _rep(mcfg, moe_dispatch_axis="data")
            state_sds = I.state_inputs(mcfg, _fed_for(shape, arch_id),
                                       run, mesh, mode=serve_sharding)
            batch_sds = I.prefill_inputs(mcfg, shape, mesh, multi_pod)
            step = make_prefill_step(mcfg)
            lowered = jax.jit(step).lower(state_sds["params"], batch_sds)
        else:  # decode
            # decode is HBM-capacity-bound: TP-only (serve) sharding
            # replicates dense params over "data", which blows the budget
            # for the >30B archs — those keep the FSDP layout (§Perf
            # decode note in EXPERIMENTS.md)
            mode = serve_sharding
            if serve_sharding == "serve" and mcfg.param_count() > 30e9:
                mode = "train"
            if mode == "serve":
                from dataclasses import replace as _rep
                mcfg = _rep(mcfg, moe_dispatch_axis="data")
            state_sds = I.state_inputs(mcfg, _fed_for(shape, arch_id),
                                       run, mesh, mode=mode)
            cache_sds, tokens, cur_pos, active = I.decode_inputs(
                mcfg, shape, mesh, multi_pod)
            step = make_serve_step(mcfg)
            cache_sh = jax.tree.map(lambda s: s.sharding, cache_sds)
            jitted = jax.jit(step, out_shardings=(None, cache_sh),
                             donate_argnums=(1,) if donate else ())
            lowered = jitted.lower(state_sds["params"], cache_sds, tokens,
                                   cur_pos, active)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        if verbose:
            print(f"== {arch_id} × {shape_name} × "
                  f"{'multi' if multi_pod else 'single'}-pod ==")
            print(mem)                       # proves it fits
            ca = compiled.cost_analysis()
            print({k: v for k, v in (ca[0] if isinstance(ca, list)
                                     else ca).items()
                   if k in ("flops", "bytes accessed")})
        mf = R.model_flops_per_round(mcfg, shape, fed)
        rl = R.analyze(compiled, mesh)
        result = {
            "arch": arch_id, "shape": shape_name, "multi_pod": multi_pod,
            "status": "ok", "t_lower_s": round(t_lower, 1),
            "t_compile_s": round(t_compile, 1),
            "model_flops": mf,
            # XLA counts a while-loop (scan) body once, so a layer- or
            # step-scanned program can read above 1
            "useful_flop_frac": (mf / rl.chips) / rl.flops if rl.flops else 0,
            **rl.as_dict(),
        }
        return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="dryrun_results.jsonl")
    ap.add_argument("--serve-sharding", default="serve",
                    choices=["train", "serve"])
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    with open(args.out, "a") as f:
        for arch in archs:
            for shape in shapes:
                for mp in meshes:
                    try:
                        res = lower_one(arch, shape, mp,
                                        serve_sharding=args.serve_sharding)
                    except Exception as e:
                        traceback.print_exc()
                        res = {"arch": arch, "shape": shape, "multi_pod": mp,
                               "status": "error", "error": repr(e)[:500]}
                    print(json.dumps({k: v for k, v in res.items()
                                      if k not in ("flops", "bytes")},
                                     default=str)[:400])
                    f.write(json.dumps(res, default=str) + "\n")
                    f.flush()


if __name__ == "__main__":
    main()
