"""Chip smoke test: the FedADC pod round and the serving engine on a TPU.

Drives the system's main path through the entry points a user calls
(``launch.train.init_state`` / ``make_train_step`` and
``serving.ServingEngine``) at qwen3-4b's published widths, cut in depth
and vocabulary to one chip's share, with random weights from ``--seed``.

    python chip_smoke.py [--seed N]     # one chip: phases 1-3
    python chip_smoke.py --four-chips   # the FSDP×TP round on a 2×2 mesh

Phases (one chip, one process):

1. Kernel round — three FedADC rounds with the Pallas kernels on and the
   lossless wire, jitted with the state donated.  The compiled round
   must hold a Mosaic kernel, every loss must be finite, and round 1's
   Δθ must agree with the same round in float32 without kernels.
2. Cross-device wire — two rounds of the top-1% sparse uplink with error
   feedback over a 16-client population with explicit client ids: the
   loss and the error-feedback residual norm must be finite, and the
   residual non-zero.
3. Serve — ``ServingEngine`` answers 4 greedy requests on the round's
   parameters; request 0 alone must give the tokens it gave in the batch.

``--four-chips`` runs phase 1's round twice on a ("data", "model") = (2, 2)
mesh and twice on one device, in one process, and checks that the
parameters agree and that every sharded leaf is split four ways.

Earlier lines are set-up facts (shapes, bytes, compile and run seconds),
not measurements.  The last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any failed
check exits non-zero; no TPU exits non-zero before any work.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import replace

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.configs.base import FedConfig, RunConfig, ShapeConfig  # noqa: E402
from repro.data.synthetic import make_token_dataset  # noqa: E402
from repro.launch import inputs as I  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.train import init_state, make_train_step  # noqa: E402
from repro.serving import SchedulerConfig, ServingEngine  # noqa: E402

ARCH = "qwen3-4b"
# the cut: published widths kept, depth and vocabulary sliced to fit one
# v5e chip's 16 GB with the round's state (see the bytes printed below)
CUT = {"n_layers": 2, "vocab_size": 32768}
# phase 2 keeps an error-feedback residual for each of 16 clients, a
# parameter-sized bf16 tree each: a shallower cut holds it
CUT_WIRE = {"n_layers": 1, "vocab_size": 4096}
CLIENTS, H, B, L = 4, 2, 4, 1024      # tokens (1, CLIENTS, H, B, L) per round
ETA = 2.0
# Relative L2 distance allowed between a bf16 round's Δθ and the float32
# one's.  The mixed round trains on bf16 copies of θ: bf16 keeps 8
# significant bits, so each of the H local iterates is rounded to within
# 2^-8 of |θ|, and an update below half an ulp is lost outright; the
# forward/backward also runs on bf16 activations.  ETA is set so a round
# moves θ by ~2% of its norm, well above that resolution; at ETA = 0.5
# (0.5% of |θ|) the distance measured on a TPU v5e was 0.139, most of it
# rounding.  A wrong kernel lands at O(1).
BOUND = 0.10
PROMPT, NEW_TOKENS, N_REQUESTS = 64, 16, 4


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def check(cond, msg: str):
    if not cond:
        fail(msg)
    print(f"  ok: {msg}", flush=True)


def model_config(cut):
    return replace(get_arch(ARCH), **cut)


def fed_config(**kw):
    return FedConfig(strategy="fedadc", variant="nesterov", local_steps=H,
                     clients_per_round=CLIENTS, eta=ETA, beta_global=0.8,
                     beta_local=0.8, use_pallas=True, **kw)


def run_config(seed, compute_dtype="bfloat16"):
    return RunConfig(param_dtype="float32", compute_dtype=compute_dtype,
                     remat="full", seed=seed)


def token_batches(vocab, n_rounds, seed):
    """n_rounds batches of (1, CLIENTS, H, B, L) tokens from the seed."""
    toks, _ = make_token_dataset(n_rounds * CLIENTS * H * B, L, vocab,
                                 seed=seed)
    toks = toks.reshape(n_rounds, 1, CLIENTS, H, B, L)
    return [{"tokens": t, "labels": t} for t in toks]


def describe(mcfg, cut):
    n = mcfg.param_count()
    print(f"model {ARCH}: d_model {mcfg.d_model}, heads {mcfg.n_heads}q/"
          f"{mcfg.n_kv_heads}kv x {mcfg.resolved_head_dim}, d_ff "
          f"{mcfg.d_ff}, qk_norm {mcfg.qk_norm}, rope_theta "
          f"{mcfg.rope_theta:g}")
    full = get_arch(ARCH)
    print("reduced: " + ", ".join(f"{k} {getattr(full, k)} -> {v}"
                                  for k, v in cut.items())
          + f"; {n / 1e6:.1f}M parameters, {4 * n / 1e9:.2f} GB in fp32")


def l2(tree) -> float:
    return float(np.sqrt(sum(np.sum(np.square(np.asarray(x, np.float64)))
                             for x in jax.tree.leaves(tree))))


def host_sub(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float32)
                        - np.asarray(y, np.float32), a, b)


def memory_line(compiled) -> str:
    m = compiled.memory_analysis()
    gb = 1e9
    return (f"arguments {m.argument_size_in_bytes / gb:.2f} GB, outputs "
            f"{m.output_size_in_bytes / gb:.2f} GB, aliased "
            f"{m.alias_size_in_bytes / gb:.2f} GB, temporaries "
            f"{m.temp_size_in_bytes / gb:.2f} GB")


def timed(label, fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    print(f"  {label}: {time.perf_counter() - t0:.1f} s", flush=True)
    return out


def compile_round(step, state, batch, label):
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    print(f"  {label} compile: {time.perf_counter() - t0:.1f} s; "
          f"{memory_line(compiled)}", flush=True)
    return compiled


# ---------------------------------------------------------------------------
# phase 1: kernel round
# ---------------------------------------------------------------------------
def reference_delta(mcfg, fed, seed, batch):
    """Round 1's Δθ with the kernels off, in float32 (host arrays)."""
    run = run_config(seed, compute_dtype="float32")
    ref_fed = replace(fed, use_pallas=False)
    state = jax.jit(lambda k: init_state(k, mcfg, ref_fed, run))(
        jax.random.PRNGKey(seed))
    step = jax.jit(make_train_step(mcfg, ref_fed, run))
    compiled = compile_round(step, state, batch, "float32 reference round")
    new, aux = timed("float32 reference round run", compiled, state, batch)
    loss = float(aux["loss"])
    check(np.isfinite(loss), f"float32 reference loss {loss:.4f} is finite")
    theta0 = jax.device_get(state["params"])
    delta = host_sub(jax.device_get(new["params"]), theta0)
    return theta0, delta


def kernel_round(mcfg, seed):
    print("phase 1: kernel round", flush=True)
    fed = fed_config()
    run = run_config(seed)
    batches = [jax.device_put(b) for b in token_batches(mcfg.vocab_size, 3,
                                                        seed)]
    theta0, d_ref = reference_delta(mcfg, fed, seed, batches[0])
    state = jax.jit(lambda k: init_state(k, mcfg, fed, run))(
        jax.random.PRNGKey(seed))
    step = jax.jit(make_train_step(mcfg, fed, run), donate_argnums=(0,))
    compiled = compile_round(step, state, batches[0], "kernel round")
    check("tpu_custom_call" in compiled.as_text(),
          "the compiled round holds Mosaic kernels (tpu_custom_call)")
    for r, batch in enumerate(batches):
        state, aux = timed(f"kernel round {r + 1} run", compiled, state,
                           batch)
        loss = float(aux["loss"])
        check(np.isfinite(loss), f"round {r + 1} loss {loss:.4f} is finite")
        if r == 0:
            d_k = host_sub(jax.device_get(state["params"]), theta0)
            rel = l2(host_sub(d_k, d_ref)) / l2(d_ref)
            print(f"  |theta0| {l2(theta0):.4f}, |dtheta float32| "
                  f"{l2(d_ref):.4f}, |dtheta kernel| {l2(d_k):.4f}")
            check(rel <= BOUND, f"round 1 dtheta within relative L2 "
                                f"{rel:.4f} <= {BOUND} of float32")
    peak = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in peak:
        print(f"  peak device bytes in use: "
              f"{peak['peak_bytes_in_use'] / 1e9:.2f} GB")
    return jax.device_get(state["params"])


# ---------------------------------------------------------------------------
# phase 2: cross-device wire
# ---------------------------------------------------------------------------
def wire_round(seed):
    print("phase 2: cross-device wire (top-1% sparse uplink + error "
          "feedback, 16 clients)", flush=True)
    mcfg = model_config(CUT_WIRE)
    describe(mcfg, CUT_WIRE)
    fed = fed_config(compressor="topk", topk_frac=0.01, sparse_uplink=True,
                     error_feedback=True, n_clients=16)
    run = run_config(seed)
    state = jax.jit(lambda k: init_state(k, mcfg, fed, run))(
        jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    batches = token_batches(mcfg.vocab_size, 2, seed + 1)
    for b in batches:
        b["client_ids"] = rng.choice(fed.n_clients, CLIENTS,
                                     replace=False).astype(np.int32)[None]
    batches = [jax.device_put(b) for b in batches]
    step = jax.jit(make_train_step(mcfg, fed, run), donate_argnums=(0,))
    compiled = compile_round(step, state, batches[0], "wire round")
    ef_norm = jax.jit(lambda ef: jnp.sqrt(sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree.leaves(ef))))
    for r, batch in enumerate(batches):
        state, aux = timed(f"wire round {r + 1} run (clients "
                           f"{np.asarray(batch['client_ids'])[0].tolist()})",
                           compiled, state, batch)
        loss = float(aux["loss"])
        check(np.isfinite(loss), f"round {r + 1} loss {loss:.4f} is finite")
        norm = float(ef_norm(state["clients"]["ef"]))
        check(np.isfinite(norm) and norm > 0,
              f"round {r + 1} error-feedback residual norm {norm:.4f} is "
              f"finite and non-zero")


# ---------------------------------------------------------------------------
# phase 3: serve
# ---------------------------------------------------------------------------
def serve(mcfg, params, seed):
    print("phase 3: serve", flush=True)
    sched = SchedulerConfig(n_slots=N_REQUESTS, max_len=PROMPT + NEW_TOKENS,
                            prefill_chunk=PROMPT, page_size=16)
    prompts, _ = make_token_dataset(N_REQUESTS, PROMPT, mcfg.vocab_size,
                                    seed=seed + 2)
    engine = ServingEngine(mcfg, params, sched=sched)
    for p in prompts:
        engine.add_request(p.tolist(), max_new_tokens=NEW_TOKENS)
    t0 = time.perf_counter()
    outs = engine.run()
    print(f"  batch of {N_REQUESTS} (compile included): "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    check(len(outs) == N_REQUESTS
          and all(len(o.tokens) == NEW_TOKENS for o in outs),
          f"all {N_REQUESTS} requests finished with {NEW_TOKENS} tokens")
    alone = ServingEngine(mcfg, params, sched=sched)
    alone.add_request(prompts[0].tolist(), max_new_tokens=NEW_TOKENS)
    solo = alone.run()[0]
    print(f"  request 0 tokens: {outs[0].tokens}")
    check(solo.tokens == outs[0].tokens,
          "request 0 alone gives the tokens it gave in the batch")


# ---------------------------------------------------------------------------
# --four-chips: the FSDP×TP round against one device
# ---------------------------------------------------------------------------
def sharded_round(mcfg, seed):
    print("four chips: FSDP x TP round on a (data, model) = (2, 2) mesh",
          flush=True)
    fed = fed_config()
    run = run_config(seed)
    batches = token_batches(mcfg.vocab_size, 2, seed)
    key = jax.random.PRNGKey(seed)

    # the same two rounds on one device
    dev0 = jax.devices()[0]
    state = jax.jit(lambda k: init_state(k, mcfg, fed, run))(key)
    theta0 = jax.device_get(state["params"])
    step = jax.jit(make_train_step(mcfg, fed, run), donate_argnums=(0,))
    one = [jax.device_put(b, dev0) for b in batches]
    compiled = compile_round(step, state, one[0], "one-device round")
    for r, batch in enumerate(one):
        state, aux = timed(f"one-device round {r + 1} run", compiled,
                           state, batch)
    single = jax.device_get(state["params"])
    del state, compiled

    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    state_sds = I.state_inputs(mcfg, fed, run, mesh)
    state_sh = jax.tree.map(lambda s: s.sharding, state_sds)
    shape = ShapeConfig("smoke", seq_len=L, global_batch=CLIENTS * H * B)
    batch_sh = jax.tree.map(lambda s: s.sharding,
                            I.train_inputs(mcfg, shape, fed, mesh, False))
    # the kernels run shard by shard under the declared mesh
    with jax.set_mesh(mesh):
        state = jax.jit(lambda k: init_state(k, mcfg, fed, run),
                        out_shardings=state_sh)(key)
        step = jax.jit(make_train_step(mcfg, fed, run),
                       out_shardings=(state_sh, None), donate_argnums=(0,))
        sharded = [jax.device_put(b, batch_sh) for b in batches]
        compiled = compile_round(step, state, sharded[0], "sharded round")
        text = compiled.as_text()
        print(f"  collectives in the compiled round: all-gather "
              f"{text.count(' all-gather(') + text.count(' all-gather-start(')}"
              f", all-reduce "
              f"{text.count(' all-reduce(') + text.count(' all-reduce-start(')}"
              f"; Mosaic kernels: {text.count('tpu_custom_call')}")
        for r, batch in enumerate(sharded):
            state, aux = timed(f"sharded round {r + 1} run", compiled,
                               state, batch)
            loss = float(aux["loss"])
            check(np.isfinite(loss),
                  f"round {r + 1} loss {loss:.4f} is finite")

    n_sharded = 0
    for leaf in jax.tree.leaves(state["params"]):
        spec = leaf.sharding.spec
        split = int(np.prod([mesh.shape[a] for a in spec if a is not None]))
        if split == 1:
            continue
        n_sharded += 1
        shards = leaf.addressable_shards
        if len(shards) != 4 or any(
                int(np.prod(s.data.shape)) * split != leaf.size
                for s in shards):
            fail(f"leaf {leaf.shape} {spec}: shard shapes "
                 f"{[s.data.shape for s in shards]}")
    check(n_sharded > 0, f"{n_sharded} sharded parameter leaves each hold 4 "
                         f"addressable shards, each 1/split of the leaf")
    got = jax.device_get(state["params"])
    rel = l2(host_sub(got, single)) / l2(host_sub(single, theta0))
    check(rel <= BOUND, f"sharded and one-device parameters agree: relative "
                        f"L2 {rel:.4f} <= {BOUND} of the 2-round update")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run the FSDP×TP round on a 2×2 mesh against one "
                         "device, and no other phase")
    args = ap.parse_args()

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is "
              f"{jax.default_backend()!r})", file=sys.stderr)
        sys.exit(2)
    devices = jax.devices()
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        sys.exit(2)
    print(f"compile cache: {use_compile_cache()}")
    print(f"devices: {len(devices)} x {devices[0].device_kind}; jax "
          f"{jax.__version__}")
    mcfg = model_config(CUT)
    describe(mcfg, CUT)
    print(f"round: fedadc nesterov, {CLIENTS} clients x H={H} x b={B} x "
          f"L={L}, eta {ETA}, fp32 master / bf16 compute", flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        sharded_round(mcfg, args.seed)
    else:
        params = kernel_round(mcfg, args.seed)     # host copy
        wire_round(args.seed)
        serve(mcfg, jax.device_put(params), args.seed)
    print(f"total: {time.perf_counter() - t0:.1f} s", flush=True)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
