"""End-to-end driver: federated fine-tuning of a (reduced) assigned
architecture with the pod engine — a few hundred FedADC rounds of a ~100M
LM on synthetic domain-skewed token data, with checkpointing.

This is the same `make_train_step` program the multi-pod dry-run lowers for
the 256/512-chip meshes; here it runs on the host mesh end-to-end.

Run:  PYTHONPATH=src python examples/pod_finetune.py [--arch qwen3-4b]
      [--rounds 200]
"""
import argparse
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpointing import save_checkpoint
from repro.configs import get_arch
from repro.configs.base import FedConfig, RunConfig
from repro.data.synthetic import make_token_dataset
from repro.launch.compile_cache import use_compile_cache
from repro.launch.train import init_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--rounds", type=int, default=150)
    ap.add_argument("--ckpt-dir", default="/tmp/fedadc_ckpt")
    ap.add_argument("--full", action="store_true",
                    help="~100M-param variant (slow on CPU; the dry-run "
                         "exercises the full-size configs)")
    args = ap.parse_args()
    use_compile_cache()

    base = get_arch(args.arch).reduced()
    if args.full:   # ~100M params
        mcfg = replace(base, n_layers=4, d_model=512, d_ff=1408,
                       vocab_size=2048, n_heads=8, n_kv_heads=4, head_dim=64)
    else:           # CPU-friendly demo (~8M params)
        mcfg = replace(base, n_layers=2, d_model=256, d_ff=704,
                       vocab_size=1024, n_heads=4, n_kv_heads=2, head_dim=64)
    fed = FedConfig(strategy="fedadc", variant="nesterov", local_steps=4,
                    clients_per_round=4, eta=0.02, beta_global=0.7,
                    beta_local=0.7)
    run = RunConfig(remat="none")

    seq, n_docs = 128 if args.full else 64, 512
    tokens, domains = make_token_dataset(n_docs, seq + 1, mcfg.vocab_size,
                                         seed=0)
    # non-iid: each client holds one domain's documents
    clients = [np.where(domains == d % 10)[0] for d in range(8)]

    state = init_state(jax.random.PRNGKey(0), mcfg, fed, run)
    step = jax.jit(make_train_step(mcfg, fed, run))
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"{args.arch}-reduced: {n_params/1e6:.1f}M params, "
          f"{fed.clients_per_round} clients × H={fed.local_steps}")

    rng = np.random.RandomState(0)
    b = 4 if args.full else 2
    t0 = time.time()
    for r in range(args.rounds):
        picks = rng.choice(len(clients), fed.clients_per_round, replace=False)
        batch_tok = np.zeros((1, fed.clients_per_round, fed.local_steps, b,
                              seq + 1), np.int32)
        for ci, c in enumerate(picks):
            sel = rng.choice(clients[c], (fed.local_steps, b))
            batch_tok[0, ci] = tokens[sel]
        batch = {"tokens": jnp.asarray(batch_tok[..., :-1]),
                 "labels": jnp.asarray(batch_tok[..., 1:])}
        state, metrics = step(state, batch)
        if (r + 1) % 25 == 0:
            print(f"round {r+1:4d}  loss {float(metrics['loss']):.4f}  "
                  f"({(time.time()-t0)/(r+1):.2f}s/round)")
    path = save_checkpoint(args.ckpt_dir, args.rounds, state["params"])
    print(f"saved {path}")


if __name__ == "__main__":
    main()
