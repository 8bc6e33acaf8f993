"""Layer 2 — trace-time audits (shaped zeros and synthetic shapes only; no
real data is read anywhere).

Three audits, each returning ``Finding``s on the same envelope as the AST
rules so one baseline and one CLI cover both layers:

* **retrace** (``trace-retrace``) — build each round engine at a tiny
  synthetic size, run two identically-shaped rounds, and assert the jit
  cache holds exactly one trace per compiled function
  (``_cache_size()``).  A second trace means a config object or weak type
  leaked into the traced signature — the PR 6 retrace contract, checked
  across an engine × codec matrix instead of two hand-written tests.
* **accumulation dtype** (``trace-accumulation-dtype``) —
  ``jax.make_jaxpr`` over the weighted reductions (``ref`` oracle + Pallas
  wrapper), the pod engine's client-serial scan, and the FedADC momentum
  update, then walk every eqn (recursing into scan/pjit/cond/pallas_call
  sub-jaxprs) and flag reductions that consume AND produce below-fp32
  floats, scans-of-scans whose outer (aggregation) carry holds no ≥fp32
  accumulator, and momentum leaves carried below fp32 — the PR 5 fp32
  cast-on-write contract, proven on the jaxpr rather than sampled by
  parity tests.
* **kernel coverage** (``trace-kernel-coverage``) — every Pallas-backed
  export in ``kernels/ops.py`` (identified by its ``interpret=`` lowering
  switch) must have a ``ref.py`` oracle and a parity test in
  ``tests/test_kernels.py``.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, List, Sequence, Set

import numpy as np

from repro.analysis.findings import Finding

# --------------------------------------------------------------------------
# shared: jaxpr walking
# --------------------------------------------------------------------------

_LOW_FLOATS = ("bfloat16", "float16")
# eqn primitives that accumulate across elements (a low-precision output
# here means the accumulator itself is low-precision)
_REDUCE_PRIMS = {"reduce_sum", "add_any", "cumsum", "dot_general",
                 "scatter-add", "segment_sum"}


def _is_low_float(dtype) -> bool:
    s = str(dtype)
    return s in _LOW_FLOATS or s.startswith("float8")


def _sub_jaxprs(eqn):
    """Sub-jaxprs of one eqn (scan/while/cond/pjit/remat/pallas_call...)."""
    out = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for item in vs:
            if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                out.append(item.jaxpr)          # ClosedJaxpr
            elif hasattr(item, "eqns"):
                out.append(item)                # Jaxpr
    return out


def _float_dtypes(vars_):
    out = []
    for v in vars_:
        aval = getattr(v, "aval", None)
        dt = getattr(aval, "dtype", None)
        if dt is not None and np.issubdtype(dt, np.floating):
            out.append(dt)
    return out


def walk_jaxpr_reductions(jaxpr, where: str) -> List[str]:
    """One violation string per reduction eqn whose output stays below fp32
    while consuming float inputs (integer reductions are fine)."""
    violations: List[str] = []

    def visit(jx):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in _REDUCE_PRIMS:
                ins = _float_dtypes(eqn.invars)
                outs = _float_dtypes(eqn.outvars)
                if ins and outs and any(_is_low_float(d) for d in ins) \
                        and all(_is_low_float(d) for d in outs):
                    violations.append(
                        f"{where}: `{name}` accumulates in {outs[0]} "
                        f"(inputs {[str(d) for d in ins]})")
            for sub in _sub_jaxprs(eqn):
                visit(sub)

    visit(jaxpr)
    return violations


def scan_carry_dtype_violations(jaxpr, where: str,
                                min_size: int = 2) -> List[str]:
    """For scans *containing* another scan (the client-serial aggregation
    loop wraps the local-training loop), the outer carry must hold at least
    one ≥fp32 multi-element float leaf — the Σw·Δ accumulator.  An
    all-low-precision outer carry means the fp32 cast-on-write contract
    regressed."""
    violations: List[str] = []

    def has_scan(jx) -> bool:
        for eqn in jx.eqns:
            if eqn.primitive.name == "scan":
                return True
            if any(has_scan(sub) for sub in _sub_jaxprs(eqn)):
                return True
        return False

    def visit(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "scan":
                body = eqn.params["jaxpr"].jaxpr
                if has_scan(body):
                    num_carry = eqn.params["num_carry"]
                    carry = [getattr(v, "aval", None)
                             for v in body.invars[:num_carry]]
                    big = [a for a in carry
                           if a is not None
                           and getattr(a, "size", 0) >= min_size
                           and hasattr(a, "dtype")
                           and np.issubdtype(a.dtype, np.floating)]
                    if big and all(_is_low_float(a.dtype) for a in big):
                        violations.append(
                            f"{where}: outer (client-serial) scan carry "
                            f"holds no ≥fp32 accumulator leaf (float "
                            f"carries: "
                            f"{[f'{a.dtype}{a.shape}' for a in big[:4]]})")
            for sub in _sub_jaxprs(eqn):
                visit(sub)

    visit(jaxpr)
    return violations


# --------------------------------------------------------------------------
# findings plumbing + tiny synthetic fixtures
# --------------------------------------------------------------------------

def _finding(rule: str, path: str, message: str, context: str) -> Finding:
    return Finding(rule=rule, path=path, line=1, message=message,
                   context=context, snippet=f"<trace:{context}>")


_IMG = 16          # must survive the CNN's 4 pools (image_size // 16 >= 1)
_NCLASS = 4


def _synthetic_dataset(n: int = 48):
    x = np.zeros((n, _IMG, _IMG, 3), np.float32)
    y = (np.arange(n) % _NCLASS).astype(np.int32)
    return x, y


def _partitions(n: int, n_clients: int) -> List[np.ndarray]:
    return [np.arange(i, n, n_clients) for i in range(n_clients)]


def _sim_config():
    from repro.federated.simulator import SimConfig
    return SimConfig(rounds=2, n_classes=_NCLASS, batch_size=4,
                     eval_every=100, eval_batch=8, cnn_width=4, seed=0)


def _build_sync(fed_kwargs: Dict):
    from repro.configs.base import FedConfig
    from repro.federated.simulator import FederatedSimulator

    fed = FedConfig(strategy="fedadc", local_steps=2, clients_per_round=4,
                    n_clients=8, **fed_kwargs)
    x, y = _synthetic_dataset()
    return FederatedSimulator(fed, _sim_config(), x, y, x[:8], y[:8],
                              _partitions(len(x), fed.n_clients))


def _build_async(fed_kwargs: Dict):
    from repro.configs.base import FedConfig, HeteroConfig
    from repro.federated.async_engine import AsyncFederatedSimulator

    fed = FedConfig(strategy="fedadc", local_steps=2, clients_per_round=4,
                    n_clients=8, buffer_k=2, **fed_kwargs)
    hetero = HeteroConfig(enabled=True, speed_dist="uniform",
                          speed_range=(0.5, 1.0), seed=0)
    x, y = _synthetic_dataset()
    return AsyncFederatedSimulator(fed, _sim_config(), hetero, x, y,
                                   x[:8], y[:8],
                                   _partitions(len(x), fed.n_clients))


def _pod_configs():
    from repro.configs import ARCHS
    from repro.configs.base import FedConfig, RunConfig

    mcfg = ARCHS["qwen3-4b"].reduced()
    fed = FedConfig(strategy="fedadc", clients_per_round=2, local_steps=2,
                    eta=0.05)
    run = RunConfig(remat="none", param_dtype="float32",
                    compute_dtype="bfloat16")
    return mcfg, fed, run


# --------------------------------------------------------------------------
# audit: accumulation dtype
# --------------------------------------------------------------------------

def audit_accumulation_dtype() -> List[Finding]:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    findings: List[Finding] = []
    K, D = 8, 16
    deltas = jax.ShapeDtypeStruct((K, D), jnp.bfloat16)
    weights = jax.ShapeDtypeStruct((K,), jnp.float32)

    # 1. the weighted reduction: oracle and Pallas wrapper on a bf16 stack
    for name, fn, path in (
            ("ref.weighted_delta_reduce", ref.weighted_delta_reduce,
             "src/repro/kernels/ref.py"),
            ("ops.weighted_delta_reduce", ops.weighted_delta_reduce,
             "src/repro/kernels/ops.py")):
        jaxpr = jax.make_jaxpr(fn)(deltas, weights).jaxpr
        for v in walk_jaxpr_reductions(jaxpr, name):
            findings.append(_finding("trace-accumulation-dtype", path, v,
                                     name))

    # 1b. the sparse scatter-accumulate aggregate on a bf16 wire: the
    # segment-sum / scatter-add must accumulate ≥fp32 even when the leaf
    # dtype (the final cast-on-write target) is bf16
    kk = 8
    svals = jax.ShapeDtypeStruct((K, kk), jnp.bfloat16)
    sidx = jax.ShapeDtypeStruct((K, kk), jnp.int32)
    for name, fn, path in (
            ("ref.sparse_weighted_delta_reduce",
             ref.sparse_weighted_delta_reduce,
             "src/repro/kernels/ref.py"),
            ("ops.sparse_weighted_delta_reduce",
             ops.sparse_weighted_delta_reduce,
             "src/repro/kernels/ops.py")):
        jaxpr = jax.make_jaxpr(
            lambda v, i, w, f=fn: f(v, i, w, (D,), jnp.bfloat16))(
                svals, sidx, weights).jaxpr
        for v in walk_jaxpr_reductions(jaxpr, name):
            findings.append(_finding("trace-accumulation-dtype", path, v,
                                     name))

    # 2. the FedADC momentum recursion, in both wire regimes: the momentum
    # leaves must come back ≥fp32 (a bf16 m accumulates Δ̄ across rounds in
    # bf16 — the PR 5 class on the server side) and no reduction inside the
    # update may accumulate low
    from repro.configs.base import FedConfig
    from repro.core.strategies import get_strategy

    fed = FedConfig(strategy="fedadc")
    strat = get_strategy(fed.strategy)
    for regime, pdt in (("fp32-params", jnp.float32),
                        ("bf16-params", jnp.bfloat16)):
        params = {"w": jax.ShapeDtypeStruct((16,), pdt)}
        mean_delta = {"w": jax.ShapeDtypeStruct((16,), pdt)}
        server_state = jax.eval_shape(strat.server_init, params)

        def upd(ss, p, md):
            return strat.server_update(ss, p, md, fed)

        jaxpr = jax.make_jaxpr(upd)(server_state, params, mean_delta).jaxpr
        ctxname = f"fedadc.server_update[{regime}]"
        for v in walk_jaxpr_reductions(jaxpr, ctxname):
            findings.append(_finding(
                "trace-accumulation-dtype", "src/repro/core/strategies.py",
                v, ctxname))
        theta_out, ss_out = jax.eval_shape(upd, server_state, params,
                                           mean_delta)
        for leaf in jax.tree.leaves(ss_out):
            if hasattr(leaf, "dtype") and _is_low_float(leaf.dtype):
                findings.append(_finding(
                    "trace-accumulation-dtype",
                    "src/repro/core/strategies.py",
                    f"{ctxname}: server momentum leaf carried in "
                    f"{leaf.dtype} — cross-round accumulation below fp32",
                    ctxname))
        for leaf in jax.tree.leaves(theta_out):
            if leaf.dtype != pdt:
                findings.append(_finding(
                    "trace-accumulation-dtype",
                    "src/repro/core/strategies.py",
                    f"{ctxname}: θ update changed the parameter dtype to "
                    f"{leaf.dtype} (expected {pdt})", ctxname))

    # 3. the pod engine's client-serial scan under the mixed-precision
    # round: the outer (aggregation) scan carry must hold the fp32 Σw·Δ
    # accumulator even though local training runs bf16
    findings.extend(_audit_pod_scan())
    return findings


def _audit_pod_scan() -> List[Finding]:
    import jax
    from repro.configs.base import ShapeConfig
    from repro.launch import inputs as I
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import make_train_step

    findings: List[Finding] = []
    mcfg, fed, run = _pod_configs()
    shape = ShapeConfig("train_audit", seq_len=32, global_batch=8,
                        kind="train")
    try:
        mesh = make_host_mesh()
        with mesh:
            state_sds = I.state_inputs(mcfg, fed, run, mesh)
            batch_sds = I.train_inputs(mcfg, shape, fed, mesh, False)
            step = make_train_step(mcfg, fed, run)
            jaxpr = jax.make_jaxpr(step)(state_sds, batch_sds).jaxpr
    except Exception as e:                       # pragma: no cover
        return [_finding("trace-accumulation-dtype",
                         "src/repro/launch/train.py",
                         f"pod engine audit could not trace: {e!r}",
                         "pod.train_step")]
    for v in scan_carry_dtype_violations(jaxpr, "pod.train_step"):
        findings.append(_finding(
            "trace-accumulation-dtype", "src/repro/launch/train.py", v,
            "pod.train_step"))
    return findings


# --------------------------------------------------------------------------
# audit: retrace
# --------------------------------------------------------------------------

# The engine round matrix the retrace audit covers: the uplink codec
# families × the downlink codec families that exercise distinct trace
# paths.  Kept small enough for the CI job; the full bit-parity
# cross-product is the engine-parity matrix's job.
RETRACE_MATRIX = (
    ("sync", {}),
    ("sync", {"compressor": "topk", "topk_frac": 0.5,
              "error_feedback": True}),
    ("sync", {"compressor": "topk", "topk_frac": 0.5,
              "error_feedback": True, "sparse_uplink": True}),
    ("sync", {"downlink_compressor": "delta"}),
    # stateful (lossy) downlink: the sync engine's broadcast runs through
    # the ReferenceStore's jit'd bcast_fn — one trace, like the async one
    ("sync", {"downlink_compressor": "delta+qsgd", "downlink_qsgd_bits": 8}),
    ("async", {}),
    ("async", {"downlink_compressor": "delta", "compressor": "qsgd",
               "qsgd_bits": 4}),
)


def audit_retrace(matrix: Sequence = RETRACE_MATRIX,
                  include_pod: bool = True) -> List[Finding]:
    """Run two+ rounds per engine config; every jit'd round-path function
    must hold exactly one trace afterwards."""
    findings: List[Finding] = []
    for engine, fed_kwargs in matrix:
        kv = ",".join(f"{k}={v}" for k, v in sorted(fed_kwargs.items()))
        ctxname = f"{engine}:{kv or 'default'}"
        try:
            if engine == "sync":
                s = _build_sync(fed_kwargs)
                s.run(rounds=2)
                # bcast_fn only traces for the stateful (lossy) downlink —
                # a stateless config leaves its cache empty, which the ≤1
                # check accepts
                jit_fns = {"round_fn": s._round_fn,
                           "bcast_fn": (s._bcast_fn, 1)}
                path = "src/repro/federated/simulator.py"
            else:
                s = _build_async(fed_kwargs)
                s.run(rounds=2)
                # the vmapped client fn legitimately traces once per
                # DISTINCT dispatch-wave size (the initial in-flight wave
                # vs the buffered-K redispatch); one trace per shape is the
                # contract, one per *call* would be a config leak.  Wave
                # sizes are the maximal runs of consecutive dispatch
                # events sharing (time, version).
                waves, run_key = [], None
                for kind, t, _c, v in s.event_log:
                    if kind != "dispatch":
                        run_key = None
                        continue
                    if (t, v) == run_key:
                        waves[-1] += 1
                    else:
                        waves.append(1)
                        run_key = (t, v)
                jit_fns = {"deltas_fn": (s._deltas_fn, len(set(waves))),
                           "apply_fn": (s._apply_fn, 1),
                           "bcast_fn": (s._bcast_fn, 1)}
                path = "src/repro/federated/async_engine.py"
        except Exception as e:
            findings.append(_finding(
                "trace-retrace", "src/repro/analysis/trace_audit.py",
                f"engine {ctxname} failed to run: {e!r}", ctxname))
            continue
        for name, fn in jit_fns.items():
            fn, allowed = fn if isinstance(fn, tuple) else (fn, 1)
            n = fn._cache_size()
            if n > allowed:
                findings.append(_finding(
                    "trace-retrace", path,
                    f"{name} holds {n} traces after identically-shaped "
                    f"rounds ({ctxname}, {allowed} distinct input shape(s))"
                    f" — a config or weak type leaked into the traced "
                    f"signature", ctxname))
    if include_pod:
        findings.extend(_audit_pod_retrace())
    return findings


def _audit_pod_retrace() -> List[Finding]:
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import init_state, make_train_step

    findings: List[Finding] = []
    mcfg, fed, run = _pod_configs()
    try:
        with make_host_mesh():
            state = init_state(jax.random.PRNGKey(0), mcfg, fed, run)
            toks = jnp.zeros((1, 2, 2, 2, 32), jnp.int32)
            batch = {"tokens": toks, "labels": toks}
            step = jax.jit(make_train_step(mcfg, fed, run))
            state, _ = step(state, batch)
            state, _ = step(state, batch)
            n = step._cache_size()
        if n != 1:
            findings.append(_finding(
                "trace-retrace", "src/repro/launch/train.py",
                f"pod train_step holds {n} traces after 2 identical calls",
                "pod:default"))
    except Exception as e:
        findings.append(_finding(
            "trace-retrace", "src/repro/launch/train.py",
            f"pod retrace audit could not run: {e!r}", "pod:default"))
    return findings


# --------------------------------------------------------------------------
# audit: kernel coverage
# --------------------------------------------------------------------------

def _pallas_exports(ops_path: str) -> Set[str]:
    """Top-level defs in ops.py whose body threads an ``interpret=``
    lowering switch — the Pallas-backed surface."""
    with open(ops_path) as f:
        tree = ast.parse(f.read())
    out: Set[str] = set()
    for node in tree.body:
        if not isinstance(node, ast.FunctionDef):
            continue
        for n in ast.walk(node):
            if isinstance(n, ast.keyword) and n.arg == "interpret":
                out.add(node.name)
                break
    return out


# ops.py export -> the ref.py oracle name when they differ
KERNEL_ORACLE_ALIASES = {
    "qsgd_compress_leaf": "qsgd_quantize",
    "topk_compress_leaf": "topk_threshold_select",
    # flash_attention's VJP rules: its oracle's forward and VJP
    "_flash_attention_fwd": "flash_attention",
    "_flash_attention_bwd": "flash_attention",
}


def audit_kernel_coverage(root: str) -> List[Finding]:
    findings: List[Finding] = []
    ops_path = os.path.join(root, "src/repro/kernels/ops.py")
    ref_path = os.path.join(root, "src/repro/kernels/ref.py")
    test_path = os.path.join(root, "tests/test_kernels.py")
    if not (os.path.exists(ops_path) and os.path.exists(ref_path)):
        return [_finding("trace-kernel-coverage", "src/repro/kernels/ops.py",
                         "kernels/ops.py or kernels/ref.py missing",
                         "kernel-coverage")]
    with open(ref_path) as f:
        ref_names = {n.name for n in ast.parse(f.read()).body
                     if isinstance(n, ast.FunctionDef)}
    test_src = ""
    if os.path.exists(test_path):
        with open(test_path) as f:
            test_src = f.read()
    for name in sorted(_pallas_exports(ops_path)):
        oracle = KERNEL_ORACLE_ALIASES.get(name, name)
        if oracle not in ref_names:
            findings.append(_finding(
                "trace-kernel-coverage", "src/repro/kernels/ref.py",
                f"Pallas export ops.{name} has no ref.py oracle "
                f"(expected `{oracle}`)", name))
        if name not in test_src and oracle not in test_src:
            findings.append(_finding(
                "trace-kernel-coverage", "tests/test_kernels.py",
                f"Pallas export ops.{name} has no parity test in "
                f"tests/test_kernels.py", name))
    return findings


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def run_trace_audits(root: str, include_retrace: bool = True
                     ) -> List[Finding]:
    findings: List[Finding] = []
    findings.extend(audit_kernel_coverage(root))
    findings.extend(audit_accumulation_dtype())
    if include_retrace:
        findings.extend(audit_retrace())
    return findings
