"""One run of one cell: set-up, the first rounds, the measured window, the
trace, and the comparison with the plain reference.

Set-up builds one object, the compiled round (``launch.train``'s
``train_step``, jitted with its state donated) and its state, with the
weights made on the device from the seed.  It drives that object through
the first rounds, which the reference follows, and hands it to the
window.  The window runs whole rounds, each on fresh tokens, keeping one
round queued behind the one that runs, and ends at the first round
boundary after ``seconds``.  After the window the program's state is
freed and the reference replays the first rounds in float32 from the same
seed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare as C
from bench import trace as T
from bench.registry import BenchmarkError
from bench.tokens import round_tokens

CHECK_ROUNDS = 3           # rounds the reference follows
COMPILE_EVENTS = ("/jax/core/compile", "/jax/compilation_cache")
CACHE_WRITE_EVENT = "/jax/compilation_cache/cache_misses"


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def seed_key(seed: int):
    """A PRNG key from every bit of a non-negative seed of any size."""
    key = jax.random.PRNGKey(seed % 2**31)
    rest = seed >> 31
    while rest:
        key = jax.random.fold_in(key, rest % 2**31)
        rest >>= 31
    return key


class CompileCounter:
    """Counts compilations and compile-cache lookups while it is on, and
    programs compiled and written to the persistent cache at any time."""

    def __init__(self):
        self.on = False
        self.count = 0
        self.last = None
        self.compiled = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == CACHE_WRITE_EVENT:
            self.compiled += 1
        if self.on and name.startswith(COMPILE_EVENTS):
            self.count += 1
            self.last = name

    def _duration(self, name, _secs, **_):
        self._event(name)


@dataclasses.dataclass
class Ctx:
    """What a per-layer metric's reader gets."""
    trace: dict
    config: dict
    workload: dict
    peak: dict
    chips: int
    tokens_per_s: float
    registry: object

    def flops(self, name):
        return self.registry.flops(name)


def program_configs(cell, platform, run_override=None):
    """The program's ModelConfig, FedConfig and RunConfig for a cell, its
    RunConfig fields replaced by `run_override` where given.  The Pallas
    kernels are on where the platform is a TPU."""
    from repro.configs.base import FedConfig, ModelConfig, RunConfig
    w = cell["workload"]
    rnd = w["round"]
    mcfg = ModelConfig(**cell["config_file"]["program"]["ModelConfig"])
    fed = FedConfig(**w["fed"], local_steps=rnd["local_steps"],
                    clients_per_round=rnd["clients"],
                    use_pallas=platform == "tpu")
    run = RunConfig(**dict(w["run"], **(run_override or {})))
    return mcfg, fed, run


def fed_dict(fed):
    return {"eta": fed.eta, "alpha": fed.alpha,
            "beta_global": fed.beta_global, "beta_local": fed.beta_local}


def leaf_names(tree):
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


class Cell:
    """A cell's program and reference on the devices it runs on."""

    def __init__(self, registry, name, devices, platform, wrap_step=None,
                 run_override=None):
        self.registry = registry
        self.cell = registry.cell(name)
        self.name = name
        self.workload = self.cell["workload"]
        self.config = self.cell["config_file"]
        self.model = registry.reference(self.config)
        self.sizes = self.model.sizes(self.config)
        self.mcfg, self.fed, self.run = program_configs(self.cell, platform,
                                                        run_override)
        self.platform = platform
        self.wrap_step = wrap_step
        rnd = self.workload["round"]
        self.shape = (rnd["clients"], rnd["local_steps"], rnd["rows"],
                      rnd["seq_len"])
        self.tokens_per_round = math.prod(self.shape)
        mesh = self.config["mesh"]
        self.devices = devices[:self.cell["chips"]]
        self.mesh = None
        if len(self.devices) > 1:
            from repro.launch.mesh import make_mesh
            self.mesh = make_mesh(tuple(mesh.values()), tuple(mesh),
                                  devices=self.devices)

    # -- inputs ------------------------------------------------------------
    def host_tokens(self, seed, r):
        return round_tokens(seed, r, *self.shape, self.sizes["vocab_size"])

    def batch(self, seed, r):
        toks = self.host_tokens(seed, r)[None]
        batch = {"tokens": toks, "labels": toks}
        return jax.device_put(batch, self.batch_sharding)

    def make_params(self, key):
        return self.model.init_params(key, self.sizes)

    # -- the program -------------------------------------------------------
    def build(self, seed):
        """-> (compiled round, state, key).  Under a mesh the caller holds
        ``jax.set_mesh(self.mesh)``."""
        from repro.configs.base import ShapeConfig
        from repro.core.strategies import get_strategy
        from repro.launch import inputs as I
        from repro.launch.train import make_train_step, state_shapes
        mcfg, fed, run = self.mcfg, self.fed, self.run
        strategy = get_strategy(fed.strategy)
        expected = state_shapes(mcfg, fed, run)

        def make_state(key):
            params = self.make_params(key)
            return {"params": params,
                    "server": strategy.server_init(params),
                    "round": jnp.zeros((), jnp.int32)}
        got = jax.eval_shape(make_state, seed_key(0))
        if jax.tree.structure(got) != jax.tree.structure(expected) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(got), jax.tree.leaves(expected))):
            raise BenchmarkError("the reference's weight tree does not fit "
                                 "the program's state")
        if self.mesh is not None:
            state_sh = jax.tree.map(lambda s: s.sharding,
                                    I.state_inputs(mcfg, fed, run, self.mesh))
            shape = ShapeConfig(self.name, seq_len=self.shape[3],
                                global_batch=self.tokens_per_round
                                // self.shape[3])
            self.batch_sharding = jax.tree.map(
                lambda s: s.sharding,
                I.train_inputs(mcfg, shape, fed, self.mesh, False))
            out_sh = {"out_shardings": (state_sh, None)}
        else:
            state_sh = jax.sharding.SingleDeviceSharding(self.devices[0])
            self.batch_sharding = state_sh
            out_sh = {}
        self._init = jax.jit(make_state, out_shardings=state_sh)
        state, key = self.new_state(seed)
        train_step = make_train_step(mcfg, fed, run)
        if self.wrap_step is not None:
            train_step = self.wrap_step(train_step, self)
        step = jax.jit(train_step, donate_argnums=(0,), **out_sh)
        compiled = step.lower(state, self.batch(seed, 0)).compile()
        return compiled, state, key

    def new_state(self, seed):
        """A fresh state for the compiled round, weights from `seed`.
        -> (state, key)."""
        key = seed_key(seed)
        return self._init(key), key

    # -- the reference -----------------------------------------------------
    def reference(self, seed, fault=None):
        """The reference's first rounds from the seed -> readings."""
        from reference import fedadc
        key = seed_key(seed)
        sh = self.reference_sharding()
        params = jax.jit(self.make_params, out_shardings=sh)(key)
        m = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=sh)(params)
        losses = []
        grad = None
        for r in range(CHECK_ROUNDS):
            toks = jnp.asarray(self.host_tokens(seed, r))
            params, m, loss = fedadc.fedadc_round(
                self.model, params, m, toks, self.sizes, fed_dict(self.fed),
                fault=fault)
            losses.append(loss)
            if r == 0:
                grad = leaf_norms(m)
        update = change_norms(params, key, self.make_params)
        return {"loss": np.asarray(jnp.stack(losses)),
                "grad": np.asarray(grad), "update": np.asarray(update),
                "names": leaf_names(params)}

    def reference_sharding(self):
        """Each leaf split over all the cell's chips on its last dimension
        where that divides, so the float32 state fits."""
        if len(self.devices) == 1:
            return jax.sharding.SingleDeviceSharding(self.devices[0])
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        flat = make_mesh((len(self.devices),), ("all",),
                         devices=self.devices)
        shapes = self.model.param_shapes(self.sizes)
        return jax.tree.map(
            lambda s: NamedSharding(flat, P(*([None] * (len(s) - 1)),
                                            "all" if s[-1] % len(
                                                self.devices) == 0
                                            else None)),
            shapes, is_leaf=lambda x: isinstance(x, tuple))


@functools.partial(jax.jit, static_argnums=(2,))
def change_norms(params, key, make_params):
    return leaf_norms(jax.tree.map(jnp.subtract, params, make_params(key)))


def first_rounds(cell, compiled, state, key, seed):
    """Drives the compiled round through the rounds the reference follows.
    -> (state, readings, seconds of the last of them)."""
    losses, grad, spent = [], None, 0.0
    for r in range(CHECK_ROUNDS):
        batch = cell.batch(seed, r)
        jax.block_until_ready(batch)
        t0 = time.perf_counter()
        state, aux = compiled(state, batch)
        jax.block_until_ready(aux)
        spent = time.perf_counter() - t0
        losses.append(aux["loss"])
        if r == 0:
            grad = leaf_norms(state["server"]["m"])
    update = change_norms(state["params"], key, cell.make_params)
    return state, {"loss": np.asarray(jnp.stack(losses)),
                   "grad": np.asarray(grad),
                   "update": np.asarray(update)}, spent


def window(cell, compiled, state, seed, seconds, batches, counter,
           annotate):
    """Whole rounds until the first round boundary after `seconds`.
    -> (state, rounds, elapsed seconds, window losses)."""
    losses = []
    pending = None
    r = 0
    counter.on = True
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    with annotate("bench.window"):
        while True:
            if r < len(batches):
                batch = batches[r]
            else:
                with annotate("bench.feed"):
                    batch = cell.batch(seed, CHECK_ROUNDS + r)
            with annotate("bench.dispatch"):
                state, aux = compiled(state, batch)
            r += 1
            losses.append(aux["loss"])
            if pending is not None:
                with annotate("bench.wait"):
                    jax.block_until_ready(pending)
                if time.perf_counter() - t0 >= seconds:
                    break
            pending = aux["loss"]
        with annotate("bench.wait"):
            jax.block_until_ready(state)
    elapsed = time.perf_counter() - t0
    counter.on = False
    return state, r, elapsed, np.asarray(jnp.stack(losses))


def log_readings(prog, ref):
    """Each leaf's norms, program against reference, and the losses."""
    log("loss program " + " ".join(f"{x:.6f}" for x in prog["loss"])
        + " reference " + " ".join(f"{x:.6f}" for x in ref["loss"]))
    for i, name in enumerate(ref["names"]):
        log(f"leaf {name}: grad {prog['grad'][i]:.6g} / {ref['grad'][i]:.6g}"
            f" update {prog['update'][i]:.6g} / {ref['update'][i]:.6g}")


def memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return max(peaks)


def run_cell(registry, name, seed, seconds, trace, t_start,
             require_tpu=True, wrap_step=None):
    """One run.  -> the result dict the benchmark prints (``checks`` last).
    Raises BenchmarkError where the run cannot be made."""
    devices = jax.devices()
    platform = devices[0].platform
    entry = registry.cell(name)
    if require_tpu:
        if platform != "tpu":
            raise BenchmarkError(f"no TPU: JAX's backend is {platform!r}")
        if len(devices) < entry["chips"]:
            raise BenchmarkError(f"cell {name} needs {entry['chips']} "
                                 f"chips, JAX sees {len(devices)}")
    peak = registry.peaks(devices[0].device_kind) if require_tpu else None
    counter = CompileCounter()
    cell = Cell(registry, name, devices, platform, wrap_step)
    mesh_ctx = jax.set_mesh(cell.mesh) if cell.mesh is not None \
        else contextlib.nullcontext()
    with mesh_ctx:
        compiled, state, key = cell.build(seed)
        hlo = compiled.as_text()
        if require_tpu and "tpu_custom_call" not in hlo:
            raise BenchmarkError("the compiled round holds no Mosaic kernel "
                                 "(tpu_custom_call): the kernels fell back "
                                 "to interpret mode")
        log(f"set-up: compiled at {time.perf_counter() - t_start:.1f} s")
        state, prog, t_round = first_rounds(cell, compiled, state, key, seed)
        log(f"set-up: first rounds done at "
            f"{time.perf_counter() - t_start:.1f} s, last round "
            f"{t_round:.3f} s")
        n_ahead = int(math.ceil(seconds / max(t_round, 1e-3))) + 2
        batches = [cell.batch(seed, CHECK_ROUNDS + r)
                   for r in range(n_ahead)]
        jax.block_until_ready(batches)
        setup_s = time.perf_counter() - t_start
        setup_compiles = counter.compiled
        log(f"set-up: {setup_s:.1f} s, {setup_compiles} programs compiled"
            + (" (a cold set-up: the cache lacked them)"
               if setup_compiles else " (every program from the cache)"))
        tdir = None
        if trace:
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(tdir)
            annotate = jax.profiler.TraceAnnotation
        else:
            annotate = contextlib.nullcontext
        try:
            state, rounds, elapsed, wlosses = window(
                cell, compiled, state, seed, seconds, batches, counter,
                annotate)
        finally:
            if trace:
                jax.profiler.stop_trace()
        if counter.count:
            raise BenchmarkError(f"compiled inside the window: "
                                 f"{counter.count} events, the last "
                                 f"{counter.last}")
        mem = memory_peak(cell.devices)
        del state, batches, compiled
    tokens_per_s = rounds * cell.tokens_per_round / elapsed
    log(f"window: {rounds} rounds of {cell.tokens_per_round} tokens in "
        f"{elapsed:.3f} s; last window loss {float(wlosses[-1]):.4f}")
    t_ref = time.perf_counter()
    ref = cell.reference(seed)
    jax.block_until_ready(ref["loss"])
    log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    log_readings(prog, ref)
    checks = C.compare(prog, ref, cell.workload["limits"])
    correct = C.passed(checks)
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(cell.devices), "memory_peak_bytes": int(mem)}
    if trace:
        metrics, busy, span, brk = read_trace(
            tdir, hlo, cell, peak, registry, rounds, elapsed)
        device.update(busy_s=busy, window_s=span)
    else:
        metrics = {"train_tokens_per_s": {"value": tokens_per_s,
                                          "unit": "tokens/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        brk = None
    result = {"correct": bool(correct), "attempted": rounds,
              "failed": int(np.sum(~np.isfinite(wlosses))),
              "metrics": metrics, "device": device}
    if brk is not None:
        result["breakdown"] = brk
    result["setup_compiles"] = setup_compiles
    result["checks"] = checks
    return result


def read_trace(tdir, hlo, cell, peak, registry, rounds, elapsed):
    files = sorted(Path(tdir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise BenchmarkError(f"expected one trace file, found {files}")
    tr = T.load_xplane(str(files[0]), T.hlo_kernels(hlo))
    shutil.rmtree(tdir)
    ctx = Ctx(trace=tr, config=cell.config, workload=cell.workload,
              peak=peak, chips=len(cell.devices),
              tokens_per_s=rounds * cell.tokens_per_round / T.window_s(tr),
              registry=registry)
    metrics = {}
    for m in registry.per_layer(cell.name):
        value = m["reader"].read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, T.mean_busy_s(tr), T.window_s(tr), T.breakdown(tr)
