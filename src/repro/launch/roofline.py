"""Counts from a compiled dry-run program: FLOPs, bytes, collectives.

Per (arch × shape × mesh) the compiled program gives per-device counts:

  flops, bytes      ``cost_analysis`` (a partitioned module is the
                    per-device program, so both are per chip)
  wire_bytes        collective traffic per device, parsed from the
                    optimized HLO
  per_device_hbm    ``memory_analysis``: arguments, outputs, temporaries
                    and generated code

Collective bytes are NOT in cost_analysis: we parse the optimized HLO and,
for every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, take the result shapes and apply the ring-transfer
factor for its replica-group size g (all-reduce 2(g−1)/g, gather/scatter/
a2a (g−1)/g, permute 1).  Result shapes in partitioned HLO are already
per-shard.

These are counts, not times: how long a program takes on the chip comes
only from a run there (``benchmarks/chip``).
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    """Sum bytes over every dtype[shape] occurrence in `text` (handles
    tuple results)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclass
class CollectiveStats:
    counts: Dict[str, int]
    result_bytes: Dict[str, int]
    wire_bytes: float

    def total_result_bytes(self) -> int:
        return sum(self.result_bytes.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    rbytes: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    wire = 0.0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)", stripped)
        if not m:
            continue
        rhs = m.group(1)
        kind = None
        for c in _COLLECTIVES:
            if re.search(rf"\b{c}(-start|-done)?\(", rhs):
                kind = c
                break
        if kind is None or "-done(" in rhs:
            continue                      # count start ops once
        # result shape(s) = text before the op name
        shape_part = rhs.split(kind)[0]
        nbytes = _shape_bytes(shape_part)
        g = 1
        gm = re.search(r"replica_groups=\{?\{([\d,]+)\}", rhs)
        if gm:
            g = len(gm.group(1).split(","))
        else:
            gm2 = re.search(r"replica_groups=\[(\d+),(\d+)\]", rhs)
            if gm2:
                g = int(gm2.group(2))
        counts[kind] += 1
        rbytes[kind] += nbytes
        if g <= 1:
            factor = 0.0 if kind != "collective-permute" else 1.0
        elif kind == "all-reduce":
            factor = 2.0 * (g - 1) / g
        elif kind == "collective-permute":
            factor = 1.0
        else:
            factor = (g - 1) / g
        wire += nbytes * factor
    return CollectiveStats(counts, rbytes, wire)


@dataclass
class Roofline:
    flops: float
    bytes_accessed: float
    wire_bytes: float
    chips: int
    collectives: CollectiveStats
    per_device_hbm: float          # from memory_analysis

    def as_dict(self):
        return {
            "flops": self.flops, "bytes": self.bytes_accessed,
            "wire_bytes": self.wire_bytes,
            "per_device_hbm_gb": self.per_device_hbm / 2**30,
            "collective_counts": self.collectives.counts,
        }


def analyze(compiled, mesh) -> Roofline:
    """compiled: jax Compiled object.  Costs reported by XLA for a
    partitioned module are per-device (the module IS the per-device
    program), so counts are already per-chip."""
    chips = mesh.devices.size
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    nbytes = float(ca.get("bytes accessed", 0.0))
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    coll = parse_collectives(hlo)
    ma = compiled.memory_analysis()
    hbm = 0.0
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes"):
        hbm += float(getattr(ma, attr, 0.0) or 0.0)
    # arguments+outputs alias for donated state; report args+temps
    return Roofline(flops=flops, bytes_accessed=nbytes,
                    wire_bytes=coll.wire_bytes, chips=chips,
                    collectives=coll, per_device_hbm=hbm)


def model_flops_per_round(mcfg, shape, fed=None) -> float:
    """MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE); D = tokens
    processed per step (per round for training: 3× for fwd+bwd is already
    the 6 factor; decode processes global_batch tokens)."""
    n = mcfg.active_param_count() if mcfg.moe is not None \
        else mcfg.param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        f = 6.0 * n * tokens
        if fed is not None and fed.distill:
            f *= 4.0 / 3.0               # extra teacher forward
        return f
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    # decode: one token per sequence (attention over the cache is the
    # dominant non-param term; reported separately by the HLO count)
    return 2.0 * n * shape.global_batch
