"""Finds the benchmark's parts by name.

``BENCHMARK.json`` at the checkout's root lists the cells and metrics.
Everything that belongs to one configuration, one cell or one metric is a
file of its own under the benchmark's directory, found by its name:

* ``configs/<config>.json`` — a model configuration;
* ``workloads/<cell>.json`` — a cell's traffic: its round shape, the
  FedADC settings and the limits its correctness check holds;
* ``metrics/<metric>.py`` — a per-layer metric's reader, ``read(ctx)``;
* ``flops/<name>.py`` — a function of operations and bytes;
* ``peaks.json`` — the chips' peaks, keyed by ``device_kind``.

Adding a configuration, a cell or a metric adds files and edits none.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parents[1]


class BenchmarkError(RuntimeError):
    """A part the benchmark names is missing or does not fit."""


def _load_json(path: Path):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchmarkError(f"missing {path}") from None


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchmarkError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Registry:
    def __init__(self, chip_dir: Path = CHIP_DIR, benchmark: Path = None):
        self.dir = Path(chip_dir)
        self.benchmark_path = Path(benchmark) if benchmark is not None \
            else self.dir.parents[1] / "BENCHMARK.json"
        self.benchmark = _load_json(self.benchmark_path)

    # -- listing -----------------------------------------------------------
    def _names(self, sub: str, suffix: str):
        return sorted(p.name[:-len(suffix)]
                      for p in (self.dir / sub).glob(f"*{suffix}"))

    def config_names(self):
        return self._names("configs", ".json")

    def workload_names(self):
        return self._names("workloads", ".json")

    def metric_names(self):
        return self._names("metrics", ".py")

    # -- loading -----------------------------------------------------------
    def cell(self, name: str):
        """The cell's entry in BENCHMARK.json, with its workload file and
        configuration file under ``"workload"`` and ``"config_file"``."""
        for entry in self.benchmark["workloads"]:
            if entry["name"] == name:
                break
        else:
            raise BenchmarkError(f"no cell {name!r} in "
                                 f"{self.benchmark_path}")
        workload = _load_json(self.dir / "workloads" / f"{name}.json")
        if workload.get("config") != entry["config"]:
            raise BenchmarkError(
                f"workloads/{name}.json names config "
                f"{workload.get('config')!r}, BENCHMARK.json "
                f"{entry['config']!r}")
        return dict(entry, workload=workload,
                    config_file=self.config(entry["config"]))

    def config(self, name: str):
        return _load_json(self.dir / "configs" / f"{name}.json")

    def per_layer(self, cell: str):
        """The per-layer metrics BENCHMARK.json gives `cell`, each with its
        reader module under ``"reader"``."""
        out = []
        for m in self.benchmark["per_layer"]:
            if "workloads" in m and cell not in m["workloads"]:
                continue
            out.append(dict(m, reader=self.metric(m["name"])))
        return out

    def metric(self, name: str):
        """A per-layer metric's reader module (``metrics/<name>.py``)."""
        return _load_module(self.dir / "metrics" / f"{name}.py",
                            f"bench_metric_{name}")

    def flops(self, name: str):
        return _load_module(self.dir / "flops" / f"{name}.py",
                            f"bench_flops_{name}")

    def peaks(self, device_kind: str):
        table = _load_json(self.dir / "peaks.json")
        if device_kind not in table["chips"]:
            raise BenchmarkError(
                f"no peaks for device_kind {device_kind!r} in peaks.json "
                f"(known: {sorted(table['chips'])})")
        return table["chips"][device_kind]

    def reference(self, config: dict):
        """The configuration's plain reference module
        (``reference/<name>.py``)."""
        import importlib
        import sys
        if str(self.dir) not in sys.path:
            sys.path.insert(0, str(self.dir))
        return importlib.import_module(f"reference.{config['reference']}")
