"""The collective readers, ``collective.round_ms`` and
``collective.exposed_share``, on hand-made traces of two chips and on a
slice of a ``qwen3-14b.silo-fsdp2x2`` trace recorded on four TPU v5e
chips."""
from __future__ import annotations

import json

import pytest
from chipbench_util import CHIP_DIR

from bench import phases as P
from bench import trace as T
from bench.harness import Ctx
from bench.registry import Registry

MS = 1_000_000      # ns
CELL = "qwen3-14b.silo-fsdp2x2"
RECORDED = CHIP_DIR / "testdata" / f"{CELL}.trace.json"
# a round of 1 client x 1 step x 1 row x 1000 tokens
WORKLOAD = {"round": {"clients": 1, "local_steps": 1, "rows": 1,
                      "seq_len": 1000}}


def ctx_of(devices, tokens_per_s):
    """A 100 ms window on the given chips' ops."""
    trace = {"window": [0, 100 * MS], "host": [], "kernels": {},
             "devices": {c: {"ops": [list(o) for o in d.get("ops", ())],
                             "async": [list(o) for o in d.get("async", ())]}
                         for c, d in devices.items()}}
    return Ctx(trace=trace, config={}, workload=WORKLOAD, peak={},
               chips=len(devices), tokens_per_s=tokens_per_s,
               registry=Registry())


def read(name, ctx):
    return Registry().metric(name).read(ctx)


def two_chips():
    """Chip 0: an async all-gather 10-40 ms overlapping an all-reduce
    30-50 ms (union 40 ms), and one that starts before the window and
    ends 5 ms inside it.  Chip 1: a reduce-scatter 60-80 ms hidden behind
    a fusion until 75 ms, and one after the window."""
    return {0: {"ops": [("fusion.1", 0, 10 * MS),
                        ("all-reduce.3", 30 * MS, 50 * MS)],
                "async": [("all-gather-start.2", 10 * MS, 40 * MS),
                          ("all-gather-start.1", -20 * MS, 5 * MS)]},
            1: {"ops": [("fusion.7", 55 * MS, 75 * MS),
                        ("reduce-scatter.4", 60 * MS, 80 * MS),
                        ("all-reduce.9", 120 * MS, 130 * MS)]}}


def test_round_ms_is_the_union_per_chip_mean_over_chips_per_round():
    # 20,000 tokens a second over a 0.1 s window: 2 rounds of 1000 tokens
    ctx = ctx_of(two_chips(), tokens_per_s=20_000.0)
    # chip 0: 0-5 and 10-50 ms = 45 ms; chip 1: 60-80 ms = 20 ms
    assert read("collective.round_ms", ctx) == pytest.approx(
        (45 + 20) / 2 / 2)


def test_exposed_share_leaves_out_overlapped_compute():
    ctx = ctx_of(two_chips(), tokens_per_s=20_000.0)
    # chip 0: 0-5 ms is under fusion.1, so 10-50 ms = 40 ms exposed;
    # chip 1: 75-80 ms = 5 ms exposed; of a 100 ms window each
    assert read("collective.exposed_share", ctx) == pytest.approx(
        100.0 * (40 + 5) / 2 / 100)


@pytest.mark.parametrize("name", ["collective.round_ms",
                                  "collective.exposed_share"])
def test_collective_readers_read_nothing_without_collectives(name):
    ctx = ctx_of({0: {"ops": [("fusion.1", 0, 10 * MS)],
                      "async": [("copy-start.1", 0, 90 * MS)]},
                  1: {"ops": [("closed_call.2", 5 * MS, 15 * MS)]}},
                 tokens_per_s=20_000.0)
    assert read(name, ctx) is None


def test_collective_readers_are_listed_for_the_four_chip_cell_only():
    registry = Registry()
    for cell in ("qwen3-4b.silo", "qwen3-4b.cohort",
                 "qwen3-14b.silo-fsdp2x2"):
        names = {m["name"] for m in registry.per_layer(cell)}
        listed = {"collective.round_ms",
                  "collective.exposed_share"} <= names
        assert listed == (registry.cell(cell)["chips"] == 4), cell


def test_recorded_four_chip_trace_reads_every_metric_of_the_cell():
    tr = json.loads(RECORDED.read_text())
    tr["devices"] = {int(c): d for c, d in tr["devices"].items()}
    assert sorted(tr["devices"]) == [0, 1, 2, 3]
    registry = Registry()
    cell = registry.cell(CELL)
    ctx = Ctx(trace=tr, config=cell["config_file"],
              workload=cell["workload"], peak=registry.peaks("TPU v5 lite"),
              chips=cell["chips"], tokens_per_s=tr["tokens_per_s"],
              registry=registry)
    got = {m["name"]: m["reader"].read(ctx)
           for m in registry.per_layer(CELL)}
    round_ms = 1e3 * T.window_s(tr) / P.rounds_in_window(ctx)
    collective_ms = got.pop("collective.round_ms")
    assert 0 < collective_ms < round_ms
    # the part of a round in which only a collective runs lies inside the
    # part in which one runs
    assert got["collective.exposed_share"] / 100 * round_ms <= collective_ms
    for name, value in got.items():
        assert 0 < value < 100, (name, value)
