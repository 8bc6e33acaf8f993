"""Telemetry subsystem: drift-metric math vs numpy oracles, the
tracer/counters/histogram primitives, JSONL schema + sinks, latency
summaries, and the engine contracts — disabled path bit-identical on all
three engines, enabling adds no jit retrace, and the async staleness
histogram stays bounded and resets per run()."""
import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig, HeteroConfig
from repro.data.partition import sort_and_partition
from repro.data.synthetic import make_image_dataset
from repro.federated.async_engine import AsyncFederatedSimulator
from repro.federated.simulator import FederatedSimulator, SimConfig
from repro.serving.request import RequestOutput
from repro.telemetry import (Counters, Histogram, JsonlSink, Telemetry,
                             Tracer, delta_dispersion, ef_residual_norm,
                             latency_summary, momentum_alignment,
                             request_itl, round_metrics,
                             streaming_dispersion, streaming_sq_norm,
                             update_norm, validate_event, validate_jsonl)


# ---------------------------------------------------------------------------
# drift metric math
# ---------------------------------------------------------------------------
class TestDriftMetrics:
    def _stacked(self, k=5, n=64, seed=0):
        rng = np.random.RandomState(seed)
        d = rng.randn(k, n).astype(np.float32)
        tree = {"w": jnp.asarray(d)}
        mean = {"w": jnp.asarray(d.mean(0))}
        return d, tree, mean

    def test_dispersion_zero_for_identical_deltas(self):
        d = jnp.ones((4, 16))
        out = delta_dispersion({"w": d}, {"w": d[0]})
        assert float(out) == pytest.approx(0.0, abs=1e-6)

    def test_dispersion_matches_numpy(self):
        d, tree, mean = self._stacked()
        dbar = d.mean(0)
        want = np.mean(((d - dbar) ** 2).sum(-1)) / (dbar ** 2).sum()
        got = float(delta_dispersion(tree, mean))
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_streaming_matches_stacked_uniform_weights(self):
        d, tree, mean = self._stacked(k=6)
        sq = sum(float(streaming_sq_norm({"w": jnp.asarray(row)},
                                         jnp.float32(1.0))) for row in d)
        got = float(streaming_dispersion(jnp.float32(sq), jnp.float32(6.0),
                                         mean))
        want = float(delta_dispersion(tree, mean))
        np.testing.assert_allclose(got, want, rtol=1e-4)

    def test_alignment_signs(self):
        v = {"w": jnp.asarray([1.0, 2.0, 3.0])}
        neg = {"w": jnp.asarray([-1.0, -2.0, -3.0])}
        assert float(momentum_alignment(v, v)) == pytest.approx(1.0, abs=1e-5)
        assert float(momentum_alignment(v, neg)) == pytest.approx(-1.0,
                                                                  abs=1e-5)

    def test_ef_residual_and_update_norm(self):
        efs = {"w": jnp.asarray([[3.0, 4.0], [0.0, 0.0]])}  # norms 5, 0
        assert float(ef_residual_norm(efs)) == pytest.approx(2.5, abs=1e-5)
        assert float(update_norm({"w": jnp.asarray([3.0, 4.0])})) == \
            pytest.approx(5.0, abs=1e-5)

    def test_round_metrics_keys_are_static(self):
        d, tree, mean = self._stacked(k=3)
        base = round_metrics(tree, mean)
        assert set(base) == {"delta_dispersion", "update_norm"}
        full = round_metrics(tree, mean, momentum=mean,
                             efs={"w": jnp.ones((3, 64))})
        assert set(full) == {"delta_dispersion", "update_norm",
                             "momentum_alignment", "ef_residual_norm"}


# ---------------------------------------------------------------------------
# tracer / counters / histogram primitives
# ---------------------------------------------------------------------------
class TestTracer:
    def test_nested_span_names(self):
        tr = Tracer(enabled=True)
        with tr.span("round"):
            with tr.span("local_train"):
                pass
        s = tr.summary()
        assert set(s) == {"round", "round/local_train"}
        assert s["round"]["count"] == 1 and s["round"]["total_s"] >= 0.0
        assert {"p50_s", "p95_s"} <= set(s["round"])
        assert len(tr.timings("round/local_train")) == 1

    def test_disabled_tracer_records_nothing(self):
        tr = Tracer(enabled=False)
        with tr.span("round"):
            pass
        assert tr.timings("round") == [] and tr.summary() == {}

    def test_spans_are_profiler_trace_annotations(self, tmp_path):
        tr = Tracer(enabled=True)
        off = Tracer(enabled=False)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tr.span("round"):
                with tr.span("local_train"):
                    pass
            with off.span("hidden"):
                pass
        finally:
            jax.profiler.stop_trace()
        path, = tmp_path.rglob("*.xplane.pb")
        names = {e.name for plane in
                 jax.profiler.ProfileData.from_file(str(path)).planes
                 for line in plane.lines for e in line.events}
        assert {"round", "round/local_train"} <= names
        assert "hidden" not in names
        assert off.span("x") is off.span("y")     # the shared no-op span

    def test_bounded_reservoir_exact_count(self):
        tr = Tracer(enabled=True, maxlen=8)
        for _ in range(50):
            with tr.span("x"):
                pass
        assert len(tr.timings("x")) == 8      # reservoir bounded
        assert tr.summary()["x"]["count"] == 50   # count stays exact


class TestCounters:
    def test_int_arithmetic_stays_int(self):
        c = Counters()
        c.inc("bytes", 3)
        c.inc("bytes", 4)
        assert c.get("bytes") == 7 and isinstance(c.get("bytes"), int)
        assert c.get("missing") == 0
        c.set("gauge", 2.5)
        assert c.snapshot() == {"bytes": 7, "gauge": 2.5}
        assert "bytes" in c and "nope" not in c


class TestHistogram:
    def test_bounded_with_overflow_and_exact_moments(self):
        h = Histogram(n_bins=4)
        h.observe_many([0, 1, 2, 3, 9])     # 9 lands in overflow
        assert h.count == 5 and h.overflow == 1
        assert h.max == 9 and h.total == 15
        assert h.mean() == pytest.approx(3.0)
        d = h.to_dict()
        assert d["count"] == 5 and d["overflow"] == 1

    def test_reset_and_negative_rejection(self):
        h = Histogram()
        h.observe(2)
        h.reset()
        assert h.count == 0 and h.max == 0 and h.mean() == 0.0
        with pytest.raises(ValueError):
            h.observe(-1)


# ---------------------------------------------------------------------------
# schema + sinks + exporters
# ---------------------------------------------------------------------------
class TestSchema:
    def _round(self):
        return {"ts": 1.0, "kind": "round", "engine": "sim",
                "round": 3, "metrics": {"loss": 0.5}}

    def test_valid_events(self):
        validate_event(self._round())
        validate_event({"ts": 1.0, "kind": "request", "engine": "serving",
                        "rid": 0, "n_tokens": 1, "ttft_s": 0.1,
                        "itl_s": None, "e2e_s": 0.1})  # itl_s nullable

    def test_unknown_kind_rejected(self):
        ev = self._round()
        ev["kind"] = "mystery"
        with pytest.raises(ValueError, match="kind"):
            validate_event(ev)

    def test_missing_field_rejected(self):
        ev = self._round()
        del ev["metrics"]
        with pytest.raises(ValueError):
            validate_event(ev)

    def test_bool_is_not_a_number(self):
        ev = {"ts": 1.0, "kind": "eval", "engine": "sim", "round": 1,
              "acc": True, "loss": 0.1}
        with pytest.raises(ValueError):
            validate_event(ev)

    def test_validate_jsonl(self, tmp_path):
        p = tmp_path / "t.jsonl"
        p.write_text(json.dumps(self._round()) + "\n")
        assert validate_jsonl(str(p)) == 1
        (tmp_path / "e.jsonl").write_text("")
        with pytest.raises(ValueError):
            validate_jsonl(str(tmp_path / "e.jsonl"))


class TestJsonlSink:
    def test_owned_path_roundtrip(self, tmp_path):
        p = tmp_path / "s.jsonl"
        with JsonlSink(str(p)) as sink:
            sink.emit({"ts": 0.0, "kind": "summary", "engine": "sim",
                        "counters": {"rounds": 1}})
        assert sink.n_events == 1 and validate_jsonl(str(p)) == 1

    def test_borrowed_object_not_closed(self):
        buf = io.StringIO()
        sink = JsonlSink(buf)
        sink.emit({"ts": 0.0, "kind": "summary", "engine": "x",
                    "counters": {}})
        sink.close()
        assert not buf.closed and buf.getvalue().count("\n") == 1

    def test_invalid_event_raises(self):
        with pytest.raises(ValueError):
            JsonlSink(io.StringIO()).emit({"kind": "round"})


# ---------------------------------------------------------------------------
# latency summaries (satellite a)
# ---------------------------------------------------------------------------
def _out(rid, arrival, first, finish, n_tokens):
    return RequestOutput(rid, [1], list(range(n_tokens)), arrival, first,
                         finish)


class TestLatency:
    def test_summary_on_synthetic_timestamps(self):
        # TTFTs 0.1..1.0 and e2e 0.2..2.0 over 10 requests: nearest-rank
        # p50 takes sorted index int(0.5*10) = 5, p95 the last value.
        outs = [_out(i, 0.0, 0.1 * (i + 1), 0.2 * (i + 1), 5)
                for i in range(10)]
        s = latency_summary(outs)
        assert s["n_requests"] == 10 and s["n_tokens"] == 50
        assert s["ttft_s"]["p50"] == pytest.approx(0.6)
        assert s["ttft_s"]["p95"] == pytest.approx(1.0)
        assert s["ttft_s"]["mean"] == pytest.approx(0.55)
        assert s["e2e_s"]["p50"] == pytest.approx(1.2)
        assert s["e2e_s"]["p95"] == pytest.approx(2.0)
        # ITL = (finish - first)/(n-1) per request
        want_itl = sorted((0.1 * (i + 1)) / 4 for i in range(10))
        assert s["itl_s"]["p50"] == pytest.approx(want_itl[5])
        assert s["n_itl_requests"] == 10

    def test_itl_none_for_single_token(self):
        single = _out(0, 0.0, 0.1, 0.1, 1)
        assert request_itl(single) is None and single.itl is None
        multi = _out(1, 0.0, 0.1, 0.5, 5)
        assert multi.itl == pytest.approx(0.1)
        s = latency_summary([single, multi])
        assert s["n_itl_requests"] == 1 and s["itl_s"] is not None

    def test_all_single_token_gives_null_itl(self):
        s = latency_summary([_out(0, 0.0, 0.1, 0.1, 1)])
        assert s["itl_s"] is None

    def test_empty_raises(self):
        with pytest.raises(ValueError):
            latency_summary([])


# ---------------------------------------------------------------------------
# Telemetry facade
# ---------------------------------------------------------------------------
class TestTelemetryFacade:
    def test_disabled_is_inert_but_history_lives(self):
        tel = Telemetry.disabled("sim")
        tel.record_round(0, {"loss": 1.0})
        tel.record_eval({"round": 1, "acc": 0.5, "loss": 1.0})
        assert len(tel.drift_curve) == 0 and tel.counters.snapshot() == {}
        assert tel.history == [{"round": 1, "acc": 0.5, "loss": 1.0}]

    def test_enabled_records_rounds(self):
        tel = Telemetry(engine="sim")
        tel.record_round(0, {"loss": 1.0, "delta_dispersion": 0.2})
        assert tel.counters.get("rounds") == 1
        assert tel.drift_curve[0]["delta_dispersion"] == pytest.approx(0.2)
        d = tel.drift_summary()
        assert d["delta_dispersion"] == {"first": 0.2, "last": 0.2}

    def test_jsonl_requires_enabled(self):
        with pytest.raises(ValueError):
            Telemetry(enabled=False, jsonl=io.StringIO())

    def test_emit_stream_is_schema_valid(self, tmp_path):
        p = tmp_path / "t.jsonl"
        tel = Telemetry(jsonl=str(p), engine="sim")
        tel.record_round(0, {"loss": 0.3})
        tel.record_eval({"round": 1, "acc": 0.1, "loss": 0.3})
        tel.emit_summary()
        tel.close()
        assert validate_jsonl(str(p)) == 3


# ---------------------------------------------------------------------------
# engine contracts (satellites b + c)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def data():
    x, y, xt, yt = make_image_dataset(600, 150, 10, image_size=16, seed=0,
                                      noise=0.5)
    parts = sort_and_partition(y, 10, s=2, seed=0)
    return x, y, xt, yt, parts


def _fed(**kw):
    base = dict(strategy="fedadc", local_steps=2, clients_per_round=3,
                n_clients=10, eta=0.03, beta_global=0.6, beta_local=0.6)
    base.update(kw)
    return FedConfig(**base)


def _simcfg(rounds=3):
    return SimConfig(model="cnn", n_classes=10, batch_size=16, rounds=rounds,
                     eval_every=rounds, cnn_width=8, seed=1)


def _leaves_equal(a, b):
    return all(bool(jnp.all(x == y)) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


class TestEngineContracts:
    def test_sync_disabled_bit_identical_and_no_retrace(self, data):
        x, y, xt, yt, parts = data
        off = FederatedSimulator(_fed(), _simcfg(), x, y, xt, yt, parts)
        h_off = off.run()
        tel = Telemetry(engine="sim")
        on = FederatedSimulator(_fed(), _simcfg(), x, y, xt, yt, parts,
                                telemetry=tel)
        h_on = on.run()
        assert _leaves_equal(off.params, on.params)
        assert [e["acc"] for e in h_off] == [e["acc"] for e in h_on]
        # enabling telemetry costs exactly one trace of the round function
        assert on._round_fn._cache_size() == 1
        assert off._round_fn._cache_size() == 1
        # drift diagnostics recorded every round, momentum metric present
        assert len(tel.drift_curve) == 3
        assert {"delta_dispersion", "momentum_alignment", "update_norm",
                "loss"} <= set(tel.drift_curve[0])

    def test_sync_ef_metrics_present(self, data):
        x, y, xt, yt, parts = data
        tel = Telemetry(engine="sim")
        FederatedSimulator(_fed(compressor="topk", topk_frac=0.1,
                                error_feedback=True),
                           _simcfg(), x, y, xt, yt, parts,
                           telemetry=tel).run()
        assert "ef_residual_norm" in tel.drift_curve[0]

    def test_async_disabled_bit_identical(self, data):
        x, y, xt, yt, parts = data
        hetero = HeteroConfig(enabled=True, speed_dist="bimodal",
                              straggler_frac=0.3, straggler_slowdown=3.0)
        fed = _fed(clients_per_round=4, buffer_k=2)
        off = AsyncFederatedSimulator(fed, _simcfg(), hetero, x, y, xt, yt,
                                      parts)
        off.run()
        tel = Telemetry(engine="async")
        on = AsyncFederatedSimulator(fed, _simcfg(), hetero, x, y, xt, yt,
                                     parts, telemetry=tel)
        on.run()
        assert _leaves_equal(off.params, on.params)
        assert len(tel.drift_curve) > 0
        assert {"delta_dispersion", "staleness_mean",
                "staleness_max"} <= set(tel.drift_curve[0])

    def test_async_staleness_hist_resets_per_run(self, data):
        """Regression: the old unbounded ``staleness_seen`` list kept
        growing across consecutive run() calls, double-counting every
        earlier round's staleness in the second run's summary."""
        x, y, xt, yt, parts = data
        e = AsyncFederatedSimulator(_fed(clients_per_round=4, buffer_k=2),
                                    _simcfg(), HeteroConfig(), x, y, xt, yt,
                                    parts)
        e.run()
        first = e.staleness_hist.to_dict()
        assert first["count"] > 0
        # run() counts cumulative server versions: ask for 3 more updates.
        # Each run applies 3 updates of K=2 flushes, so both observe the
        # same number of staleness values — without the per-run reset the
        # histogram would report double.
        e.run(rounds=6)
        assert e.version == 6
        assert e.staleness_hist.to_dict()["count"] == first["count"]

    def test_pod_disabled_aux_and_bit_identity(self):
        from repro.configs import ARCHS
        from repro.configs.base import RunConfig
        from repro.launch.mesh import make_host_mesh
        from repro.launch.train import init_state, make_train_step
        mcfg = ARCHS["qwen3-4b"].reduced()
        fed = FedConfig(strategy="fedadc", clients_per_round=2,
                        local_steps=2, eta=0.05)
        run = RunConfig(remat="none", param_dtype="float32",
                        compute_dtype="float32")
        mesh = make_host_mesh()
        with mesh:
            state = init_state(jax.random.PRNGKey(0), mcfg, fed, run)
            rng = np.random.RandomState(0)
            toks = rng.randint(0, mcfg.vocab_size, size=(1, 2, 2, 2, 16))
            batch = {"tokens": jnp.asarray(toks, jnp.int32),
                     "labels": jnp.asarray(toks, jnp.int32)}
            s_off, aux_off = make_train_step(mcfg, fed, run)(state, batch)
            assert set(aux_off) == {"loss"}    # disabled: no extra outputs
            tel = Telemetry(engine="pod")
            s_on, aux_on = make_train_step(mcfg, fed, run,
                                           telemetry=tel)(state, batch)
            assert _leaves_equal(s_off["params"], s_on["params"])
            assert _leaves_equal(s_off["server"], s_on["server"])
            m = aux_on["telemetry"]
            assert {"delta_dispersion", "update_norm",
                    "momentum_alignment"} <= set(m)
            assert all(bool(jnp.isfinite(v)) for v in m.values())
