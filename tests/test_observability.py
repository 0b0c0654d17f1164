"""Observability-plane test suite (ISSUE 8).

The contract under test: a live process exposes Prometheus-parseable
``/metrics`` (counters + cumulative histogram buckets + hbm gauges) plus
``/healthz``/``/readyz``; a request's trace id appears on every child span
in both export formats (mesh and streaming paths included, worker threads
included); ``device.memory_stats()`` sampling feeds the hbm gauges and the
per-program attribution in ``cache.stats()``; fatal faults and signals
produce an atomic flight-recorder dump that ``python -m flox_tpu.telemetry
report`` summarizes; and none of it changes results — the disabled path
stays a no-op.
"""

from __future__ import annotations

import json
import os
import signal
import urllib.error
import urllib.request

import numpy as np
import pytest

import flox_tpu
from flox_tpu import cache, exposition, telemetry
from flox_tpu.core import groupby_reduce
from flox_tpu.parallel import make_mesh
from flox_tpu.streaming import streaming_groupby_reduce

RNG = np.random.default_rng(7)


@pytest.fixture(autouse=True)
def _clean_plane():
    """Each test starts with telemetry OFF, empty buffers/registries, no
    flight path, and no readiness — even under the CI instrumented leg."""
    with flox_tpu.set_options(
        telemetry=False, telemetry_export_path=None, flight_recorder_path=None
    ):
        telemetry.reset()
        exposition.set_ready(False)
        yield
        telemetry.reset()
    exposition.stop_metrics_server()
    exposition.set_ready(False)


def _run_reduce(**kw):
    vals = np.random.default_rng(0).normal(size=(3, 48)).astype(np.float64)
    codes = np.arange(48) % 5
    return groupby_reduce(vals, codes, func="nanmean", engine="jax", **kw)


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------


def _parse_prometheus(text: str) -> tuple[dict, dict, dict]:
    """Minimal text-format parser: ``{metric-with-labels: value}`` samples,
    ``{metric: type}`` from the # TYPE lines, and ``{metric-with-labels:
    (labels, value)}`` for OpenMetrics-style exemplars hanging off
    ``_bucket`` lines (`` # {trace_id="..."} <value>``). Raises on anything
    that is not a comment, a blank, or a ``name{labels} value [exemplar]``
    sample — the golden-format guarantee the scrape contract rests on."""
    samples: dict[str, float] = {}
    types: dict[str, str] = {}
    exemplars: dict[str, tuple[str, float]] = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split()
            if len(parts) >= 4 and parts[1] == "TYPE":
                types[parts[2]] = parts[3]
            continue
        sample_part, sep, exemplar_part = line.partition(" # ")
        name_part, _, value_part = sample_part.rpartition(" ")
        assert name_part, f"unparseable sample line: {line!r}"
        value = float(value_part)  # raises for malformed values
        if "{" in name_part:
            assert name_part.endswith("}"), f"unclosed label set: {line!r}"
        samples[name_part] = value
        if sep:
            # exemplar syntax: `# {label="value"} observed-value`
            labels_part, _, obs_part = exemplar_part.rpartition(" ")
            assert labels_part.startswith("{") and labels_part.endswith("}"), (
                f"malformed exemplar on: {line!r}"
            )
            exemplars[name_part] = (labels_part, float(obs_part))
    return samples, types, exemplars


class TestPrometheusExposition:
    def test_golden_format(self):
        with flox_tpu.set_options(telemetry=True):
            _run_reduce()
            telemetry.METRICS.set_gauge("hbm.bytes_in_use", 12345.0)
        samples, types, _ = _parse_prometheus(exposition.prometheus_text())

        # counters carry the _total suffix and the counter TYPE
        assert types["flox_tpu_cache_bundle_calls_total"] == "counter"
        assert samples["flox_tpu_cache_bundle_calls_total"] >= 1
        # gauges are plain
        assert types["flox_tpu_hbm_bytes_in_use"] == "gauge"
        assert samples["flox_tpu_hbm_bytes_in_use"] == 12345.0
        # histograms: cumulative buckets over the shared edges + sum/count
        assert types["flox_tpu_span_ms_groupby_reduce"] == "histogram"
        buckets = [
            v for k, v in samples.items()
            if k.startswith('flox_tpu_span_ms_groupby_reduce_bucket{le="')
        ]
        assert len(buckets) == len(telemetry.HIST_EDGES_MS) + 1  # edges + +Inf
        assert buckets == sorted(buckets), "buckets must be cumulative"
        assert samples['flox_tpu_span_ms_groupby_reduce_bucket{le="+Inf"}'] == (
            samples["flox_tpu_span_ms_groupby_reduce_count"]
        )
        assert samples["flox_tpu_span_ms_groupby_reduce_sum"] > 0

    def test_name_sanitization(self):
        with flox_tpu.set_options(telemetry=True):
            telemetry.METRICS.inc("serve.weird-name.v2")
        samples, _, _ = _parse_prometheus(exposition.prometheus_text())
        assert "flox_tpu_serve_weird_name_v2_total" in samples


class TestMetricsServer:
    def _get(self, port, path):
        return urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=5)

    def test_endpoints(self):
        with flox_tpu.set_options(telemetry=True):
            _run_reduce()
        port = exposition.start_metrics_server(port=0)
        assert port and port > 0
        # idempotent: a second start reuses the live endpoint
        assert exposition.start_metrics_server(port=0) == port

        assert self._get(port, "/healthz").status == 200
        with pytest.raises(urllib.error.HTTPError) as err:
            self._get(port, "/readyz")
        assert err.value.code == 503  # not ready until warmup is replayed
        exposition.set_ready(True)
        assert self._get(port, "/readyz").status == 200
        with pytest.raises(urllib.error.HTTPError) as err:
            self._get(port, "/nope")
        assert err.value.code == 404

        resp = self._get(port, "/metrics")
        assert resp.status == 200
        assert "text/plain" in resp.headers["Content-Type"]
        samples, _, _ = _parse_prometheus(resp.read().decode())
        assert samples["flox_tpu_cache_bundle_calls_total"] >= 1

    def test_disabled_by_default_option(self):
        # OPTIONS["metrics_port"]=0 means no endpoint: the option-driven
        # start is a no-op returning None
        assert exposition.start_metrics_server() is None


# ---------------------------------------------------------------------------
# request tracing
# ---------------------------------------------------------------------------


class TestRequestTracing:
    def test_trace_id_on_every_child_span_mesh_and_streaming(self):
        mesh = make_mesh()
        n = 512
        labels = RNG.integers(0, 5, n)
        vals = RNG.normal(size=n)
        with flox_tpu.set_options(telemetry=True):
            with telemetry.trace("req-mesh-1"):
                groupby_reduce(vals, labels, func="sum", method="map-reduce", mesh=mesh)
            with telemetry.trace("req-stream-1"):
                streaming_groupby_reduce(vals, labels, func="sum", batch_len=128)
            records = telemetry.drain()

        by_trace: dict = {}
        for rec in records:
            by_trace.setdefault(rec.get("trace"), []).append(rec)
        # no record of either request escaped its trace context
        assert set(by_trace) <= {"req-mesh-1", "req-stream-1"}
        mesh_names = {r["name"] for r in by_trace["req-mesh-1"]}
        assert {"groupby_reduce", "factorize", "combine", "finalize"} <= mesh_names
        assert any(n in ("program-build", "mesh-dispatch") for n in mesh_names)
        stream_names = {r["name"] for r in by_trace["req-stream-1"]}
        assert {"streaming_groupby_reduce", "factorize", "finalize"} <= stream_names
        assert any(n.startswith("stream[") for n in stream_names)

    def test_trace_id_in_both_export_formats(self, tmp_path):
        with flox_tpu.set_options(telemetry=True):
            with telemetry.trace("req-fmt"):
                _run_reduce()
            records = telemetry.spans()
            jsonl = tmp_path / "t.jsonl"
            chrome = tmp_path / "t.json"
            telemetry.export_jsonl(str(jsonl), records)
            telemetry.export_chrome_trace(str(chrome), records)
        parsed = [json.loads(line) for line in jsonl.read_text().splitlines()]
        spans = [r for r in parsed if r.get("type") == "span"]
        assert spans and all(r["trace"] == "req-fmt" for r in spans)
        payload = json.loads(chrome.read_text())
        events = payload["traceEvents"]
        assert events and all(ev["args"].get("trace_id") == "req-fmt" for ev in events)

    def test_trace_reaches_prefetch_worker_records(self):
        # retry events fire on the prefetch workers; the stager re-binds the
        # stream's trace there, so they still carry the request's id
        from flox_tpu import faults

        n, batch = 512, 128
        labels = RNG.integers(0, 4, n)
        vals = RNG.normal(size=n)
        loader = faults.FlakyLoader(lambda s, e: vals[s:e], {batch: OSError}, times=1)
        with flox_tpu.set_options(telemetry=True, stream_retries=2, stream_backoff=0.0):
            with telemetry.trace("req-worker"):
                streaming_groupby_reduce(
                    loader, labels, func="sum", batch_len=batch
                )
            records = telemetry.drain()
        retries = [r for r in records if r["name"] == "retry"]
        assert retries, "the flaky loader must have produced a retry event"
        assert all(r.get("trace") == "req-worker" for r in retries)

    def test_tail_sampling_keeps_only_slow_traces(self):
        with flox_tpu.set_options(telemetry=True, telemetry_level="basic"):
            # seed the running distribution: a fleet of ~100ms requests, so
            # the p99 the verdict reads is ~100ms
            for _ in range(30):
                telemetry.METRICS.observe("trace_ms", 100.0)

            # a FAST trace (well under the p99): detail records dropped
            with telemetry.trace("fast-req"):
                t0 = 1.0
                telemetry.record_span("stage", t0, t0 + 0.001, detail=True)
            fast_records = telemetry.drain()
            assert not any(r["name"] == "stage" for r in fast_records)
            assert telemetry.METRICS.get("telemetry.tail_dropped") >= 1

            # a SLOW trace (blows the running p99): detail records survive,
            # tagged with the trace id
            import time as _time

            with telemetry.trace("slow-req"):
                telemetry.record_span("stage", 1.0, 1.5, detail=True)
                _time.sleep(0.25)
            slow_records = telemetry.drain()
            kept = [r for r in slow_records if r["name"] == "stage"]
            assert kept and kept[0]["trace"] == "slow-req"
            assert telemetry.METRICS.get("telemetry.tail_kept") >= 1

    def test_detailed_level_bypasses_parking(self):
        with flox_tpu.set_options(telemetry=True, telemetry_level="detailed"):
            with telemetry.trace("det-req"):
                telemetry.record_span("stage", 1.0, 1.001, detail=True)
            records = telemetry.drain()
        assert any(r["name"] == "stage" for r in records)

    def test_serve_request_id_becomes_trace(self):
        import asyncio

        from flox_tpu.serve import AggregationRequest, Dispatcher

        async def go():
            dispatcher = Dispatcher()
            req = AggregationRequest(
                func="sum",
                array=np.array([1.0, 2.0, 4.0, 8.0]),
                by=np.array([0, 0, 1, 1]),
                request_id="req-serve-7",
            )
            result = await dispatcher.submit(req)
            await dispatcher.close()
            return result

        with flox_tpu.set_options(telemetry=True):
            result = asyncio.run(go())
            records = telemetry.drain()
        np.testing.assert_allclose(np.asarray(result.result), [3.0, 12.0])
        execute = [r for r in records if r["name"] == "serve.execute"]
        core = [r for r in records if r["name"] == "groupby_reduce"]
        request = [r for r in records if r["name"] == "serve.request"]
        assert execute and execute[0].get("trace") == "req-serve-7"
        assert core and core[0].get("trace") == "req-serve-7"
        assert request and request[0].get("trace") == "req-serve-7"


# ---------------------------------------------------------------------------
# HBM accounting
# ---------------------------------------------------------------------------


class TestHbmAccounting:
    def test_memory_stats_shape(self):
        from flox_tpu import device

        stats = device.memory_stats()
        # CPU backends may report nothing; when they do report, the
        # aggregate keys are fixed
        if stats is not None:
            assert {"bytes_in_use", "peak_bytes_in_use", "devices"} <= set(stats)

    def test_fake_memory_stats_feed_gauges_and_attribution(self, monkeypatch):
        from flox_tpu import device

        feed = iter([
            {"bytes_in_use": 1000, "peak_bytes_in_use": 1500},
            {"bytes_in_use": 800, "peak_bytes_in_use": 1500},
            {"bytes_in_use": 2000, "peak_bytes_in_use": 2500},
        ])
        last = {"bytes_in_use": 500, "peak_bytes_in_use": 2500}
        monkeypatch.setattr(
            device, "memory_stats", lambda devices=None: next(feed, last)
        )
        with flox_tpu.set_options(telemetry=True):
            telemetry.sample_hbm(program="prog-a")
            telemetry.sample_hbm(program="prog-a")
            telemetry.sample_hbm(program="prog-b")
            telemetry.sample_hbm()
        # gauge = latest, peak gauge = running max
        assert telemetry.METRICS.get("hbm.bytes_in_use") == 500
        assert telemetry.METRICS.get("hbm.peak_bytes_in_use") == 2500
        # per-program attribution keeps each program's own max
        attribution = cache.stats()["hbm_by_program"]
        assert attribution == {"prog-a": 1000.0, "prog-b": 2000.0}
        cache.clear_all()
        assert cache.stats()["hbm_by_program"] == {}

    def test_dispatch_paths_attribute_programs(self, monkeypatch):
        from flox_tpu import device

        monkeypatch.setattr(
            device,
            "memory_stats",
            lambda devices=None: {"bytes_in_use": 4096, "peak_bytes_in_use": 8192},
        )
        with flox_tpu.set_options(telemetry=True):
            _run_reduce()
        attribution = cache.stats()["hbm_by_program"]
        assert any(key.startswith("bundle[") for key in attribution), attribution

    def test_disabled_sampling_is_untouched(self, monkeypatch):
        from flox_tpu import device

        def boom(devices=None):  # pragma: no cover - must never run
            raise AssertionError("memory_stats consulted while disabled")

        monkeypatch.setattr(device, "memory_stats", boom)
        telemetry.sample_hbm(program="nope")
        assert telemetry.METRICS.snapshot() == {}
        assert cache.stats()["hbm_by_program"] == {}


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded(self):
        with flox_tpu.set_options(telemetry=True, flight_recorder_size=16):
            for i in range(64):
                telemetry.event("tick", i=i)
            records = telemetry.FLIGHT_RECORDER.records()
        assert len(records) == 16
        assert records[-1]["attrs"]["i"] == 63  # newest kept, oldest dropped

    def test_dump_on_fatal_fault_roundtrips_through_report(self, tmp_path, capsys):
        from flox_tpu.resilience import RetryPolicy, call_with_retry

        dump = tmp_path / "flight.jsonl"
        with flox_tpu.set_options(telemetry=True, flight_recorder_path=str(dump)):
            _run_reduce()  # populate the ring with real spans

            def fatal():
                raise ValueError("programming error")

            with pytest.raises(ValueError, match="programming error"):
                call_with_retry(fatal, policy=RetryPolicy(retries=3, backoff=0.0))
        assert dump.exists(), "fatal classification must dump the flight recorder"
        parsed = [json.loads(line) for line in dump.read_text().splitlines()]
        header = parsed[0]
        assert header["name"] == "flight-recorder"
        assert header["attrs"]["reason"].startswith("fatal:ValueError")
        names = {r.get("name") for r in parsed}
        assert "groupby_reduce" in names, "ring must hold the pre-fault spans"
        assert "fatal" in names, "the fatal event itself must be recorded"
        assert parsed[-1]["type"] == "counters"
        # the dump is a valid telemetry export: report exits 0 and
        # summarizes it
        assert telemetry.main(["report", str(dump)]) == 0
        out = capsys.readouterr().out
        assert "groupby_reduce" in out

    def test_transient_fault_does_not_dump(self, tmp_path):
        from flox_tpu.resilience import RetryPolicy, call_with_retry

        dump = tmp_path / "flight.jsonl"
        with flox_tpu.set_options(telemetry=True, flight_recorder_path=str(dump)):
            calls = {"n": 0}

            def flaky():
                calls["n"] += 1
                if calls["n"] < 2:
                    raise OSError("transient hiccup")
                return "ok"

            assert call_with_retry(flaky, policy=RetryPolicy(retries=3, backoff=0.0)) == "ok"
        assert not dump.exists()

    def test_dump_on_signal(self, tmp_path):
        if not hasattr(signal, "SIGUSR2"):
            pytest.skip("no SIGUSR2 on this platform")
        dump = tmp_path / "flight-signal.jsonl"
        # install_signal_dumps registers BOTH signals: restore both, or the
        # SIGTERM dump handler leaks into every later test in this process
        previous = {
            sig: signal.getsignal(sig) for sig in (signal.SIGTERM, signal.SIGUSR2)
        }
        try:
            with flox_tpu.set_options(telemetry=True, flight_recorder_path=str(dump)):
                telemetry.event("before-signal")
                telemetry.install_signal_dumps()
                os.kill(os.getpid(), signal.SIGUSR2)
            assert dump.exists()
            parsed = [json.loads(line) for line in dump.read_text().splitlines()]
            assert parsed[0]["attrs"]["reason"] == "signal:SIGUSR2"
            assert any(r.get("name") == "before-signal" for r in parsed)
        finally:
            for sig, handler in previous.items():
                signal.signal(sig, handler)

    def test_unconfigured_dump_is_noop(self):
        with flox_tpu.set_options(telemetry=True):
            telemetry.event("something")
            assert telemetry.flight_dump(reason="no path") is None


# ---------------------------------------------------------------------------
# bit-identity + disabled-path contracts
# ---------------------------------------------------------------------------


class TestPlaneNeutrality:
    def test_bit_identity_with_plane_enabled(self, tmp_path, monkeypatch):
        from flox_tpu import device

        expected, groups = _run_reduce()
        monkeypatch.setattr(
            device,
            "memory_stats",
            lambda devices=None: {"bytes_in_use": 1, "peak_bytes_in_use": 2},
        )
        with flox_tpu.set_options(
            telemetry=True,
            flight_recorder_path=str(tmp_path / "f.jsonl"),
        ):
            port = exposition.start_metrics_server(port=0)
            with telemetry.trace("bit-req"):
                got, g2 = _run_reduce()
            assert (
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=5
                ).status
                == 200
            )
        np.testing.assert_array_equal(np.asarray(expected), np.asarray(got))
        np.testing.assert_array_equal(np.asarray(groups), np.asarray(g2))

    def test_disabled_path_allocates_nothing(self):
        # trace() and span() hand back the one shared no-op; the registry,
        # the buffer, and the flight ring stay untouched
        assert telemetry.trace("req-x") is telemetry.span("anything")
        with telemetry.trace("req-x"):
            _run_reduce()
        assert telemetry.current_trace() is None
        assert telemetry.spans() == []
        assert telemetry.METRICS.snapshot() == {}
        assert len(telemetry.FLIGHT_RECORDER) == 0


class TestNewOptions:
    @pytest.mark.parametrize(
        "bad",
        [
            {"metrics_port": -1},
            {"metrics_port": 70000},
            {"metrics_port": 1.5},
            {"flight_recorder_path": ""},
            {"flight_recorder_size": 0},
            {"flight_recorder_size": True},
            {"profile_dir": ""},
            {"profile_keep": 0},
            {"profile_keep": True},
            {"metrics_sample_interval": -1.0},
            {"metrics_sample_interval": float("inf")},
            {"replica_id": ""},
            {"replica_id": 'bad"label'},
            {"fleet_scrape_interval": 0.0},
            {"fleet_port": -1},
            {"fleet_replicas": ""},
        ],
    )
    def test_validated_at_set_time(self, bad):
        with pytest.raises(ValueError):
            flox_tpu.set_options(**bad)

    def test_env_mirrors_exist(self):
        # the FLX010 contract, asserted at runtime too: every new knob has
        # an env mirror spelled FLOX_TPU_<NAME>
        import inspect

        from flox_tpu import options as opts

        src = inspect.getsource(opts)
        for name in (
            "metrics_port", "flight_recorder_path", "flight_recorder_size",
            "profile_dir", "profile_keep", "metrics_sample_interval",
            "replica_id", "fleet_scrape_interval", "fleet_port",
            "fleet_replicas",
        ):
            assert f"FLOX_TPU_{name.upper()}" in src


# ---------------------------------------------------------------------------
# cost ledger (ISSUE 9)
# ---------------------------------------------------------------------------


class TestCostLedger:
    def test_eager_dispatch_feeds_program_ledger(self):
        cache.clear_all()  # fresh bundle: the first call must pay a compile
        with flox_tpu.set_options(telemetry=True):
            _run_reduce()
            _run_reduce()
        costs = cache.stats()["cost_by_program"]
        bundle = [k for k in costs if k.startswith("bundle[")]
        assert bundle, costs
        row = costs[bundle[0]]
        assert row["dispatches"] == 2
        assert row["device_ms"] > 0
        assert row["device_ms_max"] <= row["device_ms"]
        assert row["bytes"] > 0
        # the first call compiled, the second was a cache hit
        assert row["compiles"] >= 1
        cache.clear_all()
        assert cache.stats()["cost_by_program"] == {}

    def test_mesh_and_streaming_dispatches_attributed(self):
        mesh = make_mesh()
        n = 512
        labels = RNG.integers(0, 5, n)
        vals = RNG.normal(size=n)
        with flox_tpu.set_options(telemetry=True):
            groupby_reduce(vals, labels, func="sum", method="map-reduce", mesh=mesh)
            streaming_groupby_reduce(vals, labels, func="sum", batch_len=128)
        costs = cache.stats()["cost_by_program"]
        assert any(k.startswith("mesh[") for k in costs), costs
        assert any(k.startswith("stream[") for k in costs), costs
        stream_rows = [v for k, v in costs.items() if k.startswith("stream[")]
        assert stream_rows[0]["bytes"] > 0  # staged slab bytes attributed

    def test_slow_trace_id_lands_in_ledger(self):
        with flox_tpu.set_options(telemetry=True):
            with telemetry.trace("req-slowest"):
                _run_reduce()
        costs = cache.stats()["cost_by_program"]
        bundle = [v for k, v in costs.items() if k.startswith("bundle[")]
        assert bundle and bundle[0]["last_slow_trace"] == "req-slowest"

    def test_hbm_peak_absorbed_into_ledger(self, monkeypatch):
        from flox_tpu import device

        monkeypatch.setattr(
            device,
            "memory_stats",
            lambda devices=None: {"bytes_in_use": 4096, "peak_bytes_in_use": 8192},
        )
        with flox_tpu.set_options(telemetry=True):
            _run_reduce()
        costs = cache.stats()["cost_by_program"]
        bundle = [k for k in costs if k.startswith("bundle[")]
        assert costs[bundle[0]]["hbm_peak"] == 4096
        # the hbm_by_program view is the ledger's hbm_peak column
        assert cache.stats()["hbm_by_program"][bundle[0]] == 4096

    def test_disabled_path_records_nothing(self):
        telemetry.observe_cost("nope", device_ms=1.0, nbytes=10)
        assert cache.stats()["cost_by_program"] == {}
        assert cache.stats()["cost_by_tenant"] == {}

    def test_costs_cli_live_and_file(self, tmp_path, capsys):
        with flox_tpu.set_options(telemetry=True):
            _run_reduce()
        assert telemetry.main(["costs"]) == 0
        out = capsys.readouterr().out
        assert "bundle[" in out and "program key" in out
        # a /debug/costs-shaped scrape file round-trips through the CLI
        scrape = tmp_path / "costs.json"
        scrape.write_text(json.dumps({
            "cost_by_program": telemetry.cost_by_program(),
            "cost_by_tenant": telemetry.cost_by_tenant(),
        }))
        assert telemetry.main(["costs", str(scrape), "--top", "1"]) == 0
        out = capsys.readouterr().out
        assert "bundle[" in out

    def test_costs_cli_rejects_garbage(self, tmp_path, capsys):
        bad = tmp_path / "bad.json"
        bad.write_text("not json")
        with pytest.raises(SystemExit):
            telemetry.main(["costs", str(bad)])


class TestTenantAxis:
    def _submit(self, tenant=None, request_id=None):
        import asyncio

        from flox_tpu.serve import AggregationRequest, Dispatcher

        async def go():
            dispatcher = Dispatcher()
            result = await dispatcher.submit(AggregationRequest(
                func="sum",
                array=np.array([1.0, 2.0, 4.0, 8.0]),
                by=np.array([0, 0, 1, 1]),
                tenant=tenant,
                request_id=request_id,
            ))
            await dispatcher.close()
            return result

        return asyncio.run(go())

    def test_tenant_feeds_ledger_and_labeled_histogram(self):
        with flox_tpu.set_options(telemetry=True):
            result = self._submit(tenant="acme", request_id="req-t1")
        np.testing.assert_allclose(np.asarray(result.result), [3.0, 12.0])
        tenants = cache.stats()["cost_by_tenant"]
        assert "acme" in tenants, tenants
        assert tenants["acme"]["dispatches"] == 1
        assert tenants["acme"]["bytes"] > 0
        samples, types, _ = _parse_prometheus(exposition.prometheus_text())
        labeled = [
            k for k in samples
            if k.startswith('flox_tpu_serve_request_ms_bucket{tenant="acme",le="')
        ]
        assert len(labeled) == len(telemetry.HIST_EDGES_MS) + 1  # edges + +Inf
        assert samples['flox_tpu_serve_request_ms_count{tenant="acme"}'] == 1
        # ONE TYPE line covers the base metric and its labeled series
        assert types["flox_tpu_serve_request_ms"] == "histogram"
        text = exposition.prometheus_text()
        assert text.count("# TYPE flox_tpu_serve_request_ms histogram") == 1

    def test_untagged_requests_leave_no_tenant_rows(self):
        with flox_tpu.set_options(telemetry=True):
            self._submit()
        assert cache.stats()["cost_by_tenant"] == {}

    def test_tenant_label_sanitized_against_injection(self):
        # a client-chosen tag must not be able to inject label syntax into
        # the exposition (a raw `|le=5` would render a duplicate le label
        # and poison the whole scrape for every consumer)
        with flox_tpu.set_options(telemetry=True):
            self._submit(tenant='evil|le=5"x')
        tenants = cache.stats()["cost_by_tenant"]
        assert list(tenants) == ["evil_le_5_x"]
        text = exposition.prometheus_text()
        assert 'tenant="evil_le_5_x"' in text
        # every bucket line still carries exactly ONE le label
        for line in text.splitlines():
            if "_bucket{" in line:
                assert line.count('le="') == 1, line
        _parse_prometheus(text)  # and the whole exposition still parses

    def test_tenant_cardinality_is_bounded(self):
        # unique client tags past the cap fold into "_other" instead of
        # allocating a fresh histogram per string
        with flox_tpu.set_options(telemetry=True):
            for i in range(telemetry._TENANT_MAX + 5):
                assert telemetry.tenant_label(f"t{i}") == (
                    f"t{i}" if i < telemetry._TENANT_MAX else "_other"
                )
            # known labels keep resolving to themselves past the cap
            assert telemetry.tenant_label("t0") == "t0"
        cache.clear_all()
        assert telemetry.tenant_label("fresh") == "fresh"

    def test_coalesced_tenant_billing_sums_to_dispatch_wall(self):
        # K coalesced requests share ONE dispatch; the tenant axis bills
        # each its share, so tenant totals never exceed program totals
        import asyncio

        from flox_tpu.serve import AggregationRequest, Dispatcher

        async def go():
            dispatcher = Dispatcher(batch_window=0.05)
            arr = np.array([1.0, 2.0, 4.0, 8.0])
            by = np.array([0, 0, 1, 1])
            results = await asyncio.gather(*[
                dispatcher.submit(AggregationRequest(
                    func="sum", array=arr, by=by, tenant="acme"
                ))
                for _ in range(3)
            ])
            await dispatcher.close()
            return results

        with flox_tpu.set_options(telemetry=True):
            results = asyncio.run(go())
        assert len(results) == 3
        assert telemetry.METRICS.get("serve.dispatches") == 1
        stats = cache.stats()
        program_ms = sum(
            row["device_ms"] for key, row in stats["cost_by_program"].items()
            if key.startswith("serve[")
        )
        tenant_ms = stats["cost_by_tenant"]["acme"]["device_ms"]
        assert tenant_ms <= program_ms * 1.001 + 1e-6, (tenant_ms, program_ms)

    def test_tenant_does_not_change_results(self):
        with flox_tpu.set_options(telemetry=True):
            tagged = self._submit(tenant="acme")
            untagged = self._submit()
        np.testing.assert_array_equal(
            np.asarray(tagged.result), np.asarray(untagged.result)
        )


# ---------------------------------------------------------------------------
# exemplars (ISSUE 9)
# ---------------------------------------------------------------------------


class TestExemplars:
    def test_bucket_lines_parse_with_and_without_exemplars(self):
        with flox_tpu.set_options(telemetry=True):
            # one traced observation (carries an exemplar) and one bare
            telemetry.METRICS.observe("demo_ms", 0.5, exemplar="req-ex-1")
            telemetry.METRICS.observe("demo_ms", 700.0)
        text = exposition.prometheus_text()
        samples, _, exemplars = _parse_prometheus(text)
        with_ex = [k for k in exemplars if k.startswith("flox_tpu_demo_ms_bucket")]
        assert len(with_ex) == 1
        labels, observed = exemplars[with_ex[0]]
        assert labels == '{trace_id="req-ex-1"}'
        assert observed == 0.5
        # the untraced observation's bucket line carries none, and both
        # still parse as ordinary cumulative samples
        buckets = [
            v for k, v in samples.items()
            if k.startswith('flox_tpu_demo_ms_bucket{le="')
        ]
        assert buckets == sorted(buckets)
        assert samples['flox_tpu_demo_ms_bucket{le="+Inf"}'] == 2

    def test_exemplar_keeps_max_observation_per_bucket(self):
        with flox_tpu.set_options(telemetry=True):
            # both land in the same bucket; the larger wins the slot
            telemetry.METRICS.observe("demo_ms", 0.40, exemplar="req-small")
            telemetry.METRICS.observe("demo_ms", 0.51, exemplar="req-big")
            telemetry.METRICS.observe("demo_ms", 0.45, exemplar="req-mid")
        _, _, exemplars = _parse_prometheus(exposition.prometheus_text())
        (labels, observed), = exemplars.values()
        assert labels == '{trace_id="req-big"}'
        assert observed == 0.51

    def test_http_scrape_clean_by_default_exemplars_on_request(self):
        # the classic 0.0.4 text parser (a default Prometheus scrape)
        # aborts on exemplars, so the plain endpoint must omit them; a
        # scraper that wants them asks with ?exemplars=1
        with flox_tpu.set_options(telemetry=True):
            telemetry.METRICS.observe("demo_ms", 0.5, exemplar="req-http")
            port = exposition.start_metrics_server(port=0)
            plain = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5
            ).read().decode()
            rich = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics?exemplars=1", timeout=5
            ).read().decode()
        assert " # {trace_id=" not in plain
        assert ' # {trace_id="req-http"}' in rich
        _parse_prometheus(plain)
        _parse_prometheus(rich)

    def test_exemplar_trace_id_is_escaped(self):
        # trace ids are client-supplied (request ids): quotes/backslashes
        # must not produce malformed label syntax on the bucket line
        with flox_tpu.set_options(telemetry=True):
            telemetry.METRICS.observe("demo_ms", 0.5, exemplar='r"1\\x')
        text = exposition.prometheus_text()
        assert ' # {trace_id="r\\"1\\\\x"}' in text
        _parse_prometheus(text)

    def test_traced_spans_carry_exemplars_to_metrics(self):
        with flox_tpu.set_options(telemetry=True):
            with telemetry.trace("req-exemplar"):
                _run_reduce()
        _, _, exemplars = _parse_prometheus(exposition.prometheus_text())
        span_ex = {
            k: v for k, v in exemplars.items()
            if k.startswith("flox_tpu_span_ms_groupby_reduce_bucket")
        }
        assert span_ex
        assert all(v[0] == '{trace_id="req-exemplar"}' for v in span_ex.values())

    def test_report_links_slowest_trace(self, tmp_path, capsys):
        with flox_tpu.set_options(telemetry=True):
            with telemetry.trace("req-linked"):
                _run_reduce()
            export = tmp_path / "t.jsonl"
            telemetry.export_jsonl(str(export))
        assert telemetry.main(["report", str(export), "--histograms"]) == 0
        out = capsys.readouterr().out
        assert "slowest trace" in out
        assert "req-linked" in out


# ---------------------------------------------------------------------------
# on-demand capture (ISSUE 9)
# ---------------------------------------------------------------------------


class _FakeProfiler:
    """Deterministic stand-in for jax.profiler: records start/stop calls
    without touching the real (backend-dependent) profiler."""

    def __init__(self, fail_start=False):
        self.fail_start = fail_start
        self.starts: list[str] = []
        self.stops = 0

    def install(self, monkeypatch):
        import jax

        def start_trace(logdir):
            if self.fail_start:
                raise RuntimeError("no profiler on this backend")
            os.makedirs(logdir, exist_ok=True)
            self.starts.append(logdir)

        monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: setattr(
            self, "stops", self.stops + 1
        ))
        return self


class TestOnDemandCapture:
    @pytest.fixture(autouse=True)
    def _fresh_capture_state(self):
        from flox_tpu import profiling

        profiling._CAPTURE_STATE.clear()
        yield
        profiling._CAPTURE_STATE.clear()

    def test_unconfigured_root_is_unavailable(self):
        from flox_tpu import profiling

        with pytest.raises(profiling.CaptureUnavailableError):
            profiling.start_capture(seconds=0.05)

    def test_capture_runs_and_guard_clears(self, tmp_path, monkeypatch):
        import time as _time

        from flox_tpu import profiling

        fake = _FakeProfiler().install(monkeypatch)
        with flox_tpu.set_options(telemetry=True, profile_dir=str(tmp_path)):
            capture_dir = profiling.start_capture(seconds=0.05)
            assert capture_dir.startswith(str(tmp_path))
            assert cache.stats()["profile_capture_active"] is True
            # a second capture while one runs is refused (HTTP 409)
            with pytest.raises(profiling.CaptureBusyError):
                profiling.start_capture(seconds=0.05)
            for _ in range(100):
                if profiling.capture_active() is None:
                    break
                _time.sleep(0.02)
        assert profiling.capture_active() is None
        assert fake.starts == [capture_dir]
        assert fake.stops == 1
        assert telemetry.METRICS.get("profile.captures") == 1

    def test_profiler_less_backend_is_unavailable(self, tmp_path, monkeypatch):
        from flox_tpu import profiling

        _FakeProfiler(fail_start=True).install(monkeypatch)
        with flox_tpu.set_options(profile_dir=str(tmp_path)):
            with pytest.raises(profiling.CaptureUnavailableError):
                profiling.start_capture(seconds=0.05)
        # the guard did not leak: a later capture may start
        assert profiling.capture_active() is None

    def test_capture_dir_rotation(self, tmp_path, monkeypatch):
        import time as _time

        from flox_tpu import profiling

        _FakeProfiler().install(monkeypatch)
        with flox_tpu.set_options(profile_dir=str(tmp_path), profile_keep=2):
            for _ in range(4):
                profiling.start_capture(seconds=0.01)
                for _ in range(100):
                    if profiling.capture_active() is None:
                        break
                    _time.sleep(0.02)
        captures = sorted(p.name for p in tmp_path.iterdir())
        assert len(captures) <= 2, captures

    def test_http_endpoint_409_and_501(self, tmp_path, monkeypatch):
        import time as _time

        from flox_tpu import profiling

        _FakeProfiler().install(monkeypatch)
        port = exposition.start_metrics_server(port=0)
        with flox_tpu.set_options(telemetry=True, profile_dir=str(tmp_path)):
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/profile?seconds=0.3", timeout=5
            )
            assert resp.status == 202
            payload = json.loads(resp.read())
            assert payload["ok"] and payload["dir"].startswith(str(tmp_path))
            # concurrent second request: 409, and the reply names the clash
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/debug/profile?seconds=0.3", timeout=5
                )
            assert err.value.code == 409
            for _ in range(100):
                if profiling.capture_active() is None:
                    break
                _time.sleep(0.02)
        # unconfigured root -> clean 501, never an exception in the server
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/profile", timeout=5
            )
        assert err.value.code == 501

    def test_trace_defaults_to_profile_dir_and_warns_without_profiler(
        self, tmp_path, monkeypatch, caplog
    ):
        import logging as _logging

        from flox_tpu import profiling

        with pytest.raises(ValueError, match="profile_dir"):
            with profiling.trace():
                pass
        fake = _FakeProfiler().install(monkeypatch)
        with flox_tpu.set_options(profile_dir=str(tmp_path)):
            with profiling.trace():
                pass
        assert fake.starts == [str(tmp_path)]
        # a profiler-less backend warns and runs the block untraced
        _FakeProfiler(fail_start=True).install(monkeypatch)
        ran = []
        with caplog.at_level(_logging.WARNING, logger="flox_tpu.profiling"):
            with profiling.trace(str(tmp_path)):
                ran.append(True)
        assert ran == [True]
        assert any("untraced" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# /debug/costs + saturation gauges (ISSUE 9)
# ---------------------------------------------------------------------------


class TestDebugCostsEndpoint:
    def test_scrape_matches_cache_stats(self, tmp_path):
        with flox_tpu.set_options(telemetry=True):
            _run_reduce()
            port = exposition.start_metrics_server(port=0)
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/costs", timeout=5
            )
            assert resp.status == 200
            assert "application/json" in resp.headers["Content-Type"]
            payload = json.loads(resp.read())
            stats = cache.stats()
        assert set(payload) >= {"cost_by_program", "cost_by_tenant", "hbm_by_program"}
        assert payload["cost_by_program"].keys() == stats["cost_by_program"].keys()
        bundle = [k for k in payload["cost_by_program"] if k.startswith("bundle[")]
        assert payload["cost_by_program"][bundle[0]]["dispatches"] >= 1
        # the scrape is exactly what `telemetry costs` tabulates
        scrape = tmp_path / "scrape.json"
        scrape.write_text(json.dumps(payload))
        assert telemetry.main(["costs", str(scrape), "--top", "3"]) == 0


class TestSaturationGauges:
    def test_seeded_to_zero_at_server_start(self):
        with flox_tpu.set_options(telemetry=True):
            port = exposition.start_metrics_server(port=0)
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5
            )
            samples, types, _ = _parse_prometheus(resp.read().decode())
        for name in telemetry.SATURATION_GAUGES:
            metric = "flox_tpu_" + name.replace(".", "_")
            assert samples[metric] == 0, f"{metric} not seeded"
            assert types[metric] == "gauge"

    def test_seeding_never_rewinds_a_live_gauge(self):
        with flox_tpu.set_options(telemetry=True):
            telemetry.METRICS.set_gauge("serve.queue_depth", 7)
            telemetry.seed_saturation_gauges()
            assert telemetry.METRICS.get("serve.queue_depth") == 7

    def test_sample_saturation_reads_live_state(self):
        from flox_tpu import pipeline
        from flox_tpu.serve.dispatcher import _PENDING_REGISTRY

        with flox_tpu.set_options(telemetry=True):
            _PENDING_REGISTRY[991] = object()
            pipeline._PREFETCH_INFLIGHT[0] = 3
            try:
                telemetry.sample_saturation()
            finally:
                _PENDING_REGISTRY.pop(991, None)
                pipeline._PREFETCH_INFLIGHT[0] = 0
            assert telemetry.METRICS.get("serve.queue_depth") == 1
            assert telemetry.METRICS.get("stream.prefetch_occupancy") == 3

    def test_sampler_thread_runs_and_stops(self):
        import time as _time

        with flox_tpu.set_options(telemetry=True, metrics_sample_interval=0.01):
            assert telemetry.start_saturation_sampler() is True
            # idempotent while live
            assert telemetry.start_saturation_sampler() is True
            for _ in range(200):
                if telemetry.METRICS.gauges().get("serve.queue_depth") is not None:
                    break
                _time.sleep(0.01)
            assert telemetry.METRICS.gauges().get("serve.queue_depth") == 0
        telemetry.stop_saturation_sampler()
        assert telemetry._SAMPLER_STATE["thread"] is None

    def test_sampler_off_by_default(self):
        with flox_tpu.set_options(telemetry=True):
            assert telemetry.start_saturation_sampler() is False

    def test_prefetch_occupancy_returns_to_zero_after_stream(self):
        from flox_tpu import pipeline

        n = 512
        labels = RNG.integers(0, 4, n)
        vals = RNG.normal(size=n)
        with flox_tpu.set_options(telemetry=True, stream_prefetch=2):
            streaming_groupby_reduce(vals, labels, func="sum", batch_len=64)
        assert pipeline.prefetch_occupancy() == 0


class TestFullPlaneBitIdentity:
    def test_bit_identity_with_cost_plane_enabled(self, tmp_path, monkeypatch):
        # the whole ISSUE 9 plane at once: cost ledger feeding, exemplars,
        # tenant axis off, saturation sampler live, capture state guarded —
        # results must stay bit-identical to the disabled run
        from flox_tpu import device

        expected, groups = _run_reduce()
        monkeypatch.setattr(
            device,
            "memory_stats",
            lambda devices=None: {"bytes_in_use": 1, "peak_bytes_in_use": 2},
        )
        with flox_tpu.set_options(
            telemetry=True,
            metrics_sample_interval=0.01,
            profile_dir=str(tmp_path),
            flight_recorder_path=str(tmp_path / "f.jsonl"),
        ):
            port = exposition.start_metrics_server(port=0)
            with telemetry.trace("bit-req-9"):
                got, g2 = _run_reduce()
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/costs", timeout=5
            )
            assert resp.status == 200
        np.testing.assert_array_equal(np.asarray(expected), np.asarray(got))
        np.testing.assert_array_equal(np.asarray(groups), np.asarray(g2))
        assert cache.stats()["cost_by_program"]


# ---------------------------------------------------------------------------
# analytical cost model (ISSUE 14)
# ---------------------------------------------------------------------------


from flox_tpu import costmodel, device as device_mod, faults  # noqa: E402


def _plane(**extra):
    return flox_tpu.set_options(telemetry=True, costmodel=True, **extra)


class TestCostModelCards:
    def test_off_by_default_is_a_noop(self):
        # costmodel pinned off explicitly: the assertion must hold under a
        # CI leg exporting FLOX_TPU_COSTMODEL=1 too
        with flox_tpu.set_options(telemetry=True, costmodel=False):
            _run_reduce()
        assert costmodel.cards() == {}
        assert cache.stats()["costmodel_cards"] == 0
        gauges = telemetry.METRICS.gauges()
        assert not any(k.startswith("program.") for k in gauges)

    def test_eager_bundle_card_nonzero_flops_and_bytes(self):
        with _plane():
            _run_reduce()
        card = costmodel.card_for("bundle[nanmean]")
        assert card is not None
        assert card["analysis"] == "ok"
        assert card["flops"] > 0 and card["bytes_accessed"] > 0
        assert card["predicted_ms"] > 0
        assert card["hlo_hash"]
        assert cache.stats()["costmodel_cards"] >= 1

    def test_card_compiles_never_pollute_jax_compiles(self):
        # the analysis pass compiles the program a second time; that
        # compile must count on costmodel.card_* and leave jax.compiles
        # exactly where a cards-off run puts it
        cache.clear_all()
        with flox_tpu.set_options(telemetry=True):
            _run_reduce()
            baseline = telemetry.METRICS.get("jax.compiles")
        assert baseline >= 1  # a fresh bundle really compiled
        cache.clear_all()
        with _plane():
            _run_reduce()
            assert telemetry.METRICS.get("jax.compiles") == baseline
            assert telemetry.METRICS.get("costmodel.card_compiles") >= 1
            assert telemetry.METRICS.get("costmodel.card_compile_ms") > 0

    def test_every_runtime_path_has_a_card(self):
        # acceptance: eager, fused, streaming, and mesh dispatches on the
        # CPU backend all yield cards with nonzero analytical flops+bytes
        from flox_tpu.fusion import groupby_aggregate_many

        vals = RNG.normal(size=48)
        codes = np.arange(48) % 5
        with _plane():
            _run_reduce()
            groupby_aggregate_many(
                vals, codes, funcs=("sum", "min", "max"), engine="jax"
            )
            streaming_groupby_reduce(vals, codes, func="sum", batch_len=16)
            mesh = make_mesh(1)
            groupby_reduce(
                vals, codes, func="sum", engine="jax",
                method="map-reduce", mesh=mesh,
            )
        by_label = {c["label"]: c for c in costmodel.cards().values()}
        for label in (
            "bundle[nanmean]",
            "fused[sum+min+max]",
            "stream[reduce[sum]]",
            "mesh[sum/map-reduce]",
        ):
            card = by_label[label]
            assert card["analysis"] == "ok", (label, card)
            assert card["flops"] > 0, label
            assert card["bytes_accessed"] > 0, label
        # and each label joins its observed ledger row
        report = costmodel.program_report()["programs"]
        for label in by_label:
            assert report[label]["observed"] is not None, label

    def test_cards_memoized_per_signature(self):
        with _plane():
            _run_reduce()
            n0 = telemetry.METRICS.get("costmodel.card_compiles")
            _run_reduce()  # same program+shape: registry hit, no compile
            assert telemetry.METRICS.get("costmodel.card_compiles") == n0
        assert cache.stats()["costmodel_cards"] == 1

    def test_serve_dispatch_aliases_underlying_card(self):
        import asyncio

        from flox_tpu.serve import Dispatcher

        async def go():
            d = Dispatcher()
            res = await d.submit(
                func="sum",
                array=np.array([1.0, 2.0, 4.0, 8.0]),
                by=np.array([0, 0, 1, 1]),
                # pin the jit engine: a tiny payload under x64 would take
                # the numpy engine, which compiles no program to card
                options={"numpy_engine_max_elems": 0},
            )
            await d.close()
            return res

        with _plane():
            asyncio.run(go())
        serve_labels = [
            label
            for label in costmodel.program_report()["programs"]
            if label.startswith("serve[")
        ]
        assert serve_labels, costmodel.program_report()["programs"].keys()
        card = costmodel.card_for(serve_labels[0])
        assert card is not None and card["flops"] > 0

    def test_clear_all_drops_the_registry(self):
        with _plane():
            _run_reduce()
        assert costmodel.cards()
        cache.clear_all()
        assert costmodel.cards() == {}
        assert costmodel.card_for("bundle[nanmean]") is None
        assert cache.stats()["costmodel_cards"] == 0

    def test_full_plane_bit_identity(self):
        # acceptance: results with telemetry + cards enabled are
        # bit-identical to the plane off — eager, mesh, and streaming
        from flox_tpu.fusion import groupby_aggregate_many

        vals = RNG.normal(size=(3, 48))
        flat = vals[0]
        codes = np.arange(48) % 5
        mesh = make_mesh(1)

        def run_all():
            out = {}
            out["eager"], _ = groupby_reduce(vals, codes, func="nanmean", engine="jax")
            out["mesh"], _ = groupby_reduce(
                vals, codes, func="sum", engine="jax",
                method="map-reduce", mesh=mesh,
            )
            out["stream"], _ = streaming_groupby_reduce(
                flat, codes, func="sum", batch_len=16
            )
            fused, _ = groupby_aggregate_many(flat, codes, funcs=("sum", "max"))
            out.update({f"fused[{k}]": v for k, v in fused.items()})
            return {k: np.asarray(v) for k, v in out.items()}

        cache.clear_all()
        baseline = run_all()
        cache.clear_all()
        with _plane():
            instrumented = run_all()
        assert instrumented.keys() == baseline.keys()
        for key in baseline:
            np.testing.assert_array_equal(instrumented[key], baseline[key])


class TestRooflineJoin:
    def test_gauges_published_and_scrape_clean(self):
        with _plane():
            _run_reduce()
        gauges = telemetry.METRICS.gauges()
        assert "program.utilization|program=bundle[nanmean]" in gauges
        assert "program.predicted_ms|program=bundle[nanmean]" in gauges
        assert gauges["program.predicted_ms|program=bundle[nanmean]"] > 0
        with flox_tpu.set_options(telemetry=True):
            text = exposition.prometheus_text()
        samples, types, _ = _parse_prometheus(text)
        assert types["flox_tpu_program_utilization"] == "gauge"
        assert any(
            k.startswith('flox_tpu_program_utilization{program="bundle[nanmean]"')
            for k in samples
        ), [k for k in samples if "program_util" in k]

    def test_utilization_is_model_over_observed(self):
        with _plane():
            _run_reduce()
        row = costmodel.program_report()["programs"]["bundle[nanmean]"]
        obs = row["observed"]
        net_ms = max(0.0, obs["device_ms"] - obs["compile_ms"])
        if net_ms > 0:
            expected = row["predicted_ms"] * obs["dispatches"] / net_ms
            # abs tolerance: the published value is rounded to 6 places
            assert row["utilization"] == pytest.approx(expected, abs=1e-6)

    def test_program_report_filters(self):
        with _plane():
            _run_reduce()
            streaming_groupby_reduce(
                RNG.normal(size=48), np.arange(48) % 5, func="sum", batch_len=16
            )
        full = costmodel.program_report()["programs"]
        assert len(full) >= 2
        only = costmodel.program_report(program="bundle[")["programs"]
        assert set(only) == {k for k in full if "bundle[" in k}
        top1 = costmodel.program_report(top=1)["programs"]
        assert len(top1) == 1


class TestDriftSentinel:
    def test_honest_run_is_clean(self):
        with _plane():
            _run_reduce()
            _run_reduce()
            report = costmodel.drift_report()
        assert report["flagged"] == []
        assert report["rows"], "the bundle row must be judged"

    def test_injected_delay_flags_and_scrape_drift_matches(self):
        with _plane():
            _run_reduce()  # cold: pays the compile (net out of the model)
            with faults.dispatch_delay_inject("bundle[nanmean]", 0.5, times=1):
                _run_reduce()
            report = costmodel.drift_report()
            assert report["flagged"] == ["bundle[nanmean]"]
            # the sentinel runs identically over a /debug/programs scrape
            rows = costmodel.program_report()["programs"]
            again = costmodel.drift_report(rows)
            assert again["flagged"] == ["bundle[nanmean]"]

    def test_threshold_option_validated(self):
        with pytest.raises(ValueError):
            flox_tpu.set_options(costmodel_drift_threshold=0.5)
        with pytest.raises(ValueError):
            flox_tpu.set_options(costmodel_overhead_ms=-1.0)
        with pytest.raises(ValueError):
            flox_tpu.set_options(costmodel="yes")


class TestDebugProgramsEndpoint:
    def _get(self, port, path):
        return urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=5)

    def test_golden_format_and_filters(self):
        with _plane():
            _run_reduce()
            port = exposition.start_metrics_server(port=0)
            resp = self._get(port, "/debug/programs")
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("application/json")
            payload = json.loads(resp.read())
            assert "programs" in payload and "peaks" in payload
            assert payload["replica"] and payload["host"]
            row = payload["programs"]["bundle[nanmean]"]
            for key in (
                "digest", "flops", "bytes_accessed", "predicted_ms",
                "analysis", "observed", "utilization", "hlo_hash",
            ):
                assert key in row, key
            assert row["flops"] > 0
            assert row["observed"]["dispatches"] >= 1
            # ?top= keeps the K most expensive rows
            top = json.loads(self._get(port, "/debug/programs?top=1").read())
            assert len(top["programs"]) == 1
            # ?program= filters by substring
            none = json.loads(
                self._get(port, "/debug/programs?program=nosuch").read()
            )
            assert none["programs"] == {}

    def test_malformed_top_is_400(self):
        with _plane():
            port = exposition.start_metrics_server(port=0)
            for bad in ("abc", "0", "-3"):
                with pytest.raises(urllib.error.HTTPError) as err:
                    self._get(port, f"/debug/programs?top={bad}")
                assert err.value.code == 400
                body = json.loads(err.value.read())
                assert body["ok"] is False


class TestProgramsCLI:
    def test_live_and_file_and_top(self, tmp_path, capsys):
        with _plane():
            _run_reduce()
            assert telemetry.main(["programs"]) == 0
            out = capsys.readouterr().out
            assert "bundle[nanmean]" in out and "live process" in out
            port = exposition.start_metrics_server(port=0)
            scrape = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/programs", timeout=5
            ).read()
        path = tmp_path / "programs.json"
        path.write_bytes(scrape)
        assert telemetry.main(["programs", str(path), "--top", "1"]) == 0
        out = capsys.readouterr().out
        assert "bundle[nanmean]" in out

    def test_drift_exit_codes(self, tmp_path, capsys):
        with _plane():
            _run_reduce()
            assert telemetry.main(["programs", "--drift"]) == 0
            assert "clean" in capsys.readouterr().out
            with faults.dispatch_delay_inject("bundle[nanmean]", 0.5, times=1):
                _run_reduce()
            assert telemetry.main(["programs", "--drift"]) == 2
            assert "DRIFT" in capsys.readouterr().out

    def test_garbage_file_errors(self, tmp_path, capsys):
        bad = tmp_path / "bad.json"
        bad.write_text("[1, 2, 3]")
        with pytest.raises(SystemExit):
            telemetry.main(["programs", str(bad)])
        capsys.readouterr()


class TestBytesLimit:
    class _Dev:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    def test_summed_like_the_other_fields(self, monkeypatch):
        devs = [
            self._Dev({"bytes_in_use": 10, "peak_bytes_in_use": 20,
                       "bytes_limit": 100}),
            self._Dev({"bytes_in_use": 5, "peak_bytes_in_use": 6,
                       "bytes_limit": 200}),
        ]
        stats = device_mod.memory_stats(devices=devs)
        assert stats["bytes_in_use"] == 15
        assert stats["bytes_limit"] == 300

    def test_none_safe_when_no_device_reports_a_limit(self):
        devs = [self._Dev({"bytes_in_use": 10})]
        stats = device_mod.memory_stats(devices=devs)
        assert stats["bytes_limit"] is None

    def test_gauge_seeded_at_metrics_server_start(self, monkeypatch):
        from flox_tpu import device

        monkeypatch.setattr(
            device, "memory_stats",
            lambda devices=None: {
                "bytes_in_use": 1, "peak_bytes_in_use": 2,
                "devices": 1, "bytes_limit": 16 * 2**30,
            },
        )
        with flox_tpu.set_options(telemetry=True):
            exposition.start_metrics_server(port=0)
            assert telemetry.METRICS.get("hbm.bytes_limit") == 16 * 2**30
            text = exposition.prometheus_text()
        assert "flox_tpu_hbm_bytes_limit" in text


class TestCaptureStamping:
    def test_capture_dir_stamped_with_window_programs(self, tmp_path):
        from flox_tpu import profiling

        with _plane(profile_dir=str(tmp_path)):
            _run_reduce()  # pre-window dispatch: must NOT be stamped
            capture_dir = profiling.start_capture(seconds=0.3)
            _run_reduce()  # in-window dispatch: must be stamped
            deadline = __import__("time").time() + 10
            stamp = os.path.join(capture_dir, "programs.json")
            while __import__("time").time() < deadline and not os.path.exists(stamp):
                __import__("time").sleep(0.05)
            assert os.path.exists(stamp), "capture never stamped"
            payload = json.loads(open(stamp).read())
            progs = payload["programs"]
            assert "bundle[nanmean]" in progs
            assert progs["bundle[nanmean]"]["dispatches"] == 1
            assert progs["bundle[nanmean]"]["digest"]


class TestAutotunePrior:
    @pytest.fixture(autouse=True)
    def _fresh_store(self):
        # the autotune store survives telemetry.reset(); these tests
        # reason about an EMPTY store, so drop it on both sides
        cache.clear_all()
        yield
        cache.clear_all()

    def test_prior_consulted_when_no_measured_band(self):
        from flox_tpu import autotune

        with _plane(autotune=True):
            choice = autotune.decide(
                "fused", "fused", ("fused", "sequential"),
                dtype="float32", ngroups=8, nelems=4096,
            )
            assert choice == "fused"
            assert telemetry.METRICS.get("costmodel.prior_consults") >= 1
            assert telemetry.METRICS.get("costmodel.prior_decisions") >= 1

    def test_measured_band_outranks_the_prior(self):
        from flox_tpu import autotune

        with _plane(autotune=True):
            autotune.record(
                "fused", "sequential", 99.0,
                dtype="float32", ngroups=8, nelems=4096,
            )
            autotune.record(
                "fused", "fused", 1.0,
                dtype="float32", ngroups=8, nelems=4096,
            )
            consults0 = telemetry.METRICS.get("costmodel.prior_consults")
            choice = autotune.decide(
                "fused", "fused", ("fused", "sequential"),
                dtype="float32", ngroups=8, nelems=4096,
            )
            assert choice == "sequential"  # the measurement, not the model
            assert telemetry.METRICS.get("costmodel.prior_consults") == consults0

    def test_plane_off_keeps_the_fallback(self):
        from flox_tpu import autotune

        with flox_tpu.set_options(telemetry=True, autotune=True, costmodel=False):
            choice = autotune.decide(
                "fused", "fused", ("fused", "sequential"),
                dtype="float32", ngroups=8, nelems=4096,
            )
            assert choice == "fused"
            assert telemetry.METRICS.get("costmodel.prior_consults") == 0


@pytest.mark.parametrize(
    "kind, bw", [("TPU v5 lite", 819.0), ("cpu", 20.0), ("TPU v9 imaginary", None)]
)
def test_peak_table_is_keyed_by_device_kind(kind, bw):
    if bw is None:
        with pytest.raises(ValueError, match="no roofline peaks"):
            costmodel.peaks_for(kind)
    else:
        row = costmodel.peaks_for(kind)
        assert row["bw_gbps"] == bw and row["source"]
