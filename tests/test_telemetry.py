"""Telemetry test suite (ISSUE 4).

The contract under test: with telemetry disabled nothing changes — results
are bit-identical, no span objects are allocated, counters stay untouched;
with telemetry enabled every execution path produces a hierarchical trace
(factorize/dispatch/combine/finalize for ``groupby_reduce``), the exporters
round-trip (emit -> parse -> report), the Chrome trace file is
Perfetto-loadable JSON, and ``cache.clear_all`` resets the metrics registry.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

import flox_tpu
from flox_tpu import cache, telemetry
from flox_tpu.core import groupby_reduce
from flox_tpu.scan import groupby_scan
from flox_tpu.streaming import streaming_groupby_reduce

RNG = np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts from an empty buffer + registry with telemetry OFF
    and no export path — even when the suite itself runs under
    FLOX_TPU_TELEMETRY=1 (the CI instrumented leg), so the disabled-mode
    assertions test the option, not the environment."""
    with flox_tpu.set_options(telemetry=False, telemetry_export_path=None):
        telemetry.reset()
        yield
        telemetry.reset()


def _run_reduce(**kw):
    # a FIXED workload: bit-identity tests compare two runs of this
    vals = np.random.default_rng(0).normal(size=(3, 48)).astype(np.float64)
    codes = np.arange(48) % 5
    return groupby_reduce(vals, codes, func="nanmean", engine="jax", **kw)


# ---------------------------------------------------------------------------
# disabled mode: a true no-op
# ---------------------------------------------------------------------------


class TestDisabledMode:
    def test_span_is_the_shared_noop_singleton(self):
        # no allocation when disabled: every span() call returns ONE object
        s1 = telemetry.span("groupby_reduce")
        s2 = telemetry.span("factorize", ngroups=3)
        assert s1 is s2 is telemetry._NOOP
        with s1 as sp:
            sp.set(attr=1)  # the no-op API surface still chains
        assert telemetry.spans() == []

    def test_counters_untouched_and_no_records(self):
        result_off, _ = _run_reduce()
        streaming_groupby_reduce(
            lambda s, e: np.ones((2, e - s)), np.arange(32) % 4,
            func="nansum", batch_len=8,
        )
        assert telemetry.spans() == []
        assert telemetry.METRICS.snapshot() == {}

    def test_module_helpers_noop(self):
        telemetry.count("x")
        telemetry.event("y", a=1)
        telemetry.record_span("z", 0.0, 1.0)
        telemetry.current_set(a=1)
        assert telemetry.spans() == []
        assert telemetry.METRICS.snapshot() == {}


# ---------------------------------------------------------------------------
# enabled/disabled bit-identity
# ---------------------------------------------------------------------------


class TestBitIdentity:
    def test_reduce_identical(self):
        off, _ = _run_reduce()
        with flox_tpu.set_options(telemetry=True):
            on, _ = _run_reduce()
        np.testing.assert_array_equal(np.asarray(off), np.asarray(on))

    def test_mesh_reduce_identical(self):
        off, _ = _run_reduce(method="map-reduce")
        with flox_tpu.set_options(telemetry=True, telemetry_level="detailed"):
            on, _ = _run_reduce(method="map-reduce")
        np.testing.assert_array_equal(np.asarray(off), np.asarray(on))

    def test_scan_identical(self):
        vals = RNG.normal(size=64)
        codes = np.arange(64) % 3
        off = groupby_scan(vals, codes, func="cumsum")
        with flox_tpu.set_options(telemetry=True):
            on = groupby_scan(vals, codes, func="cumsum")
        np.testing.assert_array_equal(np.asarray(off), np.asarray(on))

    def test_streaming_identical(self):
        vals = RNG.normal(size=(2, 96))
        codes = np.arange(96) % 7

        def loader(s, e):
            return vals[:, s:e]

        off, _ = streaming_groupby_reduce(loader, codes, func="nanmean", batch_len=16)
        with flox_tpu.set_options(telemetry=True, telemetry_level="detailed"):
            on, _ = streaming_groupby_reduce(loader, codes, func="nanmean", batch_len=16)
        np.testing.assert_array_equal(np.asarray(off), np.asarray(on))


# ---------------------------------------------------------------------------
# span hierarchy per execution path
# ---------------------------------------------------------------------------


def _by_name(records):
    out = {}
    for rec in records:
        out.setdefault(rec["name"], []).append(rec)
    return out


class TestSpans:
    def test_eager_reduce_phases(self):
        with flox_tpu.set_options(telemetry=True):
            _run_reduce()
        spans = _by_name([r for r in telemetry.spans() if r["type"] == "span"])
        for phase in ("groupby_reduce", "factorize", "dispatch", "combine", "finalize"):
            assert phase in spans, f"missing {phase} span"
        root = spans["groupby_reduce"][0]
        # the phases nest under the root span
        for phase in ("factorize", "dispatch", "combine", "finalize"):
            assert spans[phase][0]["parent"] == root["id"], phase
        assert root["parent"] is None
        assert spans["factorize"][0]["attrs"]["size"] == 5
        assert spans["dispatch"][0]["attrs"]["engine"] == "jax"

    def test_mesh_reduce_phases(self):
        with flox_tpu.set_options(telemetry=True):
            _run_reduce(method="map-reduce")
        names = {r["name"] for r in telemetry.spans()}
        assert {"groupby_reduce", "factorize", "combine", "finalize"} <= names
        # first call builds the SPMD program, later ones hit the cache and
        # dispatch under the mesh-dispatch span — one of the two must be present
        assert {"dispatch", "h2d"} <= names
        assert ("program-build" in names) or ("mesh-dispatch" in names)

    def test_scan_phases(self):
        with flox_tpu.set_options(telemetry=True):
            groupby_scan(RNG.normal(size=32), np.arange(32) % 3, func="cumsum")
        names = {r["name"] for r in telemetry.spans()}
        assert {"groupby_scan", "factorize", "dispatch", "finalize"} <= names

    def test_streaming_phases_and_stream_report_attrs(self):
        vals = RNG.normal(size=(2, 64))
        codes = np.arange(64) % 4
        with flox_tpu.set_options(telemetry=True):
            streaming_groupby_reduce(
                lambda s, e: vals[:, s:e], codes, func="nanmean", batch_len=16
            )
        spans = _by_name([r for r in telemetry.spans() if r["type"] == "span"])
        assert "streaming_groupby_reduce" in spans
        assert "factorize" in spans and "finalize" in spans
        stream = [n for n in spans if n.startswith("stream[")]
        assert stream, f"no stream pass span in {sorted(spans)}"
        attrs = spans[stream[0]][0]["attrs"]
        # the StreamReport totals ride the span as attributes
        for key in ("slabs", "prefetch", "load_ms", "stage_ms", "wait_ms",
                    "dispatch_ms", "overlap_fraction", "retries"):
            assert key in attrs, key
        assert attrs["slabs"] == 4

    def test_detailed_level_stage_spans(self):
        vals = RNG.normal(size=(2, 64))
        codes = np.arange(64) % 4
        with flox_tpu.set_options(telemetry=True, telemetry_level="detailed"):
            streaming_groupby_reduce(
                lambda s, e: vals[:, s:e], codes, func="nansum", batch_len=16
            )
        stage = [r for r in telemetry.spans() if r["name"] == "stage"]
        assert len(stage) == 4  # one per slab
        assert {s["attrs"]["index"] for s in stage} == {0, 1, 2, 3}

    def test_basic_level_omits_stage_spans(self):
        vals = RNG.normal(size=(2, 64))
        codes = np.arange(64) % 4
        with flox_tpu.set_options(telemetry=True, telemetry_level="basic"):
            streaming_groupby_reduce(
                lambda s, e: vals[:, s:e], codes, func="nansum", batch_len=16
            )
        assert not [r for r in telemetry.spans() if r["name"] == "stage"]


# ---------------------------------------------------------------------------
# the phase spans in a jax.profiler capture, with telemetry off
# ---------------------------------------------------------------------------


def _capture(fn, log_dir):
    """``fn()`` under a profiler session, inside a ``caller`` annotation:
    the host events named ``caller`` or ``flox.*`` as (name, start, end,
    args), in start order."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(log_dir))
    try:
        with jax.profiler.TraceAnnotation("caller"):
            jax.block_until_ready(fn())
    finally:
        jax.profiler.stop_trace()
    (xplane,) = log_dir.glob("plugins/profile/*/*.xplane.pb")
    events = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        for line in plane.lines:
            events += [
                (e.name, e.start_ns, e.end_ns, dict(e.stats)) for e in line.events
                if e.name == "caller" or e.name.startswith("flox.")
            ]
    return sorted(events, key=lambda ev: ev[1])


class TestProfilerSpans:
    PHASES = {"flox.factorize", "flox.dispatch", "flox.h2d", "flox.finalize"}

    @pytest.mark.parametrize("call", ["reduce", "aggregate_many", "cohorts"])
    def test_phases_in_capture_with_telemetry_off(self, call, tmp_path):
        from flox_tpu.parallel.mesh import make_mesh

        vals = np.random.default_rng(0).normal(size=(3, 48))
        codes = np.arange(48) // 4 % 6
        fns = {
            "reduce": lambda: groupby_reduce(vals, codes, func="nanmean", engine="jax")[0],
            "aggregate_many": lambda: flox_tpu.groupby_aggregate_many(
                vals, codes, funcs=("mean", "max"), engine="jax")[0],
            "cohorts": lambda: groupby_reduce(
                vals, codes, func="nanmean", method="cohorts", mesh=make_mesh(4))[0],
        }
        events = _capture(fns[call], tmp_path)
        names = {n for n, *_ in events}
        assert self.PHASES <= names, sorted(names)
        root = "flox.groupby_aggregate_many" if call == "aggregate_many" else "flox.groupby_reduce"
        assert root in names
        (_, c0, c1, _), = [ev for ev in events if ev[0] == "caller"]
        for name, s, e, _ in events:
            assert c0 <= s <= e <= c1, name  # inside the caller's own annotation
        # the staging is a child of dispatch, and carries the bytes it staged
        disp = [(s, e) for n, s, e, _ in events if n == "flox.dispatch"]
        for n, s, e, args in events:
            if n == "flox.h2d":
                assert any(d0 <= s <= e <= d1 for d0, d1 in disp)
                assert int(args["bytes"]) > 0
        # telemetry stayed off: nothing recorded, no counter touched
        assert telemetry.spans() == []
        assert telemetry.METRICS.snapshot() == {}

    def test_span_is_noop_outside_a_capture(self, tmp_path):
        import jax

        assert telemetry.span("factorize") is telemetry._NOOP
        jax.profiler.start_trace(str(tmp_path))
        try:
            sp = telemetry.span("factorize", nby=1, funcs=["a"], note=None)
            assert sp is not telemetry._NOOP
            with sp as opened:
                opened.set(ngroups=3)
        finally:
            jax.profiler.stop_trace()
        assert telemetry.span("factorize") is telemetry._NOOP
        assert telemetry.spans() == []

    def test_scalar_attrs_only(self):
        assert telemetry._trace_args(
            {"n": 3, "x": 0.5, "ok": True, "s": "nanmean", "long": "x" * 65,
             "funcs": ["a"], "none": None}
        ) == {"n": 3, "x": 0.5, "ok": True, "s": "nanmean"}

    def test_with_telemetry_on_records_and_annotates(self, tmp_path):
        with flox_tpu.set_options(telemetry=True):
            events = _capture(lambda: _run_reduce()[0], tmp_path)
        assert self.PHASES <= {n for n, *_ in events}
        records = {r["name"] for r in telemetry.spans()}
        assert {"groupby_reduce", "factorize", "dispatch", "h2d", "finalize"} <= records


class TestProgramScopes:
    """The jitted programs' names and the ``flox.*`` scopes in their ops'
    name stacks (the HLO's op_name metadata, which the chip's trace keeps)."""

    @pytest.fixture
    def small_blocks(self, monkeypatch):
        from flox_tpu import utils

        monkeypatch.setattr(utils, "ROW_BLOCK_BYTES", 48 * 8 * 8)  # rows of 48 f64 in blocks of 8
        cache.clear_all()
        yield
        cache.clear_all()

    @staticmethod
    def _scopes(text):
        import re

        return set(re.findall(r"flox\.(?:kernel|rowblock|combine|finalize)", text))

    def test_bundle_and_fused(self, small_blocks, monkeypatch):
        import jax.numpy as jnp

        from flox_tpu import core, fusion

        built = []
        real = core._jitted_bundle

        def recording(*key):
            built.append(real(*key))
            return built[-1]

        recording.cache_clear = real.cache_clear  # cache.clear_all calls it
        monkeypatch.setattr(core, "_jitted_bundle", recording)
        vals = np.random.default_rng(1).normal(size=(40, 48))
        codes = np.arange(48) % 5
        groupby_reduce(vals, codes, func="nanmean", engine="jax")
        flox_tpu.groupby_aggregate_many(vals, codes, funcs=("mean", "var"), engine="jax")
        args = (jnp.asarray(codes), jnp.asarray(vals))
        (bundle,) = built
        text = bundle.lower(*args).as_text(debug_info=True)
        assert "@jit_flox_bundle" in text
        assert {"flox.kernel", "flox.rowblock"} <= self._scopes(text)
        (fused,) = fusion._FUSED_PROGRAM_CACHE.values()
        text = fused.lower(*args).as_text(debug_info=True)
        assert "@jit_flox_fused" in text
        assert {"flox.kernel", "flox.rowblock", "flox.finalize"} <= self._scopes(text)

    def test_mesh_program(self, small_blocks, monkeypatch):
        import jax.numpy as jnp

        from flox_tpu import utils
        from flox_tpu.parallel import mapreduce
        from flox_tpu.parallel.mesh import make_mesh

        monkeypatch.setattr(utils, "ROW_BLOCK_BYTES", 12 * 8 * 8)  # a shard's rows are 12 f64

        vals = np.random.default_rng(2).normal(size=(40, 48))
        codes = np.arange(48) // 4 % 6
        groupby_reduce(vals, codes, func="nanmean", method="cohorts", mesh=make_mesh(4))
        (fn,) = [v for _, v in mapreduce._PROGRAM_CACHE.items()]
        text = fn.lower(jnp.asarray(vals), jnp.asarray(codes, jnp.int32)).as_text(debug_info=True)
        assert "@jit_flox_mesh_cohorts" in text
        assert {"flox.kernel", "flox.rowblock", "flox.combine", "flox.finalize"} <= self._scopes(text)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_compile_counter_nonzero_on_fresh_program(self):
        cache.clear_all()
        with flox_tpu.set_options(telemetry=True):
            # a fresh shape after clear_all: the kernel bundle rebuilds and
            # jax compiles it — both layers must see it
            vals = RNG.normal(size=(2, 101)).astype(np.float64)
            groupby_reduce(vals, np.arange(101) % 6, func="nanmean", engine="jax")
        snap = telemetry.METRICS.snapshot()
        assert snap.get("cache.bundle_builds", 0) >= 1
        assert snap.get("cache.bundle_calls", 0) >= 1
        assert snap.get("jax.compiles", 0) >= 1, snap
        assert snap.get("jax.traces", 0) >= 1
        assert snap.get("jax.compile_ms", 0) > 0

    def test_clear_all_resets_metrics_registry(self):
        with flox_tpu.set_options(telemetry=True):
            _run_reduce()
        assert telemetry.METRICS.snapshot()
        cache.clear_all()
        assert telemetry.METRICS.snapshot() == {}

    def test_h2d_bytes_counted_by_stager(self):
        vals = RNG.normal(size=(2, 64))
        codes = np.arange(64) % 4
        with flox_tpu.set_options(telemetry=True):
            streaming_groupby_reduce(
                lambda s, e: vals[:, s:e], codes, func="nansum", batch_len=16
            )
        # every slab's data + codes crossed H2D at least once
        assert telemetry.METRICS.get("bytes.h2d") >= vals.nbytes

    def test_retry_counter_and_event(self):
        from flox_tpu.resilience import RetryPolicy, call_with_retry

        attempts = {"n": 0}

        def flaky():
            attempts["n"] += 1
            if attempts["n"] < 3:
                raise OSError("transient hiccup")
            return "ok"

        with flox_tpu.set_options(telemetry=True):
            out = call_with_retry(
                flaky, policy=RetryPolicy(retries=5, backoff=0.0), what="[0:8)"
            )
        assert out == "ok"
        assert telemetry.METRICS.get("stream.retries") == 2
        events = [r for r in telemetry.spans() if r["type"] == "event"]
        assert [e["name"] for e in events] == ["retry", "retry"]
        assert events[0]["attrs"]["what"] == "[0:8)"
        assert events[0]["attrs"]["error"] == "OSError"

    def test_profile_call_shape(self):
        profile = telemetry.profile_call(lambda: _run_reduce())
        for key in ("compile_count", "trace_count", "compile_ms", "h2d_bytes",
                    "phase_ms", "cache_sizes"):
            assert key in profile, key
        assert "groupby_reduce" in profile["phase_ms"]
        assert "bundle_lru" in profile["cache_sizes"]
        # profile_call restores the switch: nothing keeps recording after
        from flox_tpu.options import OPTIONS

        assert OPTIONS["telemetry"] is False

    def test_registry_is_threadsafe_counterwise(self):
        import threading

        reg = telemetry.MetricsRegistry()

        def spin():
            for _ in range(1000):
                reg.inc("n")

        threads = [threading.Thread(target=spin) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert reg.get("n") == 4000

    def test_gauges(self):
        reg = telemetry.MetricsRegistry()
        reg.set_gauge("g", 2.0)
        reg.max_gauge("g", 1.0)
        assert reg.get("g") == 2.0
        reg.max_gauge("g", 5.0)
        assert reg.get("g") == 5.0


# ---------------------------------------------------------------------------
# exporters: emit -> parse -> report
# ---------------------------------------------------------------------------


class TestExporters:
    def _instrumented_records(self):
        with flox_tpu.set_options(telemetry=True):
            _run_reduce()
        return telemetry.spans()

    def test_chrome_trace_roundtrip(self, tmp_path):
        records = self._instrumented_records()
        path = tmp_path / "trace.json"
        telemetry.export_chrome_trace(str(path), records)
        payload = json.loads(path.read_text())  # must be ONE valid JSON doc
        events = payload["traceEvents"]
        assert events
        # the Perfetto/Chrome contract: complete events with ts+dur, us units
        for ev in events:
            assert ev["ph"] in ("X", "i")
            assert "ts" in ev and "pid" in ev and "tid" in ev
            if ev["ph"] == "X":
                assert "dur" in ev
        names = {ev["name"] for ev in events}
        for phase in ("groupby_reduce", "factorize", "dispatch", "combine", "finalize"):
            assert phase in names
        assert "floxTpuCounters" in payload

    def test_jsonl_roundtrip_and_report(self, tmp_path):
        records = self._instrumented_records()
        path = tmp_path / "trace.jsonl"
        telemetry.export_jsonl(str(path), records)
        parsed, counters = telemetry._load_export(str(path))
        assert {r["name"] for r in parsed} == {r["name"] for r in records}
        assert counters == telemetry.METRICS.snapshot()
        lines = telemetry._report_lines(str(path))
        text = "\n".join(lines)
        assert "factorize" in text and "dispatch" in text
        assert "cache.bundle_calls" in text

    def test_report_reads_both_formats_identically(self, tmp_path):
        records = self._instrumented_records()
        j = tmp_path / "t.jsonl"
        c = tmp_path / "t.json"
        telemetry.export_jsonl(str(j), records)
        telemetry.export_chrome_trace(str(c), records)
        rows_j = telemetry.summarize(telemetry._load_export(str(j))[0])
        rows_c = telemetry.summarize(telemetry._load_export(str(c))[0])
        assert [r["name"] for r in rows_j] == [r["name"] for r in rows_c]
        assert [r["count"] for r in rows_j] == [r["count"] for r in rows_c]

    def test_report_cli(self, tmp_path, capsys):
        records = self._instrumented_records()
        path = tmp_path / "trace.json"
        telemetry.export_chrome_trace(str(path), records)
        rc = telemetry.main(["report", str(path)])
        assert rc == 0
        out = capsys.readouterr().out
        assert "groupby_reduce" in out
        assert "counters/gauges" in out

    def test_export_path_jsonl_streams_incrementally(self, tmp_path):
        path = tmp_path / "stream.jsonl"
        with flox_tpu.set_options(telemetry=True, telemetry_export_path=str(path)):
            _run_reduce()
            telemetry.flush()
        lines = [json.loads(line) for line in path.read_text().splitlines() if line]
        assert any(r.get("name") == "groupby_reduce" for r in lines)
        assert lines[-1]["type"] == "counters"
        # streamed records left the in-process buffer
        assert telemetry.spans() == []

    def test_export_path_chrome_written_on_flush(self, tmp_path):
        path = tmp_path / "trace.json"
        with flox_tpu.set_options(telemetry=True, telemetry_export_path=str(path)):
            _run_reduce()
            telemetry.flush()
        payload = json.loads(path.read_text())
        assert payload["traceEvents"]
        assert payload["floxTpuCounters"].get("cache.bundle_calls", 0) >= 1

    def test_report_cli_rejects_garbage(self, tmp_path):
        bad = tmp_path / "not-a-trace.json"
        bad.write_text("{definitely not json")
        with pytest.raises(SystemExit):
            telemetry.main(["report", str(bad)])


# ---------------------------------------------------------------------------
# option validation
# ---------------------------------------------------------------------------


class TestOptions:
    def test_validated_at_set_time(self):
        with pytest.raises(ValueError):
            flox_tpu.set_options(telemetry=1)  # bool, not int
        with pytest.raises(ValueError):
            flox_tpu.set_options(telemetry_level="verbose")
        with pytest.raises(ValueError):
            flox_tpu.set_options(telemetry_export_path="")

    def test_context_manager_restores(self):
        from flox_tpu.options import OPTIONS

        before = OPTIONS["telemetry"]
        with flox_tpu.set_options(telemetry=True):
            assert OPTIONS["telemetry"] is True
        assert OPTIONS["telemetry"] is before


# ---------------------------------------------------------------------------
# the acceptance criterion, end to end
# ---------------------------------------------------------------------------


def test_acceptance_perfetto_trace_with_compile_counter(tmp_path):
    """A groupby_reduce with telemetry enabled produces a Perfetto-loadable
    trace containing factorize/dispatch/combine/finalize spans and a nonzero
    compile counter (ISSUE 4 acceptance)."""
    cache.clear_all()
    telemetry.reset()
    path = tmp_path / "acceptance.json"
    with flox_tpu.set_options(telemetry=True):
        vals = RNG.normal(size=(4, 97)).astype(np.float64)
        result, groups = groupby_reduce(
            vals, np.arange(97) % 9, func="nanmean", engine="jax"
        )
        telemetry.export_chrome_trace(str(path))
    payload = json.loads(path.read_text())
    names = {ev["name"] for ev in payload["traceEvents"]}
    assert {"groupby_reduce", "factorize", "dispatch", "combine", "finalize"} <= names
    assert payload["floxTpuCounters"].get("jax.compiles", 0) > 0
    # and the trace is self-describing enough for the report tool
    rows = telemetry.summarize(telemetry._load_export(str(path))[0])
    assert any(r["name"] == "dispatch" for r in rows)


# ---------------------------------------------------------------------------
# histograms (ISSUE 6): log-spaced buckets, p50/p99, report CLI
# ---------------------------------------------------------------------------


class TestHistograms:
    def test_observe_and_percentile(self):
        reg = telemetry.MetricsRegistry()
        for v in (1.0, 1.0, 1.0, 1.0, 100.0):
            reg.observe("lat_ms", v)
        hist = reg.histograms()["lat_ms"]
        assert hist["count"] == 5
        assert hist["sum"] == pytest.approx(104.0)
        assert hist["min"] == 1.0 and hist["max"] == 100.0
        assert sum(hist["counts"]) == 5
        p50 = reg.percentile("lat_ms", 0.50)
        assert 0.5 <= p50 <= 2.1  # inside (or clamped to) the 1.0 bucket
        assert reg.percentile("lat_ms", 0.99) == pytest.approx(100.0)
        assert reg.percentile("lat_ms", 0.0) == 1.0  # clamped to observed min
        assert reg.percentile("unknown", 0.5) is None

    def test_reset_clears_histograms(self):
        telemetry.METRICS.observe("lat_ms", 5.0)
        cache.clear_all()
        assert telemetry.METRICS.histograms() == {}

    def test_spans_feed_histograms_when_enabled(self):
        with flox_tpu.set_options(telemetry=True):
            with telemetry.span("phasex"):
                pass
        hists = telemetry.METRICS.histograms()
        assert "span_ms.phasex" in hists
        assert hists["span_ms.phasex"]["count"] == 1

    def test_disabled_spans_leave_histograms_untouched(self):
        with telemetry.span("phasex"):
            pass
        assert telemetry.METRICS.histograms() == {}

    def test_summarize_has_exact_percentiles(self):
        records = [
            {"type": "span", "name": "p", "dur_us": d * 1e3}
            for d in (1.0, 2.0, 3.0, 4.0, 100.0)
        ]
        row = telemetry.summarize(records)[0]
        assert row["p50_ms"] == 3.0
        assert row["p99_ms"] == 100.0

    def test_exports_carry_histograms_both_formats(self, tmp_path):
        with flox_tpu.set_options(telemetry=True):
            with telemetry.span("phasex"):
                pass
            j, c = tmp_path / "t.jsonl", tmp_path / "t.json"
            telemetry.export_jsonl(str(j))
            telemetry.export_chrome_trace(str(c))
        payload = json.loads(c.read_text())
        assert "span_ms.phasex" in payload["floxTpuHistograms"]
        assert payload["floxTpuHistEdgesMs"] == list(telemetry.HIST_EDGES_MS)
        _, _, hists_j = telemetry._parse_export(str(j))
        _, _, hists_c = telemetry._parse_export(str(c))
        assert hists_j["span_ms.phasex"]["count"] == 1
        assert hists_c["span_ms.phasex"]["count"] == 1

    def test_report_cli_histograms_flag(self, tmp_path, capsys):
        with flox_tpu.set_options(telemetry=True):
            with telemetry.span("phasex"):
                pass
            path = tmp_path / "t.jsonl"
            telemetry.export_jsonl(str(path))
        rc = telemetry.main(["report", str(path), "--histograms"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "histograms" in out
        assert "span_ms.phasex" in out
        assert "p99" in out
        # the default table now carries the exact per-phase percentiles too
        assert "p50 ms" in out and "p99 ms" in out

    def test_report_cli_rejects_malformed_jsonl_line(self, tmp_path, capsys):
        path = tmp_path / "t.jsonl"
        path.write_text(
            '{"type": "span", "name": "ok", "dur_us": 1.0}\n'
            "{broken json line\n"
        )
        with pytest.raises(SystemExit) as exc_info:
            telemetry.main(["report", str(path)])
        assert exc_info.value.code != 0
        err = capsys.readouterr().err
        assert ":2:" in err  # the error names the malformed line

    def test_report_cli_rejects_non_object_jsonl_line(self, tmp_path):
        path = tmp_path / "t.jsonl"
        path.write_text('{"type": "span", "name": "ok", "dur_us": 1.0}\n[1, 2]\n')
        with pytest.raises(SystemExit) as exc_info:
            telemetry.main(["report", str(path)])
        assert exc_info.value.code != 0
