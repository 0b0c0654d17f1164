"""Fault-injection suite for the resilient streaming executor (ISSUE 3).

Every resilience claim is exercised against the deterministic harness in
``flox_tpu.faults``: transient loader faults retry with backoff and leave
the result bit-identical; a fault repeated past ``stream_retries`` surfaces
the ORIGINAL exception; programming errors never retry; a simulated-OOM
slab splits on the power-of-two ladder without retracing the base step
(compile-count asserted); and kill-at-slab-k + resume reproduces the
uninterrupted result exactly — for reduce/scan/quantile, prefetch on and
off, single-device and CPU-mesh shard_map paths.
"""

from __future__ import annotations

import os
import time

import numpy as np
import pytest

import flox_tpu
from flox_tpu import faults
from flox_tpu.resilience import (
    FATAL,
    OOM,
    TRANSIENT,
    _SNAPSHOTS,
    StreamCounters,
    classify_error,
    register_transient,
)
from flox_tpu.streaming import (
    _STEP_CACHE,
    streaming_groupby_reduce,
    streaming_groupby_scan,
)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    n = 3000
    vals = rng.normal(size=(3, n))
    vals[:, ::11] = np.nan
    labels = rng.integers(0, 7, n)
    return vals, labels


@pytest.fixture(autouse=True)
def _clean_snapshots():
    _SNAPSHOTS.clear()
    yield
    _SNAPSHOTS.clear()


def _bits(x):
    return np.ascontiguousarray(np.asarray(x)).tobytes()


# ---------------------------------------------------------------------------
# error taxonomy


class TestClassifier:
    @pytest.mark.parametrize("exc", [
        IOError("read failed"),
        OSError("connection reset"),
        ConnectionError("refused"),
        TimeoutError("slow backend"),
        BrokenPipeError(),
    ])
    def test_io_family_is_transient(self, exc):
        assert classify_error(exc) == TRANSIENT

    @pytest.mark.parametrize("exc", [
        ValueError("bad arg"),
        TypeError("not callable"),
        KeyError("missing"),
        IndexError("oob"),
        NotImplementedError("nope"),
        faults.StreamKilled("preempted"),
        # configuration errors in the OSError family can never succeed on
        # retry: burning the backoff budget on them is the FLX006 hazard
        FileNotFoundError("/wrong/path/chunk.0.0"),
        PermissionError("denied"),
        IsADirectoryError("/data"),
        NotADirectoryError("/data/file/x"),
    ])
    def test_programming_errors_are_fatal(self, exc):
        assert classify_error(exc) == FATAL

    def test_non_recoverable_os_can_opt_back_in(self):
        # an eventually-consistent store whose missing-key reads ARE
        # transient re-registers the type explicitly
        from flox_tpu.resilience import _TRANSIENT_TYPES

        assert classify_error(FileNotFoundError("s3 404")) == FATAL
        register_transient(FileNotFoundError)
        try:
            assert classify_error(FileNotFoundError("s3 404")) == TRANSIENT
        finally:
            _TRANSIENT_TYPES.remove(FileNotFoundError)

    def test_oom_family(self):
        assert classify_error(faults.SimulatedOOM("slab")) == OOM
        assert classify_error(MemoryError()) == OOM
        # the real jaxlib error, classified by name + status token so no
        # version-pinned import is needed
        XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
        assert classify_error(
            XlaRuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating 1 bytes.")
        ) == OOM
        assert classify_error(XlaRuntimeError("UNAVAILABLE: backend rpc")) == TRANSIENT
        assert classify_error(XlaRuntimeError("INVALID_ARGUMENT: shapes")) == FATAL

    def test_register_transient_extends(self):
        class ThrottlingError(Exception):
            pass

        assert classify_error(ThrottlingError()) == FATAL
        register_transient(ThrottlingError)
        assert classify_error(ThrottlingError()) == TRANSIENT
        with pytest.raises(TypeError):
            register_transient("not a type")

    def test_device_loss_family(self):
        from flox_tpu.resilience import DEVICE_LOST

        assert classify_error(faults.SimulatedDeviceLoss("chip 0")) == DEVICE_LOST
        XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
        assert classify_error(
            XlaRuntimeError("INTERNAL: device lost (it crashed)")
        ) == DEVICE_LOST
        assert classify_error(XlaRuntimeError("DEVICE_LOST: gone")) == DEVICE_LOST


class TestClassifierWrappedChains:
    """ISSUE 12 satellite: a transient/oom/device-loss error wrapped in a
    generic RuntimeError (asyncio.to_thread plumbing, loader-SDK
    ``raise ... from exc``) must not be misclassified fatal — the chain is
    walked when the outer verdict is fatal, and ONLY then (an explicitly
    transient outer error never consults its context)."""

    def test_cause_chain_unwraps_transient(self):
        outer = RuntimeError("loader wrapper")
        outer.__cause__ = IOError("read failed")
        assert classify_error(outer) == TRANSIENT

    def test_context_chain_unwraps_transient(self):
        try:
            try:
                raise IOError("flaky read")
            except IOError:
                raise ValueError("raised while handling")  # noqa: B904
        except ValueError as exc:
            assert exc.__context__ is not None
            assert classify_error(exc) == TRANSIENT

    def test_cause_chain_unwraps_oom_and_device_loss(self):
        from flox_tpu.resilience import DEVICE_LOST

        outer = RuntimeError("wrapper")
        outer.__cause__ = MemoryError()
        assert classify_error(outer) == OOM
        outer = KeyError("wrapper")
        outer.__cause__ = faults.SimulatedDeviceLoss("chip")
        assert classify_error(outer) == DEVICE_LOST

    def test_nested_two_level_chain(self):
        inner = OSError("socket reset")
        mid = RuntimeError("mid wrapper")
        mid.__cause__ = inner
        outer = RuntimeError("outer wrapper")
        outer.__cause__ = mid
        assert classify_error(outer) == TRANSIENT

    def test_plain_fatal_stays_fatal(self):
        outer = RuntimeError("genuine bug")
        outer.__cause__ = TypeError("still a bug")
        assert classify_error(outer) == FATAL

    def test_self_referential_chain_terminates(self):
        exc = RuntimeError("cyclic")
        exc.__context__ = exc
        assert classify_error(exc) == FATAL

    def test_transient_outer_never_consults_chain(self):
        # an explicitly transient classification is already the verdict;
        # a fatal link underneath must not harden it
        outer = IOError("transient outer")
        outer.__cause__ = TypeError("fatal inner")
        assert classify_error(outer) == TRANSIENT

    def test_to_thread_propagated_exception_keeps_class(self):
        import asyncio

        async def main():
            def boom():
                raise IOError("raised inside to_thread")

            try:
                await asyncio.to_thread(boom)
            except Exception as exc:  # noqa: BLE001 — classifying is the test
                return classify_error(exc)

        assert asyncio.run(main()) == TRANSIENT


class TestBackoffJitter:
    """ISSUE 12 satellite: full jitter on the exponential backoff, so
    prefetch workers hitting the same transient fault do not retry in
    lockstep — seedable for deterministic chaos runs."""

    def test_full_jitter_spreads_within_cap(self):
        from flox_tpu.resilience import RetryPolicy, seed_backoff

        seed_backoff(7)
        policy = RetryPolicy(backoff=0.1)
        delays = [policy.delay(2) for _ in range(64)]
        cap = 0.1 * 4
        assert all(0 < d <= cap for d in delays)
        # genuinely jittered: not all equal, and spread across the window
        assert len({round(d, 9) for d in delays}) > 8
        assert min(delays) < cap / 4 and max(delays) > cap / 2

    def test_seeded_schedule_is_reproducible(self):
        from flox_tpu.resilience import RetryPolicy, seed_backoff

        policy = RetryPolicy(backoff=0.05)
        seed_backoff(123)
        first = [policy.delay(a) for a in range(6)]
        seed_backoff(123)
        assert [policy.delay(a) for a in range(6)] == first

    def test_zero_backoff_stays_zero(self):
        from flox_tpu.resilience import RetryPolicy

        assert RetryPolicy(backoff=0.0).delay(3) == 0.0

    def test_jittered_retries_stay_bit_identical(self, data):
        # the jitter changes WHEN retries fire, never WHAT they compute
        from flox_tpu.resilience import seed_backoff

        vals, labels = data
        base, _ = streaming_groupby_reduce(vals, labels, func="nanmean", batch_len=700)
        seed_backoff(99)
        flaky = faults.FlakyLoader(lambda s, e: vals[:, s:e], {700: IOError}, times=2)
        with flox_tpu.set_options(stream_backoff=0.001):
            got, _ = streaming_groupby_reduce(
                flaky, labels, func="nanmean", batch_len=700
            )
        assert _bits(got) == _bits(base)
        assert flaky.loads_of(700) == 3


# ---------------------------------------------------------------------------
# retry with backoff + per-slab deadline


class TestRetryBackoff:
    @pytest.mark.parametrize("depth", [0, 2])
    def test_transient_fault_retried_bit_identical(self, data, depth):
        vals, labels = data
        base, _ = streaming_groupby_reduce(vals, labels, func="nanmean", batch_len=700)
        flaky = faults.FlakyLoader(lambda s, e: vals[:, s:e], {1400: IOError}, times=2)
        with flox_tpu.set_options(stream_prefetch=depth, stream_backoff=0.001):
            from flox_tpu import profiling

            with profiling.stream_monitor() as reports:
                got, _ = streaming_groupby_reduce(
                    flaky, labels, func="nanmean", batch_len=700
                )
        assert _bits(got) == _bits(base)
        assert flaky.loads_of(1400) == 3  # 2 injected failures + the success
        # the retries flow into the StreamReport counters
        assert reports[0].retries == 2
        assert reports[0].backoff_ms > 0
        assert "retries 2" in reports[0].summary()

    @pytest.mark.parametrize("depth", [0, 3])
    def test_exhausted_retries_surface_original_exception(self, data, depth):
        # acceptance: a fault injected stream_retries + 1 times surfaces the
        # ORIGINAL exception (not a wrapper), promptly, pool torn down
        import threading

        vals, labels = data
        with flox_tpu.set_options(
            stream_prefetch=depth, stream_retries=2, stream_backoff=0.001
        ):
            flaky = faults.FlakyLoader(
                lambda s, e: vals[:, s:e], {1400: IOError("loader died at 1400")},
                times=3,
            )
            with pytest.raises(IOError, match="loader died at 1400"):
                streaming_groupby_reduce(flaky, labels, func="nanmean", batch_len=700)
        time.sleep(0.05)
        assert not [t for t in threading.enumerate() if "flox-tpu-stage" in t.name]

    def test_fatal_error_never_retried(self, data):
        vals, labels = data
        flaky = faults.FlakyLoader(
            lambda s, e: vals[:, s:e], {1400: TypeError("bug, not weather")}, times=-1
        )
        with flox_tpu.set_options(stream_retries=5, stream_backoff=0.001):
            with pytest.raises(TypeError, match="bug, not weather"):
                streaming_groupby_reduce(flaky, labels, func="nanmean", batch_len=700)
        assert flaky.loads_of(1400) == 1  # one attempt, zero retries

    def test_slab_deadline_bounds_backoff(self, data):
        vals, labels = data
        flaky = faults.FlakyLoader(lambda s, e: vals[:, s:e], {1400: IOError}, times=-1)
        t0 = time.perf_counter()
        with flox_tpu.set_options(
            stream_retries=50, stream_backoff=30.0, stream_slab_timeout=0.05
        ):
            with pytest.raises(TimeoutError, match="stream_slab_timeout"):
                streaming_groupby_reduce(flaky, labels, func="nanmean", batch_len=700)
        # the deadline refuses the 30 s backoff sleep instead of serving it
        assert time.perf_counter() - t0 < 10.0

    def test_scan_and_quantile_retry_too(self, data):
        vals, labels = data
        base_scan = streaming_groupby_scan(vals, labels, func="nancumsum", batch_len=700)
        flaky = faults.FlakyLoader(lambda s, e: vals[:, s:e], {1400: IOError}, times=1)
        with flox_tpu.set_options(stream_backoff=0.001):
            got = streaming_groupby_scan(flaky, labels, func="nancumsum", batch_len=700)
        assert _bits(got) == _bits(base_scan)

        v32 = vals.astype(np.float32)
        base_q, _ = streaming_groupby_reduce(v32, labels, func="nanmedian", batch_len=1000)
        flaky_q = faults.FlakyLoader(lambda s, e: v32[:, s:e], {1000: IOError}, times=2)
        with flox_tpu.set_options(stream_backoff=0.001):
            got_q, _ = streaming_groupby_reduce(
                flaky_q, labels, func="nanmedian", batch_len=1000
            )
        assert _bits(got_q) == _bits(base_q)


# ---------------------------------------------------------------------------
# graceful OOM degradation: halve + re-stage on the power-of-two ladder


class TestOOMSplit:
    def test_reduce_split_completes_and_matches(self, data):
        vals, labels = data
        ref, _ = streaming_groupby_reduce(vals, labels, func="nanmean", batch_len=700)
        from flox_tpu import profiling

        with faults.inject(oom_at=[1400]) as plan:
            with profiling.stream_monitor() as reports:
                got, _ = streaming_groupby_reduce(
                    vals, labels, func="nanmean", batch_len=700
                )
        assert [rec for rec in plan.log if rec[0] == "SimulatedOOM"] == [
            ("SimulatedOOM", 1400, 2100)
        ]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=1e-12, equal_nan=True
        )
        assert reports[0].oom_splits == 1
        assert "oom-splits 1" in reports[0].summary()

    def test_position_reductions_split_exactly(self, data):
        # argmax positions are integers: sub-slab offsets must be exact
        vals, labels = data
        v = np.nan_to_num(vals, nan=0.5)
        ref, _ = streaming_groupby_reduce(v, labels, func="argmax", batch_len=700)
        with faults.inject(oom_at=[700, 2100]):
            got, _ = streaming_groupby_reduce(v, labels, func="argmax", batch_len=700)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_base_step_not_retraced_ladder_reused(self, data):
        # acceptance: the split completes WITHOUT retracing the base step —
        # sub-slabs pad to a power-of-two rung that compiles once and is
        # reused by every later split
        vals, labels = data
        _STEP_CACHE.clear()
        ref, _ = streaming_groupby_reduce(vals, labels, func="sum", batch_len=500)
        step = next(v for k, v in _STEP_CACHE.items() if k[0] == "reduce-step")
        base_traces = step._jitted._cache_size()
        with faults.inject(oom_at=[1000, 2500]) as plan:
            got, _ = streaming_groupby_reduce(vals, labels, func="sum", batch_len=500)
        assert sum(1 for rec in plan.log if rec[0]) == 2  # both slabs split
        # ONE new trace: the 256-wide rung, shared by both split slabs
        assert step._jitted._cache_size() == base_traces + 1
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-12)
        # a later run splitting a third slab reuses the rung: no new traces
        with faults.inject(oom_at=[2000]):
            streaming_groupby_reduce(vals, labels, func="sum", batch_len=500)
        assert step._jitted._cache_size() == base_traces + 1

    def test_recursive_split(self, data):
        # oom_times=2: the first re-staged sub-slab (same start offset)
        # OOMs again and splits one rung deeper
        vals, labels = data
        ref, _ = streaming_groupby_reduce(vals, labels, func="sum", batch_len=700)
        counters_seen = []
        from flox_tpu import profiling

        with faults.inject(oom_at=[1400], oom_times=2):
            with profiling.stream_monitor() as reports:
                got, _ = streaming_groupby_reduce(
                    vals, labels, func="sum", batch_len=700
                )
        counters_seen.append(reports[0].oom_splits)
        assert counters_seen[0] == 2
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-12)

    def test_ladder_half_descends_for_any_quantum(self):
        from flox_tpu.resilience import _ladder_half

        # power-of-two quanta: pure pow2 ladder
        assert _ladder_half(1000, 1) == 512
        assert _ladder_half(512, 1) == 256
        assert _ladder_half(3, 1) == 2
        assert _ladder_half(1000, 8) == 512
        # non-power-of-two quanta must still descend: rounding the pow2
        # rung up to the quantum may reach the span itself, where the
        # largest quantum multiple below it is the legal split
        assert _ladder_half(24, 6) == 18
        assert _ladder_half(18, 6) == 12
        assert _ladder_half(12, 6) == 6
        for quantum in (1, 2, 3, 5, 6, 7, 8):
            length = 16 * quantum
            while length > quantum:
                half = _ladder_half(length, quantum)
                assert quantum <= half < length and half % quantum == 0, (
                    length, quantum, half,
                )
                length = half

    def test_unsplittable_oom_surfaces(self, data):
        # a slab that OOMs at EVERY granularity cannot degrade: the original
        # resource-exhausted error surfaces once the ladder hits bottom
        vals, labels = data
        with faults.inject(oom_at=[1400], oom_times=-1):
            with pytest.raises(faults.SimulatedOOM, match="RESOURCE_EXHAUSTED"):
                streaming_groupby_reduce(vals, labels, func="sum", batch_len=700)

    def test_scan_split_forward_and_reverse(self, data):
        vals, labels = data
        for func in ("nancumsum", "bfill"):
            ref = streaming_groupby_scan(vals, labels, func=func, batch_len=700)
            with faults.inject(oom_at=[1400]):
                got = streaming_groupby_scan(vals, labels, func=func, batch_len=700)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref), rtol=1e-12, atol=1e-12,
                equal_nan=True,
            )

    def test_quantile_split(self, data):
        vals, labels = data
        v32 = vals.astype(np.float32)
        ref, _ = streaming_groupby_reduce(v32, labels, func="nanmedian", batch_len=1000)
        with faults.inject(oom_at=[1000]):
            got, _ = streaming_groupby_reduce(v32, labels, func="nanmedian", batch_len=1000)
        # counting passes are exact: the split result is bit-identical
        assert _bits(got) == _bits(ref)

    def test_mesh_split_positions_exact(self, data):
        from flox_tpu.parallel.mesh import make_mesh

        vals, labels = data
        v = np.nan_to_num(vals, nan=0.5)[:, :2400]
        lab = labels[:2400]
        mesh = make_mesh()
        ref, _ = streaming_groupby_reduce(v, lab, func="argmax", batch_len=800, mesh=mesh)
        with faults.inject(oom_at=[800]):
            got, _ = streaming_groupby_reduce(
                v, lab, func="argmax", batch_len=800, mesh=mesh
            )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# checkpoint / resume: kill-at-slab-k is bit-identical to uninterrupted


class _KillScenario:
    """One kill+resume scenario: ``baseline()`` the uninterrupted bytes,
    ``prepare()`` resets state before the killed attempt, ``run()`` executes
    (raising StreamKilled under the plan) and returns result bytes.

    The scan scenario streams through a writer into a NaN-poisoned buffer:
    the killed run writes slabs [0, k), the resumed run rewrites from the
    checkpoint cursor on — any slab NEITHER covers stays NaN and fails the
    byte comparison, so the test cannot pass by accident of leftover state.
    """

    def __init__(self, kind, vals, labels, mesh=None, batch_len=500):
        self.kind = kind
        self.labels = labels
        self.batch_len = batch_len
        self.mesh_kw = {} if mesh is None else {"mesh": mesh}
        # f32 keys keep the quantile at 33 passes instead of 65
        self.vals = vals.astype(np.float32) if kind == "quantile" else vals
        if kind == "scan":
            self.buf = np.full(vals.shape, np.nan)
            self.kill_plan = {"kill_at": [2 * batch_len]}
        elif kind == "reduce":
            self.kill_plan = {"kill_at": [2 * batch_len]}
        else:  # kill inside the quantile bit passes, past the count pass
            self.kill_plan = {"kill_after": 8}

    def prepare(self):
        if self.kind == "scan":
            self.buf[...] = np.nan

    def run(self):
        if self.kind == "scan":
            r = streaming_groupby_scan(
                self.vals, self.labels, func="nancumsum", batch_len=self.batch_len,
                out=lambda s, e, res: self.buf.__setitem__((..., slice(s, e)), res),
                **self.mesh_kw,
            )
            assert r is None
            return self.buf.tobytes()
        func = "nanmedian" if self.kind == "quantile" else "nanmean"
        got, _ = streaming_groupby_reduce(
            self.vals, self.labels, func=func, batch_len=self.batch_len,
            **self.mesh_kw,
        )
        return _bits(got)

    def baseline(self):
        self.prepare()
        return self.run()


class TestKillResume:
    @pytest.mark.parametrize("depth", [0, 2])
    @pytest.mark.parametrize("kind", ["reduce", "scan", "quantile"])
    def test_single_device_bit_identical(self, data, kind, depth):
        vals, labels = data
        sc = _KillScenario(kind, vals, labels)
        with flox_tpu.set_options(stream_prefetch=depth):
            base = sc.baseline()
            with flox_tpu.set_options(stream_checkpoint_every=2):
                sc.prepare()
                with faults.inject(**sc.kill_plan):
                    with pytest.raises(faults.StreamKilled):
                        sc.run()
                assert len(_SNAPSHOTS) == 1
                from flox_tpu import profiling

                with profiling.stream_monitor() as reports:
                    resumed = sc.run()
                assert reports[-1].counters.resumed_at is not None
        assert resumed == base  # byte strings
        assert _SNAPSHOTS == {}  # done() dropped the snapshot

    @pytest.mark.parametrize("kind", ["reduce", "scan", "quantile"])
    def test_mesh_bit_identical(self, data, kind):
        from flox_tpu.parallel.mesh import make_mesh

        vals, labels = data
        sc = _KillScenario(
            kind, vals[:, :2400], labels[:2400], mesh=make_mesh(), batch_len=800
        )
        base = sc.baseline()
        with flox_tpu.set_options(stream_checkpoint_every=1):
            sc.prepare()
            with faults.inject(**sc.kill_plan):
                with pytest.raises(faults.StreamKilled):
                    sc.run()
            assert len(_SNAPSHOTS) == 1
            resumed = sc.run()
        assert resumed == base

    def test_resume_skips_processed_slabs(self, data):
        vals, labels = data
        calls = []

        def loader(s, e):
            calls.append((s, e))
            return vals[:, s:e]

        base, _ = streaming_groupby_reduce(vals, labels, func="nanmean", batch_len=500)
        with flox_tpu.set_options(stream_checkpoint_every=2):
            with faults.inject(kill_at=[4 * 500]):
                with pytest.raises(faults.StreamKilled):
                    streaming_groupby_reduce(loader, labels, func="nanmean", batch_len=500)
            calls.clear()
            got, _ = streaming_groupby_reduce(loader, labels, func="nanmean", batch_len=500)
        assert _bits(got) == _bits(base)
        # slabs before the checkpoint cursor were NOT re-read (the probe
        # loader(0, 1) is the only touch below it)
        assert not [c for c in calls if c[0] == 0 and c[1] - c[0] > 1]
        assert min(s for s, e in calls if e - s > 1) == 4 * 500

    def test_npz_spill_survives_process_death(self, data, tmp_path):
        vals, labels = data
        base, _ = streaming_groupby_reduce(vals, labels, func="nanmean", batch_len=500)
        with flox_tpu.set_options(
            stream_checkpoint_every=2, stream_checkpoint_path=str(tmp_path)
        ):
            with faults.inject(kill_at=[4 * 500]):
                with pytest.raises(faults.StreamKilled):
                    streaming_groupby_reduce(vals, labels, func="nanmean", batch_len=500)
            spilled = list(tmp_path.glob("*.npz"))
            assert len(spilled) == 1
            # "new process": the in-memory registry is gone, only the file
            _SNAPSHOTS.clear()
            got, _ = streaming_groupby_reduce(vals, labels, func="nanmean", batch_len=500)
            assert _bits(got) == _bits(base)
            assert list(tmp_path.glob("*.npz")) == []  # done() removed it

    def test_corrupt_spill_falls_back_to_fresh_run(self, data, tmp_path):
        vals, labels = data
        base, _ = streaming_groupby_reduce(vals, labels, func="nanmean", batch_len=500)
        target = tmp_path / "snap.npz"
        target.write_bytes(b"not an npz at all")
        with flox_tpu.set_options(
            stream_checkpoint_every=2, stream_checkpoint_path=str(target)
        ):
            got, _ = streaming_groupby_reduce(vals, labels, func="nanmean", batch_len=500)
        assert _bits(got) == _bits(base)

    def test_scan_without_writer_not_checkpointed(self, data):
        # no writer = nowhere for already-emitted slabs to survive a kill,
        # so the scan takes no snapshots rather than promising a resume it
        # cannot honor
        vals, labels = data
        with flox_tpu.set_options(stream_checkpoint_every=1):
            streaming_groupby_scan(vals, labels, func="nancumsum", batch_len=500)
            with faults.inject(kill_at=[3 * 500]):
                with pytest.raises(faults.StreamKilled):
                    streaming_groupby_scan(vals, labels, func="nancumsum", batch_len=500)
            assert _SNAPSHOTS == {}

    def test_disabled_by_default(self, data):
        vals, labels = data
        with faults.inject(kill_at=[2 * 500]):
            with pytest.raises(faults.StreamKilled):
                streaming_groupby_reduce(vals, labels, func="nanmean", batch_len=500)
        assert _SNAPSHOTS == {}

    def test_different_agg_identity_misses_stale_snapshot(self, data):
        # the reduce key carries the RESOLVED aggregation identity: a
        # dtype= override changes the accumulators, so a snapshot from the
        # float32 run must not fold into the float64 rerun
        vals, labels = data
        v32 = np.nan_to_num(vals, nan=0.0).astype(np.float32)
        base64, _ = streaming_groupby_reduce(
            v32, labels, func="nansum", dtype=np.float64, batch_len=500
        )
        with flox_tpu.set_options(stream_checkpoint_every=2):
            with faults.inject(kill_at=[4 * 500]):
                with pytest.raises(faults.StreamKilled):
                    streaming_groupby_reduce(
                        v32, labels, func="nansum", dtype=np.float32, batch_len=500
                    )
            assert len(_SNAPSHOTS) == 1
            got, _ = streaming_groupby_reduce(
                v32, labels, func="nansum", dtype=np.float64, batch_len=500
            )
        assert _bits(got) == _bits(base64)
        assert len(_SNAPSHOTS) == 1  # the float32 snapshot was never touched

    def test_scan_checkpoint_identity_distinguishes_custom_scans(self):
        # a custom Scan sharing a builtin's name must produce a different
        # checkpoint identity — resuming a cumsum snapshot into a custom
        # same-named scan would silently fold mismatched carries
        from flox_tpu.aggregations import SCANS, Scan
        from flox_tpu.streaming import _scan_ckpt_id

        builtin = SCANS["cumsum"]
        custom = Scan(
            "cumsum", scan="cumsum", reduction="sum",
            binary_op=lambda a, b: a + b, identity=0,
        )
        assert _scan_ckpt_id(custom) != _scan_ckpt_id(builtin)
        assert _scan_ckpt_id(builtin) == _scan_ckpt_id(SCANS["cumsum"])

    def test_changed_data_tripwire_misses_stale_snapshot(self, data):
        # the checkpoint key fingerprints the probe slab: a run over edited
        # data must NOT resume from the old run's snapshot (which would
        # silently fold stale state into the new values)
        vals, labels = data
        v2 = vals.copy()
        v2[:, 0] = 5.0  # the fixture's column 0 is NaN: give the probe new bytes
        base2, _ = streaming_groupby_reduce(v2, labels, func="nanmean", batch_len=500)
        with flox_tpu.set_options(stream_checkpoint_every=2):
            with faults.inject(kill_at=[4 * 500]):
                with pytest.raises(faults.StreamKilled):
                    streaming_groupby_reduce(vals, labels, func="nanmean", batch_len=500)
            assert len(_SNAPSHOTS) == 1
            got, _ = streaming_groupby_reduce(v2, labels, func="nanmean", batch_len=500)
        assert _bits(got) == _bits(base2)
        assert len(_SNAPSHOTS) == 1  # the stale v1 snapshot was never touched

    def test_clear_all_drops_snapshots(self, data):
        vals, labels = data
        with flox_tpu.set_options(stream_checkpoint_every=1):
            with faults.inject(kill_at=[2 * 500]):
                with pytest.raises(faults.StreamKilled):
                    streaming_groupby_reduce(vals, labels, func="nanmean", batch_len=500)
        assert len(_SNAPSHOTS) == 1
        flox_tpu.cache.clear_all()
        assert _SNAPSHOTS == {}


# ---------------------------------------------------------------------------
# loader contract


class TestLoaderContract:
    @pytest.mark.parametrize("depth", [0, 2])
    def test_wrong_shape_names_slab_range(self, data, depth):
        vals, labels = data
        bad = faults.misshaping_loader(lambda s, e: vals[:, s:e], at=1400, shape=(3, 11))
        with flox_tpu.set_options(stream_prefetch=depth):
            with pytest.raises(ValueError, match=r"slab \[1400:2100\).*\(3, 11\)"):
                streaming_groupby_reduce(bad, labels, func="nanmean", batch_len=700)

    def test_dtype_drift_names_slab_range(self, data):
        vals, labels = data

        def bad(s, e):
            sl = vals[:, s:e]
            return sl.astype(np.float32) if s >= 1400 else sl

        with pytest.raises(ValueError, match=r"slab \[1400:2100\).*float32"):
            streaming_groupby_reduce(bad, labels, func="nanmean", batch_len=700)

    def test_contract_violation_not_retried(self, data):
        vals, labels = data
        calls = []

        def bad(s, e):
            calls.append((s, e))
            if s == 1400:
                return np.zeros((3, 5))
            return vals[:, s:e]

        with flox_tpu.set_options(stream_retries=5, stream_backoff=0.001):
            with pytest.raises(ValueError, match="loader contract"):
                streaming_groupby_reduce(bad, labels, func="nanmean", batch_len=700)
        assert len([c for c in calls if c[0] == 1400]) == 1


# ---------------------------------------------------------------------------
# option validation (set-time, not mid-stream)


class TestOptionValidation:
    @pytest.mark.parametrize("kwargs", [
        {"stream_retries": -1},
        {"stream_retries": 2.5},
        {"stream_retries": True},
        {"stream_backoff": -0.1},
        {"stream_backoff": "fast"},
        {"stream_backoff": float("nan")},
        {"stream_backoff": float("inf")},
        {"stream_slab_timeout": float("nan")},
        {"stream_slab_timeout": -1},
        {"stream_checkpoint_every": -2},
        {"stream_checkpoint_every": 1.5},
        {"stream_checkpoint_path": ""},
        {"stream_checkpoint_path": 123},
        {"stream_prefetch": -1},
        {"stream_prefetch": True},
        {"stream_dispatch_depth": -2},
    ])
    def test_invalid_values_raise_at_set_time(self, kwargs):
        with pytest.raises(ValueError):
            flox_tpu.set_options(**kwargs)

    def test_valid_values_roundtrip(self, tmp_path):
        from flox_tpu.options import OPTIONS

        before = {k: OPTIONS[k] for k in OPTIONS}
        with flox_tpu.set_options(
            stream_retries=0, stream_backoff=0.0, stream_slab_timeout=1.5,
            stream_checkpoint_every=10, stream_checkpoint_path=str(tmp_path),
        ):
            assert OPTIONS["stream_checkpoint_every"] == 10
        assert {k: OPTIONS[k] for k in OPTIONS} == before
        # pathlib.Path is a filesystem option: accepted, not rejected
        with flox_tpu.set_options(stream_checkpoint_path=tmp_path):
            assert OPTIONS["stream_checkpoint_path"] == tmp_path

    def test_env_mirrors_follow_validator_bounds(self):
        # malformed/out-of-bounds env values fall back instead of breaking
        # import — mirroring the _env_int contract
        from flox_tpu.options import _env_float, _env_int

        os.environ["_FLOX_TEST_ENV"] = "-3"
        try:
            assert _env_int("_FLOX_TEST_ENV", 2, 0) == 2
            assert _env_float("_FLOX_TEST_ENV", 0.5) == 0.5
            os.environ["_FLOX_TEST_ENV"] = "junk"
            assert _env_int("_FLOX_TEST_ENV", 2, 0) == 2
            assert _env_float("_FLOX_TEST_ENV", 0.5) == 0.5
            os.environ["_FLOX_TEST_ENV"] = "0.25"
            assert _env_float("_FLOX_TEST_ENV", 0.5) == 0.25
            # nan would reach time.sleep mid-retry, inf would sleep forever:
            # the env cannot seed what set_options refuses
            for bad in ("nan", "inf", "-inf"):
                os.environ["_FLOX_TEST_ENV"] = bad
                assert _env_float("_FLOX_TEST_ENV", 0.5) == 0.5
        finally:
            del os.environ["_FLOX_TEST_ENV"]

    def test_env_float_open_and_upper_bounds(self):
        # ISSUE 5 (FLX010): every OPTIONS field now has an env mirror, which
        # needs _env_float to express `0 < x <= 1`-shaped validator bounds
        from flox_tpu.options import _env_float

        try:
            os.environ["_FLOX_TEST_ENV"] = "0"
            assert _env_float("_FLOX_TEST_ENV", 0.25, 0.0, 1.0, lo_open=True) == 0.25
            os.environ["_FLOX_TEST_ENV"] = "1.5"
            assert _env_float("_FLOX_TEST_ENV", 0.25, 0.0, 1.0, lo_open=True) == 0.25
            os.environ["_FLOX_TEST_ENV"] = "0.75"
            assert _env_float("_FLOX_TEST_ENV", 0.25, 0.0, 1.0, lo_open=True) == 0.75
            os.environ["_FLOX_TEST_ENV"] = "1.0"
            assert _env_float("_FLOX_TEST_ENV", 0.25, 0.0, 1.0, lo_open=True) == 1.0
        finally:
            del os.environ["_FLOX_TEST_ENV"]

    def test_every_option_has_env_mirror(self):
        # the static FLX010 contract, asserted at runtime too: re-importing
        # options with a mirror set must seed the field; invalid values fall
        # back (the cannot-seed-what-set_options-refuses contract)
        import importlib
        import flox_tpu.options as options_mod

        probes = {
            "FLOX_TPU_DEFAULT_ENGINE": ("default_engine", "numpy", "bogus"),
            "FLOX_TPU_QUANTILE_IMPL": ("quantile_impl", "select", "bogus"),
            "FLOX_TPU_MATMUL_NUM_GROUPS_MAX": ("matmul_num_groups_max", 77, "junk"),
            "FLOX_TPU_STREAM_DONATE": ("stream_donate", "off", "maybe"),
        }
        saved = {k: os.environ.get(k) for k in probes}
        # every module that imported OPTIONS holds the original object; a
        # reload rebinds the module's names, so they are put back after it
        module_names = dict(vars(options_mod))
        try:
            for env, (field, good, _bad) in probes.items():
                os.environ[env] = str(good)
            mod = importlib.reload(options_mod)
            for env, (field, good, _bad) in probes.items():
                assert mod.OPTIONS[field] == good, field
            for env, (field, _good, bad) in probes.items():
                os.environ[env] = str(bad)
            defaults = {"default_engine": "jax", "quantile_impl": "auto",
                        "matmul_num_groups_max": 384, "stream_donate": "auto"}
            mod = importlib.reload(options_mod)
            for field, expected in defaults.items():
                assert mod.OPTIONS[field] == expected, field
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            vars(options_mod).update(module_names)


# ---------------------------------------------------------------------------
# the harness itself


class TestFaultHarness:
    def test_plan_is_deterministic(self, data):
        vals, labels = data
        logs = []
        for _ in range(2):
            with faults.inject(oom_at=[1400]) as plan:
                streaming_groupby_reduce(vals, labels, func="sum", batch_len=700)
            logs.append(list(plan.log))
        assert logs[0] == logs[1]
        assert ("SimulatedOOM", 1400, 2100) in logs[0]

    def test_inject_nests_and_restores(self):
        assert not faults.active()
        with faults.inject(kill_after=100):
            assert faults.active()
            with faults.inject(oom_at=[0]):
                assert faults.active()
            assert faults.active()
        assert not faults.active()

    def test_poke_noop_without_plan(self):
        faults.poke(0, 100)  # must not raise

    def test_counters_are_threadsafe_accumulators(self):
        c = StreamCounters()
        import threading

        def spin():
            for _ in range(1000):
                c.record_retry(0.001)

        ts = [threading.Thread(target=spin) for _ in range(4)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert c.retries == 4000
        assert abs(c.backoff_ms - 4000 * 1.0) < 1e-6
