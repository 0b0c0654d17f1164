"""Autotuner test suite (ISSUE 6).

The contract under test: with the tuner OFF (the default, record-only mode)
every ``auto`` dispatch decision is bit-identical to the static heuristics
and recording still accretes; with it ON, decisions come from the store's
observed winners (nearest measured band), a persisted store serves a fresh
process without re-sweeping (the two-process smoke, asserted by the
sweep/hit counters), ``cache.clear_all`` resets the in-memory store, and a
corrupt or partial cache file falls back to heuristics with a warning.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import flox_tpu
from flox_tpu import autotune, cache
from flox_tpu.core import groupby_reduce

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_store():
    """Every test starts from an empty in-memory store with the tuner OFF
    and no persistence path — even under the CI FLOX_TPU_AUTOTUNE=1 leg,
    so the off-mode assertions test the option, not the environment."""
    with flox_tpu.set_options(autotune=False, autotune_cache_path=None):
        cache.clear_all()
        yield
        cache.clear_all()


def _seed_segment_sum(winner="matmul", loser="scatter", **kw):
    keykw = dict(dtype="float32", ngroups=12, nelems=1 << 20)
    keykw.update(kw)
    autotune.record("segment_sum", winner, 50.0, **keykw)
    autotune.record("segment_sum", loser, 10.0, **keykw)
    return keykw


# ---------------------------------------------------------------------------
# key schema + store mechanics
# ---------------------------------------------------------------------------


class TestStore:
    def test_make_key_bands(self):
        k = autotune.make_key(
            "segment_sum", dtype="float32", ngroups=12, nelems=1 << 20,
            platform="cpu",
        )
        assert k == "segment_sum|cpu|float32|g4|e11"
        # ngroups/nelems in the same band share the key; a decade apart differ
        same = autotune.make_key(
            "segment_sum", dtype="float32", ngroups=15, nelems=(1 << 20) + 7,
            platform="cpu",
        )
        assert same == k
        far = autotune.make_key(
            "segment_sum", dtype="float32", ngroups=12, nelems=1 << 28,
            platform="cpu",
        )
        assert far != k

    def test_record_then_decide(self):
        kw = _seed_segment_sum()
        # off: fallback always wins (record-only mode)
        assert (
            autotune.decide("segment_sum", "scatter", ["scatter", "matmul"], **kw)
            == "scatter"
        )
        with flox_tpu.set_options(autotune=True):
            assert (
                autotune.decide("segment_sum", "scatter", ["scatter", "matmul"], **kw)
                == "matmul"
            )

    def test_decide_restricted_to_eligible_candidates(self):
        kw = _seed_segment_sum()
        with flox_tpu.set_options(autotune=True):
            # the winner is not eligible on this call -> next best eligible
            assert (
                autotune.decide("segment_sum", "scatter", ["scatter"], **kw)
                == "scatter"
            )

    def test_nearest_band_lookup_with_tolerance(self):
        _seed_segment_sum(nelems=1 << 20)
        with flox_tpu.set_options(autotune=True):
            # 4x away in elements: within the kernel-family tolerance
            assert (
                autotune.decide(
                    "segment_sum", "scatter", ["scatter", "matmul"],
                    dtype="float32", ngroups=12, nelems=1 << 22,
                )
                == "matmul"
            )
            # the engine family is strict: records must not stretch bands
            autotune.record("engine", "numpy", 99.0, dtype="float64", nelems=1 << 8)
            autotune.record("engine", "jax", 1.0, dtype="float64", nelems=1 << 8)
            assert (
                autotune.decide(
                    "engine", "jax", ["numpy", "jax"],
                    dtype="float64", nelems=1 << 24,
                )
                == "jax"
            )

    def test_ewma_flips_winner_and_bumps_version(self):
        kw = dict(dtype="float32", ngroups=12, nelems=1 << 20)
        autotune.record("segment_sum", "scatter", 10.0, **kw)
        with flox_tpu.set_options(autotune=True):
            v0 = autotune.decision_fingerprint()
            autotune.record("segment_sum", "matmul", 50.0, **kw)
            v1 = autotune.decision_fingerprint()
            assert v1 != v0  # the flip must invalidate compiled programs
            autotune.record("segment_sum", "matmul", 60.0, **kw)
            assert autotune.decision_fingerprint() == v1  # no flip, no bump

    def test_fingerprint_constant_when_disabled(self):
        fp0 = autotune.decision_fingerprint()
        _seed_segment_sum()
        assert autotune.decision_fingerprint() == fp0 == (False,)
        from flox_tpu.options import trace_fingerprint

        assert trace_fingerprint()[-1] == (False,)

    def test_clear_all_resets_in_memory_store(self):
        kw = _seed_segment_sum()
        assert cache.stats()["autotune"] > 0
        cache.clear_all()
        assert cache.stats()["autotune"] == 0
        with flox_tpu.set_options(autotune=True):
            assert (
                autotune.decide("segment_sum", "scatter", ["scatter", "matmul"], **kw)
                == "scatter"
            )
        assert autotune.decision_record()["sweeps"] == 0


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------


class TestPersistence:
    def test_round_trip_same_decision(self, tmp_path):
        kw = _seed_segment_sum()
        path = str(tmp_path / "autotune.json")
        assert autotune.save(path) == path
        cache.clear_all()
        with flox_tpu.set_options(autotune=True, autotune_cache_path=path):
            # lazy reload at first consult: same decision as before the clear
            assert (
                autotune.decide("segment_sum", "scatter", ["scatter", "matmul"], **kw)
                == "matmul"
            )

    def test_save_is_atomic_no_tmp_left_behind(self, tmp_path):
        _seed_segment_sum()
        path = str(tmp_path / "store" / "autotune.json")
        autotune.save(path)
        assert json.load(open(path))["records"]
        leftovers = [f for f in os.listdir(tmp_path / "store") if f != "autotune.json"]
        assert leftovers == []

    @pytest.mark.parametrize(
        "content",
        ["{truncated", '{"version": 999, "records": {}}', '{"version": 1}', "[1, 2]"],
        ids=["corrupt", "alien-version", "partial", "wrong-type"],
    )
    def test_corrupt_cache_falls_back_with_warning(self, tmp_path, content):
        path = str(tmp_path / "autotune.json")
        with open(path, "w") as f:
            f.write(content)
        with flox_tpu.set_options(autotune=True, autotune_cache_path=path):
            with pytest.warns(RuntimeWarning, match="falling back to heuristics"):
                chosen = autotune.decide(
                    "segment_sum", "scatter", ["scatter", "matmul"],
                    dtype="float32", ngroups=12, nelems=1 << 20,
                )
        assert chosen == "scatter"

    def test_missing_cache_file_is_silent(self, tmp_path):
        import warnings

        path = str(tmp_path / "never-written.json")
        with flox_tpu.set_options(autotune=True, autotune_cache_path=path):
            with warnings.catch_warnings():
                warnings.simplefilter("error")
                autotune.decide(
                    "segment_sum", "scatter", ["scatter"],
                    dtype="float32", ngroups=12, nelems=1 << 20,
                )

    def test_second_process_decides_without_resweeping(self, tmp_path):
        """The acceptance criterion's two-process contract, in-process: a
        fresh store (post clear_all) with a persisted cache path makes the
        measured decision with ZERO sweeps and a counted cache hit."""
        kw = _seed_segment_sum()
        path = str(tmp_path / "autotune.json")
        autotune.save(path)
        cache.clear_all()  # "process 2": empty store, unloaded state
        with flox_tpu.set_options(autotune=True, autotune_cache_path=path):
            chosen = autotune.decide(
                "segment_sum", "scatter", ["scatter", "matmul"], **kw
            )
            rec = autotune.decision_record()
        assert chosen == "matmul"
        assert rec["sweeps"] == 0
        assert rec["cache_hits"] >= 1

    def test_cross_process_cache_hits(self, tmp_path):
        """A REAL second process: run the same tiny reduction twice in
        subprocesses sharing one cache file; the first sweeps, the second
        serves every measured decision from disk (sweeps == 0)."""
        path = str(tmp_path / "autotune.json")
        code = (
            "import json, numpy as np\n"
            "import flox_tpu\n"
            "from flox_tpu import autotune\n"
            "rng = np.random.default_rng(0)\n"
            "v = rng.normal(size=(4, 3000)).astype(np.float32)\n"
            "l = np.repeat(np.arange(5), 600)\n"
            "flox_tpu.groupby_reduce(v, l, func='nanmean', engine='jax')\n"
            "autotune.save()\n"
            "rec = autotune.decision_record()\n"
            "print(json.dumps({'sweeps': rec['sweeps'], 'hits': rec['cache_hits'],"
            " 'entries': rec['entries']}))\n"
        )
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            FLOX_TPU_AUTOTUNE="1", FLOX_TPU_AUTOTUNE_CACHE_PATH=path,
        )
        env.pop("FLOX_TPU_TELEMETRY", None)
        outs = []
        for _ in range(2):
            proc = subprocess.run(
                [sys.executable, "-c", code], cwd=REPO, env=env,
                capture_output=True, text=True, timeout=240,
            )
            assert proc.returncode == 0, proc.stderr
            outs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
        assert outs[0]["sweeps"] >= 1  # first process measured candidates
        assert outs[1]["sweeps"] == 0  # second served from the persisted cache
        assert outs[1]["entries"] >= outs[0]["entries"]
        assert outs[1]["hits"] >= 1

    def test_save_merges_existing_disk_store(self, tmp_path):
        """save() folds the on-disk store in first: a record-only process
        that never consulted the store (so the lazy load never ran) must
        not clobber another process's persisted measurements (regression:
        the atexit save wiped every record but its own)."""
        path = str(tmp_path / "autotune.json")
        _seed_segment_sum()
        autotune.save(path)
        cache.clear_all()  # fresh "process": empty store, never loaded
        autotune.record("stream_prefetch", "4", 10.0, nelems=1 << 20)
        autotune.save(path)
        payload = json.load(open(path))
        families = {k.split("|")[0] for k in payload["records"]}
        assert families == {"segment_sum", "stream_prefetch"}

    def test_seed_not_suppressed_by_partial_disk_store(self, tmp_path, monkeypatch):
        """A persisted store holding only OTHER families must not suppress
        history seeding (regression: seeding was gated on a fully empty
        store, so a stream-records-only file starved the quantile flip)."""
        autotune.record("stream_prefetch", "4", 10.0, nelems=1 << 20)
        path = str(tmp_path / "autotune.json")
        autotune.save(path)
        cache.clear_all()
        os.makedirs(tmp_path / "BENCH_HISTORY")
        with open(tmp_path / "BENCH_HISTORY" / "bench_runs.jsonl", "w") as f:
            f.write(json.dumps(
                {"platform": "cpu", "impl_sweep_gbps": {"matmul": 9.0}}
            ) + "\n")
        monkeypatch.setattr(autotune, "_repo_root", lambda: str(tmp_path))
        with flox_tpu.set_options(autotune=True, autotune_cache_path=path):
            rec = autotune.decision_record()  # triggers lazy load + seed
            assert any(k.startswith("stream_prefetch|") for k in rec["winners"])
            seeded = [
                v for v in rec["winners"].values() if v["source"] == "seed"
            ]
            assert seeded, "history seeding was suppressed by the disk store"

    def test_seed_defers_to_real_observations(self):
        """A measured record outranks committed evidence for the same key."""
        kw = dict(dtype="float32", ngroups=12, nelems=1 << 20, platform="tpu")
        autotune.record("quantile", "sort", 5.0, source="observed", **kw)
        autotune.record("quantile", "select", 99.0, source="seed", **kw)
        rec = autotune.lookup("quantile", **kw)
        assert list(rec["candidates"]) == ["sort"]  # seed skipped the key


# ---------------------------------------------------------------------------
# record-only bit-identity + wired decision points
# ---------------------------------------------------------------------------


class TestDispatchWiring:
    def test_record_only_is_bit_identical(self):
        """With the tuner off, a store full of would-flip records must not
        change a single bit of any result (the FLOX_TPU_AUTOTUNE=0
        acceptance criterion)."""
        rng = np.random.default_rng(0)
        vals = rng.normal(size=(3, 4096)).astype(np.float32)
        codes = np.arange(4096) % 7
        calls = [
            ("nansum", {}),
            ("nanmean", {}),
            ("nanquantile", {"finalize_kwargs": {"q": 0.9}}),
        ]
        baseline = [
            np.asarray(groupby_reduce(vals, codes, func=f, engine="jax", **kw)[0])
            for f, kw in calls
        ]
        # would-flip records for every wired family
        autotune.record("segment_sum", "matmul", 99.0, dtype="float32",
                        ngroups=7, nelems=vals.size)
        autotune.record("quantile", "select", 99.0, dtype="float32",
                        ngroups=7, nelems=vals.size)
        autotune.record("engine", "numpy", 99.0, dtype="float64", nelems=vals.size)
        again = [
            np.asarray(groupby_reduce(vals, codes, func=f, engine="jax", **kw)[0])
            for f, kw in calls
        ]
        for a, b in zip(baseline, again):
            np.testing.assert_array_equal(a, b)

    def test_segment_sum_impl_consults_store(self):
        from flox_tpu.kernels import _segment_sum_impl

        import jax

        proxy = jax.ShapeDtypeStruct((4096, 8), np.float32)
        assert _segment_sum_impl(proxy, 12) == "scatter"  # CPU heuristic
        autotune.record("segment_sum", "matmul", 99.0, dtype="float32",
                        ngroups=12, nelems=4096 * 8)
        assert _segment_sum_impl(proxy, 12) == "scatter"  # still off
        with flox_tpu.set_options(autotune=True):
            assert _segment_sum_impl(proxy, 12) == "matmul"

    def test_quantile_choice_consults_store(self):
        from flox_tpu.kernels import _quantile_impl_choice

        import jax

        proxy = jax.ShapeDtypeStruct((4096, 8), np.float32)
        assert _quantile_impl_choice(proxy, 12) == "sort"
        autotune.record("quantile", "select", 99.0, dtype="float32",
                        ngroups=12, nelems=4096 * 8)
        with flox_tpu.set_options(autotune=True):
            assert _quantile_impl_choice(proxy, 12) == "select"
            # an explicit policy always wins over the tuner
            with flox_tpu.set_options(quantile_impl="sort"):
                assert _quantile_impl_choice(proxy, 12) == "sort"

    def test_engine_choice_consults_store(self):
        from flox_tpu.core import _choose_engine

        arr = np.zeros(512, dtype=np.float64)
        assert _choose_engine(None, arr, False) == "numpy"  # small-host heuristic
        autotune.record("engine", "jax", 99.0, dtype="float64", nelems=512)
        autotune.record("engine", "numpy", 1.0, dtype="float64", nelems=512)
        with flox_tpu.set_options(autotune=True):
            assert _choose_engine(None, arr, False) == "jax"
        # explicit engine= always wins
        with flox_tpu.set_options(autotune=True):
            assert _choose_engine("numpy", arr, False) == "numpy"

    def test_numpy_engine_max_elems_option(self):
        from flox_tpu.core import _choose_engine

        arr = np.zeros(512, dtype=np.float64)
        assert _choose_engine(None, arr, False) == "numpy"
        with flox_tpu.set_options(numpy_engine_max_elems=256):
            assert _choose_engine(None, arr, False) == "jax"
        with flox_tpu.set_options(numpy_engine_max_elems=0):
            assert _choose_engine(None, arr, False) == "jax"

    def test_autotuned_run_matches_heuristic_run_numerically(self):
        """With the tuner ON and a store that flips the segment-sum path,
        results stay numerically equivalent (different lowerings may differ
        in last-bit summation order, never beyond fp tolerance)."""
        rng = np.random.default_rng(1)
        vals = rng.normal(size=(4, 2048)).astype(np.float32)
        codes = np.arange(2048) % 5
        base, _ = groupby_reduce(vals, codes, func="nanmean", engine="jax")
        autotune.record("segment_sum", "matmul", 99.0, dtype="float32",
                        ngroups=5, nelems=vals.size)
        with flox_tpu.set_options(autotune=True):
            tuned, _ = groupby_reduce(vals, codes, func="nanmean", engine="jax")
        np.testing.assert_allclose(
            np.asarray(base), np.asarray(tuned), rtol=1e-5, atol=1e-6
        )

    def test_engine_sweep_records_under_swept_band(self):
        """The engine micro-sweep caps its workload; the measurement must
        land under the SWEPT size's band (regression: a small-array numpy
        win filed under a 10M-element band would route large host arrays
        to the numpy engine against the measured crossover)."""
        with flox_tpu.set_options(autotune=True):
            nelems = 10_000_000
            autotune.prime_engine("float64", nelems)
            # far beyond the cap band: no sweep, no mislabeled record
            assert autotune.lookup("engine", dtype="float64", nelems=nelems) is None
            in_band = autotune._SWEEP_ENGINE_N_MAX
            autotune.prime_engine("float64", in_band)
            rec = autotune.lookup("engine", dtype="float64", nelems=in_band)
            if rec is not None:  # sweep budget permitting
                key = autotune.make_key(
                    "engine", dtype="float64", nelems=in_band
                )
                assert key in autotune._AUTOTUNE_CACHE

    def test_prime_reduce_sweeps_once_per_key(self):
        with flox_tpu.set_options(autotune=True):
            rng = np.random.default_rng(0)
            vals = rng.normal(size=(4, 3000)).astype(np.float32)
            codes = np.repeat(np.arange(5), 600)
            groupby_reduce(vals, codes, func="nanmean", engine="jax")
            s1 = autotune.decision_record()["sweeps"]
            assert s1 >= 1
            groupby_reduce(vals, codes, func="nanmean", engine="jax")
            assert autotune.decision_record()["sweeps"] == s1  # memoized


# ---------------------------------------------------------------------------
# streaming observations + decisions
# ---------------------------------------------------------------------------


class TestStreaming:
    def test_stream_reports_feed_the_store_record_only(self):
        from flox_tpu.streaming import streaming_groupby_reduce

        rng = np.random.default_rng(0)
        data = rng.normal(size=(8, 4000)).astype(np.float32)
        month = (np.arange(4000) // 300) % 12
        streaming_groupby_reduce(data, month, func="nanmean", batch_len=1000)
        rec = autotune.decision_record()
        prefixes = {k.split("|")[0] for k in rec["winners"]}
        assert "stream_prefetch" in prefixes
        assert "stream_slab" in prefixes

    def test_streaming_autotuned_matches_heuristic(self):
        from flox_tpu.streaming import streaming_groupby_reduce

        rng = np.random.default_rng(0)
        data = rng.normal(size=(8, 4000)).astype(np.float32)
        month = (np.arange(4000) // 300) % 12
        base = np.asarray(
            streaming_groupby_reduce(data, month, func="nanmean")[0]
        )
        with flox_tpu.set_options(autotune=True):
            tuned = np.asarray(
                streaming_groupby_reduce(data, month, func="nanmean")[0]
            )
        np.testing.assert_allclose(base, tuned, rtol=1e-5, atol=1e-6)

    def test_pick_stream_prefetch_identity_without_records(self):
        with flox_tpu.set_options(autotune=True):
            assert autotune.pick_stream_prefetch(2, nelems=1 << 20) == 2

    def test_pick_stream_batch_bytes_identity_without_records(self):
        with flox_tpu.set_options(autotune=True):
            assert (
                autotune.pick_stream_batch_bytes(256 * 2**20, nelems=1 << 30)
                == 256 * 2**20
            )

    def test_pick_stream_prefetch_serves_recorded_winner(self):
        autotune.record("stream_prefetch", "4", 10.0, nelems=1 << 20)
        autotune.record("stream_prefetch", "2", 1.0, nelems=1 << 20)
        with flox_tpu.set_options(autotune=True):
            assert autotune.pick_stream_prefetch(2, nelems=1 << 20) == 4

    def test_explicit_stream_prefetch_is_never_adapted(self):
        """An explicit set_options(stream_prefetch=...) pins the depth even
        with the tuner on and a contrary record (regression: the tuner once
        overrode the pinned depth with an observed depth-0 win)."""
        from flox_tpu import profiling
        from flox_tpu.streaming import streaming_groupby_reduce

        rng = np.random.default_rng(0)
        data = rng.normal(size=(4, 4000)).astype(np.float32)
        month = (np.arange(4000) // 300) % 12
        nelems = data.size
        autotune.record("stream_prefetch", "0", 99.0, nelems=nelems)
        with flox_tpu.set_options(autotune=True, stream_prefetch=2):
            with profiling.stream_monitor() as reports:
                streaming_groupby_reduce(data, month, func="nanmean", batch_len=997)
        assert reports[0].prefetch == 2

    def test_explicit_batch_bytes_is_never_adapted(self):
        """batch_bytes= is a device-memory cap: the tuner adapts slab
        sizing only when the caller specified neither batch_len nor
        batch_bytes (regression: a recorded small-slab winner overrode an
        explicit byte budget)."""
        from flox_tpu import profiling
        from flox_tpu.streaming import streaming_groupby_reduce

        rng = np.random.default_rng(0)
        data = rng.normal(size=(64, 20000)).astype(np.float32)
        month = (np.arange(20000) // 300) % 12
        autotune.record("stream_slab", "2^16", 99.0, nelems=data.size)
        with flox_tpu.set_options(autotune=True):
            with profiling.stream_monitor() as reports:
                streaming_groupby_reduce(
                    data, month, func="nanmean", batch_bytes=256 * 2**20
                )
        # the explicit 256 MiB budget covers the whole array in one slab;
        # the recorded 64 KiB winner would have split it into dozens
        assert len(reports[0].slabs) == 1

    def test_checkpoint_path_pins_stream_slab_sizing(self, tmp_path):
        """Autotuned batch sizing is off under a checkpoint path: the
        derived batch_len is part of the checkpoint identity key and must
        be reproducible by the process that resumes the stream."""
        from flox_tpu import profiling
        from flox_tpu.streaming import streaming_groupby_reduce

        rng = np.random.default_rng(0)
        data = rng.normal(size=(64, 20000)).astype(np.float32)
        month = (np.arange(20000) // 300) % 12
        # a recorded small-slab winner that WOULD flip the derived batch_len
        autotune.record("stream_slab", "2^16", 99.0, nelems=data.size)
        with flox_tpu.set_options(autotune=True):
            with profiling.stream_monitor() as adapted:
                streaming_groupby_reduce(data, month, func="nanmean")
        with flox_tpu.set_options(
            autotune=True, stream_checkpoint_path=str(tmp_path / "ckpt.npz")
        ):
            with profiling.stream_monitor() as pinned:
                streaming_groupby_reduce(data, month, func="nanmean")
        assert len(adapted[0].slabs) > len(pinned[0].slabs)


# ---------------------------------------------------------------------------
# seeding + regression sentinel
# ---------------------------------------------------------------------------


class TestSeedAndSentinel:
    def _bench_record(self, platform="tpu"):
        return {
            "platform": platform,
            "value": 800.0,
            "impl_sweep_gbps": {"scatter": 120.0, "matmul": 700.0, "pallas": 800.0},
            "quantile_gbps": {"sort": 90.0, "select": 300.0},
            "streaming": {"gbps_sync": 10.0, "gbps_prefetch": 20.0},
            "workload": {"nlat": 181, "nlon": 360, "ntime": 26304, "ngroups": 12},
        }

    def test_seed_from_bench_files(self, tmp_path):
        os.makedirs(tmp_path / "BENCH_HISTORY")
        with open(tmp_path / "BENCH_HISTORY" / "bench_runs.jsonl", "w") as f:
            f.write(json.dumps(self._bench_record("cpu")) + "\n")
            f.write(json.dumps(self._bench_record()) + "\n")
        assert autotune.seed(str(tmp_path)) > 0
        # the seeded on-chip numbers resolve the open quantile decision for
        # the tpu platform key (this CPU process keys decide() by its own
        # platform, so assert through the platform-explicit lookup)
        rec = autotune.lookup(
            "quantile", dtype="float32", ngroups=12,
            nelems=181 * 360 * 26304, platform="tpu",
        )
        assert rec is not None
        assert max(rec["candidates"], key=lambda c: rec["candidates"][c]["gbps"]) == "select"

    def test_sentinel_flags_regression(self, tmp_path):
        hist = tmp_path / "bench_runs.jsonl"
        with open(hist, "w") as f:
            f.write(json.dumps({"platform": "cpu", "value": 10.0,
                                "impl_sweep_gbps": {"scatter": 10.0}}) + "\n")
        verdict = autotune.regression_sentinel(
            {"headline": 8.0, "segment_sum[scatter]": 9.9},
            history_path=str(hist), platform="cpu",
        )
        assert verdict["status"] == "regression"
        assert verdict["regressed"] == ["headline"]
        assert verdict["families"]["headline"]["regressed"] is True
        assert verdict["families"]["segment_sum[scatter]"]["regressed"] is False

    def test_sentinel_ok_within_threshold(self, tmp_path):
        hist = tmp_path / "bench_runs.jsonl"
        with open(hist, "w") as f:
            f.write(json.dumps({"platform": "cpu", "value": 10.0}) + "\n")
        verdict = autotune.regression_sentinel(
            {"headline": 9.0}, history_path=str(hist), platform="cpu"
        )
        assert verdict["status"] == "ok"

    def test_sentinel_ignores_other_platform_rounds(self, tmp_path):
        hist = tmp_path / "bench_runs.jsonl"
        with open(hist, "w") as f:
            f.write(json.dumps({"platform": "tpu", "value": 1000.0}) + "\n")
        verdict = autotune.regression_sentinel(
            {"headline": 5.0}, history_path=str(hist), platform="cpu"
        )
        assert verdict["status"] == "ok"
        assert verdict["compared_against"] is None

    def test_sentinel_matches_workload(self, tmp_path):
        """A sub-scale smoke round is never compared against a full-size
        round: workload-recording rounds only diff against their own shape
        (regression: CI's bounded bench smoke read as a chronic >15%
        'regression' against the committed full-scale round)."""
        hist = tmp_path / "bench_runs.jsonl"
        full = {"nlat": 181, "nlon": 360, "ntime": 26304, "ngroups": 12}
        tiny = {"nlat": 4, "nlon": 16, "ntime": 2000, "ngroups": 12}
        with open(hist, "w") as f:
            f.write(json.dumps(
                {"platform": "cpu", "value": 10.0, "workload": full}
            ) + "\n")
        verdict = autotune.regression_sentinel(
            {"headline": 0.5}, history_path=str(hist), platform="cpu",
            workload=tiny,
        )
        assert verdict["status"] == "ok"
        assert verdict["compared_against"] is None
        verdict = autotune.regression_sentinel(
            {"headline": 0.5}, history_path=str(hist), platform="cpu",
            workload=full,
        )
        assert verdict["status"] == "regression"

    def test_sentinel_missing_history_is_ok(self, tmp_path):
        verdict = autotune.regression_sentinel(
            {"headline": 5.0}, history_path=str(tmp_path / "nope.jsonl"),
            platform="cpu",
        )
        assert verdict["status"] == "ok"

    def test_sentinel_cli_report_only(self, capsys):
        rc = autotune.main(["sentinel"])
        assert rc == 0  # report-only even when the verdict is "regression"
        out = json.loads(capsys.readouterr().out)
        assert out["status"] in ("ok", "regression")

    def test_report_cli(self, capsys):
        _seed_segment_sum()
        rc = autotune.main(["report"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["entries"] == 1


# ---------------------------------------------------------------------------
# bench integration
# ---------------------------------------------------------------------------


class TestBenchIntegration:
    def test_benchmarks_sentinel_row_shape(self):
        import benchmarks

        rows = [
            {"bench": "era5_dayofyear[jax]", "value": 5.0, "unit": "GB/s"},
            {"bench": "time_reduce[1d-sum-jax]", "value": 0.5, "unit": "ms"},
        ]
        row = benchmarks.sentinel_row(rows, "cpu")
        assert row["bench"] == "regression_sentinel"
        assert row["unit"] == "verdict"
        assert row["value"]["status"] in ("ok", "regression")
        assert "era5_dayofyear[jax]" in row["value"]["families"]
        assert "time_reduce[1d-sum-jax]" not in row["value"]["families"]


# ---------------------------------------------------------------------------
# option plumbing
# ---------------------------------------------------------------------------


class TestOptions:
    def test_validated_at_set_time(self):
        with pytest.raises(ValueError):
            flox_tpu.set_options(autotune=1)  # bool only, 1 is a bug
        with pytest.raises(ValueError):
            flox_tpu.set_options(autotune_cache_path="")
        with pytest.raises(ValueError):
            flox_tpu.set_options(numpy_engine_max_elems=-1)
        with pytest.raises(ValueError):
            flox_tpu.set_options(numpy_engine_max_elems=True)

    def test_env_mirrors_seed_defaults(self):
        code = (
            "from flox_tpu.options import OPTIONS\n"
            "assert OPTIONS['autotune'] is True\n"
            "assert OPTIONS['autotune_cache_path'] == '/tmp/at.json'\n"
            "assert OPTIONS['numpy_engine_max_elems'] == 1234\n"
            "print('ok')\n"
        )
        env = dict(
            os.environ, JAX_PLATFORMS="cpu", FLOX_TPU_AUTOTUNE="1",
            FLOX_TPU_AUTOTUNE_CACHE_PATH="/tmp/at.json",
            FLOX_TPU_NUMPY_ENGINE_MAX_ELEMS="1234",
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], cwd=REPO, env=env,
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr
        assert "ok" in proc.stdout

    def test_malformed_env_falls_back(self):
        code = (
            "from flox_tpu.options import OPTIONS\n"
            "assert OPTIONS['autotune'] is False\n"
            "assert OPTIONS['numpy_engine_max_elems'] == 32768\n"
            "print('ok')\n"
        )
        env = dict(
            os.environ, JAX_PLATFORMS="cpu", FLOX_TPU_AUTOTUNE="banana",
            FLOX_TPU_NUMPY_ENGINE_MAX_ELEMS="-5",
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], cwd=REPO, env=env,
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr
        assert "ok" in proc.stdout

    def test_context_exit_restores_explicit_pin(self):
        """The context-manager form unpins on exit along with restoring the
        value: once a knob rides its built-in default again it is back on
        the tuner's auto surface. Plain-setter pins stay for the session."""
        from flox_tpu.options import explicitly_set

        if "FLOX_TPU_STREAM_PREFETCH" in os.environ:
            pytest.skip("depth pinned by the environment")
        assert not explicitly_set("stream_prefetch")
        with flox_tpu.set_options(stream_prefetch=4):
            assert explicitly_set("stream_prefetch")
        assert not explicitly_set("stream_prefetch")
