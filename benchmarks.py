"""Benchmark suite: the reference's asv workloads on this framework.

Parity target: BASELINE.md / the reference's asv_bench —
``time_reduce`` (reduce.py:12-117), ``time_reduce_bare`` (reduce.py:88-104),
``time_quantile`` (reduce.py:146-161), cohort-detection timing and
graph-size-style metrics (cohorts.py:40-81), and the synthetic workloads
(ERA5 day-of-year, PerfectMonthly, OISST, NWM county zonal stats,
RandomBigArray).

Run: ``python benchmarks.py [--scale small|full] [--engine jax|numpy]``.
Prints one JSON line per benchmark:
``{"bench": ..., "value": ..., "unit": ...}``.
``bench.py`` remains the single-line headline benchmark for the driver.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _timeit(fn, reps=3):
    fn()  # warm (compile)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def _block(x):
    if hasattr(x, "block_until_ready"):
        x.block_until_ready()
    return x


def bench_reduce(engine: str):
    """time_reduce parity: N=3000, 1-D and 2-D, core func sweep."""
    from flox_tpu import groupby_reduce

    n = 3000
    rng = np.random.default_rng(0)
    labels = np.repeat(np.arange(5), n // 5)
    out = []
    for shape_name, vals in [("1d", rng.normal(size=n)), ("2d", rng.normal(size=(5, n)))]:
        for func in ["sum", "nansum", "mean", "nanmean", "max", "nanmax", "count"]:
            t = _timeit(lambda: _block(groupby_reduce(vals, labels, func=func, engine=engine)[0]))
            out.append({"bench": f"time_reduce[{shape_name}-{func}-{engine}]", "value": round(t * 1e3, 3), "unit": "ms"})
    return out


def bench_reduce_bare(engine: str):
    """time_reduce_bare parity: the engine kernel alone."""
    from flox_tpu.aggregations import generic_aggregate

    n = 3000
    rng = np.random.default_rng(0)
    labels = np.repeat(np.arange(5), n // 5)
    vals = rng.normal(size=n)
    out = []
    for func in ["nansum", "nanmean", "nanmax", "nanlen"]:
        t = _timeit(
            lambda: _block(
                generic_aggregate(labels, vals, engine=engine, func=func, size=5, fill_value=0)
            )
        )
        out.append({"bench": f"time_reduce_bare[{func}-{engine}]", "value": round(t * 1e3, 3), "unit": "ms"})
    return out


def bench_quantile(engine: str, scale: str):
    """time_quantile parity: q=0.9 yearly resample of a (T, 25, 25) array."""
    from flox_tpu import groupby_reduce

    nt = 31411 if scale == "full" else 4000
    rng = np.random.default_rng(0)
    vals = rng.normal(size=(25, 25, nt))
    years = (np.arange(nt) // 365).astype(np.int64)
    t = _timeit(
        lambda: _block(
            groupby_reduce(vals, years, func="quantile", engine=engine, finalize_kwargs={"q": 0.9})[0]
        )
    )
    return [{"bench": f"time_quantile[{engine}]", "value": round(t * 1e3, 2), "unit": "ms"}]


def _era5_labels(scale: str):
    nt = 26304 if scale == "full" else 8760
    day = ((np.arange(nt) // 24) % 365).astype(np.int64)
    return nt, day


def bench_era5_dayofyear(engine: str, scale: str):
    """ERA5 day-of-year climatology (scaled spatial grid)."""
    from flox_tpu import groupby_reduce

    nt, day = _era5_labels(scale)
    nspace = 72 * 144 if scale == "full" else 24 * 48
    rng = np.random.default_rng(0)
    vals = rng.normal(size=(nspace, nt)).astype(np.float32)
    t = _timeit(lambda: _block(groupby_reduce(vals, day, func="nanmean", engine=engine)[0]))
    gbps = vals.nbytes / t / 1e9
    return [{"bench": f"era5_dayofyear[{engine}]", "value": round(gbps, 2), "unit": "GB/s"}]


def bench_era5_resampling(engine: str, scale: str):
    """ERA5 hourly->daily resampling (reference cohorts.py:119-132): many
    output groups (365/y), each spanning exactly 24 consecutive steps."""
    from flox_tpu import groupby_reduce

    nyears = 5 if scale == "full" else 1
    nt = nyears * 365 * 24
    nspace = 37 * 72 if scale == "full" else 24 * 24
    day = (np.arange(nt) // 24).astype(np.int64)
    rng = np.random.default_rng(0)
    vals = rng.normal(size=(nspace, nt)).astype(np.float32)
    t = _timeit(lambda: _block(groupby_reduce(vals, day, func="mean", engine=engine)[0]))
    gbps = vals.nbytes / t / 1e9
    return [{"bench": f"era5_resampling[{engine}]", "value": round(gbps, 2), "unit": "GB/s"}]


def bench_nwm_zonal(engine: str, scale: str):
    """NWM county zonal stats: 2-D labels, ~900 groups (cohorts.py:84-97)."""
    from flox_tpu import groupby_reduce

    side = 1500 if scale == "full" else 400
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 900, size=(side, side))
    vals = rng.normal(size=(side, side)).astype(np.float32)
    t = _timeit(lambda: _block(groupby_reduce(vals, labels, func="nanmean", engine=engine)[0]))
    return [{"bench": f"nwm_zonal_stats[{engine}]", "value": round(t * 1e3, 2), "unit": "ms"}]


def bench_random_big(engine: str, scale: str):
    """RandomBigArray map-reduce stress (scaled; cohorts.py:242-248)."""
    from flox_tpu import groupby_reduce

    nt = 100_000 if scale == "full" else 20_000
    nspace = 2000 if scale == "full" else 200
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 5000, size=nt)
    vals = rng.normal(size=(nspace, nt)).astype(np.float32)
    t = _timeit(lambda: _block(groupby_reduce(vals, labels, func="nansum", engine=engine)[0]))
    gbps = vals.nbytes / t / 1e9
    return [{"bench": f"random_big_array[{engine}]", "value": round(gbps, 2), "unit": "GB/s"}]


def bench_fused(engine: str, scale: str):
    """fused_sweep_gbps: groupby_aggregate_many's one-pass multi-statistic
    dispatch vs N sequential groupby_reduce passes on the climatology
    family set (impl_sweep_gbps style — GB/s against ONE logical read of
    the bytes for both, so the sequential row shows the bytes-touched
    penalty directly). The measurements feed the "fused" autotune family."""
    from flox_tpu import groupby_aggregate_many, groupby_reduce

    funcs = ("mean", "var", "min", "max")
    nt = 8760 if scale == "full" else 2000
    rows = 64 if scale == "full" else 16
    rng = np.random.default_rng(0)
    vals = rng.normal(size=(rows, nt)).astype(np.float32)
    labels = (np.arange(nt) // 31) % 12
    nbytes = vals.nbytes

    def run_fused():
        outs, _ = groupby_aggregate_many(vals, labels, funcs=funcs, engine=engine)
        for v in outs.values():
            _block(v)

    def run_seq():
        for f in funcs:
            _block(groupby_reduce(vals, labels, func=f, engine=engine)[0])

    t_fused = _timeit(run_fused)
    t_seq = _timeit(run_seq)
    out = [
        {"bench": f"fused_sweep_gbps[fused-{engine}]",
         "value": round(nbytes / t_fused / 1e9, 3), "unit": "GB/s"},
        {"bench": f"fused_sweep_gbps[sequential-{engine}]",
         "value": round(nbytes / t_seq / 1e9, 3), "unit": "GB/s"},
        {"bench": f"fused_speedup[{engine}]",
         "value": round(t_seq / t_fused, 2), "unit": "x"},
    ]
    if engine == "jax":
        # only device-path measurements feed the dispatch family: the
        # store keys carry no engine axis, and host-numpy ratios say
        # nothing about the jax fused-vs-sequential decision
        try:
            from flox_tpu import autotune

            for cand, t in (("fused", t_fused), ("sequential", t_seq)):
                autotune.record(
                    "fused", cand, nbytes / t / 1e9, dtype=str(vals.dtype),
                    ngroups=12, nelems=vals.size, source="bench",
                )
        except Exception:  # noqa: BLE001 — recording is best-effort
            pass
    return out


def bench_mesh_methods(scale: str):
    """Mesh execution-method comparison (the analogue of the reference's
    time_combine: _simple_combine vs _grouped_combine, combine.py:27-77 —
    here the combine strategies are whole SPMD programs)."""
    from flox_tpu import groupby_reduce
    from flox_tpu.parallel import make_mesh

    mesh = make_mesh()
    n = 500_000 if scale == "full" else 100_000
    rng = np.random.default_rng(0)
    labels = np.tile(np.arange(366), n // 366 + 1)[:n]
    vals = rng.normal(size=(8, n)).astype(np.float32)
    out = []
    for method in ["map-reduce", "cohorts"]:
        t = _timeit(
            lambda: _block(
                groupby_reduce(vals, labels, func="nanmean", method=method, mesh=mesh)[0]
            )
        )
        out.append({"bench": f"time_mesh_combine[{method}]", "value": round(t * 1e3, 2), "unit": "ms"})
    # distributed order statistics (radix-select counting passes psum'd):
    # the capability row the reference cannot run at all
    t = _timeit(
        lambda: _block(
            groupby_reduce(vals, labels, func="nanmedian", method="map-reduce", mesh=mesh)[0]
        )
    )
    out.append({"bench": "time_mesh_quantile[nanmedian-mapreduce]", "value": round(t * 1e3, 2), "unit": "ms"})
    return out


def bench_streaming(scale: str):
    """Out-of-core streaming throughput (the role the reference's dask/cubed
    chunked runtimes play) — ERA5-month shape streamed in bounded slabs."""
    from flox_tpu.streaming import streaming_groupby_reduce

    nt = 26304 if scale == "full" else 8760
    nspace = 72 * 144 if scale == "full" else 24 * 48
    rng = np.random.default_rng(0)
    month = ((np.arange(nt) // (24 * 30.44)).astype(np.int64)) % 12
    data = rng.normal(size=(nspace, nt)).astype(np.float32)
    streaming_groupby_reduce(data, month, func="nanmean", batch_bytes=64 * 2**20)  # warm
    t0 = time.perf_counter()
    streaming_groupby_reduce(data, month, func="nanmean", batch_bytes=64 * 2**20)
    t = time.perf_counter() - t0
    out = [
        {"bench": "time_streaming[era5-nanmean]", "value": round(t * 1e3, 1), "unit": "ms"},
        {"bench": "streaming_throughput[era5-nanmean]",
         "value": round(data.nbytes / t / 1e9, 2), "unit": "GB/s"},
    ]
    # round-5 additions: out-of-core exact median (nbits+1 passes) and the
    # carry-based streaming scan. batch_len forces >= 4 slabs at every
    # scale so the row measures the MULTI-SLAB paths it is named for (the
    # per-slab count accumulation / cross-slab carry), not a degenerate
    # one-slab run; one warm call excludes trace+compile like the row above
    from flox_tpu.streaming import streaming_groupby_scan

    sub = data[: max(1, nspace // 8)]
    blen = nt // 4

    def run_q():
        # block: the 33 bit-pass dispatches are async — unsynced timing
        # would stop the clock at dispatch, not completion
        _block(streaming_groupby_reduce(sub, month, func="nanmedian", batch_len=blen)[0])

    run_q()  # warm (compile)
    t0 = time.perf_counter()
    run_q()
    tq = time.perf_counter() - t0
    out.append({"bench": "time_streaming[era5-nanmedian-33pass]",
                "value": round(tq * 1e3, 1), "unit": "ms"})
    # throughput against ONE logical read: the 33-pass cost shows up as a
    # visibly lower GB/s than the nanmean row's single pass
    out.append({"bench": "streaming_throughput[era5-nanmedian-33pass]",
                "value": round(sub.nbytes / tq / 1e9, 3), "unit": "GB/s"})

    def run_s():
        streaming_groupby_scan(sub[0], month, func="nancumsum", batch_len=blen)

    run_s()  # warm (compile)
    t0 = time.perf_counter()
    run_s()
    ts = time.perf_counter() - t0
    out.append({"bench": "time_streaming[era5-scan-nancumsum]",
                "value": round(ts * 1e3, 1), "unit": "ms"})

    # -- prefetch pipeline vs synchronous staging under simulated IO latency
    # (pipeline.py): the loader sleeps like a zarr/S3 chunk read, so the
    # win is measurable on CPU CI — sleep releases the GIL while the
    # staging pool loads the next slabs. Same staged bytes either way
    # (results are bit-identical); only the overlap differs.
    import flox_tpu
    from flox_tpu import profiling

    latency_s = 0.010  # ~an object-store range-read RTT (>= the 5 ms floor)
    blen_p = max(1, nt // 16)
    # the row isolates the IO-overlap win, so keep per-slab compute small
    # next to the simulated latency (sub: 1/8 of the spatial rows) — the
    # compute-bound regime is already covered by the rows above
    psub = sub

    def sim_loader(s, e):
        time.sleep(latency_s)
        return psub[:, s:e]

    def run_p(depth):
        with flox_tpu.set_options(stream_prefetch=depth):
            with profiling.stream_monitor() as reports:
                _block(streaming_groupby_reduce(
                    sim_loader, month, func="nanmean", batch_len=blen_p
                )[0])
        return reports[0]

    # the prefetch row measures the configured depth (or 2 if the session
    # disabled prefetch — the row exists to show the pipeline delta)
    configured = flox_tpu.options.OPTIONS["stream_prefetch"] or 2
    run_p(0)
    run_p(configured)  # warm BOTH modes (compile + thread-pool first-spin)
    times = {}
    for d, tag in ((0, "sync"), (configured, "prefetch")):
        best, rep = None, None
        for _ in range(3):  # best-of-3: a noisy rep must not fake (or
            t0 = time.perf_counter()  # hide) the overlap win
            r = run_p(d)
            dt = time.perf_counter() - t0
            if best is None or dt < best:
                best, rep = dt, r
        times[tag] = best
        out.append({"bench": f"streaming_throughput[era5-nanmean-simio-{tag}]",
                    "value": round(psub.nbytes / times[tag] / 1e9, 3), "unit": "GB/s"})
        out.append({"bench": f"streaming_overlap[era5-nanmean-simio-{tag}]",
                    "value": round(rep.overlap_fraction, 3), "unit": "fraction"})
    out.append({"bench": "streaming_prefetch_speedup[era5-nanmean-simio]",
                "value": round(times["sync"] / times["prefetch"], 2), "unit": "x"})
    return out


def bench_scan(engine: str, scale: str):
    """Grouped-scan timing (reference tracks scans through its asv suite)."""
    from flox_tpu import groupby_scan

    n = 500_000 if scale == "full" else 100_000
    rng = np.random.default_rng(0)
    labels = np.tile(np.arange(12), n // 12 + 1)[:n]
    vals = rng.normal(size=n)
    out = []
    for func in ["cumsum", "ffill"]:
        t = _timeit(lambda: _block(groupby_scan(vals, labels, func=func, engine=engine)))
        out.append({"bench": f"time_scan[{func}-{engine}]", "value": round(t * 1e3, 2), "unit": "ms"})
    return out


def bench_scan_blelloch(scale: str):
    """Distributed Blelloch scan over the mesh (jax backend; once per run)."""
    from flox_tpu import groupby_scan

    n = 500_000 if scale == "full" else 100_000
    rng = np.random.default_rng(0)
    labels = np.tile(np.arange(12), n // 12 + 1)[:n]
    vals = rng.normal(size=n)
    t = _timeit(lambda: _block(groupby_scan(vals, labels, func="cumsum", method="blelloch")))
    return [{"bench": "time_scan[cumsum-blelloch]", "value": round(t * 1e3, 2), "unit": "ms"}]


def bench_telemetry(scale: str):
    """ISSUE 4: one instrumented pass of the ERA5 day-of-year headline so
    every benchmark round records its compile counts, retrace counts, and
    span-phase breakdown — the after-the-fact diagnosis BENCH rounds 1-5
    lacked whenever the accelerator probe fell back to CPU."""
    from flox_tpu import cache, groupby_reduce, telemetry

    nt, day = _era5_labels(scale)
    nspace = 72 * 144 if scale == "full" else 24 * 48
    rng = np.random.default_rng(0)
    vals = rng.normal(size=(nspace, nt)).astype(np.float32)
    cache.clear_all()  # fresh caches: the profile records REAL compile work
    try:
        profile = telemetry.profile_call(
            lambda: _block(groupby_reduce(vals, day, func="nanmean", engine="jax")[0])
        )
    except Exception as exc:  # noqa: BLE001 — diagnostics must not kill the sweep
        profile = {"error": f"{type(exc).__name__}: {exc}"}
    return [{"bench": "telemetry[era5-nanmean]", "value": profile, "unit": "profile"}]


def bench_highcard(engine: str, scale: str):
    """Dense vs the sort (present-groups) engine on a sparse-presence
    high-cardinality workload — the ``highcard_gbps[...]`` rows the
    dense-vs-sort crossover (docs/engines.md) is recorded from."""
    from flox_tpu import groupby_reduce

    size = 1 << (20 if scale == "full" else 17)
    n = 1 << (16 if scale == "full" else 14)
    present = max(64, size >> 8)
    rng = np.random.default_rng(11)
    ids = rng.choice(size, present, replace=False)
    codes = ids[rng.integers(0, present, n)]
    vals = rng.normal(size=n)
    eg = np.arange(size)
    out = []
    for eng, label in ((engine, "dense"), ("sort", "sort")):
        t = _timeit(lambda e=eng: _block(groupby_reduce(
            vals, codes, func="nanmean", expected_groups=eg, engine=e,
        )[0]))
        out.append({
            "bench": f"highcard_gbps[{label}-{size}g-{engine}]",
            "value": round(vals.nbytes / t / 1e9, 3), "unit": "GB/s",
        })
    return out


def bench_costmodel(scale: str):
    """Analytical-cards sweep (ISSUE 14): run the ERA5 nanmean with the
    cost-model plane on and emit each program's card next to the drift
    verdict — every benchmarks.py round carries the predicted-vs-observed
    join, so a program that silently got slower shows up in the committed
    artifact, not just in a live scrape."""
    import flox_tpu
    from flox_tpu import cache, costmodel, groupby_reduce

    nt, day = _era5_labels(scale)
    nspace = 72 * 144 if scale == "full" else 24 * 48
    rng = np.random.default_rng(0)
    vals = rng.normal(size=(nspace, nt)).astype(np.float32)
    cache.clear_all()
    try:
        with flox_tpu.set_options(telemetry=True, costmodel=True):
            _block(groupby_reduce(vals, day, func="nanmean", engine="jax")[0])
            drift = costmodel.drift_report()
            # keyed by digest (the registry identity) — one label can hold
            # several cards, one per input signature
            record = {
                "cards": {
                    digest: {
                        "label": card["label"],
                        "flops": card["flops"],
                        "bytes_accessed": card["bytes_accessed"],
                        "predicted_ms": card["predicted_ms"],
                        "analysis": card["analysis"],
                    }
                    for digest, card in costmodel.cards().items()
                },
                "drift_flagged": drift["flagged"],
            }
    except Exception as exc:  # noqa: BLE001 — diagnostics must not kill the sweep
        record = {"error": f"{type(exc).__name__}: {exc}"}
    finally:
        cache.clear_all()
    return [{"bench": "costmodel[era5-nanmean]", "value": record, "unit": "cards"}]


def bench_cohort_detection(scale: str):
    """time_find_group_cohorts + track_num_cohorts parity."""
    from flox_tpu import cache
    from flox_tpu.cohorts import chunks_from_shards, find_group_cohorts

    nt, day = _era5_labels(scale)
    chunks = chunks_from_shards(nt, nt // 48)

    def run():
        cache.clear_all()  # the reference's asv clears flox.cache the same way
        return find_group_cohorts(day, chunks, expected_groups=range(365))

    t = _timeit(run)
    method, mapping = run()
    return [
        {"bench": "time_find_group_cohorts[era5]", "value": round(t * 1e3, 2), "unit": "ms"},
        {"bench": "track_num_cohorts[era5]", "value": len(mapping), "unit": "cohorts"},
        {"bench": "track_method[era5]", "value": method, "unit": "method"},
    ]


def sentinel_row(rows: list, platform: str) -> dict:
    """Regression sentinel over this sweep's GB/s rows (ISSUE 6): diff each
    throughput row against the newest committed ``BENCH_HISTORY/r*_cpu.jsonl``
    round with a matching row, flagging drops past the autotune threshold.
    Report-only by construction — the verdict is a row, never an exit code."""
    import glob
    import os
    import re

    from flox_tpu.autotune import _REGRESSION_THRESHOLD, compare_families

    current = {
        r["bench"]: r["value"]
        for r in rows
        if r.get("unit") == "GB/s" and isinstance(r.get("value"), (int, float))
    }
    previous: dict = {}
    compared = None
    here = os.path.dirname(os.path.abspath(__file__))
    rounds = []
    for path in glob.glob(os.path.join(here, "BENCH_HISTORY", "r*_cpu.jsonl")):
        m = re.match(r"r(\d+)_cpu\.jsonl$", os.path.basename(path))
        if m:
            rounds.append((int(m.group(1)), path))
    # newest round first, by the parsed round NUMBER: lexicographic order
    # inverts r99/r100 (and any unpadded name) the moment digits grow
    for _, path in sorted(rounds, reverse=True):
        try:
            with open(path) as f:
                lines = [json.loads(line) for line in f if line.strip()]
        except (OSError, ValueError):
            continue
        plat = next((r["value"] for r in lines if r.get("bench") == "platform"), None)
        if plat != platform:
            continue
        previous = {
            r["bench"]: r["value"]
            for r in lines
            if r.get("unit") == "GB/s" and isinstance(r.get("value"), (int, float))
        }
        compared = os.path.basename(path)
        break
    families, regressed = compare_families(current, previous)
    return {
        "bench": "regression_sentinel",
        "value": {
            "status": "regression" if regressed else "ok",
            "platform": platform,
            "threshold": _REGRESSION_THRESHOLD,
            "compared_against": compared,
            "regressed": regressed,
            "families": families,
        },
        "unit": "verdict",
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["small", "full"], default="small")
    ap.add_argument("--engine", choices=["jax", "numpy", "both"], default="jax")
    ap.add_argument(
        "--platform", choices=["default", "cpu"], default="default",
        help="cpu forces the CPU backend before any device init (the "
        "asv-style CPU record in BENCH_HISTORY/r{N}_cpu.jsonl)",
    )
    ap.add_argument(
        "--sweeps", type=int, default=3,
        help="run the whole battery N times and record the per-row MEDIAN "
        "(VERDICT r4 #3: back-to-back reps share transient host load; "
        "sweeps minutes apart sample the session's noise distribution). "
        "1 = a quick single sweep.",
    )
    args = ap.parse_args()

    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    # report the backend that ACTUALLY initialized, not the CLI arg — the
    # capture tooling uses this row as evidence a sweep ran on hardware
    print(json.dumps(
        {"bench": "platform", "value": jax.default_backend(), "unit": "config"}
    ))

    engines = ["jax", "numpy"] if args.engine == "both" else [args.engine]

    def one_sweep():
        results = []
        for engine in engines:
            results += bench_reduce(engine)
            results += bench_reduce_bare(engine)
            results += bench_quantile(engine, args.scale)
            results += bench_era5_dayofyear(engine, args.scale)
            results += bench_era5_resampling(engine, args.scale)
            results += bench_nwm_zonal(engine, args.scale)
            results += bench_random_big(engine, args.scale)
            results += bench_fused(engine, args.scale)
            results += bench_highcard(engine, args.scale)
            results += bench_scan(engine, args.scale)
        if "jax" in engines:
            # mesh benchmarks need a working jax backend; keep --engine numpy
            # runnable on hosts without one
            results += bench_mesh_methods(args.scale)
            results += bench_scan_blelloch(args.scale)
            results += bench_streaming(args.scale)
            results += bench_telemetry(args.scale)
            results += bench_costmodel(args.scale)
        results += bench_cohort_detection(args.scale)
        return results

    sweeps = [one_sweep() for _ in range(max(1, args.sweeps))]
    print(json.dumps({
        "bench": "timer", "value": f"median-of-{len(sweeps)}-sweeps",
        "unit": "config",
    }))
    # per-row median across sweeps; non-numeric rows pass through from the
    # first sweep (config rows are sweep-invariant)
    by_name: dict = {}
    for sweep in sweeps:
        for r in sweep:
            by_name.setdefault(r["bench"], []).append(r)
    medians = []
    for name, rows in by_name.items():
        vals = sorted(r["value"] for r in rows if isinstance(r["value"], (int, float)))
        if vals:
            k = len(vals)
            med = vals[k // 2] if k % 2 else round((vals[k // 2 - 1] + vals[k // 2]) / 2, 6)
            out = dict(rows[0], value=med)
        else:
            out = rows[0]
        medians.append(out)
        print(json.dumps(out))
    # report-only regression sentinel over the medians (ISSUE 6)
    print(json.dumps(sentinel_row(medians, jax.default_backend())))


if __name__ == "__main__":
    main()
