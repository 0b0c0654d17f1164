"""Headline benchmark: ERA5 hourly -> monthly-mean climatology on one chip.

Metric (BASELINE.json): ERA5 ``groupby('time.month').mean()`` GB/s/chip.
Baseline: the in-repo host numpy engine (``ufunc.at``/bincount — the same
primitive family as the reference's numpy_groupies engine) on the identical
workload. Prints ONE JSON line.

Scale knobs (env):
  FLOX_TPU_BENCH_NLAT / NLON / NTIME — workload shape (default 181x360x26304,
  ~6.8 GB float32: 3 years of hourly steps on a 1-degree grid).
  FLOX_TPU_BENCH_REPS — timed repetitions (default 5).
  FLOX_TPU_BENCH_CHAIN — iterations in the differenced timing chain
  (default 8, min 2; see the timing note in main()).

It measures on the TPU only: with no TPU it exits non-zero.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))
HISTORY_PATH = os.path.join(_REPO, "BENCH_HISTORY", "bench_runs.jsonl")


def _append_history(line: dict) -> None:
    try:
        os.makedirs(os.path.dirname(HISTORY_PATH), exist_ok=True)
        with open(HISTORY_PATH, "a") as f:
            f.write(json.dumps(line) + "\n")
    except OSError as exc:  # pragma: no cover
        import sys

        print(f"flox-tpu bench: could not append history: {exc}",
              file=sys.stderr, flush=True)


def main() -> None:
    import sys

    import jax

    from flox_tpu.device import compile_cache
    from flox_tpu.kernels import generic_kernel

    if jax.devices()[0].platform != "tpu":
        sys.exit(f"bench.py measures on the TPU; found {jax.devices()[0].platform}")
    compile_cache()
    # 3 calendar years of hourly steps INCLUDING the 2016 leap day = 26304,
    # the headline shape (BASELINE.md: array (721, 1440, 26304))
    default_ntime = 24 * (365 * 3 + 1)
    default_nlat = 181
    nlat = int(os.environ.get("FLOX_TPU_BENCH_NLAT", default_nlat))
    nlon = int(os.environ.get("FLOX_TPU_BENCH_NLON", 360))
    ntime = int(os.environ.get("FLOX_TPU_BENCH_NTIME", default_ntime))
    reps = int(os.environ.get("FLOX_TPU_BENCH_REPS", 5))

    # month-of-year labels for 3 years of hourly stamps (12 groups)
    hours = np.arange(ntime, dtype=np.int64)
    day = hours // 24
    month = ((day % 365) // 30.44).astype(np.int32) % 12
    size = 12

    nbytes = nlat * nlon * ntime * 4

    # --- TPU/jax path: generate the workload directly on device ------------
    # Shipping ~7 GB from the host is not part of the metric; synthesize
    # the same distribution on device instead.
    import jax.numpy as jnp

    dev_data = jax.jit(
        lambda k: jax.random.normal(k, (nlat * nlon, ntime), jnp.float32)
    )(jax.random.PRNGKey(0))
    dev_data.block_until_ready()
    dev_codes = jax.device_put(month)

    # Time a jitted chain of K dependent iterations with a host fetch of
    # the (tiny) result, and difference against a 1-iteration chain so the
    # constant fetch/dispatch overhead cancels:
    #   t_iter = (t_K - t_1) / (K - 1)
    # The inter-iteration dependence rides the (tiny) codes array — a
    # data-sized `v + f(out)` temp would double the HBM footprint and OOM
    # the full workload — so per-iteration HBM traffic stays ~one pass over
    # the same data buffer. XLA cannot fold the zero (out may be NaN/inf)
    # nor CSE the iterations (each sees a distinct codes value).
    def chain(iters, func, **kw):
        @jax.jit
        def run(c, v):
            out = generic_kernel(func, c, v, size=size, **kw)
            for _ in range(iters - 1):
                # nan_to_num: an empty group's NaN mean must not reach the
                # int cast (NaN->int is implementation-defined garbage)
                c2 = c + jnp.nan_to_num(out.ravel()[:1] * 0.0).astype(c.dtype)
                out = generic_kernel(func, c2, v, size=size, **kw)
            return out

        return run

    chain_k = max(2, int(os.environ.get("FLOX_TPU_BENCH_CHAIN", 8)))

    def best_time(fn, data):
        np.asarray(fn(dev_codes, data))  # compile + warm
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(fn(dev_codes, data))
            times.append(time.perf_counter() - t0)
        return min(times)

    def measure_impl(func="nanmean", data=None, **kw):
        data = dev_data if data is None else data
        t_1 = best_time(chain(1, func, **kw), data)
        t_k = best_time(chain(chain_k, func, **kw), data)
        t = (t_k - t_1) / (chain_k - 1)
        # noise floor: fall back to the single-shot fetch time
        return t_1 if t <= 0 else t

    # Sweep the three segment-sum lowerings and take the winner: the
    # headline then doubles as the on-chip policy measurement (scatter vs
    # MXU one-hot GEMM vs Pallas).
    from flox_tpu.kernels import _segment_sum_impl
    from flox_tpu.options import OPTIONS

    # the kernel sees the array with the reduce axis leading
    proxy = jax.ShapeDtypeStruct((ntime, nlat * nlon), np.float32)
    impls = ("scatter", "matmul", "pallas")
    sweep: dict = {}
    for impl in impls:
        OPTIONS["segment_sum_impl"] = impl
        # explicit policies silently fall back to scatter when their
        # guards fail — measure (and label) what would actually run, or
        # the sweep reports a scatter time under another impl's name
        resolved = _segment_sum_impl(proxy, size)
        if resolved != impl:
            print(f"flox-tpu bench: impl {impl!r} resolves to {resolved!r} "
                  "here; skipping duplicate measurement", file=sys.stderr, flush=True)
            continue
        try:
            sweep[impl] = measure_impl()
        except Exception as exc:  # noqa: BLE001 — keep the bench alive
            print(f"flox-tpu bench: impl {impl!r} failed: {exc}",
                  file=sys.stderr, flush=True)
            sweep[impl] = None
        jax.clear_caches()
    ok = {k: v for k, v in sweep.items() if v}
    if not ok:
        raise RuntimeError(f"all segment-sum impls failed: {sweep}")
    winner = min(ok, key=ok.get)
    OPTIONS["segment_sum_impl"] = winner
    t_dev = ok[winner]
    sweep_gbps = {k: round(nbytes / v / 1e9, 2) for k, v in ok.items()}
    gbps = nbytes / t_dev / 1e9

    # --- host baseline: an independent numpy_groupies-equivalent -----------
    # numpy_groupies is not installed; its nanmean primitive is
    # bincount-with-weights (npg aggregate_numpy), reproduced verbatim here
    # so the baseline is NOT this repo's own engine (BASELINE.json names
    # single-host numpy_groupies as the reference point).
    def npg_equivalent_nanmean(codes, values, size):
        ncols = values.shape[0]
        flat_codes = (
            np.broadcast_to(codes, values.shape)
            + (np.arange(ncols, dtype=np.int32)[:, None] * size)
        ).reshape(-1)
        v = values.reshape(-1)
        nanmask = np.isnan(v)
        zeroed = np.where(nanmask, 0.0, v)
        sums = np.bincount(flat_codes, weights=zeroed, minlength=ncols * size)
        cnts = np.bincount(flat_codes[~nanmask], minlength=ncols * size)
        with np.errstate(invalid="ignore"):
            return (sums / cnts).reshape(ncols, size)

    # bincount throughput is size-invariant well before this point; a bounded
    # row subset (~512 MB) keeps the single-core baseline measurement (and
    # its flat-codes temporary) from dominating the benchmark's wall-clock.
    host_rows = min(nlat * nlon, max(1, int(512e6) // (ntime * 4)))
    rng = np.random.default_rng(0)
    host_data = rng.normal(size=(host_rows, ntime)).astype(np.float32)
    t0 = time.perf_counter()
    npg_equivalent_nanmean(month, host_data, size)
    t_host = time.perf_counter() - t0
    gbps_host = host_data.nbytes / t_host / 1e9

    # --- order statistics on chip (VERDICT r2 #3): grouped quantile -------
    # The two-key lax.sort path is the open perf question; record its
    # throughput next to the mean's so the gap is a measured artifact, not
    # a guess. Bounded rows: the sort materializes ~3 data-sized arrays
    # (codes/data/iota), so the full ~7 GB workload would not fit HBM.
    backend = jax.default_backend()
    quantile_gbps = None
    # sweep BOTH order-statistics lowerings (VERDICT r3 #3): the two-key
    # lax.sort path vs the sort-free radix-select (nbits segment-sum
    # counting passes on the MXU). The recorded dict is the measurement
    # that decides the "auto" policy.
    import flox_tpu

    q_rows = min(nlat * nlon, max(1, int(1.0e9) // (ntime * 4)))
    quantile_gbps = {}
    for qimpl in ("sort", "select"):
        try:
            with flox_tpu.set_options(quantile_impl=qimpl):
                tq = measure_impl("nanquantile", dev_data[:q_rows], q=0.9)
            quantile_gbps[qimpl] = round(q_rows * ntime * 4 / tq / 1e9, 2)
        except Exception as exc:  # noqa: BLE001 — keep the headline alive
            print(f"flox-tpu bench: quantile[{qimpl}] failed: {exc}",
                  file=sys.stderr, flush=True)
            quantile_gbps[qimpl] = None
        jax.clear_caches()
    # --- streaming pipeline: prefetched staging vs synchronous inline ----
    # (flox_tpu/pipeline.py) measured with a simulated-latency loader (a
    # ~zarr/S3 range read) so the overlap win is visible on any host; GB/s
    # against ONE logical read of the streamed bytes
    import flox_tpu
    from flox_tpu.streaming import streaming_groupby_reduce

    stream_lat_s = 0.005
    s_data = host_data[: min(host_rows, 256)]
    s_blen = max(1, ntime // 16)

    def _stream_loader(s, e):
        time.sleep(stream_lat_s)
        return s_data[:, s:e]

    def _stream_time(depth):
        with flox_tpu.set_options(stream_prefetch=depth):
            t0 = time.perf_counter()
            res = streaming_groupby_reduce(
                _stream_loader, month, func="nanmean", batch_len=s_blen
            )[0]
            np.asarray(res)  # streamed reduce is async — sync before stopping
            return time.perf_counter() - t0

    _stream_time(0)  # warm both modes (compile + thread-pool first-spin)
    _stream_time(2)
    t_sync = min(_stream_time(0) for _ in range(2))
    t_pre = min(_stream_time(2) for _ in range(2))
    streaming = {
        "simio_latency_ms": stream_lat_s * 1e3,
        "gbps_sync": round(s_data.nbytes / t_sync / 1e9, 3),
        "gbps_prefetch": round(s_data.nbytes / t_pre / 1e9, 3),
        "prefetch_speedup": round(t_sync / t_pre, 2),
    }

    # --- multi-statistic fusion: one pass vs N sequential passes ----------
    # (flox_tpu/fusion.py) the climatology family set {mean, var, min, max}
    # through groupby_aggregate_many (one program, bytes staged once) vs
    # four sequential groupby_reduce passes. GB/s is against ONE logical
    # read of the bytes for BOTH, so the sequential number directly shows
    # the bytes-touched penalty; the measurements seed the "fused"
    # autotune family that arbitrates the dispatch.
    fused_info = None
    try:
        f_funcs = ("mean", "var", "min", "max")
        f_rows = min(nlat * nlon, max(1, int(256e6) // (ntime * 4)))
        f_data = dev_data[:f_rows]
        f_bytes = f_rows * ntime * 4
        f_reps = max(2, reps // 2)

        def _t_fused():
            t0 = time.perf_counter()
            outs, _ = flox_tpu.groupby_aggregate_many(f_data, month, funcs=f_funcs)
            for v in outs.values():
                np.asarray(v)
            return time.perf_counter() - t0

        def _t_seq():
            t0 = time.perf_counter()
            for f in f_funcs:
                np.asarray(flox_tpu.groupby_reduce(f_data, month, func=f)[0])
            return time.perf_counter() - t0

        _t_fused()  # compile + warm both paths outside the timed reps
        _t_seq()
        t_fused = min(_t_fused() for _ in range(f_reps))
        t_seq = min(_t_seq() for _ in range(f_reps))
        fused_info = {
            "funcs": list(f_funcs),
            # the band the sweep actually measured (f_rows may be far
            # below the headline workload) — autotune records key on it
            "nelems": f_rows * ntime,
            "fused_sweep_gbps": {
                "fused": round(f_bytes / t_fused / 1e9, 3),
                "sequential": round(f_bytes / t_seq / 1e9, 3),
            },
            "speedup": round(t_seq / t_fused, 2),
        }
    except Exception as exc:  # noqa: BLE001 — keep the headline alive
        print(f"flox-tpu bench: fused sweep failed: {exc}",
              file=sys.stderr, flush=True)

    # --- high-cardinality: dense vs the sort (present-groups) engine ------
    # (kernels.py sort section) a million-label universe with sparse
    # presence — the user-ID / geohash / station-ID regime. GB/s against
    # ONE logical read of the data for BOTH engines, so the dense number
    # directly shows the ngroups-accumulator penalty. The pair seeds the
    # "highcard" autotune family, and the coarse universe scan records the
    # dense-vs-sort crossover band (docs/engines.md).
    highcard_info = None
    try:
        hc_size = 1 << 20
        hc_n = 1 << 16
        hc_present = 1 << 12  # 0.4% of the universe present
        rng_hc = np.random.default_rng(11)
        hc_ids = rng_hc.choice(hc_size, hc_present, replace=False)
        hc_codes = hc_ids[rng_hc.integers(0, hc_present, hc_n)]
        hc_vals = jax.device_put(
            rng_hc.normal(size=hc_n).astype(np.float32)
        )
        hc_reps = max(2, reps // 2)

        def _t_hc(engine, universe):
            t0 = time.perf_counter()
            np.asarray(flox_tpu.groupby_reduce(
                hc_vals, hc_codes % universe, func="nanmean",
                expected_groups=np.arange(universe), engine=engine,
            )[0])
            return time.perf_counter() - t0

        _t_hc("jax", hc_size)  # compile + warm both engines
        _t_hc("sort", hc_size)
        t_hc_dense = min(_t_hc("jax", hc_size) for _ in range(hc_reps))
        t_hc_sort = min(_t_hc("sort", hc_size) for _ in range(hc_reps))
        # coarse crossover scan: the smallest universe (same data, labels
        # folded down) where the sort engine wins — the band boundary the
        # autotuner refines at runtime
        crossover = None
        for logu in range(13, 21):
            u = 1 << logu
            _t_hc("jax", u), _t_hc("sort", u)
            td = min(_t_hc("jax", u) for _ in range(2))
            ts = min(_t_hc("sort", u) for _ in range(2))
            if ts < td:
                crossover = u
                break
        highcard_info = {
            "ngroups": hc_size,
            "nelems": hc_n,
            "present": hc_present,
            "dense_gbps": round(hc_vals.nbytes / t_hc_dense / 1e9, 3),
            "sort_gbps": round(hc_vals.nbytes / t_hc_sort / 1e9, 3),
            "speedup": round(t_hc_dense / t_hc_sort, 2),
            "crossover_ngroups": crossover,
        }
    except Exception as exc:  # noqa: BLE001 — keep the headline alive
        print(f"flox-tpu bench: highcard sweep failed: {exc}",
              file=sys.stderr, flush=True)

    # --- resident dataset registry: inline vs registry-hit serving --------
    # (flox_tpu/serve/registry.py) the factorize-once fast path measured at
    # three payload sizes over the serve-loop request path: each rep times
    # json.loads of the request line + dispatch, exactly what a replica
    # pays per protocol line. An inline request parses its full payload
    # from JSON, digests it, factorizes, and stages it H2D; a registry hit
    # parses a ~40-byte line and reuses the pinned, prefactorized entry
    # (its stored fingerprint IS the program key — zero hashing). The line
    # text is encoded OUTSIDE the timing (client cost, not replica cost).
    # batch_window=0 so neither path pays the micro-batch wait, and
    # sequential awaits mean no coalescing: every rep is a real dispatch.
    # p50 (not min): the win is a per-request overhead, so the central
    # tendency is the honest number.
    registry_info = None
    try:
        import asyncio

        from flox_tpu.serve import registry as _dsregistry
        from flox_tpu.serve.dispatcher import AggregationRequest, Dispatcher

        r_reps = max(9, reps)
        r_sizes = (1 << 14, 1 << 16, 1 << 18)
        rng_r = np.random.default_rng(7)
        r_fields = ("array", "by", "func", "dataset")

        async def _registry_sweep() -> dict:
            rows: dict = {}
            d = Dispatcher(batch_window=0.0)
            try:
                for n_r in r_sizes:
                    vals = rng_r.normal(size=n_r).astype(np.float32)
                    labels = rng_r.integers(0, 12, size=n_r).astype(np.int32)
                    name = f"bench-{n_r}"
                    _dsregistry.put(name, array=vals, by=labels)
                    inline_line = json.dumps({
                        "array": vals.tolist(), "by": labels.tolist(),
                        "func": "mean",
                    })
                    hit_line = json.dumps({"func": "mean", "dataset": name})

                    async def _once(line: str) -> None:
                        msg = json.loads(line)
                        await d.submit(AggregationRequest(
                            **{k: msg.get(k) for k in r_fields}))

                    async def _p50(line: str) -> float:
                        await _once(line)  # compile + warm
                        times = []
                        for _ in range(r_reps):
                            t0 = time.perf_counter()
                            await _once(line)
                            times.append(time.perf_counter() - t0)
                        return float(np.median(times))

                    t_inline = await _p50(inline_line)
                    t_hit = await _p50(hit_line)
                    rows[str(n_r)] = {
                        "p50_inline_ms": round(t_inline * 1e3, 3),
                        "p50_hit_ms": round(t_hit * 1e3, 3),
                        "inline_gbps": round(vals.nbytes / t_inline / 1e9, 3),
                        "hit_gbps": round(vals.nbytes / t_hit / 1e9, 3),
                        "speedup": round(t_inline / t_hit, 2),
                    }
                    _dsregistry.delete(name)
            finally:
                await d.close()
            return rows

        registry_info = {
            "platform": backend,
            "reps": r_reps,
            "timed_path": "json.loads(request line) + dispatch (the serve "
                          "loop's per-line cost); line encode is client-side",
            "sizes": asyncio.run(_registry_sweep()),
        }
    except Exception as exc:  # noqa: BLE001 — keep the headline alive
        print(f"flox-tpu bench: registry sweep failed: {exc}",
              file=sys.stderr, flush=True)

    # --- durable incremental aggregation store (ISSUE 18) -----------------
    # (flox_tpu/store.py) two numbers: append throughput — what one
    # exactly-once durable ingest costs (journal fsync + checksummed
    # segment write per slab) — and the read-path win the store exists
    # for: query() merges the persisted O(ngroups) present-groups carry
    # instead of re-reducing raw history, timed against recomputing the
    # full concatenated history inline at three history lengths. The
    # recompute cost grows with history; the store query does not. The
    # analytic store-vs-recompute verdict (costmodel "store_query" family)
    # rides along so a committed artifact shows prediction next to
    # measurement. History lengths shrink with FLOX_TPU_BENCH_REPS<=2 so
    # the CI smoke round stays cheap.
    store_info = None
    try:
        import shutil
        import tempfile

        from flox_tpu.store import IncrementalAggregationStore

        s_funcs = ("sum", "count", "mean", "var")
        s_ngroups = 64
        s_n = 1 << 13 if reps <= 2 else 1 << 15
        s_gens = (4, 16) if reps <= 2 else (8, 32, 128)
        s_reps = max(3, reps)
        rng_s = np.random.default_rng(11)
        sroot = tempfile.mkdtemp(prefix="flox-bench-store-")
        try:
            s = IncrementalAggregationStore.create(
                os.path.join(sroot, "bench"), funcs=s_funcs, size=s_ngroups
            )
            slab_list: list = []
            append_times: list = []
            lengths: dict = {}
            for target in s_gens:
                while len(slab_list) < target:
                    codes = rng_s.integers(0, s_ngroups, size=s_n)
                    vals = rng_s.normal(size=s_n)
                    slab_list.append((codes, vals))
                    t0 = time.perf_counter()
                    s.append(codes, vals)
                    append_times.append(time.perf_counter() - t0)
                s.query()  # warm (first query after appends builds the carry)
                tq = []
                for _ in range(s_reps):
                    t0 = time.perf_counter()
                    s.query()
                    tq.append(time.perf_counter() - t0)
                t_store = float(np.median(tq))
                all_codes = np.concatenate([c for c, _ in slab_list])
                all_vals = np.concatenate([v for _, v in slab_list])
                tr = []
                for _ in range(s_reps):
                    t0 = time.perf_counter()
                    res, _ = flox_tpu.groupby_aggregate_many(
                        all_vals, all_codes, funcs=s_funcs,
                        expected_groups=np.arange(s_ngroups),
                    )
                    for v in res.values():
                        np.asarray(v)
                    tr.append(time.perf_counter() - t0)
                t_rec = float(np.median(tr))
                lengths[str(target)] = {
                    "history_mb": round(all_vals.nbytes / 1e6, 2),
                    "p50_query_ms": round(t_store * 1e3, 3),
                    "p50_recompute_ms": round(t_rec * 1e3, 3),
                    "speedup": round(t_rec / t_store, 2),
                }
            append_p50 = float(np.median(append_times))
            store_info = {
                "platform": backend,
                "reps": s_reps,
                "slab_elems": s_n,
                "ngroups": s_ngroups,
                "funcs": list(s_funcs),
                "p50_append_ms": round(append_p50 * 1e3, 3),
                "append_mbps": round(
                    (s_n * 8) / append_p50 / 1e6, 1
                ),
                "timed_path": "query() = persisted-carry merge + finalize; "
                              "recompute = groupby_aggregate_many over the "
                              "full concatenated history",
                "lengths": lengths,
            }
            try:
                from flox_tpu import costmodel as _cm

                with flox_tpu.set_options(costmodel=True, telemetry=True):
                    store_info["analytic_verdict"] = _cm.analytic_prior(
                        "store_query", "recompute", ("store", "recompute"),
                        nelems=len(slab_list) * s_n, ngroups=s_ngroups,
                        dtype="float64",
                    )
            except Exception:  # noqa: BLE001 — verdict is decoration
                pass
        finally:
            shutil.rmtree(sroot, ignore_errors=True)
    except Exception as exc:  # noqa: BLE001 — keep the headline alive
        print(f"flox-tpu bench: store sweep failed: {exc}",
              file=sys.stderr, flush=True)

    # --- telemetry profile of the headline reduction (ISSUE 4) ------------
    # one instrumented pass, OUTSIDE the timed reps so the numbers above
    # stay clean: compile counts + span-phase breakdown make this round
    # diagnosable after the fact — above all the CPU-fallback case, where
    # a low GB/s alone cannot distinguish a retrace storm from a staging
    # bottleneck from plain host-core arithmetic
    from flox_tpu import cache as _flox_cache, telemetry as _telemetry

    try:
        _flox_cache.clear_all()
        jax.clear_caches()
        # the full user-facing path (factorize -> dispatch -> combine ->
        # finalize), not the bare chain kernel: phase spans only exist there
        telemetry_profile = _telemetry.profile_call(
            lambda: np.asarray(
                flox_tpu.groupby_reduce(dev_data, month, func="nanmean")[0]
            )
        )
    except Exception as exc:  # noqa: BLE001 — diagnostics must not kill the bench
        print(f"flox-tpu bench: telemetry profile failed: {exc}",
              file=sys.stderr, flush=True)
        telemetry_profile = None

    # --- analytical cost model (ISSUE 14) ---------------------------------
    # re-run the headline call with the cards plane on: the round's JSON
    # carries each program's analytical flops/bytes + roofline predicted_ms
    # next to the measured GB/s, and the drift sentinel verdict — the
    # "silently got slower after a JAX upgrade" regression detector riding
    # every committed bench artifact
    try:
        from flox_tpu import costmodel as _costmodel

        with flox_tpu.set_options(telemetry=True, costmodel=True):
            np.asarray(flox_tpu.groupby_reduce(dev_data, month, func="nanmean")[0])
            drift = _costmodel.drift_report()
            costmodel_record = {
                # keyed by digest — the registry's identity: one label can
                # hold several cards (one per input signature), and a
                # committed artifact must not let them overwrite each other
                "cards": {
                    digest: {
                        "label": card["label"],
                        "flops": card["flops"],
                        "bytes_accessed": card["bytes_accessed"],
                        "predicted_ms": card["predicted_ms"],
                        "analysis": card["analysis"],
                    }
                    for digest, card in _costmodel.cards().items()
                },
                "platform": _costmodel.platform_name(),
                "drift_flagged": drift["flagged"],
                "drift_threshold": drift["threshold"],
            }
    except Exception as exc:  # noqa: BLE001 — diagnostics must not kill the bench
        print(f"flox-tpu bench: costmodel failed: {exc}",
              file=sys.stderr, flush=True)
        costmodel_record = None

    # --- autotune store feed + regression sentinel (ISSUE 6) --------------
    # the round's sweep results ARE the measurements the autotuner's `auto`
    # dispatch wants: record them under the workload's bands (source=bench
    # outranks nothing — EWMA-merged like any observation) and persist when
    # a cache path is configured. The sentinel then diffs this round's
    # per-family GB/s against the LAST history round (same platform) —
    # computed BEFORE this round is appended, so it compares rounds, not
    # the round against itself.
    from flox_tpu import autotune

    try:
        nelems_bench = nlat * nlon * ntime
        for impl, impl_gbps in sweep_gbps.items():
            autotune.record("segment_sum", impl, impl_gbps, dtype="float32",
                            ngroups=size, nelems=nelems_bench, source="bench")
        for qimpl, q_gbps in (quantile_gbps or {}).items():
            if q_gbps:
                autotune.record("quantile", qimpl, q_gbps, dtype="float32",
                                ngroups=size, nelems=nelems_bench, source="bench")
        # the fused sweep seeds the fused-vs-sequential dispatch family —
        # under the band it MEASURED (its bounded row subset), not the
        # headline workload's
        for cand, f_gbps in ((fused_info or {}).get("fused_sweep_gbps") or {}).items():
            if f_gbps:
                autotune.record(
                    "fused", cand, f_gbps, dtype="float32", ngroups=size,
                    nelems=(fused_info or {}).get("nelems", nelems_bench),
                    source="bench",
                )
        # the highcard sweep seeds the dense-vs-sort routing family, under
        # the universe/element bands it measured
        if highcard_info:
            for cand in ("dense", "sort"):
                hc_gbps = highcard_info.get(f"{cand}_gbps")
                if hc_gbps:
                    autotune.record(
                        "highcard", cand, hc_gbps, dtype="float32",
                        ngroups=highcard_info["ngroups"],
                        nelems=highcard_info["nelems"], source="bench",
                    )
        autotune.save()  # no-op without a configured autotune_cache_path
        families = {"headline": gbps}
        families.update({f"segment_sum[{k}]": v for k, v in sweep_gbps.items()})
        families.update(
            {f"quantile[{k}]": v for k, v in (quantile_gbps or {}).items() if v}
        )
        families["streaming[sync]"] = streaming["gbps_sync"]
        families["streaming[prefetch]"] = streaming["gbps_prefetch"]
        if highcard_info:
            families["highcard[dense]"] = highcard_info["dense_gbps"]
            families["highcard[sort]"] = highcard_info["sort_gbps"]
        families.update(
            {f"fused[{k}]": v
             for k, v in ((fused_info or {}).get("fused_sweep_gbps") or {}).items()
             if v}
        )
        regressions = autotune.regression_sentinel(
            families, history_path=HISTORY_PATH, platform=backend,
            workload={"nlat": nlat, "nlon": nlon, "ntime": ntime,
                      "nbytes": nbytes, "ngroups": size},
        )
        autotune_record = autotune.decision_record()
    except Exception as exc:  # noqa: BLE001 — diagnostics must not kill the bench
        print(f"flox-tpu bench: autotune/sentinel failed: {exc}",
              file=sys.stderr, flush=True)
        regressions = None
        autotune_record = None

    # one shared field set: the persisted hardware record and the stdout
    # line must never drift apart about what was measured
    core = {
        "metric": "ERA5 groupby(time.month).mean() GB/s/chip",
        "value": round(gbps, 2),
        "unit": "GB/s",
        "vs_baseline": round(gbps / gbps_host, 2),
        "baseline": "single-host bincount nanmean (numpy_groupies equivalent)",
        "platform": backend,
        "segment_sum_impl": winner,
        "impl_sweep_gbps": sweep_gbps,
        "quantile_gbps": quantile_gbps,
        "streaming": streaming,
        "fused": fused_info,
        "highcard": highcard_info,
        "registry": registry_info,
        "store": store_info,
        "telemetry": telemetry_profile,
        "costmodel": costmodel_record,
        "autotune": autotune_record,
        "regressions": regressions,
    }
    line = {**core, "note": "measured on the TPU; winner of the impl sweep"}
    _append_history({
        "wall_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        **line,
        # the sentinel matches rounds by platform AND workload: a bounded
        # smoke round must never be compared against a full-size one
        "workload": {"nlat": nlat, "nlon": nlon, "ntime": ntime,
                     "nbytes": nbytes, "ngroups": size},
    })
    print(json.dumps(line))


if __name__ == "__main__":
    main()
