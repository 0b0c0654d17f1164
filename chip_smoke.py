"""Bring-up smoke: the main path on the chip, end to end, at the headline size.

One process drives every runtime path once through the public entry points
on the ERA5-shaped hourly -> monthly climatology of BASELINE.md: 65160 grid
cells x 26304 hourly steps (2016-2018), float32 (~6.86 GB), generated on the
device from ``--seed``. Each phase checks its answers (float64 numpy oracles
on a fixed sample of cells, or the same call on another runtime) and prints
one line: cold seconds (compile included), warm seconds, largest error. Any
failure raises, so the exit code is non-zero. The last line of standard
output is the device JSON the driver reads.

    python chip_smoke.py              # one chip: reduce, fused, scan,
                                      # high cardinality, streaming, serve
    python chip_smoke.py --chips 4    # four chips: the mesh phase only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # tiny shapes, CPU

With no TPU (and no ``--rehearse``) it exits non-zero and prints no result.
These seconds are a smoke signal, not a benchmark.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import re
import sys
import time
import warnings
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent

#: 1-degree global grid x 3 calendar years of hourly steps incl. the 2016
#: leap day (BASELINE.md headline shape)
NCELL, NTIME = 181 * 360, 24 * (365 * 3 + 1)
#: tiny shapes for the CPU rehearsal: one leap year keeps 12 months and 366
#: days of year present
NCELL_REHEARSE, NTIME_REHEARSE = 512, 24 * 366
SAMPLE = 256  # cells fetched to the host for the float64 oracles
SCAN_CELLS = 4096  # the K at which the Pallas scan used to be refused
#: high-cardinality drive (verify skill): 1M-label universe, 0.4 % present
HC_UNIVERSE, HC_PRESENT, HC_ROWS = 1_000_000, 4_000, 8
#: f32 tolerances against float64 oracles (relative)
RTOL = {"sum": 2e-5, "mean": 2e-5, "min": 1e-6, "max": 1e-6, "var": 1e-4, "cumsum": 2e-5}


def log(line: str) -> None:
    print(line, flush=True)


def rtol_for(func: str) -> float:
    return RTOL[func.removeprefix("nan").removeprefix("cum") if "cum" not in func else "cumsum"]


def max_rel_err(got, want, scale: float = 1e-30) -> float:
    """Largest error of ``got`` against the float64 ``want``, relative to
    ``max(|want|, scale)``; NaN must sit exactly where the oracle has NaN."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    if got.shape != want.shape:
        raise AssertionError(f"shape {got.shape} != oracle shape {want.shape}")
    if (np.isnan(got) != np.isnan(want)).any():
        raise AssertionError("NaN pattern differs from the oracle")
    ok = ~np.isnan(want)
    if not ok.any():
        return 0.0
    return float(np.max(np.abs(got[ok] - want[ok]) / np.maximum(np.abs(want[ok]), scale)))


def check(what: str, got, want, rtol: float, scale: float = 1e-30) -> float:
    err = max_rel_err(got, want, scale)
    if not err <= rtol:
        raise AssertionError(f"{what}: max relative error {err:.3e} > {rtol:.0e}")
    return err


def block(out):
    import jax

    return jax.tree.map(lambda a: a.block_until_ready() if isinstance(a, jax.Array) else a, out)


def cold_warm(fn):
    """Run ``fn`` twice to completion: (result, cold s, warm s)."""
    t0 = time.perf_counter()
    block(fn())
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = block(fn())
    return out, cold, time.perf_counter() - t0


def phase(name: str, cold: float, warm: float, err: float, extra: str = "") -> None:
    log(
        f"phase {name}: cold_s={cold:.3f} (compile included) warm_s={warm:.3f} "
        f"max_rel_err={err:.3e}" + (f" {extra}" if extra else "")
    )


def pallas_kernels_in(fn, *args) -> str:
    """Pallas kernels the program of ``fn(*args)`` lowers to, or "xla"."""
    import jax

    text = jax.jit(fn).lower(*args).as_text()
    return "+".join(sorted(set(re.findall(r'kernel_name = "(\w+)"', text)))) or "xla"


def oracle(func: str, xs: np.ndarray, labels: np.ndarray, ngroups: int) -> np.ndarray:
    """Per-group float64 numpy reduction over the trailing axis."""
    f = getattr(np, func)
    x64 = xs.astype(np.float64)
    with np.errstate(all="ignore"), warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        return np.stack([f(x64[:, labels == g], axis=1) for g in range(ngroups)], axis=1)


def cumsum_oracle(xs: np.ndarray, labels: np.ndarray) -> np.ndarray:
    out = np.empty(xs.shape, dtype=np.float64)
    for g in np.unique(labels):
        m = labels == g
        out[:, m] = np.nancumsum(xs[:, m].astype(np.float64), axis=1)
    return out


def make_data(seed: int, ncell: int, ntime: int, sharding):
    """ERA5-like 2 m temperature (280 + 10 N(0,1) K) on the device, with NaN
    runs in every 17th cell. Filled block by block in place, so the peak is
    the array plus one block (one ``random.normal`` call needs twice its
    output)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    nblk = next(d for d in range(1, ncell + 1) if ncell % d == 0 and ncell // d * ntime * 4 <= 2**30)
    rows = ncell // nblk

    def gen(key):
        def body(i, buf):
            blk = 280.0 + 10.0 * jax.random.normal(jax.random.fold_in(key, i), (rows, ntime), jnp.float32)
            r = i * rows + lax.broadcasted_iota(jnp.int32, (rows, ntime), 0)
            c = lax.broadcasted_iota(jnp.int32, (rows, ntime), 1)
            blk = jnp.where((r % 17 == 0) & (c % 997 == 5), jnp.nan, blk)
            return lax.dynamic_update_slice(buf, blk, (i * rows, 0))

        return lax.fori_loop(0, nblk, body, jnp.zeros((ncell, ntime), jnp.float32))

    x = jax.jit(gen, out_shardings=sharding)(jax.random.key(seed))
    return x.block_until_ready()


def time_labels(ntime: int):
    import pandas as pd

    t = pd.date_range("2016-01-01", periods=ntime, freq="h")
    return (t.month.to_numpy() - 1).astype(np.int32), (t.dayofyear.to_numpy() - 1).astype(np.int32)


def sample_rows(seed: int, ncell: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    rows = rng.choice(ncell, min(SAMPLE, ncell), replace=False)
    return np.unique(np.concatenate([[0, 17], rows]))  # two cells with NaN runs


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_reduce(x, xs, rows, month):
    import jax.numpy as jnp

    from flox_tpu import groupby_reduce
    from flox_tpu.kernels import generic_kernel

    codes = jnp.asarray(month)
    results = {}
    for func in ("nanmean", "nansum", "nanmin", "nanmax", "nanvar"):
        (out, groups), cold, warm = cold_warm(lambda f=func: groupby_reduce(x, month, func=f))
        if list(groups) != list(range(12)):
            raise AssertionError(f"{func}: groups {groups!r}")
        err = check(func, np.asarray(out)[rows], oracle(func, xs, month, 12), rtol_for(func))
        lowering = pallas_kernels_in(lambda c, a, f=func: generic_kernel(f, c, a, size=12), codes, x)
        phase(f"reduce[{func}]", cold, warm, err, f"lowering={lowering}")
        results[func] = out
    return results


def phase_fused(x, xs, rows, month):
    import jax.numpy as jnp

    from flox_tpu import groupby_reduce
    from flox_tpu.aggregations import fused_chunk_stats
    from flox_tpu.fusion import groupby_aggregate_many, plan_fused

    funcs = ("mean", "var", "min", "max")
    (fused, _), cold, warm = cold_warm(lambda: groupby_aggregate_many(x, month, funcs=funcs))
    err = 0.0
    for func in funcs:
        seq, _ = groupby_reduce(x, month, func=func)
        got = np.asarray(fused[func])
        # fused and sequential run different lowerings (one megakernel pass
        # against one program per statistic): both answer to the oracle,
        # and to each other at the same tolerance
        check(f"fused {func} vs sequential", got, np.asarray(seq, np.float64), rtol_for(func))
        err = max(err, check(f"fused {func}", got[rows], oracle(func, xs, month, 12), rtol_for(func)))
    plan = plan_fused(funcs, None, np.dtype("float32"), None, 0, None)
    lowering = pallas_kernels_in(
        lambda c, a: fused_chunk_stats(plan, c, a, size=12, engine="jax", eager=True),
        jnp.asarray(month), x,
    )
    phase("fused[mean+var+min+max]", cold, warm, err, f"lowering={lowering}")


def phase_scan(x, xs, rows, month):
    import jax.numpy as jnp

    from flox_tpu import groupby_scan
    from flox_tpu.kernels import generic_kernel

    k = min(SCAN_CELLS, x.shape[0])
    xk = x[:k]
    out, cold, warm = cold_warm(lambda: groupby_scan(xk, month, func="nancumsum"))
    if out.shape != xk.shape:
        raise AssertionError(f"scan shape {out.shape} != {xk.shape}")
    sel = rows[rows < k]
    err = check("nancumsum", np.asarray(out[sel]), cumsum_oracle(xs[np.isin(rows, sel)], month), RTOL["cumsum"])
    lowering = pallas_kernels_in(
        lambda c, a: generic_kernel("nancumsum", c, a, size=12), jnp.asarray(month), xk
    )
    phase(f"scan[nancumsum,{k} cells]", cold, warm, err, f"lowering={lowering}")


def phase_highcard(seed: int, n: int):
    from flox_tpu import groupby_reduce

    rng = np.random.default_rng(seed + 1)
    present = np.sort(rng.choice(HC_UNIVERSE, HC_PRESENT, replace=False))
    codes = present[rng.integers(0, HC_PRESENT, n)]
    vals = rng.normal(size=(HC_ROWS, n)).astype(np.float32)
    universe = np.arange(HC_UNIVERSE)
    (srt, _), cold, warm = cold_warm(
        lambda: groupby_reduce(vals, codes, func="nanmean", expected_groups=universe, engine="sort")
    )
    dense, _ = groupby_reduce(vals, codes, func="nanmean", expected_groups=universe, engine="jax")
    cnt = np.bincount(codes, minlength=HC_UNIVERSE).astype(np.float64)
    with np.errstate(invalid="ignore"):
        want = np.stack(
            [np.bincount(codes, weights=v.astype(np.float64), minlength=HC_UNIVERSE) / cnt for v in vals]
        )
    # means of a few N(0, 1) values sit near 0: errors relative to the
    # data's scale (1), not to the mean itself
    err = check("sort engine", np.asarray(srt), want, RTOL["mean"], scale=1.0)
    check("dense engine", np.asarray(dense), want, RTOL["mean"], scale=1.0)
    check("sort vs dense engine", np.asarray(srt), np.asarray(dense, np.float64), RTOL["mean"], scale=1.0)
    phase(f"highcard[sort vs jax,{HC_PRESENT}/{HC_UNIVERSE} present,n={n}]", cold, warm, err)


def phase_streaming(x, month, eager_mean):
    from flox_tpu import streaming_groupby_reduce

    q = x.shape[0] // 4
    host = np.asarray(x[:q])  # host slabs: what a zarr/netCDF loader hands over
    (out, _), cold, warm = cold_warm(lambda: streaming_groupby_reduce(host, month, func="nanmean"))
    err = check("streaming vs eager", np.asarray(out), np.asarray(eager_mean[:q], np.float64), RTOL["mean"])
    phase(f"streaming[nanmean,{q} cells from host]", cold, warm, err)


def phase_serve(x, month):
    from flox_tpu import groupby_reduce
    from flox_tpu.serve import registry
    from flox_tpu.serve.dispatcher import AggregationRequest, Dispatcher

    data = np.asarray(x[: min(2048, x.shape[0])])
    registry.put("era5", array=data, by=month)
    inline = data[:64, : 24 * 366]
    inline_by = month[: 24 * 366]
    reqs = [
        AggregationRequest(func="nanmean", dataset="era5", request_id="ds-nanmean"),
        AggregationRequest(func="nanmax", dataset="era5", request_id="ds-nanmax"),
        AggregationRequest(func="nanvar", dataset="era5", request_id="ds-nanvar"),
        AggregationRequest(func="nansum", dataset="era5", rows=[0, 24 * 90], request_id="ds-rows"),
    ] + [
        AggregationRequest(func=f, array=inline, by=inline_by, request_id=f"inline-{f}")
        for f in ("nanmean", "nanmin", "nansum", "nanvar")
    ]

    async def serve_all():
        d = Dispatcher()
        try:
            return await asyncio.gather(*(d.submit(r) for r in reqs))
        finally:
            await d.close()

    answers, cold, warm = cold_warm(lambda: asyncio.run(serve_all()))
    err = 0.0
    for req, res in zip(reqs, answers):
        if req.dataset is None:
            want, _ = groupby_reduce(inline, inline_by, func=req.func)
        elif req.rows is not None:
            # the entry's groups were fixed at put time: all 12 months
            want, _ = groupby_reduce(
                data[:, slice(*req.rows)], month[slice(*req.rows)], func=req.func,
                expected_groups=np.arange(12),
            )
        else:
            want, _ = groupby_reduce(data, month, func=req.func)
        err = max(err, check(req.request_id, np.asarray(res.result), np.asarray(want, np.float64), 1e-6))
    registry.delete("era5")
    phase(f"serve[{len(reqs)} requests, dataset= and inline]", cold, warm, err)


def run_one_chip(args, ncell: int, ntime: int) -> None:
    import jax
    from jax.sharding import SingleDeviceSharding

    month, _ = time_labels(ntime)
    t0 = time.perf_counter()
    x = make_data(args.seed, ncell, ntime, SingleDeviceSharding(jax.devices()[0]))
    rows = sample_rows(args.seed, ncell)
    xs = np.asarray(x[rows])
    log(f"setup: data {x.shape} {x.dtype} ({x.nbytes / 1e9:.2f} GB) on device in {time.perf_counter() - t0:.3f} s")
    means = phase_reduce(x, xs, rows, month)["nanmean"]
    phase_fused(x, xs, rows, month)
    phase_scan(x, xs, rows, month)
    phase_highcard(args.seed, 2**20 if not args.rehearse else 2**14)
    phase_streaming(x, month, means)
    phase_serve(x, month)


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------


def collectives(compiled_text: str) -> list[str]:
    return sorted(set(re.findall(
        r"\b(all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all)(?:-start)?\(",
        compiled_text,
    )))


def program_collectives(cache, key_has, x, codes_spec) -> list[str]:
    """Collectives in the compiled HLO of the cached mesh program whose
    cache key holds every item of ``key_has``."""
    import jax

    fns = [cache.get(k) for k in list(cache) if all(any(v is item or v == item for v in k) for item in key_has)]
    if len(fns) != 1:
        raise AssertionError(f"expected one cached mesh program for {key_has}, found {len(fns)}")
    codes = jax.ShapeDtypeStruct((x.shape[-1],), np.int32, sharding=codes_spec)
    return collectives(fns[0].lower(x, codes).compile().as_text())


def run_mesh(args, ncell: int, ntime: int) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from flox_tpu import groupby_reduce, groupby_scan
    from flox_tpu.parallel import mapreduce, scan as pscan
    from flox_tpu.parallel.mesh import make_mesh

    month, doy = time_labels(ntime)
    mesh1, mesh4 = make_mesh(1), make_mesh(4)
    along_time = NamedSharding(mesh4, P(None, "data"))
    t0 = time.perf_counter()
    x1 = make_data(args.seed, ncell, ntime, SingleDeviceSharding(jax.devices()[0]))
    rows = sample_rows(args.seed, ncell)
    xs = np.asarray(x1[rows])
    k = min(SCAN_CELLS, ncell)
    xk1 = x1[:k]
    log(f"setup: data {x1.shape} {x1.dtype} ({x1.nbytes / 1e9:.2f} GB) on device 0 in {time.perf_counter() - t0:.3f} s")

    calls = {
        "map-reduce[nanmean,month]": lambda a, m: groupby_reduce(a, month, func="nanmean", method="map-reduce", mesh=m)[0],
        "cohorts[nanmean,dayofyear]": lambda a, m: groupby_reduce(a, doy, func="nanmean", method="cohorts", mesh=m)[0],
    }
    refs = {name: block(call(x1, mesh1)) for name, call in calls.items()}
    ref_scan = block(groupby_scan(xk1, month, func="nancumsum", method="blelloch", mesh=mesh1))

    x4 = jax.device_put(x1, along_time).block_until_ready()
    xk4 = jax.device_put(xk1, along_time).block_until_ready()
    x1.delete()
    xk1.delete()
    for arr in (x4, xk4):
        if len({s.device for s in arr.addressable_shards}) != 4:
            raise AssertionError("input shards do not sit on 4 distinct devices")

    codes_spec = NamedSharding(mesh4, P("data"))
    for name, call in calls.items():
        out, cold, warm = cold_warm(lambda c=call: c(x4, mesh4))
        if len({s.device for s in out.addressable_shards}) != 4:
            raise AssertionError(f"{name}: output does not sit on 4 distinct devices")
        err = check(f"{name} 4 chips vs 1", np.asarray(out), np.asarray(refs[name], np.float64), RTOL["mean"])
        labels, ng = (month, 12) if "month" in name else (doy, 366)
        check(f"{name} vs oracle", np.asarray(out)[rows], oracle("nanmean", xs, labels, ng), RTOL["mean"])
        method = name.split("[")[0]
        coll = program_collectives(mapreduce._PROGRAM_CACHE, (method, mesh4), x4, codes_spec)
        if not {"all-reduce", "reduce-scatter"} & set(coll):
            raise AssertionError(f"{name}: no all-reduce or reduce-scatter in the HLO ({coll})")
        phase(f"mesh {name}", cold, warm, err, f"collectives={'+'.join(coll)}")

    out, cold, warm = cold_warm(lambda: groupby_scan(xk4, month, func="nancumsum", method="blelloch", mesh=mesh4))
    if len({s.device for s in out.addressable_shards}) != 4:
        raise AssertionError("blelloch scan: output does not sit on 4 distinct devices")
    err = check("blelloch 4 chips vs 1", np.asarray(out), np.asarray(ref_scan, np.float64), RTOL["cumsum"])
    coll = program_collectives(pscan._SCAN_CACHE, ("blelloch", mesh4), xk4, codes_spec)
    if not coll:
        raise AssertionError("blelloch scan: no collective in the HLO")
    phase(f"mesh blelloch[nancumsum,{k} cells]", cold, warm, err, f"collectives={'+'.join(coll)}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh phase, across four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on any platform (the CPU rehearsal)")
    args = ap.parse_args()

    sys.path.insert(0, str(HERE))
    try:
        import flox_tpu
    except ImportError as exc:
        sys.exit(f"chip_smoke: flox_tpu is not beside this script ({exc})")
    if Path(flox_tpu.__file__).resolve().parent.parent != HERE:
        sys.exit(f"chip_smoke: imported flox_tpu from {flox_tpu.__file__}, not from {HERE}")
    import jax

    dev = jax.devices()[0]
    count = len(jax.devices())
    if dev.platform != "tpu" and not args.rehearse:
        sys.exit(f"chip_smoke: no TPU here (jax found {dev.platform}); nothing was run")
    if count < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} devices; jax found {count}")
    log(f"device: platform={dev.platform} kind={dev.device_kind} count={count}")
    from flox_tpu.device import compile_cache

    log(f"compile cache: {compile_cache()}")
    ncell, ntime = (NCELL_REHEARSE, NTIME_REHEARSE) if args.rehearse else (NCELL, NTIME)
    t0 = time.perf_counter()
    (run_mesh if args.chips == 4 else run_one_chip)(args, ncell, ntime)
    log(f"total: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
