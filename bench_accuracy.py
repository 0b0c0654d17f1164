"""Accuracy certification at ERA5 scale (VERDICT r2 #2, r3 #2).

Measures — does not argue — the error of every user-reachable reduction
path against a float64 host oracle on the headline workload family
(hourly -> monthly climatology: 26304 steps of ERA5-like temperatures,
12 month groups). Two metrics per path:

* ``max_ulp``  — worst output's distance, in float32 ULPs, from the
  f32-rounding of the exact f64 result (0 = correctly rounded);
* ``max_rel``  — worst relative error vs the f64 oracle.

Paths certified: the three segment-sum lowerings (XLA scatter, MXU
one-hot GEMM, Pallas) with the Pallas kernel in all three accumulation
disciplines (plain / kahan / dd), plus the user-facing fused nanmean and
nanvar through ``generic_kernel`` exactly as ``groupby_reduce`` runs
them.

On CPU the Pallas kernels run in interpret mode, which reproduces the
tiled accumulation structure but not Mosaic's exact MXU reduction order;
the on-chip run of this same script (``chiprun -- python
bench_accuracy.py --json``) is the hardware certificate. The
reduction length is always the full 26304 steps — accumulation error
grows with N, not with the number of cells — while the cell count is
bounded off-chip to keep interpret mode tractable.

Usage:
    python bench_accuracy.py            # markdown table (for docs/engines.md)
    python bench_accuracy.py --json     # one JSON line

Env: FLOX_ACC_CELLS / FLOX_ACC_NTIME / FLOX_ACC_SEED override the shape.

Reference analogue: the reference certifies against numpy_groupies on
f64 hosts (tests/test_core.py assert_equal tolerances); on TPUs f64
hardware does not exist, so the certificate must be measured per path.
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np


def _monotonic_key_f32(x: np.ndarray) -> np.ndarray:
    """Map f32 bit patterns to int64 keys whose difference counts the
    representable floats between two values (the standard sign-magnitude
    to two's-complement trick)."""
    u = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32).astype(np.int64)
    return np.where(u < 0x80000000, u + 0x80000000, 0x100000000 - u)


def ulp_dist_f32(got: np.ndarray, want_f64: np.ndarray) -> np.ndarray:
    """ULP distance between ``got`` (f32) and the f32-rounding of the f64
    oracle. NaN/inf lanes are excluded by the caller."""
    return np.abs(
        _monotonic_key_f32(np.asarray(got, np.float32))
        - _monotonic_key_f32(want_f64.astype(np.float32))
    )


def _measure(got, want_f64):
    got64 = np.asarray(got, np.float64)
    finite = np.isfinite(want_f64) & (want_f64 != 0)
    rel = np.abs(got64 - want_f64)[finite] / np.abs(want_f64)[finite]
    return {
        "max_ulp": int(ulp_dist_f32(got, want_f64)[finite].max()),
        "max_rel": float(rel.max()),
    }


def run(cells: int, ntime: int, seed: int) -> dict:
    import jax

    from flox_tpu import set_options
    from flox_tpu.kernels import generic_kernel
    from flox_tpu.pallas_kernels import segment_sum_pallas

    on_accel = jax.default_backend() != "cpu"

    # month-of-year labels for hourly stamps — the headline workload's
    # grouping (12 groups, ~2192 members each at 3 years)
    day = np.arange(ntime, dtype=np.int64) // 24
    codes = (((day % 365) // 30.44).astype(np.int32)) % 12
    size = 12

    # ERA5-like 2m temperature in Kelvin: a large common offset is the
    # adversarial case for f32 accumulation (relative ULP of the running
    # sum >> ULP of the data)
    rng = np.random.default_rng(seed)
    data = (280.0 + 10.0 * rng.standard_normal((cells, ntime))).astype(np.float32)

    # f64 oracles on host
    want_sum = np.stack(
        [data[:, codes == g].astype(np.float64).sum(axis=1) for g in range(size)],
        axis=1,
    )
    want_mean = np.stack(
        [data[:, codes == g].astype(np.float64).mean(axis=1) for g in range(size)],
        axis=1,
    )
    want_var = np.stack(
        [data[:, codes == g].astype(np.float64).var(axis=1) for g in range(size)],
        axis=1,
    )

    dev = jax.device_put(data)
    dev_codes = jax.device_put(codes)

    table: dict[str, dict] = {}

    # --- segment-sum lowerings through the real dispatch ------------------
    for impl in ("scatter", "matmul"):
        with set_options(segment_sum_impl=impl):
            got = np.asarray(generic_kernel("sum", dev_codes, dev, size=size))
        table[f"sum/{impl}"] = _measure(got, want_sum)

    # pallas × accumulation discipline (kernel entry point: the dispatch
    # would pick one accum from options; the certificate needs all three)
    pdata = np.moveaxis(data, -1, 0)  # (N, K) as the kernel consumes it
    for accum in ("plain", "kahan", "dd"):
        got = np.asarray(
            segment_sum_pallas(
                pdata, codes, size, interpret=not on_accel, accum=accum
            )
        ).T
        table[f"sum/pallas-{accum}"] = _measure(got, want_sum)

    # --- user-facing fused paths exactly as groupby_reduce runs them ------
    got = np.asarray(generic_kernel("nanmean", dev_codes, dev, size=size))
    table["nanmean/auto"] = _measure(got, want_mean)
    got = np.asarray(generic_kernel("nanvar", dev_codes, dev, size=size))
    table["nanvar/auto"] = _measure(got, want_var)

    import time

    return {
        "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "platform": jax.default_backend(),
        "pallas_mode": "mosaic" if on_accel else "interpret",
        "workload": {
            "cells": cells, "ntime": ntime, "ngroups": size,
            "distribution": "280 + 10*N(0,1) Kelvin f32", "seed": seed,
        },
        "table": table,
    }


def to_markdown(rec: dict) -> str:
    w = rec["workload"]
    lines = [
        f"Workload: {w['cells']} cells x {w['ntime']} hourly steps, "
        f"{w['ngroups']} month groups, {w['distribution']}; "
        f"platform={rec['platform']} (pallas: {rec['pallas_mode']}).",
        "",
        "| path | max ULP (f32) | max rel error |",
        "|---|---|---|",
    ]
    for path, m in rec["table"].items():
        lines.append(f"| {path} | {m['max_ulp']} | {m['max_rel']:.2e} |")
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    import jax

    on_accel = jax.default_backend() != "cpu"
    # full reduction length always (26304 = 3 calendar years of hourly steps
    # incl. the leap day, the headline shape); cells bounded off-chip
    cells = int(os.environ.get("FLOX_ACC_CELLS", 4096 if on_accel else 128))
    ntime = int(os.environ.get("FLOX_ACC_NTIME", 24 * (365 * 3 + 1)))
    seed = int(os.environ.get("FLOX_ACC_SEED", 0))

    rec = run(cells, ntime, seed)
    if args.json:
        print(json.dumps(rec))
    else:
        print(to_markdown(rec))


if __name__ == "__main__":
    main()
