"""The comparison that decides ``correct``: numbers, each against its limit.

- ``groups``: 1 when the groups the call returned differ from the
  reference's, else 0. Exact.
- ``nan_mismatch``: answers that are NaN on one side only. Exact.
- ``rel_err.<func>``: the widest gap between an answer and the float64
  reference, relative to the reference's magnitude, over the answers both
  sides give as numbers.

The limits live in the traffic mix's file; ``PERF.md`` gives the readings
each was set from.
"""

from __future__ import annotations

import math

import numpy as np


def numbers(got_groups, got: dict, ref_groups, ref: dict) -> dict[str, float]:
    """The compared numbers for one call's answers at the sampled cells."""
    out = {"groups": 0.0 if np.array_equal(np.asarray(got_groups), np.asarray(ref_groups)) else 1.0}
    mismatch = 0
    for f, want in ref.items():
        have = got.get(f)
        if have is None or np.shape(have) != want.shape:
            out[f"rel_err.{f}"] = math.inf
            mismatch += want.size
            continue
        have = np.asarray(have, dtype=np.float64)
        nan_have, nan_want = np.isnan(have), np.isnan(want)
        mismatch += int(np.count_nonzero(nan_have != nan_want))
        both = ~nan_have & ~nan_want
        if not both.any():
            out[f"rel_err.{f}"] = 0.0
            continue
        with np.errstate(all="ignore"):
            rel = np.abs(have[both] - want[both]) / np.maximum(np.abs(want[both]), np.finfo(np.float64).tiny)
        out[f"rel_err.{f}"] = float(np.max(rel))
    out["nan_mismatch"] = float(mismatch)
    return out


def worst(readings: list[dict[str, float]]) -> dict[str, float]:
    """Each number's worst value over several calls' readings."""
    keys = sorted({k for r in readings for k in r})
    return {k: max(r.get(k, math.inf) for r in readings) for k in keys}


def verdict(values: dict[str, float], limits: dict[str, float]) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``. A number with no limit, or a
    limit with no number, fails."""
    names = sorted(set(values) | set(limits))
    checks = {n: {"value": values.get(n, math.inf), "limit": limits.get(n, -math.inf)} for n in names}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return correct, checks
