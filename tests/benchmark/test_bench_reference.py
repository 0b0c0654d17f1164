"""The benchmark's float64 reference, comparison and logical bytes."""

import json
import math
import sys
import warnings
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import compare, data, reference, traffic  # noqa: E402

FUNCS = ["sum", "nansum", "count", "mean", "nanmean", "var", "nanvar", "std", "nanstd",
         "min", "nanmin", "max", "nanmax"]


def brute(series, labels, func):
    """One group at a time, one series at a time, with numpy's own reductions."""
    groups = np.unique(labels)
    out = np.empty((series.shape[1], groups.size))
    with np.errstate(all="ignore"), warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # nanmean of an all-NaN group
        for j in range(series.shape[1]):
            for k, g in enumerate(groups):
                v = series[labels == g, j].astype(np.float64)
                out[j, k] = np.count_nonzero(~np.isnan(v)) if func == "count" else getattr(np, func)(v)
    return groups, out


@pytest.mark.parametrize("func", FUNCS)
def test_reference_matches_brute_force(func, monkeypatch):
    rng = np.random.default_rng(0)
    labels = rng.integers(3, 9, 57)
    series = (280 + 10 * rng.standard_normal((57, 7))).astype(np.float32)
    series[rng.random(series.shape) < 0.1] = np.nan
    series[labels == 5, 2] = np.nan  # one group all NaN in one series
    monkeypatch.setattr(reference, "BLOCK_BYTES", 4 * 57 * 3)  # three series per block
    groups, got = reference.groupby(series, labels, [func])
    want_groups, want = brute(series, labels, func)
    np.testing.assert_array_equal(groups, want_groups)
    np.testing.assert_allclose(got[func], want, rtol=1e-12, equal_nan=True)


def test_control_rounds_to_bfloat16():
    series = np.array([[280.3], [281.7]], dtype=np.float32)
    _, exact = reference.groupby(series, np.array([1, 1]), ["mean"])
    _, low = reference.groupby_bf16(series, np.array([1, 1]), ["mean"])
    assert exact["mean"][0, 0] == pytest.approx(281.0, rel=1e-7)
    assert low["mean"][0, 0] == 281.0  # 280.3 -> 280, 281.7 -> 282 in bfloat16


def test_compare_numbers_and_verdict():
    ref = {"nanmean": np.array([[1.0, np.nan], [2.0, 4.0]])}
    same = compare.numbers([1, 2], {"nanmean": ref["nanmean"].copy()}, np.array([1, 2]), ref)
    assert same == {"groups": 0.0, "nan_mismatch": 0.0, "rel_err.nanmean": 0.0}
    off = compare.numbers([1, 3], {"nanmean": np.array([[1.0, 1.0], [2.0, 4.4]])}, [1, 2], ref)
    assert off["groups"] == 1.0 and off["nan_mismatch"] == 1.0
    assert off["rel_err.nanmean"] == pytest.approx(0.1)
    missing = compare.numbers([1, 2], {}, [1, 2], ref)
    assert math.isinf(missing["rel_err.nanmean"]) and missing["nan_mismatch"] == 4
    ok, checks = compare.verdict(same, {"groups": 0, "nan_mismatch": 0, "rel_err.nanmean": 1e-5})
    assert ok and checks["rel_err.nanmean"] == {"value": 0.0, "limit": 1e-5}
    assert not compare.verdict(off, {"groups": 0, "nan_mismatch": 0, "rel_err.nanmean": 1e-5})[0]
    assert not compare.verdict(same, {"groups": 0, "nan_mismatch": 0})[0]  # a number with no limit
    assert compare.worst([{"a": 1.0}, {"a": 3.0, "b": 0.0}]) == {"a": 3.0, "b": math.inf}


def _cell(name):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    w = next(w for w in bench["workloads"] if w["name"] == name)
    c = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((ROOT / c["file"]).read_text())
    return config, traffic.load(ROOT / "benchmark" / "traffic" / f"{w['traffic']}.json")


@pytest.mark.parametrize("name, shape, groups, in_bytes, out_bytes", [
    # 45 x 1440 x 26304 f32 in; 64800 cells x 12 months x 1 statistic out
    ("era5_hourly_025.month_nanmean", (45, 1440, 26304), 12, 45 * 1440 * 26304 * 4, 64800 * 12 * 4),
    # 1,038,240 cells x 696 months in; 12 months x 4 statistics out
    ("perfect_monthly.month_mean_var_min_max", (721, 1440, 696), 12, 721 * 1440 * 696 * 4, 1038240 * 12 * 4 * 4),
    # 180 x 1440 x 26304 in; 259200 cells x 366 days of year out
    ("era5_hourly_025_x4.doy_cohorts_nanmean", (180, 1440, 26304), 366, 180 * 1440 * 26304 * 4, 259200 * 366 * 4),
])
def test_logical_bytes_of_each_cell(name, shape, groups, in_bytes, out_bytes):
    config, mix = _cell(name)
    assert data.shape(config) == shape
    assert traffic.ngroups(config, mix) == groups
    assert traffic.logical_bytes(config, mix, groups) == (in_bytes, out_bytes)


def test_seed_words_and_sample_are_seeded():
    big = 2**31 + 7  # the driver's seeds exceed 32 signed bits
    assert data.seed_words(big) == data.seed_words(big) != data.seed_words(big + 1)
    config, _ = _cell("era5_hourly_025.month_nanmean")
    a, b = data.sample_cells(config, big), data.sample_cells(config, big)
    np.testing.assert_array_equal(a, b)
    assert a.size == 2**30 // (26304 * 4) and np.all(np.diff(a) > 0) and a[-1] < 64800
