"""Zonal statistics over a 2-D label raster: ``groupby_reduce`` of a
(time, y, x) device array by (y, x) labels, against the plain float64 numpy
groupby of ``benchmark/reference.py`` (which imports nothing of flox_tpu).

With ``utils.SPAN_BLOCK_BYTES`` shrunk, the span-blocked path reduces the
raster in slabs of ``y`` rows, and the ``reduce.span_blocks`` counter says
how many; with 1-D labels, or an array within one slab, the span reduces
whole and the counter stays silent.
"""

import numpy as np
import pytest

import flox_tpu
from benchmark import reference
from flox_tpu import groupby_reduce, telemetry, utils

jnp = pytest.importorskip("jax").numpy

FUNCS = ["nanmean", "nansum", "count", "nanmax"]
T = 3


def _asv_raster(ny, nx, counties=5):
    """flox's asv ``NWMMidwest`` raster at a small size: 5 x 5 counties, each
    label the product of its row and column, so group 0 (the first row and
    column) is the largest and 6 of the 17 expected groups never occur."""
    x = np.repeat(np.arange(counties), nx // counties)
    y = np.repeat(np.arange(counties), ny // counties)
    by = x[None, :] * y[:, None]
    return by, np.arange(by.max() + 1)


def _random_raster(ny, nx):
    """Skewed random labels: group 0 holds about half the cells, and groups
    9-11 of the 12 expected never occur."""
    rng = np.random.default_rng(3)
    by = np.where(rng.random((ny, nx)) < 0.5, 0, rng.integers(1, 9, (ny, nx)))
    return by, np.arange(12)


RASTERS = {"asv": _asv_raster, "random": _random_raster}


def _data(by, seed=0):
    rng = np.random.default_rng(seed)
    x = (280 + 10 * rng.standard_normal((T,) + by.shape)).astype(np.float32)
    x[rng.random(x.shape) < 0.05] = np.nan
    x[0][by == by.max()] = np.nan  # one present group is all NaN at the first step
    return x


def _reduce(x, by, func, expected, **kw):
    """``(result, groups, slabs)``: the call on the device array, and the
    slabs its span-blocked path counted (0 where it did not run)."""
    with flox_tpu.set_options(telemetry=True):
        before = telemetry.METRICS.snapshot().get("reduce.span_blocks", 0)
        out, groups = groupby_reduce(jnp.asarray(x), by, func=func, expected_groups=expected, **kw)
        slabs = telemetry.METRICS.snapshot().get("reduce.span_blocks", 0) - before
    return np.asarray(out), groups, slabs


def _reference(x, by, func, expected):
    """The float64 reference over the flattened raster, laid out over the
    expected groups: (T, len(expected))."""
    series = x.reshape(x.shape[0], -1).T
    groups, answers = reference.groupby(series, by.ravel(), [func])
    return reference.over(expected, groups, answers)[1][func]


def _check(got, want, func):
    assert got.shape == want.shape
    if func in ("count", "nanmax"):
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=2e-6)


# (raster, shape (y, x), rows a slab holds, slabs); rows of 16 leave a tail
# slab of 12 rows, and rows of 5 (below one tile of 8) one of 2
ENGAGED = [("asv", (60, 150), 16, 4), ("random", (37, 20), 5, 8)]


@pytest.mark.parametrize("func", FUNCS)
@pytest.mark.parametrize("raster, shape, rows, slabs", ENGAGED)
def test_span_blocked_matches_reference(monkeypatch, raster, shape, rows, slabs, func):
    by, expected = RASTERS[raster](*shape)
    x = _data(by)
    monkeypatch.setattr(utils, "SPAN_BLOCK_BYTES", rows * T * shape[1] * x.itemsize)
    got, groups, counted = _reduce(x, by, func, expected)
    assert counted == slabs
    np.testing.assert_array_equal(groups, expected)
    _check(got, _reference(x, by, func, expected), func)


@pytest.mark.parametrize("func", FUNCS)
def test_span_blocked_equals_whole_span(monkeypatch, func):
    """The slabs give the answers, and the dtype, the whole span gives."""
    by, expected = _asv_raster(60, 150)
    x = _data(by, seed=1)
    whole, _, counted = _reduce(x, by, func, expected)
    assert counted == 0  # the array is within one slab
    monkeypatch.setattr(utils, "SPAN_BLOCK_BYTES", 8 * T * 150 * x.itemsize)
    blocked, _, counted = _reduce(x, by, func, expected)
    assert counted == 8
    assert blocked.dtype == whole.dtype
    _check(blocked, whole, func)


@pytest.mark.parametrize("func", FUNCS)
def test_sort_engine_reduces_compact_codes_in_slabs(monkeypatch, func):
    """The present-groups engine compacts the codes once, then the slabs
    reduce over the compact domain."""
    by, expected = _asv_raster(60, 150)
    x = _data(by, seed=2)
    monkeypatch.setattr(utils, "SPAN_BLOCK_BYTES", 16 * T * 150 * x.itemsize)
    got, _, counted = _reduce(x, by, func, expected, engine="sort")
    assert counted == 4
    _check(got, _reference(x, by, func, expected), func)


@pytest.mark.parametrize("func", FUNCS)
def test_host_engine_reduces_whole(monkeypatch, func):
    """``engine="numpy"`` reduces on the host: no slabs, the same answers."""
    by, expected = _asv_raster(60, 150)
    x = _data(by, seed=5)
    monkeypatch.setattr(utils, "SPAN_BLOCK_BYTES", 64)
    got, _, counted = _reduce(x, by, func, expected, engine="numpy")
    assert counted == 0
    _check(got, _reference(x, by, func, expected), func)


@pytest.mark.parametrize("func", FUNCS)
def test_one_label_dim_reduces_whole(monkeypatch, func):
    """Labels over the last dim only: the span is that dim, and the
    span-blocked path does not run, however small the slab size."""
    monkeypatch.setattr(utils, "SPAN_BLOCK_BYTES", 64)
    rng = np.random.default_rng(4)
    by = rng.integers(0, 7, 150)
    expected = np.arange(9)
    x = _data(np.zeros((60, 150), dtype=int))
    got, _, counted = _reduce(x, by, func, expected)
    assert counted == 0
    want = _reference(x.reshape(-1, 150), by.reshape(1, -1), func, expected).reshape(T, 60, -1)
    _check(got, want, func)
