"""End-to-end groupby_reduce tests against per-group numpy oracles.

Modeled on the reference's giant parametrized sweep
(tests/test_core.py:222-388): {func × engine × 1d/2d × NaN-in-data ×
NaN-in-by × expected/None × finalize_kwargs} compared against plain numpy
applied to each group's masked slice.
"""

import numpy as np
import pandas as pd
import pytest
import scipy.stats

from flox_tpu.core import groupby_reduce

RNG = np.random.default_rng(123)

ALL_FUNCS = [
    "sum", "nansum", "prod", "nanprod", "mean", "nanmean", "var", "nanvar",
    "std", "nanstd", "max", "nanmax", "min", "nanmin", "argmax", "nanargmax",
    "argmin", "nanargmin", "any", "all", "count",
    "first", "last", "nanfirst", "nanlast",
    "median", "nanmedian", "quantile", "nanquantile", "mode", "nanmode",
]


def _np_oracle(func):
    """func name -> plain numpy callable over axis=-1 (independent oracle)."""
    if func == "count":
        return lambda g, **kw: np.sum(~np.isnan(g), axis=-1)
    if func in ("first", "nanfirst"):
        def first_(g, **kw):
            if func == "first":
                return g[..., 0]
            out = np.full(g.shape[:-1], np.nan)
            for idx in np.ndindex(g.shape[:-1]):
                valid = g[idx][~np.isnan(g[idx])]
                if valid.size:
                    out[idx] = valid[0]
            return out
        return first_
    if func in ("last", "nanlast"):
        def last_(g, **kw):
            if func == "last":
                return g[..., -1]
            out = np.full(g.shape[:-1], np.nan)
            for idx in np.ndindex(g.shape[:-1]):
                valid = g[idx][~np.isnan(g[idx])]
                if valid.size:
                    out[idx] = valid[-1]
            return out
        return last_
    if func in ("mode", "nanmode"):
        def mode_(g, **kw):
            nan_policy = "omit" if func == "nanmode" else "propagate"
            res = scipy.stats.mode(g, axis=-1, nan_policy=nan_policy, keepdims=False)
            return res.mode
        return mode_
    if func in ("quantile", "nanquantile"):
        base = np.nanquantile if func == "nanquantile" else np.quantile
        return lambda g, q=0.5, **kw: base(g, q, axis=-1)
    np_func = getattr(np, func)
    return lambda g, **kw: np_func(g, axis=-1, **kw)


def compare(result, expected, func):
    result = np.asarray(result)
    rtol, atol = 1e-12, 1e-12
    np.testing.assert_allclose(
        result.astype(np.float64),
        np.asarray(expected).astype(np.float64),
        rtol=rtol,
        atol=atol,
        equal_nan=True,
    )


def reference_loop(func, values, codes, size, **kw):
    """Apply the oracle per group; NaN where undefined."""
    oracle = _np_oracle(func)
    q = kw.get("q")
    lead = values.shape[:-1]
    extra = (len(q),) if q is not None and np.ndim(q) > 0 else ()
    out = np.full(extra + lead + (size,), np.nan)
    for g in range(size):
        sel = np.flatnonzero(codes == g)
        if sel.size == 0:
            if func in ("sum", "nansum"):
                out[..., g] = 0
            elif func in ("prod", "nanprod"):
                out[..., g] = 1
            elif func == "count":
                out[..., g] = 0
            elif func == "all":
                out[..., g] = 1
            elif func == "any":
                out[..., g] = 0
            elif "arg" in func:
                out[..., g] = -1
            continue
        grp = values[..., sel]
        with np.errstate(invalid="ignore", divide="ignore"), np.testing.suppress_warnings() as sup:
            sup.filter(RuntimeWarning)
            if "arg" in func:
                if func.startswith("nanarg"):
                    allnan = np.all(np.isnan(grp), axis=-1)
                    safe = np.where(
                        np.isnan(grp), -np.inf if "max" in func else np.inf, grp
                    )
                    local = np.argmax(safe, -1) if "max" in func else np.argmin(safe, -1)
                    res = np.where(allnan, -1, sel[local])
                else:
                    local = np.argmax(grp, -1) if "max" in func else np.argmin(grp, -1)
                    res = sel[local]
            elif func.startswith("nan") and func not in ("nanfirst", "nanlast", "nanmode", "nanquantile", "nanmedian"):
                allnan = np.all(np.isnan(grp), axis=-1)
                res = _np_oracle(func)(grp, **kw)
                if func in ("nanmean", "nanvar", "nanstd", "nanmedian"):
                    res = np.where(allnan, np.nan, res)
            else:
                res = _np_oracle(func)(grp, **kw)
            if func in ("nanquantile",) and np.ndim(kw.get("q", 0.5)) > 0:
                out[..., g] = res
                continue
        out[..., g] = res
    return out


@pytest.mark.parametrize("shape", ["1d", "2d"])
@pytest.mark.parametrize("add_nan", [False, True])
@pytest.mark.parametrize("func", ALL_FUNCS)
def test_groupby_reduce_all(engine, func, shape, add_nan):
    n, size = 60, 4
    codes = RNG.integers(0, size, n)
    labels = codes.astype(np.int64)
    values = np.round(RNG.normal(size=(3, n) if shape == "2d" else (n,)), 1)
    if add_nan:
        values[..., RNG.random(n) < 0.25] = np.nan
    # no skips: the argmax/argmin NaN semantics and partial-NaN mode are
    # pinned to numpy / scipy>=1.11 behavior (VERDICT r3 #10)

    fkw = {}
    if func in ("var", "nanvar", "std", "nanstd"):
        fkw = {"ddof": 1}
    if func in ("quantile", "nanquantile"):
        fkw = {"q": 0.7}

    result, groups = groupby_reduce(values, labels, func=func, engine=engine, finalize_kwargs=fkw)
    np.testing.assert_array_equal(groups, np.arange(size))

    expected = reference_loop(func, values, codes, size, **fkw)
    # ddof guard: groups with n<=ddof give NaN in both
    compare(result, expected, func)


@pytest.mark.parametrize("nby", [2, 3])
@pytest.mark.parametrize("nan_by", [False, True])
@pytest.mark.parametrize("func", ALL_FUNCS)
def test_groupby_reduce_all_multiby(engine, func, nby, nan_by):
    """Product-grid correctness for every func at nby 2-3, with and without
    NaN labels, against the per-group oracle (reference
    tests/test_core.py:222-388 sweeps nby 1-3; the nby=1 leg is
    test_groupby_reduce_all)."""
    import zlib

    rng = np.random.default_rng(zlib.crc32(f"{func}-{nby}-{nan_by}".encode()))
    n = 60
    values = np.round(rng.normal(size=n), 1)
    sizes = (3, 2, 2)[:nby]
    bys = [rng.integers(0, s, n).astype(np.float64) for s in sizes]
    if nan_by:
        for b in bys:
            b[rng.random(n) < 0.15] = np.nan

    fkw = {}
    if func in ("var", "nanvar", "std", "nanstd"):
        fkw = {"ddof": 1}
    if func in ("quantile", "nanquantile"):
        fkw = {"q": 0.7}

    result, *groups = groupby_reduce(
        values, *bys, func=func, engine=engine, finalize_kwargs=fkw
    )

    # oracle: row-major ravel of per-by codes over the discovered-group grid
    exp_groups = [np.unique(b[~np.isnan(b)]) for b in bys]
    for g, e in zip(groups, exp_groups):
        np.testing.assert_array_equal(np.asarray(g, dtype=np.float64), e)
    grid = tuple(len(e) for e in exp_groups)
    flat_codes = np.zeros(n, dtype=np.int64)
    invalid = np.zeros(n, dtype=bool)
    for b, e in zip(bys, exp_groups):
        nanmask = np.isnan(b)
        c = np.searchsorted(e, np.where(nanmask, e[0], b))
        flat_codes = flat_codes * len(e) + c
        invalid |= nanmask
    flat_codes[invalid] = -1

    expected = reference_loop(func, values, flat_codes, int(np.prod(grid)), **fkw)
    assert np.asarray(result).shape == grid
    compare(np.asarray(result).reshape(-1), expected, func)


@pytest.mark.parametrize("func", ["sum", "nanmean", "max", "count"])
def test_expected_groups_reindex(engine, func):
    labels = np.array([1, 1, 3, 3, 5])
    vals = np.array([1.0, 2.0, 3.0, 4.0, 5.0])
    result, groups = groupby_reduce(
        vals, labels, func=func, engine=engine, expected_groups=np.array([1, 2, 3, 4, 5])
    )
    np.testing.assert_array_equal(groups, [1, 2, 3, 4, 5])
    res = np.asarray(result).astype(float)
    if func == "sum":
        np.testing.assert_allclose(res, [3, 0, 7, 0, 5])
    elif func == "count":
        np.testing.assert_allclose(res, [2, 0, 2, 0, 1])
    elif func == "nanmean":
        np.testing.assert_allclose(res, [1.5, np.nan, 3.5, np.nan, 5.0], equal_nan=True)
    elif func == "max":
        np.testing.assert_allclose(res, [2, np.nan, 4, np.nan, 5], equal_nan=True)


def test_nan_labels_dropped(engine):
    labels = np.array([0.0, np.nan, 0.0, 1.0])
    vals = np.array([1.0, 100.0, 2.0, 3.0])
    result, groups = groupby_reduce(vals, labels, func="sum", engine=engine)
    np.testing.assert_allclose(np.asarray(result).astype(float), [3.0, 3.0])
    np.testing.assert_array_equal(groups, [0.0, 1.0])


def test_binning(engine):
    vals = np.array([0.5, 1.5, 2.5, 3.5, 4.5])
    result, bins = groupby_reduce(
        vals, vals, func="count", engine=engine,
        expected_groups=np.array([0.0, 2.0, 4.0, 6.0]), isbin=True,
    )
    assert isinstance(bins, pd.IntervalIndex)
    np.testing.assert_array_equal(np.asarray(result), [2, 2, 1])


def test_multi_by_product_grid(engine):
    by1 = np.array([0, 0, 1, 1, 0, 1])
    by2 = np.array(["a", "b", "a", "b", "a", "a"])
    vals = np.arange(6.0)
    result, g1, g2 = groupby_reduce(vals, by1, by2, func="sum", engine=engine)
    np.testing.assert_array_equal(g1, [0, 1])
    np.testing.assert_array_equal(g2, ["a", "b"])
    # grid: (0,a)=0+4, (0,b)=1, (1,a)=2+5, (1,b)=3
    np.testing.assert_allclose(np.asarray(result).astype(float), [[4, 1], [7, 3]])


def test_partial_axis_reduction(engine):
    # labels 2d, reduce only the last axis -> per-row group spaces
    labels = np.array([[0, 1, 0], [1, 1, 0]])
    vals = np.arange(6.0).reshape(2, 3)
    result, groups = groupby_reduce(vals, labels, func="sum", engine=engine, axis=-1)
    np.testing.assert_allclose(np.asarray(result).astype(float), [[2, 1], [5, 7]])


def test_axis_beyond_by(engine):
    # reduce over an axis the labels don't span: labels broadcast
    labels = np.array([0, 1, 0])
    vals = np.arange(6.0).reshape(2, 3)
    result, groups = groupby_reduce(vals, labels, func="sum", engine=engine, axis=(0, 1))
    np.testing.assert_allclose(np.asarray(result).astype(float), [0 + 2 + 3 + 5, 1 + 4])


def test_min_count(engine):
    labels = np.array([0, 0, 1])
    vals = np.array([1.0, np.nan, np.nan])
    result, _ = groupby_reduce(vals, labels, func="nansum", engine=engine, min_count=1)
    np.testing.assert_allclose(np.asarray(result).astype(float), [1.0, np.nan], equal_nan=True)
    result, _ = groupby_reduce(vals, labels, func="nansum", engine=engine, min_count=2)
    np.testing.assert_allclose(np.asarray(result).astype(float), [np.nan, np.nan], equal_nan=True)


def test_sort_false(engine):
    labels = np.array([3, 1, 3, 2])
    vals = np.array([1.0, 2.0, 3.0, 4.0])
    _, groups_sorted = groupby_reduce(vals, labels, func="sum", engine=engine, sort=True)
    np.testing.assert_array_equal(groups_sorted, [1, 2, 3])
    _, groups_unsorted = groupby_reduce(vals, labels, func="sum", engine=engine, sort=False)
    np.testing.assert_array_equal(groups_unsorted, [3, 1, 2])


def test_datetime_minmax(engine):
    dt = np.array(["2020-01-03", "2020-01-01", "2020-01-02", "NaT"], dtype="datetime64[ns]")
    labels = np.array([0, 0, 1, 1])
    result, _ = groupby_reduce(dt, labels, func="min", engine=engine)
    assert result.dtype == dt.dtype
    np.testing.assert_array_equal(
        result, np.array(["2020-01-01", "NaT"], dtype="datetime64[ns]")
    )
    result, _ = groupby_reduce(dt, labels, func="nanmin", engine=engine)
    np.testing.assert_array_equal(
        result, np.array(["2020-01-01", "2020-01-02"], dtype="datetime64[ns]")
    )


def test_datetime_mean_casts_back(engine):
    # non-dtype-preserving reductions of datetimes return the datetime dtype,
    # NaN -> NaT (parity: reference core.py:1205-1211); var-like results keep
    # numeric units (ns²) and counts/indices stay integral
    dt = np.array(
        ["2021-01-01T00", "2021-01-01T12", "2021-01-02T00", "NaT"],
        dtype="datetime64[ns]",
    )
    labels = np.array([0, 0, 1, 1])
    result, _ = groupby_reduce(dt, labels, func="nanmean", engine=engine)
    assert result.dtype == dt.dtype
    np.testing.assert_array_equal(
        result, np.array(["2021-01-01T06", "2021-01-02T00"], dtype="datetime64[ns]")
    )
    # non-skipna mean propagates NaT
    result, _ = groupby_reduce(dt, labels, func="mean", engine=engine)
    assert not np.isnat(result[0]) and np.isnat(result[1])
    # all-NaT group -> NaT
    result, _ = groupby_reduce(
        np.array(["2021-01-01", "NaT", "NaT"], dtype="datetime64[ns]"),
        np.array([0, 1, 1]), func="nanmean", engine=engine,
    )
    assert np.isnat(result[1])
    result, _ = groupby_reduce(dt, labels, func="nanmedian", engine=engine)
    assert result.dtype == dt.dtype
    assert result[1] == np.datetime64("2021-01-02T00", "ns")
    result, _ = groupby_reduce(dt, labels, func="nanvar", engine=engine)
    assert result.dtype.kind == "f"
    result, _ = groupby_reduce(dt, labels, func="count", engine=engine)
    assert result.dtype.kind == "i" and list(result) == [2, 1]
    result, _ = groupby_reduce(dt, labels, func="nanargmax", engine=engine)
    assert result.dtype.kind == "i" and list(result) == [1, 2]
    # timedelta round-trips the same way
    td = dt - dt[0]
    result, _ = groupby_reduce(td, labels, func="nanmean", engine=engine)
    assert result.dtype == td.dtype
    assert result[0] == np.timedelta64(6 * 3600 * 10**9, "ns")


def test_datetime_mean_mesh():
    from flox_tpu.parallel import make_mesh

    dt = np.array(
        ["2021-01-01T00", "2021-01-01T12", "2021-01-02T00", "NaT"],
        dtype="datetime64[ns]",
    )
    labels = np.array([0, 0, 1, 1])
    result, _ = groupby_reduce(dt, labels, func="nanmean", method="map-reduce", mesh=make_mesh(4))
    assert result.dtype == dt.dtype
    np.testing.assert_array_equal(
        result, np.array(["2021-01-01T06", "2021-01-02T00"], dtype="datetime64[ns]")
    )


def test_bool_input(engine):
    labels = np.array([0, 0, 1, 1])
    vals = np.array([True, False, True, True])
    result, _ = groupby_reduce(vals, labels, func="sum", engine=engine)
    np.testing.assert_array_equal(np.asarray(result), [1, 2])
    result, _ = groupby_reduce(vals, labels, func="all", engine=engine)
    np.testing.assert_array_equal(np.asarray(result), [False, True])


def test_dtype_request(engine):
    labels = np.array([0, 1, 0])
    vals = np.array([1, 2, 3], dtype=np.int32)
    result, _ = groupby_reduce(vals, labels, func="sum", engine=engine, dtype=np.float32)
    assert np.asarray(result).dtype == np.float32


def test_fill_value_absent_groups(engine):
    labels = np.array([0, 0])
    vals = np.array([1.0, 2.0])
    result, _ = groupby_reduce(
        vals, labels, func="sum", engine=engine,
        expected_groups=np.array([0, 1]), fill_value=-999.0,
    )
    np.testing.assert_allclose(np.asarray(result).astype(float), [3.0, -999.0])


def test_jax_input_array(engine):
    import jax.numpy as jnp

    labels = np.array([0, 1, 0])
    vals = jnp.asarray([1.0, 2.0, 3.0])
    result, _ = groupby_reduce(vals, labels, func="sum", engine="jax")
    np.testing.assert_allclose(np.asarray(result), [4.0, 2.0])


def test_quantile_multi_q(engine):
    labels = np.array([0, 0, 0, 1, 1, 1])
    vals = np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
    result, _ = groupby_reduce(
        vals, labels, func="quantile", engine=engine, finalize_kwargs={"q": [0.0, 0.5, 1.0]}
    )
    np.testing.assert_allclose(
        np.asarray(result), [[1.0, 4.0], [2.0, 5.0], [3.0, 6.0]]
    )


# --- regression tests for review findings -----------------------------------


def test_datetime_ns_precision(engine):
    # max of ns-resolution timestamps must be exact (no float roundtrip)
    dt = np.array(
        ["2000-01-01T00:00:00.123456789", "2000-01-01T00:00:00.123456456"],
        dtype="datetime64[ns]",
    )
    out, _ = groupby_reduce(dt, np.array([0, 0]), func="max", engine=engine)
    assert out[0] == dt[0]


def test_datetime_nat_leading_dims(engine):
    # NaT exclusion must be per-element, not collapsed across leading dims
    dt2 = np.array(
        [["NaT", "2000-01-02", "2000-01-03", "NaT"],
         ["2000-01-05", "2000-01-06", "2000-01-07", "2000-01-08"]],
        dtype="datetime64[ns]",
    )
    by = np.array([0, 0, 1, 1])
    out, _ = groupby_reduce(dt2, by, func="nanmin", engine=engine)
    expected = np.array(
        [["2000-01-02", "2000-01-03"], ["2000-01-05", "2000-01-07"]],
        dtype="datetime64[ns]",
    )
    np.testing.assert_array_equal(out, expected)
    # non-skipna: NaT propagates
    out, _ = groupby_reduce(dt2, by, func="min", engine=engine)
    assert np.isnat(out[0]).all() and not np.isnat(out[1]).any()


def test_min_count_int_input(engine):
    # min_count on integer input must produce NaN, not a silent 0
    r, _ = groupby_reduce(
        np.array([1, 2, 3, 4]), np.array([0, 0, 1, 2]),
        func="nansum", min_count=2, engine=engine,
    )
    np.testing.assert_allclose(np.asarray(r).astype(float), [3, np.nan, np.nan], equal_nan=True)


def test_jit_bundle_cache_stable():
    # NaN fills must not defeat the jit program cache
    from flox_tpu.core import _jitted_bundle

    _jitted_bundle.cache_clear()
    for _ in range(3):
        groupby_reduce(np.arange(6.0), np.array([0, 1, 0, 1, 0, 1]), func="mean", engine="jax")
    info = _jitted_bundle.cache_info()
    assert info.misses == 1 and info.hits == 2


def test_invalid_method():
    with pytest.raises(ValueError, match="method"):
        groupby_reduce(np.arange(4.0), np.array([0, 1, 0, 1]), func="sum", method="bogus")


# --- dtype preservation matrix (reference test_core.py:1135-1176) -----------


DTYPE_FUNCS_PRESERVING = ["max", "nanmax", "min", "nanmin", "first", "last", "nanfirst", "nanlast"]


@pytest.mark.parametrize("dtype", [np.int32, np.int64, np.float32, np.float64])
@pytest.mark.parametrize("func", DTYPE_FUNCS_PRESERVING)
def test_dtype_preserved(engine, func, dtype):
    labels = np.array([0, 1, 0, 1])
    vals = np.array([4, 1, 3, 2], dtype=dtype)
    result, _ = groupby_reduce(vals, labels, func=func, engine=engine)
    assert np.asarray(result).dtype == np.dtype(dtype), (func, dtype)


@pytest.mark.parametrize("dtype", [np.int32, np.float32, np.float64])
def test_dtype_sum_promotes_ints(engine, dtype):
    labels = np.array([0, 1, 0, 1])
    vals = np.array([4, 1, 3, 2], dtype=dtype)
    result, _ = groupby_reduce(vals, labels, func="sum", engine=engine)
    got = np.asarray(result).dtype
    if np.dtype(dtype).kind == "i":
        assert got.kind == "i" and got.itemsize >= 4
    else:
        assert got == np.dtype(dtype)


@pytest.mark.parametrize("func", ["mean", "nanmean", "var", "nanvar"])
def test_dtype_mean_of_ints_is_float(engine, func):
    labels = np.array([0, 1, 0, 1])
    vals = np.array([4, 1, 3, 2], dtype=np.int64)
    result, _ = groupby_reduce(vals, labels, func=func, engine=engine)
    assert np.asarray(result).dtype.kind == "f"


def test_dtype_count_is_int(engine):
    result, _ = groupby_reduce(
        np.array([1.0, 2.0]), np.array([0, 1]), func="count", engine=engine
    )
    assert np.asarray(result).dtype.kind == "i"


# --- fill_value behaviour across funcs (reference test_core.py:1109-1133) ---


FILL_FUNCS = ["sum", "nansum", "prod", "mean", "nanmean", "max", "nanmin", "var",
              "std", "count", "first", "nanlast", "median", "nanquantile"]


@pytest.mark.parametrize("func", FILL_FUNCS)
def test_fill_value_applied_to_absent_groups(engine, func):
    labels = np.array([0, 0, 2, 2])
    vals = np.array([1.0, 2.0, 3.0, 4.0])
    fkw = {"q": 0.5} if "quantile" in func else {}
    result, _ = groupby_reduce(
        vals, labels, func=func, engine=engine,
        expected_groups=np.array([0, 1, 2]), fill_value=-123.0, finalize_kwargs=fkw,
    )
    res = np.asarray(result).astype(float)
    assert res[1] == -123.0, (func, res)
    assert res[0] != -123.0 and res[2] != -123.0


def test_explicit_nat_fill(engine):
    # an explicit NaT fill must not crash or round timestamps through float
    dt = np.array(["2000-01-01T00:00:00.123456789", "2000-01-02"], dtype="datetime64[ns]")
    labels = np.array([0, 0])
    result, _ = groupby_reduce(
        dt, labels, func="first", engine=engine,
        expected_groups=np.array([0, 1]), fill_value=np.datetime64("NaT"),
    )
    assert result.dtype == dt.dtype
    assert result[0] == dt[0] and np.isnat(result[1])


def test_min_count_complex(engine):
    # min_count masking must not destroy imaginary parts
    vals = np.array([1 + 2j, 3 - 1j, 9 + 9j])
    labels = np.array([0, 0, 1])
    result, _ = groupby_reduce(vals, labels, func="nansum", engine=engine, min_count=2)
    res = np.asarray(result)
    assert res.dtype.kind == "c"
    assert res[0] == 4 + 1j and np.isnan(res[1].real)


def test_custom_aggregation(engine):
    # users can define custom aggregations (public Aggregation export,
    # reference aggregations.py:161)
    from flox_tpu import Aggregation

    def sum_of_cubes(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
        import flox_tpu.engine_numpy as en

        arr = np.asarray(array)
        return en.generic_kernel("sum", group_idx, arr**3, size=size, fill_value=fill_value)

    agg = Aggregation("sum_of_cubes", numpy=(sum_of_cubes,), chunk=(sum_of_cubes,), combine=("sum",))
    vals = np.array([1.0, 2.0, 3.0, 4.0])
    labels = np.array([0, 0, 1, 1])
    result, groups = groupby_reduce(vals, labels, func=agg, engine=engine)
    np.testing.assert_allclose(np.asarray(result).astype(float), [9.0, 91.0])


def test_three_groupers_product_grid(engine):
    # nby=3 (reference sweep covers nby 1-3, test_core.py:222-388)
    rng = np.random.default_rng(77)
    n = 60
    b1 = rng.integers(0, 2, n)
    b2 = rng.integers(0, 3, n)
    b3 = rng.integers(0, 2, n)
    vals = np.round(rng.normal(size=n), 1)
    result, g1, g2, g3 = groupby_reduce(vals, b1, b2, b3, func="sum", engine=engine)
    assert np.asarray(result).shape == (2, 3, 2)
    expected = np.zeros((2, 3, 2))
    for i in range(2):
        for j in range(3):
            for k in range(2):
                expected[i, j, k] = vals[(b1 == i) & (b2 == j) & (b3 == k)].sum()
    np.testing.assert_allclose(np.asarray(result).astype(float), expected, rtol=1e-12)


def test_datetime_sum_nat_propagates(engine):
    # review regression: non-skipna sum must not cast the NaN-bearing float
    # back to int64 mid-reduction (kernel dtype request skipped on the
    # datetime path)
    td = np.array([1000, 2000, 3000, "NaT"], dtype="timedelta64[ns]")
    labels = np.array([0, 0, 1, 1])
    result, _ = groupby_reduce(td, labels, func="sum", engine=engine)
    assert result.dtype == td.dtype
    assert result[0] == np.timedelta64(3000, "ns") and np.isnat(result[1])
    result, _ = groupby_reduce(td, labels, func="nansum", engine=engine)
    assert result[1] == np.timedelta64(3000, "ns")


class TestNonNumericData:
    """first/last/count on string/object arrays via the position-proxy path
    (reference: its numpy engines accept any dtype; strategies.py unicode)."""

    S = np.array(["a", "bb", "ccc", "dd", None, "e"], dtype=object)
    LABELS = np.array([0, 1, 0, 1, 2, 2])

    @pytest.mark.parametrize(
        "func,expected",
        [
            ("first", ["a", "bb", None]),
            ("last", ["ccc", "dd", "e"]),
            ("nanfirst", ["a", "bb", "e"]),
            ("nanlast", ["ccc", "dd", "e"]),
            ("count", [2, 2, 1]),
        ],
    )
    def test_object_reductions(self, func, expected):
        result, groups = groupby_reduce(self.S, self.LABELS, func=func)
        assert list(np.asarray(result)) == expected
        np.testing.assert_array_equal(groups, [0, 1, 2])

    def test_unicode_with_empty_group(self):
        s = np.array(["x", "y", "z", "w"])
        labels = np.array([0, 0, 2, 2])
        result, _ = groupby_reduce(
            s, labels, func="last", expected_groups=np.array([0, 1, 2])
        )
        assert list(result) == ["y", None, "w"]

    def test_on_mesh(self):
        from flox_tpu.parallel import make_mesh

        s = np.tile(np.array(["x", "y", "z", "w"]), 4)
        labels = np.tile(np.array([0, 0, 2, 2]), 4)
        result, _ = groupby_reduce(
            s, labels, func="first", method="map-reduce", mesh=make_mesh(8)
        )
        assert list(result) == ["x", "z"]

    def test_2d_strings(self):
        s = np.array([["a", "b", "c"], ["d", "e", "f"]], dtype=object)
        labels = np.array([0, 1, 0])
        result, _ = groupby_reduce(s, labels, func="last")
        assert np.asarray(result).tolist() == [["c", "b"], ["f", "e"]]

    def test_unsupported_func_raises(self):
        with pytest.raises(TypeError, match="non-numeric data"):
            groupby_reduce(self.S, self.LABELS, func="sum")

    def test_count_honors_fill_value(self):
        s = np.array(["x", "y"], dtype=object)
        labels = np.array([0, 0])
        result, _ = groupby_reduce(
            s, labels, func="count", fill_value=-1,
            expected_groups=np.array([0, 1]),
        )
        assert list(np.asarray(result)) == [2, -1]

    def test_finalize_kwargs_rejected(self):
        with pytest.raises(NotImplementedError, match="finalize_kwargs"):
            groupby_reduce(self.S, self.LABELS, func="count", finalize_kwargs={"q": 0.5})


@pytest.mark.parametrize("engine", ["jax", "numpy"])
class TestPinnedEdgeSemantics:
    """VERDICT r3 #10: inf/NaN argreduction ties and partial-NaN mode are
    pinned, not skipped. Oracles: numpy argmax/argmin; scipy>=1.11
    stats.mode(nan_policy="propagate")."""

    def test_argmax_first_nan_beats_inf(self, engine):
        vals = np.array([np.inf, np.nan, 3.0, np.nan, -np.inf, 2.0])
        codes = np.array([0, 0, 0, 1, 1, 1])
        got, _ = groupby_reduce(vals, codes, func="argmax", engine=engine)
        np.testing.assert_array_equal(np.asarray(got), [1, 3])  # first NaN wins
        got, _ = groupby_reduce(vals, codes, func="argmin", engine=engine)
        np.testing.assert_array_equal(np.asarray(got), [1, 3])
        # and without NaN, inf wins normally
        clean = np.array([1.0, np.inf, -np.inf, 5.0])
        ccodes = np.array([0, 0, 0, 0])
        got, _ = groupby_reduce(clean, ccodes, func="argmax", engine=engine)
        assert int(np.asarray(got)[0]) == 1

    def test_mode_partial_nan_counts_as_one_value(self, engine):
        import scipy.stats

        vals = np.array([1.0, 1.0, 2.0, np.nan,  # g0: mode 1.0 (NaN minority)
                         5.0, np.nan, np.nan, 7.0])  # g1: mode NaN (majority)
        codes = np.array([0, 0, 0, 0, 1, 1, 1, 1])
        got, _ = groupby_reduce(vals, codes, func="mode", engine=engine)
        got = np.asarray(got)
        for g in range(2):
            want = scipy.stats.mode(
                vals[codes == g], nan_policy="propagate", keepdims=False
            ).mode
            np.testing.assert_array_equal(got[g], want)
        assert got[0] == 1.0 and np.isnan(got[1])

    def test_mode_nan_tie_prefers_value(self, engine):
        # 2x NaN vs 2x 3.0: scipy's unique order puts NaN last -> 3.0 wins
        vals = np.array([3.0, 3.0, np.nan, np.nan, 9.0])
        codes = np.zeros(5, dtype=np.int64)
        got, _ = groupby_reduce(vals, codes, func="mode", engine=engine)
        assert float(np.asarray(got)[0]) == 3.0


@pytest.mark.parametrize(
    "func, kw",
    [("nanvar", {}), ("nanmax", {}), ("nanquantile", {"finalize_kwargs": {"q": [0.25, 0.75]}}),
     ("nanargmin", {})],
)
def test_row_blocks_match_unblocked(monkeypatch, func, kw):
    # past utils.ROW_BLOCK_BYTES the eager and fused programs run the kept
    # rows in sequential blocks (bounded device temps); rows reduce on their
    # own, so the answer must not move — tail block and nD kept dims included
    import flox_tpu
    from flox_tpu import cache, utils
    from flox_tpu.fusion import groupby_aggregate_many

    vals = RNG.normal(size=(5, 7, 96))
    vals[0, 1, ::7] = np.nan
    labels = np.arange(96) % 5
    cache.clear_all()
    want, _ = groupby_reduce(vals, labels, func=func, engine="jax", **kw)
    want_many, _ = groupby_aggregate_many(vals, labels, funcs=("mean", "var", "max"), engine="jax")
    monkeypatch.setattr(utils, "ROW_BLOCK_BYTES", 8 * 96 * 8)  # 8-row blocks of 35 rows
    cache.clear_all()
    got, _ = groupby_reduce(vals, labels, func=func, engine="jax", **kw)
    got_many, _ = groupby_aggregate_many(vals, labels, funcs=("mean", "var", "max"), engine="jax")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for name in want_many:
        np.testing.assert_array_equal(np.asarray(got_many[name]), np.asarray(want_many[name]))
    with flox_tpu.set_options(telemetry=False):
        cache.clear_all()


@pytest.mark.parametrize("method", ["map-reduce", "cohorts"])
def test_row_blocks_match_unblocked_on_the_mesh(monkeypatch, method):
    # the mesh chunk stage row-blocks each shard the same way
    from flox_tpu import cache, utils

    vals = RNG.normal(size=(40, 64))
    vals[3, ::5] = np.nan
    labels = np.arange(64) % 6
    cache.clear_all()
    want, _ = groupby_reduce(vals, labels, func="nanvar", method=method)
    monkeypatch.setattr(utils, "ROW_BLOCK_BYTES", 8 * 8 * 8)  # shards of 8 steps
    cache.clear_all()
    got, _ = groupby_reduce(vals, labels, func="nanvar", method=method)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    cache.clear_all()
