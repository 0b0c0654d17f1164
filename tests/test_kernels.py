"""Unit tests for the jax engine kernels against per-group numpy oracles.

Oracle strategy mirrors the reference's (tests/test_core.py:86-113): apply
the plain numpy function to each group's masked slice.
"""

import numpy as np
import pytest
import scipy.stats

from flox_tpu import engine_numpy as engine_numpy_mod
from flox_tpu import kernels


def oracle(func, values, codes, size, **kw):
    """Per-group loop with plain numpy — the independent reference result."""
    np_func = {
        "sum": np.sum,
        "nansum": np.nansum,
        "prod": np.prod,
        "nanprod": np.nanprod,
        "max": np.max,
        "nanmax": np.nanmax,
        "min": np.min,
        "nanmin": np.nanmin,
        "mean": np.mean,
        "nanmean": np.nanmean,
        "var": np.var,
        "nanvar": np.nanvar,
        "std": np.std,
        "nanstd": np.nanstd,
        "median": np.median,
        "nanmedian": np.nanmedian,
        "all": np.all,
        "any": np.any,
        "argmax": np.argmax,
        "argmin": np.argmin,
        "nanargmax": np.nanargmax,
        "nanargmin": np.nanargmin,
    }[func]
    out = []
    for g in range(size):
        grp = values[..., codes == g]
        if grp.shape[-1] == 0:
            out.append(np.full(values.shape[:-1], np.nan))
            continue
        with np.errstate(invalid="ignore"), np.testing.suppress_warnings() as sup:
            sup.filter(RuntimeWarning)
            if func.startswith(("arg", "nanarg")):
                res = np.apply_along_axis(lambda s: np_func(s), -1, grp)
                # convert group-local index to flat index
                flat_positions = np.flatnonzero(codes == g)
                res = flat_positions[res]
            else:
                res = np_func(grp, axis=-1, **kw)
        out.append(res)
    return np.stack(out, axis=-1).astype(np.float64)


RNG = np.random.default_rng(42)


@pytest.fixture(params=["1d", "2d", "nan", "empty-group", "nan-labels"])
def case(request):
    n, size = 57, 5
    codes = RNG.integers(0, size, n).astype(np.int64)
    values = RNG.normal(size=(n,)).astype(np.float64)
    if request.param == "2d":
        values = RNG.normal(size=(3, n))
    elif request.param == "nan":
        values[RNG.random(n) < 0.3] = np.nan
    elif request.param == "empty-group":
        codes[codes == 2] = 1  # group 2 has no members
    elif request.param == "nan-labels":
        codes[RNG.random(n) < 0.2] = -1
    return values, codes, size


SIMPLE_FUNCS = [
    "sum", "nansum", "prod", "nanprod", "max", "nanmax", "min", "nanmin",
    "mean", "nanmean", "var", "nanvar", "std", "nanstd",
]


@pytest.mark.parametrize("func", SIMPLE_FUNCS)
def test_simple_reductions(case, func):
    values, codes, size = case
    got = np.asarray(kernels.generic_kernel(func, codes, values, size=size, fill_value=np.nan))
    expected = np.full(values.shape[:-1] + (size,), np.nan)
    for g in range(size):
        sel = codes == g
        if not sel.any():
            continue
        grp = values[..., sel]
        with np.errstate(invalid="ignore"), np.testing.suppress_warnings() as sup:
            sup.filter(RuntimeWarning)
            expected[..., g] = getattr(np, func)(grp, axis=-1)
    np.testing.assert_allclose(got, expected, rtol=1e-12, atol=1e-12)


def test_count(case):
    values, codes, size = case
    got = np.asarray(kernels.generic_kernel("nanlen", codes, values, size=size))
    expected = np.zeros(values.shape[:-1] + (size,))
    for g in range(size):
        grp = values[..., codes == g]
        expected[..., g] = np.sum(~np.isnan(grp), axis=-1)
    np.testing.assert_array_equal(got, expected)


@pytest.mark.parametrize("func", ["argmax", "argmin", "nanargmax", "nanargmin"])
def test_argreductions(case, func):
    values, codes, size = case
    got = np.asarray(kernels.generic_kernel(func, codes, values, size=size, fill_value=-1))
    expected = np.full(values.shape[:-1] + (size,), -1, dtype=np.int64)
    for g in range(size):
        sel = np.flatnonzero(codes == g)
        if sel.size == 0:
            continue
        grp = values[..., sel]
        with np.errstate(invalid="ignore"):
            if func.startswith("nanarg"):
                valid = ~np.all(np.isnan(grp), axis=-1)
                local = np.full(grp.shape[:-1], 0, dtype=np.int64)
                safe = np.where(np.isnan(grp), -np.inf if "max" in func else np.inf, grp)
                local = np.argmax(safe, -1) if "max" in func else np.argmin(safe, -1)
                res = np.where(valid, sel[local], -1)
            else:
                local = np.argmax(grp, -1) if "max" in func else np.argmin(grp, -1)
                res = sel[local]
        expected[..., g] = res
    np.testing.assert_array_equal(got, expected)


@pytest.mark.parametrize("func", ["first", "last", "nanfirst", "nanlast"])
def test_first_last(case, func):
    values, codes, size = case
    got = np.asarray(kernels.generic_kernel(func, codes, values, size=size, fill_value=np.nan))
    expected = np.full(values.shape[:-1] + (size,), np.nan)
    for g in range(size):
        sel = np.flatnonzero(codes == g)
        if sel.size == 0:
            continue
        grp = values[..., sel]
        if func.startswith("nan"):
            valid = ~np.isnan(grp)
            order = range(grp.shape[-1]) if "first" in func else range(grp.shape[-1] - 1, -1, -1)
            res = np.full(grp.shape[:-1], np.nan)
            done = np.zeros(grp.shape[:-1], dtype=bool)
            for i in order:
                pick = valid[..., i] & ~done
                res = np.where(pick, grp[..., i], res)
                done |= valid[..., i]
        else:
            res = grp[..., 0] if func == "first" else grp[..., -1]
        expected[..., g] = res
    np.testing.assert_allclose(got, expected, rtol=0, atol=0, equal_nan=True)


@pytest.mark.parametrize("q", [0.5, 0.9, [0.25, 0.75]])
def test_quantile(case, q):
    values, codes, size = case
    got = np.asarray(kernels.generic_kernel("nanquantile", codes, values, size=size, q=q))
    qs = np.atleast_1d(q)
    expected = np.full((len(qs),) + values.shape[:-1] + (size,), np.nan)
    for g in range(size):
        grp = values[..., codes == g]
        if grp.shape[-1] == 0 or np.all(np.isnan(grp)):
            continue
        with np.testing.suppress_warnings() as sup:
            sup.filter(RuntimeWarning)
            expected[..., g] = np.nanquantile(grp, qs, axis=-1)
    if np.ndim(q) == 0:
        expected = expected[0]
    np.testing.assert_allclose(got, expected, rtol=1e-12, atol=1e-12, equal_nan=True)


def test_median(case):
    values, codes, size = case
    got = np.asarray(kernels.generic_kernel("nanmedian", codes, values, size=size))
    expected = np.full(values.shape[:-1] + (size,), np.nan)
    for g in range(size):
        grp = values[..., codes == g]
        if grp.shape[-1] == 0 or np.all(np.isnan(grp)):
            continue
        with np.testing.suppress_warnings() as sup:
            sup.filter(RuntimeWarning)
            expected[..., g] = np.nanmedian(grp, axis=-1)
    np.testing.assert_allclose(got, expected, rtol=1e-12, atol=1e-12, equal_nan=True)


def test_mode():
    codes = np.array([0, 0, 0, 1, 1, 1, 1, 2, 0])
    values = np.array([3.0, 1.0, 3.0, 5.0, 5.0, 2.0, 2.0, 7.0, 1.0])
    got = np.asarray(kernels.generic_kernel("mode", codes, values, size=3))
    # group 0: [3,1,3,1] -> tie between 1 (x2) and 3 (x2) -> smallest = 1
    # group 1: [5,5,2,2] -> tie -> 2 ; group 2: [7] -> 7
    np.testing.assert_array_equal(got, [1.0, 2.0, 7.0])


def test_nanmode():
    codes = np.array([0, 0, 0, 1, 1])
    values = np.array([np.nan, 2.0, 2.0, np.nan, np.nan])
    got = np.asarray(kernels.generic_kernel("nanmode", codes, values, size=2, fill_value=np.nan))
    np.testing.assert_allclose(got, [2.0, np.nan], equal_nan=True)


def test_bool_all_any():
    codes = np.array([0, 0, 1, 1, 2])
    values = np.array([True, False, True, True, False])
    got_all = np.asarray(kernels.generic_kernel("all", codes, values, size=4))
    got_any = np.asarray(kernels.generic_kernel("any", codes, values, size=4))
    np.testing.assert_array_equal(got_all, [False, True, False, True])
    np.testing.assert_array_equal(got_any, [True, True, False, False])


def test_cumsum():
    codes = np.array([0, 1, 0, 1, 0])
    values = np.array([1.0, 10.0, 2.0, 20.0, 3.0])
    got = np.asarray(kernels.generic_kernel("cumsum", codes, values, size=2))
    np.testing.assert_allclose(got, [1.0, 10.0, 3.0, 30.0, 6.0])


def test_nancumsum_2d():
    codes = np.array([0, 1, 0, 1])
    values = np.array([[1.0, np.nan, 2.0, 5.0], [4.0, 1.0, np.nan, 1.0]])
    got = np.asarray(kernels.generic_kernel("nancumsum", codes, values, size=2))
    np.testing.assert_allclose(got, [[1.0, 0.0, 3.0, 5.0], [4.0, 1.0, 4.0, 2.0]])


def test_ffill_bfill():
    codes = np.array([0, 1, 0, 1, 0, 1])
    values = np.array([np.nan, 1.0, 2.0, np.nan, np.nan, np.nan])
    got_f = np.asarray(kernels.generic_kernel("ffill", codes, values, size=2))
    np.testing.assert_allclose(got_f, [np.nan, 1.0, 2.0, 1.0, 2.0, 1.0], equal_nan=True)
    got_b = np.asarray(kernels.generic_kernel("bfill", codes, values, size=2))
    np.testing.assert_allclose(got_b, [2.0, 1.0, 2.0, np.nan, np.nan, np.nan], equal_nan=True)


def test_var_chunk_triple():
    codes = np.array([0, 0, 1, 1, 1])
    values = np.array([1.0, 3.0, 2.0, 4.0, 6.0])
    ma = kernels.generic_kernel("var_chunk", codes, values, size=2)
    m2, total, cnt = (np.asarray(a) for a in ma)
    np.testing.assert_allclose(total, [4.0, 12.0])
    np.testing.assert_allclose(cnt, [2.0, 3.0])
    np.testing.assert_allclose(m2, [2.0, 8.0])  # sum (x - mean)^2


def test_nan_labels_excluded():
    codes = np.array([0, -1, 0, 1])
    values = np.array([1.0, 100.0, 2.0, 3.0])
    got = np.asarray(kernels.generic_kernel("sum", codes, values, size=2))
    np.testing.assert_allclose(got, [3.0, 3.0])


class TestMatmulPath:
    """The one-hot-GEMM segment-sum path must agree with scatter exactly
    in semantics (incl. NaN propagation and missing labels)."""

    def _both(self, func, codes, values, size, **kw):
        import flox_tpu

        with flox_tpu.set_options(segment_sum_impl="matmul"):
            a = np.asarray(kernels.generic_kernel(func, codes, values, size=size, **kw))
        with flox_tpu.set_options(segment_sum_impl="scatter"):
            b = np.asarray(kernels.generic_kernel(func, codes, values, size=size, **kw))
        return a, b

    @pytest.mark.parametrize("func", ["sum", "nansum", "mean", "nanmean", "var", "nanvar"])
    def test_agrees_with_scatter(self, func):
        rng = np.random.default_rng(3)
        codes = rng.integers(0, 5, 200)
        values = rng.normal(size=(3, 200))
        values[..., rng.random(200) < 0.2] = np.nan
        codes[rng.random(200) < 0.1] = -1
        a, b = self._both(func, codes, values, 5)
        np.testing.assert_allclose(a, b, rtol=1e-10, atol=1e-12, equal_nan=True)

    def test_nan_does_not_poison_other_groups(self):
        # non-skipna sum: NaN must hit only its own group (0*NaN hazard)
        codes = np.array([0, 0, 1, 1])
        values = np.array([1.0, np.nan, 2.0, 3.0])
        a, b = self._both("sum", codes, values, 2)
        np.testing.assert_allclose(a, [np.nan, 5.0], equal_nan=True)
        np.testing.assert_allclose(b, [np.nan, 5.0], equal_nan=True)

    def test_missing_labels_drop(self):
        codes = np.array([0, -1, 0, 1])
        values = np.array([1.0, 100.0, 2.0, 3.0])
        a, _ = self._both("sum", codes, values, 2)
        np.testing.assert_allclose(a, [3.0, 3.0])

    @pytest.mark.parametrize("func", ["sum", "nansum", "nanmean"])
    def test_wide_k_blocked(self, func):
        # K wide enough to trigger the lax.map column-blocking (incl. the
        # non-multiple-of-kb padding path) must match scatter exactly
        import flox_tpu

        rng = np.random.default_rng(7)
        n, k = 1000, 300  # kb floors to 128 at the minimum block budget
        codes = rng.integers(0, 6, n)
        values = rng.normal(size=(k, n))
        values[rng.random((k, n)) < 0.05] = np.nan
        values[0, :3] = np.inf
        with flox_tpu.set_options(matmul_block_bytes=2**20):
            a, b = self._both(func, codes, values, 6)
        np.testing.assert_allclose(a, b, rtol=1e-10, atol=1e-12, equal_nan=True)

    def test_huge_n_falls_back_to_scatter(self):
        # blocking bounds K, not N: when even a 128-lane block exceeds the
        # HBM ceiling, the path must refuse (shape-only check, no alloc)
        class Fake:
            shape = (2**24, 64)
            ndim = 2
            dtype = np.dtype("float32")

        assert kernels._use_matmul_path("sum", Fake(), 12) is False

        class FakeOk(Fake):
            shape = (2**16, 64)

        assert kernels._use_matmul_path("sum", FakeOk(), 12) is True

    def test_empty_input(self):
        # zero-length reductions must not divide by zero in the block sizing
        codes = np.zeros(0, dtype=np.int64)
        values = np.zeros((3, 0))
        a, b = self._both("sum", codes, values, 2)
        np.testing.assert_allclose(a, b)
        np.testing.assert_allclose(a, np.zeros((3, 2)))


def test_matmul_path_inf_exact():
    # inf must stay local to its group and column (0*inf hazard in the GEMM)
    import flox_tpu

    codes = np.array([0, 1, 0, 1])
    values = np.array([[np.inf, 1.0, 2.0, 3.0],
                       [1.0, -np.inf, np.inf, 4.0],
                       [1.0, 2.0, 3.0, 4.0]])
    with flox_tpu.set_options(segment_sum_impl="matmul"):
        a = np.asarray(kernels.generic_kernel("sum", codes, values, size=2))
    with flox_tpu.set_options(segment_sum_impl="scatter"):
        b = np.asarray(kernels.generic_kernel("sum", codes, values, size=2))
    expected = np.array([[np.inf, 4.0], [np.inf, -np.inf + 4.0], [4.0, 6.0]])
    np.testing.assert_array_equal(a, expected)
    np.testing.assert_array_equal(b, expected)


def test_options_invalidate_jit_cache():
    # toggling matmul_path must not serve a stale compiled bundle
    import flox_tpu
    from flox_tpu.core import groupby_reduce

    codes = np.array([0, 1] * 50)
    vals = np.arange(100.0).reshape(2, 50).repeat(2, axis=1)[:, :100].reshape(2, 100)
    with flox_tpu.set_options(segment_sum_impl="matmul"):
        a, _ = groupby_reduce(vals, codes, func="sum", engine="jax")
    with flox_tpu.set_options(segment_sum_impl="scatter"):
        b, _ = groupby_reduce(vals, codes, func="sum", engine="jax")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-12)


class TestPallasPath:
    """Pallas segment-sum (interpret mode off-TPU) vs scatter."""

    def _both(self, func, codes, values, size, **kw):
        import flox_tpu

        with flox_tpu.set_options(segment_sum_impl="pallas"):
            a = np.asarray(kernels.generic_kernel(func, codes, values, size=size, **kw))
        with flox_tpu.set_options(segment_sum_impl="scatter"):
            b = np.asarray(kernels.generic_kernel(func, codes, values, size=size, **kw))
        return a, b

    @pytest.mark.parametrize("func", ["sum", "nansum", "nanmean"])
    def test_agrees_with_scatter(self, func):
        rng = np.random.default_rng(4)
        codes = rng.integers(0, 5, 64)
        values = rng.normal(size=(2, 64)).astype(np.float32)
        values[..., rng.random(64) < 0.2] = np.nan
        codes[rng.random(64) < 0.1] = -1
        a, b = self._both(func, codes, values, 5)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6, equal_nan=True)

    def test_inf_exact(self):
        codes = np.array([0, 1, 0, 1] * 4)
        values = np.zeros((2, 16), dtype=np.float32)
        values[0, 0] = np.inf
        values[1, 1] = -np.inf
        a, b = self._both("sum", codes, values, 2)
        np.testing.assert_array_equal(a, b)


class TestPallasMinMax:
    """Pallas VPU select-reduce min/max (interpret mode off-TPU) vs scatter."""

    def _both(self, func, codes, values, size, **kw):
        import flox_tpu

        with flox_tpu.set_options(segment_minmax_impl="pallas"):
            a = np.asarray(kernels.generic_kernel(func, codes, values, size=size, **kw))
        with flox_tpu.set_options(segment_minmax_impl="scatter"):
            b = np.asarray(kernels.generic_kernel(func, codes, values, size=size, **kw))
        return a, b

    @pytest.mark.parametrize("func", ["max", "min", "nanmax", "nanmin"])
    def test_agrees_with_scatter(self, func):
        rng = np.random.default_rng(11)
        codes = rng.integers(0, 5, 77)
        values = rng.normal(size=(2, 77)).astype(np.float32)
        values[..., rng.random(77) < 0.2] = np.nan
        codes[rng.random(77) < 0.1] = -1
        codes[codes == 3] = 1  # empty group
        a, b = self._both(func, codes, values, 5, fill_value=np.nan)
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=0, equal_nan=True)

    def test_int32(self):
        rng = np.random.default_rng(12)
        codes = rng.integers(0, 4, 130)
        values = rng.integers(-1000, 1000, size=(3, 130)).astype(np.int32)
        a, b = self._both("max", codes, values, 4)
        np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("func", ["max", "nanmin"])
    def test_bfloat16(self, func):
        import jax.numpy as jnp

        rng = np.random.default_rng(15)
        codes = rng.integers(0, 4, 150)
        values = jnp.asarray(rng.normal(size=(2, 150)).astype(np.float32)).astype(
            jnp.bfloat16
        )
        if func == "nanmin":
            values = values.at[..., ::7].set(jnp.nan)
        a, b = self._both(func, codes, values, 4)
        assert a.dtype == np.asarray(values).dtype
        np.testing.assert_array_equal(a, b)

    def test_ragged_direct_vs_oracle(self):
        # non-divisible shapes through the raw kernel against a numpy loop
        from flox_tpu.pallas_kernels import segment_minmax_pallas

        rng = np.random.default_rng(13)
        n, k, size = 301, 135, 6
        values = rng.normal(size=(n, k)).astype(np.float32)
        codes = rng.integers(-1, size, n).astype(np.int32)
        got = np.asarray(
            segment_minmax_pallas(values, codes, size, "min", interpret=True)
        )
        for g in range(size):
            grp = values[codes == g]
            want = grp.min(0) if len(grp) else np.full(k, np.inf, np.float32)
            np.testing.assert_array_equal(got[g], want)

    def test_group_cap_falls_back(self):
        import flox_tpu

        rng = np.random.default_rng(14)
        codes = rng.integers(0, 5, 64)
        values = rng.normal(size=64).astype(np.float32)
        with flox_tpu.set_options(
            segment_minmax_impl="pallas", pallas_minmax_num_groups_max=3
        ):
            # over the cap: resolves to scatter, still correct
            out = np.asarray(kernels.generic_kernel("max", codes, values, size=5))
        for g in range(5):
            np.testing.assert_allclose(out[g], values[codes == g].max(), rtol=1e-6)


class TestPallasScan:
    """Pallas triangular-matmul grouped cumsum (interpret mode) vs the
    sort-based segmented path and per-group numpy loops."""

    def _oracle(self, func, values, codes):
        out = np.empty_like(values, dtype=np.float64)
        for g in np.unique(codes):
            m = codes == g
            grp = values[..., m].astype(np.float64)
            out[..., m] = np.cumsum(np.nan_to_num(grp, nan=0.0), -1) if func == "nancumsum" else np.cumsum(grp, -1)
        return out

    @pytest.mark.parametrize("func", ["cumsum", "nancumsum"])
    @pytest.mark.parametrize("shape", [(257,), (3, 300)])
    def test_vs_oracle_and_segmented(self, func, shape):
        import flox_tpu

        rng = np.random.default_rng(21)
        n = shape[-1]
        codes = rng.integers(0, 5, n)
        codes[rng.random(n) < 0.1] = -1  # missing labels scan among themselves
        values = rng.normal(size=shape).astype(np.float32)
        values[rng.random(shape) < 0.15] = np.nan
        with flox_tpu.set_options(scan_impl="pallas"):
            a = np.asarray(kernels.generic_kernel(func, codes, values, size=5))
        with flox_tpu.set_options(scan_impl="segmented"):
            b = np.asarray(kernels.generic_kernel(func, codes, values, size=5))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6, equal_nan=True)
        ok = codes >= 0
        want = self._oracle(func, values, codes)
        np.testing.assert_allclose(a[..., ok], want[..., ok], rtol=1e-5, atol=1e-5, equal_nan=True)

    def test_nan_poisons_rest_of_group_across_tiles(self):
        # non-skipna: a NaN early in a group must poison every later element
        # of that group (including across the 128-lane tile boundary), and
        # ONLY that group
        from flox_tpu.pallas_kernels import segment_cumsum_pallas

        n = 400
        codes = (np.arange(n) % 3).astype(np.int32)
        values = np.ones(n, dtype=np.float32)
        values[30] = np.nan  # group 0, first tile
        got = np.asarray(segment_cumsum_pallas(values, codes, 3, skipna=False, interpret=True))
        g0 = np.flatnonzero(codes == 0)
        before = g0[g0 < 30]
        after = g0[g0 >= 30]
        assert np.isfinite(got[before]).all()
        assert np.isnan(got[after]).all()
        others = codes != 0
        assert np.isfinite(got[others]).all()

    @pytest.mark.parametrize("func", ["cumsum", "nancumsum"])
    def test_inf_semantics(self, func):
        # r2 advisor (high): ±inf used to survive the zero-fill and poison
        # every group through inf×0=NaN in the masked matmuls. One inf must
        # stay inside its own group and follow IEEE prefix semantics —
        # including across tile boundaries through the marker carries.
        from flox_tpu.pallas_kernels import segment_cumsum_pallas

        n = 1200  # 3 tiles of 512 — markers must ride the carry rows
        codes = (np.arange(n) % 3).astype(np.int32)
        values = np.ones(n, dtype=np.float32)
        values[30] = np.inf     # group 0: +inf from here on...
        values[900] = -np.inf   # ...then +inf + -inf = NaN (tile 2)
        values[61] = -np.inf    # group 1: -inf from here on
        values[50] = np.nan     # group 2: NaN poisons (cumsum only)
        got = np.asarray(
            segment_cumsum_pallas(values, codes, 3, skipna=(func == "nancumsum"), interpret=True)
        )
        f = np.nancumsum if func == "nancumsum" else np.cumsum
        want = np.empty(n, np.float64)
        for g in range(3):
            m = codes == g
            want[m] = f(values[m].astype(np.float64))
        np.testing.assert_allclose(got, want, rtol=1e-6, equal_nan=True)

    def test_carry_overflow_does_not_poison_other_groups(self):
        # an all-finite running sum that overflows f32 in the carry must
        # report +inf for ITS group's later lanes only — not NaN everywhere
        # through inf×0 in the one-hot gather
        from flox_tpu.pallas_kernels import segment_cumsum_pallas

        n = 1100
        codes = (np.arange(n) % 2).astype(np.int32)
        values = np.ones(n, dtype=np.float32)
        values[codes == 0] = 3e38  # group 0 overflows within the first tile
        got = np.asarray(segment_cumsum_pallas(values, codes, 2, skipna=False, interpret=True))
        g1 = got[codes == 1]
        np.testing.assert_allclose(g1, np.arange(1, len(g1) + 1), rtol=1e-6)
        g0 = got[codes == 0]
        assert np.isposinf(g0[-1])  # overflowed group saturates at +inf
        assert not np.isnan(g0).any()

    def test_opposite_sign_overflow_keeps_first_inf(self):
        # +overflow, carry reset, then a would-be -overflow of the reset
        # carry: IEEE keeps +inf (a true +inf running sum absorbs finite
        # negatives) — must not turn into NaN via both markers
        from flox_tpu.pallas_kernels import segment_cumsum_pallas

        n = 1100
        vals = np.full(n, 3e38, np.float32)
        vals[400:] = -3e38
        codes = np.zeros(n, dtype=np.int32)
        got = np.asarray(segment_cumsum_pallas(vals, codes, 1, skipna=False, interpret=True))
        assert np.isposinf(got[1])  # overflows at the second element
        assert np.isposinf(got[-1])
        assert not np.isnan(got).any()

    def test_overflow_then_opposite_inf_value_is_nan(self):
        # in-tile arithmetic +overflow followed by a -inf VALUE: the true
        # sequential sum is +inf + (-inf) = NaN from that element on
        from flox_tpu.pallas_kernels import segment_cumsum_pallas

        vals = np.full(200, 0.0, np.float32)
        vals[0] = 3e38
        vals[1] = 3e38
        vals[5] = -np.inf
        codes = np.zeros(200, dtype=np.int32)
        got = np.asarray(segment_cumsum_pallas(vals, codes, 1, skipna=False, interpret=True))
        assert np.isposinf(got[1]) and np.isposinf(got[4])
        assert np.isnan(got[5:]).all()
        # ...and the reverse order stays -inf (a -inf running sum cannot
        # re-overflow positive)
        vals2 = np.full(200, 3e38, np.float32)
        vals2[0] = -np.inf
        got2 = np.asarray(segment_cumsum_pallas(vals2, codes, 1, skipna=False, interpret=True))
        assert np.isneginf(got2).all()

    def test_all_finite_tile_after_inf_tile(self):
        # the carried-marker-only branch (no local nonfinite in the tile)
        from flox_tpu.pallas_kernels import segment_cumsum_pallas

        n = 1100
        codes = np.zeros(n, dtype=np.int32)
        values = np.ones(n, dtype=np.float32)
        values[3] = np.inf  # tile 0; tiles 1-2 are all finite
        got = np.asarray(segment_cumsum_pallas(values, codes, 1, skipna=False, interpret=True))
        assert np.isfinite(got[:3]).all()
        assert np.isposinf(got[3:]).all()

    def test_bf16_accumulates_f32(self):
        import jax.numpy as jnp

        from flox_tpu.pallas_kernels import segment_cumsum_pallas

        n = 2000
        vals = jnp.ones(n, jnp.bfloat16)
        codes = np.zeros(n, dtype=np.int32)
        got = np.asarray(segment_cumsum_pallas(vals, codes, 1, skipna=False, interpret=True).astype(jnp.float32))
        # a bf16 running sum would saturate at 256; f32 accumulation keeps
        # counting (each element individually rounds to its bf16 neighbour)
        assert got[-1] > 1900

    def test_group_cap_falls_back(self):
        import flox_tpu

        rng = np.random.default_rng(22)
        codes = rng.integers(0, 5, 64)
        values = rng.normal(size=64).astype(np.float32)
        with flox_tpu.set_options(scan_impl="pallas", pallas_scan_num_groups_max=3):
            out = np.asarray(kernels.generic_kernel("cumsum", codes, values, size=5))
        np.testing.assert_allclose(out, self._oracle("cumsum", values, codes), rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("skipna", [False, True])
    def test_nonfinite_state_is_sticky_under_adversarial_magnitudes(self, skipna):
        # mixed-sign values within a tile-width factor of f32 max: the MXU
        # (or interpret-mode) tree reduction may overflow to ±inf or form
        # NaN from opposite-sign inf partials in ANY order. Whatever event
        # fires, the group-state model requires it to be sticky — a lane
        # that reports nonfinite must never be followed by a finite lane of
        # the same group (ADVICE r3: a tree-reduction NaN with no inf lane
        # previously slipped the _clean branch and silently reverted).
        from flox_tpu.pallas_kernels import segment_cumsum_pallas

        rng = np.random.default_rng(99)
        n = 1600
        codes = (np.arange(n) % 3).astype(np.int32)
        values = (rng.choice([-1.0, 1.0], n) * rng.uniform(1e38, 3e38, n)).astype(
            np.float32
        )
        got = np.asarray(
            segment_cumsum_pallas(values, codes, 3, skipna=skipna, interpret=True)
        )
        for g in range(3):
            lane_ok = np.isfinite(got[codes == g])
            first_bad = np.argmax(~lane_ok) if (~lane_ok).any() else len(lane_ok)
            assert lane_ok[:first_bad].all()
            assert not lane_ok[first_bad:].any()


def test_pallas_kahan_accuracy():
    # compensated f32 accumulation lands within one output-ulp of the f64
    # oracle; plain accumulation drifts by multiple ulps
    from flox_tpu.pallas_kernels import segment_sum_pallas

    rng = np.random.default_rng(0)
    n = 100_000
    data = rng.normal(1e4, 1, size=(n, 1)).astype(np.float32)
    codes = np.zeros(n, dtype=np.int32)
    oracle = data.astype(np.float64).sum()
    plain = float(np.asarray(segment_sum_pallas(data, codes, 1, interpret=True, accum="plain"))[0, 0])
    kahan = float(np.asarray(segment_sum_pallas(data, codes, 1, interpret=True, accum="kahan"))[0, 0])
    ulp = np.spacing(np.float32(oracle)).astype(np.float64)
    assert abs(kahan - oracle) <= ulp
    assert abs(kahan - oracle) <= abs(plain - oracle)


class TestPallasDoubleDouble:
    """The dd (2×f32) accumulation mode: the strict-parity answer to the
    'bit-exact float64 means' north star on hardware without f64."""

    def test_dd_is_correctly_rounded_f64(self):
        # dd must land on the f32-rounding of the exact f64 sum — not just
        # within an ulp — on a workload where plain f32 visibly drifts
        from flox_tpu.pallas_kernels import segment_sum_pallas

        rng = np.random.default_rng(1)
        n = 200_000
        data = rng.normal(1e4, 1, size=(n, 1)).astype(np.float32)
        codes = (np.arange(n) % 3).astype(np.int32)
        got = np.asarray(segment_sum_pallas(data, codes, 3, interpret=True, accum="dd"))
        for g in range(3):
            oracle = data[codes == g].astype(np.float64).sum()
            assert got[g, 0] == np.float32(oracle), (g, got[g, 0], oracle)

    def test_dd_cancellation(self):
        # catastrophic cancellation across tiles: pairs (x, -x) plus a tiny
        # residual — the lo word must carry the residual that plain/Kahan
        # f32 sums round away when the running sum is large
        from flox_tpu.pallas_kernels import segment_sum_pallas

        n = 4096
        data = np.zeros((n, 1), np.float32)
        data[: n // 2, 0] = 3e7
        data[n // 2 :, 0] = -3e7
        data[0, 0] += 1.0  # exact in f32 at 3e7 scale
        codes = np.zeros(n, dtype=np.int32)
        oracle = data.astype(np.float64).sum()  # == 1.0
        got = float(np.asarray(segment_sum_pallas(data, codes, 1, interpret=True, accum="dd"))[0, 0])
        assert got == np.float32(oracle), (got, oracle)

    def test_unknown_accum_rejected(self):
        # a typo like "khan" must raise, not silently select plain
        # accumulation at lower-than-requested accuracy (ADVICE r3)
        from flox_tpu.pallas_kernels import segment_sum_pallas

        data = np.ones((8, 1), np.float32)
        codes = np.zeros(8, np.int32)
        with pytest.raises(ValueError, match="accum"):
            segment_sum_pallas(data, codes, 1, interpret=True, accum="khan")

    def test_dd_matches_options_knob(self):
        import flox_tpu
        from flox_tpu.pallas_kernels import segment_sum_pallas

        rng = np.random.default_rng(2)
        data = rng.normal(size=(1000, 2)).astype(np.float32)
        codes = (np.arange(1000) % 4).astype(np.int32)
        with flox_tpu.set_options(pallas_accum="dd"):
            via_opt = np.asarray(segment_sum_pallas(data, codes, 4, interpret=True))
        explicit = np.asarray(segment_sum_pallas(data, codes, 4, interpret=True, accum="dd"))
        np.testing.assert_array_equal(via_opt, explicit)

    def test_dd_nonfinite_semantics_preserved(self):
        # the marker machinery is orthogonal to the accumulation discipline
        from flox_tpu.pallas_kernels import segment_sum_pallas

        data = np.ones((600, 1), np.float32)
        data[10, 0] = np.inf
        data[20, 0] = np.nan
        codes = (np.arange(600) % 3).astype(np.int32)
        got = np.asarray(segment_sum_pallas(data, codes, 3, interpret=True, accum="dd"))
        assert np.isposinf(got[1, 0])  # 10 % 3 == 1
        assert np.isnan(got[2, 0])  # 20 % 3 == 2
        assert np.isfinite(got[0, 0])

    def test_dd_large_values_still_split_exactly(self):
        # 2e34 is below the split-overflow bound (f32max/4097 ≈ 8.3e34), so
        # the Dekker split still applies and the sum is exact
        from flox_tpu.pallas_kernels import segment_sum_pallas

        data = np.full((256, 1), 2e34, np.float32)
        codes = np.zeros(256, dtype=np.int32)
        oracle = data.astype(np.float64).sum()
        got = float(np.asarray(segment_sum_pallas(data, codes, 1, interpret=True, accum="dd"))[0, 0])
        assert got == np.float32(oracle)

    def test_dd_huge_values_skip_split(self):
        # above the bound the guard keeps values whole: no overflow garbage,
        # f32-grade accuracy (the documented reordered-summation boundary)
        from flox_tpu.pallas_kernels import segment_sum_pallas

        data = np.full((256, 1), 1e35, np.float32)
        codes = np.zeros(256, dtype=np.int32)
        oracle = data.astype(np.float64).sum()
        got = float(np.asarray(segment_sum_pallas(data, codes, 1, interpret=True, accum="dd"))[0, 0])
        assert np.isfinite(got)
        np.testing.assert_allclose(got, oracle, rtol=1e-5)


@pytest.mark.parametrize(
    "method",
    ["linear", "hazen", "weibull", "interpolated_inverted_cdf",
     "median_unbiased", "normal_unbiased", "lower", "higher", "midpoint"],
)
def test_quantile_methods_match_numpy(method):
    # the jax engine's (alpha, beta) families must match np.quantile exactly
    rng = np.random.default_rng(5)
    codes = rng.integers(0, 4, 50)
    values = rng.normal(size=50)
    a = np.asarray(kernels.generic_kernel("quantile", codes, values, size=4, q=0.3, method=method))
    expected = np.stack(
        [np.quantile(values[codes == g], 0.3, method=method) for g in range(4)]
    )
    np.testing.assert_allclose(a, expected, rtol=1e-12, atol=1e-12)


def test_quantile_nearest_half_to_even():
    # np.quantile 'nearest' rounds the virtual index half-to-even
    values = np.array([0.0, 1.0, 2.0, 3.0])
    codes = np.zeros(4, dtype=np.int64)
    got = float(np.asarray(
        kernels.generic_kernel("quantile", codes, values, size=1, q=0.5, method="nearest")
    )[0])
    assert got == np.quantile(values, 0.5, method="nearest")


def test_nan_fill_promotes_int_data():
    # NaN fill on integer input must produce NaN, not a truncated 0
    codes = np.array([0, 0, 0])
    values = np.array([5, 7, 9], dtype=np.int64)
    for func in ["first", "max", "mode"]:
        a = np.asarray(kernels.generic_kernel(func, codes, values, size=2, fill_value=np.nan))
        b = np.asarray(engine_numpy_mod.generic_kernel(func, codes, values, size=2, fill_value=np.nan))
        assert np.isnan(a[1]) and np.isnan(b[1]), func


def test_complex_nan_fill_keeps_imaginary():
    from flox_tpu import engine_numpy

    vals = np.array([1 + 2j, 3 - 1j, 2 + 2j])
    codes = np.array([0, 0, 0])
    b = np.asarray(engine_numpy.generic_kernel("sum", codes, vals, size=2, fill_value=np.nan))
    assert b.dtype.kind == "c" and b[0] == 6 + 3j and np.isnan(b[1].real)


@pytest.mark.parametrize("policy", ["pallas", "auto"])
def test_pallas_probe_failure_raises_on_tpu(monkeypatch, policy):
    # a kernel the chip's compiler refuses must fail the reduction, never
    # hand it quietly to the XLA paths (a broken kernel would read as slow)
    import flox_tpu
    from flox_tpu import kernels as K
    from flox_tpu import pallas_kernels

    monkeypatch.setattr(K, "_PALLAS_PROBE_RESULT", [])
    def boom(*a, **k):
        raise RuntimeError("lowering failed")
    monkeypatch.setattr(pallas_kernels, "segment_sum_pallas", boom)
    monkeypatch.setattr("jax.default_backend", lambda: "tpu")
    import jax.numpy as jnp

    with flox_tpu.set_options(segment_sum_impl=policy):
        with pytest.raises(RuntimeError, match="lowering failed"):
            K._segment_sum_impl(jnp.zeros((64, 4), jnp.float32), 12)
    assert K._PALLAS_PROBE_RESULT == []


def test_pallas_probe_wrong_result_raises_on_tpu(monkeypatch):
    import flox_tpu
    from flox_tpu import kernels as K
    from flox_tpu import pallas_kernels

    monkeypatch.setattr(K, "_PALLAS_SCAN_PROBE_RESULT", [])
    monkeypatch.setattr(
        pallas_kernels, "segment_cumsum_pallas", lambda d, *a, **k: d * 0
    )
    monkeypatch.setattr("jax.default_backend", lambda: "tpu")
    import jax.numpy as jnp

    with flox_tpu.set_options(scan_impl="auto"):
        with pytest.raises(RuntimeError, match="wrong result"):
            K._scan_impl_choice(jnp.zeros((64, 4), jnp.float32), 12)


def test_quantile_bf16_large_group():
    # index arithmetic must not run in bf16 (cannot represent odd counts >256)
    import jax.numpy as jnp

    n = 301
    values = jnp.arange(n, dtype=jnp.bfloat16)
    codes = np.zeros(n, dtype=np.int64)
    got = kernels.generic_kernel("quantile", codes, values, size=1, q=0.9, method="lower")
    expected = np.quantile(np.arange(n, dtype=np.float64), 0.9, method="lower")
    assert float(np.asarray(got.astype(jnp.float32))[0]) == expected


class TestBf16Accumulation:
    """bf16/f16 mantissas cannot count past 256; every additive path must
    accumulate in f32 (kernels._acc_dtype) while presenting the input dtype.
    Regression for the round-1 advisor finding (nanmean of 2000 bf16 values
    returned the saturated partial instead of the mean)."""

    N = 2000  # far beyond bf16's exact-integer range

    def _data(self, dtype):
        import jax.numpy as jnp

        x = jnp.linspace(0.0, 1.0, self.N).astype(dtype)
        codes = np.zeros(self.N, dtype=np.int64)
        return x, codes

    @pytest.mark.parametrize("dtype_name", ["bfloat16", "float16"])
    @pytest.mark.parametrize(
        "func,expect,tol",
        [("nanmean", 0.5, 0.01), ("nansum", 1000.0, 10.0),
         ("nanvar", 1 / 12, 0.005), ("nanstd", (1 / 12) ** 0.5, 0.01)],
    )
    def test_eager(self, dtype_name, func, expect, tol):
        import jax.numpy as jnp

        from flox_tpu import groupby_reduce

        x, codes = self._data(jnp.dtype(dtype_name))
        out, _ = groupby_reduce(x, codes, func=func)
        assert str(out.dtype) == dtype_name  # result dtype contract kept
        assert abs(float(np.asarray(out.astype(jnp.float32))[0]) - expect) < tol

    @pytest.mark.parametrize("impl", ["scatter", "matmul", "pallas"])
    def test_segment_sum_impls(self, impl):
        import jax.numpy as jnp

        import flox_tpu

        x, codes = self._data(jnp.bfloat16)
        with flox_tpu.set_options(segment_sum_impl=impl):
            out = kernels.generic_kernel("nansum", codes, x, size=1)
        assert abs(float(np.asarray(out.astype(jnp.float32))[0]) - 1000.0) < 10.0

    def test_pallas_returns_f32_accumulator(self):
        import jax.numpy as jnp

        from flox_tpu.pallas_kernels import segment_sum_pallas

        x, codes = self._data(jnp.bfloat16)
        out = segment_sum_pallas(x[:, None] * jnp.ones((1, 128), jnp.bfloat16),
                                 codes, 1, interpret=True)
        assert out.dtype == jnp.float32
        assert abs(float(out[0, 0]) - 1000.0) < 1.0

    @pytest.mark.parametrize("method", ["map-reduce", "cohorts"])
    @pytest.mark.parametrize("func,expect,tol",
                             [("nanmean", 0.5, 0.01), ("nanvar", 1 / 12, 0.005)])
    def test_mesh_intermediates_travel_f32(self, method, func, expect, tol):
        import jax.numpy as jnp

        from flox_tpu import groupby_reduce
        from flox_tpu.parallel import make_mesh

        x, codes = self._data(jnp.bfloat16)
        out, _ = groupby_reduce(x, codes, func=func, method=method, mesh=make_mesh(8))
        assert str(out.dtype) == "bfloat16"
        assert abs(float(np.asarray(out.astype(jnp.float32))[0]) - expect) < tol

    def test_cumsum_running_sum(self):
        import jax.numpy as jnp

        from flox_tpu import groupby_scan

        x, codes = self._data(jnp.bfloat16)
        out = groupby_scan(x, codes, func="nancumsum")
        assert str(out.dtype) == "bfloat16"
        assert abs(float(np.asarray(out.astype(jnp.float32))[-1]) - 1000.0) < 10.0

    def test_int_nan_fill_promotion_survives(self):
        # the cast-back must not undo the NaN-fill promotion for int data
        vals = np.array([1, 2, 3], dtype=np.int32)
        codes = np.array([0, 0, 0])
        out = np.asarray(kernels.generic_kernel("nansum", codes, vals, size=2, fill_value=np.nan))
        assert out.dtype.kind == "f" and out[0] == 6 and np.isnan(out[1])


class TestFusedNanmean:
    """Single-pass nanmean on the marker paths: counts come from
    rowcount(codes) - nan_c so the data streams HBM once."""

    def _case(self):
        # float32: the pallas path only lowers f32/bf16, and the whole point
        # is exercising the FUSED kernels, not a silent scatter fallback
        rng = np.random.default_rng(0)
        n, k, size = 4000, 16, 12
        data = rng.normal(size=(k, n)).astype(np.float32)
        data[:, ::7] = np.nan
        data[0, 5] = np.inf
        data[1, 6] = -np.inf
        data[2, 10] = np.inf
        data[2, 11] = -np.inf
        codes = rng.integers(0, size, n)
        import warnings

        out = np.empty((k, size))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for g in range(size):
                out[:, g] = np.nanmean(data[:, codes == g].astype(np.float64), axis=1)
        return data, codes, size, out

    @pytest.mark.parametrize("impl", ["scatter", "matmul", "pallas"])
    def test_vs_oracle_with_nonfinite(self, impl):
        import flox_tpu
        from flox_tpu.kernels import _segment_sum_impl
        import jax.numpy as jnp

        data, codes, size, expected = self._case()
        with flox_tpu.set_options(segment_sum_impl=impl):
            # guard against vacuous fallback: the policy must resolve to the
            # impl under test for this f32 workload
            assert _segment_sum_impl(jnp.asarray(data).T, size) == impl or impl == "scatter"
            got = np.asarray(kernels.generic_kernel("nanmean", codes, data, size=size))
        np.testing.assert_allclose(got, expected, rtol=2e-6, atol=2e-6, equal_nan=True)

    @pytest.mark.parametrize("impl", ["matmul", "pallas"])
    def test_impls_match_scatter_exactly_for_counts(self, impl):
        # empty groups and all-NaN groups must behave identically to scatter
        import flox_tpu

        # >= 8 rows so the pallas size guard does not silently fall back
        vals = np.tile(np.array([1.0, np.nan, np.nan, 4.0], dtype=np.float32), 4)
        codes = np.tile(np.array([0, 1, 1, 0]), 4)
        with flox_tpu.set_options(segment_sum_impl="scatter"):
            ref = np.asarray(kernels.generic_kernel("nanmean", codes, vals, size=3))
        with flox_tpu.set_options(segment_sum_impl=impl):
            got = np.asarray(kernels.generic_kernel("nanmean", codes, vals, size=3))
        np.testing.assert_allclose(got, ref, equal_nan=True)
        assert got[0] == 2.5 and np.isnan(got[1]) and np.isnan(got[2])

    def test_skipna_reapply_keeps_inf_rules(self):
        from flox_tpu.utils import reapply_nonfinite
        import jax.numpy as jnp

        sums = jnp.array([1.0, 2.0, 3.0, 4.0])
        nan_c = jnp.array([1.0, 0.0, 0.0, 1.0])
        pos_c = jnp.array([0.0, 1.0, 1.0, 0.0])
        neg_c = jnp.array([0.0, 0.0, 1.0, 0.0])
        out = np.asarray(reapply_nonfinite(sums, nan_c, pos_c, neg_c, skipna=True))
        # NaN markers ignored; +inf -> inf; ±inf -> NaN
        assert out[0] == 1.0 and np.isposinf(out[1]) and np.isnan(out[2]) and out[3] == 4.0


class TestFusedVariance:
    """The variance family shares the fused marker-count sum (one data pass
    for total+counts; the dev² pass follows)."""

    @pytest.mark.parametrize("impl", ["scatter", "matmul", "pallas"])
    @pytest.mark.parametrize("func", ["nanvar", "nanstd"])
    def test_vs_oracle(self, impl, func):
        import warnings

        import flox_tpu

        rng = np.random.default_rng(1)
        data = rng.normal(size=(16, 4000)).astype(np.float32)
        data[:, ::7] = np.nan
        codes = rng.integers(0, 12, 4000)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            base = np.stack(
                [np.nanvar(data[:, codes == g].astype(np.float64), axis=1) for g in range(12)], -1
            )
        expected = np.sqrt(base) if func == "nanstd" else base
        with flox_tpu.set_options(segment_sum_impl=impl):
            got = np.asarray(kernels.generic_kernel(func, codes, data, size=12))
        np.testing.assert_allclose(got, expected, rtol=2e-4, atol=1e-6, equal_nan=True)

    @pytest.mark.parametrize("impl", ["matmul", "pallas"])
    def test_var_chunk_triple_matches_scatter(self, impl):
        import flox_tpu

        rng = np.random.default_rng(2)
        data = rng.normal(size=(4, 512)).astype(np.float32)
        data[:, ::5] = np.nan
        codes = rng.integers(0, 6, 512)
        with flox_tpu.set_options(segment_sum_impl="scatter"):
            ref = kernels.generic_kernel("var_chunk", codes, data, size=6, skipna=True)
        with flox_tpu.set_options(segment_sum_impl=impl):
            got = kernels.generic_kernel("var_chunk", codes, data, size=6, skipna=True)
        for a, b in zip(ref.arrays, got.arrays):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5, atol=1e-6)


def test_var_int_dtype_request_keeps_nan_mask():
    # review regression: the NaN mask must come from the PRE-cast data — an
    # int dtype request would destroy NaNs before the mask sees them
    out = np.asarray(
        kernels.generic_kernel(
            "nanvar", np.array([0, 0, 0]), np.array([1.0, np.nan, 3.0]), size=1, dtype=np.int32
        )
    )
    assert abs(out[0] - 1.0) < 1e-12
    ch = kernels.generic_kernel(
        "var_chunk", np.array([0, 0, 0]), np.array([1.0, np.nan, 3.0]),
        size=1, dtype=np.int32, skipna=True,
    )
    assert float(np.asarray(ch.arrays[2])[0]) == 2.0


class TestRadixSelectQuantile:
    """quantile_impl="select": sort-free MSB radix bisection must be
    BIT-IDENTICAL to the two-key-sort path (both produce exact order
    statistics, then share the interpolation code)."""

    METHODS = ("linear", "lower", "higher", "nearest", "midpoint",
               "hazen", "weibull", "interpolated_inverted_cdf",
               "median_unbiased", "normal_unbiased")

    def _both(self, func, codes, data, size, **kw):
        import flox_tpu

        a = np.asarray(kernels.generic_kernel(func, codes, data, size=size, **kw))
        with flox_tpu.set_options(quantile_impl="select"):
            b = np.asarray(kernels.generic_kernel(func, codes, data, size=size, **kw))
        return a, b

    @pytest.mark.parametrize("method", METHODS)
    @pytest.mark.parametrize("func", ["quantile", "nanquantile"])
    def test_all_methods_bit_exact(self, func, method):
        rng = np.random.default_rng(11)
        n = 3000
        codes = rng.integers(0, 6, n)
        data = np.round(rng.normal(size=n), 2)  # heavy duplicates
        # NaNs confined to groups 0-1: propagate mode ("quantile") must
        # still select REAL values in groups 2-5 — NaN everywhere would
        # make the skipna=False leg vacuously pass on all-NaN outputs
        nan_rows = (rng.random(n) < 0.4) & (codes <= 1)
        data[nan_rows] = np.nan
        data[3], data[9] = np.inf, -np.inf
        a, b = self._both(func, codes, data, 6, q=0.7, method=method)
        np.testing.assert_array_equal(a, b)
        # groups 2-5 hold no NaN values, so even propagate mode must have
        # selected real values there (the comparison is not all-NaN-vs-all-NaN)
        assert not np.isnan(a[2:]).any()

    def test_vector_q_2d_f32(self):
        rng = np.random.default_rng(12)
        codes = rng.integers(0, 5, 700)
        data = rng.normal(size=(3, 700)).astype(np.float32)
        data[:, rng.random(700) < 0.2] = np.nan
        a, b = self._both("nanquantile", codes, data, 5, q=[0.0, 0.25, 0.9, 1.0])
        np.testing.assert_array_equal(a, b)

    def test_empty_and_allnan_groups(self):
        codes = np.array([0, 0, 2, 2, 3])
        data = np.array([1.0, 2.0, np.nan, np.nan, 5.0])
        a, b = self._both("nanquantile", codes, data, 5, q=0.5)
        np.testing.assert_array_equal(a, b)
        assert np.isnan(b[[1, 2, 4]]).all()  # empty g1/g4, all-NaN g2
        np.testing.assert_allclose(b[[0, 3]], [1.5, 5.0])

    def test_bf16_sixteen_bit_radix(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(13)
        codes = rng.integers(0, 4, 400)
        data = jnp.asarray(rng.normal(size=400), jnp.bfloat16)
        a, b = self._both("nanquantile", codes, data, 4, q=0.5)
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        )

    def test_median_and_missing_labels(self):
        rng = np.random.default_rng(14)
        codes = rng.integers(-1, 4, 900)  # -1 = missing, must drop out
        data = rng.normal(size=900)
        a, b = self._both("nanmedian", codes, data, 4)
        np.testing.assert_array_equal(a, b)

    def test_oracle_linear(self):
        # independent anchor: select matches np.nanquantile directly
        import flox_tpu

        rng = np.random.default_rng(15)
        codes = rng.integers(0, 3, 500)
        data = rng.normal(size=500)
        with flox_tpu.set_options(quantile_impl="select"):
            got = np.asarray(
                kernels.generic_kernel("nanquantile", codes, data, size=3, q=0.3)
            )
        want = np.array([np.nanquantile(data[codes == g], 0.3) for g in range(3)])
        np.testing.assert_allclose(got, want, rtol=1e-12)

    @pytest.mark.parametrize("dt", [np.int32, np.int64, np.int8, np.uint16])
    def test_integer_dtype_request_bit_exact(self, dt):
        # an explicit integer dtype skips the float cast: the monotonic key
        # must order two's-complement negatives correctly (review finding)
        rng = np.random.default_rng(21)
        codes = rng.integers(0, 4, 600)
        lo = -120 if np.issubdtype(dt, np.signedinteger) else 0
        data = rng.integers(lo, 120, 600).astype(dt)
        for method in ("lower", "linear"):
            a, b = self._both(
                "quantile", codes, data, 4, q=0.4, method=method, dtype=dt
            )
            np.testing.assert_array_equal(a, b)


def test_pallas_probe_runs_outside_the_callers_trace(monkeypatch):
    # the policy is consulted while jit (or shard_map) traces; the probe
    # must still execute on concrete arrays, not trace into the caller
    import jax
    import jax.numpy as jnp

    from flox_tpu import kernels as K
    from flox_tpu import pallas_kernels

    monkeypatch.setattr(K, "_PALLAS_SCAN_PROBE_RESULT", [])
    monkeypatch.setattr(
        pallas_kernels, "segment_cumsum_pallas", lambda d, *a, **k: jnp.cumsum(d, axis=0)
    )
    monkeypatch.setattr("jax.default_backend", lambda: "tpu")
    picked = []
    jax.jit(lambda x: picked.append(K._scan_impl_choice(x, 12)) or x)(jnp.zeros((64, 4), jnp.float32))
    assert picked == ["pallas"] and K._PALLAS_SCAN_PROBE_RESULT == [True]
