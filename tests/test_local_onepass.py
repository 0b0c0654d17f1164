"""The mesh and streaming local stage's one pass (``mapreduce._local_stats``).

Where ``kernels.fused_segment_stats`` serves the count and every chunk leg,
the shard-local stage computes them in one Pallas pass (interpret mode
here) and takes the non-NaN count from the sum kernel's NaN markers. Its
answers must be the per-leg pair's (``_local_counts`` + ``_local_chunk``)
bit for bit, on every path that runs it; elsewhere the per-leg kernels run
unchanged. Small shapes on a 4-device CPU mesh.
"""

import logging
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flox_tpu
from flox_tpu import cache, telemetry
from flox_tpu.aggregations import _initialize_aggregation
from flox_tpu.core import groupby_reduce
from flox_tpu.parallel import make_mesh
from flox_tpu.parallel import mapreduce
from flox_tpu.streaming import streaming_groupby_reduce

N, SIZE = 203, 7  # N not divisible by the 4 shards: the padding path too

PATHS = ["map-reduce", "cohorts", "blocked", "stream", "stream-mesh"]

#: (func, variant): "pallas" takes the one pass; every other variant, and
#: nanvar's var_chunk leg and count alone, keeps the per-leg kernels
ONEPASS = [("nanmean", "pallas"), ("nansum", "pallas"), ("mean", "pallas"),
           ("sum", "pallas"), ("nanmax", "pallas")]
PERLEG = [("nanmean", "int"), ("nanmean", "wide"), ("nanmean", "scatter"),
          ("nanvar", "pallas"), ("count", "pallas")]

CASES = [
    pytest.param(path, func, variant, id=f"{path}-{func}-{variant}")
    for path in PATHS
    for func, variant in ONEPASS + PERLEG
    # nanmax's max combine cannot be distributed by group ownership, so it
    # has no blocked program (it raises over the ceiling instead)
    if not (path == "blocked" and func == "nanmax")
]


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh(4)


def _inputs(path, variant):
    rng = np.random.default_rng(26)
    # every group present, some missing labels; integer-valued data so the
    # float64 reference's sums are exact in f32 too
    codes = np.concatenate([np.arange(SIZE), rng.integers(-1, SIZE, N - SIZE)])
    rng.shuffle(codes)
    x = rng.integers(-50, 50, size=(3, N)).astype(np.float32)
    if variant == "int":
        x = x.astype(np.int32)
    else:
        x[:, rng.random(N) < 0.2] = np.nan
        g0, g1 = np.flatnonzero(codes == 0), np.flatnonzero(codes == 1)
        x[0, g0[0]] = np.inf  # a lone +inf
        x[1, g1[0]], x[1, g1[-1]] = np.inf, -np.inf  # +inf and -inf: NaN
        x[2, g0[-1]] = -np.inf
    if path == "blocked":
        x = np.tile(x, (7000, 1))
    return x, codes


def _options(variant):
    opts = {"segment_sum_impl": "scatter" if variant == "scatter" else "pallas"}
    if variant == "wide":
        # below the blocked program's per-owner block of 2 groups too
        opts["pallas_num_groups_max"] = 1
    return opts


def _reference(func, x, codes):
    xd = x.astype(np.float64)
    out = np.empty(x.shape[:-1] + (SIZE,))
    with warnings.catch_warnings(), np.errstate(all="ignore"):
        warnings.simplefilter("ignore", RuntimeWarning)
        for g in range(SIZE):
            v = xd[..., codes == g]
            out[..., g] = (
                np.sum(~np.isnan(v), axis=-1) if func == "count"
                else getattr(np, func)(v, axis=-1)
            )
    return out


def _blocking_ceiling(func, x, ndev):
    """A dense_intermediate_bytes_max that sends the mesh call to the blocked
    owner-by-owner program: under the dense estimate, over the blocked one
    (and at least the option's 1 MiB floor, hence the blocked path's many
    rows)."""
    agg = _initialize_aggregation(func, None, x.dtype, None, 0, None)
    lead = int(np.prod(x.shape[:-1]))
    est = mapreduce.dense_intermediate_bytes(lead, SIZE, x.dtype, agg, ndev)
    blocked = lead * SIZE * mapreduce._est_itemsize(x.dtype) + est // ndev
    ceiling = max((blocked + est) // 2, 2**20)
    assert blocked <= ceiling < est
    return ceiling


def _run(path, func, x, codes, mesh, caplog):
    kw = {"func": func, "expected_groups": np.arange(SIZE)}
    if path == "stream":
        return streaming_groupby_reduce(x, codes, batch_len=64, **kw)[0]
    if path == "stream-mesh":
        return streaming_groupby_reduce(x, codes, batch_len=128, mesh=mesh, **kw)[0]
    if path == "blocked":
        caplog.clear()
        ceiling = _blocking_ceiling(func, x, 4)
        with flox_tpu.set_options(dense_intermediate_bytes_max=ceiling):
            with caplog.at_level(logging.DEBUG, logger="flox_tpu"):
                out = groupby_reduce(x, codes, method="map-reduce", mesh=mesh, **kw)[0]
        assert "blocked owner-by-owner" in caplog.text
        return out
    return groupby_reduce(x, codes, method=path, mesh=mesh, **kw)[0]


def _per_leg(agg, codes_sh, arr_sh, size, count_skipna, nat):
    """The local stage as it was: the count and each leg on its own."""
    return (
        mapreduce._local_counts(codes_sh, arr_sh, size, count_skipna, nat),
        mapreduce._local_chunk(agg, codes_sh, arr_sh, size, nat),
    )


def _leaves(inters):
    return [a for i in inters for a in getattr(i, "arrays", (i,))]


@pytest.mark.parametrize("path,func,variant", CASES)
def test_local_onepass_matches_per_leg(mesh4, monkeypatch, caplog, path, func, variant):
    x, codes = _inputs(path, variant)
    onepass = variant == "pallas" and func not in ("nanvar", "count")
    with flox_tpu.set_options(**_options(variant)):
        # the helper itself: the per-leg pair's values and dtypes
        agg = _initialize_aggregation(func, None, x.dtype, None, 0, None)
        count_skipna = func.startswith("nan") or func == "count"
        args = (jnp.asarray(codes), jnp.asarray(x), SIZE, count_skipna, False)
        counts, inters = mapreduce._local_stats(agg, *args)
        counts0, inters0 = _per_leg(agg, *args)
        assert counts.dtype == counts0.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts0))
        for a, b in zip(_leaves(inters), _leaves(inters0), strict=True):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # the whole path, counting which local stage its programs built
        cache.clear_all()
        with flox_tpu.set_options(telemetry=True):
            got = np.asarray(_run(path, func, x, codes, mesh4, caplog))
            ran_onepass = telemetry.METRICS.get("mesh.local_onepass")
            ran_perleg = telemetry.METRICS.get("mesh.local_perleg")
        if onepass:
            assert ran_onepass > 0 and ran_perleg == 0
        else:
            assert ran_onepass == 0 and ran_perleg > 0

        # the same path with the per-leg local stage: bit for bit
        cache.clear_all()
        monkeypatch.setattr(mapreduce, "_local_stats", _per_leg)
        want = np.asarray(_run(path, func, x, codes, mesh4, caplog))
        cache.clear_all()
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(
        got.astype(np.float64), _reference(func, x, codes),
        rtol=1e-5 if func == "nanvar" else 1e-6, atol=0, equal_nan=True,
    )


def _eqns(jaxpr):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                if isinstance(sub, ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize("method", ["map-reduce", "cohorts"])
def test_mesh_nanmean_local_stage_has_no_count_scatter(mesh4, method):
    """At 366 day-of-year groups under the Pallas policy the mesh nanmean
    counts its non-NaN elements from the sum kernel's markers: the only
    scatter-add left is the codes-only (N,) row count, none over (N, ...)."""
    from jax.sharding import PartitionSpec as P

    from flox_tpu.parallel.mesh import shard_map

    size, ndev, shard_len = 366, 4, 256
    agg = _initialize_aggregation("nanmean", None, np.float32, None, 0, None)
    with flox_tpu.set_options(segment_sum_impl="pallas"):
        program = mapreduce._build_program(
            agg, size=size, size_pad=size + (-size) % ndev, method=method,
            axis_name=("data",), shard_len=shard_len, nat=False, ndev=ndev,
        )
        fn = shard_map(
            program, mesh=mesh4, in_specs=(P(None, "data"), P("data")),
            out_specs=P(), check_vma=False,
        )
        jaxpr = jax.make_jaxpr(fn)(
            jax.ShapeDtypeStruct((8, ndev * shard_len), jnp.float32),
            jax.ShapeDtypeStruct((ndev * shard_len,), jnp.int32),
        )
    eqns = list(_eqns(jaxpr.jaxpr))
    assert any(e.primitive.name == "pallas_call" for e in eqns)
    scatters = [e for e in eqns if e.primitive.name == "scatter-add"]
    assert [e.invars[0].aval.shape for e in scatters if e.invars[0].aval.ndim > 1] == []
