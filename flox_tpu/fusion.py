"""Single-pass multi-statistic fusion: ``groupby_aggregate_many`` (L4).

A climatology asking for ``{mean, std, min, max}`` over the same codes used
to stage and read the same bytes once PER STATISTIC and compile one program
each. flox's own ``Aggregation`` blueprint is explicitly multi-output (mean
is sum+count in one chunk pass — reference aggregations.py:161); this
module generalizes that to an arbitrary statistic set:

* The **fusion planner** (``aggregations.plan_fused``) merges the requested
  blueprints into one deduplicated multi-output chunk plan — sum/count feed
  mean AND var through the Chan triple's leaves, min/max ride free next to
  them, presence counts collapse to one leg.
* The **eager path** traces chunk legs + every per-statistic finalize into
  ONE jitted program (cached in :data:`_FUSED_PROGRAM_CACHE`); on the
  Pallas policy the legs collapse further into the multi-statistic
  megakernel (``pallas_kernels.segment_multistat_pallas``) — one HBM pass,
  all accumulators resident in VMEM.
* The **mesh path** runs the fused plan as one SPMD program under one
  ``_PROGRAM_CACHE`` key: one psum-combined collective serves all N
  statistics (``parallel.mapreduce`` consumes the plan through the same
  ``_local_chunk`` / ``_combine_intermediates`` contract as any agg).
* The **streaming path** (``streaming.streaming_groupby_aggregate_many``)
  folds the fused intermediates through the carry — an ERA5-style
  mean+std+extremes job is one streaming pass instead of four, with
  checkpoint/resume and OOM slab-splitting working on the fused carry.
* **Dispatch integration**: the ``"fused"`` autotune family arbitrates
  fused-vs-sequential from measured GB/s (bench.py's ``fused_sweep_gbps``
  seeds it), and the cost ledger bills the staged bytes exactly once under
  the fused program key.
"""

from __future__ import annotations

import logging
from typing import Any

import numpy as np

from . import cache, factorize as fct, telemetry, utils
from .aggregations import (
    FUSABLE_FUNCS,
    FusedAggregation,
    fused_chunk_stats,
    plan_fused,
)
from .options import OPTIONS

logger = logging.getLogger("flox_tpu.fusion")

__all__ = ["groupby_aggregate_many", "FUSABLE_FUNCS"]

#: compiled fused eager programs, keyed on the fused plan's semantic
#: identity (per-statistic fills/dtypes included) + size +
#: trace_fingerprint — the multi-output analogue of core._jitted_bundle.
#: LRU-bounded and registered in cache.clear_all (floxlint FLX08 pattern).
_FUSED_PROGRAM_CACHE: cache.LRUCache = cache.LRUCache(maxsize=256)


def fused_program_label(funcs) -> str:
    """The cost-ledger / serve program label of a fused statistic set."""
    return "fused[" + "+".join(funcs) + "]"


def store_program_label(kind: str, funcs) -> str:
    """The cost-ledger program label of a durable-store operation
    (``store.append[fused[sum+count]]``): the op kind wrapping the fused
    statistic set the store carries, so per-store ledger rows join the
    same program axis as inline fused dispatches."""
    return f"store.{kind}[{fused_program_label(funcs)}]"


def _fused_key(fused: FusedAggregation, size: int) -> tuple:
    from .options import trace_fingerprint
    from .parallel.mapreduce import _agg_cache_key

    return (_agg_cache_key(fused), size, trace_fingerprint())


def finalize_many(fused: FusedAggregation, results, out_shape=None) -> dict:
    """Per-statistic final dtype casts (+ reshape) -> ``{func: array}``,
    shared by the eager, mesh, and streaming drivers."""
    from .core import _astype_final

    out = {}
    for f, agg, r in zip(fused.funcs, fused.aggs, results):
        r = _astype_final(r, agg, None)
        if out_shape is not None and tuple(r.shape) != tuple(out_shape):
            r = r.reshape(out_shape)
        out[f] = r
    return out


def _sequential_fallback(
    array, bys, funcs, *, per_func_kw, common_kw
) -> tuple:
    """N independent ``groupby_reduce`` passes — the measured-loser branch
    of the fused-vs-sequential autotune family (and the reference
    behavior the fused path is benchmarked against)."""
    from .core import groupby_reduce

    results = {}
    groups: tuple = ()
    for f in funcs:
        r, *groups = groupby_reduce(
            array, *bys, func=f, **per_func_kw(f), **common_kw
        )
        results[f] = r
    return (results, *groups)


def groupby_aggregate_many(
    array: Any,
    *by: Any,
    funcs: "tuple | list" = ("sum", "count", "min", "max", "var"),
    expected_groups: Any = None,
    sort: bool = True,
    isbin: Any = False,
    axis: Any = None,
    fill_value: Any = None,
    dtype: Any = None,
    min_count: int | None = None,
    engine: str | None = None,
    finalize_kwargs: dict | None = None,
    method: str | None = None,
    mesh: Any = None,
    axis_name: str = "data",
) -> tuple:
    """N grouped statistics in ONE pass over the data.

    Returns ``(results, *groups)`` with ``results`` a dict mapping each
    requested func name to its array — each entry bit-identical to the
    corresponding sequential ``groupby_reduce(..., func=f)`` call on the
    same runtime, but the data is staged and read once for the whole set
    and exactly one program compiles per runtime.

    ``funcs``: names from :data:`FUSABLE_FUNCS` (the additive + extrema +
    variance families; argreductions and order statistics keep their
    sequential paths). ``fill_value`` / ``dtype`` / ``finalize_kwargs``
    accept either one value for all statistics or a per-func dict, e.g.
    ``finalize_kwargs={"var": {"ddof": 1}}``. ``method``/``mesh`` run the
    fused plan as one SPMD program (``method='map-reduce'``); for
    out-of-core data see ``streaming_groupby_aggregate_many``.

    Examples
    --------
    >>> import numpy as np
    >>> from flox_tpu import groupby_aggregate_many
    >>> values = np.array([1.0, 2.0, 4.0, 8.0])
    >>> labels = np.array([0, 0, 1, 1])
    >>> out, groups = groupby_aggregate_many(
    ...     values, labels, funcs=("sum", "max"), engine="numpy")
    >>> out["sum"]
    array([ 3., 12.])
    >>> out["max"]
    array([2., 8.])
    """
    with telemetry.span(
        "groupby_aggregate_many", funcs=list(funcs), method=method
    ):
        return _aggregate_many_impl(
            array, *by, funcs=tuple(funcs), expected_groups=expected_groups,
            sort=sort, isbin=isbin, axis=axis, fill_value=fill_value,
            dtype=dtype, min_count=min_count, engine=engine,
            finalize_kwargs=finalize_kwargs, method=method, mesh=mesh,
            axis_name=axis_name,
        )


def _aggregate_many_impl(
    array: Any,
    *by: Any,
    funcs: tuple,
    expected_groups: Any,
    sort: bool,
    isbin: Any,
    axis: Any,
    fill_value: Any,
    dtype: Any,
    min_count: int | None,
    engine: str | None,
    finalize_kwargs: dict | None,
    method: str | None,
    mesh: Any,
    axis_name: str,
) -> tuple:
    from .core import (
        _choose_engine,
        _convert_expected_groups_to_index,
        _normalize_expected,
        _normalize_isbin,
        _normalize_reduce_axes,
    )
    from .sparse import is_sparse_array

    if not by:
        raise TypeError("Must pass at least one `by`")
    if method not in (None, "map-reduce", "cohorts"):
        raise NotImplementedError(
            "groupby_aggregate_many supports method=None (eager) and "
            "'map-reduce'/'cohorts' on a mesh; 'blockwise' finalizes per "
            "shard through the single-statistic kernels — run sequential "
            "groupby_reduce calls there."
        )
    if is_sparse_array(array):
        raise NotImplementedError(
            "sparse inputs are not fusable; run sequential groupby_reduce calls"
        )

    nby = len(by)
    if nby == 1 and isinstance(by[0], fct.Prefactorized):
        # registry fast path: factorization happened at put_dataset time —
        # no factorize span, and the put-staged device codes feed the fused
        # program directly (zero codes H2D on the hit path)
        return _aggregate_many_prefactorized(
            array, by[0], funcs=funcs, expected_groups=expected_groups,
            isbin=isbin, axis=axis, fill_value=fill_value, dtype=dtype,
            min_count=min_count, engine=engine,
            finalize_kwargs=finalize_kwargs, method=method, mesh=mesh,
            axis_name=axis_name,
        )
    bys = [utils.asarray_host(b) for b in by]
    bys = list(np.broadcast_arrays(*bys)) if nby > 1 else bys
    array_is_jax = utils.is_jax_array(array)
    engine = _choose_engine(engine, array, array_is_jax)
    arr = array if array_is_jax else np.asarray(array)
    from . import dtypes as dtps

    arr_dtype = np.dtype(arr.dtype)
    if arr_dtype.kind in "OSUmM" or dtps.is_datetime_like(arr_dtype):
        raise NotImplementedError(
            f"groupby_aggregate_many supports numeric data; got {arr_dtype} "
            "(datetime/object inputs keep the sequential groupby_reduce path)"
        )
    if arr_dtype.kind == "b":
        # core's bool rule, set-wide: additive reductions need the int
        # view (segment add rejects bool); all/any/count are bool-native.
        # A set mixing bools into float/extrema statistics has no single
        # input view that matches every sequential call — reject it.
        addlike = {"sum", "nansum", "prod", "nanprod"}
        boolsafe = {"all", "any", "count"}
        if set(funcs) <= boolsafe:
            pass
        elif set(funcs) <= (addlike | boolsafe):
            arr = arr.astype(np.int64 if utils.x64_enabled() else np.int32)
        else:
            raise NotImplementedError(
                f"bool data fuses only {sorted(addlike | boolsafe)}; run "
                f"{sorted(set(funcs) - addlike - boolsafe)} sequentially"
            )

    from .core import _assert_by_is_aligned

    _assert_by_is_aligned(arr.shape, bys)
    expected = _normalize_expected(expected_groups, nby)
    isbin_t = _normalize_isbin(isbin, nby)
    expected_idx = _convert_expected_groups_to_index(expected, isbin_t, sort)

    arr, bys, n_keep, bndim = _normalize_reduce_axes(arr, bys, axis)
    keep_by_shape = tuple(bys[0].shape[:n_keep])

    with telemetry.span("factorize", nby=nby) as _fsp:
        codes, found_groups, grp_shape, ngroups, size, props = fct.factorize_cached(
            tuple(bys), axes=tuple(range(n_keep, bndim)),
            expected_groups=expected_idx, sort=sort,
        )
        _fsp.set(ngroups=ngroups, size=size)
    if ngroups == 0 or size == 0:
        raise ValueError("No groups to reduce over (empty expected_groups?)")

    min_count_ = 0 if min_count is None else min_count
    fused = plan_fused(funcs, dtype, arr.dtype, fill_value, min_count_, finalize_kwargs)

    # -- flatten for the kernels (the groupby_reduce contract) -------------
    span = int(np.prod(bys[0].shape)) if bys[0].size else 0
    lead_shape = arr.shape[: arr.ndim - bndim]
    arr_flat = arr.reshape(lead_shape + (span,))
    codes_flat = np.asarray(codes).reshape(-1)
    out_shape = lead_shape + keep_by_shape + grp_shape

    def per_func_kw(f):
        def pick(v):
            return v.get(f) if isinstance(v, dict) else v

        return {
            "fill_value": pick(fill_value), "dtype": pick(dtype),
            "finalize_kwargs": pick(finalize_kwargs), "min_count": min_count,
        }

    common_kw = {
        "expected_groups": expected_groups, "sort": sort, "isbin": isbin,
        "axis": axis, "engine": engine, "method": method, "mesh": mesh,
        "axis_name": axis_name,
    }

    # -- fused-vs-sequential dispatch (the "fused" autotune family) --------
    if OPTIONS["autotune"] and engine == "jax":
        from . import autotune

        nelems = int(np.prod(arr_flat.shape)) if arr_flat.ndim else 0
        choice = autotune.decide(
            "fused", "fused", ("fused", "sequential"),
            dtype=str(arr_flat.dtype), ngroups=size, nelems=nelems,
        )
        if choice == "sequential":
            logger.debug("fused autotune: sequential wins for this band")
            return _sequential_fallback(
                array, by, funcs, per_func_kw=per_func_kw, common_kw=common_kw
            )

    if method is not None or mesh is not None:
        # -- one SPMD program for the whole statistic set ------------------
        from .parallel.mapreduce import sharded_groupby_reduce

        with telemetry.span("combine", method=method or "map-reduce", size=size):
            results = sharded_groupby_reduce(
                arr_flat, codes_flat, fused, size=size, mesh=mesh,
                axis_name=axis_name, method=method or "map-reduce",
            )
        with telemetry.span("finalize"):
            out = finalize_many(fused, results, out_shape)
        return (out,) + tuple(_index_values(g) for g in found_groups)

    if engine == "numpy":
        inters = fused_chunk_stats(
            fused, codes_flat, arr_flat, size=size, engine="numpy", eager=True
        )
        with telemetry.span("finalize"):
            out = finalize_many(fused, fused.finalize_fused(inters), out_shape)
        return (out,) + tuple(_index_values(g) for g in found_groups)

    # -- eager jax: ONE jitted program for chunk legs + every finalize -----
    from .parallel.mapreduce import dense_intermediate_bytes

    lead_elems = int(np.prod(lead_shape)) if lead_shape else 1
    est = dense_intermediate_bytes(lead_elems, size, arr_flat.dtype, fused, ndev=1)
    ceiling = OPTIONS["dense_intermediate_bytes_max"]
    if est > ceiling:
        raise ValueError(
            f"{fused.name!r} over {size} groups needs ~{utils.fmt_bytes(est)} "
            f"of dense (..., size) device intermediates, above the "
            f"{utils.fmt_bytes(ceiling)} dense_intermediate_bytes_max ceiling. "
            "Options: pass mesh=; reduce expected_groups; or raise "
            "set_options(dense_intermediate_bytes_max=...)."
        )

    key = _fused_key(fused, size)
    program = _FUSED_PROGRAM_CACHE.get(key)
    if program is None:
        telemetry.count("cache.fused_program_misses")
        import jax

        def run_rows(codes_d, array_d):
            inters = fused_chunk_stats(
                fused, codes_d, array_d, size=size, engine="jax", eager=True
            )
            with jax.named_scope("flox.finalize"):
                return fused.finalize_fused(inters)

        def flox_fused(codes_d, array_d):
            return utils.map_row_blocks(run_rows, codes_d, array_d)

        program = jax.jit(flox_fused)
        _FUSED_PROGRAM_CACHE[key] = program
    else:
        telemetry.count("cache.fused_program_hits")

    tm_on = telemetry.enabled()
    if tm_on:
        # cost-ledger baseline (the chunk_reduce discipline): the staged
        # bytes are billed ONCE for the whole statistic set — that 1x-vs-Nx
        # ledger delta IS the fusion win, surfaced per program key
        from time import perf_counter

        compiles0 = telemetry.METRICS.get("jax.compiles")
        compile_ms0 = telemetry.METRICS.get("jax.compile_ms")
        t0 = perf_counter()
    with telemetry.span("dispatch", engine="jax", nstats=len(funcs), size=size):
        # staging stays INSIDE the span (it always covered transfer +
        # execute); the device refs are kept for the card site below
        codes_d = utils.asarray_device(codes_flat)
        arr_d = utils.asarray_device(arr_flat)
        results = program(codes_d, arr_d)
    if tm_on:
        # observed wall snapshotted BEFORE the card analysis: its
        # lower+compile must not bill as device time (it would read as
        # drift on the first dispatch)
        dispatch_ms = (perf_counter() - t0) * 1e3
        prog = fused_program_label(funcs)
        telemetry.sample_hbm(program=prog)
        # analytical card for the ONE fused program (costmodel plane):
        # memoized per shape signature, recorded before the ledger write so
        # the first dispatch's gauge join already finds it
        from . import costmodel

        costmodel.ensure_card(prog, program, (codes_d, arr_d))
        telemetry.observe_cost(
            prog,
            device_ms=dispatch_ms,
            nbytes=int(getattr(arr_flat, "nbytes", 0))
            + int(getattr(codes_flat, "nbytes", 0)),
            compiles=int(telemetry.METRICS.get("jax.compiles") - compiles0),
            compile_ms=telemetry.METRICS.get("jax.compile_ms") - compile_ms0,
        )
    with telemetry.span("finalize"):
        out = finalize_many(fused, results, out_shape)
    return (out,) + tuple(_index_values(g) for g in found_groups)


def _aggregate_many_prefactorized(
    array: Any,
    pf: "fct.Prefactorized",
    *,
    funcs: tuple,
    expected_groups: Any,
    isbin: Any,
    axis: Any,
    fill_value: Any,
    dtype: Any,
    min_count: int | None,
    engine: str | None,
    finalize_kwargs: dict | None,
    method: str | None,
    mesh: Any,
    axis_name: str,
) -> tuple:
    """Fused multi-statistic over a :class:`factorize.Prefactorized` ``by``
    — the registry fast path of :func:`groupby_aggregate_many`. Mirrors the
    inline body from the engine choice onward, minus factorize and minus
    the codes H2D (put-staged ``codes_dev`` feeds the fused program)."""
    from .core import _choose_engine

    bad = [
        name
        for name, val in (
            ("expected_groups", expected_groups),
            ("axis", axis),
        )
        if val is not None
    ]
    if isbin not in (False, (False,)):
        bad.append("isbin")
    if bad:
        raise NotImplementedError(
            f"Prefactorized `by` does not support {bad}: the factorization "
            "is fixed at put time (re-put the dataset with different groups)"
        )
    array_is_jax = utils.is_jax_array(array)
    engine = _choose_engine(engine, array, array_is_jax)
    arr = array if array_is_jax else np.asarray(array)
    arr_dtype = np.dtype(arr.dtype)
    from . import dtypes as dtps

    if arr_dtype.kind in "OSUmM" or dtps.is_datetime_like(arr_dtype):
        raise NotImplementedError(
            f"groupby_aggregate_many supports numeric data; got {arr_dtype}"
        )
    if arr_dtype.kind == "b":
        addlike = {"sum", "nansum", "prod", "nanprod"}
        boolsafe = {"all", "any", "count"}
        if set(funcs) <= boolsafe:
            pass
        elif set(funcs) <= (addlike | boolsafe):
            arr = arr.astype(np.int64 if utils.x64_enabled() else np.int32)
        else:
            raise NotImplementedError(
                f"bool data fuses only {sorted(addlike | boolsafe)}; run "
                f"{sorted(set(funcs) - addlike - boolsafe)} sequentially"
            )
    bndim = len(pf.by_shape)
    if arr.ndim < bndim or tuple(arr.shape[arr.ndim - bndim:]) != tuple(pf.by_shape):
        raise ValueError(
            f"`array` with shape {arr.shape} does not align with the "
            f"prefactorized `by` shape {pf.by_shape}"
        )

    size = pf.size
    min_count_ = 0 if min_count is None else min_count
    fused = plan_fused(funcs, dtype, arr.dtype, fill_value, min_count_, finalize_kwargs)

    lead_shape = arr.shape[: arr.ndim - bndim]
    arr_flat = arr.reshape(lead_shape + (pf.n,))
    out_shape = lead_shape + pf.group_shape

    if OPTIONS["autotune"] and engine == "jax":
        from . import autotune

        nelems = int(np.prod(arr_flat.shape)) if arr_flat.ndim else 0
        choice = autotune.decide(
            "fused", "fused", ("fused", "sequential"),
            dtype=str(arr_flat.dtype), ngroups=size, nelems=nelems,
        )
        if choice == "sequential":
            def per_func_kw(f):
                def pick(v):
                    return v.get(f) if isinstance(v, dict) else v

                return {
                    "fill_value": pick(fill_value), "dtype": pick(dtype),
                    "finalize_kwargs": pick(finalize_kwargs),
                    "min_count": min_count,
                }

            return _sequential_fallback(
                array, (pf,), funcs, per_func_kw=per_func_kw,
                common_kw={
                    "engine": engine, "method": method, "mesh": mesh,
                    "axis_name": axis_name,
                },
            )

    if method is not None or mesh is not None:
        from .parallel.mapreduce import sharded_groupby_reduce

        codes_run = pf.codes if (method == "cohorts" or pf.codes_dev is None) else pf.codes_dev
        with telemetry.span("combine", method=method or "map-reduce", size=size):
            results = sharded_groupby_reduce(
                arr_flat, codes_run, fused, size=size, mesh=mesh,
                axis_name=axis_name, method=method or "map-reduce",
            )
        with telemetry.span("finalize"):
            out = finalize_many(fused, results, out_shape)
        return (out,) + tuple(_index_values(g) for g in pf.found_groups)

    if engine == "numpy":
        inters = fused_chunk_stats(
            fused, pf.codes, arr_flat, size=size, engine="numpy", eager=True
        )
        with telemetry.span("finalize"):
            out = finalize_many(fused, fused.finalize_fused(inters), out_shape)
        return (out,) + tuple(_index_values(g) for g in pf.found_groups)

    from .parallel.mapreduce import dense_intermediate_bytes

    lead_elems = int(np.prod(lead_shape)) if lead_shape else 1
    est = dense_intermediate_bytes(lead_elems, size, arr_flat.dtype, fused, ndev=1)
    ceiling = OPTIONS["dense_intermediate_bytes_max"]
    if est > ceiling:
        raise ValueError(
            f"{fused.name!r} over {size} groups needs ~{utils.fmt_bytes(est)} "
            f"of dense (..., size) device intermediates, above the "
            f"{utils.fmt_bytes(ceiling)} dense_intermediate_bytes_max ceiling."
        )

    key = _fused_key(fused, size)
    program = _FUSED_PROGRAM_CACHE.get(key)
    if program is None:
        telemetry.count("cache.fused_program_misses")
        import jax

        def run_rows(codes_d, array_d):
            inters = fused_chunk_stats(
                fused, codes_d, array_d, size=size, engine="jax", eager=True
            )
            with jax.named_scope("flox.finalize"):
                return fused.finalize_fused(inters)

        def flox_fused(codes_d, array_d):
            return utils.map_row_blocks(run_rows, codes_d, array_d)

        program = jax.jit(flox_fused)
        _FUSED_PROGRAM_CACHE[key] = program
    else:
        telemetry.count("cache.fused_program_hits")

    tm_on = telemetry.enabled()
    if tm_on:
        from time import perf_counter

        compiles0 = telemetry.METRICS.get("jax.compiles")
        compile_ms0 = telemetry.METRICS.get("jax.compile_ms")
        t0 = perf_counter()
    with telemetry.span("dispatch", engine="jax", nstats=len(funcs), size=size):
        codes_d = utils.asarray_device(
            pf.codes_dev if pf.codes_dev is not None else pf.codes
        )
        arr_d = utils.asarray_device(arr_flat)
        results = program(codes_d, arr_d)
    if tm_on:
        dispatch_ms = (perf_counter() - t0) * 1e3
        prog = fused_program_label(funcs)
        telemetry.sample_hbm(program=prog)
        from . import costmodel

        costmodel.ensure_card(prog, program, (codes_d, arr_d))
        telemetry.observe_cost(
            prog,
            device_ms=dispatch_ms,
            nbytes=int(getattr(arr_flat, "nbytes", 0))
            + int(getattr(pf.codes, "nbytes", 0)),
            compiles=int(telemetry.METRICS.get("jax.compiles") - compiles0),
            compile_ms=telemetry.METRICS.get("jax.compile_ms") - compile_ms0,
        )
    with telemetry.span("finalize"):
        out = finalize_many(fused, results, out_shape)
    return (out,) + tuple(_index_values(g) for g in pf.found_groups)


def _index_values(idx):
    from .core import _index_values as _iv

    return _iv(idx)
