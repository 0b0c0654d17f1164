"""Core orchestration: ``groupby_reduce`` and the chunk-level reducer (L4).

Parity target: /root/reference/flox/core.py — ``groupby_reduce``
(core.py:739-1222), ``chunk_reduce`` (214-394), ``_finalize_results``
(410-475), ``_reduce_blockwise`` (478-524), plus the argreduction chunk
wrapper (157-211).

TPU-first architecture:

* The hot path, ``chunk_reduce``, traces ALL requested kernels into one
  ``jax.jit`` program (cached per static signature), so XLA fuses the shared
  scatter work — mean's sum+count are one pass, exactly the fusion the
  reference gets by hand-deduplicating ``nanlen`` (core.py:348-391).
* Group codes are computed host-side by pandas when labels are unknown
  (data-dependent → host, as the reference keeps them) and can stay fully
  on-device when ``expected_groups`` is known (factorize.factorize_device).
* The eager path below IS the single-chip program; the distributed methods
  (map-reduce / blockwise / cohorts over a mesh) build on the same
  ``chunk_reduce`` inside ``shard_map`` (see parallel/).
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Callable, Sequence

import numpy as np
import pandas as pd

from . import dtypes, factorize as fct, telemetry, utils
from .aggregations import Aggregation, _initialize_aggregation, generic_aggregate, normalize_engine
from .cache import LRUCache
from .options import OPTIONS

logger = logging.getLogger("flox_tpu.core")

__all__ = ["groupby_reduce", "chunk_reduce"]

_NAT_INT = np.iinfo(np.int64).min  # NaT viewed as int64


# ---------------------------------------------------------------------------
# argument normalization
# ---------------------------------------------------------------------------


def _assert_by_is_aligned(shape: tuple[int, ...], bys: Sequence[np.ndarray]) -> None:
    """All ``by`` arrays must match the trailing dims of ``array``
    (parity: core.py:589-607)."""
    for b in bys:
        if b.ndim > len(shape) or shape[-b.ndim :] != b.shape:
            raise ValueError(
                f"`by` has shape {b.shape} which does not align with the trailing "
                f"dimensions of `array` with shape {shape}."
            )


def _convert_expected_groups_to_index(
    expected, isbin: Sequence[bool], sort: bool
) -> tuple[pd.Index | None, ...]:
    """Normalize user expected_groups to pandas Indexes
    (parity: core.py:616-682)."""
    out = []
    for exp, bin_ in zip(expected, isbin):
        if exp is None:
            out.append(None)
        elif isinstance(exp, pd.IntervalIndex):
            out.append(exp)
        elif isinstance(exp, pd.Index) and not bin_:
            out.append(exp)
        elif bin_:
            out.append(pd.IntervalIndex.from_breaks(np.asarray(exp)))
        else:
            values = utils.asarray_host(np.asarray(exp))
            if sort:
                values = np.sort(values)
            out.append(pd.Index(values))
    return tuple(out)


def _normalize_expected(expected, nby: int):
    if expected is None:
        return (None,) * nby
    if nby == 1 and not isinstance(expected, tuple):
        return (expected,)
    if not isinstance(expected, tuple):
        raise ValueError("With multiple `by`, `expected_groups` must be a tuple.")
    if len(expected) != nby:
        raise ValueError(
            f"Must have one expected_groups entry per `by` ({nby}); got {len(expected)}."
        )
    return expected


def _normalize_isbin(isbin, nby: int) -> tuple[bool, ...]:
    if isinstance(isbin, bool):
        return (isbin,) * nby
    return tuple(isbin)


# ---------------------------------------------------------------------------
# chunk_reduce: the hot kernel bundle
# ---------------------------------------------------------------------------


def _norm_chunk_entry(entry) -> tuple[str | Callable, dict]:
    if isinstance(entry, tuple):
        return entry[0], dict(entry[1])
    return entry, {}


@functools.lru_cache(maxsize=512)
def _jitted_bundle(funcs_key, size: int, engine: str, opts_key: tuple = ()):
    """Build & cache one jitted program running all kernels of a reduction.

    ``funcs_key`` is a hashable encoding of (func, fill_value, dtype-str,
    extra-kwargs) per kernel. jit caching is on this key + jax's own shape
    tracing.
    """
    import jax

    # body runs only on an lru_cache miss: a fresh jit program is built (it
    # still traces/compiles per input shape — jax.compiles counts those)
    telemetry.count("cache.bundle_builds")

    specs = funcs_key

    def run_rows(codes, array):
        outs = []
        for func, fv, dt, kw in specs:
            outs.append(
                generic_aggregate(
                    codes,
                    array,
                    engine="jax",
                    func=func,
                    size=size,
                    fill_value=np.nan if isinstance(fv, str) and fv == "__nan__" else fv,
                    dtype=np.dtype(dt) if dt is not None else None,
                    **dict(kw),
                )
            )
        return tuple(outs)

    def flox_bundle(codes, array):
        return utils.map_row_blocks(run_rows, codes, array)

    # the name is the program's in the profiler's trace
    return jax.jit(flox_bundle)


def chunk_reduce(
    array,
    codes,
    *,
    funcs: Sequence[str | Callable | tuple],
    size: int,
    fill_values: Sequence[Any],
    dtypes_: Sequence[Any],
    engine: str,
    kwargss: Sequence[dict] | None = None,
    jit: bool = True,
    prog_family: str = "bundle",
):
    """Run a bundle of grouped reductions over the trailing axis.

    ``array``: (..., N); ``codes``: (N,) int with -1 missing. Returns a list
    of per-func results, each (..., size) (parity: core.py:214-394 minus the
    re-factorization, which happens once in groupby_reduce here).

    Repeated (func, kwargs) entries are computed once and fanned out
    (parity: the nanlen dedup at core.py:352).

    ``prog_family`` names the cost-ledger / program-card label family
    (``bundle[...]`` for the dense eager path, ``sort[...]`` when the
    present-groups engine dispatches this bundle over the compact domain),
    so ``/debug/programs`` utilization and the drift sentinel can tell the
    two apart.
    """
    if kwargss is None:
        kwargss = [{}] * len(funcs)

    # dedup identical kernel invocations
    seen: dict[tuple, int] = {}
    plan: list[tuple] = []
    positions: list[int] = []
    for func, fv, dt, kw in zip(funcs, fill_values, dtypes_, kwargss):
        func_n, extra = _norm_chunk_entry(func)
        merged = {k: (tuple(v) if isinstance(v, list) else v) for k, v in {**extra, **kw}.items()}
        key = (
            func_n if isinstance(func_n, str) else id(func_n),
            None if fv is None else (repr(fv)),
            # .name, not .str: extension dtypes (bfloat16) stringify to
            # '|V2' via .str, which round-trips to a void dtype
            None if dt is None else np.dtype(dt).name,
            tuple(sorted(merged.items())),
        )
        if key in seen:
            positions.append(seen[key])
        else:
            seen[key] = len(plan)
            positions.append(len(plan))
            plan.append((func_n, fv, dt, merged))

    if engine == "jax" and jit and all(isinstance(p[0], str) for p in plan):
        funcs_key = tuple(
            (f, _hashable_fill(fv), None if dt is None else np.dtype(dt).name, tuple(sorted(kw.items())))
            for f, fv, dt, kw in plan
        )
        from .options import trace_fingerprint

        telemetry.count("cache.bundle_calls")
        bundle = _jitted_bundle(funcs_key, size, engine, trace_fingerprint())
        tm_on = telemetry.enabled()
        if tm_on:
            # cost-ledger baseline: dispatch wall + the jax-compile delta
            # this bundle call provokes, attributed per program key below.
            # All of it gated so the disabled hot path reads no clock and
            # builds no label.
            from time import perf_counter

            compiles0 = telemetry.METRICS.get("jax.compiles")
            compile_ms0 = telemetry.METRICS.get("jax.compile_ms")
            t_dispatch0 = perf_counter()
        if tm_on:
            prog = prog_family + "[" + "+".join(str(p[0]) for p in plan) + "]"
            # deterministic drift-injection hook (faults.dispatch_delay):
            # the sentinel tests delay THIS dispatch so the observed wall
            # honestly diverges from the analytical model
            from . import faults

            if faults.dispatch_delay_active():
                faults.dispatch_delay_poke(prog)
        with telemetry.span(
            "dispatch", engine=engine, nkernels=len(plan), size=size,
            funcs=[p[0] for p in plan if isinstance(p[0], str)],
        ):
            # staging stays INSIDE the span: the dispatch span has always
            # covered transfer + execute, and the trace view must not
            # silently shrink; the device refs are kept for the card site
            codes_d = utils.asarray_device(codes)
            array_d = utils.asarray_device(array)
            results = bundle(codes_d, array_d)
        if tm_on:
            # observed wall snapshotted BEFORE the card analysis below: its
            # lower+compile is bookkeeping, and billing it as device time
            # would read as drift on the very first dispatch
            dispatch_ms = (perf_counter() - t_dispatch0) * 1e3
            # HBM pressure right after the device dispatch, attributed to
            # this kernel bundle (cache.stats()["hbm_by_program"]); no-op
            # off-device, and the label join costs nothing when off
            telemetry.sample_hbm(program=prog)
            # the program's analytical card (costmodel plane, opt-in): one
            # lower+compile per (label, shape signature), memoized — the
            # roofline join behind program.utilization/predicted_ms
            from . import costmodel

            costmodel.ensure_card(prog, bundle, (codes_d, array_d))
            telemetry.observe_cost(
                prog,
                device_ms=dispatch_ms,
                nbytes=int(getattr(array, "nbytes", 0))
                + int(getattr(codes, "nbytes", 0)),
                compiles=int(telemetry.METRICS.get("jax.compiles") - compiles0),
                compile_ms=telemetry.METRICS.get("jax.compile_ms") - compile_ms0,
            )
    else:
        with telemetry.span(
            "dispatch", engine=engine, nkernels=len(plan), size=size,
        ):
            results = [
                generic_aggregate(
                    codes,
                    array,
                    engine=engine,
                    func=f,
                    size=size,
                    fill_value=fv,
                    dtype=dt,
                    **kw,
                )
                for f, fv, dt, kw in plan
            ]
    return [results[i] for i in positions]


def _hashable_fill(fv):
    if fv is None:
        return None
    try:
        if np.ndim(fv) == 0 and np.isnan(fv):
            return "__nan__"  # nan != nan would defeat the lru_cache
    except (TypeError, ValueError):
        pass
    if isinstance(fv, (bool, int, float, complex, str)):
        return fv
    return float(fv) if np.ndim(fv) == 0 else repr(fv)


# ---------------------------------------------------------------------------
# groupby_reduce
# ---------------------------------------------------------------------------


def _normalize_reduce_axes(arr, bys, axis):
    """Move the reduced by-dims to the trailing position (the flatten
    contract shared by groupby_reduce, groupby_scan and the streaming
    runtime — parity: reference core.py:957-1018).

    Returns ``(arr, bys, n_keep, bndim)``: the (possibly transposed) array
    and labels, the count of kept (non-reduced) by-dims now leading the
    by-span, and the by-span rank after any broadcast. ``axis`` entries
    below the by-span broadcast the labels over those dims first.
    """
    bndim = bys[0].ndim
    if axis is None:
        axes = tuple(range(arr.ndim - bndim, arr.ndim))
    else:
        axes = utils.normalize_axis_tuple(axis, arr.ndim)
    first_by_ax = arr.ndim - bndim
    if any(ax < first_by_ax for ax in axes):
        # reducing over dims the labels don't cover: broadcast labels over them
        new_bndim = arr.ndim - min(axes)
        target_shape = arr.shape[-new_bndim:]
        bys = [np.broadcast_to(b, target_shape) for b in bys]
        bndim = new_bndim
        first_by_ax = arr.ndim - bndim

    rel_axes = tuple(ax - first_by_ax for ax in axes)  # axes within by dims
    # transpose the by-dims block so reduced dims are trailing
    by_keep = [d for d in range(bndim) if d not in rel_axes]
    by_order = by_keep + list(rel_axes)
    if by_order != list(range(bndim)):
        bys = [b.transpose(by_order) for b in bys]
        arr_order = list(range(first_by_ax)) + [first_by_ax + d for d in by_order]
        arr = arr.transpose(arr_order)
    return arr, bys, len(by_keep), bndim


def _choose_engine(engine, array, array_is_jax: bool) -> str:
    """Default engine choice (parity: _choose_engine, core.py:712-736).

    The jit path wins for device arrays and anything sizeable; small host
    arrays skip jit dispatch overhead via the numpy engine — but only when
    both engines produce the same result dtype (x64 on), so the choice is
    invisible to the caller. The size crossover is
    ``OPTIONS["numpy_engine_max_elems"]`` (measured round 5, CPU host,
    nanmean, 10 groups, median of 20: numpy/jax ms = 0.15/0.60 @512,
    0.19/0.64 @2048, 0.93/1.93 @32768, 8.4/6.3 @131072 — crossover
    ~64-100k; 32768 is the last measured point where numpy wins 2x, and
    device dispatch only pushes the crossover higher on an accelerator).
    With the autotuner on, a measured "engine" record for the size band
    overrides the threshold — both engines are x64-equivalent here, so the
    swap stays invisible to the caller.
    """
    if engine is not None:
        return normalize_engine(engine)
    if not array_is_jax and utils.x64_enabled():
        arr = np.asarray(array)
        nelems = int(arr.size)
        heuristic = (
            "numpy"
            if nelems < OPTIONS["numpy_engine_max_elems"]
            else OPTIONS["default_engine"]
        )
        # consult the tuner only when the fallback is the jax engine — a
        # default_engine="numpy" session forced the host engine and the
        # tuner must not second-guess that
        if OPTIONS["autotune"] and OPTIONS["default_engine"] == "jax":
            from . import autotune

            dt = arr.dtype
            autotune.prime_engine(dt, nelems)
            chosen = autotune.decide(
                "engine", heuristic, ("numpy", "jax"),
                dtype=str(dt), nelems=nelems,
            )
            if chosen != heuristic:
                logger.debug("engine autotune: %s (heuristic %s)", chosen, heuristic)
            return chosen
        if heuristic == "numpy":
            logger.debug("engine heuristic: small host array -> numpy")
        return heuristic
    return OPTIONS["default_engine"]


_NON_NUMERIC_FUNCS = ("first", "last", "nanfirst", "nanlast", "count")


def _reduce_non_numeric(arr, bys, func: str, *, fill_value, **passthrough):
    """first/last/count on string/object arrays (reference: its numpy
    engines take any dtype, tests/strategies.py unicode data).

    Non-numeric values cannot live on device, but their *positions* can:
    reduce a float64 global-position proxy through the normal machinery
    (so every engine/method/mesh works unchanged), then gather host-side.
    Positions are exact to 2**53 elements with x64, 2**24 without (the jax
    engine computes in f32 then) — the caller guards the latter.
    """
    valid = ~pd.isna(arr)
    if func == "count":
        proxy = np.where(valid, 1.0, np.nan)
        return groupby_reduce(proxy, *bys, func="count", fill_value=fill_value, **passthrough)

    pos = np.arange(arr.size, dtype=np.float64).reshape(arr.shape)
    skipna = func.startswith("nan")
    proxy = np.where(valid, pos, np.nan) if skipna else pos
    minmax = "nanmin" if "first" in func else "nanmax"
    posr, *groups = groupby_reduce(proxy, *bys, func=minmax, **passthrough)
    posr = np.asarray(posr)
    empty = ~np.isfinite(posr)
    idx = np.where(empty, 0, posr).astype(np.int64)
    out = arr.reshape(-1)[idx]
    if empty.any():
        fill = fill_value  # None is a fine missing marker for objects
        if out.dtype.kind in "SU":
            out = out.astype(object)
        out[empty] = fill
    return (out, *groups)


def groupby_reduce(
    array: Any,
    *by: Any,
    func: str | Aggregation,
    expected_groups: Any = None,
    sort: bool = True,
    isbin: bool | Sequence[bool] = False,
    axis: int | Sequence[int] | None = None,
    fill_value: Any = None,
    dtype: Any = None,
    min_count: int | None = None,
    method: str | None = None,
    engine: str | None = None,
    reindex: Any = None,
    finalize_kwargs: dict | None = None,
    mesh: Any = None,
    axis_name: str = "data",
) -> tuple:
    """GroupBy reduction (parity: core.py:739-1222; same signature contract).

    Returns ``(result, *groups)`` where ``result`` has the reduced axes
    replaced by one axis per grouper (plus any new dims, e.g. quantile's q).

    ``method=None`` runs the fused eager path on one device. Passing
    ``method`` ("map-reduce" | "cohorts" | "blockwise") runs the reduction
    as one SPMD program over ``mesh`` (default: a 1-D mesh over all
    devices), sharding the reduced axis and combining with collectives —
    the TPU analogue of the reference's dask execution methods (core.py:89).

    Examples
    --------
    >>> import numpy as np
    >>> from flox_tpu import groupby_reduce
    >>> values = np.array([1.0, 2.0, 4.0, 8.0])
    >>> labels = np.array([0, 0, 1, 1])
    >>> result, groups = groupby_reduce(values, labels, func="sum", engine="numpy")
    >>> result
    array([ 3., 12.])
    >>> groups
    array([0, 1])

    Binning, and a group with no members filled per the aggregation:

    >>> result, bins = groupby_reduce(
    ...     values, values, func="count", engine="numpy",
    ...     expected_groups=np.array([0.0, 3.0, 6.0, 9.0]), isbin=True,
    ... )
    >>> result
    array([2, 1, 1])
    """
    with telemetry.span(
        "groupby_reduce",
        func=func if isinstance(func, str) else getattr(func, "name", "custom"),
        method=method,
    ):
        return _groupby_reduce_impl(
            array, *by, func=func, expected_groups=expected_groups, sort=sort,
            isbin=isbin, axis=axis, fill_value=fill_value, dtype=dtype,
            min_count=min_count, method=method, engine=engine, reindex=reindex,
            finalize_kwargs=finalize_kwargs, mesh=mesh, axis_name=axis_name,
        )


def _groupby_reduce_impl(
    array: Any,
    *by: Any,
    func: str | Aggregation,
    expected_groups: Any,
    sort: bool,
    isbin: bool | Sequence[bool],
    axis: int | Sequence[int] | None,
    fill_value: Any,
    dtype: Any,
    min_count: int | None,
    method: str | None,
    engine: str | None,
    reindex: Any,
    finalize_kwargs: dict | None,
    mesh: Any,
    axis_name: str,
) -> tuple:
    """The :func:`groupby_reduce` body, under the public wrapper's root
    telemetry span (the wrapper exists so the span covers every early
    dispatch — sparse, non-numeric — without touching their returns).
    Defaults live ONLY on the public wrapper, which forwards every
    argument — no defaults here, so signature drift fails loudly."""
    if not by:
        raise TypeError("Must pass at least one `by`")
    if method not in (None, "map-reduce", "blockwise", "cohorts"):
        raise ValueError(
            f"method must be one of None, 'map-reduce', 'blockwise', 'cohorts'; got {method!r}"
        )
    # -- reindex mapping (parity: _validate_reindex, reference core.py:527-586)
    # dense-by-design: every intermediate is already dense over
    # expected_groups (shape-static is what XLA fusion and mesh collectives
    # require — docs/implementation.md), so reindex=True is implicit.
    # ReindexStrategy values map onto that reality instead of raising:
    #   * blockwise=True/None + AUTO/NUMPY  -> the implicit dense behavior
    #   * array_type=SPARSE_COO             -> sparse host result leg
    #   * blockwise=False (dense type)      -> no-op eagerly and for
    #     cohorts/blockwise (label-aligned combine is already what those do;
    #     the reference *requires* False there); raises for mesh map-reduce,
    #     where the dense combine cannot be skipped — the bytes ceiling +
    #     blocked program provide that capability instead.
    from .reindex import ReindexArrayType, ReindexStrategy

    reindex_sparse: ReindexStrategy | None = None
    reindex_blockwise_false = False
    if isinstance(reindex, ReindexStrategy):
        if reindex.array_type is ReindexArrayType.SPARSE_COO:
            reindex_sparse = reindex
        elif reindex.blockwise is False:
            reindex_blockwise_false = True
    elif reindex is False:
        reindex_blockwise_false = True
    elif reindex not in (None, True):
        raise TypeError(
            f"reindex must be None, a bool, or a ReindexStrategy; got {reindex!r}"
        )
    if reindex_sparse is not None:
        _fname = func if isinstance(func, str) else getattr(func, "name", "")
        if not isinstance(_fname, str) or any(
            f in _fname for f in ("first", "last", "prod", "var", "std", "arg")
        ):
            # parity: _is_reindex_sparse_supported_reduction (reference
            # lib.py:134-139) — these have no meaningful implicit fill
            raise ValueError(
                f"reindex with array_type=SPARSE_COO does not support {_fname!r}"
            )
        if len(by) > 1:
            raise NotImplementedError(
                "SPARSE_COO reindex supports a single `by` (the sparse axis "
                "is the trailing group axis)"
            )
    nby = len(by)

    if nby == 1 and isinstance(by[0], fct.Prefactorized):
        # registry fast path: factorization (codes, group tables, present
        # table) happened once at put_dataset time — route around the
        # factorize span and the codes H2D entirely
        return _prefactorized_reduce(
            array, by[0], func=func, expected_groups=expected_groups,
            axis=axis, isbin=isbin, fill_value=fill_value, dtype=dtype,
            min_count=min_count, method=method, engine=engine,
            reindex=reindex, finalize_kwargs=finalize_kwargs, mesh=mesh,
            axis_name=axis_name,
        )

    from .sparse import is_sparse_array

    if is_sparse_array(array):
        # sparse inputs reduce without densifying (parity: aggregate_sparse);
        # options the sparse reducer cannot honor are rejected, not dropped
        unsupported = {
            "min_count": min_count, "axis": axis, "method": method,
            "finalize_kwargs": finalize_kwargs, "mesh": mesh,
            # dense strategies / False are eager no-ops here; only the
            # sparse result leg is unplumbed for sparse inputs
            "reindex (SPARSE_COO)": reindex_sparse,
        }
        bad = [k for k, v in unsupported.items() if v is not None]
        if bad:
            raise NotImplementedError(
                f"sparse inputs do not support {bad} (grouping is over the last "
                "axis, eagerly, with the reference's aggregate_sparse func subset)"
            )
        return _sparse_path(
            array, by, func=func, expected_groups=expected_groups, isbin=isbin,
            sort=sort, fill_value=fill_value, dtype=dtype,
            # validate/alias even though the sparse reducer is engine-fixed:
            # engine='numbagg' etc. must fail the same way everywhere
            engine=normalize_engine(engine) if engine is not None else None,
        )

    # -- host-side label normalization ------------------------------------
    bys = [utils.asarray_host(b) for b in by]
    bys = list(np.broadcast_arrays(*bys)) if nby > 1 else bys
    array_is_jax = utils.is_jax_array(array)
    # explicit engine choices are never second-guessed (the autotuner's own
    # rule): only a heuristic-chosen dense engine may re-route to the sort
    # (present-groups) engine in _route_highcard below
    engine_explicit = engine is not None
    engine = _choose_engine(engine, array, array_is_jax)
    arr = array if array_is_jax else np.asarray(array)
    _assert_by_is_aligned(arr.shape, bys)

    if not array_is_jax and arr.dtype.kind in "OSU":
        if not isinstance(func, str) or func not in _NON_NUMERIC_FUNCS:
            raise TypeError(
                f"non-numeric data (dtype {arr.dtype}) supports only "
                f"{_NON_NUMERIC_FUNCS}; got {func!r}"
            )
        if dtype is not None:
            raise TypeError("dtype= is not supported for non-numeric reductions")
        if finalize_kwargs:
            # rejected, not dropped (same stance as the sparse path)
            raise NotImplementedError(
                "finalize_kwargs are not supported for non-numeric reductions"
            )
        if reindex_sparse is not None:
            raise NotImplementedError(
                "SPARSE_COO reindex is not supported for non-numeric reductions"
            )
        if not utils.x64_enabled() and arr.size >= 2**24:
            # f32 positions are exact only to 2**24; beyond that the gather
            # silently returns wrong elements
            if mesh is not None or method is not None:
                raise ValueError(
                    f"non-numeric reductions of {arr.size} elements on the "
                    "mesh need jax_enable_x64 (positions exceed f32's exact "
                    "integer range)."
                )
            logger.debug("non-numeric proxy with x64 disabled: numpy engine")
            engine = "numpy"
        return _reduce_non_numeric(
            arr, bys, func, fill_value=fill_value,
            expected_groups=expected_groups, sort=sort, isbin=isbin, axis=axis,
            min_count=min_count, method=method, engine=engine,
            mesh=mesh, axis_name=axis_name, reindex=reindex,
        )

    expected = _normalize_expected(expected_groups, nby)
    isbin_t = _normalize_isbin(isbin, nby)
    expected_idx = _convert_expected_groups_to_index(expected, isbin_t, sort)

    # -- axis normalization: reduce axes must be trailing -----------------
    arr, bys, n_keep, bndim = _normalize_reduce_axes(arr, bys, axis)
    nred_shape = tuple(bys[0].shape[n_keep:])
    keep_by_shape = tuple(bys[0].shape[:n_keep])

    # -- factorize (host) --------------------------------------------------
    with telemetry.span("factorize", nby=nby) as _fsp:
        codes, found_groups, grp_shape, ngroups, size, props = fct.factorize_cached(
            tuple(bys), axes=tuple(range(n_keep, bndim)), expected_groups=expected_idx, sort=sort
        )
        _fsp.set(ngroups=ngroups, size=size)
    logger.debug(
        "groupby_reduce: func=%s ngroups=%d size=%d offset=%s engine=%s",
        func if isinstance(func, str) else func.name,
        ngroups,
        size,
        props.offset_group,
        engine,
    )
    if ngroups == 0 or size == 0:
        raise ValueError("No groups to reduce over (empty expected_groups?)")

    # -- method/engine heuristics (parity: core.py:685-736) ----------------
    if method is None and mesh is not None:
        # user opted into the mesh without picking a method: let cohort
        # detection recommend one (the reference's _choose_method defers to
        # find_group_cohorts the same way). Shard count = the product of the
        # *named* mesh axes — on a 2-D mesh sharded over one axis, the data
        # splits over that axis only, not mesh.devices.size.
        from .cohorts import chunks_from_shards, find_group_cohorts
        from .parallel.mapreduce import _norm_axes

        n_shards = int(
            np.prod([mesh.shape[a] for a in _norm_axes(axis_name, mesh)])
        )
        flat = np.asarray(codes).reshape(-1)
        method, _ = find_group_cohorts(
            flat, chunks_from_shards(flat.shape[0], n_shards),
            expected_groups=range(size),
        )
        logger.debug("groupby_reduce: auto-selected method=%s", method)

    if reindex_blockwise_false:
        # any non-None method runs the sharded program (a default mesh is
        # substituted when mesh=None), so key on the resolved method
        if method == "map-reduce":
            raise NotImplementedError(
                "reindex=False (blockwise=False) with method='map-reduce' on a "
                "mesh: the SPMD combine is dense over expected_groups by design "
                "and cannot be skipped. The capability it targets — avoiding "
                "huge dense intermediates — is provided instead by "
                "set_options(dense_intermediate_bytes_max=...): additive "
                "reductions above the ceiling auto-route to the blocked "
                "owner-by-owner program. Use method='cohorts'/'blockwise', or "
                "drop reindex=."
            )
        # eager / cohorts / blockwise: combine (if any) is already
        # label-aligned — the request is the behavior; nothing to change
        logger.debug("reindex(blockwise=False): no-op on this path")

    # -- dtype round-trips -------------------------------------------------
    func_name = func if isinstance(func, str) else func.name
    arr_dtype = np.dtype(arr.dtype)
    datetime_dtype = arr_dtype if dtypes.is_datetime_like(arr_dtype) else None
    if datetime_dtype is not None:
        arr = arr.view("int64") if not array_is_jax else arr
        if engine in ("jax", "sort") and not utils.x64_enabled():
            # int64-ns timestamps cannot survive the x64-off int32 downcast;
            # route to the host engine rather than corrupt values
            logger.debug("datetime input with x64 disabled: using numpy engine")
            engine = "numpy"
    bool_input = arr_dtype.kind == "b"
    if bool_input and func_name in ("sum", "nansum", "prod", "nanprod", "count"):
        arr = arr.astype(np.int64 if utils.x64_enabled() else np.int32)

    # -- min_count semantics (parity: core.py:1026-1038) -------------------
    if min_count is None:
        min_count_ = 0
        if fill_value is not None and func_name in ("nansum", "nanprod"):
            min_count_ = 1
    else:
        min_count_ = min_count

    agg = _initialize_aggregation(
        func, dtype, arr.dtype if datetime_dtype is None else np.dtype("int64"),
        fill_value, min_count_, finalize_kwargs
    )
    if datetime_dtype is not None and agg.preserves_dtype:
        from .aggregations import set_nat_final_fill

        set_nat_final_fill(agg, fill_value)
    elif (
        datetime_dtype is not None
        and agg.reduction_type != "argreduce"
        and agg.name not in ("count", "len", "any", "all")
    ):
        # float-returning reductions of datetimes (mean/var/median/quantile/
        # sum): convert NaT -> NaN once, here, so every skipna/propagation
        # rule applies unchanged; timestamp-valued results round back to the
        # datetime dtype in _astype_final (parity: core.py:985-1001,
        # 1205-1211). f64 keeps ~256 ns resolution on epoch values — the
        # same loss the reference's float interpolation/division has.
        arr_f = np.asarray(arr).astype(np.float64)
        arr_f[np.asarray(arr) == _NAT_INT] = np.nan
        arr = arr_f

    # -- flatten for the kernel -------------------------------------------
    nred = int(np.prod(nred_shape)) if nred_shape else 1
    span = int(np.prod(keep_by_shape + nred_shape)) if (keep_by_shape or nred_shape) else 1
    lead_shape = arr.shape[: arr.ndim - bndim]
    codes_flat = np.asarray(codes).reshape(-1)
    span_rows = 0
    device_eager = method is None and engine in ("jax", "sort") and array_is_jax
    if device_eager and not keep_by_shape and datetime_dtype is None:
        span_rows = _span_block_rows(arr, nred_shape, agg)
    # a span-blocked reduction reads the array as it is laid out: flattening
    # a multi-dim span relayouts the whole array
    arr_flat = arr if span_rows else arr.reshape(lead_shape + (span,))

    if method is not None:
        # -- sharded SPMD reduction over the mesh ---------------------------
        if datetime_dtype is not None and not utils.x64_enabled():
            raise ValueError(
                "datetime inputs on the mesh path need jax_enable_x64 "
                "(int64 timestamps cannot survive the int32 downcast)."
            )
        from .parallel.mapreduce import sharded_groupby_reduce

        # present-groups mesh execution: compact the (host-known) codes
        # once before the SPMD program builds, so every per-device
        # accumulator AND every collective — psum, and the cohorts
        # psum_scatter whose ownership tiles now slice the compact domain —
        # carries only present-group slices; the dense layout reappears
        # host-side after finalize (kernels.scatter_present_dense)
        mesh_present = None
        codes_run = codes_flat  # codes_flat itself stays in the dense code
        size_run = size         # domain (_sparsify_result reads it below)
        if engine == "sort":
            from .kernels import compact_codes, present_cap, present_groups

            mesh_present = present_groups(codes_flat, size)
            if len(mesh_present) < size:
                ncap = present_cap(len(mesh_present), size)
                codes_run = compact_codes(codes_flat, mesh_present)
                _note_highcard(size, ncap, len(mesh_present))
                size_run = ncap
            else:
                mesh_present = None

        # "combine" here is the whole SPMD program: per-shard chunk reduce +
        # the collective tree-combine + on-device finalize, fused in one
        # shard_map (the program-build / dispatch child spans live in
        # parallel/mapreduce.py)
        with telemetry.span("combine", method=method, size=size_run):
            result = sharded_groupby_reduce(
                arr_flat,
                codes_run,
                agg,
                size=size_run,
                mesh=mesh,
                axis_name=axis_name,
                method=method,
                nat=datetime_dtype is not None,
            )
        with telemetry.span("finalize"):
            result = _astype_final(result, agg, datetime_dtype)
            if mesh_present is not None:
                from .kernels import scatter_present_dense

                result = _redevice_scattered(
                    scatter_present_dense(np.asarray(result), mesh_present, size),
                    array_is_jax,
                )
    else:
        # -- eager single-device reduction ---------------------------------
        if engine in ("jax", "sort"):
            engine = _route_highcard(
                engine, codes_flat, arr_flat, lead_shape, size, agg,
                explicit=engine_explicit,
            )
        if engine == "jax" and OPTIONS["autotune"]:
            # first-call candidate measurement (budgeted, once per banded
            # key): runs HERE, on the host outside any trace, so the traced
            # decision points below only ever do a dict lookup
            from . import autotune

            autotune.prime_reduce(
                func_name, arr_flat.dtype, size, int(np.prod(arr_flat.shape))
            )
        if engine == "sort":
            # -- present-groups (sort) engine: compact once, reduce over the
            # banded capacity with the unchanged jax kernels, scatter the
            # dense layout host-side at the very end. Accumulator bytes
            # track n_present, not the label universe.
            from .kernels import compact_codes, present_cap, present_groups

            present = present_groups(codes_flat, size)
            n_present = len(present)
            ncap = present_cap(n_present, size)
            ccodes = compact_codes(codes_flat, present)
            _note_highcard(size, ncap, n_present)
            if OPTIONS["autotune"]:
                from . import autotune

                autotune.prime_reduce(
                    func_name, arr_flat.dtype, ncap, int(np.prod(arr_flat.shape))
                )
            if span_rows:
                result_c = _span_reduce(arr, ccodes.reshape(nred_shape), agg, size=ncap, rows=span_rows)
            else:
                result_c = _reduce_blockwise(
                    arr_flat,
                    ccodes,
                    agg,
                    size=ncap,
                    engine="jax",
                    datetime_dtype=datetime_dtype,
                    prog_family="sort",
                )
            from .kernels import scatter_present_dense

            result = _redevice_scattered(
                scatter_present_dense(np.asarray(result_c), present, size),
                array_is_jax,
            )
        elif span_rows:
            result = _span_reduce(arr, codes_flat.reshape(nred_shape), agg, size=size, rows=span_rows)
        else:
            result = _reduce_blockwise(
                arr_flat,
                codes_flat,
                agg,
                size=size,
                engine=engine,
                datetime_dtype=datetime_dtype,
            )

    # -- reshape: (..., size) -> (..., *keep_by, *grp_shape) ---------------
    out_shape = lead_shape + keep_by_shape + grp_shape
    new_dims = agg.new_dims()
    if new_dims:
        out_shape = new_dims + out_shape
    result = result.reshape(out_shape)

    if reindex_sparse is not None:
        result = _sparsify_result(result, codes_flat, ngroups, agg)

    groups = tuple(_index_values(g) for g in found_groups)
    return (result,) + groups


def _prefactorized_reduce(
    array: Any,
    pf: "fct.Prefactorized",
    *,
    func: str | Aggregation,
    expected_groups: Any,
    axis: Any,
    isbin: Any,
    fill_value: Any,
    dtype: Any,
    min_count: int | None,
    method: str | None,
    engine: str | None,
    reindex: Any,
    finalize_kwargs: dict | None,
    mesh: Any,
    axis_name: str,
) -> tuple:
    """The registry (serve) fast path: ``by`` arrived as a
    :class:`factorize.Prefactorized`, so codes, the expected-groups table,
    and the sort engine's present table were computed — and device-staged —
    at ``put_dataset`` time. This path never opens a ``factorize`` span,
    and with a device-resident ``array`` it dispatches with zero
    ``bytes.h2d`` (both codes and data pass ``utils.asarray_device``
    untouched).

    Options that would require re-deriving the factorization are rejected,
    not dropped — re-put the dataset to change the grouping.
    """
    bad = [
        name
        for name, val in (
            ("expected_groups", expected_groups),
            ("axis", axis),
            ("reindex", reindex),
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
    engine_explicit = engine is not None
    engine = _choose_engine(engine, array, array_is_jax)
    arr = array if array_is_jax else np.asarray(array)

    func_name = func if isinstance(func, str) else func.name
    arr_dtype = np.dtype(arr.dtype)
    if arr_dtype.kind in "OSU" or dtypes.is_datetime_like(arr_dtype):
        raise NotImplementedError(
            f"Prefactorized `by` supports numeric data; got dtype {arr_dtype} "
            "(datetime/object inputs keep the inline groupby_reduce path)"
        )
    bndim = len(pf.by_shape)
    if arr.ndim < bndim or tuple(arr.shape[arr.ndim - bndim:]) != tuple(pf.by_shape):
        raise ValueError(
            f"`array` with shape {arr.shape} does not align with the "
            f"prefactorized `by` shape {pf.by_shape}"
        )
    if arr_dtype.kind == "b" and func_name in ("sum", "nansum", "prod", "nanprod", "count"):
        arr = arr.astype(np.int64 if utils.x64_enabled() else np.int32)

    # -- min_count semantics: identical to the inline path ----------------
    if min_count is None:
        min_count_ = 0
        if fill_value is not None and func_name in ("nansum", "nanprod"):
            min_count_ = 1
    else:
        min_count_ = min_count
    agg = _initialize_aggregation(
        func, dtype, arr.dtype, fill_value, min_count_, finalize_kwargs
    )

    lead_shape = arr.shape[: arr.ndim - bndim]
    arr_flat = arr.reshape(lead_shape + (pf.n,))

    if method is None and mesh is not None:
        from .cohorts import chunks_from_shards, find_group_cohorts
        from .parallel.mapreduce import _norm_axes

        n_shards = int(np.prod([mesh.shape[a] for a in _norm_axes(axis_name, mesh)]))
        method, _ = find_group_cohorts(
            pf.codes, chunks_from_shards(pf.n, n_shards),
            expected_groups=range(pf.size),
        )
        logger.debug("prefactorized: auto-selected method=%s", method)

    if method is not None:
        # -- sharded SPMD reduction: put-staged device codes feed the mesh
        # program directly (cohorts keeps host codes — ownership detection
        # is host-side)
        from .parallel.mapreduce import sharded_groupby_reduce

        mesh_present = None
        size_run = pf.size
        if engine == "sort" and len(pf.present) < pf.size:
            mesh_present = pf.present
            size_run = pf.ncap
            _note_highcard(pf.size, pf.ncap, len(pf.present))
            codes_run = pf.ccodes if method == "cohorts" or pf.ccodes_dev is None else pf.ccodes_dev
        else:
            codes_run = pf.codes if method == "cohorts" or pf.codes_dev is None else pf.codes_dev
        with telemetry.span("combine", method=method, size=size_run):
            result = sharded_groupby_reduce(
                arr_flat, codes_run, agg, size=size_run, mesh=mesh,
                axis_name=axis_name, method=method, nat=False,
            )
        with telemetry.span("finalize"):
            result = _astype_final(result, agg, None)
            if mesh_present is not None:
                from .kernels import scatter_present_dense

                result = _redevice_scattered(
                    scatter_present_dense(np.asarray(result), mesh_present, pf.size),
                    array_is_jax,
                )
    else:
        # -- eager single-device reduction ---------------------------------
        if engine in ("jax", "sort"):
            engine = _route_highcard_prefactorized(
                engine, pf, arr_flat, lead_shape, agg, explicit=engine_explicit
            )
        if engine == "sort":
            _note_highcard(pf.size, pf.ncap, len(pf.present))
            ccodes = pf.ccodes_dev if pf.ccodes_dev is not None else pf.ccodes
            result_c = _reduce_blockwise(
                arr_flat, ccodes, agg, size=pf.ncap, engine="jax",
                prog_family="sort",
            )
            from .kernels import scatter_present_dense

            result = _redevice_scattered(
                scatter_present_dense(np.asarray(result_c), pf.present, pf.size),
                array_is_jax,
            )
        else:
            codes = pf.codes_dev if engine == "jax" and pf.codes_dev is not None else pf.codes
            result = _reduce_blockwise(arr_flat, codes, agg, size=pf.size, engine=engine)

    out_shape = lead_shape + pf.group_shape
    new_dims = agg.new_dims()
    if new_dims:
        out_shape = new_dims + out_shape
    result = result.reshape(out_shape)
    return (result,) + tuple(_index_values(g) for g in pf.found_groups)


def _route_highcard_prefactorized(engine, pf, arr_flat, lead_shape, agg, *,
                                  explicit: bool) -> str:
    """Dense-vs-sort routing off the put-time tables: the same decisions as
    :func:`_route_highcard`, with zero per-request hashing — ``present`` /
    ``ncap`` come off the :class:`factorize.Prefactorized` instead of the
    content-fingerprinted ``present_groups`` memo."""
    from .options import OPTIONS
    from .parallel.mapreduce import dense_intermediate_bytes

    lead_elems = int(np.prod(lead_shape)) if lead_shape else 1
    ceiling = OPTIONS["dense_intermediate_bytes_max"]
    est = dense_intermediate_bytes(lead_elems, pf.size, arr_flat.dtype, agg, ndev=1)
    over = est > ceiling
    if engine == "jax" and not over and (
        explicit or pf.size < OPTIONS["sort_engine_min_groups"]
    ):
        return "jax"
    if over:
        est_sort = dense_intermediate_bytes(lead_elems, pf.ncap, arr_flat.dtype, agg, ndev=1)
        if est_sort > ceiling or (engine == "jax" and explicit):
            from .utils import fmt_bytes

            raise ValueError(
                f"{agg.name!r} over {pf.size} groups needs ~{fmt_bytes(est)} "
                f"of dense (..., size) device intermediates, above the "
                f"{fmt_bytes(ceiling)} dense_intermediate_bytes_max ceiling. "
                "Options: pass mesh=; use engine='sort'; or raise "
                "set_options(dense_intermediate_bytes_max=...)."
            )
        telemetry.count("highcard.ceiling_routes")
        return "sort"
    if engine == "sort":
        return "sort"
    return "sort" if pf.ncap * _HIGHCARD_DENSITY_DEN <= pf.size else "jax"


def _sparsify_result(result, codes_flat, ngroups: int, agg: Aggregation):
    """SPARSE_COO result leg (parity: ReindexStrategy(array_type=SPARSE_COO),
    reference reindex.py:106-157 + core.py:527-586).

    The *compute* stays dense — static shapes are load-bearing for XLA — and
    the sparse container packages the host result, storing only the groups
    that actually occur in `by`. Occurrence is the UNION over kept rows: a
    group found in any kept row of a multi-row `by` is stored for every
    row (the container's columns are shared), so nnz can exceed what a
    strictly per-row sparse reindex (the reference's, which stores each
    block's own groups) would produce; for a single-row `by` the two agree.
    Returns a jax BCOO when the implicit fill is zero, HostCOO otherwise.
    """
    host = np.asarray(result)
    if host.dtype.kind in "mMOSU":
        raise NotImplementedError(
            f"SPARSE_COO reindex does not support results of dtype {host.dtype}"
        )
    # codes are offset by kept-row (row*ngroups + g, factorize.offset_labels)
    # when `by` has kept axes; fold back to group ids. A group is stored if
    # it occurs in ANY kept row — the container's columns are shared.
    valid = codes_flat[codes_flat >= 0]
    present = np.unique(valid % ngroups)
    from .reindex import reindex_sparse_coo

    return reindex_sparse_coo(
        host[..., present],
        pd.Index(present),
        pd.RangeIndex(ngroups),
        fill_value=agg.final_fill_value,
    )


def _index_values(idx: pd.Index):
    if isinstance(idx, pd.IntervalIndex):
        return idx
    return idx.values


def _redevice_scattered(result, array_is_jax: bool):
    """Keep the dense path's return-type contract after a host-side
    present-groups scatter: a device-array input yields a device-array
    result (one H2D put of the single dense result buffer — the output
    contract either way). Host inputs keep the host array. The put is
    skipped only when the dense result ALONE would breach the dense
    ceiling — there the routed run's alternative was an exception, and a
    host result is the usable degradation.
    """
    if not array_is_jax:
        return result
    from .options import OPTIONS  # function-local: follows a reloaded module

    if result.nbytes > OPTIONS["dense_intermediate_bytes_max"]:
        logger.debug(
            "highcard: dense result (%d bytes) above the ceiling; "
            "returning a host array", result.nbytes,
        )
        return result
    import jax

    return jax.device_put(result)


def _note_highcard(size: int, ncap: int, n_present: int) -> None:
    """Allocation accounting of the present-groups engine as telemetry
    gauges: the compact capacity actually accumulated over vs the dense
    universe it replaced. Exported on /metrics like every gauge; the CI
    highcard leg asserts "no dense (..., ngroups) allocation" through
    these plus the program-card memory numbers."""
    telemetry.count("highcard.sort_dispatches")
    if not telemetry.enabled():
        return
    telemetry.METRICS.set_gauge("highcard.acc_groups", float(ncap))
    telemetry.METRICS.set_gauge("highcard.present_groups", float(n_present))
    telemetry.METRICS.set_gauge(
        "highcard.dense_groups_avoided", float(max(0, size - ncap))
    )


#: density heuristic for the cold dense-vs-sort call: the sort engine's
#: overheads (one host unique pass, one compact relabel, the final dense
#: scatter) are worth paying once the dense accumulators outweigh the
#: compact ones ~8x — i.e. <= 1/8 of the universe is present. Autotuned
#: bands and the cost-model analytic prior refine this per platform.
_HIGHCARD_DENSITY_DEN = 8


def _route_highcard(engine, codes_flat, arr_flat, lead_shape, size, agg, *,
                    explicit: bool) -> str:
    """Dense-vs-sort routing for the eager device path.

    The hard ceiling first: a dense (..., size) intermediate estimate above
    ``dense_intermediate_bytes_max`` auto-routes heuristic-chosen engines to
    the sort (present-groups) engine — the huge-label-space guard that used
    to be a dead end now degrades to the engine built for that regime. An
    explicitly pinned ``engine="jax"`` still fails actionably (explicit
    choices are never second-guessed), with the sort engine named as the
    remedy. Below the ceiling, universes past ``sort_engine_min_groups``
    consult the "highcard" autotune family: measured ngroups/nelems bands
    outrank the cost-model analytic prior, which outranks the density
    heuristic (:data:`_HIGHCARD_DENSITY_DEN`).
    """
    # OPTIONS re-imported here, not the module-level binding: the option
    # suite reloads flox_tpu.options, and a function-local import follows
    # the live module (the old ceiling guard did the same)
    from .options import OPTIONS
    from .parallel.mapreduce import dense_intermediate_bytes

    lead_elems = int(np.prod(lead_shape)) if lead_shape else 1
    ceiling = OPTIONS["dense_intermediate_bytes_max"]
    est = dense_intermediate_bytes(lead_elems, size, arr_flat.dtype, agg, ndev=1)
    over = est > ceiling
    if engine == "jax" and not over and (
        explicit or size < OPTIONS["sort_engine_min_groups"]
    ):
        return "jax"  # the common case pays neither a unique pass nor routing
    from .kernels import present_cap, present_groups

    present = present_groups(codes_flat, size)  # memoized; the sort path reuses it
    ncap = present_cap(len(present), size)
    if over:
        est_sort = dense_intermediate_bytes(lead_elems, ncap, arr_flat.dtype, agg, ndev=1)
        if est_sort > ceiling or (engine == "jax" and explicit):
            from .utils import fmt_bytes

            sort_note = (
                f"even the sort engine's compact domain ({ncap} present-group "
                f"slots, ~{fmt_bytes(est_sort)}) exceeds the ceiling"
                if est_sort > ceiling
                else "engine='sort' (FLOX_TPU_DEFAULT_ENGINE=sort) reduces over "
                f"only the {len(present)} groups actually present"
            )
            raise ValueError(
                f"{agg.name!r} over {size} groups needs ~{fmt_bytes(est)} "
                f"of dense (..., size) device intermediates, above the "
                f"{fmt_bytes(ceiling)} dense_intermediate_bytes_max "
                f"ceiling; {sort_note}. Options: pass mesh= (map-reduce "
                "auto-routes to the blocked owner-by-owner program for "
                "additive reductions); reduce expected_groups; use "
                "engine='sort' or engine='numpy' on host data; or raise "
                "set_options(dense_intermediate_bytes_max=...) if the device "
                "really has the headroom."
            )
        if engine == "jax":
            logger.debug(
                "highcard: dense estimate over ceiling -> sort engine "
                "(size=%d present=%d)", size, len(present),
            )
            telemetry.count("highcard.ceiling_routes")
        return "sort"
    if engine == "sort":
        return "sort"
    nelems = int(np.prod(arr_flat.shape))
    heuristic = "sort" if ncap * _HIGHCARD_DENSITY_DEN <= size else "dense"
    chosen = heuristic
    if OPTIONS["autotune"]:
        from . import autotune

        autotune.prime_highcard(arr_flat.dtype, size, len(present), nelems)
        chosen = autotune.decide(
            "highcard", heuristic, ("dense", "sort"),
            dtype=str(arr_flat.dtype), ngroups=size, nelems=nelems,
        )
    if chosen != heuristic:
        logger.debug("highcard autotune: %s (heuristic %s)", chosen, heuristic)
    return "sort" if chosen == "sort" else "jax"


def _reduce_blockwise(arr_flat, codes_flat, agg: Aggregation, *, size, engine,
                      datetime_dtype=None, prog_family="bundle"):
    """Single-pass eager reduction + finalize (parity: core.py:478-524)."""
    numpy_funcs = list(agg.numpy)
    fills: list[Any] = [agg.final_fill_value] * len(numpy_funcs)
    kdtypes: list[Any] = [None] * len(numpy_funcs)
    base_kwargs = dict(agg.finalize_kwargs)
    if datetime_dtype is not None:
        base_kwargs["nat"] = True  # INT64_MIN is a missing marker, not a value
    kwargss: list[dict] = [dict(base_kwargs) for _ in numpy_funcs]

    if agg.min_count > 0:
        numpy_funcs.append("nanlen")
        fills.append(0)
        kdtypes.append(None)
        kwargss.append({"nat": True} if datetime_dtype is not None else {})

    # dtype request for the kernel: the final dtype for accumulating funcs.
    # Not on the datetime path — there the data was converted to float64
    # with NaT as NaN, and an int64 request would cast the NaNs to garbage
    # mid-reduction; the int64 view happens once, in _astype_final.
    if datetime_dtype is None:
        if not agg.preserves_dtype and agg.name in ("sum", "nansum", "prod", "nanprod"):
            kdtypes[0] = agg.final_dtype
        if agg.name in ("mean", "nanmean", "var", "nanvar", "std", "nanstd") and np.dtype(agg.final_dtype).kind == "f":
            kdtypes[0] = agg.final_dtype

    results = chunk_reduce(
        arr_flat,
        codes_flat,
        funcs=numpy_funcs,
        size=size,
        fill_values=fills,
        dtypes_=kdtypes,
        engine=engine,
        kwargss=kwargss,
        prog_family=prog_family,
    )

    # "combine" eagerly: fold the per-kernel intermediates into one result
    # (multi-stage finalize + the min_count mask) — the single-device
    # analogue of the mesh path's collective combine
    with telemetry.span("combine", nresults=len(results)):
        if agg.min_count > 0:
            counts = results[-1]
            results = results[:-1]
        else:
            counts = None

        if agg.finalize is not None and len(agg.numpy) > 1:
            # multi-stage custom Aggregation: the eager stages are intermediates
            # and finalize folds them (parity: _finalize_results, core.py:410-475).
            # Registry aggs use a single fused eager kernel, already final.
            result = agg.finalize(*results, **agg.finalize_kwargs)
        else:
            result = results[0]

        if counts is not None:
            result = _where(counts < agg.min_count, agg.final_fill_value, result)

    with telemetry.span("finalize"):
        result = _astype_final(result, agg, datetime_dtype)
    return result


def _span_block_rows(arr, nred_shape, agg: Aggregation) -> int:
    """Rows of the first label dim in each slab of a span-blocked reduction
    (:func:`_span_reduce`), or 0 where the span reduces whole: labels over
    one dim, an array within one slab (``utils.SPAN_BLOCK_BYTES``), or an
    aggregation with no chunk/combine split (order statistics)."""
    if len(nred_shape) < 2 or agg.blockwise_only:
        return 0
    row_bytes = int(np.prod(arr.shape)) * np.dtype(arr.dtype).itemsize // nred_shape[0]
    rows = utils.SPAN_BLOCK_BYTES // max(1, row_bytes)
    if rows >= nred_shape[0]:
        return 0
    # whole (8, 128) tiles when the cut dim is the array's second-minor one
    return rows // 8 * 8 if rows >= 8 else max(rows, 1)


#: the span-blocked programs, one per resolved aggregation, group count and
#: slab height (jax.jit keys each on its shapes)
_SPAN_PROGRAMS = LRUCache(maxsize=64)


def _span_reduce(arr, codes, agg: Aggregation, *, size: int, rows: int):
    """``agg`` of the device array ``arr`` over its trailing ``codes.ndim``
    dims, grouped by ``codes`` (shaped like those dims), in slabs of ``rows``
    rows of the first of them. Each slab is flattened on its own and reduced
    by the aggregation's chunk kernels to per-group intermediates, which
    fold into the running ones with its combine ops, as the streaming
    runtime folds host slabs; the finalized (..., size) result comes back.
    No program holds the whole array flattened."""
    from .options import trace_fingerprint
    from .parallel.mapreduce import _agg_cache_key

    nslabs = -(-codes.shape[0] // rows)
    telemetry.count("reduce.span_blocks", nslabs)
    key = (_agg_cache_key(agg), size, rows, trace_fingerprint())
    program = _SPAN_PROGRAMS.get(key)
    if program is None:
        program = _SPAN_PROGRAMS[key] = _span_program(agg, size=size, rows=rows)
    with telemetry.span("dispatch", engine="jax", size=size, span_blocks=nslabs):
        result = program(utils.asarray_device(codes), arr)
    with telemetry.span("finalize"):
        return _astype_final(result, agg)


def _span_program(agg: Aggregation, *, size: int, rows: int):
    """The jitted ``flox_spanblock(codes, arr)`` of :func:`_span_reduce`."""
    import jax

    from .parallel.mapreduce import _finalize_combined
    from .streaming import _init_state_like_merged, _merge_into, _slab_stats

    agg = agg.without_appended_count()  # every slab's stats carry the counts
    count_skipna = agg.name.startswith("nan") or agg.name == "count" or agg.min_count > 0

    def flox_spanblock(codes, arr):
        lead = arr.ndim - codes.ndim
        row = codes.size // codes.shape[0]  # span elements per row

        def stats(start, n):
            slab = jax.lax.dynamic_slice_in_dim(arr, start, n, axis=lead)
            slab = slab.reshape(slab.shape[:lead] + (n * row,))
            c = jax.lax.dynamic_slice_in_dim(codes, start, n, axis=0).reshape(-1)
            return _slab_stats(agg, slab, c, start * row, size=size, count_skipna=count_skipna, nat=False)

        def fold(state, start, n):
            return _merge_into(agg, state, *stats(start, n), nat=False)

        with jax.named_scope("flox.spanblock"):
            nfull, tail = divmod(codes.shape[0], rows)
            state = _init_state_like_merged(agg, *stats(0, rows), nat=False)
            state = jax.lax.fori_loop(1, nfull, lambda i, st: fold(st, i * rows, rows), state)
            if tail:
                state = fold(state, nfull * rows, tail)
        with jax.named_scope("flox.finalize"):
            inters, counts = state
            return _finalize_combined(agg, inters, counts)

    # the name is the program's in the profiler's trace
    return jax.jit(flox_spanblock)


def _where(cond, fill, x):
    if utils.is_jax_array(x):
        import jax.numpy as jnp

        cond = jnp.broadcast_to(jnp.asarray(cond), x.shape)
        inexact = jnp.issubdtype(x.dtype, jnp.floating) or jnp.issubdtype(
            x.dtype, jnp.complexfloating
        )
        if utils.is_nan_fill(fill) and not inexact:
            x = x.astype(jnp.float64 if utils.x64_enabled() else jnp.float32)
        return jnp.where(cond, jnp.asarray(fill).astype(x.dtype), x)
    cond = np.broadcast_to(np.asarray(cond), np.shape(x))
    xdt = np.asarray(x).dtype
    inexact = np.issubdtype(xdt, np.floating) or np.issubdtype(xdt, np.complexfloating)
    if utils.is_nan_fill(fill) and not inexact:
        x = np.asarray(x).astype(np.float64)
    return np.where(cond, fill, x)


# datetime reductions whose result is NOT a point in time: counts, bools,
# indices, and variance (units of ns²) stay numeric (the reference casts
# var/std back too, core.py:1205-1211 — a unit error this build corrects)
_DT_KEEP_NUMERIC = frozenset(
    {"count", "len", "any", "all", "var", "nanvar", "std", "nanstd"}
)


def _astype_final(result, agg: Aggregation, datetime_dtype=None):
    final = np.dtype(agg.final_dtype)
    if datetime_dtype is not None and agg.preserves_dtype:
        # values stayed int64 end-to-end; missing groups carry _NAT_INT == NaT
        res = np.asarray(result)
        if res.dtype.kind == "f":  # only via an explicit float user fill
            res = np.where(np.isnan(res), _NAT_INT, res)
        return res.astype("int64").view(datetime_dtype)
    if (
        datetime_dtype is not None
        and agg.name not in _DT_KEEP_NUMERIC
        and agg.reduction_type != "argreduce"
    ):
        # non-dtype-preserving timestamp results (mean/median/quantile/sum of
        # datetimes) round-trip back from float epoch values, NaN -> NaT
        # (parity: core.py:1205-1211)
        res = np.asarray(result)
        if res.dtype.kind == "f":
            nanmask = np.isnan(res)
            out = np.round(np.where(nanmask, 0.0, res)).astype("int64")
            out[nanmask] = _NAT_INT
        else:
            out = res.astype("int64")
        return out.view(datetime_dtype)
    if utils.is_jax_array(result):
        import jax.numpy as jnp

        if not utils.x64_enabled() and final.itemsize == 8 and final.kind in "fiu":
            final = np.dtype(final.kind + "4")
        if result.dtype != final:
            # don't downcast float results carrying NaN fills into ints
            if final.kind in "iu" and jnp.issubdtype(result.dtype, jnp.floating):
                if bool(jnp.isnan(result).any()):
                    return result
            result = result.astype(final)
        return result
    res = np.asarray(result)
    if res.dtype != final:
        if final.kind in "iub" and res.dtype.kind == "f" and np.isnan(res).any():
            return res  # promoted to hold missing values
        res = res.astype(final)
    return res


def _sparse_path(array, by, *, func, expected_groups, isbin, sort, fill_value, dtype, engine):
    """Route BCOO inputs to the sparse reducer (grouping over the last axis,
    1-D labels — the reference's aggregate_sparse scope)."""
    from .sparse import sparse_groupby_reduce

    if len(by) != 1:
        raise NotImplementedError("sparse inputs support a single 1-D `by`")
    if not isinstance(func, str):
        raise NotImplementedError("sparse inputs support named funcs only")
    bys = [utils.asarray_host(by[0])]
    if bys[0].ndim != 1 or bys[0].shape[0] != array.shape[-1]:
        raise ValueError("sparse inputs need a 1-D `by` matching the last axis")
    expected = _normalize_expected(expected_groups, 1)
    isbin_t = _normalize_isbin(isbin, 1)
    expected_idx = _convert_expected_groups_to_index(expected, isbin_t, sort)
    codes, found_groups, grp_shape, ngroups, size, props = fct.factorize_(
        bys, axes=(0,), expected_groups=expected_idx, sort=sort
    )
    result = sparse_groupby_reduce(
        array, np.asarray(codes).reshape(-1), func=func, size=size,
        fill_value=fill_value, dtype=dtype,
    )
    return (result,) + tuple(_index_values(g) for g in found_groups)
