"""Aggregation blueprints, registry, and engine dispatch (L2).

Parity target: /root/reference/flox/aggregations.py — the ``Aggregation``
declarative blueprint (aggregations.py:161-301), the ~30-entry registry
(881-922), ``generic_aggregate`` engine dispatch (60-133), the single-pass
variance machinery (348-526), scans (716-922) and
``_initialize_aggregation`` (925-1030).

TPU-first deltas:

* Combines are expressed as *collective-friendly* elementwise merge ops over
  dense, shape-static intermediates ("sum" → ``lax.psum``, "max" → ``pmax``,
  the variance triple → a two-phase psum Chan merge) rather than
  concatenate-then-regroup.
* ``reindex=True`` semantics are baked in: every intermediate is dense over
  ``expected_groups``, which is what XLA fusion and mesh collectives need.
"""

from __future__ import annotations

import copy
from dataclasses import dataclass, field
from typing import Any, Callable, Literal

import numpy as np

from . import dtypes, utils
from .multiarray import MultiArray

__all__ = [
    "Aggregation",
    "FusedAggregation",
    "Scan",
    "AGGREGATIONS",
    "SCANS",
    "FUSABLE_FUNCS",
    "generic_aggregate",
    "plan_fused",
    "fused_chunk_stats",
    "_initialize_aggregation",
    "_initialize_scan",
    "is_supported_aggregation",
]


def normalize_engine(engine: str) -> str:
    """Map engine names (including the reference's) to ours.

    The reference's ``engine="flox"`` is its native vectorised engine
    (reference aggregate_flox.py); ours is the jax/XLA engine, so the name
    aliases to ``"jax"``. ``"sort"`` is the present-groups engine
    (kernels.py sort section): the jax kernels run over the compact domain
    of groups actually present, the high-cardinality analogue of the
    reference's sort+``ufunc.reduceat`` engine. ``"numbagg"`` (reference
    aggregate_numbagg.py) has no analogue by design — every device path
    here is already JIT-compiled by XLA — so it raises with that
    explanation rather than "unknown".
    """
    if engine == "flox":
        return "jax"
    if engine == "numbagg":
        raise ValueError(
            "engine='numbagg' has no analogue in flox_tpu: numbagg exists to "
            "give the reference a JIT-compiled kernel path, and every device "
            "path here is already JIT-compiled by XLA. Use engine='jax' (the "
            "default; alias 'flox') or engine='numpy' (independent host "
            "engine). See docs/api.md, 'Engines'."
        )
    if engine not in ("jax", "numpy", "sort"):
        raise ValueError(
            f"Unknown engine {engine!r}; expected 'jax', 'numpy' or 'sort'."
        )
    return engine


def generic_aggregate(
    group_idx,
    array,
    *,
    engine: str,
    func: str | Callable,
    axis: int = -1,
    size: int,
    fill_value=None,
    dtype=None,
    **kwargs,
):
    """Engine dispatcher (parity: aggregations.py:60-133)."""
    engine = normalize_engine(engine)
    if callable(func):
        return func(
            group_idx, array, axis=axis, size=size, fill_value=fill_value, dtype=dtype, **kwargs
        )
    if engine == "jax":
        from . import kernels

        return kernels.generic_kernel(
            func, group_idx, array, axis=axis, size=size, fill_value=fill_value, dtype=dtype, **kwargs
        )
    if engine == "numpy":
        from . import engine_numpy

        return engine_numpy.generic_kernel(
            func, group_idx, array, axis=axis, size=size, fill_value=fill_value, dtype=dtype, **kwargs
        )
    if engine == "sort":
        from . import kernels

        return kernels.sort_kernel(
            func, group_idx, array, axis=axis, size=size, fill_value=fill_value, dtype=dtype, **kwargs
        )
    raise ValueError(f"Unknown engine {engine!r}; expected 'jax', 'numpy' or 'sort'.")


# ---------------------------------------------------------------------------
# Aggregation blueprint
# ---------------------------------------------------------------------------

# Combine ops understood by the tree/collective combiner. "sum"/"max"/"min"/
# "prod" merge dense intermediates elementwise; "var" is the Chan-style
# triple merge; "arg" merges (value, global-index) pairs; "first"/"last"
# merge (value, global-position) picking the extreme position.
T_Combine = Literal["sum", "max", "min", "prod", "var", "argmax", "argmin", "first", "last", "concat"]


@dataclass
class Aggregation:
    """Declarative recipe for one grouped reduction.

    Stages (parity with aggregations.py:161-301):

    * ``numpy``:   kernels for the single-device eager path (fused, direct).
    * ``chunk``:   kernels run per shard/block producing dense intermediates.
    * ``combine``: merge ops applied across shards/blocks (collectives).
    * ``finalize``: maps combined intermediates -> final result.

    ``numpy``/``chunk`` entries may be user callables with the engine plugin
    signature ``f(group_idx, array, *, axis, size, fill_value, dtype, **kw)``.
    ``combine`` entries may be user callables too: on the mesh the shards'
    dense intermediates are all-gathered and the callable folds the stack,
    ``op(stacked)`` with ``stacked`` shaped ``(n_shards, ..., size)`` ->
    ``(..., size)`` (the collective analogue of the reference's
    ``_grouped_combine``, dask.py:233-317).
    """

    name: str
    numpy: tuple[str | Callable, ...] = ()
    chunk: tuple[str | Callable, ...] | None = None
    combine: tuple[T_Combine, ...] | None = None
    finalize: Callable | None = None
    preprocess: Callable | None = None
    fill_value: dict[str, Any] = field(default_factory=dict)  # {"intermediate": (...), "numpy": (...)}
    final_fill_value: Any = dtypes.NA
    dtypes_: dict[str, Any] = field(default_factory=dict)
    final_dtype: Any = None
    reduction_type: Literal["reduce", "argreduce"] = "reduce"
    preserves_dtype: bool = False
    new_dims_func: Callable | None = None  # finalize_kwargs -> tuple of new dim sizes
    # resolved by _initialize_aggregation:
    finalize_kwargs: dict[str, Any] = field(default_factory=dict)
    min_count: int = 0
    appended_count: bool = False  # a trailing nanlen was added for min_count

    def __post_init__(self):
        if not self.numpy:
            self.numpy = (self.name,)
        if self.chunk is None and self.combine is None:
            # blockwise-only aggregation (median/quantile/mode/first/last on
            # float): must see all data for a group at once
            pass

    @property
    def blockwise_only(self) -> bool:
        return self.chunk is None

    def new_dims(self) -> tuple[int, ...]:
        if self.new_dims_func is None:
            return ()
        return self.new_dims_func(**self.finalize_kwargs)

    def without_appended_count(self) -> "Aggregation":
        """This aggregation without the ``nanlen`` leg that ``min_count``
        appended. The runtimes that carry counts of their own (the mesh
        programs, streaming, the span-blocked path, a fused plan) mask by
        those; left in, the leg reaches ``finalize`` as a stray positional
        argument (var's ddof became a count array)."""
        if not self.appended_count:
            return self
        agg = copy.deepcopy(self)
        agg.chunk = agg.chunk[:-1]
        agg.combine = agg.combine[:-1]
        agg.fill_value["intermediate"] = agg.fill_value["intermediate"][:-1]
        agg.appended_count = False
        return agg


# --- finalize helpers -------------------------------------------------------


def _is_jaxish(x) -> bool:
    import jax

    return isinstance(x, (jax.Array, jax.core.Tracer))


def _mean_finalize(total, count, **kw):
    import numpy as _np

    if _is_jaxish(total):
        return total / count
    with _np.errstate(invalid="ignore", divide="ignore"):
        return total / count


def _var_finalize(ma: MultiArray, ddof=0, **kw):
    m2, total, count = ma.arrays
    denom = count - ddof
    if _is_jaxish(m2):
        import jax.numpy as jnp

        out = m2 / jnp.where(denom > 0, denom, 1)
        return jnp.where(denom > 0, out, jnp.asarray(jnp.nan, out.dtype))
    import numpy as _np

    with _np.errstate(invalid="ignore", divide="ignore"):
        out = m2 / _np.where(denom > 0, denom, 1)
    return _np.where(denom > 0, out, _np.nan)


def _std_finalize(ma: MultiArray, ddof=0, **kw):
    out = _var_finalize(ma, ddof=ddof)
    if _is_jaxish(out):
        import jax.numpy as jnp

        return jnp.sqrt(out)
    return np.sqrt(out)


def _pick_second(a, b, **kw):
    return b


def _quantile_new_dims(q=0.5, **kw):
    return () if np.ndim(q) == 0 else (len(q),)


# --- registry ---------------------------------------------------------------


def _agg(name, **kw) -> Aggregation:
    return Aggregation(name, **kw)


AGGREGATIONS: dict[str, Aggregation] = {}


def _register(agg: Aggregation) -> None:
    AGGREGATIONS[agg.name] = agg


# counts
_register(_agg("count", numpy=("nanlen",), chunk=("nanlen",), combine=("sum",),
               fill_value={"intermediate": (0,), "numpy": (0,)}, final_fill_value=0,
               final_dtype=np.intp))

# sums / products
for nm, skipna in [("sum", False), ("nansum", True)]:
    _register(_agg(nm, chunk=(nm,), combine=("sum",),
                   fill_value={"intermediate": (0,), "numpy": (0,)}, final_fill_value=0))
for nm in ["prod", "nanprod"]:
    _register(_agg(nm, chunk=(nm,), combine=("prod",),
                   fill_value={"intermediate": (1,), "numpy": (1,)}, final_fill_value=1))

# mean family: chunk = (sum, count), combine = (sum, sum), finalize = divide
for nm, sum_k, len_k in [("mean", "sum", "len"), ("nanmean", "nansum", "nanlen")]:
    _register(_agg(nm, numpy=(nm,), chunk=(sum_k, len_k), combine=("sum", "sum"),
                   finalize=_mean_finalize,
                   fill_value={"intermediate": (0, 0), "numpy": (np.nan,)},
                   final_fill_value=dtypes.NA, final_dtype=None))

# var/std family: chunk = var_chunk triple, combine = Chan merge
for nm, skipna, fin in [("var", False, _var_finalize), ("nanvar", True, _var_finalize),
                        ("std", False, _std_finalize), ("nanstd", True, _std_finalize)]:
    _register(_agg(nm, numpy=(nm,),
                   chunk=(("var_chunk", {"skipna": skipna}),), combine=("var",), finalize=fin,
                   fill_value={"intermediate": (0,), "numpy": (np.nan,)},
                   final_fill_value=dtypes.NA))

# min/max
for nm, comb, sentinel in [("max", "max", dtypes.NINF), ("nanmax", "max", dtypes.NINF),
                           ("min", "min", dtypes.INF), ("nanmin", "min", dtypes.INF)]:
    _register(_agg(nm, chunk=(nm,), combine=(comb,),
                   fill_value={"intermediate": (sentinel,), "numpy": (dtypes.NA,)},
                   final_fill_value=dtypes.NA, preserves_dtype=True))

# bool reductions
_register(_agg("all", chunk=("all",), combine=("min",),
               fill_value={"intermediate": (True,), "numpy": (True,)}, final_fill_value=True,
               final_dtype=np.bool_))
_register(_agg("any", chunk=("any",), combine=("max",),
               fill_value={"intermediate": (False,), "numpy": (False,)}, final_fill_value=False,
               final_dtype=np.bool_))

# argreductions: eager path = direct kernel; chunked path pairs the extreme
# value with its global index (parity: aggregations.py:549-632)
for nm in ["argmax", "argmin", "nanargmax", "nanargmin"]:
    base = nm.removeprefix("nan")
    val_k = nm.replace("arg", "")  # max / nanmax / ...
    _register(_agg(nm, numpy=(nm,), chunk=(val_k, nm), combine=(base,),
                   finalize=_pick_second, reduction_type="argreduce",
                   fill_value={"intermediate": (dtypes.NINF if "max" in nm else dtypes.INF, -1),
                               "numpy": (-1,)},
                   final_fill_value=-1, final_dtype=np.intp))

# first/last: order-dependent; combine by tracking the global position
for nm, comb in [("first", "first"), ("last", "last"),
                 ("nanfirst", "first"), ("nanlast", "last")]:
    _register(_agg(nm, chunk=(nm,), combine=(comb,),
                   fill_value={"intermediate": (dtypes.NA,), "numpy": (dtypes.NA,)},
                   final_fill_value=dtypes.NA, preserves_dtype=True))

# order statistics: blockwise-only (chunk=None), like the reference
# (aggregations.py:672-712) — they need every element of a group at once.
for nm in ["median", "nanmedian"]:
    _register(_agg(nm, chunk=None, combine=None,
                   fill_value={"numpy": (dtypes.NA,)}, final_fill_value=dtypes.NA))
for nm in ["quantile", "nanquantile"]:
    _register(_agg(nm, chunk=None, combine=None,
                   fill_value={"numpy": (dtypes.NA,)}, final_fill_value=dtypes.NA,
                   new_dims_func=_quantile_new_dims))
for nm in ["mode", "nanmode"]:
    _register(_agg(nm, chunk=None, combine=None,
                   fill_value={"numpy": (dtypes.NA,)}, final_fill_value=dtypes.NA,
                   preserves_dtype=True))


def is_supported_aggregation(func: str) -> bool:
    """Public capability probe (parity: aggregations.py:1033-1054)."""
    return func in AGGREGATIONS


# ---------------------------------------------------------------------------
# initialization: resolve dtypes and fill values against the input array
# ---------------------------------------------------------------------------


def set_nat_final_fill(agg: "Aggregation", fill_value) -> None:
    """Dtype-preserving datetime reductions: the missing marker is NaT
    (INT64_MIN on the int64 view), never float NaN — float would corrupt
    ns-resolution timestamps; an explicit datetime/NaT fill is viewed to
    its int64 representation. ONE implementation shared by the eager core
    and the streaming runtime so the NaT discipline cannot drift."""
    if fill_value is None:
        agg.final_fill_value = np.iinfo(np.int64).min
    elif isinstance(agg.final_fill_value, (np.datetime64, np.timedelta64)):
        agg.final_fill_value = int(agg.final_fill_value.astype("int64"))
    agg.final_dtype = np.dtype("int64")


def shift_nat_identity_fills(agg: "Aggregation") -> None:
    """The NINF-resolved empty fill (iinfo.min) is byte-identical to the
    NaT marker; shift it so groups absent from a shard/slab are not
    mistaken for NaT-containing ones by marker re-injection. Shared by the
    mesh programs and the streaming runtime."""
    nat = np.iinfo(np.int64).min
    agg.fill_value["intermediate"] = tuple(
        (fv + 1 if isinstance(fv, (int, np.integer)) and fv == nat else fv)
        for fv in agg.fill_value.get("intermediate", ())
    )


def _initialize_aggregation(
    func: str | Aggregation,
    dtype,
    array_dtype,
    fill_value,
    min_count: int,
    finalize_kwargs: dict[str, Any] | None,
) -> Aggregation:
    """Resolve a registry template into a concrete plan
    (parity: aggregations.py:925-1030)."""
    if isinstance(func, FusedAggregation):
        # a fused plan is already fully resolved (per-statistic fills and
        # dtypes live in its member aggs); re-resolving would mangle it
        raise TypeError(
            "FusedAggregation plans run through groupby_aggregate_many / "
            "streaming_groupby_aggregate_many, not single-statistic entry "
            "points"
        )
    if isinstance(func, Aggregation):
        agg = copy.deepcopy(func)
    else:
        try:
            agg = copy.deepcopy(AGGREGATIONS[func])
        except KeyError:
            raise ValueError(f"Unsupported aggregation: {func!r}") from None

    array_dtype = np.dtype(array_dtype)
    agg.finalize_kwargs = dict(finalize_kwargs or {})
    agg.min_count = min_count

    # final dtype
    if agg.final_dtype is not None and dtype is None:
        final = np.dtype(agg.final_dtype)
    else:
        final = dtypes.normalize_dtype(
            dtype, array_dtype, preserves_dtype=agg.preserves_dtype, fill_value=fill_value
        )
        if not agg.preserves_dtype and agg.name not in ("sum", "nansum", "prod", "nanprod"):
            # mean/var/etc. of int data is float
            if agg.name not in ("count", "all", "any") and final.kind in "iub":
                final = np.result_type(final, np.float64 if utils.x64_enabled() else np.float32)
    agg.final_dtype = final

    # resolve final fill value; with min_count the default must be a missing
    # marker (NaN), not the reduction identity — that's the whole point of
    # min_count (parity: core.py:1026-1038 + aggregations.py:1005-1014)
    if fill_value is None:
        fill_value = dtypes.NA if min_count > 0 else agg.final_fill_value
    if fill_value in (dtypes.NA, dtypes.INF, dtypes.NINF):
        promoted, na = dtypes.maybe_promote(final)
        if fill_value is dtypes.NA:
            # only promote if some group can actually be missing; the caller
            # decides — record the NA-resolved value for use at finalize time
            fill_value = dtypes.get_fill_value(promoted, dtypes.NA)
        else:
            fill_value = dtypes.get_fill_value(final, fill_value)
    agg.final_fill_value = fill_value

    # resolve intermediate fills against the working dtype; argreductions'
    # first intermediate is the extreme VALUE (array dtype), not the index
    work_dtype = (
        array_dtype if (agg.preserves_dtype or agg.reduction_type == "argreduce") else final
    )
    inter = agg.fill_value.get("intermediate", ())
    agg.fill_value["intermediate"] = tuple(
        dtypes.get_fill_value(work_dtype, fv) if fv in (dtypes.NA, dtypes.INF, dtypes.NINF) else fv
        for fv in inter
    )

    # min_count: append a count intermediate so finalize can mask
    # (parity: aggregations.py:1005-1014)
    if min_count > 0 and agg.chunk is not None and "nanlen" not in _chunk_names(agg):
        agg.chunk = tuple(agg.chunk) + ("nanlen",)
        agg.combine = tuple(agg.combine) + ("sum",)
        agg.fill_value["intermediate"] = tuple(agg.fill_value["intermediate"]) + (0,)
        agg.appended_count = True

    return agg


def _chunk_names(agg: Aggregation) -> tuple[str, ...]:
    out = []
    for c in agg.chunk or ():
        if isinstance(c, tuple):
            out.append(c[0])
        elif isinstance(c, str):
            out.append(c)
    return tuple(out)


# ---------------------------------------------------------------------------
# multi-statistic fusion: one chunk pass serving N requested statistics
# ---------------------------------------------------------------------------

#: statistics the fusion planner can merge into one multi-output chunk plan:
#: everything whose chunk intermediates merge with the elementwise/Chan
#: combines. Argreductions and first/last carry position channels with
#: order-dependent merges, and order statistics are multi-pass — they stay
#: on the sequential path.
FUSABLE_FUNCS = frozenset(
    {
        "sum", "nansum", "prod", "nanprod", "count",
        "min", "nanmin", "max", "nanmax",
        "mean", "nanmean", "var", "nanvar", "std", "nanstd",
        "all", "any",
    }
)

_SKIPNA_FUNCS = frozenset(
    {"nansum", "nanprod", "count", "nanmin", "nanmax", "nanmean",
     "nanvar", "nanstd"}
)


@dataclass
class FusedAggregation(Aggregation):
    """A multi-output aggregation: one deduplicated chunk plan serving N
    requested statistics.

    The planner (:func:`plan_fused`) merges the requested ``Aggregation``
    blueprints: identical chunk kernels collapse to one leg (mean's
    sum+count, min_count's nanlen, every presence count), and when a
    var-family statistic is requested its Chan triple's (total, count)
    leaves serve mean directly — the data is touched once for the whole
    statistic set. ``chunk`` / ``combine`` / ``fill_value`` hold the
    deduplicated legs in the exact layout the generic runtimes consume
    (``_local_chunk`` iteration, ``_combine_intermediates`` psum/pmax/Chan
    merges, the streaming ``_merge_into`` carry), so ONE mesh program /
    ONE streaming carry covers all N statistics. ``slots`` maps each
    statistic to its legs; :meth:`finalize_fused` folds the combined legs
    into the per-statistic results.
    """

    #: resolved per-statistic blueprints, request order
    aggs: tuple = ()
    #: requested names, request order (the output dict keys)
    funcs: tuple = ()
    #: per-statistic addressing into the deduplicated legs (see plan_fused)
    slots: tuple = ()
    #: per-leg eager-path dtype requests (None on the mesh/streaming paths,
    #: which never request dtypes — mirroring _local_chunk vs chunk_reduce)
    eager_dtypes: tuple = ()

    def finalize_fused(self, inters, counts=None):
        """Combined legs -> tuple of finalized per-statistic results.

        ``counts`` (the runtimes' generic count channel) is ignored: every
        statistic reads its OWN presence leg, because skipna and
        propagating statistics disagree about what "empty" means. Works on
        jax arrays (traced — the eager program and the mesh programs call
        it in-jit) and on host numpy (the numpy engine).
        """
        results = []
        for agg, slot in zip(self.aggs, self.slots):
            results.append(_finalize_slot(agg, slot, inters, self.min_count))
        return tuple(results)


def _read_leg(inters, addr):
    """Resolve a leg address: an int (whole leg) or (leg, leaf) into a
    MultiArray leg (the var triple's total/count leaves)."""
    if isinstance(addr, tuple):
        leg, leaf = addr
        return inters[leg].arrays[leaf]
    return inters[addr]


def _xp_for(x):
    if _is_jaxish(x):
        import jax.numpy as jnp

        return jnp
    return np


def _masked_fill(result, empty, fill_value):
    """Apply a final fill where ``empty`` — THE final-fill promotion rules
    (NaN fills promote int results to float, identity fills cast to the
    result dtype, complex counts as inexact), dual-mode jax/numpy. The
    single implementation behind the fused finalize AND the mesh programs'
    ``_apply_final_fill`` (parallel/mapreduce.py), so fused/sequential
    parity cannot drift."""
    if fill_value is None:
        return result
    xp = _xp_for(result)
    try:
        fill_is_nan = bool(np.isnan(fill_value))
    except (TypeError, ValueError):
        fill_is_nan = False
    fv = xp.asarray(fill_value)
    res_inexact = xp.issubdtype(result.dtype, xp.floating) or xp.issubdtype(
        result.dtype, xp.complexfloating
    )
    if xp.issubdtype(fv.dtype, xp.floating) and not res_inexact:
        if fill_is_nan:
            promoted = (
                xp.float64 if (xp is np or utils.x64_enabled()) else xp.float32
            )
            result = result.astype(promoted)
        else:
            fv = fv.astype(result.dtype)
    empty_b = xp.broadcast_to(xp.asarray(empty), result.shape)
    return xp.where(empty_b, fv.astype(result.dtype), result)


def _finalize_slot(agg: Aggregation, slot: dict, inters, min_count: int):
    """One statistic's result from the combined legs."""
    kind = slot["kind"]
    if kind == "var":
        ma = inters[slot["leg"]]
        fin = _std_finalize if slot["std"] else _var_finalize
        out = fin(ma, **agg.finalize_kwargs)
        present = ma.arrays[2] > 0
    elif kind == "mean":
        total = _read_leg(inters, slot["sum"])
        cnt = _read_leg(inters, slot["count"])
        cntf = cnt.astype(total.dtype) if cnt.dtype != total.dtype else cnt
        if _is_jaxish(total):
            out = total / cntf
        else:
            with np.errstate(invalid="ignore", divide="ignore"):
                out = total / cntf
        present = cnt > 0
    elif kind == "count":
        out = inters[slot["leg"]]
        present = out > 0
    else:  # "direct": sum/prod/min/max/all/any — the leg IS the value
        out = inters[slot["leg"]]
        present = _read_leg(inters, slot["present"]) > 0
    xp = _xp_for(out)
    out = _masked_fill(out, ~xp.asarray(present), agg.final_fill_value)
    if min_count > 0:
        nn = inters[slot["nanlen"]]
        out = _masked_fill(out, nn < min_count, agg.final_fill_value)
    return out


def plan_fused(
    funcs,
    dtype,
    array_dtype,
    fill_value,
    min_count: int,
    finalize_kwargs,
) -> FusedAggregation:
    """The fusion planner: merge N statistic blueprints into one
    multi-output chunk plan (the generalization of the reference's
    mean = sum+count single-pass blueprint, aggregations.py:161, to an
    arbitrary statistic set).

    ``funcs``: statistic names (see :data:`FUSABLE_FUNCS`). ``fill_value``
    and ``finalize_kwargs`` may be per-statistic dicts (``{"var": ...}``)
    or a single value applied to all. Deduplication: identical chunk legs
    collapse; when a var-family statistic shares its skipna mode with
    mean, mean reads the Chan triple's (total, count) leaves instead of
    adding legs — min/max ride free next to them.
    """
    funcs = tuple(funcs)
    if len(funcs) == 0:
        raise ValueError("groupby_aggregate_many needs at least one func")
    if len(set(funcs)) != len(funcs):
        raise ValueError(f"duplicate funcs in {funcs!r}")
    bad = [f for f in funcs if not isinstance(f, str) or f not in FUSABLE_FUNCS]
    if bad:
        raise NotImplementedError(
            f"cannot fuse {bad!r}: fusable statistics are "
            f"{sorted(FUSABLE_FUNCS)} (argreductions, first/last and order "
            "statistics keep their sequential paths)"
        )

    def per_func(v, f):
        if isinstance(v, dict):
            return v.get(f)
        return v

    aggs = []
    for f in funcs:
        agg = _initialize_aggregation(
            f, per_func(dtype, f), array_dtype, per_func(fill_value, f),
            min_count, per_func(finalize_kwargs, f) or {},
        )
        # the fused plan carries ONE shared nanlen leg for min_count; the
        # per-agg appended count would otherwise mask the combine signature
        # (var's ("var",) becomes ("var", "sum")) and misclassify the Chan
        # triple below
        aggs.append(agg.without_appended_count())
    aggs = tuple(aggs)

    legs: list[dict] = []  # {"entry", "combine", "fill", "eager_dtype"}
    index: dict[tuple, int] = {}

    def add_leg(entry, combine, fill, eager_dtype=None):
        if isinstance(entry, tuple):
            name, kw = entry[0], tuple(sorted(dict(entry[1]).items()))
        else:
            name, kw = entry, ()
        key = (
            name, kw, repr(fill),
            None if eager_dtype is None else np.dtype(eager_dtype).name,
        )
        if key in index:
            return index[key]
        index[key] = len(legs)
        legs.append(
            {"entry": entry, "combine": combine, "fill": fill,
             "eager_dtype": eager_dtype}
        )
        return index[key]

    # pass 1: var-family triples first, so mean can alias into them
    var_leg: dict[bool, int] = {}  # skipna -> leg index
    for f, agg in zip(funcs, aggs):
        if agg.combine == ("var",):
            skipna = f in _SKIPNA_FUNCS
            var_leg.setdefault(
                skipna,
                add_leg(("var_chunk", {"skipna": skipna}), "var",
                        agg.fill_value["intermediate"][0]),
            )

    nanlen_leg = add_leg("nanlen", "sum", 0) if min_count > 0 else None

    slots: list[dict] = []
    for f, agg in zip(funcs, aggs):
        skipna = f in _SKIPNA_FUNCS
        # presence ("no fill needed") semantics per statistic: nanmin/nanmax
        # of an all-NaN group is missing (nanlen), but nansum/nanprod of one
        # is the identity — numpy semantics: only zero-TOTAL-element groups
        # take the fill there (kernels._make_addlike's comment)
        presence_entry = "nanlen" if f in ("nanmin", "nanmax") else "len"
        if agg.combine == ("var",):
            slot = {
                "kind": "var", "leg": var_leg[skipna],
                "std": f in ("std", "nanstd"),
            }
        elif f in ("mean", "nanmean"):
            if skipna in var_leg:
                # sum/count feed mean AND var: read the Chan triple's
                # (total, count) leaves — zero extra legs
                tleg = var_leg[skipna]
                slot = {
                    "kind": "mean",
                    "sum": (tleg, 1), "count": (tleg, 2),
                    "present": (tleg, 2),
                }
            else:
                sum_k, len_k = agg.chunk[0], agg.chunk[1]
                # the float work dtype, so int inputs promote exactly as
                # the direct eager mean kernel does
                s = add_leg(sum_k, "sum", 0, eager_dtype=agg.final_dtype)
                c = add_leg(len_k, "sum", 0)
                slot = {"kind": "mean", "sum": s, "count": c, "present": c}
        elif f == "count":
            leg = add_leg("nanlen", "sum", 0)
            slot = {"kind": "count", "leg": leg}
        else:
            entry = agg.chunk[0]
            fill = agg.fill_value["intermediate"][0]
            edt = None
            if f in ("sum", "nansum", "prod", "nanprod") and not agg.preserves_dtype:
                edt = agg.final_dtype  # chunk_reduce's kdtypes[0] rule
            leg = add_leg(entry, agg.combine[0], fill, eager_dtype=edt)
            p = add_leg(presence_entry, "sum", 0)
            slot = {"kind": "direct", "leg": leg, "present": p}
        if min_count > 0:
            slot["nanlen"] = nanlen_leg
        slots.append(slot)

    fused = FusedAggregation(
        name="fused[" + "+".join(funcs) + "]",
        numpy=funcs,
        chunk=tuple(leg["entry"] for leg in legs),
        combine=tuple(leg["combine"] for leg in legs),
        fill_value={"intermediate": tuple(leg["fill"] for leg in legs)},
        final_fill_value=0,
        min_count=min_count,
        aggs=aggs,
        funcs=funcs,
        slots=tuple(slots),
        eager_dtypes=tuple(leg["eager_dtype"] for leg in legs),
    )
    return fused


def fused_chunk_stats(
    agg: FusedAggregation, group_idx, array, *, size: int, engine: str = "jax",
    eager: bool = False,
):
    """Run the fused chunk plan: one intermediate per leg.

    The jax-engine path routes the megakernel-eligible legs (sums, counts,
    min/max over the same float data) through
    ``kernels.fused_segment_stats`` — ONE Pallas pass with every
    accumulator resident in VMEM — and falls back to per-leg XLA
    ``segment_*`` kernels otherwise (still one jitted program, fused by
    XLA). ``eager=True`` applies the per-leg dtype requests the eager
    bundle makes (mesh/streaming never request dtypes — parity with
    ``_local_chunk``)."""
    from . import kernels

    names = [leg[0] if isinstance(leg, tuple) else leg for leg in agg.chunk]
    # resolved BEFORE any array-derived name exists: only dtype NAMES are
    # compared below, so no traced value ever reaches a numpy call
    # (FLX011-clean — .dtype is a host attribute on tracers too)
    req_names = [
        None if _rd is None else np.dtype(_rd).name for _rd in agg.eager_dtypes
    ]
    dtype_name = str(array.dtype)

    mega: dict[int, Any] = {}
    if engine == "jax":
        mega_mask = [
            n in ("sum", "nansum", "min", "nanmin", "max", "nanmax",
                  "len", "nanlen")
            # a pending dtype-request cast would change what the one-pass
            # kernel sums; only no-op requests may ride it
            and (not eager or req_names[i] is None or req_names[i] == dtype_name)
            for i, n in enumerate(names)
        ]
        wanted = tuple(dict.fromkeys(
            names[i] for i, ok in enumerate(mega_mask) if ok
        ))
        if len(wanted) >= 2:
            got = kernels.fused_segment_stats(
                group_idx, array, size=size, want=wanted
            )
            if got is not None:
                for i, ok in enumerate(mega_mask):
                    if ok and names[i] in got:
                        mega[i] = got[names[i]]

    inters = []
    for i, (entry, fv) in enumerate(zip(agg.chunk, agg.fill_value["intermediate"])):
        if i in mega:
            inters.append(mega[i])
            continue
        if isinstance(entry, tuple):
            name, extra = entry[0], dict(entry[1])
        else:
            name, extra = entry, {}
        dt = agg.eager_dtypes[i] if eager else None
        if engine == "jax" and not eager and name in (
            "sum", "nansum", "prod", "nanprod"
        ):
            # bf16/f16 intermediates travel and merge in the f32
            # accumulator (parity: _local_chunk's keep_acc)
            extra["keep_acc"] = True
        inters.append(
            generic_aggregate(
                group_idx, array, engine=engine, func=name, size=size,
                fill_value=fv, dtype=dt, **extra,
            )
        )
    return inters


# ---------------------------------------------------------------------------
# scans (parity: aggregations.py:716-922)
# ---------------------------------------------------------------------------


@dataclass
class Scan:
    """Blueprint for a grouped scan.

    * ``scan``: the within-block grouped scan kernel.
    * ``reduction``: per-block per-group summary carried across blocks
      (cumsum -> "sum" of the block; ffill -> "nanlast" value).
    * ``binary_op``: how an incoming carry combines with block values.
    * ``identity``: carry for groups not yet seen.
    """

    name: str
    scan: str
    reduction: str
    binary_op: Callable | None
    identity: Any
    # "apply_binary_op": add carry to scanned block; "ffill": where-NaN fill
    mode: Literal["apply_binary_op", "ffill"] = "apply_binary_op"
    preserves_dtype: bool = False


SCANS: dict[str, Scan] = {
    "cumsum": Scan("cumsum", scan="cumsum", reduction="sum", binary_op=None, identity=0),
    "nancumsum": Scan("nancumsum", scan="nancumsum", reduction="nansum", binary_op=None, identity=0),
    "ffill": Scan("ffill", scan="ffill", reduction="nanlast", binary_op=None, identity=np.nan,
                  mode="ffill", preserves_dtype=True),
    "bfill": Scan("bfill", scan="bfill", reduction="nanfirst", binary_op=None, identity=np.nan,
                  mode="ffill", preserves_dtype=True),
}


def _initialize_scan(func: str | Scan) -> Scan:
    if isinstance(func, Scan):
        return copy.deepcopy(func)
    try:
        return copy.deepcopy(SCANS[func])
    except KeyError:
        raise ValueError(f"Unsupported scan: {func!r}") from None
