"""Out-of-core grouped reductions and scans: stream host slabs through
device accumulators (L5).

The reference handles bigger-than-memory arrays by delegating to a chunked
runtime (dask: /root/reference/flox/dask.py:325-573; cubed:
cubed.py:30-162) whose workers each hold one chunk. On a TPU host the
equivalent capability is *streaming*: the array lives in host RAM (or
behind a loader callable — zarr, memmap, a file reader), slabs of the
reduced axis are `device_put` one at a time, and dense per-group
intermediates accumulate **on device** via the same pairwise merges the
mesh runtime applies collectively. HBM holds one slab + the (…, size)
accumulators — never the array.

Design notes (TPU-first):

* The per-slab step is ONE jitted function (chunk kernels + merge fused);
  slabs all share a static shape (the tail slab is padded with ``-1``
  codes), so it compiles once.
* Staging is pipelined (flox_tpu/pipeline.py): a bounded prefetch pool
  loads, pads, and ``device_put``\\ s slab ``i+k`` while the device reduces
  slab ``i`` — jax's async dispatch alone hides only *compute* behind the
  inline staging, not the load+stage wall itself. All three entry points
  (reduce, scan, quantile) iterate the same :func:`pipeline.stream_slabs`
  source, single-device and mesh alike; ``OPTIONS["stream_prefetch"]=0``
  restores the synchronous inline loop (bit-identical results either way).
* The jitted steps donate their carry (``pipeline.maybe_donate``) so the
  dense ``(…, size)`` accumulators update in place across slabs, and a
  dispatch throttle (``OPTIONS["stream_dispatch_depth"]``) syncs the carry
  every K steps so in-flight slabs cannot pile up unboundedly in HBM.
* The pairwise variance merge is the reference's ``_var_combine``
  (aggregations.py:392-451) — the Chan update, applied slab-by-slab.
"""

from __future__ import annotations

import logging
import math
from typing import Any, Callable

import numpy as np

from . import cache, factorize as fct, utils
from .aggregations import Aggregation, _initialize_aggregation
from .multiarray import MultiArray

logger = logging.getLogger("flox_tpu.streaming")

__all__ = [
    "streaming_groupby_reduce",
    "streaming_groupby_scan",
    "streaming_groupby_aggregate_many",
]

_BIG = np.iinfo(np.int32).max

#: slab byte budget when the caller passes neither batch_len nor
#: batch_bytes — the only sizing leg the autotuner may adapt (an explicit
#: batch_bytes= is a device-memory cap the tuner never second-guesses)
_DEFAULT_BATCH_BYTES = 256 * 2**20

# compiled step/pass/program functions for every streaming runtime path
# (single-device steps, quantile passes, scan steps, mesh shard_map
# pairs) — a fresh jax.jit object per call would recompile on every
# streaming_groupby_* invocation, so repeat same-shaped calls
# (per-variable pipelines) would pay full retrace. Keys carry the
# semantic identity plus trace_fingerprint() (appended by _step_cached).
# LRU-bounded: a cold key past capacity evicts the single stalest step
# (counted in cache.stats()["evictions"]), never the whole hot set — the
# old wholesale clear-at-256 dropped every hot program under sustained
# mixed-key traffic, exactly the serving workload's shape.
_STEP_CACHE: cache.LRUCache = cache.LRUCache(maxsize=256)


def _mesh_stream_layout(mesh, axis_name, batch_len: int, lead_ndim: int):
    """The ONE place the slab sharding layout is decided: device_put
    shardings and shard_map in_specs must stay byte-identical, and
    batch_len must divide into equal shards — both runtimes (reduce and
    quantile) read this."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .parallel.mapreduce import _norm_axes

    axes = _norm_axes(axis_name, mesh)
    ndev = int(np.prod([mesh.shape[a] for a in axes]))
    batch_len = -(-batch_len // ndev) * ndev  # shards must be equal
    spec_entry = axes if len(axes) > 1 else axes[0]
    sspec = P(*([None] * lead_ndim + [spec_entry]))
    cspec = P(spec_entry)
    return (
        axes, ndev, batch_len, spec_entry, sspec, cspec,
        NamedSharding(mesh, sspec), NamedSharding(mesh, cspec),
    )


def _step_cached(key, build):
    from . import telemetry
    from .options import trace_fingerprint

    key = key + (trace_fingerprint(),)
    fn = _STEP_CACHE.get(key)
    if fn is None:
        telemetry.count("cache.step_misses")
        fn = build()
        # bounded LRU insert: past capacity this evicts ONE stale step
        _STEP_CACHE[key] = fn
    else:
        telemetry.count("cache.step_hits")
    return fn


def streaming_groupby_reduce(
    array: Any,
    by: Any,
    *,
    func: str | Aggregation,
    batch_len: int | None = None,
    batch_bytes: int | None = None,
    expected_groups: Any = None,
    isbin: Any = False,
    sort: bool = True,
    axis: Any = None,
    fill_value: Any = None,
    dtype: Any = None,
    min_count: int | None = None,
    finalize_kwargs: dict | None = None,
    mesh: Any = None,
    axis_name: str | tuple[str, ...] = "data",
    engine: str | None = None,
) -> tuple:
    """Grouped reduction streaming slabs to device.

    ``array``: a host array ``(..., *by.shape)`` **or** a loader
    ``callable(start, stop) -> np.ndarray`` returning ``(..., stop-start)``
    slabs (zarr/memmap-style); with a loader, pass 1-D full-axis labels in
    ``by`` — its length defines ``N``. Returns ``(result, groups)`` exactly
    like :func:`flox_tpu.groupby_reduce`.

    nD ``by`` and partial-axis reductions (``axis=`` a subset of the
    by-span, exactly as in ``groupby_reduce``) are supported for host
    arrays: kept dims fold into disjoint per-row code ranges (the same
    flatten ``core.groupby_reduce`` uses), so the stream still walks one
    flat trailing axis. Loaders define a 1-D axis contract, so they keep
    1-D ``by`` / ``axis=None``.

    Supported: every aggregation with a chunk stage, PLUS exact
    quantile/median — the radix-select bisection consumes only per-group
    counts, which accumulate slab by slab, so order statistics stream in
    ``nbits + 1`` full passes over the loader (33 for f32; an explicit,
    documented IO trade — see :func:`_stream_quantile`). ``mode`` cannot
    stream (run-length structure needs contiguous sorted groups); use the
    mesh blockwise method for it.

    ``mesh=`` composes streaming with the sharded runtime (the
    chunked-runtime × scheduler composition the reference gets from dask,
    /root/reference/flox/dask.py:325-573): every slab is ``device_put``
    sharded over the mesh's ``axis_name`` axes, each device folds its
    shard into its OWN accumulator (zero collectives while streaming —
    jax's async dispatch overlaps host loads with device reduction on all
    chips), and ONE collective combine at the end applies the same
    psum / pmax / two-psum Chan merges the mesh map-reduce program uses.
    Bigger-than-host+HBM arrays therefore stream onto N chips at N× the
    slab bandwidth. Above ``dense_intermediate_bytes_max``, additive
    reductions switch to the blocked owner-by-owner form: per-device
    accumulators are ``(…, size/ndev)`` from the start, so group spaces
    beyond any single device's ceiling stream too (see
    docs/distributed.md).
    """
    from . import telemetry

    if isinstance(func, (tuple, list)):
        # the fused multi-statistic routing lives in the impl, but the
        # single-statistic API boundary must fail loudly, not silently
        # change its (array, groups) return contract to (dict, groups)
        raise TypeError(
            "streaming_groupby_reduce takes one func; pass statistic sets "
            "to streaming_groupby_aggregate_many"
        )
    with telemetry.span(
        "streaming_groupby_reduce",
        func=func if isinstance(func, str) else getattr(func, "name", "custom"),
        mesh=mesh is not None,
    ):
        return _streaming_groupby_reduce_impl(
            array, by, func=func, batch_len=batch_len, batch_bytes=batch_bytes,
            expected_groups=expected_groups, isbin=isbin, sort=sort, axis=axis,
            fill_value=fill_value, dtype=dtype, min_count=min_count,
            finalize_kwargs=finalize_kwargs, mesh=mesh, axis_name=axis_name,
            engine=engine,
        )


def _streaming_groupby_reduce_impl(
    array: Any,
    by: Any,
    *,
    func: str | Aggregation,
    batch_len: int | None,
    batch_bytes: int | None,
    expected_groups: Any,
    isbin: Any,
    sort: bool,
    axis: Any,
    fill_value: Any,
    dtype: Any,
    min_count: int | None,
    finalize_kwargs: dict | None,
    mesh: Any,
    axis_name: str | tuple[str, ...],
    engine: str | None = None,
) -> tuple:
    """The :func:`streaming_groupby_reduce` body, under the public
    wrapper's root telemetry span (per-pass ``stream[...]`` spans come from
    ``pipeline.stream_slabs``; defaults live only on the wrapper)."""
    from . import dtypes as dtps

    labels = utils.asarray_host(by)
    keep_by_shape: tuple = ()

    loader: Callable[[int, int], Any]
    if callable(array):
        if labels.ndim != 1:
            raise NotImplementedError(
                "loader inputs define a 1-D (start, stop) axis contract: "
                "pass 1-D labels (pre-flatten nD layouts host-side)"
            )
        if axis is not None:
            raise NotImplementedError("axis= needs a host array, not a loader")
        loader = array
        lead_shape = None  # discovered from the first slab
        bys = [labels]
        red_axes = (0,)
    else:
        arr = np.asarray(array) if not utils.is_jax_array(array) else array
        bndim = labels.ndim
        if arr.shape[arr.ndim - bndim:] != labels.shape:
            raise ValueError(
                f"array trailing dims {arr.shape[arr.ndim - bndim:]} != "
                f"by shape {labels.shape}"
            )
        # -- axis normalization: reduced by-dims must trail — the SAME
        # helper core.groupby_reduce uses, so the flatten contracts cannot
        # drift apart (kept dims fold into disjoint per-row code ranges and
        # the stream walks ONE flat axis)
        from .core import _normalize_reduce_axes

        arr, (labels,), n_keep, bndim = _normalize_reduce_axes(arr, [labels], axis)
        keep_by_shape = labels.shape[:n_keep]
        lead_shape = arr.shape[: arr.ndim - bndim]
        span = int(np.prod(labels.shape)) if labels.size else 0
        arr = arr.reshape(lead_shape + (span,))
        loader = lambda s, e: arr[..., s:e]
        bys = [labels]
        red_axes = tuple(range(n_keep, bndim))
    n = int(np.prod(bys[0].shape))

    # -- host factorize over the full label span (cheap: labels only) ------
    from .core import _convert_expected_groups_to_index, _normalize_expected, _normalize_isbin

    expected = _normalize_expected(expected_groups, 1)
    expected_idx = _convert_expected_groups_to_index(expected, _normalize_isbin(isbin, 1), sort)
    from . import telemetry

    with telemetry.span("factorize") as _fsp:
        codes, found_groups, grp_shape, ngroups, size, props = fct.factorize_(
            bys, axes=red_axes, expected_groups=expected_idx, sort=sort
        )
        _fsp.set(ngroups=ngroups, size=size)
    # ONE contiguous int32 copy for the whole stream: per-slab slices are
    # then zero-copy contiguous views, so the loop (and the prefetch
    # workers) never re-copy or re-cast codes per slab
    codes = np.ascontiguousarray(np.asarray(codes).reshape(-1), dtype=np.int32)
    if size == 0:
        raise ValueError("No groups to reduce over (empty expected_groups?)")

    probe = np.asarray(loader(0, 1))  # one probe: dtype AND lead shape
    datetime_dtype = probe.dtype if dtps.is_datetime_like(probe.dtype) else None
    nat = False
    if datetime_dtype is not None and not utils.x64_enabled():
        raise ValueError(
            "datetime/timedelta streaming needs jax_enable_x64 (int64 NaT "
            "sentinels do not survive the int32 downcast)."
        )
    fused_funcs = tuple(func) if isinstance(func, (tuple, list)) else None
    if fused_funcs is not None:
        # multi-statistic fusion: ONE streaming pass (one carry, one step
        # program, one checkpoint identity) serves the whole statistic set
        if datetime_dtype is not None:
            raise NotImplementedError(
                "fused multi-statistic streaming supports numeric data; "
                "stream datetime reductions one func at a time"
            )
        from .aggregations import plan_fused

        agg = plan_fused(
            fused_funcs, dtype, probe.dtype, fill_value,
            0 if min_count is None else min_count, finalize_kwargs,
        )
    else:
        agg = _initialize_aggregation(
            func, dtype,
            probe.dtype if datetime_dtype is None else np.dtype("int64"),
            fill_value, 0 if min_count is None else min_count, finalize_kwargs,
        )
        if agg.appended_count:
            # the streaming runtime computes counts itself (count_skipna
            # channel + _apply_final_fill threshold); the appended nanlen
            # would otherwise leak into agg.finalize as a stray positional
            # arg — var's ddof became a count array, poisoning every group
            # (the same strip sharded_groupby_reduce applies)
            agg.chunk = agg.chunk[:-1]
            agg.combine = agg.combine[:-1]
            agg.fill_value["intermediate"] = agg.fill_value["intermediate"][:-1]
            agg.appended_count = False
    if datetime_dtype is not None:
        # same dtype round-trips as core.groupby_reduce (core.py:495-541),
        # applied PER SLAB so the conversion streams with the data
        from .core import _NAT_INT

        base_loader = loader
        if agg.preserves_dtype:
            # min/max/first/last: exact int64 view, NaT as the sentinel
            from .aggregations import set_nat_final_fill

            nat = True
            set_nat_final_fill(agg, fill_value)
            loader = lambda s, e: np.asarray(base_loader(s, e)).view("int64")
        elif agg.reduction_type == "argreduce" or agg.name in (
            "count", "len", "any", "all"
        ):
            nat = True
            loader = lambda s, e: np.asarray(base_loader(s, e)).view("int64")
        else:
            # float-returning reductions (mean/var/std/sum): f64 epoch
            # values with NaT -> NaN, rounded back in _astype_final
            def loader(s, e):
                sl = np.asarray(base_loader(s, e)).view("int64")
                f = sl.astype(np.float64)
                f[sl == _NAT_INT] = np.nan
                return f
        # no re-probe: the wrap changes dtype only between 8-byte types
        # (datetime64 -> int64/float64), so lead shape and itemsize — the
        # only things probe feeds — are unchanged, and a zarr/S3 loader
        # should not pay a second remote chunk read
    stream_orderstat = False
    if agg.blockwise_only:
        if agg.name in ("median", "nanmedian", "quantile", "nanquantile"):
            # quantile/median DO stream: the radix-select bisection only
            # ever needs per-group COUNTS, which accumulate slab by slab —
            # (nbits + 1) full passes over the data (see _stream_quantile).
            # With mesh= each slab is sharded and every counting pass
            # psums — out-of-core AND distributed at once.
            stream_orderstat = True
        else:
            raise NotImplementedError(
                f"{agg.name!r} cannot stream on this path; use "
                "groupby_reduce(method='blockwise', mesh=...) after "
                "rechunk.reshard_for_blockwise."
            )
    if (
        n >= _BIG
        and not utils.x64_enabled()
        and (agg.reduction_type == "argreduce" or agg.combine in (("first",), ("last",)))
    ):
        raise ValueError(
            f"position-tracking reductions over {n} elements need int64 "
            "positions; enable jax_enable_x64 (int32 would wrap and collide "
            "with the sentinel)."
        )

    if lead_shape is None:
        lead_shape = probe.shape[:-1]
    itemsize = probe.dtype.itemsize
    row_bytes = int(np.prod(lead_shape, dtype=np.int64)) * itemsize if lead_shape else itemsize

    # -- present-groups (sort) engine: compact once for the WHOLE stream ---
    # The stream's codes are host-known upfront, so the union of groups any
    # slab can touch is known before the first slab stages: compact the
    # code span once and the carry — through every step program, OOM
    # split, checkpoint snapshot and the mesh collectives — is sized by
    # the groups present in the stream, not the label universe. A resumed
    # process recomputes the identical present table from the identical
    # inputs, so checkpoint identities (which fingerprint the compact
    # codes + capacity) match bit-for-bit across kill/resume.
    present_table = None
    size_full = size
    engine = _route_stream_highcard(
        engine, codes, size, probe, lead_shape, agg, n=n
    )
    if engine == "sort":
        from .core import _note_highcard
        from .kernels import compact_codes, present_cap, present_groups

        present_table = present_groups(codes, size)
        if len(present_table) < size:
            ncap = present_cap(len(present_table), size)
            codes = compact_codes(codes, present_table)
            _note_highcard(size, ncap, len(present_table))
            size = ncap
        else:
            present_table = None  # universe fully present: dense == compact
    if batch_len is None:
        from .options import OPTIONS

        explicit_bytes = batch_bytes is not None
        if not explicit_bytes:
            batch_bytes = _DEFAULT_BATCH_BYTES
        if (
            OPTIONS["autotune"]
            and not explicit_bytes
            and not OPTIONS["stream_checkpoint_path"]
        ):
            # observed-best slab byte budget for this stream-size band
            # (fed by past StreamReport observations); the default budget
            # otherwise. Explicit sizing is never second-guessed — a
            # passed batch_len pins the slab length and a passed
            # batch_bytes is a device-memory cap — only the
            # nothing-specified default adapts. With checkpointing on, the
            # derived batch_len is part of the checkpoint identity key: it
            # must be reproducible by the resuming process, and a store
            # whose winner shifted between runs would silently orphan the
            # snapshot — so adaptation is off whenever a checkpoint path
            # is configured.
            from .autotune import pick_stream_batch_bytes

            lead_elems = int(np.prod(lead_shape, dtype=np.int64)) if lead_shape else 1
            batch_bytes = pick_stream_batch_bytes(
                batch_bytes, nelems=int(n) * lead_elems
            )
        batch_len = max(1, min(n, batch_bytes // max(row_bytes, 1)))

    if stream_orderstat:
        result = _stream_quantile(
            agg, loader, codes, size=size, n=n, batch_len=batch_len,
            lead_shape=tuple(lead_shape), mesh=mesh, axis_name=axis_name,
            # the datetime wrap changes the effective dtype to float64
            probe_dtype=np.float64 if datetime_dtype is not None else probe.dtype,
            data_probe=probe,
        )
        from .core import _astype_final, _index_values

        result = _astype_final(result, agg, datetime_dtype)
        result = _scatter_stream(result, present_table, size_full)
        out_shape = (
            agg.new_dims() + tuple(lead_shape) + tuple(keep_by_shape) + grp_shape
        )
        if result.shape != out_shape:
            result = result.reshape(out_shape)
        return (result,) + tuple(_index_values(g) for g in found_groups)

    skipna = agg.name.startswith("nan") or agg.name == "count"
    count_skipna = skipna or agg.min_count > 0

    if nat:
        from .aggregations import shift_nat_identity_fills

        shift_nat_identity_fills(agg)

    slab_shard = codes_shard = None
    spec_entry = None
    mesh_key = None
    shard_quantum = 1
    if mesh is not None:
        from .options import OPTIONS
        from .parallel.mapreduce import _is_additive, dense_intermediate_bytes
        from .utils import fmt_bytes

        axes, ndev, batch_len, spec_entry, _sspec, _cspec, slab_shard, codes_shard = (
            _mesh_stream_layout(mesh, axis_name, batch_len, len(lead_shape))
        )
        shard_quantum = ndev

        # ceiling routing — the same decision sharded_groupby_reduce makes:
        # per-device accumulators are one dense (..., size) buffer set, so
        # above the ceiling additive aggs switch to owner-blocked
        # accumulation and everything else fails actionably
        lead_elems = int(np.prod(lead_shape, dtype=np.int64)) if lead_shape else 1
        est = dense_intermediate_bytes(lead_elems, size, probe.dtype, agg, ndev)
        ceiling = OPTIONS["dense_intermediate_bytes_max"]
        blocked = False
        if est > ceiling:
            result_bytes = lead_elems * size * max(4, itemsize)
            blocked_est = result_bytes + est // ndev
            if _is_additive(agg) and blocked_est <= ceiling:
                blocked = True
            else:
                how = (
                    "its combine cannot be distributed by group ownership"
                    if not _is_additive(agg)
                    else f"even the blocked owner-by-owner form needs "
                    f"~{fmt_bytes(blocked_est)}/device over {ndev} device(s)"
                )
                raise ValueError(
                    f"streaming {agg.name!r} over {size} groups needs "
                    f"~{fmt_bytes(est)} of dense (..., size) accumulators per "
                    f"device, above the {fmt_bytes(ceiling)} "
                    f"dense_intermediate_bytes_max ceiling, and {how}. Options: "
                    "use engine='sort' (FLOX_TPU_DEFAULT_ENGINE=sort — the "
                    "carry then covers only the groups present in the stream); "
                    "reduce expected_groups; shard over more devices; or raise "
                    "set_options(dense_intermediate_bytes_max=...) if the "
                    "devices really have the headroom."
                )

        # program cache (the _PROGRAM_CACHE pattern from the sharded
        # runtime): repeat same-shaped calls — per-variable streaming over
        # a dataset, pipelines — reuse the three compiled shard_map
        # programs instead of retracing
        from .parallel.mapreduce import _agg_cache_key

        def _build_mesh_pair():
            if blocked:
                size_pad = size + (-size) % ndev
                return (
                    _build_mesh_step_blocked(
                        agg, size_pad=size_pad, ndev=ndev, count_skipna=count_skipna,
                        nat=nat, mesh=mesh, axes=axes, lead_ndim=len(lead_shape),
                    ),
                    _build_mesh_final_blocked(agg, size=size, mesh=mesh, axes=axes),
                )
            return (
                _build_mesh_step(
                    agg, size=size, count_skipna=count_skipna,
                    nat=nat, mesh=mesh, axes=axes, lead_ndim=len(lead_shape),
                ),
                _build_mesh_final(agg, mesh=mesh, axes=axes, nat=nat),
            )

        # no shard_len in the key: the step programs are shape-polymorphic
        # (per-device offsets come from the traced shard width), so streams
        # that differ only in batch_len share one cached (step, final) pair
        step, final = _step_cached(
            ("mesh", _agg_cache_key(agg), size, axes, mesh, nat,
             blocked, len(lead_shape)),
            _build_mesh_pair,
        )
        mesh_key = (tuple(axes), ndev, blocked)
    else:
        from .parallel.mapreduce import _agg_cache_key

        step = _step_cached(
            ("reduce-step", _agg_cache_key(agg), size, count_skipna, nat),
            lambda: _build_step(agg, size=size, count_skipna=count_skipna, nat=nat),
        )
    nbatches = math.ceil(n / batch_len)

    from .pipeline import DispatchThrottle, SlabStager, stream_slabs
    from .profiling import timed
    from .resilience import (
        StreamCheckpointer,
        StreamCounters,
        device_restore,
        dispatch_slab,
    )

    counters = StreamCounters()
    stager = SlabStager(
        loader, codes, n=n, batch_len=batch_len, lead_shape=tuple(lead_shape),
        slab_shard=slab_shard, codes_shard=codes_shard, with_offset=True,
        counters=counters,
    )
    from .parallel.mapreduce import _agg_cache_key

    ckpt = StreamCheckpointer.for_stream(
        # repr(_agg_cache_key) carries the RESOLVED aggregation identity
        # (dtype= override, custom chunk/combine, finalize_kwargs) as a
        # picklable string — a snapshot from a same-named but different
        # aggregation must miss, not silently fold. Custom-callable ids
        # differ across processes, so a cross-process .npz resume of a
        # custom agg misses too: a fresh run, never a mismatched one.
        kind="reduce", name=repr(_agg_cache_key(agg)), n=n, batch_len=batch_len,
        size=size, codes=codes, lead_shape=tuple(lead_shape), mesh_key=mesh_key,
        extra=(nat, count_skipna, str(probe.dtype)), data_probe=probe,
        counters=counters,
    )
    state = None
    skip = 0
    snap = ckpt.restore()
    if snap is not None:
        # bit-identical resume: the carry round-trips host exactly, and the
        # remaining slabs refold in the same stream order
        skip = snap.slabs_done
        state = device_restore(snap.payload, mesh=mesh, spec_entry=spec_entry)
    done = skip
    throttle = DispatchThrottle()

    from . import costmodel

    # the cost-ledger key pipeline.stream_slabs bills this stream under —
    # the card label must match it exactly or the roofline join misses.
    # The step arguments are captured as ShapeDtypeStructs DURING the loop
    # but the card (one lower+compile for analysis) is recorded AFTER it,
    # so the analysis wall never lands in the pass's billed dispatch time.
    stream_prog = f"stream[reduce[{agg.name}]]"
    card_capture: list = []

    def apply_step(st, sb):
        if costmodel.enabled() and len(card_capture) < 2:
            # first slab captures the init program, second the steady-state
            # carry program (the one that dominates a long stream) — mesh
            # runners expose both on _jitted/_jitted_init, the single-device
            # step covers both arities through one jitted function
            if st is None and hasattr(step, "_jitted_init"):
                card_capture.append((
                    step._jitted_init,
                    costmodel.aval_args((sb.data, sb.codes, sb.offset)),
                ))
            else:
                card_capture.append((
                    getattr(step, "_jitted", None),
                    costmodel.aval_args((st, sb.data, sb.codes, sb.offset)),
                ))
        return step(st, sb.data, sb.codes, sb.offset)

    with timed(f"stream [{agg.name}] {nbatches} slab(s) x {batch_len}"):
        # the pipeline stages slab i+k (load, pad, device_put against the
        # shardings above) while the step for slab i runs; the step itself
        # dispatches async, and the throttle syncs the carry every K steps.
        # dispatch_slab adds the fault hook + OOM halve-and-re-stage, and
        # the checkpointer snapshots the carry every K processed slabs.
        for sl in stream_slabs(
            loader, codes, n=n, batch_len=batch_len, lead_shape=tuple(lead_shape),
            slab_shard=slab_shard, codes_shard=codes_shard, with_offset=True,
            label=f"reduce[{agg.name}]", skip=skip, counters=counters, stager=stager,
        ):
            state = dispatch_slab(
                apply_step, state, sl, stager=stager, counters=counters,
                shard_quantum=shard_quantum,
                highcard_hint=_highcard_oom_hint(agg, size, present_table),
            )
            throttle.tick(state)
            done += 1
            ckpt.tick(lambda: state, slabs_done=done)

    if card_capture:
        # steady-state program preferred (the capture list's tail); the
        # analysis compile runs here, outside the stream's timed window
        fn, sds = card_capture[-1]
        costmodel.ensure_card(stream_prog, fn, sds)

    out_shape = tuple(lead_shape) + tuple(keep_by_shape) + grp_shape
    if mesh is not None:
        with telemetry.span("finalize", mesh=True):
            result = final(state)
            ckpt.done()
            from .core import _astype_final, _index_values

            if fused_funcs is not None:
                out = _finalize_many_stream(
                    agg, result, out_shape, present_table, size_full
                )
                return (out,) + tuple(_index_values(g) for g in found_groups)
            result = _astype_final(result, agg, datetime_dtype)
            result = _scatter_stream(result, present_table, size_full)
            if result.shape != out_shape:
                result = result.reshape(out_shape)
        return (result,) + tuple(_index_values(g) for g in found_groups)

    with telemetry.span("finalize"):
        inters, counts = state
        from .parallel.mapreduce import _finalize_combined

        result = _finalize_combined(agg, inters, counts)
        ckpt.done()
        from .core import _astype_final, _index_values

        if fused_funcs is not None:
            # one streaming pass -> the whole statistic set
            out = _finalize_many_stream(
                agg, result, out_shape, present_table, size_full
            )
            return (out,) + tuple(_index_values(g) for g in found_groups)
        result = _astype_final(result, agg, datetime_dtype)
        result = _scatter_stream(result, present_table, size_full)
        # (..., size) -> (..., *keep_by, *groups): kept by-dims ride the group
        # axis as disjoint code ranges (factorize_ offsetting) and unfold here
        if result.shape != out_shape:
            result = result.reshape(out_shape)
    return (result,) + tuple(_index_values(g) for g in found_groups)


def streaming_groupby_aggregate_many(
    array: Any,
    by: Any,
    *,
    funcs: "tuple | list" = ("sum", "count", "min", "max", "var"),
    batch_len: int | None = None,
    batch_bytes: int | None = None,
    expected_groups: Any = None,
    isbin: Any = False,
    sort: bool = True,
    axis: Any = None,
    fill_value: Any = None,
    dtype: Any = None,
    min_count: int | None = None,
    finalize_kwargs: dict | None = None,
    mesh: Any = None,
    axis_name: str | tuple[str, ...] = "data",
    engine: str | None = None,
) -> tuple:
    """N grouped statistics in ONE streaming pass over the loader.

    The multi-statistic form of :func:`streaming_groupby_reduce`: the
    fusion planner (``aggregations.plan_fused``) merges the requested
    statistic blueprints into one multi-output chunk plan, so every slab
    is staged ONCE and folds into one fused carry — an ERA5-style
    mean+std+extremes job is one pass over the data instead of four.
    Checkpoint/resume (the fused carry snapshots under one stream
    identity) and OOM slab-splitting work exactly as for a single
    statistic; ``mesh=`` composes with the sharded runtime (one collective
    combine for the whole set). Returns ``(results, groups)`` with
    ``results`` a dict mapping func name -> array, each bit-identical to
    the corresponding single-statistic streaming call.
    """
    from . import telemetry

    with telemetry.span(
        "streaming_groupby_aggregate_many", funcs=list(funcs),
        mesh=mesh is not None,
    ):
        return _streaming_groupby_reduce_impl(
            array, by, func=tuple(funcs), batch_len=batch_len,
            batch_bytes=batch_bytes, expected_groups=expected_groups,
            isbin=isbin, sort=sort, axis=axis, fill_value=fill_value,
            dtype=dtype, min_count=min_count, finalize_kwargs=finalize_kwargs,
            mesh=mesh, axis_name=axis_name, engine=engine,
        )


def _route_stream_highcard(engine, codes, size, probe, lead_shape, agg, *, n):
    """Dense-vs-sort routing for the streaming runtime — the streaming
    sibling of ``core._route_highcard``. ``engine=None`` auto-routes:
    above ``dense_intermediate_bytes_max`` the sort engine is taken
    whenever its compact domain fits (the carry the ladder could never
    shrink now tracks present groups); between ``sort_engine_min_groups``
    and the ceiling the "highcard" autotune family decides — except when a
    checkpoint path is configured, where routing must be reproducible by
    the resuming process, so only the static heuristic applies (the same
    rule the adaptive slab sizing follows). Explicit engines are never
    second-guessed; "numpy" has no streaming form and is rejected.
    """
    from .options import OPTIONS

    if engine is not None:
        from .aggregations import normalize_engine

        engine = normalize_engine(engine)
        if engine == "numpy":
            raise ValueError(
                "the streaming runtime folds slabs on device; engine='numpy' "
                "has no streaming form (use groupby_reduce on host data)."
            )
        return engine
    from .parallel.mapreduce import dense_intermediate_bytes

    lead_elems = int(np.prod(lead_shape, dtype=np.int64)) if lead_shape else 1
    est = dense_intermediate_bytes(lead_elems, size, probe.dtype, agg, 1)
    ceiling = OPTIONS["dense_intermediate_bytes_max"]
    over = est > ceiling
    if OPTIONS["default_engine"] == "sort":
        return "sort"
    if not over and size < OPTIONS["sort_engine_min_groups"]:
        return "jax"
    from .kernels import present_cap, present_groups

    present = present_groups(codes, size)  # memoized; the sort path reuses it
    ncap = present_cap(len(present), size)
    if over:
        est_sort = dense_intermediate_bytes(lead_elems, ncap, probe.dtype, agg, 1)
        if est_sort <= ceiling:
            from . import telemetry

            telemetry.count("highcard.ceiling_routes")
            logger.debug(
                "stream highcard: dense estimate over ceiling -> sort engine "
                "(size=%d present=%d)", size, len(present),
            )
            return "sort"
        return "jax"  # the mesh blocked program / ceiling error downstream decides
    from .core import _HIGHCARD_DENSITY_DEN

    heuristic = "sort" if ncap * _HIGHCARD_DENSITY_DEN <= size else "dense"
    chosen = heuristic
    if OPTIONS["autotune"] and not OPTIONS["stream_checkpoint_path"]:
        from . import autotune

        nelems = int(n) * lead_elems
        autotune.prime_highcard(probe.dtype, size, len(present), nelems)
        chosen = autotune.decide(
            "highcard", heuristic, ("dense", "sort"),
            dtype=str(probe.dtype), ngroups=size, nelems=nelems,
        )
    return "sort" if chosen == "sort" else "jax"


def _highcard_oom_hint(agg, size: int, present_table) -> str | None:
    """The ngroups-dominated flag for the OOM ladder (see
    ``resilience.dispatch_slab``): set on dense runs whose accumulators
    span a universe past ``sort_engine_min_groups`` — the allocation the
    ladder can never shrink — and never on already-compacted runs."""
    from .options import OPTIONS

    if present_table is not None or size < OPTIONS["sort_engine_min_groups"]:
        return None
    return (
        f"the {agg.name!r} accumulators are dense over the {size}-label "
        "universe, which slab-splitting cannot shrink. The sort "
        "(present-groups) engine accumulates only over groups actually "
        "present: pass engine='sort' (or set FLOX_TPU_DEFAULT_ENGINE=sort), "
        "or lower expected_groups."
    )


def _scatter_stream(result, present_table, size_full: int):
    """Expand a compact streaming result to the dense (..., size) layout
    (host-side; no-op on dense runs)."""
    if present_table is None:
        return result
    from .kernels import scatter_present_dense

    return scatter_present_dense(np.asarray(result), present_table, size_full)


def _finalize_many_stream(agg, result, out_shape, present_table, size_full: int):
    """Fused finalize with the present-groups scatter-back: each statistic
    expands from the compact domain before the (dense) reshape. Dense runs
    take the shared :func:`fusion.finalize_many` unchanged."""
    from .fusion import finalize_many

    if present_table is None:
        return finalize_many(agg, result, out_shape)
    outs = finalize_many(agg, result, None)
    fixed = {}
    for f, v in outs.items():
        v = _scatter_stream(v, present_table, size_full)
        if tuple(v.shape) != tuple(out_shape):
            v = v.reshape(out_shape)
        fixed[f] = v
    return fixed


def _slab_stats(agg: Aggregation, slab, ccodes, offset, *, size: int,
                count_skipna: bool, nat: bool):
    """Chunk intermediates + counts for one slab (or one shard of a slab).
    ``offset`` is the slab's global start position (traced), already
    including the device offset on the mesh path."""
    import jax.numpy as jnp

    from .kernels import generic_kernel
    from .parallel.mapreduce import _local_counts, _local_stats

    if agg.reduction_type != "argreduce" and agg.combine not in (("first",), ("last",)):
        counts, inters = _local_stats(agg, ccodes, slab, size, count_skipna, nat)
        return inters, counts
    skipna = agg.name.startswith("nan")
    kw = {"nat": True} if nat else {}
    counts = _local_counts(ccodes, slab, size, count_skipna, nat)
    if agg.reduction_type == "argreduce":
        val_f, arg_f = agg.chunk
        val = generic_kernel(
            val_f, ccodes, slab, size=size,
            fill_value=agg.fill_value["intermediate"][0], **kw,
        )
        local_arg = generic_kernel(arg_f, ccodes, slab, size=size, fill_value=-1, **kw)
        gidx = jnp.where(local_arg >= 0, local_arg + offset, -1)
        return [val, gidx], counts
    from .parallel.mapreduce import _local_firstlast

    val, pos = _local_firstlast(
        ccodes, slab, size, skipna=skipna,
        last=agg.combine == ("last",), nat=nat, offset=offset,
    )
    return [val, pos], counts


def _merge_into(agg: Aggregation, state, inters, counts, *, nat: bool):
    """Fold one slab's intermediates into the running state — the
    sequential form of the mesh collectives, shared by the single-device
    and the per-device (mesh) accumulation loops."""
    import jax.numpy as jnp

    skipna = agg.name.startswith("nan")
    # NaT marker re-injection applies only to propagating (non-skipna)
    # merges — skipna identity fills were shifted off the sentinel upstream
    nat_markers = nat and not skipna
    acc_inters, acc_counts = state
    out = []
    if agg.reduction_type == "argreduce":
        arg_of_max = "max" in str(agg.chunk[1])
        va, ia = acc_inters
        vb, ib = inters
        better = _argmerge_better(va, vb, arg_of_max)
        tie = vb == va
        if jnp.issubdtype(va.dtype, jnp.floating):
            tie = tie | (jnp.isnan(va) & jnp.isnan(vb))
        if nat_markers:
            # NaT-propagating: a NaT extreme wins over any value (its
            # position is the group's first NaT); both-NaT is already a
            # tie through integer equality
            marker = jnp.asarray(np.iinfo(np.int64).min, va.dtype)
            na_, nb_ = va == marker, vb == marker
            better = (better & ~na_ & ~nb_) | (nb_ & ~na_)
        ia_safe = jnp.where(ia >= 0, ia, _BIG)
        ib_safe = jnp.where(ib >= 0, ib, _BIG)
        idx = jnp.where(better, ib_safe, jnp.where(tie, jnp.minimum(ia_safe, ib_safe), ia_safe))
        out = [jnp.where(better, vb, va), jnp.where(idx < _BIG, idx, -1)]
    elif agg.combine in (("first",), ("last",)):
        va, pa = acc_inters
        vb, pb = inters
        if agg.combine == ("last",):
            take_b = (pb >= 0) & ((pa < 0) | (pb > pa))
        else:
            take_b = (pb < _BIG) & ((pa >= _BIG) | (pb < pa))
        out = [jnp.where(take_b, vb, va), jnp.where(take_b, pb, pa)]
    else:
        for a, b, op in zip(acc_inters, inters, agg.combine):
            out.append(_pair_merge(op, a, b, nat=nat_markers))
    return out, acc_counts + counts


def _init_state_like_merged(agg: Aggregation, inters, counts, *, nat: bool):
    """Cast the first slab's state to the dtypes a merge would produce.

    Custom callable combines may promote (``jnp.stack([a, b]).sum(0)``
    widens int32 chunk counts to int64 under x64), so without this the
    carry pytree changes dtype between slab 1 and slab 2 — a step retrace,
    and a donated init buffer that cannot alias its output. The self-merge
    here is traced only for its (static) output dtypes; XLA DCEs the
    computation, so the init step costs nothing extra."""
    import jax

    merged = _merge_into(agg, (inters, counts), inters, counts, nat=nat)
    return jax.tree.map(lambda x, m: x.astype(m.dtype), (inters, counts), merged)


def _build_step(agg: Aggregation, *, size: int, count_skipna: bool,
                nat: bool = False):
    """One jitted step: slab -> chunk intermediates -> merge into state.
    The carry is donated (pipeline.maybe_donate) so the dense accumulators
    update in place across slabs; the first call's ``state=None`` donates
    an empty pytree, so one jitted function covers both arities."""
    from .pipeline import maybe_donate

    def step(state, slab, ccodes, offset):
        inters, counts = _slab_stats(
            agg, slab, ccodes, offset, size=size, count_skipna=count_skipna, nat=nat
        )
        if state is None:
            return _init_state_like_merged(agg, inters, counts, nat=nat)
        return _merge_into(agg, state, inters, counts, nat=nat)

    jitted = maybe_donate(step, donate_argnums=(0,))

    def run(state, slab, ccodes, offset):
        # first call establishes the state pytree; jit caches both arities
        return jitted(state, slab, ccodes, offset)

    # the OOM-split tests assert compile counts against the underlying jit
    # cache (the power-of-two ladder claim: splits reuse rungs, the base
    # step is never retraced)
    run._jitted = jitted
    return run


def _build_mesh_step(agg: Aggregation, *, size: int,
                     count_skipna: bool, nat: bool, mesh, axes, lead_ndim: int):
    """Per-slab step on the mesh: each device folds its shard of the slab
    into ITS OWN accumulator — zero collectives while streaming. State
    leaves are (ndev, ..., size) sharded over the leading device axis;
    the one collective combine happens in :func:`_build_mesh_final`.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from .parallel.mapreduce import _flat_axis_index

    spec_entry = axes if len(axes) > 1 else axes[0]
    slab_spec = P(*([None] * lead_ndim + [spec_entry]))

    def local_step(state, slab_sh, codes_sh, offset):
        # shard-contiguous layout: device d holds slab[d*L:(d+1)*L], so the
        # global position of its first element is offset + d*L. L comes
        # from the traced shard's own trailing dim, NOT the batch_len this
        # builder was keyed on: an OOM-split sub-slab re-enters the same
        # jitted step at half the span, and a static L would misplace every
        # position-tracking reduction (argmin/argmax/first/last)
        dev = _flat_axis_index(axes)
        goff = offset + dev.astype(offset.dtype) * slab_sh.shape[-1]
        inters, counts = _slab_stats(
            agg, slab_sh, codes_sh, goff, size=size,
            count_skipna=count_skipna, nat=nat,
        )
        if state is None:
            inters, counts = _init_state_like_merged(agg, inters, counts, nat=nat)
            return _expand_dev(inters), counts[None]
        st = jax.tree.map(lambda x: x[0], state)
        minters, mcounts = _merge_into(agg, st, inters, counts, nat=nat)
        return _expand_dev(minters), mcounts[None]

    return _mesh_step_runner(local_step, mesh, slab_spec, spec_entry)


def _mesh_step_runner(local_step, mesh, slab_spec, spec_entry):
    """Two jitted shard_map programs (first slab has no state yet). The
    steady-state program donates the per-device carry so every chip's
    accumulators update in place across slabs."""
    import jax
    from jax.sharding import PartitionSpec as P

    from .parallel.mesh import shard_map
    from .pipeline import maybe_donate

    def init_step(slab_sh, codes_sh, offset):
        return local_step(None, slab_sh, codes_sh, offset)

    common = dict(mesh=mesh, out_specs=P(spec_entry), check_vma=False)
    init_fn = jax.jit(shard_map(
        init_step, in_specs=(slab_spec, P(spec_entry), P()), **common
    ))
    step_fn = maybe_donate(shard_map(
        local_step, in_specs=(P(spec_entry), slab_spec, P(spec_entry), P()), **common
    ), donate_argnums=(0,))

    def run(state, slab, ccodes, offset):
        if state is None:
            return init_fn(slab, ccodes, offset)
        return step_fn(state, slab, ccodes, offset)

    # the costmodel card site lowers the underlying jitted programs (the
    # steady-state carry step and the first-slab init) without executing
    run._jitted = step_fn
    run._jitted_init = init_fn
    return run


def _expand_dev(inters):
    """Re-attach the per-device leading axis to every accumulator leaf."""
    import jax

    return jax.tree.map(lambda x: x[None], inters)


def _build_mesh_final(agg: Aggregation, *, mesh, axes, nat: bool):
    """The ONE collective combine: per-device accumulated states meet the
    SAME combine contract as the mesh map-reduce program — literally the
    shared ``_combine_intermediates``/``_finalize_combined`` helpers in
    parallel/mapreduce.py. Output replicated."""
    import jax
    from jax.sharding import PartitionSpec as P

    from .parallel.mapreduce import _combine_intermediates, _finalize_combined
    from .parallel.mesh import shard_map

    spec_entry = axes if len(axes) > 1 else axes[0]

    def final(state):
        st = jax.tree.map(lambda x: x[0], state)
        inters, counts = st
        counts_g = jax.lax.psum(counts, axes)
        combined = _combine_intermediates(agg, inters, axes, nat)
        return _finalize_combined(agg, combined, counts_g)

    return jax.jit(
        shard_map(
            final, mesh=mesh, in_specs=(P(spec_entry),), out_specs=P(),
            check_vma=False,
        )
    )


def _build_mesh_step_blocked(agg: Aggregation, *, size_pad: int, ndev: int,
                             count_skipna: bool, nat: bool, mesh, axes,
                             lead_ndim: int):
    """Huge-label-space streaming (the streaming form of the blocked
    owner-by-owner program, parallel/mapreduce.py): per slab, a fori_loop
    walks the ndev owner blocks — each block's (..., size/ndev)
    intermediates are psum'd and the owner keeps its slice — so no dense
    (..., size) buffer ever materializes on any device, per slab OR in the
    accumulators. Communication per slab totals one psum of (..., size);
    the data makes ndev passes per slab (the price of the ceiling).
    Additive combines only (sum / the var triple)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .parallel.mapreduce import (
        _combine_simple,
        _combine_var,
        _flat_axis_index,
        _local_stats,
    )

    spec_entry = axes if len(axes) > 1 else axes[0]
    slab_spec = P(*([None] * lead_ndim + [spec_entry]))
    b = size_pad // ndev
    skipna = agg.name.startswith("nan")
    nat_markers = nat and not skipna

    def local_step(state, slab_sh, codes_sh, offset):
        me = _flat_axis_index(axes)

        def block(d):
            in_block = (codes_sh >= d * b) & (codes_sh < (d + 1) * b)
            bc = jnp.where(in_block, codes_sh - d * b, -1)
            counts_local, inters = _local_stats(agg, bc, slab_sh, b, count_skipna, nat)
            counts = jax.lax.psum(counts_local, axes)
            outs = []
            for inter, op in zip(inters, agg.combine):
                outs.append(
                    _combine_var(inter, axes)
                    if op == "var"
                    else _combine_simple(op, inter, axes, nat=nat_markers)
                )
            return counts, outs

        c0, o0 = block(0)
        keep0 = me == 0
        carry0 = jax.tree.map(lambda x: jnp.where(keep0, x, jnp.zeros_like(x)), (c0, o0))

        def body(d, carry):
            c, o = block(d)
            keep = me == d
            return jax.tree.map(lambda new, acc: jnp.where(keep, new, acc), (c, o), carry)

        counts_blk, inters_blk = jax.lax.fori_loop(1, ndev, body, carry0)
        if state is None:
            inters_blk, counts_blk = _init_state_like_merged(
                agg, inters_blk, counts_blk, nat=nat
            )
            return _expand_dev(inters_blk), counts_blk[None]
        st = jax.tree.map(lambda x: x[0], state)
        acc_inters, acc_counts = st
        merged = [
            _pair_merge(op, a, new, nat=nat_markers)
            for a, new, op in zip(acc_inters, inters_blk, agg.combine)
        ]
        return _expand_dev(merged), (acc_counts + counts_blk)[None]

    return _mesh_step_runner(local_step, mesh, slab_spec, spec_entry)


def _build_mesh_final_blocked(agg: Aggregation, *, size: int, mesh, axes):
    """Finalize per-owner accumulators and gather the full group axis —
    the tail of the blocked owner-by-owner program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .parallel.mapreduce import _crop, _finalize_combined
    from .parallel.mesh import shard_map

    spec_entry = axes if len(axes) > 1 else axes[0]

    def final(state):
        st = jax.tree.map(lambda x: x[0], state)
        inters, counts = st
        result_own = _finalize_combined(agg, inters, counts)
        full = jax.lax.all_gather(
            jnp.moveaxis(result_own, -1, 0), axes, tiled=True
        )
        return _crop(jnp.moveaxis(full, 0, -1), size)

    return jax.jit(
        shard_map(
            final, mesh=mesh, in_specs=(P(spec_entry),), out_specs=P(),
            check_vma=False,
        )
    )


def streaming_groupby_scan(
    array: Any,
    by: Any,
    *,
    func: str,
    batch_len: int | None = None,
    batch_bytes: int | None = None,
    expected_groups: Any = None,
    dtype: Any = None,
    out: Callable[[int, int, Any], None] | None = None,
    mesh: Any = None,
    axis_name: str | tuple[str, ...] = "data",
) -> Any:
    """Out-of-core grouped scan: slabs stream through a per-group carry.

    The reference runs scans over chunked arrays via dask's cumreduction
    (dask.py:576-663); this is the sequential form of the same Blelloch
    decomposition — each slab runs the within-slab segmented scan, the
    per-group block summary becomes the next slab's carry, and the carry
    is applied through the codes. ``bfill`` streams the slabs in REVERSE
    (the ``(start, stop)`` loader contract is random-access).

    ``array``: host array ``(..., n)`` or loader ``callable(start, stop)``;
    ``by``: 1-D labels along the streamed (scan) axis. ``out``: optional
    writer ``callable(start, stop, result_slab)`` — with a writer the
    result streams straight back out (nothing array-sized materializes;
    returns None); without one the full result array is allocated.
    Semantics match :func:`flox_tpu.groupby_scan` exactly, including
    datetime64/timedelta64 NaT rules and int promotion.

    ``mesh=`` completes the composition matrix: each slab scatters over
    the mesh and runs the SAME distributed Blelloch program as the
    in-memory mesh scan (within-slab carry exchange over the collective),
    with the cross-slab carry folded at the slab boundary — out-of-core
    AND multi-chip scans, results still streamable through ``out=``.
    """
    from . import telemetry

    with telemetry.span("streaming_groupby_scan", func=func, mesh=mesh is not None):
        return _streaming_groupby_scan_impl(
            array, by, func=func, batch_len=batch_len, batch_bytes=batch_bytes,
            expected_groups=expected_groups, dtype=dtype, out=out,
            mesh=mesh, axis_name=axis_name,
        )


def _streaming_groupby_scan_impl(
    array: Any,
    by: Any,
    *,
    func: str,
    batch_len: int | None,
    batch_bytes: int | None,
    expected_groups: Any,
    dtype: Any,
    out: Callable[[int, int, Any], None] | None,
    mesh: Any,
    axis_name: str | tuple[str, ...],
) -> Any:
    """The :func:`streaming_groupby_scan` body, under the public wrapper's
    root telemetry span (defaults live only on the wrapper)."""
    import math

    import jax
    import jax.numpy as jnp

    from . import dtypes as dtps, telemetry
    from .aggregations import _initialize_scan
    from .core import _convert_expected_groups_to_index, _normalize_expected, _normalize_isbin
    from .kernels import _nan_mask, generic_kernel
    from .profiling import timed

    labels = utils.asarray_host(by)
    if labels.ndim != 1:
        raise NotImplementedError(
            "streaming_groupby_scan scans the streamed axis: pass 1-D labels "
            "(use groupby_scan for in-memory nD layouts)"
        )
    n = labels.shape[0]

    if callable(array):
        loader = array
        lead_shape = None
    else:
        arr = np.asarray(array) if not utils.is_jax_array(array) else array
        if arr.shape[-1] != n:
            raise ValueError(
                f"array trailing dim {arr.shape[-1]} != by length {n}"
            )
        lead_shape = arr.shape[:-1]
        loader = lambda s, e: arr[..., s:e]

    expected = _normalize_expected(expected_groups, 1)
    expected_idx = _convert_expected_groups_to_index(expected, _normalize_isbin(False, 1), True)
    with telemetry.span("factorize") as _fsp:
        codes, found_groups, grp_shape, ngroups, size, props = fct.factorize_(
            [labels], axes=(0,), expected_groups=expected_idx, sort=True
        )
        _fsp.set(ngroups=ngroups, size=size)
    # ONE contiguous int32 copy for the whole stream (per-slab slices are
    # zero-copy contiguous views; see streaming_groupby_reduce)
    codes = np.ascontiguousarray(np.asarray(codes).reshape(-1), dtype=np.int32)
    if size == 0:
        raise ValueError("No groups to scan over (empty expected_groups?)")

    scan = _initialize_scan(func)

    probe = np.asarray(loader(0, 1))
    if lead_shape is None:
        lead_shape = probe.shape[:-1]
    arr_dtype = probe.dtype
    datetime_dtype = arr_dtype if dtps.is_datetime_like(arr_dtype) else None
    nat = datetime_dtype is not None
    base_loader = loader
    if nat:
        # same rules as groupby_scan (scan.py:118-151)
        if scan.name in ("cumsum", "nancumsum") and arr_dtype.kind == "M":
            raise TypeError(
                "cumsum of datetime64 values is undefined (numpy cannot add "
                "points in time); cumsum timedelta64 works."
            )
        if dtype is not None:
            raise TypeError(
                "dtype= is not supported for datetime/timedelta scans; the "
                "scan runs on the exact int64 view and returns "
                f"{arr_dtype} unchanged."
            )
        if not utils.x64_enabled():
            raise ValueError(
                "datetime/timedelta streaming scans need jax_enable_x64 "
                "(int64 NaT sentinels do not survive int32 truncation)."
            )
        loader = lambda s, e: np.asarray(base_loader(s, e)).view("int64")
    # int promotion for accumulating scans (parity: scan.py:153-156)
    if scan.name in ("cumsum", "nancumsum") and dtype is None and not nat:
        if arr_dtype.kind in "iub":
            dtype = np.result_type(arr_dtype, np.int_)

    itemsize = probe.dtype.itemsize
    row_bytes = int(np.prod(lead_shape, dtype=np.int64)) * itemsize if lead_shape else itemsize
    if batch_len is None:
        if batch_bytes is None:
            batch_bytes = _DEFAULT_BATCH_BYTES
        batch_len = max(1, min(n, batch_bytes // max(row_bytes, 1)))
    nbatches = math.ceil(n / batch_len)

    has_missing = bool((codes < 0).any())
    reverse = scan.name == "bfill"
    kw = {"nat": True} if nat else {}

    def apply_carry_codes(table, ccodes):
        safe = jnp.where(ccodes < 0, size, ccodes)
        pad = jnp.zeros(table.shape[:-1] + (1,), table.dtype)
        return jnp.take(jnp.concatenate([table, pad], axis=-1), safe, axis=-1)

    if scan.mode == "apply_binary_op":

        def slab_scan(slab, ccodes, carry, had):
            local = generic_kernel(scan.scan, ccodes, slab, size=size, dtype=dtype, **kw)
            if nat:
                from .kernels import _NAT_INT

                is_nat = slab == jnp.asarray(_NAT_INT, slab.dtype)
                summed = jnp.where(is_nat, jnp.zeros((), slab.dtype), slab)
            else:
                summed = slab
            block = generic_kernel(
                scan.reduction, ccodes, summed, size=size, fill_value=0
            ).astype(local.dtype)
            if carry is None:
                out_slab = local
                new_carry = block
            else:
                out_slab = local + apply_carry_codes(carry, ccodes)
                new_carry = carry + block
            new_had = had
            if nat and scan.scan == "cumsum":
                # non-skipna datetime poisoning: a NaT earlier in the group
                # poisons everything after — sticky per-group channel
                from .kernels import _NAT_INT

                had_slab = generic_kernel(
                    "sum", ccodes, is_nat.astype(jnp.int32), size=size, fill_value=0
                ) > 0
                nat_val = jnp.asarray(_NAT_INT, out_slab.dtype)
                if had is not None:
                    poison_e = apply_carry_codes(had.astype(jnp.int8), ccodes) > 0
                    out_slab = jnp.where(poison_e, nat_val, out_slab)
                    new_had = had | had_slab
                else:
                    new_had = had_slab
                out_slab = jnp.where(local == nat_val, nat_val, out_slab)
            return out_slab, new_carry, new_had

    else:  # ffill / bfill

        def slab_scan(slab, ccodes, carry, has):
            local = generic_kernel(scan.scan, ccodes, slab, size=size, **kw)
            is_float = jnp.issubdtype(slab.dtype, jnp.floating)
            valid_cnt = generic_kernel("nanlen", ccodes, slab, size=size, **kw)
            edge_val = generic_kernel(
                scan.reduction, ccodes, slab, size=size,
                fill_value=jnp.nan if is_float else 0, **kw,
            )
            mask = _nan_mask(local, nat)
            still = ~mask if mask is not None else jnp.zeros(local.shape, bool)
            out_slab = local
            if carry is not None:
                carry_e = apply_carry_codes(carry, ccodes)
                has_e = apply_carry_codes(has.astype(jnp.int8), ccodes) > 0
                out_slab = jnp.where(still & has_e & (ccodes >= 0), carry_e, local)
                new_carry = jnp.where(valid_cnt > 0, edge_val.astype(carry.dtype), carry)
                new_has = has | (valid_cnt > 0)
            else:
                new_carry = edge_val
                new_has = valid_cnt > 0
            return out_slab, new_carry, new_has

    if mesh is not None:
        return _run_mesh_stream_scan(
            scan, loader, codes, size=size, n=n, batch_len=batch_len,
            lead_shape=tuple(lead_shape), dtype=dtype, nat=nat,
            datetime_dtype=datetime_dtype, has_missing=has_missing,
            reverse=reverse, out=out, mesh=mesh, axis_name=axis_name,
            # the wrap views datetimes as int64; no second loader probe
            probe_dtype=np.dtype("int64") if nat else probe.dtype,
            data_probe=probe,
        )

    from .pipeline import SlabStager, maybe_donate, stream_slabs
    from .resilience import (
        StreamCheckpointer,
        StreamCounters,
        device_restore,
        dispatch_slab,
    )

    init_fn, step_fn = _step_cached(
        ("scan-step", scan.name, size, nat, str(dtype), has_missing),
        lambda: (
            jax.jit(lambda slab, ccodes: slab_scan(slab, ccodes, None, None)),
            # the per-group carry (and the sticky NaT/has channel) is
            # donated: updated in place across slabs
            maybe_donate(slab_scan, donate_argnums=(2, 3)),
        ),
    )

    counters = StreamCounters()
    stager = SlabStager(
        loader, codes, n=n, batch_len=batch_len, lead_shape=tuple(lead_shape),
        pad=False, counters=counters,
    )
    # checkpointing a scan needs the already-emitted slabs to survive the
    # kill, which only a writer gives us (the in-memory result array dies
    # with the run) — so snapshots are taken only on the out= path
    ckpt = StreamCheckpointer.for_stream(
        kind="scan", name=_scan_ckpt_id(scan), n=n, batch_len=batch_len, size=size,
        codes=codes, lead_shape=tuple(lead_shape),
        extra=(nat, str(dtype), has_missing, reverse), data_probe=probe,
        counters=counters, enabled=out is not None,
    )
    carry = had = None
    skip = 0
    snap = ckpt.restore()
    if snap is not None:
        skip = snap.slabs_done
        carry, had = device_restore(snap.payload)
    done = skip

    result_arr = None

    def apply_scan(cur, sb):
        c, h = cur
        if c is None:
            out_slab, c, h = init_fn(sb.data, sb.codes)
        else:
            out_slab, c, h = step_fn(sb.data, sb.codes, c, h)
        nonlocal result_arr
        result_arr = _emit_scan_slab(
            out_slab, sb.codes_host, sb.start, sb.stop, nat=nat,
            datetime_dtype=datetime_dtype, has_missing=has_missing, out=out,
            result_arr=result_arr, lead_shape=lead_shape, n=n,
        )
        return c, h

    with timed(f"stream-scan [{scan.name}] {nbatches} slab(s)"):
        # prefetch overlaps the next load with this slab's compute+emit
        # (the emit's host conversion syncs per slab, so no dispatch
        # throttle is needed here); pad=False keeps the single-device scan
        # contract of ragged tail slabs. An OOM-split sub-slab stays ragged
        # too, and splits run in reverse span order for bfill so the carry
        # still flows against the stream.
        for sl in stream_slabs(
            loader, codes, n=n, batch_len=batch_len, lead_shape=tuple(lead_shape),
            pad=False, reverse=reverse, label=f"scan[{scan.name}]",
            skip=skip, counters=counters, stager=stager,
        ):
            carry, had = dispatch_slab(
                apply_scan, (carry, had), sl, stager=stager, counters=counters,
                reverse=reverse,
            )
            done += 1
            ckpt.tick(lambda: (carry, had), slabs_done=done)
    ckpt.done()
    if out is not None:
        return None
    return result_arr


def _scan_ckpt_id(scan) -> str:
    """Resolved Scan identity for the checkpoint key (the scan-side
    analogue of the reduce path's ``repr(_agg_cache_key(agg))``): a custom
    Scan sharing a builtin's name must MISS the builtin's snapshot, never
    silently fold into it. Callable binary_ops carry id(), so cross-process
    resume of a custom scan misses too — a fresh run, never a mismatch."""
    op = scan.binary_op
    op_id = None if op is None else (getattr(op, "__qualname__", repr(op)), id(op))
    return repr((
        scan.name, scan.scan, scan.reduction, op_id, scan.identity,
        scan.mode, scan.preserves_dtype,
    ))


def _emit_scan_slab(out_slab, ccodes_np, s, e, *, nat, datetime_dtype,
                    has_missing, out, result_arr, lead_shape, n):
    """Trim/mask/view one scanned slab and hand it to the writer or the
    result array — the ONE emit step both scan loops (single-device and
    mesh) share, so missing-label masking and the datetime view cannot
    drift between them. Returns the (possibly just-allocated) result
    array."""
    res = np.asarray(out_slab)[..., : e - s]
    if has_missing:
        from .scan import _mask_positions

        res = np.asarray(_mask_positions(res, ccodes_np[: e - s] < 0, nat=nat))
    if nat:
        res = res.astype("int64").view(datetime_dtype)
    if out is not None:
        out(s, e, res)
        return result_arr
    if result_arr is None:
        result_arr = np.empty(tuple(lead_shape) + (n,), res.dtype)
    result_arr[..., s:e] = res
    return result_arr


def _run_mesh_stream_scan(scan, loader, codes, *, size, n, batch_len, lead_shape,
                          dtype, nat, datetime_dtype, has_missing, reverse,
                          out, mesh, axis_name, probe_dtype, data_probe=None):
    """streaming × mesh scan: each slab runs the distributed Blelloch with
    cross-slab carry I/O (parallel.scan.build_stream_scan_step)."""
    import math

    import jax.numpy as jnp

    from .profiling import timed

    axes, _ndev, batch_len, _spec_entry, _sspec, _cspec, slab_shard, codes_shard = (
        _mesh_stream_layout(mesh, axis_name, batch_len, len(lead_shape))
    )
    nbatches = math.ceil(n / batch_len)

    from .parallel.scan import build_stream_scan_step

    step = _step_cached(
        ("scan-mesh-step", scan.name, size, nat, str(dtype), axes, mesh,
         len(lead_shape)),
        lambda: build_stream_scan_step(
            scan, size=size, mesh=mesh, axis_name=axes, nat=nat,
            lead_ndim=len(lead_shape),
        ),
    )

    # carry init needs the working dtype up front: the promoted/cast slab
    # dtype for cumsum sums and ffill edge values
    work_dtype = np.dtype(dtype) if dtype is not None else probe_dtype
    c0 = jnp.zeros(lead_shape + (size,), work_dtype)
    c1 = jnp.zeros(lead_shape + (size,), jnp.int8)  # had-NaT / has-value

    if dtype is not None:
        # fold the promotion cast into the (possibly prefetched) staging
        base_loader = loader
        loader = lambda s, e: np.asarray(base_loader(s, e)).astype(work_dtype, copy=False)

    from .pipeline import SlabStager, stream_slabs
    from .resilience import (
        StreamCheckpointer,
        StreamCounters,
        device_restore,
        dispatch_slab,
    )

    counters = StreamCounters()
    stager = SlabStager(
        loader, codes, n=n, batch_len=batch_len, lead_shape=tuple(lead_shape),
        slab_shard=slab_shard, codes_shard=codes_shard, counters=counters,
    )
    # writer-gated for the same reason as the single-device scan; the carry
    # pair is replicated (out_specs P()), so restore needs no resharding
    ckpt = StreamCheckpointer.for_stream(
        kind="scan-mesh", name=_scan_ckpt_id(scan), n=n, batch_len=batch_len, size=size,
        codes=codes, lead_shape=tuple(lead_shape),
        extra=(nat, str(dtype), has_missing, reverse, tuple(axes)),
        data_probe=data_probe, counters=counters, enabled=out is not None,
    )
    skip = 0
    snap = ckpt.restore()
    if snap is not None:
        skip = snap.slabs_done
        c0, c1 = device_restore(snap.payload)
    done = skip

    result_arr = None

    def apply_scan(cur, sb):
        a, b = cur
        out_sh, a, b = step(sb.data, sb.codes, a, b)
        nonlocal result_arr
        result_arr = _emit_scan_slab(
            out_sh, sb.codes_host, sb.start, sb.stop, nat=nat,
            datetime_dtype=datetime_dtype, has_missing=has_missing, out=out,
            result_arr=result_arr, lead_shape=lead_shape, n=n,
        )
        return a, b

    with timed(f"stream-scan-mesh [{scan.name}] {nbatches} slab(s)"):
        # the emit's host conversion syncs per slab (no throttle needed);
        # prefetch overlaps the next slab's load+scatter with it. No OOM
        # splitting here (stager=None): the distributed Blelloch carry
        # exchange is not sub-slab associative under padding, so an OOM
        # surfaces rather than risking a wrong fold — retry, checkpoint,
        # and the fault hook still apply.
        for sl in stream_slabs(
            loader, codes, n=n, batch_len=batch_len, lead_shape=tuple(lead_shape),
            slab_shard=slab_shard, codes_shard=codes_shard, reverse=reverse,
            label=f"scan-mesh[{scan.name}]", skip=skip, counters=counters,
            stager=stager,
        ):
            c0, c1 = dispatch_slab(
                apply_scan, (c0, c1), sl, counters=counters, reverse=reverse,
            )
            done += 1
            ckpt.tick(lambda: (c0, c1), slabs_done=done)
    ckpt.done()
    if out is not None:
        return None
    return result_arr


def _stream_quantile(agg: Aggregation, loader, codes, *, size: int, n: int,
                     batch_len: int, lead_shape: tuple, probe_dtype,
                     mesh=None, axis_name="data", data_probe=None):
    """Out-of-core EXACT quantile/median: the radix-select bisection
    (kernels._radix_select) only ever consumes per-group COUNTS, and counts
    accumulate slab by slab — so order statistics stream in ``nbits + 1``
    full passes over the loader (1 count pass + one per key bit; 33 for
    f32, 65 for f64). The reference cannot do this at all: its chunked
    quantile requires whole groups per block (dask.py's blockwise
    constraint). Bit-identical to the eager select path — same counts,
    same bit-by-bit reconstruction.

    IO cost is the point to understand: the data is read ``nbits + 1``
    times. For a zarr/S3 loader that is ``nbits + 1`` remote sweeps — an
    explicit, documented trade for never materializing the array.
    """
    import math

    import jax
    import jax.numpy as jnp

    from .kernels import (
        _from_leading,
        _nan_mask,
        _quantile_alpha_beta,
        _quantile_rank_sets,
        _radix_pass_count,
        _radix_update,
        _safe_codes,
        _seg,
        _to_leading,
        _uint_type,
        _uint_to_value,
        _valid_keys,
        _counts,
    )
    from .profiling import timed

    skipna = agg.name.startswith("nan")
    fkw = dict(agg.finalize_kwargs)
    if agg.name in ("median", "nanmedian"):
        q, method = 0.5, "linear"
    else:
        if "q" not in fkw:
            raise TypeError(f"{agg.name} requires finalize_kwargs={{'q': ...}}")
        q = fkw["q"]
        method = fkw.get("method", "linear")
    qs = np.atleast_1d(np.asarray(q, dtype=np.float64))
    scalar_q = np.ndim(q) == 0
    alpha, beta = _quantile_alpha_beta(method)

    axes = None
    slab_shard = codes_shard = None
    shard_quantum = 1
    if mesh is not None:
        # out-of-core AND distributed: slabs scatter over the mesh and each
        # counting pass psums — the per-group bisection state is replicated,
        # so the two compositions stack with no new machinery. The layout
        # comes from the SAME helper the reduce runtime uses.
        axes, _ndev, batch_len, _spec_entry, sspec, cspec, slab_shard, codes_shard = (
            _mesh_stream_layout(mesh, axis_name, batch_len, len(lead_shape))
        )
        shard_quantum = _ndev
    nbatches = math.ceil(n / batch_len)

    from .pipeline import DispatchThrottle, SlabStager, stream_slabs
    from .resilience import (
        StreamCheckpointer,
        StreamCounters,
        device_restore,
        dispatch_slab,
    )

    counters = StreamCounters()
    # ONE stager for all nbits + 1 passes: the retry policy and the loader
    # dtype contract hold across the whole multi-pass run
    stager = SlabStager(
        loader, codes, n=n, batch_len=batch_len, lead_shape=tuple(lead_shape),
        slab_shard=slab_shard, codes_shard=codes_shard, counters=counters,
    )

    def slabs(pass_label, skip=0):
        # each counting pass is one full pipelined sweep over the loader:
        # prefetch restarts per pass (the loader contract is random-access)
        return stream_slabs(
            loader, codes, n=n, batch_len=batch_len, lead_shape=tuple(lead_shape),
            slab_shard=slab_shard, codes_shard=codes_shard,
            label=f"quantile[{agg.name}] {pass_label}",
            skip=skip, counters=counters, stager=stager,
        )

    # resolved float dtype: same rule as the eager kernel (probe_dtype comes
    # from the caller's one probe — no second remote chunk read). MUST be
    # the CANONICALIZED dtype: with x64 off jax downcasts f64 slabs to f32,
    # and keying nbits off the host dtype would run 65 passes on uint32
    # keys — out-of-range shifts (implementation-defined on TPU) and double
    # the loader IO
    from jax.dtypes import canonicalize_dtype

    if np.issubdtype(probe_dtype, np.floating):
        fdtype = canonicalize_dtype(probe_dtype)
    else:
        fdtype = jnp.float64 if utils.x64_enabled() else jnp.float32
    ut = _uint_type(fdtype)
    nbits = jnp.dtype(ut).itemsize * 8
    cdtype = jnp.float32 if n < 2**24 else jnp.int32

    def prep(slab):
        data = _to_leading(slab)
        if data.dtype != fdtype:
            data = data.astype(fdtype)
        return data

    def _build_passes():
        def count_pass(nn, hasnan, slab, ccodes):
            data = prep(slab)
            sc = _safe_codes(ccodes, size)
            mask = _nan_mask(data)
            nn_add = _counts(sc, size, mask=mask)
            hn = _seg("max", (~mask).astype(jnp.int8), sc, size) if (
                not skipna and mask is not None
            ) else None
            if axes is not None:
                nn_add = jax.lax.psum(nn_add, axes)
                if hn is not None:
                    hn = jax.lax.pmax(hn, axes)
            nn = nn + nn_add
            if hn is not None:
                hasnan = jnp.maximum(hasnan, hn)
            return nn, hasnan

        def bit_pass(cnt, prefix, slab, ccodes, bshift):
            data = prep(slab)
            keys = _valid_keys(data, _nan_mask(data))
            add = _radix_pass_count(
                keys, _safe_codes(ccodes, size), size, prefix, bshift, cdtype
            )
            if axes is not None:
                add = jax.lax.psum(add, axes)
            return cnt + add

        # pass accumulators are donated (pipeline.maybe_donate): nn/hasnan
        # and the per-bit cnt update in place across slabs, and the
        # bisection state updates in place across bits. prefix/rank are NOT
        # donated into bit_pass — prefix is re-read for every slab of a pass
        from .pipeline import maybe_donate

        if axes is None:
            return (
                maybe_donate(count_pass, donate_argnums=(0, 1)),
                maybe_donate(bit_pass, donate_argnums=(0,)),
                maybe_donate(_radix_update, donate_argnums=(0, 1)),
            )

        # mesh: slab/codes sharded in (the SAME sspec/cspec the staging
        # pipeline uses); bisection state replicated in AND out
        from jax.sharding import PartitionSpec as P

        from .parallel.mesh import shard_map

        return (
            maybe_donate(shard_map(
                count_pass, mesh=mesh,
                in_specs=(P(), P(), sspec, cspec), out_specs=P(),
                check_vma=False,
            ), donate_argnums=(0, 1)),
            maybe_donate(shard_map(
                bit_pass, mesh=mesh,
                in_specs=(P(), P(), sspec, cspec, P()), out_specs=P(),
                check_vma=False,
            ), donate_argnums=(0,)),
            maybe_donate(_radix_update, donate_argnums=(0, 1)),
        )

    count_pass, bit_pass, update = _step_cached(
        ("quantile-pass", size, str(fdtype), str(cdtype), skipna,
         None if axes is None else (axes, mesh), len(lead_shape)),
        _build_passes,
    )

    # multi-pass checkpointing: phase 0 = the count pass (payload nn/hasnan),
    # phase 1+i = bit pass i (payload carries the full bisection state —
    # nn/hasnan for the finalize, prefix/rank for the bisection, cnt for the
    # pass in flight). The rank-set meta is NOT checkpointed: it re-derives
    # deterministically from the restored nn.
    ckpt = StreamCheckpointer.for_stream(
        kind="quantile", name=agg.name, n=n, batch_len=batch_len, size=size,
        codes=codes, lead_shape=tuple(lead_shape),
        mesh_key=None if axes is None else tuple(axes),
        extra=(tuple(np.asarray(qs).tolist()), method, str(fdtype)),
        data_probe=data_probe, counters=counters,
    )
    snap = ckpt.restore()
    phase0, skip0 = (0, 0) if snap is None else (snap.phase, snap.slabs_done)

    trail = lead_shape  # leading layout puts the reduce axis first
    throttle = DispatchThrottle()

    def apply_count(st, sb):
        return count_pass(st[0], st[1], sb.data, sb.codes)

    with timed(f"stream-quantile [{agg.name}] {nbits + 1} passes x {nbatches} slab(s)"):
        # counts accumulate EXACTLY in int32 (f32 would drift past 2^24 and
        # shift rank positions — the bit-identity claim rests on this)
        bit0, bit_skip, cnt0 = 0, 0, None
        if phase0 == 0:
            if snap is not None:
                nn, hasnan = device_restore(snap.payload)
            else:
                nn = jnp.zeros((size,) + trail, jnp.int32)
                hasnan = jnp.zeros((size,) + trail, jnp.int8)
            done = skip0
            for sl in slabs("count", skip=skip0):
                nn, hasnan = dispatch_slab(
                    apply_count, (nn, hasnan), sl, stager=stager,
                    counters=counters, shard_quantum=shard_quantum,
                    highcard_hint=_highcard_oom_hint(agg, size, None),
                )
                throttle.tick(nn)
                done += 1
                ckpt.tick(lambda: (nn, hasnan), slabs_done=done, phase=0)
        else:
            nn, hasnan, prefix, rank, cnt0 = device_restore(snap.payload)
            bit0, bit_skip = phase0 - 1, skip0

        idx_dtype = jnp.float64 if utils.x64_enabled() else jnp.float32
        nnf = nn.astype(idx_dtype)
        ranks, meta = _quantile_rank_sets(qs, nnf, method, alpha, beta)
        m = ranks.shape[0]
        if phase0 == 0:
            prefix = jnp.zeros((m, size) + trail, ut)
            rank = ranks.astype(jnp.int32)
        for i in range(bit0, nbits):
            bshift = jnp.asarray(nbits - 1 - i, ut)
            if i == bit0 and cnt0 is not None:
                cnt, skip_i = cnt0, bit_skip
            else:
                cnt, skip_i = jnp.zeros((m, size) + trail, jnp.int32), 0

            def apply_bit(st, sb):
                return bit_pass(st, prefix, sb.data, sb.codes, bshift)

            done = skip_i
            for sl in slabs(f"bit {i}", skip=skip_i):
                cnt = dispatch_slab(
                    apply_bit, cnt, sl, stager=stager, counters=counters,
                    shard_quantum=shard_quantum,
                    highcard_hint=_highcard_oom_hint(agg, size, None),
                )
                throttle.tick(cnt)
                done += 1
                ckpt.tick(
                    lambda: (nn, hasnan, prefix, rank, cnt),
                    slabs_done=done, phase=1 + i,
                )
            prefix, rank = update(prefix, rank, cnt, bshift)
    ckpt.done()

    selected = _uint_to_value(prefix, fdtype)
    group_has_nan = (hasnan > 0) if not skipna else None
    fv = agg.final_fill_value
    try:
        fv_arr = jnp.asarray(np.nan if fv is None else fv, fdtype)
    except (TypeError, ValueError):
        fv_arr = jnp.asarray(jnp.nan, fdtype)
    threshold = max(agg.min_count, 1)

    from .kernels import _quantile_interp_value

    outs = []
    for k, _qi in enumerate(qs):
        val = _quantile_interp_value(method, meta[k], selected, fdtype)
        val = jnp.where(nn < threshold, fv_arr, val)
        if group_has_nan is not None:
            val = jnp.where(group_has_nan, jnp.asarray(jnp.nan, fdtype), val)
        outs.append(_from_leading(val))
    if scalar_q:
        return outs[0]
    return jnp.stack(outs, axis=0)


def _argmerge_better(va, vb, arg_of_max: bool):
    import jax.numpy as jnp

    better = (vb > va) if arg_of_max else (vb < va)
    if jnp.issubdtype(va.dtype, jnp.floating):
        # NaN-propagating semantics: a NaN extreme wins over a number
        better = better | (jnp.isnan(vb) & ~jnp.isnan(va))
    return better


def _pair_merge(op, a, b, nat: bool = False):
    """Sequential form of the mesh collectives (parallel/mapreduce.py):
    psum -> add, pmax -> maximum, the var triple -> the Chan update
    (reference _var_combine, aggregations.py:392-451). ``nat`` re-injects
    the NaT marker through min/max exactly as _combine_simple does."""
    import jax.numpy as jnp

    if op in ("max", "min") and nat and jnp.issubdtype(a.dtype, jnp.signedinteger):
        # the signedinteger guard matches _combine_simple
        # (parallel/mapreduce.py): bool intermediates (the 'all'/'any'
        # combines) must NOT compare against the int64 marker — the cast
        # marker is True and would absorb every merge
        m = jnp.maximum(a, b) if op == "max" else jnp.minimum(a, b)
        marker = jnp.asarray(np.iinfo(np.int64).min, a.dtype)
        return jnp.where((a == marker) | (b == marker), marker, m)
    if op == "var":
        m2a, ta, na = a.arrays
        m2b, tb, nb = b.arrays
        nab = na + nb
        tab = ta + tb
        mua = ta / jnp.where(na > 0, na, 1)
        mub = tb / jnp.where(nb > 0, nb, 1)
        muab = tab / jnp.where(nab > 0, nab, 1)
        m2 = m2a + m2b + na * (mua - muab) ** 2 + nb * (mub - muab) ** 2
        return MultiArray((m2, tab, nab))
    if op == "sum":
        return a + b
    if op == "prod":
        return a * b
    if op == "max":
        return jnp.maximum(a, b)
    if op == "min":
        return jnp.minimum(a, b)
    if callable(op):
        # the mesh contract: op(stacked) over the shard axis — here the
        # "shards" are the two accumulation halves; leaf-wise for pytrees
        if isinstance(a, MultiArray):
            return op(
                MultiArray(tuple(jnp.stack([x, y]) for x, y in zip(a.arrays, b.arrays)))
            )
        return op(jnp.stack([a, b]))
    raise NotImplementedError(f"streaming merge for combine op {op!r}")
