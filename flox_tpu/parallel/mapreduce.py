"""Sharded groupby reductions: one SPMD program per aggregation (L5).

The reference's three dask execution methods (core.py:89, dask.py:325-573)
map onto mesh programs as follows:

* ``map-reduce``: shard-local ``chunk_reduce`` producing dense (size,)
  intermediates, then XLA collectives as the tree combine — ``psum`` for
  additive intermediates (the reference's ``_simple_combine``,
  dask.py:90-144), ``pmax``/``pmin`` for extrema, a two-phase psum for the
  variance triple (the collective form of the Chan merge the reference does
  pairwise in ``_var_combine``, aggregations.py:392-451), and
  all_gather+fold for order-dependent tails (first/last/prod — the
  reference's ``_grouped_combine`` cases, dask.py:233-317).
* ``cohorts``: ``psum_scatter`` distributes *group ownership* — each device
  combines and finalizes ``size/ndev`` groups, then the result is
  all-gathered. Communication drops from O(size × ndev) to O(size), the
  same economics that motivate the reference's cohort graph surgery
  (cohorts.py:109-301) — but as a single collective, not N subgraphs.
* ``blockwise``: no combine at all — valid when each group's members are
  entirely within one shard (after rechunk.reshard_for_blockwise); each shard
  finalizes its own groups and owners are selected by nonzero counts
  (parity: dask.py:520-541). This is also how order statistics
  (median/quantile/mode) run on a mesh, since they need whole groups.

Everything here is traced under one ``jax.jit``: factorized codes go in,
the finalized dense result comes out, and XLA overlaps the per-shard
reduction with the collectives.
"""

from __future__ import annotations

import functools
from time import perf_counter
from typing import Any

import numpy as np

from .. import utils
from ..aggregations import Aggregation
from ..cache import LRUCache
from ..multiarray import MultiArray
from .mesh import axis_size, make_mesh, shard_map

_BIG = np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# local building blocks (traced inside shard_map)
# ---------------------------------------------------------------------------


def _local_chunk(agg: Aggregation, codes_sh, arr_sh, size: int, nat: bool):
    """Run the agg's chunk kernels on this shard -> list of intermediates.

    Chunk entries may be kernel names or user callables with the plugin
    signature ``f(group_idx, array, *, axis, size, fill_value, dtype, **kw)``
    (the reference's custom-Aggregation contract, aggregations.py:161-301).
    """
    from ..aggregations import FusedAggregation, fused_chunk_stats
    from ..kernels import generic_kernel

    if isinstance(agg, FusedAggregation):
        # the multi-statistic plan has its own executor: deduplicated legs,
        # megakernel-eligible subsets collapsed into one Pallas pass
        return fused_chunk_stats(agg, codes_sh, arr_sh, size=size, engine="jax")

    inters = []
    fills = agg.fill_value.get("intermediate", ())
    for entry, fv in zip(agg.chunk, list(fills) + [None] * len(agg.chunk)):
        if isinstance(entry, tuple):
            name, extra = entry[0], dict(entry[1])
        else:
            name, extra = entry, {}
        if nat:
            extra["nat"] = True
        if callable(name):
            inters.append(name(codes_sh, arr_sh, size=size, fill_value=fv, **extra))
            continue
        if name in ("sum", "nansum", "prod", "nanprod", "sum_of_squares", "nansum_of_squares"):
            # bf16/f16 intermediates must travel and psum in the f32
            # accumulator; the cast back to the final dtype happens once,
            # at finalize (kernels._acc_dtype)
            extra["keep_acc"] = True
        extra.update(agg.finalize_kwargs if name.startswith("var_chunk") else {})
        inters.append(
            generic_kernel(name, codes_sh, arr_sh, size=size, fill_value=fv, **extra)
        )
    return inters


def _local_counts(codes_sh, arr_sh, size: int, skipna: bool, nat: bool):
    from ..kernels import generic_kernel

    func = "nanlen" if skipna else "len"
    kw = {"nat": True} if nat else {}
    return generic_kernel(func, codes_sh, arr_sh, size=size, **kw)


#: what the one-pass primitive leaves in a group no element reached, per leg
_ONEPASS_EMPTY = {
    "sum": 0, "nansum": 0, "len": 0, "nanlen": 0,
    "min": np.inf, "nanmin": np.inf, "max": -np.inf, "nanmax": -np.inf,
}


def _local_stats(agg: Aggregation, codes_sh, arr_sh, size: int, count_skipna: bool, nat: bool):
    """This shard's ``(counts, chunk intermediates)``: what
    :func:`_local_counts` and :func:`_local_chunk` return, bit for bit and
    dtype for dtype.

    When the count and every chunk leg are legs ``kernels.fused_segment_stats``
    serves, ONE pass computes them all: the sum kernel's NaN markers give
    the non-NaN count as ``rowcount - nan_c``, so no (N, ...) int32 count
    scatter runs. Where the primitive declines (int data, a scatter policy,
    too many groups, a leg such as ``var_chunk``, counts alone) the per-leg
    kernels run unchanged. A fused multi-statistic plan keeps its own
    one-pass executor (``fused_chunk_stats``) beside the count kernel."""
    import jax.numpy as jnp

    from .. import telemetry
    from ..aggregations import FusedAggregation
    from ..kernels import _FUSABLE_LEG_NAMES, _acc_dtype, fused_segment_stats

    legs = tuple(agg.chunk)
    fills = tuple(agg.fill_value.get("intermediate", ())) + (None,) * len(legs)
    count_name = "nanlen" if count_skipna else "len"
    got = None
    if (
        not isinstance(agg, FusedAggregation)
        and all(isinstance(leg, str) and leg in _FUSABLE_LEG_NAMES for leg in legs)
        and all(fv is None or fv == _ONEPASS_EMPTY[leg] for leg, fv in zip(legs, fills))
    ):
        want = tuple(dict.fromkeys(legs + (count_name,)))
        got = fused_segment_stats(codes_sh, arr_sh, size=size, want=want)
    if got is None:
        telemetry.count("mesh.local_perleg")
        return (
            _local_counts(codes_sh, arr_sh, size, count_skipna, nat),
            _local_chunk(agg, codes_sh, arr_sh, size, nat),
        )
    telemetry.count("mesh.local_onepass")

    def as_leg(name):
        # the per-leg dtypes: int32 counts (exact: the primitive refuses
        # N >= 2**24), sums in the accumulator (keep_acc), min/max in the data's
        if name in ("len", "nanlen"):
            return got[name].astype(jnp.int32)
        if name in ("sum", "nansum"):
            return got[name].astype(_acc_dtype(arr_sh.dtype))
        return got[name].astype(arr_sh.dtype)

    return as_leg(count_name), [as_leg(leg) for leg in legs]


def _local_firstlast(codes_sh, arr_sh, size: int, *, skipna: bool, last: bool, nat: bool, offset):
    """(value, global position) per group for the first/last combine."""
    import jax
    import jax.numpy as jnp

    from ..kernels import _from_leading, _iota_like, _nan_mask, _safe_codes, _seg, _to_leading

    codes = _safe_codes(codes_sh, size)
    data = _to_leading(arr_sh)
    mask = _nan_mask(data, nat) if skipna else None
    iota = _iota_like(data) + offset  # global positions
    if mask is not None:
        iota = jnp.where(mask, iota, -1 if last else _BIG)
    pos = _seg("max" if last else "min", iota, codes, size)
    ok = (pos >= 0) & (pos < _BIG)
    local_idx = jnp.clip(pos - offset, 0, data.shape[0] - 1)
    val = jnp.take_along_axis(data, local_idx, axis=0)
    # positions from other shards will be resolved by the combine; mark
    # invalid local picks so they lose
    pos = jnp.where(ok, pos, -1 if last else _BIG)
    return _from_leading(val), _from_leading(pos)


# ---------------------------------------------------------------------------
# combines (collectives)
# ---------------------------------------------------------------------------


def _combine_simple(op: str, x, axis_name: str, nat: bool = False):
    import jax
    import jax.numpy as jnp

    if op == "sum":
        return jax.lax.psum(x, axis_name)
    if op in ("max", "min"):
        out = jax.lax.pmax(x, axis_name) if op == "max" else jax.lax.pmin(x, axis_name)
        # XLA's all-reduce max/min DROPS NaN; numpy's min/max propagate it.
        # Re-inject the missing marker where any shard's intermediate had it
        # (NaN for floats, INT64_MIN==NaT for datetime views).
        if jnp.issubdtype(x.dtype, jnp.floating):
            has_nan = jax.lax.psum(jnp.isnan(x).astype(jnp.int32), axis_name) > 0
            out = jnp.where(has_nan, jnp.asarray(jnp.nan, out.dtype), out)
        elif nat and jnp.issubdtype(x.dtype, jnp.signedinteger):
            marker = jnp.asarray(np.iinfo(np.int64).min, dtype=x.dtype)
            has_nat = jax.lax.psum((x == marker).astype(jnp.int32), axis_name) > 0
            out = jnp.where(has_nat, marker, out)
        return out
    if op == "prod":
        gathered = jax.lax.all_gather(x, axis_name)  # (ndev, size, ...)
        return gathered.prod(axis=0)
    raise ValueError(f"Unknown combine op {op!r}")


def _combine_var(ma: MultiArray, axis_name: str):
    """Collective Chan merge: two psums instead of pairwise host folds."""
    import jax
    import jax.numpy as jnp

    m2, total, n = ma.arrays
    big_n = jax.lax.psum(n, axis_name)
    big_t = jax.lax.psum(total, axis_name)
    mu = big_t / jnp.where(big_n > 0, big_n, 1)
    mu_d = total / jnp.where(n > 0, n, 1)
    adj = n * (mu_d - mu) ** 2
    big_m2 = jax.lax.psum(m2 + adj, axis_name)
    return MultiArray((big_m2, big_t, big_n))


def _combine_arg(val, idx, axis_name: str, arg_of_max: bool, nat: bool = False):
    import jax
    import jax.numpy as jnp

    gv = _combine_simple("max" if arg_of_max else "min", val, axis_name, nat=nat)
    hit = val == gv
    if jnp.issubdtype(val.dtype, jnp.floating):
        # NaN-propagating argreductions: the winning value may be NaN, and
        # NaN != NaN — shards whose extreme is NaN must still contend
        hit = hit | (jnp.isnan(val) & jnp.isnan(gv))
    cand = jnp.where(hit & (idx >= 0), idx, _BIG)
    gidx = jax.lax.pmin(cand, axis_name)
    return gv, jnp.where(gidx < _BIG, gidx, -1)


def _combine_firstlast(val, pos, axis_name: str, last: bool):
    import jax
    import jax.numpy as jnp

    vals = jax.lax.all_gather(val, axis_name)  # (ndev, ..., size)
    poss = jax.lax.all_gather(pos, axis_name)
    pick = jnp.argmax(poss, axis=0) if last else jnp.argmin(poss, axis=0)
    val_g = jnp.take_along_axis(vals, pick[None], axis=0)[0]
    pos_g = jnp.take_along_axis(poss, pick[None], axis=0)[0]
    ok = (pos_g >= 0) & (pos_g < _BIG)
    return val_g, ok


def _combine_intermediates(agg: Aggregation, inters, axis_name, nat: bool):
    """Cross-shard combine of dense per-shard intermediates.

    ``inters``: [val, global_idx] for argreductions, [val, pos] for
    first/last, else one entry per ``agg.combine`` op. The ONE place the
    combine contract lives — shared by the map-reduce program and the
    streaming mesh runtime's final combine (streaming.py), so the NaT
    re-injection rule, the user-fold gather shape, and the Chan merge
    cannot drift between the two.
    """
    import jax

    skipna = agg.name.startswith("nan") or agg.name == "count"
    nat_markers = nat and not skipna
    if agg.reduction_type == "argreduce":
        gv, garg = _combine_arg(
            inters[0], inters[1], axis_name,
            arg_of_max="max" in str(agg.chunk[1]), nat=nat_markers,
        )
        return [gv, garg]
    if agg.combine in (("first",), ("last",)):
        val_g, _ok = _combine_firstlast(
            inters[0], inters[1], axis_name, last=agg.combine == ("last",)
        )
        return [val_g]
    combined = []
    for inter, op in zip(inters, agg.combine):
        if op == "var":
            combined.append(_combine_var(inter, axis_name))
        elif callable(op):
            # general combine for user Aggregations (the reference's
            # _grouped_combine role, dask.py:233-317): gather every
            # shard's dense intermediate and hand the stack to the user
            # fold — contract: op(stacked) with stacked (ndev, ..., size)
            # -> (..., size). Leaf-wise over MultiArray pytrees.
            if isinstance(inter, MultiArray):
                gathered = MultiArray(
                    tuple(jax.lax.all_gather(a, axis_name) for a in inter.arrays)
                )
            else:
                gathered = jax.lax.all_gather(inter, axis_name)
            combined.append(op(gathered))
        else:
            combined.append(_combine_simple(op, inter, axis_name, nat=nat_markers))
    return combined


def _finalize_combined(agg: Aggregation, combined, counts):
    """Pick/fold the combined intermediates into the result and apply the
    final fill — shared by every mesh program and the streaming runtime."""
    from ..aggregations import FusedAggregation

    if isinstance(agg, FusedAggregation):
        # multi-output: one tuple entry per requested statistic, each with
        # its own presence/fill semantics (the generic counts channel is
        # advisory here — every slot reads its own presence leg)
        return agg.finalize_fused(combined, counts)
    if agg.reduction_type == "argreduce":
        result = combined[1]
    elif agg.finalize is not None:
        result = agg.finalize(*combined, **agg.finalize_kwargs)
    else:
        result = combined[0]
    return _apply_final_fill(result, counts, agg)


# ---------------------------------------------------------------------------
# the SPMD program
# ---------------------------------------------------------------------------


def _pad_to(n: int, multiple: int) -> int:
    return (-n) % multiple


def _norm_axes(axis_name, mesh=None) -> tuple[str, ...]:
    """Accept a single mesh axis name or a tuple (e.g. ("dcn", "ici"));
    validates against the mesh when given."""
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    if mesh is not None:
        missing = [a for a in axes if a not in mesh.shape]
        if missing:
            raise ValueError(f"mesh has no axes {missing}; mesh axes: {tuple(mesh.shape)}")
    return axes


def _flat_axis_index(axes: tuple[str, ...]):
    """Flattened device index across mesh axes, major-to-minor — matches the
    order PartitionSpec((a0, a1)) shards the data axis."""
    import jax

    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * axis_size(a) + jax.lax.axis_index(a)
    return idx


@functools.lru_cache(maxsize=256)
def _cached_mesh_default():
    return make_mesh()


# order statistics whose device kernel can distribute by psum-ing the
# radix-select counting passes (kernels._radix_select axis_name=). mode is
# NOT here: its run-length structure needs contiguous sorted groups.
_DISTRIBUTED_ORDER_STATS = ("median", "nanmedian", "quantile", "nanquantile")


def _is_additive(agg: Aggregation) -> bool:
    """Combines expressible as psum / psum_scatter (the ops the cohorts and
    blocked programs can distribute by group ownership)."""
    return agg.reduction_type != "argreduce" and bool(agg.combine) and all(
        op in ("sum", "var") for op in agg.combine
    )


from ..utils import fmt_bytes  # noqa: E402 — guard-message formatting


def _est_itemsize(dtype) -> int:
    """Accumulator width for the footprint estimate: intermediates travel in
    >= f32 accumulators; complex dtypes keep their full 2x width."""
    return max(4, np.dtype(str(dtype)).itemsize)


def dense_intermediate_bytes(
    lead_elems: int, size: int, dtype, agg: Aggregation, ndev: int
) -> int:
    """Per-device HBM estimate for the dense (..., size) intermediates a
    map-reduce program materializes (VERDICT r3 #6). Counts one buffer per
    chunk leg plus the counts leg; legs whose combine all_gathers (callable
    folds, prod, first/last) cost ndev x their dense size."""
    itemsize = _est_itemsize(dtype)
    per_leg = lead_elems * size * itemsize
    legs = 1  # counts
    # blockwise-only aggs (order statistics) have no chunk/combine legs:
    # one result buffer next to the counts
    ops = agg.combine or ("sum",) * max(1, len(agg.chunk or ()) or 1)
    if agg.combine in (("first",), ("last",)) or agg.reduction_type == "argreduce":
        legs += 2  # (value, position) pair, pmax/pmin combine
        if agg.combine in (("first",), ("last",)):
            legs += 2 * (ndev - 1)  # the pair is all_gathered
        return per_leg * legs
    for op in ops:
        if op == "var":
            legs += 3  # the Chan triple psums leaf-wise
        elif op == "sum" or op in ("max", "min"):
            legs += 1
        else:  # callable user folds and prod travel via all_gather
            legs += ndev
    return per_leg * legs


def sharded_groupby_reduce(
    array: Any,
    codes: Any,
    agg: Aggregation,
    *,
    size: int,
    mesh: Any = None,
    axis_name: str | tuple[str, ...] = "data",
    method: str = "map-reduce",
    nat: bool = False,
) -> Any:
    """Run one grouped reduction as a sharded SPMD program.

    ``array``: (..., N) (host or device), sharded over the trailing axis;
    ``codes``: (N,) int64 with -1 = missing. Returns the finalized dense
    result, replicated: shape (*new_dims, ..., size).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        mesh = _cached_mesh_default()
    axes = _norm_axes(axis_name, mesh)
    ndev = int(np.prod([mesh.shape[a] for a in axes]))

    if agg.blockwise_only and method != "blockwise":
        if agg.name in _DISTRIBUTED_ORDER_STATS:
            # quantile/median DO run distributed here — the radix-select
            # bisection's counting passes psum across shards, so no shard
            # ever needs a whole group (kernels._radix_select). The
            # reference must force blockwise for order statistics
            # (core.py:685-709); this framework does not.
            if method == "cohorts":
                import warnings

                # the caller asked for cohorts BY NAME and is getting a
                # different execution method — that reroute must be
                # visible to them, not buried in a debug log (ADVICE r5)
                warnings.warn(
                    f"method='cohorts' has no ownership win for order "
                    f"statistics; {agg.name!r} runs the distributed "
                    "radix-select 'map-reduce' program instead",
                    UserWarning,
                    stacklevel=2,
                )
            method = "map-reduce"
        else:
            raise NotImplementedError(
                f"{agg.name!r} needs whole groups on one shard; use method='blockwise' "
                "with shard-local groups (rechunk.reshard_for_blockwise prepares that "
                "layout — the reference forces blockwise for these too, core.py:685-709)."
            )

    agg = agg.without_appended_count()  # the mesh programs compute counts themselves

    if nat:
        from ..aggregations import shift_nat_identity_fills

        shift_nat_identity_fills(agg)

    # -- huge-label-space routing (VERDICT r3 #6) --------------------------
    # Estimate the dense per-device intermediate footprint; above the
    # ceiling, additive aggs run the blocked program (every intermediate is
    # (..., size/ndev) from the start, one psum per owner block) and
    # non-additive ones fail actionably instead of OOMing HBM.
    from ..options import OPTIONS

    arr_probe = array if hasattr(array, "shape") else np.asarray(array)
    lead_elems = int(np.prod(arr_probe.shape[:-1])) if arr_probe.ndim > 1 else 1
    est = dense_intermediate_bytes(lead_elems, size, arr_probe.dtype, agg, ndev)
    ceiling = OPTIONS["dense_intermediate_bytes_max"]
    blocked = False
    if est > ceiling and method in ("map-reduce", "cohorts"):
        # blocked peak per device: the replicated dense result (irreducible
        # — the output contract is a full (..., size) array) plus the
        # per-owner-block intermediates, est/ndev. If even that exceeds the
        # ceiling (ndev too small, or the result alone is too big), blocking
        # would proceed straight into the OOM it exists to prevent — raise.
        result_bytes = lead_elems * size * _est_itemsize(arr_probe.dtype)
        blocked_est = result_bytes + est // ndev
        if _is_additive(agg) and blocked_est <= ceiling:
            blocked = True
            method = "cohorts"  # blocked execution lives in the cohorts program
            import logging

            logging.getLogger("flox_tpu.parallel.mapreduce").debug(
                "dense intermediates ~%s exceed dense_intermediate_bytes_max"
                " (%s): using the blocked owner-by-owner program",
                fmt_bytes(est), fmt_bytes(ceiling),
            )
        else:
            how = (
                "its combine cannot be distributed by group ownership"
                if not _is_additive(agg)
                else f"even the blocked owner-by-owner program needs "
                f"~{fmt_bytes(blocked_est)}/device over {ndev} device(s)"
            )
            raise ValueError(
                f"{agg.name!r} over {size} groups needs ~{fmt_bytes(est)} of "
                f"dense (..., size) intermediates per device, above the "
                f"{fmt_bytes(ceiling)} dense_intermediate_bytes_max ceiling, "
                f"and {how}. Options: use engine='sort' "
                "(FLOX_TPU_DEFAULT_ENGINE=sort — intermediates and collectives "
                "then cover only the groups actually present); reduce "
                "expected_groups; shard over more devices; use "
                "method='blockwise' after rechunk.reshard_for_blockwise (whole "
                "groups per shard, no dense combine); or raise "
                "set_options(dense_intermediate_bytes_max=...) if the device "
                "really has the headroom."
            )

    cohort_perm = None
    if method == "cohorts" and not blocked:
        # align psum_scatter ownership tiles with detected cohorts (memoized
        # detection — the auto-method path already ran it on these codes).
        # Blocked runs skip detection: at the group counts that trigger
        # blocking, the host-side bitmask/containment analysis costs more
        # than the locality it buys, and block ownership is already uniform.
        from ..cohorts import chunks_from_shards, find_group_cohorts, ownership_permutation

        codes_np = np.asarray(codes).reshape(-1)
        _, mapping = find_group_cohorts(
            codes_np, chunks_from_shards(codes_np.shape[0], ndev),
            expected_groups=range(size),
        )
        cohort_perm = ownership_permutation(mapping, size, ndev)

    from .. import telemetry
    from ..options import trace_fingerprint

    with telemetry.span("dispatch", method=method, ndev=ndev, size=size):
        arr = utils.asarray_device(array)
        n = int(np.shape(codes)[0])
        pad = _pad_to(n, ndev)

        def padded(c):
            return jnp.concatenate([c, jnp.full((pad,), -1, dtype=jnp.int32)]) if pad else c

        if utils.is_jax_array(codes):
            # pre-staged device codes (a registry put / factorize.Prefactorized
            # feeds its per-shard codes straight in): skip the host round-trip —
            # the put already paid the one H2D
            codes_dev = padded(codes if codes.dtype == jnp.int32 else codes.astype(jnp.int32))
        else:
            # the codes cross as int32; their padding is an eager device op
            # of the same staging
            with telemetry.span("h2d", bytes=4 * n):
                codes_dev = padded(jnp.asarray(np.asarray(codes), dtype=jnp.int32))
        if pad:
            widths = [(0, 0)] * (arr.ndim - 1) + [(0, pad)]
            arr = jnp.pad(arr, widths)
        shard_len = codes_dev.shape[0] // ndev

        # pad the group axis for psum_scatter ownership slicing
        size_pad = size + _pad_to(n=size, multiple=ndev) if method == "cohorts" else size

        spec_entry = axes if len(axes) > 1 else axes[0]
        in_specs = (
            P(*([None] * (arr.ndim - 1) + [spec_entry])),
            P(spec_entry),
        )
        out_specs = P()  # replicated

        cache_key = (
            _agg_cache_key(agg), size, size_pad, method, axes, shard_len, nat,
            mesh, arr.ndim, blocked, trace_fingerprint(),
            None if cohort_perm is None else cohort_perm.tobytes(),
        )
        tm_on = telemetry.enabled()
        if tm_on:
            # cost-ledger baseline: dispatch wall + the jax-compile delta this
            # mesh dispatch provokes (the build path's first run pays the
            # trace+compile; the hit path should read ~0 compiles)
            compiles0 = telemetry.METRICS.get("jax.compiles")
            compile_ms0 = telemetry.METRICS.get("jax.compile_ms")
            t_dispatch0 = perf_counter()

        fn = _PROGRAM_CACHE.get(cache_key)
        if fn is None:
            telemetry.count("cache.program_misses")
            program = _build_program(
                agg, size=size, size_pad=size_pad, method=method, axis_name=axes,
                shard_len=shard_len, nat=nat, cohort_perm=cohort_perm,
                blocked=blocked, ndev=ndev,
            )
            # check_vma=False: outputs are replicated by construction (psum /
            # all_gather), but the static checker cannot infer that through
            # argmin/take_along_axis owner-selection.
            fn = jax.jit(
                shard_map(
                    program, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
                )
            )
            # bounded LRU: a cold key past capacity evicts ONE stale program
            # (counted in cache.stats()["evictions"]), never the whole hot set
            _PROGRAM_CACHE[cache_key] = fn
            from ..profiling import timed

            # jit/shard_map construction is lazy — trace + XLA compile happen
            # on the first call, so THAT is what the build timer must wrap
            with timed(f"sharded program trace+compile+first-run [{agg.name}/{method}]"):
                with telemetry.span(
                    "program-build", agg=agg.name, method=method, ndev=ndev, size=size
                ):
                    result = fn(arr, codes_dev)
        else:
            telemetry.count("cache.program_hits")
            with telemetry.span("mesh-dispatch", agg=agg.name, method=method, ndev=ndev, size=size):
                result = fn(arr, codes_dev)
    if tm_on:
        # observed wall snapshotted BEFORE the card analysis: its
        # lower+compile must not bill as device time (it would read as
        # drift on the first dispatch)
        dispatch_ms = (perf_counter() - t_dispatch0) * 1e3
        prog = f"mesh[{agg.name}/{method}]"
        telemetry.sample_hbm(program=prog)
        # analytical card of the SPMD program (costmodel plane): lowering
        # re-enters the same shard_map closure, so the card reflects the
        # per-device program actually dispatched
        from .. import costmodel

        costmodel.ensure_card(prog, fn, (arr, codes_dev))
        telemetry.observe_cost(
            prog,
            device_ms=dispatch_ms,
            nbytes=int(getattr(arr, "nbytes", 0))
            + int(getattr(codes_dev, "nbytes", 0)),
            compiles=int(telemetry.METRICS.get("jax.compiles") - compiles0),
            compile_ms=telemetry.METRICS.get("jax.compile_ms") - compile_ms0,
        )
    return result


#: compiled shard_map programs, LRU-bounded: get() renews recency, inserts
#: past capacity evict the single least-recently-served program (the old
#: wholesale clear-at-256 dropped every hot program under sustained
#: mixed-key traffic — exactly the serving workload's shape)
_PROGRAM_CACHE: LRUCache = LRUCache(maxsize=256)


def _agg_cache_key(agg: Aggregation):
    """Hashable identity of a resolved Aggregation for the program cache.
    Registry-derived aggs with equal keys trace identical programs."""

    def h(v):
        if isinstance(v, (list, tuple)):
            return tuple(h(x) for x in v)
        if isinstance(v, float) and np.isnan(v):
            return "__nan__"
        if isinstance(v, dict):
            return tuple(sorted((k, h(x)) for k, x in v.items()))
        if callable(v):
            # id() too: distinct lambdas share a "<lambda>" qualname and must
            # not collide in the program cache
            return (getattr(v, "__qualname__", repr(v)), id(v))
        return repr(v) if isinstance(v, np.generic) else v

    from ..aggregations import FusedAggregation

    # a fused plan's per-statistic identity (final fill/dtype/kwargs per
    # slot) lives in its member aggs, not the shared legs — two plans with
    # identical legs but different per-stat fills must not share a program
    fused_extra = ()
    if isinstance(agg, FusedAggregation):
        fused_extra = tuple(
            (a.name, h(a.final_fill_value), str(a.final_dtype), h(a.finalize_kwargs))
            for a in agg.aggs
        )

    return (
        agg.name,
        h(agg.chunk),
        h(agg.combine),
        h(agg.numpy),
        h(agg.fill_value.get("intermediate", ())),
        h(agg.final_fill_value),
        str(agg.final_dtype),
        h(agg.finalize_kwargs),
        agg.min_count,
        agg.reduction_type,
        fused_extra,
    )


def _apply_final_fill(result, counts, agg: Aggregation):
    """Mask groups below the contribution threshold with the final fill.

    Shared by every mesh program (map-reduce/cohorts finalize AND
    blockwise), with the promotion+where core in ONE place —
    ``aggregations._masked_fill``, which the fused multi-statistic
    finalize also uses — so the promotion rules cannot drift apart.
    Counts are (..., size) with the group axis LAST, exactly like the
    trailing dims of the result, so standard right-aligned broadcasting
    (inside ``_masked_fill``) covers both extra leading dims (quantile's
    q) and matching shapes.
    """
    from ..aggregations import _masked_fill

    final_fill = agg.final_fill_value
    if isinstance(final_fill, str):
        raise TypeError("string fill values are not supported on device")
    threshold = max(agg.min_count, 1)
    return _masked_fill(result, counts < threshold, final_fill)


def _build_program(
    agg, *, size, size_pad, method, axis_name, shard_len, nat,
    cohort_perm=None, blocked=False, ndev=1,
):
    import jax
    import jax.numpy as jnp

    if cohort_perm is not None:
        # slot -> group (size_pad; `size` = zero-pad column) and its inverse
        # group -> slot (size) — static constants baked into the program
        perm_c = jnp.asarray(cohort_perm, dtype=jnp.int32)
        inv_np = np.empty(size, dtype=np.int64)
        valid = cohort_perm < size
        inv_np[cohort_perm[valid]] = np.flatnonzero(valid)
        inv_c = jnp.asarray(inv_np, dtype=jnp.int32)

    skipna = agg.name.startswith("nan") or agg.name == "count"
    # min_count thresholds count non-NaN contributions (the reference appends
    # nanlen regardless of skipna, aggregations.py:1005-1014)
    count_skipna = skipna or agg.min_count > 0

    def finalize(combined, counts):
        with jax.named_scope("flox.finalize"):
            return _finalize_combined(agg, combined, counts)

    def numpy_kernel(f, codes_sh, arr_sh, **extra):
        """Invoke one whole-reduction (agg.numpy) kernel — the SINGLE place
        the orderstat and blockwise programs assemble finalize_kwargs/nat,
        so the two paths cannot drift."""
        kw = dict(agg.finalize_kwargs)
        if nat:
            kw["nat"] = True
        kw.update(extra)
        if callable(f):
            return f(codes_sh, arr_sh, size=size, fill_value=None, **kw)
        from ..kernels import generic_kernel

        return generic_kernel(f, codes_sh, arr_sh, size=size, fill_value=None, **kw)

    def local_stage(codes_sh, arr_sh):
        """This shard's counts and chunk intermediates, over blocks of its
        kept rows when the shard is large (``utils.map_row_blocks``), so
        their device temps stay bounded."""
        return utils.map_row_blocks(
            lambda c, a: _local_stats(agg, c, a, size, count_skipna, nat),
            codes_sh, arr_sh,
        )

    def orderstat_program(arr_sh, codes_sh):
        """Distributed quantile/median: ONE kernel call whose radix-select
        counting passes psum across shards (kernels._quantile_impl
        axis_name=). The selected value is reconstructed bit-by-bit from
        the global counts — never gathered from any single shard — so the
        result is replicated by construction. Capability the reference
        does not have: it forces method='blockwise' for order statistics
        (core.py:685-709)."""
        counts_local = _local_counts(codes_sh, arr_sh, size, count_skipna, nat)
        with jax.named_scope("flox.combine"):
            counts = jax.lax.psum(counts_local, axis_name)
        result = numpy_kernel(agg.numpy[0], codes_sh, arr_sh, axis_name=axis_name)
        with jax.named_scope("flox.finalize"):
            return _apply_final_fill(result, counts, agg)

    def mapreduce_program(arr_sh, codes_sh):
        if agg.reduction_type == "argreduce":
            counts_local = _local_counts(codes_sh, arr_sh, size, count_skipna, nat)
            val_f, arg_f = agg.chunk  # e.g. ("max", "argmax")
            from ..kernels import generic_kernel

            kw = {"nat": True} if nat else {}
            val = generic_kernel(
                val_f, codes_sh, arr_sh, size=size,
                fill_value=agg.fill_value["intermediate"][0], **kw,
            )
            local_arg = generic_kernel(arg_f, codes_sh, arr_sh, size=size, fill_value=-1, **kw)
            offset = _flat_axis_index(axis_name).astype(jnp.int64 if utils.x64_enabled() else jnp.int32) * shard_len
            gidx = jnp.where(local_arg >= 0, local_arg + offset, -1)
            inters = [val, gidx]
        elif agg.combine in (("first",), ("last",)):
            counts_local = _local_counts(codes_sh, arr_sh, size, count_skipna, nat)
            offset = _flat_axis_index(axis_name).astype(jnp.int32) * shard_len
            val, pos = _local_firstlast(
                codes_sh, arr_sh, size, skipna=skipna,
                last=agg.combine == ("last",), nat=nat, offset=offset,
            )
            inters = [val, pos]
        else:
            counts_local, inters = local_stage(codes_sh, arr_sh)
        with jax.named_scope("flox.combine"):
            counts = jax.lax.psum(counts_local, axis_name)
            combined = _combine_intermediates(agg, inters, axis_name, nat)
        return finalize(combined, counts)

    def blocked_cohorts_program(arr_sh, codes_sh):
        """Huge-label-space variant (VERDICT r3 #6): no dense (..., size)
        buffer ever materializes. A fori_loop walks the ndev owner blocks;
        each iteration chunk-reduces only that block's groups into a
        (..., size/ndev) buffer, psums it (replicated), and the owner
        mask-keeps its slice. Communication totals one psum of (..., size)
        — the same bytes as plain map-reduce — but peak HBM is
        (..., size/ndev) x O(1) buffers. The data makes ndev passes, the
        price of the memory ceiling."""
        me = _flat_axis_index(axis_name)
        b = size_pad // ndev

        def block(d):
            in_block = (codes_sh >= d * b) & (codes_sh < (d + 1) * b)
            bc = jnp.where(in_block, codes_sh - d * b, -1)
            counts_local, inters = _local_stats(agg, bc, arr_sh, b, count_skipna, nat)
            with jax.named_scope("flox.combine"):
                counts = jax.lax.psum(counts_local, axis_name)
            outs = []
            for inter, op in zip(inters, agg.combine):
                with jax.named_scope("flox.combine"):
                    outs.append(
                        _combine_var(inter, axis_name)
                        if op == "var"
                        else _combine_simple(op, inter, axis_name, nat=nat and not skipna)
                    )
            return counts, outs

        c0, o0 = block(0)
        keep0 = me == 0
        with jax.named_scope("flox.combine"):
            carry0 = jax.tree.map(lambda x: jnp.where(keep0, x, jnp.zeros_like(x)), (c0, o0))

        def body(d, carry):
            c, o = block(d)
            keep = me == d
            with jax.named_scope("flox.combine"):
                return jax.tree.map(lambda new, acc: jnp.where(keep, new, acc), (c, o), carry)

        counts_own, owned = jax.lax.fori_loop(1, ndev, body, carry0)
        result_own = finalize(owned, counts_own)
        with jax.named_scope("flox.combine"):
            full = jax.lax.all_gather(
                jnp.moveaxis(result_own, -1, 0), axis_name, tiled=True
            )
            return _crop(jnp.moveaxis(full, 0, -1), size)

    def cohorts_program(arr_sh, codes_sh):
        # psum_scatter needs every intermediate to be additive; route others
        # through map-reduce (matching how the reference falls back to
        # map-reduce when cohort detection finds nothing to exploit)
        if not _is_additive(agg):
            return mapreduce_program(arr_sh, codes_sh)
        if blocked:
            return blocked_cohorts_program(arr_sh, codes_sh)

        from ..kernels import generic_kernel

        def pad_groups(x):
            if size_pad == size:
                return x
            widths = [(0, 0)] * (x.ndim - 1) + [(0, size_pad - size)]
            return jnp.pad(x, widths)

        def to_slots(x):
            """Pad the group axis and place groups in their ownership slots
            (identity layout when no cohort alignment was found)."""
            x = pad_groups(x)
            if cohort_perm is not None:
                x = jnp.take(x, perm_c, axis=-1)
            return x

        def from_slots(full):
            """Gathered slot layout -> original group order, cropped."""
            if cohort_perm is not None:
                return jnp.take(full, inv_c, axis=-1)
            return _crop(full, size)

        counts_raw, inters = local_stage(codes_sh, arr_sh)
        with jax.named_scope("flox.combine"):
            counts_local = to_slots(counts_raw)
            counts_own = jax.lax.psum_scatter(
                jnp.moveaxis(counts_local, -1, 0), axis_name, scatter_dimension=0, tiled=True
            )
            counts_own = jnp.moveaxis(counts_own, 0, -1)

            owned = []
            for inter, op in zip(inters, agg.combine):
                if op == "var":
                    # scatter each leaf; the Chan adjustment needs the scattered
                    # totals, so do it leaf-wise after scattering sums
                    m2, total, nn = inter.arrays
                    mu_d = total / jnp.where(nn > 0, nn, 1)
                    big_t = _pscatter(to_slots(total), axis_name)
                    big_n = _pscatter(to_slots(nn), axis_name)
                    # mu over owned slice must be compared against each shard's
                    # mu_d — requires the adjustment before scattering:
                    # psum_scatter(m2 + n*(mu_d - mu)^2) with mu broadcast back.
                    mu = big_t / jnp.where(big_n > 0, big_n, 1)
                    mu_full = _unscatter_broadcast(mu, axis_name)
                    adj = nn * (mu_d - from_slots(mu_full)) ** 2
                    big_m2 = _pscatter(to_slots(m2 + adj), axis_name)
                    owned.append(MultiArray((big_m2, big_t, big_n)))
                else:
                    owned.append(_pscatter(to_slots(inter), axis_name))

        result_own = finalize(owned, counts_own)
        # replicate: gather the owned slices back into the full group axis
        with jax.named_scope("flox.combine"):
            full = jax.lax.all_gather(jnp.moveaxis(result_own, -1, 0), axis_name, tiled=True)
            return from_slots(jnp.moveaxis(full, 0, -1))

    def blockwise_program(arr_sh, codes_sh):
        counts_local = _local_counts(codes_sh, arr_sh, size, count_skipna, nat)
        locals_ = [numpy_kernel(f, codes_sh, arr_sh) for f in agg.numpy]
        if agg.reduction_type == "argreduce" and len(locals_) > 1:
            result_local = locals_[1]
        elif agg.finalize is not None and len(agg.numpy) > 1:
            # multi-stage custom Aggregation (see core._reduce_blockwise)
            result_local = agg.finalize(*locals_, **agg.finalize_kwargs)
        else:
            result_local = locals_[0]
        if agg.reduction_type == "argreduce":
            offset = _flat_axis_index(axis_name).astype(jnp.int32) * shard_len
            result_local = jnp.where(result_local >= 0, result_local + offset, -1)
        # owner = the shard that saw this group's elements (precondition:
        # exactly one, after reshard_for_blockwise)
        with jax.named_scope("flox.combine"):
            counts_all = jax.lax.all_gather(counts_local, axis_name)  # (ndev, ..., size)
            res_all = jax.lax.all_gather(result_local, axis_name)  # (ndev, *new, ..., size)
            owner = jnp.argmax(counts_all > 0, axis=0)  # (..., size)
            extra = res_all.ndim - 1 - owner.ndim  # new dims (e.g. quantile's q)
            pick = jnp.broadcast_to(
                owner.reshape((1,) * extra + owner.shape), res_all.shape[1:]
            )
            result = jnp.take_along_axis(res_all, pick[None], axis=0)[0]
            counts = jax.lax.psum(counts_local, axis_name)
        with jax.named_scope("flox.finalize"):
            return _apply_final_fill(result, counts, agg)

    if method == "map-reduce":
        program = orderstat_program if agg.blockwise_only else mapreduce_program
    elif method == "cohorts":
        program = cohorts_program
    elif method == "blockwise":
        program = blockwise_program
    else:
        raise ValueError(f"Unknown method {method!r}")
    # the jitted program's name, which the profiler's trace shows
    program.__name__ = program.__qualname__ = f"flox_mesh_{method.replace('-', '_')}"
    return program


def _pscatter(x, axis_name):
    """psum_scatter over the trailing (group) axis; returns the owned slice."""
    import jax
    import jax.numpy as jnp

    moved = jnp.moveaxis(x, -1, 0)
    out = jax.lax.psum_scatter(moved, axis_name, scatter_dimension=0, tiled=True)
    return jnp.moveaxis(out, 0, -1)


def _unscatter_broadcast(x_own, axis_name):
    """all_gather an owned slice back to the full (padded) group axis."""
    import jax
    import jax.numpy as jnp

    moved = jnp.moveaxis(x_own, -1, 0)
    full = jax.lax.all_gather(moved, axis_name, tiled=True)
    return jnp.moveaxis(full, 0, -1)


def _crop(x, size):
    return x[..., :size]
