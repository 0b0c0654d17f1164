"""Sharded grouped scans: the mesh Blelloch scan (L5).

Parity target: the reference's dask scan pipeline (dask.py:576-663) —
``cumreduction(method="blelloch")`` with ``chunk_scan`` / ``grouped_reduce``
/ ``scan_binary_op`` (scan.py:318-352, aggregations.py:792-846).

Mesh realization, one jitted SPMD program:

1. each shard runs the segmented within-shard scan (the same
   ``associative_scan`` kernel as the eager path);
2. each shard computes its per-group block summary (sum of the block for
   cumsum; last valid value for ffill) — the Blelloch "preop";
3. carries are exchanged with ONE ``all_gather`` (ndev × size values) and
   each shard folds its exclusive prefix — the cross-shard "binop". For
   cumsum that fold is a select-then-sum over the gathered (ndev, size)
   block summaries; for ffill it picks the nearest preceding shard with a
   valid value;
4. the carry is gathered back per element through the group codes.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from .. import utils
from ..aggregations import Scan
from .mapreduce import _cached_mesh_default, _flat_axis_index, _norm_axes, _pad_to
from .mesh import shard_map

_SCAN_CACHE: dict = {}


def sharded_groupby_scan(
    array: Any,
    codes: Any,
    scan: Scan,
    *,
    size: int,
    mesh: Any = None,
    axis_name: str | tuple[str, ...] = "data",
    dtype: Any = None,
    method: str = "blelloch",
    nat: bool = False,
) -> Any:
    """Sharded grouped scan over the trailing axis. Returns same shape as
    ``array`` (padded positions stripped).

    ``method="blockwise"`` skips the carry exchange entirely — valid only
    when every group is shard-local (validated host-side; the analogue of
    the reference's blockwise scan after rechunk_for_blockwise,
    scan.py:48-78 + dask.py:624-651).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        mesh = _cached_mesh_default()
    axes = _norm_axes(axis_name, mesh)
    ndev = int(np.prod([mesh.shape[a] for a in axes]))

    if method == "blockwise":
        _validate_shard_local(np.asarray(codes).reshape(-1), ndev)

    arr = utils.asarray_device(array)
    if dtype is not None:
        arr = arr.astype(dtype)
    codes_dev = jnp.asarray(np.asarray(codes), dtype=jnp.int32)
    n = codes_dev.shape[0]
    pad = _pad_to(n, ndev)
    if pad:
        codes_dev = jnp.concatenate([codes_dev, jnp.full((pad,), -1, dtype=jnp.int32)])
        widths = [(0, 0)] * (arr.ndim - 1) + [(0, pad)]
        arr = jnp.pad(arr, widths)

    spec_entry = axes if len(axes) > 1 else axes[0]
    in_specs = (P(*([None] * (arr.ndim - 1) + [spec_entry])), P(spec_entry))
    out_specs = P(*([None] * (arr.ndim - 1) + [spec_entry]))

    from ..options import trace_fingerprint

    cache_key = (scan.name, size, axes, mesh, arr.ndim, str(arr.dtype), method, nat, trace_fingerprint())
    from .. import telemetry

    fn = _SCAN_CACHE.get(cache_key)
    if fn is None:
        telemetry.count("cache.scan_misses")
        if method == "blockwise":
            program = _build_blockwise_scan_program(scan, size=size, nat=nat)
        else:
            program = _build_scan_program(scan, size=size, axis_name=axes, nat=nat)
        # check_vma=False: the Pallas scan kernel's outputs carry no
        # varying-axis annotation, which the checker demands inside
        # shard_map (the chip refused the Blelloch program with it on)
        fn = jax.jit(
            shard_map(
                program, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
            )
        )
        if len(_SCAN_CACHE) > 256:
            _SCAN_CACHE.clear()
        _SCAN_CACHE[cache_key] = fn
    else:
        telemetry.count("cache.scan_hits")
    with telemetry.span(
        "mesh-scan", scan=scan.name, method=method, ndev=ndev, size=size
    ):
        out = fn(arr, codes_dev)
    if pad:
        out = out[..., :n]
    return out


def build_stream_scan_step(scan: Scan, *, size: int, mesh, axis_name="data",
                           nat: bool = False, lead_ndim: int = 0):
    """One jitted shard_map step for the streaming × mesh scan composition:
    ``(slab_sharded, codes_sharded, carry_a, carry_b) ->
    (out_sharded, new_carry_a, new_carry_b)`` — the within-slab distributed
    Blelloch (identical to the in-memory program) plus the cross-slab
    carry fold. Carry state: (per-group sums, had-NaT int8) for
    cumsum-mode; (per-group edge value, has int8) for ffill/bfill."""
    from jax.sharding import PartitionSpec as P

    from ..pipeline import maybe_donate

    axes = _norm_axes(axis_name, mesh)
    program = _build_scan_program(
        scan, size=size, axis_name=axes, nat=nat, stream_carry=True
    )
    spec_entry = axes if len(axes) > 1 else axes[0]
    arr_spec = P(*([None] * lead_ndim + [spec_entry]))

    # the cross-slab carry pair is donated: updated in place across slabs
    return maybe_donate(
        shard_map(
            program, mesh=mesh,
            in_specs=(arr_spec, P(spec_entry), P(), P()),
            out_specs=(arr_spec, P(), P()),
            check_vma=False,
        ),
        donate_argnums=(2, 3),
    )


def _validate_shard_local(codes: np.ndarray, ndev: int) -> None:
    """Blockwise precondition: every group's positions within one shard."""
    n = codes.shape[0]
    shard_len = -(-n // ndev) if n else 1
    valid = codes >= 0
    if not valid.any():
        return
    shard_of = np.arange(n) // shard_len
    order = np.argsort(codes[valid], kind="stable")
    grp = codes[valid][order]
    shd = shard_of[valid][order]
    boundaries = np.flatnonzero(np.diff(grp)) + 1
    firsts = np.r_[0, boundaries]
    lasts = np.r_[boundaries, grp.size] - 1
    bad = np.flatnonzero(shd[firsts] != shd[lasts])
    if bad.size:
        i = firsts[bad[0]]
        raise ValueError(
            f"method='blockwise' needs every group on one shard, but group "
            f"{int(grp[i])} spans shards {int(shd[i])}..{int(shd[lasts[bad[0]]])}; "
            "reshard first (rechunk.reshard_for_blockwise) or use "
            "method='blelloch'."
        )


def _build_blockwise_scan_program(scan: Scan, *, size, nat=False):
    """Shard-local groups: the within-shard segmented scan IS the answer —
    zero collectives (parity: the reference's blockwise scan, dask.py:624-651)."""
    from ..kernels import generic_kernel

    def program(arr_sh, codes_sh):
        return generic_kernel(scan.scan, codes_sh, arr_sh, size=size, nat=nat)

    return program


def _build_scan_program(scan: Scan, *, size, axis_name, nat=False, stream_carry=False):
    """``stream_carry=True`` builds the STREAMING variant: the program takes
    a replicated cross-slab carry state and returns ``(out, new_state)`` —
    the same within-slab Blelloch plus the slab-boundary fold, so
    out-of-core scans distribute over the mesh with the identical carry
    semantics (streaming.streaming_groupby_scan mesh path)."""
    import jax
    import jax.numpy as jnp

    from ..kernels import generic_kernel

    def program(arr_sh, codes_sh, *carry_state):
        # 1. within-shard segmented scan
        local = generic_kernel(scan.scan, codes_sh, arr_sh, size=size, nat=nat)

        if scan.mode == "apply_binary_op":
            if nat:
                # int64-viewed datetimes: NaT is a sentinel, not an IEEE
                # value, so — unlike float NaN, which rides the carry sum
                # arithmetically — the block summaries need an explicit
                # had-NaT channel (parity: the reference's scan binop
                # handles datetime uniformly, aggregations.py:792-846).
                # Block sums are NaT-as-zero; the non-skipna poison is
                # re-applied from the channel after the fold.
                from ..kernels import _NAT_INT

                is_nat = arr_sh == jnp.asarray(_NAT_INT, arr_sh.dtype)
                summed = jnp.where(is_nat, jnp.zeros((), arr_sh.dtype), arr_sh)
            else:
                summed = arr_sh
            # 2. block summary: per-group sum of this shard
            block = generic_kernel(
                scan.reduction, codes_sh, summed, size=size, fill_value=0
            )
            block = block.astype(local.dtype)
            # 3. exclusive prefix across shards: gather (ndev, ..., size) and
            # fold devices strictly before mine. A select-then-sum, not a
            # masked multiply: NaN blocks (cumsum propagation) would poison
            # every carry through NaN * 0. The had-NaT channel (non-skipna
            # datetime poisoning) rides the SAME gather as an extra leading
            # slot — the carry exchange stays ONE collective.
            poison_channel = nat and scan.scan == "cumsum"
            if poison_channel:
                had = generic_kernel(
                    "sum", codes_sh, is_nat.astype(jnp.int32), size=size,
                    fill_value=0,
                ).astype(block.dtype)
                payload = jnp.stack([block, had])  # (2, ..., size)
                g = jax.lax.all_gather(payload, axis_name)  # (ndev, 2, ..., size)
                gathered = g[:, 0]
                g_had = g[:, 1] > 0
            else:
                gathered = jax.lax.all_gather(block, axis_name)  # (ndev, ..., size)
            ndev = gathered.shape[0]
            me = _flat_axis_index(axis_name)
            mask = (jnp.arange(ndev) < me).reshape((ndev,) + (1,) * (gathered.ndim - 1))
            carry = jnp.sum(
                jnp.where(mask, gathered, jnp.zeros((), gathered.dtype)), axis=0
            )  # (..., size)
            # 4. add the carry through the codes
            safe = jnp.where(codes_sh < 0, size, codes_sh)
            carry_pad = jnp.concatenate(
                [carry, jnp.zeros(carry.shape[:-1] + (1,), carry.dtype)], axis=-1
            )
            per_elem = jnp.take(carry_pad, safe, axis=-1)
            out = local + per_elem
            if stream_carry:
                # cross-slab carry: previous slabs' per-group totals add to
                # every element; the new state folds THIS slab's global
                # block totals in (psum = all shards of the slab)
                prev_sums = carry_state[0]
                prev_pad = jnp.concatenate(
                    [prev_sums, jnp.zeros(prev_sums.shape[:-1] + (1,), prev_sums.dtype)],
                    axis=-1,
                )
                out = out + jnp.take(prev_pad, safe, axis=-1).astype(out.dtype)
                new_sums = prev_sums + jax.lax.psum(block, axis_name).astype(prev_sums.dtype)
            if poison_channel:
                # non-skipna: a NaT anywhere earlier in the group poisons
                # every later element. In-shard poisoning is already in
                # ``local`` (== NaT sentinel); cross-shard comes from the
                # had-NaT channel folded over shards strictly before me.
                poison = jnp.any(mask & g_had, axis=0)  # (..., size)
                if stream_carry:
                    poison = poison | (carry_state[1] > 0)  # earlier slabs
                poison_pad = jnp.concatenate(
                    [poison, jnp.zeros(poison.shape[:-1] + (1,), bool)], axis=-1
                )
                poison_e = jnp.take(poison_pad, safe, axis=-1)
                nat_val = jnp.asarray(_NAT_INT, out.dtype)
                out = jnp.where(poison_e | (local == nat_val), nat_val, out)
            # skipna (nancumsum): NaT counts as zero on the eager path, so
            # the plain carry add is already exact — no sentinel survives
            # the within-shard scan
            if stream_carry:
                slab_had = (
                    (jnp.any(g_had, axis=0).astype(jnp.int8) | carry_state[1])
                    if poison_channel
                    else carry_state[1]
                )
                return out, new_sums, slab_had
            return out

        # ffill/bfill: carry = last (first) valid value per group in shards
        # strictly before (after) me
        reverse = scan.name == "bfill"
        is_float = jnp.issubdtype(arr_sh.dtype, jnp.floating)
        valid_f = generic_kernel(
            "nanlen", codes_sh, arr_sh, size=size, nat=nat
        )  # per-group valid counts this shard
        last_val = generic_kernel(
            "nanlast" if not reverse else "nanfirst",
            codes_sh,
            arr_sh,
            size=size,
            fill_value=jnp.nan if is_float else 0,
            nat=nat,
        )
        g_vals = jax.lax.all_gather(last_val, axis_name)  # (ndev, ..., size)
        g_valid = jax.lax.all_gather(valid_f > 0, axis_name)
        ndev = g_vals.shape[0]
        me = _flat_axis_index(axis_name)
        before = (jnp.arange(ndev) < me) if not reverse else (jnp.arange(ndev) > me)
        before = before.reshape((ndev,) + (1,) * (g_vals.ndim - 1))
        eligible = g_valid & before
        # index of the closest eligible shard (max index for ffill, min for bfill)
        dev_idx = jnp.arange(ndev).reshape((ndev,) + (1,) * (g_vals.ndim - 1))
        if not reverse:
            pick = jnp.max(jnp.where(eligible, dev_idx, -1), axis=0)
        else:
            pick = jnp.min(jnp.where(eligible, dev_idx, ndev), axis=0)
        has_carry = (pick >= 0) & (pick < ndev)
        pick_c = jnp.clip(pick, 0, ndev - 1)
        carry = jnp.take_along_axis(g_vals, pick_c[None], axis=0)[0]
        # apply: positions still missing after the local fill take the carry
        safe = jnp.where(codes_sh < 0, size, codes_sh)

        def gather_groups(x):
            pad = jnp.zeros(x.shape[:-1] + (1,), x.dtype)
            return jnp.take(jnp.concatenate([x, pad], axis=-1), safe, axis=-1)

        carry_e = gather_groups(carry)
        has_e = gather_groups(has_carry.astype(jnp.int8)) > 0
        from ..kernels import _nan_mask

        mask = _nan_mask(local, nat)  # None when nothing can be missing
        still = ~mask if mask is not None else jnp.zeros(local.shape, bool)
        out = jnp.where(still & has_e & (codes_sh >= 0), carry_e, local)
        if not stream_carry:
            return out
        # cross-slab: positions STILL missing after the within-slab fill
        # take the previous slabs' carry; the new state picks this slab's
        # edge value (last valid shard for ffill, first for bfill) over
        # ALL shards, keeping the old value for groups absent here
        prev_val, prev_has = carry_state
        mask2 = _nan_mask(out, nat)
        still2 = ~mask2 if mask2 is not None else jnp.zeros(out.shape, bool)
        out = jnp.where(
            still2 & (gather_groups(prev_has) > 0) & (codes_sh >= 0),
            gather_groups(prev_val).astype(out.dtype),
            out,
        )
        any_valid = jnp.any(g_valid, axis=0)  # (..., size), over ALL shards
        if not reverse:
            pick_all = jnp.max(jnp.where(g_valid, dev_idx, -1), axis=0)
        else:
            pick_all = jnp.min(jnp.where(g_valid, dev_idx, ndev), axis=0)
        pick_all_c = jnp.clip(pick_all, 0, ndev - 1)
        slab_edge = jnp.take_along_axis(g_vals, pick_all_c[None], axis=0)[0]
        new_val = jnp.where(any_valid, slab_edge.astype(prev_val.dtype), prev_val)
        new_has = prev_has | any_valid.astype(prev_has.dtype)
        return out, new_val, new_has

    return program
