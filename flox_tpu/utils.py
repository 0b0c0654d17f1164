"""Duck-array and null-handling utilities (L0).

Parity target: /root/reference/flox/xrutils.py (isnull/notnull at
xrutils.py:149-187, duck-array predicates at xrutils.py:85-146), re-thought
for a JAX world: the load-bearing split here is *host arrays* (numpy, where
labels/metadata live and object/datetime dtypes are legal) vs *device arrays*
(jax, always numeric, traced under jit).
"""

from __future__ import annotations

import importlib
import math
from collections.abc import Iterable
from typing import Any

import numpy as np

from . import dtypes


def module_available(name: str) -> bool:
    try:
        importlib.import_module(name)
    except ImportError:
        return False
    return True


HAS_XARRAY = module_available("xarray")
HAS_MATPLOTLIB = module_available("matplotlib")


def fmt_bytes(n: float) -> str:
    """Human size for guard messages: GiB above 1, MiB below."""
    return f"{n / 2**30:.1f} GiB" if n >= 2**30 else f"{n / 2**20:.1f} MiB"


def is_jax_array(x: Any) -> bool:
    import jax

    return isinstance(x, jax.Array)


def x64_enabled() -> bool:
    """Whether jax is configured for 64-bit dtypes (True when jax is absent)."""
    try:
        import jax

        return bool(jax.config.jax_enable_x64)
    except ImportError:  # pragma: no cover
        return True


def is_duck_array(value: Any) -> bool:
    if isinstance(value, np.ndarray):
        return True
    return (
        hasattr(value, "ndim")
        and hasattr(value, "shape")
        and hasattr(value, "dtype")
        and (hasattr(value, "__array_function__") or hasattr(value, "__array_namespace__"))
    )


def asarray_host(x: Any) -> np.ndarray:
    """Materialize on host as numpy (labels, metadata, finalize-side work)."""
    if isinstance(x, np.ndarray):
        return x
    if is_jax_array(x):
        return np.asarray(x)
    return np.asarray(x)

def asarray_device(x: Any):
    """Put on device as a jnp array, viewing datetimes as int64."""
    import jax.numpy as jnp

    if is_jax_array(x):
        return x
    x = np.asarray(x)
    if dtypes.is_datetime_like(x.dtype):
        x = x.view("int64")
    from . import telemetry

    if telemetry.enabled():
        # host -> device staging bytes (the streaming pipeline's device_put
        # counts its own in pipeline.SlabStager)
        telemetry.METRICS.inc("bytes.h2d", int(x.nbytes))
    with telemetry.span("h2d") as h2d:
        out = jnp.asarray(x)
        h2d.set(bytes=int(out.nbytes))  # as staged: int64 codes cross as int32 without x64
    return out


def isnull(data: Any):
    """Elementwise missing-mask valid for any dtype (host or device).

    Parity: xrutils.isnull (xrutils.py:149-168) — NaN for floats/complex,
    NaT for datetimes, never-null for ints/bools; object arrays checked via
    pandas on host.
    """
    if is_jax_array(data):
        import jax.numpy as jnp

        if jnp.issubdtype(data.dtype, jnp.floating) or jnp.issubdtype(
            data.dtype, jnp.complexfloating
        ):
            return jnp.isnan(data)
        return jnp.zeros(data.shape, dtype=bool)
    data = np.asarray(data)
    dtype = data.dtype
    if np.issubdtype(dtype, np.floating) or np.issubdtype(dtype, np.complexfloating):
        return np.isnan(data)
    if dtypes.is_datetime_like(dtype):
        return np.isnat(data)
    if dtype.kind == "O":
        import pandas as pd

        return pd.isnull(data)
    return np.zeros(data.shape, dtype=bool)


def notnull(data: Any):
    return ~isnull(data)


def is_scalar(value: Any) -> bool:
    return np.ndim(value) == 0 and not isinstance(value, (list, tuple, dict, set))


def normalize_axis_tuple(axis: int | Iterable[int], ndim: int) -> tuple[int, ...]:
    if np.isscalar(axis):
        axis = (int(axis),)  # type: ignore[arg-type]
    return tuple(sorted(ax % ndim for ax in axis))  # type: ignore[union-attr]


def moveaxis_to_end(array, axes: tuple[int, ...]):
    """Move ``axes`` to the trailing positions, preserving their order."""
    keep = [ax for ax in range(array.ndim) if ax not in axes]
    return array.transpose(keep + list(axes)), tuple(keep)


def reapply_nonfinite(sums, nan_c, pos_c, neg_c, *, skipna: bool = False):
    """Re-apply IEEE non-finite propagation to segment sums computed on
    zero-filled data with NaN/+inf/-inf marker counts (shared by the MXU
    GEMM and Pallas segment-sum paths so their semantics cannot drift).

    ``skipna=True`` treats NaN as absent (the fused nan-aggregation path
    sums over raw, unmasked data): zeroed NaNs simply do not contribute,
    and only the ±inf rules apply."""
    import jax.numpy as jnp

    poison = (pos_c > 0) & (neg_c > 0)
    if not skipna:
        poison = poison | (nan_c > 0)
    return jnp.where(
        poison,
        jnp.asarray(jnp.nan, sums.dtype),
        jnp.where(
            pos_c > 0,
            jnp.asarray(jnp.inf, sums.dtype),
            jnp.where(neg_c > 0, jnp.asarray(-jnp.inf, sums.dtype), sums),
        ),
    )


def is_nan_fill(v) -> bool:
    """True only for genuine float/complex NaN fills. NaT (datetime64 /
    timedelta64) answers True to np.isnan but must NOT trigger float
    promotion — timestamps would lose ns precision through float64."""
    if isinstance(v, (np.datetime64, np.timedelta64)):
        return False
    try:
        return bool(np.isnan(v))
    except (TypeError, ValueError):
        return False


#: a reduction's device temps scale with its input (a NaN-masked copy, a
#: squared-deviation pass); past this many input bytes the kept rows run in
#: sequential blocks so those temps stay block-sized. Unblocked, nanvar over
#: the 6.9 GB headline array needed 19.2 GB of a v5e's 15.75 GB.
ROW_BLOCK_BYTES = 2**30

#: a reduction over several trailing label dims of a device array larger
#: than this runs in slabs of the first label dim this size (core's
#: span-blocked path). Each slab is copied once into the kernels' flat
#: (..., N) layout and its masks and counts are slab-sized, so a slab a
#: quarter of ROW_BLOCK_BYTES keeps the call's temporaries near 1 GiB;
#: flattening the whole span instead relayouts the whole array.
SPAN_BLOCK_BYTES = 2**28


def map_row_blocks(fn, codes, array):
    """``fn(codes, array)`` for ``array`` (..., N) reduced over its trailing
    axis, evaluated over blocks of the kept rows when the array exceeds
    :data:`ROW_BLOCK_BYTES`. Every row reduces on its own, so the blocked
    result is the unblocked one. ``fn`` returns a pytree of leaves shaped
    ``(extra..., *kept, size)``; any other shape runs unblocked."""
    import jax
    import jax.numpy as jnp

    kept = tuple(array.shape[:-1])
    n = array.shape[-1]
    k = math.prod(kept)
    row_bytes = n * array.dtype.itemsize
    kb = (ROW_BLOCK_BYTES // max(row_bytes, 1)) // 8 * 8
    if not kept or kb < 8 or k < 2 * kb:
        return fn(codes, array)
    shapes = jax.eval_shape(fn, codes, jax.ShapeDtypeStruct((kb, n), array.dtype))
    if any(leaf.ndim < 2 or leaf.shape[-2] != kb for leaf in jax.tree.leaves(shapes)):
        return fn(codes, array)
    # the block slices, the loop over them and the merge, as the profiler
    # names them; fn's own ops keep their own scopes inside
    with jax.named_scope("flox.rowblock"):
        flat = array.reshape(k, n)
        nfull = k // kb

        def one(i):
            return fn(codes, jax.lax.dynamic_slice_in_dim(flat, i * kb, kb, axis=0))

        stacked = jax.lax.map(one, jnp.arange(nfull))  # leaves (nfull, extra..., kb, size)
        tail = fn(codes, flat[nfull * kb :]) if nfull * kb < k else None

        def merge(leaf, tail_leaf):
            ax = leaf.ndim - 3  # the block axis lands just before the kb axis
            merged = jnp.moveaxis(leaf, 0, ax)
            merged = merged.reshape(merged.shape[:ax] + (nfull * kb,) + merged.shape[ax + 2 :])
            if tail_leaf is not None:
                merged = jnp.concatenate([merged, tail_leaf], axis=ax)
            return merged.reshape(merged.shape[:ax] + kept + merged.shape[ax + 1 :])

        if tail is None:
            return jax.tree.map(lambda leaf: merge(leaf, None), stacked)
        return jax.tree.map(merge, stacked, tail)
