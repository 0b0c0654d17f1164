"""Fully-traceable device-resident groupby (L4, jit-composable surface).

The main ``groupby_reduce`` keeps unknown-label discovery host-side, like
the reference. When ``expected_groups`` is known, NOTHING needs the host:
factorization is a ``searchsorted`` (factorize.factorize_device), the
reduction is the kernel bundle, and the whole pipeline is one traceable
function users can place inside their own ``jax.jit`` / ``shard_map`` /
training step — the capability the reference cannot offer (its engines are
host numpy).

This realizes the "device-resident integer group codes" design point of the
build plan (SURVEY.md §7 step 2; reference counterpart factorize.py:42-99
is host-only).
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Sequence

import numpy as np

from . import factorize as fct
from .aggregations import _initialize_aggregation

__all__ = [
    "codes_device",
    "compile_cache",
    "groupby_reduce_device",
    "memory_stats",
    "reinitialize",
]

#: the compile cache's home when the environment names none: fixed, since
#: the path is part of the cache key (a moving directory never hits)
REPO_COMPILE_CACHE = Path(__file__).resolve().parent.parent / ".jax_cache"


def compile_cache(default: Any = None) -> Path:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: jax reads it itself and
    no other directory is set here. Otherwise the cache goes to ``default``
    (the serve AOT directory) or ``<repo>/.jax_cache`` (gitignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    import jax

    path = Path(default) if default is not None else REPO_COMPILE_CACHE
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path


def reinitialize() -> bool:
    """Tear down and re-create the JAX backend client — the recovery step
    after a device-loss classification (``resilience.DEVICE_LOST``).

    Clears jax's live backend clients so the next dispatch re-initializes
    the runtime (PJRT re-enumerates devices), and drops this package's
    compiled-program caches — executables compiled against the dead client
    hold dangling device references and must never be served again. The
    metrics registry, cost ledger, and autotune store are deliberately
    untouched: recovery is not a stats reset. Returns whether the backend
    teardown succeeded (``False`` degrades to cache-drop-only, which is
    still the correct half of the story on backends that self-heal).
    Never raises: recovery must be drivable from an error path.
    """
    import jax.extend.backend

    torn_down = _teardown_quietly(jax.extend.backend.clear_backends)
    try:
        from .core import _jitted_bundle
        from .fusion import _FUSED_PROGRAM_CACHE
        from .parallel.mapreduce import _PROGRAM_CACHE
        from .parallel.scan import _SCAN_CACHE
        from .streaming import _STEP_CACHE

        _jitted_bundle.cache_clear()
        _PROGRAM_CACHE.clear()
        _SCAN_CACHE.clear()
        _STEP_CACHE.clear()
        _FUSED_PROGRAM_CACHE.clear()
    except Exception:  # noqa: BLE001 — partial recovery beats masking the loss
        pass
    return torn_down


def memory_stats(devices: Sequence | None = None) -> dict[str, int] | None:
    """Aggregate allocator statistics across the local devices.

    Returns ``{"bytes_in_use", "peak_bytes_in_use", "devices",
    "bytes_limit"}`` summed over every local device that reports stats
    (``peak`` falls back to ``bytes_in_use`` for allocators that track no
    peak; ``bytes_limit`` is the per-device HBM capacity summed the same
    way, ``None`` when no reporting device exposes one — the in-use gauges
    then stay unitless rather than inventing a denominator), or ``None``
    when no device reports any — CPU backends commonly return nothing, and
    the telemetry HBM gauges (``telemetry.sample_hbm``) simply stay absent
    there. Never raises: observability must not take a dispatch down.
    """
    import jax

    try:
        devs = list(jax.local_devices()) if devices is None else list(devices)
    except Exception:  # noqa: BLE001 — no backend at all
        return None
    in_use = peak = limit = 0
    reporting = limit_reporting = 0
    for dev in devs:
        stats = _device_stats(dev)
        if not stats:
            continue
        reporting += 1
        dev_in_use = int(stats.get("bytes_in_use", 0))
        in_use += dev_in_use
        peak += int(stats.get("peak_bytes_in_use", dev_in_use))
        dev_limit = stats.get("bytes_limit")
        if dev_limit:
            limit_reporting += 1
            limit += int(dev_limit)
    if not reporting:
        return None
    return {
        "bytes_in_use": in_use,
        "peak_bytes_in_use": peak,
        "devices": reporting,
        "bytes_limit": limit if limit_reporting else None,
    }


def _teardown_quietly(fn: Any) -> bool:
    """Run the backend teardown; ``False`` if it raised (recovery
    proceeds to the cache drop either way)."""
    import warnings

    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            fn()
        return True
    except Exception:  # noqa: BLE001 — a failed teardown, not a fault
        return False


def _device_stats(dev: Any) -> dict | None:
    """One device's allocator stats, or None where the backend has none."""
    stats = getattr(dev, "memory_stats", None)
    try:
        return stats() if callable(stats) else None
    except Exception:  # noqa: BLE001 — a backend without the query
        return None


def codes_device(
    by: Any,
    expected_values: Sequence | None = None,
    *,
    bins: Sequence | None = None,
    closed: str = "right",
) -> Any:
    """Traceable label -> dense code computation on device.

    Exactly one of ``expected_values`` (sorted unique labels) or ``bins``
    (interval edges) must be given. Returns int32 codes with -1 = missing.
    """
    if (expected_values is None) == (bins is None):
        raise ValueError("Pass exactly one of expected_values or bins")
    if bins is not None:
        return fct.bin_device(by, bins, closed=closed)
    return fct.factorize_device(by, expected_values)


def groupby_reduce_device(
    array: Any,
    *by: Any,
    func: str,
    expected_values: Sequence | None = None,
    bins: Sequence | None = None,
    fill_value: Any = None,
    dtype: Any = None,
    finalize_kwargs: dict | None = None,
) -> Any:
    """Grouped reduction with every step on device — safe inside ``jax.jit``.

    ``by`` entries are device arrays whose *flattened* elements align with
    the trailing dims of ``array``; ``expected_values`` / ``bins`` give the
    static group space (one entry per ``by``; a bare array is accepted for
    one grouper). Reduces over all ``by`` dims. Returns the dense result
    (..., *group_sizes) — no groups tuple (they are exactly the expected
    values, which the caller already has).

    Limitations vs the host orchestrator: no unknown-label discovery, no
    partial-axis reduction, no datetime round-trips — those need the host.
    """
    import jax.numpy as jnp

    from .kernels import generic_kernel

    nby = len(by)
    if nby == 0:
        raise TypeError("Must pass at least one `by`")

    def _norm(spec):
        if spec is None:
            return (None,) * nby
        if nby == 1:
            # a bare array OR a plain list of group values is one spec;
            # only a 1-tuple is the explicit per-grouper form
            if isinstance(spec, tuple) and len(spec) == 1:
                return spec
            return (spec,)
        if not isinstance(spec, (tuple, list)) or len(spec) != nby:
            raise ValueError(
                f"With {nby} groupers, pass a tuple of {nby} expected_values/bins entries"
            )
        return tuple(spec)

    expected_t = _norm(expected_values)
    bins_t = _norm(bins)

    codes_list = []
    sizes = []
    for b, exp, edges in zip(by, expected_t, bins_t):
        flat = jnp.asarray(b).reshape(-1)
        if edges is not None:
            codes_list.append(fct.bin_device(flat, edges))
            sizes.append(len(edges) - 1)
        elif exp is not None:
            codes_list.append(fct.factorize_device(flat, exp))
            sizes.append(len(exp))
        else:
            raise ValueError("groupby_reduce_device needs expected_values or bins per `by`")

    # ravel multi-by codes on device; any -1 component -> -1
    codes = codes_list[0]
    size = sizes[0]
    for c, s in zip(codes_list[1:], sizes[1:]):
        missing = (codes < 0) | (c < 0)
        codes = jnp.where(missing, -1, codes * s + c)
        size *= s

    arr = jnp.asarray(array)
    n = codes.shape[0]
    lead = arr.shape[: arr.ndim - _span_ndim(arr.shape, n)]
    arr_flat = arr.reshape(lead + (n,))

    agg = _initialize_aggregation(
        func, dtype, np.dtype(str(arr.dtype)), fill_value, 0, finalize_kwargs
    )
    kw = dict(agg.finalize_kwargs)
    kernel_dtype = None
    if agg.name in ("sum", "nansum", "prod", "nanprod", "mean", "nanmean",
                    "var", "nanvar", "std", "nanstd") or dtype is not None:
        kernel_dtype = np.dtype(agg.final_dtype)
        if not _x64():
            # don't request 64-bit accumulation the backend cannot represent
            if kernel_dtype.itemsize == 8 and kernel_dtype.kind in "fiu":
                kernel_dtype = np.dtype(kernel_dtype.kind + "4")
    result = generic_kernel(
        agg.numpy[0] if isinstance(agg.numpy[0], str) else func,
        codes,
        arr_flat,
        size=size,
        fill_value=agg.final_fill_value if not _is_sentinel(agg.final_fill_value) else None,
        dtype=kernel_dtype,
        **kw,
    )
    if kernel_dtype is not None and result.dtype != kernel_dtype:
        result = result.astype(kernel_dtype)
    new_dims = agg.new_dims()
    out_shape = new_dims + lead + tuple(sizes)
    return result.reshape(out_shape)


def _span_ndim(shape: tuple[int, ...], n: int) -> int:
    """How many trailing dims of ``shape`` flatten to ``n`` elements."""
    prod = 1
    for i, s in enumerate(reversed(shape), start=1):
        prod *= s
        if prod == n:
            return i
    raise ValueError(f"`by` length {n} does not match trailing dims of array shape {shape}")


def _x64() -> bool:
    from . import utils

    return utils.x64_enabled()


def _is_sentinel(v) -> bool:
    from . import dtypes

    return v in (dtypes.NA, dtypes.INF, dtypes.NINF)
