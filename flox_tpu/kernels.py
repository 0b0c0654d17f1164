"""The "jax" engine: grouped-reduction kernels on XLA (L1).

This is the TPU replacement for the reference's engine layer
(/root/reference/flox/aggregate_flox.py, aggregate_npg.py): one function per
reduction with the uniform plugin signature

    f(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw)

where ``group_idx`` is an integer code array of shape ``(N,)`` (code ``-1``
means "missing label"), ``array`` has shape ``(..., N)`` (the reduced axes
flattened into the trailing dim), and ``size`` is the **static** number of
groups. Returns shape ``(..., size)`` (quantile adds a leading q-dim).

Design notes (why this is not a port):

* The reference's engines are sort+``ufunc.reduceat`` (aggregate_flox.py:133-192)
  or bincount tricks (numpy_groupies). On TPU the natural primitive is the
  XLA segment reduction (``jax.ops.segment_sum`` family) — a single fused
  scatter-reduce that XLA lowers efficiently; no host-side argsort needed for
  the common reductions.
* Missing labels: code ``-1`` is clamped to an extra trailing segment which
  is sliced off — the device-shape-stable analogue of the reference's
  nan-sentinel size bump (factorize.py:201-210).
* Order statistics (quantile/median/mode) use ``jax.lax.sort`` with
  ``num_keys=2`` for a (group, value) lexicographic sort — the TPU-native
  replacement for the reference's complex-number partition trick
  (aggregate_flox.py:50-130), which does not translate to XLA.
* Grouped scans (cumsum/ffill) use a segmented binary operator under
  ``jax.lax.associative_scan`` — log-depth on device, and the same operator
  is reused across shards by the distributed Blelloch scan.
* Denormal (subnormal) inputs follow XLA's flush-to-zero semantics — the
  same behavior TPU hardware has — so comparisons against host numpy can
  differ in the last bit for values below ~1e-308 (f64) / ~1e-38 (f32).
* Everything here is shape-static and jit-safe; ``core.chunk_reduce`` traces
  the full multi-kernel bundle into ONE jitted program so XLA fuses the
  shared factorize/scatter work across outputs (e.g. mean = sum+count in one
  pass).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .multiarray import MultiArray

__all__ = ["KERNELS", "generic_kernel", "fused_segment_stats"]

_BIG = np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _to_leading(array):
    """(..., N) -> (N, ...) so segment ops reduce axis 0."""
    return jnp.moveaxis(jnp.asarray(array), -1, 0)


def _from_leading(out):
    return jnp.moveaxis(out, 0, -1)


def _safe_codes(group_idx, size: int):
    codes = jnp.asarray(group_idx).astype(jnp.int32).reshape(-1)
    return jnp.where(codes < 0, size, codes)


def minmax_identity(op: str, dtype):
    """Identity element of grouped min/max for ``dtype``: -inf (floats) /
    iinfo.min (ints) for max, +inf / iinfo.max for min. The ABSORBING
    element — what NaN/NaT maps to so it wins the reduction — is the
    opposite op's identity. Single source of truth for the scatter and
    Pallas paths and the argreductions."""
    if jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return float("-inf") if op == "max" else float("inf")
    info = np.iinfo(np.dtype(str(dtype)))
    return info.min if op == "max" else info.max


def _acc_dtype(dt):
    """Accumulation dtype for additive segment reductions.

    Sub-f32 floats (bf16/f16) accumulate in f32: their mantissas cannot even
    count past 256, so running sums and counts saturate (nanmean of 2000 bf16
    values would return the last partial, not the mean). The MXU natively
    accumulates bf16 GEMMs into f32, so the GEMM/Pallas paths pay nothing
    for this; the scatter path pays one upcast.
    """
    if dt in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    return dt


#: the name scope of every segment-reduction lowering (Pallas or XLA), as the
#: profiler's trace names the ops under it
KERNEL_SCOPE = "flox.kernel"


def _on_tpu() -> bool:
    """One definition of "on the TPU": the pallas kernels run natively
    there and in interpret mode (the CPU tests) anywhere else, and the auto
    policies key off it."""
    return jax.default_backend() == "tpu"


def _use_matmul_path(op: str, data, size: int) -> bool:
    """Additive segment reductions over few groups run as a one-hot matmul.

    ``out[g, k] = Σ_n onehot[n, g] · data[n, k]`` is a plain GEMM: on TPU it
    rides the MXU at full HBM streaming bandwidth, where XLA's scatter-add
    serializes on the VPU. The one-hot is (N, size) — negligible traffic
    while ``size`` is small, which is the common climatology case (12
    months, 366 days). Float-only (integer sums must stay exact beyond the
    f32 mantissa); policy "auto" engages it on TPU backends only — on CPU
    XLA's scatter beats the un-tiled one-hot GEMM.
    """
    from .options import OPTIONS

    if op != "sum":
        return False
    if not (size <= OPTIONS["matmul_num_groups_max"] and jnp.issubdtype(data.dtype, jnp.floating)):
        return False
    # footprint guard: the one-hot is (N, size); its traffic relative to the
    # data is size/K. Keep it bounded and never let the materialized one-hot
    # exceed a hard cap — a long 1-D array with many groups must stay on the
    # scatter path.
    n = data.shape[0]
    k = int(np.prod(data.shape[1:])) if data.ndim > 1 else 1
    itemsize = np.dtype(str(data.dtype)).itemsize
    if size > 4 * k:
        return False
    if n * size * itemsize > 2**31:
        return False
    # wide-K inputs are safe: _seg_matmul_sum blocks the K axis so the
    # per-block marker masks stay ~matmul_block_bytes (an unblocked
    # bench-scale array OOMed on chip: 2.3 GB input -> 9.1 GB of mask
    # temporaries -> allocation failure). Blocking bounds K but not N, and
    # the block floors at 8 rows — when even the smallest possible block's
    # four (min(k, 8), N) masks would reach 2 GB, refuse and fall back to
    # scatter.
    if 4 * min(k, 8) * n * itemsize >= 2**31:
        return False
    return True


def _seg_matmul_sum(data, codes, size: int, *, skipna: bool = False, return_nan_counts: bool = False):
    """(N, ...) × one-hot(N, size) -> (size, ...) on the MXU.

    A thin IEEE-reapply wrapper over :func:`_seg_matmul_raw`."""
    sums, nan_c, pos_c, neg_c = _seg_matmul_raw(data, codes, size)
    from .utils import reapply_nonfinite

    out_v = reapply_nonfinite(sums, nan_c, pos_c, neg_c, skipna=skipna)
    if return_nan_counts:
        # lets nanmean fuse its count: non-NaN count = rowcount - nan_c,
        # with rowcount a codes-only (no data traffic) segment sum
        return out_v, nan_c
    return out_v


def _seg_matmul_raw(data, codes, size: int):
    """The GEMM core: raw zero-filled sums plus NaN/±inf marker counts,
    each shaped ``(size,) + data.shape[1:]`` — callers re-apply IEEE
    propagation per skipna mode (one GEMM pass can serve BOTH the sum and
    nansum variants of the fused multi-statistic plan).

    codes may contain the missing sentinel (== size); the one-hot row is all
    zeros there, so missing labels drop out for free.

    Non-finite values cannot ride the GEMM directly — ``0 × inf`` and
    ``0 × NaN`` against other groups' zero one-hot entries would poison
    their sums — so the data is zero-filled and per-column marker blocks
    (NaN / +inf / -inf indicators) are appended to the K axis; a single GEMM
    produces sums and markers, and IEEE propagation rules are re-applied.
    The extra traffic is why ``_use_matmul_path`` requires a wide kept axis;
    the endgame for narrow shapes is the Pallas segment-sum kernel.

    precision=HIGHEST keeps f32 operands f32 on the MXU (the default would
    demote them to bf16, losing accuracy vs the scatter path this replaces).

    Like the Pallas kernel, the GEMMs consume the data through its (K, N)
    transpose: every caller reaches here via ``_to_leading`` (a lazy
    ``moveaxis(-1, 0)``), so the transposes cancel and the original HBM
    buffer streams into the MXU with no transposed copy — at benchmark
    scale (~7 GB) that copy alone was an OOM.
    """
    from .options import OPTIONS

    n = data.shape[0]
    onehot = (codes[:, None] == jnp.arange(size, dtype=codes.dtype)[None, :]).astype(
        data.dtype
    )  # (N, size)
    # explicit K: reshape(-1) is ambiguous for zero-length inputs
    k = int(np.prod(data.shape[1:])) if data.ndim > 1 else 1
    flat_t = data.reshape(n, k).T  # (K, N) — cancels the caller's moveaxis
    acc = _acc_dtype(data.dtype)

    def stats_gemm(block):
        """(kb, N) -> (kb, 4, size): [sums, nan, +inf, -inf] per col/group.

        bf16 operands stream at full rate while the MXU accumulates into f32
        (its native mode); without this the sums AND the marker counts would
        saturate at bf16's 8-bit mantissa.
        """
        isnan = jnp.isnan(block)
        ispos = jnp.isposinf(block)
        isneg = jnp.isneginf(block)
        zeroed = jnp.where(isnan | ispos | isneg, jnp.zeros((), block.dtype), block)

        def gemm(x):
            return jax.lax.dot_general(
                x,
                onehot,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=acc,
                precision=jax.lax.Precision.HIGHEST,
            )  # (kb, size)

        return jnp.stack(
            [gemm(zeroed), gemm(isnan.astype(block.dtype)),
             gemm(ispos.astype(block.dtype)), gemm(isneg.astype(block.dtype))],
            axis=1,
        )

    # the (kb, N) marker masks are the path's only HBM-scale temps; bound
    # them by looping row blocks sequentially (lax.map) when K is wide —
    # per-block temps stay ~matmul_block_bytes while the data still streams
    # through the MXU once. The ragged tail block runs unpadded outside the
    # loop, so no full-size padded copy is ever made.
    itemsize = np.dtype(str(data.dtype)).itemsize
    kb_max = max(
        8,
        (OPTIONS["matmul_block_bytes"] // (4 * max(n, 1) * itemsize)) // 8 * 8,
    )
    if k <= kb_max:
        parts = stats_gemm(flat_t)  # (K, 4, size)
    else:
        nfull = k // kb_max

        def one(i):
            return stats_gemm(
                jax.lax.dynamic_slice_in_dim(flat_t, i * kb_max, kb_max, axis=0)
            )

        outs = jax.lax.map(one, jnp.arange(nfull))  # (nfull, kb, 4, size)
        parts = outs.reshape(nfull * kb_max, 4, size)
        if nfull * kb_max < k:
            parts = jnp.concatenate(
                [parts, stats_gemm(flat_t[nfull * kb_max :])], axis=0
            )

    trail = (size,) + data.shape[1:]
    sums = parts[:, 0].T.reshape(trail)  # (size, K) -> (size, ...)
    nan_c = parts[:, 1].T.reshape(trail)
    pos_c = parts[:, 2].T.reshape(trail)
    neg_c = parts[:, 3].T.reshape(trail)
    return sums, nan_c, pos_c, neg_c


_PALLAS_PROBE_RESULT: list = []  # memoized one-time runtime validation
_PALLAS_MINMAX_PROBE_RESULT: list = []


def _probed_ok(memo, exec_probe, label) -> bool:
    """One-time check that a Pallas kernel compiles and runs right on a
    tiny input on the TPU backend. The kernels are tested in interpret
    mode on CPU, but the chip's compiler can still refuse them (tiling,
    VMEM). A failure raises the compiler's error: on the chip a broken
    kernel must fail, not pass for a slow one on the XLA paths.

    The probe runs in a fresh thread. The policy is often consulted while
    an outer jit or shard_map traces, and a probe traced there inherits
    that context (its arrays become tracers; under shard_map its
    pallas_call needs varying-axis annotations); jax keeps trace state per
    thread."""
    if not memo:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(1) as pool:
            ok = pool.submit(exec_probe).result()
        if not ok:
            raise RuntimeError(
                f"pallas {label} kernel returned a wrong result on "
                f"{jax.devices()[0].device_kind}"
            )
        memo.append(True)
    return True


def _pallas_runtime_ok() -> bool:
    from .pallas_kernels import segment_sum_pallas

    def _exec():
        probe = segment_sum_pallas(
            jnp.ones((8, 128), jnp.float32), jnp.zeros(8, jnp.int32), 2
        )
        return np.asarray(probe)[0, 0] == 8.0

    return _probed_ok(_PALLAS_PROBE_RESULT, _exec, "segment-sum")


_PALLAS_RADIXBIN_PROBE_RESULT: list = []


def _pallas_radixbin_runtime_ok() -> bool:
    from .pallas_kernels import segment_sum_radixbin_pallas

    def _exec():
        probe = segment_sum_radixbin_pallas(
            jnp.ones((8, 128), jnp.float32), jnp.zeros(8, jnp.int32), 2
        )
        return np.asarray(probe)[0, 0] == 8.0

    return _probed_ok(_PALLAS_RADIXBIN_PROBE_RESULT, _exec, "radixbin-segment-sum")


_PALLAS_SCAN_PROBE_RESULT: list = []


def _pallas_scan_runtime_ok() -> bool:
    from .pallas_kernels import segment_cumsum_pallas

    def _exec():
        data = jnp.ones((16, 128), jnp.float32)
        probe = segment_cumsum_pallas(
            data, jnp.zeros(16, jnp.int32), 2, skipna=False
        )
        return np.asarray(probe)[15, 0] == 16.0

    return _probed_ok(_PALLAS_SCAN_PROBE_RESULT, _exec, "grouped-scan")


def _scan_impl_choice(data, size) -> str:
    """Pick the grouped-cumsum lowering: the sort+log-depth segmented scan
    vs the Pallas triangular-matmul kernel (one HBM pass)."""
    from .options import OPTIONS

    policy = OPTIONS["scan_impl"]
    ok = (
        isinstance(size, int)
        and str(data.dtype) in ("float32", "bfloat16")
        and size + 1 <= OPTIONS["pallas_scan_num_groups_max"]
        and data.shape[0] >= 8
    )
    if policy == "segmented" or not ok:
        return "segmented"
    on_tpu = _on_tpu()
    if policy == "pallas":
        return "pallas" if (not on_tpu or _pallas_scan_runtime_ok()) else "segmented"
    # auto: interpret-mode pallas is slow on CPU; on TPU the sort-based path
    # pays an argsort plus a log-depth scan through HBM
    if on_tpu and _pallas_scan_runtime_ok():
        return "pallas"
    return "segmented"


def _pallas_minmax_runtime_ok() -> bool:
    from .pallas_kernels import segment_minmax_pallas

    def _exec():
        data = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
        probe = segment_minmax_pallas(data, jnp.zeros(8, jnp.int32), 2, "max")
        return np.asarray(probe)[0, 0] == 7 * 128.0

    return _probed_ok(_PALLAS_MINMAX_PROBE_RESULT, _exec, "segment-min/max")


def _segment_sum_impl(data, size: int) -> str:
    """Pick the segment-sum implementation per the policy + constraints.

    Policy ``"auto"`` consults the autotune store when the tuner is on
    (``FLOX_TPU_AUTOTUNE=1``): an observed winner among the lowerings whose
    guards pass on this call wins over the static platform heuristic. With
    the tuner off (record-only mode) the heuristic below is the whole
    story — bit-identical to the pre-autotune dispatch."""
    from .options import OPTIONS

    policy = OPTIONS["segment_sum_impl"]
    floating = jnp.issubdtype(data.dtype, jnp.floating)
    if policy == "scatter" or not floating:
        return "scatter"
    if policy == "matmul":
        return "matmul" if _use_matmul_path("sum", data, size) else "scatter"
    pallas_ok = (
        str(data.dtype) in ("float32", "bfloat16")
        and size <= OPTIONS["pallas_num_groups_max"]
        and data.shape[0] >= 8
    )
    # the radix-binning grid covers the group counts past the dense
    # kernel's VMEM cap — the sort engine's compact domain lives here
    radixbin_ok = (
        str(data.dtype) in ("float32", "bfloat16")
        and size <= OPTIONS["radixbin_num_groups_max"]
        and data.shape[0] >= 8
    )
    on_tpu = _on_tpu()
    if policy == "pallas":
        return "pallas" if pallas_ok and (not on_tpu or _pallas_runtime_ok()) else "scatter"
    if policy == "radixbin":
        return (
            "radixbin"
            if radixbin_ok and (not on_tpu or _pallas_radixbin_runtime_ok())
            else "scatter"
        )
    # auto on TPU: pallas if it validates at runtime, else radix-binning for
    # the group counts past its VMEM cap, else the GEMM path if its guards
    # pass (pure XLA, no custom lowering), else scatter
    if on_tpu and pallas_ok and _pallas_runtime_ok():
        heuristic = "pallas"
    elif on_tpu and not pallas_ok and radixbin_ok and _pallas_radixbin_runtime_ok():
        heuristic = "radixbin"
    elif on_tpu and _use_matmul_path("sum", data, size):
        heuristic = "matmul"
    else:
        heuristic = "scatter"
    if OPTIONS["autotune"]:
        from . import autotune

        eligible = ["scatter"]
        if _use_matmul_path("sum", data, size):
            eligible.append("matmul")
        if pallas_ok and on_tpu and _pallas_runtime_ok():
            eligible.append("pallas")
        if radixbin_ok and on_tpu and _pallas_radixbin_runtime_ok():
            eligible.append("radixbin")
        nelems = data.shape[0] * (
            int(np.prod(data.shape[1:])) if data.ndim > 1 else 1
        )
        return autotune.decide(
            "segment_sum", heuristic, eligible,
            dtype=str(data.dtype), ngroups=size, nelems=nelems,
        )
    return heuristic


def _segment_minmax_impl(data, size: int) -> str:
    """Pick the segment-min/max implementation per the policy + constraints.

    Min/max cannot ride the MXU (no (max, ·) semiring), so the choice is
    scatter vs the VPU select-reduce Pallas kernel, whose cost grows with
    the group count — hence the ``pallas_minmax_num_groups_max`` gate.
    """
    from .options import OPTIONS

    policy = OPTIONS["segment_minmax_impl"]
    ok = (
        str(data.dtype) in ("float32", "bfloat16", "int32")
        and size <= OPTIONS["pallas_minmax_num_groups_max"]
        and data.shape[0] >= 8
    )
    if policy == "scatter" or not ok:
        return "scatter"
    on_tpu = _on_tpu()
    if policy == "pallas":
        return "pallas" if (not on_tpu or _pallas_minmax_runtime_ok()) else "scatter"
    # auto: scatter is competitive on CPU; on TPU it serializes on the VPU
    if on_tpu and _pallas_minmax_runtime_ok():
        return "pallas"
    return "scatter"


def _seg(op: str, data, codes, size: int):
    """Segment-reduce ``data`` (N, ...) by ``codes`` (N,) into (size, ...).

    Allocates one extra segment for missing labels and slices it off, so the
    output shape depends only on the static ``size``. Additive float
    reductions may take the MXU one-hot-matmul or Pallas path per the
    ``segment_sum_impl`` policy; both carry non-finite marker columns, since
    even skipna-masked data may contain legitimate ±inf values.

    Additive ops on sub-f32 floats accumulate — and return — f32 (see
    ``_acc_dtype``); callers that want the input dtype back cast at the end.
    """
    if op in ("max", "min") and _segment_minmax_impl(data, size) == "pallas":
        from .pallas_kernels import segment_minmax_pallas

        return segment_minmax_pallas(data, codes, size, op, interpret=not _on_tpu())
    if op == "sum":
        impl = _segment_sum_impl(data, size)
        if impl == "pallas":
            from .pallas_kernels import segment_sum_pallas

            # interpret mode keeps the kernel testable off-TPU
            return segment_sum_pallas(
                data, codes, size, interpret=not _on_tpu()
            )
        if impl == "radixbin":
            from .pallas_kernels import segment_sum_radixbin_pallas

            return segment_sum_radixbin_pallas(
                data, codes, size, interpret=not _on_tpu()
            )
        if impl == "matmul":
            # non-finite handling is built into the GEMM (marker columns), so
            # skipna-masked and raw data take the same path
            return _seg_matmul_sum(data, codes, size)
    if op in ("sum", "prod") and jnp.issubdtype(data.dtype, jnp.floating):
        acc = _acc_dtype(data.dtype)
        if data.dtype != acc:
            data = data.astype(acc)
    fn = {
        "sum": jax.ops.segment_sum,
        "prod": jax.ops.segment_prod,
        "max": jax.ops.segment_max,
        "min": jax.ops.segment_min,
    }[op]
    out = fn(data, codes, num_segments=size + 1)
    return out[:size]


def _counts(codes, size: int, mask=None, dtype=jnp.int32):
    """Per-group element counts, optionally restricted by ``mask`` (N, ...)."""
    if mask is None:
        ones = jnp.ones(codes.shape, dtype=dtype)
    else:
        ones = mask.astype(dtype)
    return _seg("sum", ones, codes, size)


def _is_nan_fill(fv) -> bool:
    from . import utils as _u

    return _u.is_nan_fill(fv)


def _promote_for_nan_fill(out, fv):
    """A NaN fill on integer output must promote, not truncate to garbage."""
    inexact = jnp.issubdtype(out.dtype, jnp.floating) or jnp.issubdtype(
        out.dtype, jnp.complexfloating
    )
    if _is_nan_fill(fv) and not inexact:
        from . import utils as _u

        return out.astype(jnp.float64 if _u.x64_enabled() else jnp.float32)
    return out


def _fill_empty(out, present, fill_value):
    """Replace groups with no contributing elements by ``fill_value``."""
    if fill_value is None:
        return out
    out = _promote_for_nan_fill(out, fill_value)
    present = _bcast_present(jnp.asarray(present), out)
    return jnp.where(present, out, jnp.asarray(fill_value).astype(out.dtype))


_NAT_INT = np.iinfo(np.int64).min  # NaT viewed as int64 (core passes nat=True)


def _nan_mask(array, nat: bool = False):
    if jnp.issubdtype(array.dtype, jnp.floating) or jnp.issubdtype(array.dtype, jnp.complexfloating):
        return ~jnp.isnan(array)
    if nat and jnp.issubdtype(array.dtype, jnp.signedinteger):
        # datetime64 data arrives viewed as int64; INT64_MIN is NaT
        return array != jnp.asarray(_NAT_INT, dtype=array.dtype)
    return None  # non-float: nothing is NaN


def _maybe_cast(array, dtype):
    if dtype is not None and array.dtype != np.dtype(dtype):
        return array.astype(dtype)
    return array


def _iota_like(data):
    """(N, ...) index-along-axis-0 array broadcast to data's shape."""
    n = data.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    return jnp.broadcast_to(idx.reshape((n,) + (1,) * (data.ndim - 1)), data.shape)


# ---------------------------------------------------------------------------
# simple reductions
# ---------------------------------------------------------------------------


def _make_addlike(op: str, identity, skipna: bool):
    def kernel(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
        codes = _safe_codes(group_idx, size)
        data = _to_leading(array)
        mask = _nan_mask(data, kw.get("nat", False)) if skipna else None
        if mask is not None:
            data = jnp.where(mask, data, jnp.asarray(identity, dtype=data.dtype))
        data = _maybe_cast(data, dtype)
        out = _seg(op, data, codes, size)  # f32-accumulated for bf16/f16
        if fill_value is not None and fill_value != identity:
            # numpy semantics: nansum of an all-NaN group is the identity (0),
            # so "empty" means zero *total* elements, not zero non-NaN ones.
            present = _counts(codes, size) > 0
            out = _fill_empty(out, present, fill_value)
        if (
            jnp.issubdtype(data.dtype, jnp.floating)
            and out.dtype != data.dtype
            and not kw.get("keep_acc", False)
        ):
            # result dtype contract: same as the (request-resolved) input.
            # (int data is untouched — a NaN fill may have promoted it.)
            # keep_acc=True keeps the f32 accumulator — the mesh chunk stage
            # uses it so bf16 intermediates travel/psum in f32, casting back
            # only at finalize.
            out = out.astype(data.dtype)
        return _from_leading(out)

    return kernel


sum_ = _make_addlike("sum", 0, skipna=False)
nansum = _make_addlike("sum", 0, skipna=True)
prod = _make_addlike("prod", 1, skipna=False)
nanprod = _make_addlike("prod", 1, skipna=True)


def _make_minmax(op: str, skipna: bool):
    def kernel(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
        codes = _safe_codes(group_idx, size)
        data = _to_leading(array)
        data = _maybe_cast(data, dtype)
        nat = kw.get("nat", False)
        mask = _nan_mask(data, nat)
        isint = not jnp.issubdtype(data.dtype, jnp.floating)
        if skipna and mask is not None:
            ident = jnp.asarray(minmax_identity(op, data.dtype), dtype=data.dtype)
            data = jnp.where(mask, data, ident)
        elif not skipna and mask is not None:
            # NaN/NaT propagates through min/max in numpy; segment_min/max on
            # TPU would otherwise drop it. Force-propagate by mapping the
            # missing marker to the absorbing element (the opposite op's
            # identity).
            absorb = jnp.asarray(
                minmax_identity("min" if op == "max" else "max", data.dtype),
                dtype=data.dtype,
            )
            missing_marker = jnp.asarray(
                _NAT_INT if isint else jnp.nan, dtype=data.dtype
            )
            has_nan = _seg("max", (~mask).astype(jnp.int8), codes, size) > 0
            data = jnp.where(mask, data, absorb)
            out = _seg(op, data, codes, size)
            out = jnp.where(has_nan, missing_marker, out)
            present = _counts(codes, size) > 0
            out = _fill_empty(out, _bcast_present(present, out), fill_value)
            return _from_leading(out)
        out = _seg(op, data, codes, size)
        present = _counts(codes, size, mask=mask if skipna else None) > 0
        out = _fill_empty(out, _bcast_present(present, out), fill_value)
        return _from_leading(out)

    return kernel


def _bcast_present(present, out):
    """Broadcast a (size,)-or-(size, ...) presence mask against out."""
    if present.ndim < out.ndim:
        present = present.reshape(present.shape + (1,) * (out.ndim - present.ndim))
    return present


max_ = _make_minmax("max", skipna=False)
nanmax = _make_minmax("max", skipna=True)
min_ = _make_minmax("min", skipna=False)
nanmin = _make_minmax("min", skipna=True)


def nanlen(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
    """Count of non-NaN elements per group (the reference's 'nanlen')."""
    codes = _safe_codes(group_idx, size)
    data = _to_leading(array)
    mask = _nan_mask(data, kw.get("nat", False))
    out = _counts(codes, size, mask=mask, dtype=dtype or jnp.int32)
    if mask is None and out.ndim < data.ndim:
        out = jnp.broadcast_to(
            out.reshape(out.shape + (1,) * (data.ndim - out.ndim)), (size,) + data.shape[1:]
        )
    if fill_value is not None and fill_value != 0:
        out = _fill_empty(out, out > 0, fill_value)
    return _from_leading(out)


def len_(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
    codes = _safe_codes(group_idx, size)
    data = _to_leading(array)
    out = _counts(codes, size, dtype=dtype or jnp.int32)
    out = jnp.broadcast_to(
        out.reshape(out.shape + (1,) * (data.ndim - out.ndim)), (size,) + data.shape[1:]
    )
    return _from_leading(out)


_PALLAS_MULTISTAT_PROBE_RESULT: list = []


def _pallas_multistat_runtime_ok() -> bool:
    from .pallas_kernels import segment_multistat_pallas

    def _exec():
        data = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
        sums, _nan, _pos, _neg, mins, maxs = segment_multistat_pallas(
            data, jnp.zeros(8, jnp.int32), 2
        )
        return (
            np.asarray(sums)[0, 0] == float(sum(range(0, 8 * 128, 128)))
            and np.asarray(mins)[0, 0] == 0.0
            and np.asarray(maxs)[0, 0] == 7 * 128.0
        )

    return _probed_ok(_PALLAS_MULTISTAT_PROBE_RESULT, _exec, "multistat")


_FUSABLE_LEG_NAMES = frozenset(
    {"sum", "nansum", "len", "nanlen", "min", "nanmin", "max", "nanmax"}
)


def _fused_stats_leading(data, codes, size: int, want: tuple):
    """Multi-output single-pass segment statistics on the marker paths —
    the general form of the old ``_fused_sum_counts`` special case, shared
    by the mean/var kernels and the multi-statistic fusion planner
    (aggregations.fused_chunk_stats).

    ``data`` (N, ...) in the leading kernel layout, ``codes`` already
    sentinel-safe; ``want`` ⊆ {sum, nansum, len, nanlen, min, nanmin, max,
    nanmax}. The GEMM/Pallas kernels zero non-finite values themselves and
    emit NaN/±inf marker counts, so ONE pass yields every sum variant
    (IEEE re-applied per skipna mode), non-NaN counts as
    ``rowcount(codes) - nan_c`` (rowcount touches only the codes), and —
    on the Pallas megakernel — grouped min/max with all accumulators
    resident in VMEM across the sequential grid. Returns ``{name: (size,
    ...)}`` or None when the policy resolves to scatter or a guard fails
    (callers then run the per-leg kernels, which XLA still fuses into one
    program).
    """
    want = tuple(want)
    if not set(want) <= _FUSABLE_LEG_NAMES:
        return None
    sumish = [w for w in want if w in ("sum", "nansum")]
    minmaxish = [w for w in want if w in ("min", "nanmin", "max", "nanmax")]
    if not (sumish or minmaxish):
        return None  # counts alone never justify a fused data pass
    if not jnp.issubdtype(data.dtype, jnp.floating) or data.shape[0] >= 2**24:
        # 2^24: the f32 marker-count exactness guard
        return None
    impl = _segment_sum_impl(data, size)
    mins = maxs = None
    if minmaxish:
        from .options import OPTIONS

        ok = (
            str(data.dtype) in ("float32", "bfloat16")
            and size <= min(
                OPTIONS["pallas_num_groups_max"],
                OPTIONS["pallas_minmax_num_groups_max"],
            )
            and data.shape[0] >= 8
            and impl == "pallas"
            and (not _on_tpu() or _pallas_multistat_runtime_ok())
        )
        if not ok:
            return None
        from .pallas_kernels import segment_multistat_pallas

        sums, nan_c, pos_c, neg_c, mins, maxs = segment_multistat_pallas(
            data, codes, size, interpret=not _on_tpu()
        )
    elif impl == "matmul":
        sums, nan_c, pos_c, neg_c = _seg_matmul_raw(data, codes, size)
    elif impl == "pallas":
        from .pallas_kernels import segment_sum_raw_pallas

        sums, nan_c, pos_c, neg_c = segment_sum_raw_pallas(
            data, codes, size, interpret=not _on_tpu()
        )
    else:
        return None

    from .utils import reapply_nonfinite

    out: dict = {}
    acc = sums.dtype
    if "sum" in want:
        out["sum"] = reapply_nonfinite(sums, nan_c, pos_c, neg_c, skipna=False)
    if "nansum" in want:
        out["nansum"] = reapply_nonfinite(sums, nan_c, pos_c, neg_c, skipna=True)
    if "len" in want or "nanlen" in want:
        rowcount = _bcast_present(_counts(codes, size), sums)  # codes-only
        if "len" in want:
            out["len"] = jnp.broadcast_to(rowcount, sums.shape)
        if "nanlen" in want:
            out["nanlen"] = (
                jnp.broadcast_to(rowcount, sums.shape).astype(acc)
                - nan_c.astype(acc)
            )
    if minmaxish:
        # the megakernel computes min/max NaN-masked; the propagating
        # variants re-inject NaN exactly as _make_minmax does (its
        # has_nan flag IS nan_c > 0 here)
        has_nan = nan_c > 0
        nanv = jnp.asarray(jnp.nan, mins.dtype)
        if "nanmin" in want:
            out["nanmin"] = mins
        if "min" in want:
            out["min"] = jnp.where(has_nan, nanv, mins)
        if "nanmax" in want:
            out["nanmax"] = maxs
        if "max" in want:
            out["max"] = jnp.where(has_nan, nanv, maxs)
    return out


def fused_segment_stats(group_idx, array, *, size: int, want: tuple):
    """Plugin-layout entry to :func:`_fused_stats_leading`: ``array``
    (..., N) in, ``{name: (..., size)}`` out (or None) — what the fusion
    planner's chunk executor calls."""
    with jax.named_scope(KERNEL_SCOPE):
        codes = _safe_codes(group_idx, size)
        data = _to_leading(array)
        raw = _fused_stats_leading(data, codes, size, tuple(want))
        if raw is None:
            return None
        return {k: _from_leading(v) for k, v in raw.items()}


def _fused_sum_counts(cast, codes, size: int):
    """Single-pass skipna (total, non-NaN count): the mean/var fast path,
    now one ``want`` set of the general fused primitive."""
    got = _fused_stats_leading(cast, codes, size, ("nansum", "nanlen"))
    if got is None:
        return None
    return got["nansum"], got["nanlen"]


def _mean_impl(group_idx, array, *, size, fill_value, dtype, skipna):
    codes = _safe_codes(group_idx, size)
    data = _to_leading(array)
    if dtype is None and not jnp.issubdtype(data.dtype, jnp.floating):
        dtype = jnp.result_type(data.dtype, jnp.float32)

    cast = _maybe_cast(data, dtype)
    fused = _fused_sum_counts(cast, codes, size) if skipna else None
    if fused is not None:
        total, cnt = fused
        orig_dtype = cast.dtype
    else:
        mask = _nan_mask(data) if skipna else None
        sdata = cast if mask is None else jnp.where(mask, cast, jnp.zeros((), cast.dtype))
        total = _seg("sum", sdata, codes, size)  # f32-accumulated for bf16/f16
        # counts in int32: exact, and immune to the data dtype (bf16 counts
        # saturate at 256 — the mean of 2000 values must not divide by 256)
        cnt = _bcast_present(_counts(codes, size, mask=mask), total).astype(total.dtype)
        orig_dtype = sdata.dtype
    out = total / cnt
    out = _fill_empty(out, cnt > 0, fill_value if fill_value is not None else jnp.nan)
    if out.dtype != orig_dtype and jnp.issubdtype(orig_dtype, jnp.floating):
        out = out.astype(orig_dtype)  # divide in f32, present as bf16
    return _from_leading(out)


def mean(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
    return _mean_impl(group_idx, array, size=size, fill_value=fill_value, dtype=dtype, skipna=False)


def nanmean(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
    return _mean_impl(group_idx, array, size=size, fill_value=fill_value, dtype=dtype, skipna=True)


def _sum_of_squares(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, skipna=False, **kw):
    arr = jnp.asarray(array)
    out_dtype = arr.dtype
    if jnp.issubdtype(arr.dtype, jnp.floating) and _acc_dtype(arr.dtype) != arr.dtype:
        arr = arr.astype(_acc_dtype(arr.dtype))  # square in f32, not bf16
    out = (nansum if skipna else sum_)(
        group_idx, arr * arr, axis=axis, size=size, fill_value=fill_value, dtype=dtype,
        keep_acc=kw.get("keep_acc", False),
    )
    if dtype is None and not kw.get("keep_acc", False) and out.dtype != out_dtype and jnp.issubdtype(out_dtype, jnp.floating):
        out = out.astype(out_dtype)
    return out


sum_of_squares = partial(_sum_of_squares, skipna=False)
nansum_of_squares = partial(_sum_of_squares, skipna=True)


# ---------------------------------------------------------------------------
# variance: single-pass-per-chunk triple, numerically shifted by the group
# mean (the TPU analogue of the reference's var_chunk, aggregations.py:348-389)
# ---------------------------------------------------------------------------


def _var_impl(group_idx, array, *, size, fill_value, dtype, ddof, skipna, std):
    codes = _safe_codes(group_idx, size)
    data = _to_leading(array)
    if dtype is None and not jnp.issubdtype(data.dtype, jnp.floating):
        dtype = jnp.result_type(data.dtype, jnp.float32)
    cast = _maybe_cast(data, dtype)
    # mask on the PRE-cast data: an int dtype request would destroy the
    # NaNs before the mask sees them (review regression)
    mask = _nan_mask(data) if skipna else None
    zdata = cast if mask is None else jnp.where(mask, cast, jnp.zeros((), cast.dtype))
    fused = _fused_sum_counts(cast, codes, size) if skipna else None
    if fused is not None:
        total, cnt_f = fused
        cnt_b = cnt_f
    else:
        total = _seg("sum", zdata, codes, size)  # f32-accumulated for bf16/f16
        cnt_b = _bcast_present(_counts(codes, size, mask=mask), total)  # int32, exact
        cnt_f = cnt_b.astype(total.dtype)
    mean_g = total / jnp.where(cnt_f > 0, cnt_f, 1)
    # gather each element's group mean and accumulate squared deviations
    # (zdata - gathered promotes bf16 deviations to the f32 mean dtype, so
    # the squared-deviation accumulation stays f32 end-to-end)
    gathered = jnp.take(jnp.concatenate([mean_g, jnp.zeros((1,) + mean_g.shape[1:], mean_g.dtype)]), codes, axis=0)
    dev = zdata - gathered
    if mask is not None:
        dev = jnp.where(mask, dev, jnp.zeros((), dev.dtype))
    m2 = _seg("sum", dev * dev, codes, size)
    denom = cnt_f - ddof
    out = m2 / jnp.where(denom > 0, denom, 1)
    out = jnp.where(denom > 0, out, jnp.asarray(jnp.nan, out.dtype))
    if std:
        out = jnp.sqrt(out)
    out = _fill_empty(out, cnt_b > 0, fill_value if fill_value is not None else jnp.nan)
    if out.dtype != zdata.dtype and jnp.issubdtype(zdata.dtype, jnp.floating):
        out = out.astype(zdata.dtype)
    return _from_leading(out)


def var(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, ddof=0, **kw):
    return _var_impl(group_idx, array, size=size, fill_value=fill_value, dtype=dtype, ddof=ddof, skipna=False, std=False)


def nanvar(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, ddof=0, **kw):
    return _var_impl(group_idx, array, size=size, fill_value=fill_value, dtype=dtype, ddof=ddof, skipna=True, std=False)


def std(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, ddof=0, **kw):
    return _var_impl(group_idx, array, size=size, fill_value=fill_value, dtype=dtype, ddof=ddof, skipna=False, std=True)


def nanstd(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, ddof=0, **kw):
    return _var_impl(group_idx, array, size=size, fill_value=fill_value, dtype=dtype, ddof=ddof, skipna=True, std=True)


def var_chunk(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, skipna=True, **kw):
    """Per-chunk variance statistics: MultiArray (sum_sq_dev, sum, count).

    The deviations are taken about the *chunk's* per-group mean, so the
    combine stage needs only the Chan-style merge (see parallel.mapreduce /
    aggregations._var_combine) — this is the numerically-stable single-pass
    strategy of the reference (aggregations.py:348-451), expressed as a
    pytree so collectives apply leaf-wise.
    """
    codes = _safe_codes(group_idx, size)
    data = _to_leading(array)
    if dtype is None and not jnp.issubdtype(data.dtype, jnp.floating):
        dtype = jnp.result_type(data.dtype, jnp.float32)
    cast = _maybe_cast(data, dtype)
    # mask on the PRE-cast data: an int dtype request would destroy the
    # NaNs before the mask sees them (review regression)
    mask = _nan_mask(data) if skipna else None
    zdata = cast if mask is None else jnp.where(mask, cast, jnp.zeros((), cast.dtype))
    fused = _fused_sum_counts(cast, codes, size) if skipna else None
    if fused is not None:
        total, cnt_f = fused
    else:
        total = _seg("sum", zdata, codes, size)  # f32-accumulated for bf16/f16
        cnt_f = _bcast_present(_counts(codes, size, mask=mask), total).astype(total.dtype)
    mean_g = total / jnp.where(cnt_f > 0, cnt_f, 1)
    gathered = jnp.take(
        jnp.concatenate([mean_g, jnp.zeros((1,) + mean_g.shape[1:], mean_g.dtype)]), codes, axis=0
    )
    dev = zdata - gathered
    if mask is not None:
        dev = jnp.where(mask, dev, jnp.zeros((), dev.dtype))
    m2 = _seg("sum", dev * dev, codes, size)
    # the triple stays in the f32 accumulator dtype deliberately: these are
    # cross-shard intermediates (psum'd by the Chan merge); the final dtype
    # cast happens once, at finalize
    if cnt_f.shape != total.shape:
        cnt_f = jnp.broadcast_to(cnt_f, total.shape)
    return MultiArray(
        (_from_leading(m2), _from_leading(total), _from_leading(cnt_f))
    )


# ---------------------------------------------------------------------------
# bool reductions
# ---------------------------------------------------------------------------


def all_(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
    codes = _safe_codes(group_idx, size)
    data = _to_leading(array).astype(bool).astype(jnp.int8)
    out = _seg("min", data, codes, size).astype(bool)
    present = _counts(codes, size) > 0
    out = jnp.where(_bcast_present(present, out), out, True if fill_value is None else fill_value)
    return _from_leading(out)


def any_(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
    codes = _safe_codes(group_idx, size)
    data = _to_leading(array).astype(bool).astype(jnp.int8)
    out = _seg("max", data, codes, size).astype(bool)
    present = _counts(codes, size) > 0
    out = jnp.where(_bcast_present(present, out), out, False if fill_value is None else fill_value)
    return _from_leading(out)


# ---------------------------------------------------------------------------
# argreductions and positional first/last
# ---------------------------------------------------------------------------


def _arg_impl(group_idx, array, *, size, fill_value, skipna, arg_of_max, nat=False):
    codes = _safe_codes(group_idx, size)
    data = _to_leading(array)
    mask = _nan_mask(data, nat)
    key = data
    if mask is not None:
        op = "max" if arg_of_max else "min"
        if skipna:
            ident = jnp.asarray(minmax_identity(op, data.dtype), dtype=data.dtype)
            key = jnp.where(mask, data, ident)
        else:
            # NaN propagates with numpy's exact semantics: the FIRST NaN
            # position wins outright, even when the group also holds ±inf
            # (np.argmax([inf, nan]) == 1). NaNs are excluded from the
            # value race here and re-applied as a position override below.
            absorb = jnp.asarray(
                minmax_identity("min" if arg_of_max else "max", data.dtype),
                dtype=data.dtype,
            )
            key = jnp.where(mask, data, absorb)
    best = _seg("max" if arg_of_max else "min", key, codes, size)
    best_per_elem = jnp.take(
        jnp.concatenate([best, jnp.zeros((1,) + best.shape[1:], best.dtype)]), codes, axis=0
    )
    iota = _iota_like(key)
    cand = jnp.where(key == best_per_elem, iota, _BIG)
    if skipna and mask is not None:
        cand = jnp.where(mask, cand, _BIG)
    out = _seg("min", cand, codes, size)
    if not skipna and mask is not None:
        # numpy parity: any NaN (NaT) in the group short-circuits the value
        # race — the first missing position is the answer
        first_nan = _seg("min", jnp.where(mask, _BIG, iota), codes, size)
        out = jnp.where(first_nan < _BIG, first_nan, out)
    valid_counts = _counts(codes, size, mask=mask if skipna else None)
    present = _bcast_present(valid_counts, out) > 0
    fv = -1 if fill_value is None else fill_value
    out = jnp.where(present & (out < _BIG), out, fv)
    return _from_leading(out)


def argmax(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
    return _arg_impl(group_idx, array, size=size, fill_value=fill_value, skipna=False, arg_of_max=True, nat=kw.get("nat", False))


def argmin(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
    return _arg_impl(group_idx, array, size=size, fill_value=fill_value, skipna=False, arg_of_max=False, nat=kw.get("nat", False))


def nanargmax(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
    return _arg_impl(group_idx, array, size=size, fill_value=fill_value, skipna=True, arg_of_max=True, nat=kw.get("nat", False))


def nanargmin(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
    return _arg_impl(group_idx, array, size=size, fill_value=fill_value, skipna=True, arg_of_max=False, nat=kw.get("nat", False))


def _firstlast_impl(group_idx, array, *, size, fill_value, skipna, last, nat=False):
    codes = _safe_codes(group_idx, size)
    data = _to_leading(array)
    mask = _nan_mask(data, nat) if skipna else None
    iota = _iota_like(data)
    if mask is not None:
        iota = jnp.where(mask, iota, -1 if last else _BIG)
    pos = _seg("max" if last else "min", iota, codes, size)
    valid = (pos >= 0) & (pos < _BIG)
    gather_at = jnp.clip(pos, 0, data.shape[0] - 1)
    out = jnp.take_along_axis(data, gather_at, axis=0)
    is_inexact = jnp.issubdtype(data.dtype, jnp.floating) or jnp.issubdtype(
        data.dtype, jnp.complexfloating
    )
    fv = fill_value if fill_value is not None else (jnp.nan if is_inexact else 0)
    out = _promote_for_nan_fill(out, fv)
    out = jnp.where(valid, out, jnp.asarray(fv).astype(out.dtype))
    return _from_leading(out)


def first(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
    return _firstlast_impl(group_idx, array, size=size, fill_value=fill_value, skipna=False, last=False, nat=kw.get("nat", False))


def last(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
    return _firstlast_impl(group_idx, array, size=size, fill_value=fill_value, skipna=False, last=True, nat=kw.get("nat", False))


def nanfirst(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
    return _firstlast_impl(group_idx, array, size=size, fill_value=fill_value, skipna=True, last=False, nat=kw.get("nat", False))


def nanlast(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
    return _firstlast_impl(group_idx, array, size=size, fill_value=fill_value, skipna=True, last=True, nat=kw.get("nat", False))


# ---------------------------------------------------------------------------
# order statistics: quantile / median / mode via a (group, value) lex sort.
#
# jax.lax.sort with num_keys=2 gives a per-column lexicographic sort along
# axis 0 — the shape-static TPU replacement for the reference's complex-
# number partition trick (aggregate_flox.py:50-130).
# ---------------------------------------------------------------------------


def _group_sort(codes, data):
    """Sort (codes, data) lexicographically along axis 0; NaNs sort last
    within each group (lax.sort total order puts NaN after +inf)."""
    codes_b = jnp.broadcast_to(
        codes.reshape((codes.shape[0],) + (1,) * (data.ndim - 1)), data.shape
    ).astype(jnp.int32)
    iota = _iota_like(data)
    sorted_codes, sorted_data, sorted_iota = jax.lax.sort(
        (codes_b, data, iota), dimension=0, num_keys=2
    )
    return sorted_codes, sorted_data, sorted_iota


def _uint_type(dtype):
    return {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}[
        jnp.dtype(dtype).itemsize
    ]


def _monotonic_uint(data):
    """Order-preserving unsigned-integer view: floats use the IEEE sign
    trick (negatives bit-invert, non-negatives set the sign bit — unsigned
    compare then matches total order, NaN above +inf); signed ints flip
    the sign bit (two's complement is already ordered below it); unsigned
    ints pass through."""
    ut = _uint_type(data.dtype)
    nbits = jnp.dtype(ut).itemsize * 8
    bits = jax.lax.bitcast_convert_type(data, ut)
    sign = jnp.asarray(1, ut) << (nbits - 1)
    if jnp.issubdtype(data.dtype, jnp.floating):
        return jnp.where((bits & sign) != 0, ~bits, bits | sign)
    if jnp.issubdtype(data.dtype, jnp.signedinteger):
        return bits ^ sign
    return bits


def _uint_to_value(key, dtype):
    ut = _uint_type(dtype)
    nbits = jnp.dtype(ut).itemsize * 8
    sign = jnp.asarray(1, ut) << (nbits - 1)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        bits = jnp.where((key & sign) != 0, key ^ sign, ~key)
    elif jnp.issubdtype(jnp.dtype(dtype), jnp.signedinteger):
        bits = key ^ sign
    else:
        bits = key
    return jax.lax.bitcast_convert_type(bits.astype(ut), dtype)


def _radix_select(data, codes, size, ranks, valid_mask, axis_name=None):
    """Exact per-group order statistics WITHOUT sorting: MSB radix
    bisection over the monotonic integer view of ``data``.

    ``ranks``: (m, size) + data.shape[1:], m independent sets of 0-based
    within-group ranks. Returns the same shape — the exact rank-th
    smallest valid value per group/column (bit-identical to indexing the
    sorted data).

    Why: ``lax.sort`` on TPU is many materialized HBM passes; this runs
    ``nbits`` counting passes where each count is a segment-sum — i.e. the
    one-hot MXU GEMM / Pallas path under the ``segment_sum_impl`` policy —
    and ALL m rank lanes share every pass's data read (their predicates
    stack into one widened segment-sum). The sort-free analogue of the
    reference's complex-partition trick (aggregate_flox.py:50-130), shaped
    for the hardware instead of for numpy.

    ``axis_name``: mesh axis name(s) when running inside ``shard_map`` on a
    SHARD of the data. The bisection state (prefix, rank) is per-group and
    replicated; the only cross-element op is the counting segment-sum, so a
    ``psum`` per pass makes the selection exactly global — the selected
    value is reconstructed bit-by-bit from the counts, never gathered from
    any one shard. This is what lets quantile/median run method='map-reduce'
    on a mesh (the reference must force blockwise for order statistics,
    core.py:685-709: its combine would need whole groups on one worker).
    """
    ut = _uint_type(data.dtype)
    nbits = jnp.dtype(ut).itemsize * 8
    keys = _valid_keys(data, valid_mask)
    n = data.shape[0]
    if axis_name is not None:
        from .parallel.mesh import axis_size

        axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        n = n * int(np.prod([axis_size(a) for a in axes]))
    # counts ride f32 (the MXU path) when the GLOBAL count cannot overflow
    # its exact integer range; int32 scatter otherwise
    cdtype = jnp.float32 if n < 2**24 else jnp.int32
    m = ranks.shape[0]
    trail = data.shape[1:]

    state0 = (jnp.zeros((m, size) + trail, ut), ranks.astype(jnp.int32))

    def body(i, st):
        prefix, rank = st
        bshift = jnp.asarray(nbits - 1 - i, ut)
        cnt = _radix_pass_count(keys, codes, size, prefix, bshift, cdtype)
        if axis_name is not None:
            # int32 psum: exact, and local f32 counts were exact below 2^24
            cnt = jax.lax.psum(cnt, axis_name)
        return _radix_update(prefix, rank, cnt, bshift)

    prefix, _ = jax.lax.fori_loop(0, nbits, body, state0)
    return _uint_to_value(prefix, data.dtype)


def _valid_keys(data, valid_mask):
    """Monotonic uint view with invalid lanes parked at the maximal key:
    every valid key is strictly below it (valid data is never
    NaN-with-full-payload), so ranks targeting the first nn elements can
    never land on one."""
    ut = _uint_type(data.dtype)
    keys = _monotonic_uint(data)
    if valid_mask is not None:
        keys = jnp.where(valid_mask, keys, ~jnp.zeros((), ut))
    return keys


def _radix_pass_count(keys, codes, size, prefix, bshift, cdtype):
    """One counting pass of the radix bisection: per rank lane, how many
    elements fall in the candidate subtree whose high bits match the
    prefix. Shared by the eager/mesh select (fori body above) and the
    streaming driver (streaming._stream_quantile), which accumulates it
    slab by slab."""
    ut = keys.dtype
    m = prefix.shape[0]
    pad_row = jnp.zeros((m, 1) + keys.shape[1:], ut)
    shifted = jnp.right_shift(keys, bshift)
    # candidate subtree with bit b == 0: high bits match the prefix
    # (whose bit b is still 0) after the shift
    table = jnp.concatenate([jnp.right_shift(prefix, bshift), pad_row], axis=1)
    pred = shifted[None] == jnp.take(table, codes, axis=1)
    # one widened segment-sum counts every rank lane in a single pass
    cnt = _seg("sum", jnp.moveaxis(pred, 0, -1).astype(cdtype), codes, size)
    return jnp.moveaxis(cnt, -1, 0).astype(jnp.int32)  # (m, size, ...)


def _radix_update(prefix, rank, cnt, bshift):
    """Bisection step: lanes whose rank falls past the zero-subtree count
    descend into the one-subtree (set bit b, discount the count)."""
    take_hi = rank >= cnt
    bit = jnp.asarray(1, prefix.dtype) << bshift
    return (
        jnp.where(take_hi, prefix | bit, prefix),
        jnp.where(take_hi, rank - cnt, rank),
    )


# Continuous interpolation families share numpy's (alpha, beta)
# plotting-position parametrization: h = q*(n + 1 - a - b) + a - 1,
# clipped to [0, n-1], linearly interpolated. The discrete variants
# (lower/higher/nearest/midpoint) derive from the linear h.
_ALPHA_BETA = {
    "linear": (1.0, 1.0),
    "hazen": (0.5, 0.5),
    "weibull": (0.0, 0.0),
    "interpolated_inverted_cdf": (0.0, 1.0),
    "median_unbiased": (1 / 3, 1 / 3),
    "normal_unbiased": (3 / 8, 3 / 8),
}


def _quantile_alpha_beta(method: str):
    if method in _ALPHA_BETA:
        return _ALPHA_BETA[method]
    if method in ("lower", "higher", "nearest", "midpoint"):
        return 1.0, 1.0
    raise ValueError(
        f"Unsupported quantile method {method!r}; supported: "
        f"{sorted(_ALPHA_BETA) + ['lower', 'higher', 'nearest', 'midpoint']} "
        "(the numpy engine additionally supports every np.quantile method)."
    )


def _quantile_rank_sets(qs, nnf, method, alpha, beta):
    """Every within-group rank the stacked bisection must select, across
    ALL q values (each counting pass serves every lane), plus per-q meta
    (pos, lo_in, ia, ib) for the interpolation. Shared by the eager/mesh
    select and the streaming driver."""
    rank_list: list = []
    meta = []
    for qi in qs:
        pos = qi * (nnf + 1 - alpha - beta) + (alpha - 1)
        pos = jnp.clip(pos, 0, jnp.maximum(nnf - 1, 0))
        lo_in = jnp.floor(pos).astype(jnp.int32)
        hi_in = jnp.ceil(pos).astype(jnp.int32)
        if method == "nearest":
            # np.quantile rounds the virtual index half-to-even
            ia = ib = len(rank_list)
            rank_list.append(jnp.round(pos).astype(jnp.int32))
        elif method == "lower":
            ia = ib = len(rank_list)
            rank_list.append(lo_in)
        elif method == "higher":
            ia = ib = len(rank_list)
            rank_list.append(hi_in)
        else:
            ia, ib = len(rank_list), len(rank_list) + 1
            rank_list += [lo_in, hi_in]
        meta.append((pos, lo_in, ia, ib))
    return jnp.stack(rank_list), meta


def _quantile_interp_value(method, meta_k, selected, dtype):
    """Interpolate one q's value from the radix-selected order statistics —
    the ONE place the select-path method branches live, shared by the
    eager/mesh kernel and the streaming driver (streaming._stream_quantile).
    'nearest' selected its rounded rank directly, so it reads v_lo."""
    pos, lo_in, ia, ib = meta_k
    v_lo, v_hi = selected[ia], selected[ib]
    if method in ("lower", "nearest"):
        return v_lo
    if method == "higher":
        return v_hi
    if method == "midpoint":
        return (v_lo + v_hi) / 2
    frac = (pos - lo_in).astype(dtype)
    return v_lo + frac * (v_hi - v_lo)


def _quantile_impl_choice(data=None, size: int = 0) -> str:
    """Sort-vs-select for grouped order statistics. ``"auto"`` resolves to
    the autotune store's observed winner when the tuner is on (the on-chip
    ``quantile_gbps`` sweep and seeded BENCH_HISTORY rounds feed it —
    mechanically resolving the open decision docs/engines.md used to
    carry); sort is the measured CPU status quo otherwise."""
    from .options import OPTIONS

    policy = OPTIONS["quantile_impl"]
    if policy == "auto":
        if OPTIONS["autotune"] and data is not None:
            from . import autotune

            nelems = int(np.prod(data.shape)) if data.ndim else 0
            return autotune.decide(
                "quantile", "sort", ("sort", "select"),
                dtype=str(data.dtype), ngroups=size, nelems=nelems,
            )
        return "sort"
    return policy


def _quantile_impl(group_idx, array, *, size, fill_value, dtype, q, skipna,
                   method="linear", axis_name=None):
    codes = _safe_codes(group_idx, size)
    data = _to_leading(array)
    if not jnp.issubdtype(data.dtype, jnp.floating):
        default_float = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        data = data.astype(dtype if dtype is not None else default_float)
    mask = _nan_mask(data)
    if not skipna and mask is not None:
        # NaN propagates: a group containing any NaN yields NaN.
        has_nan_local = _seg("max", (~mask).astype(jnp.int8), codes, size)
        if axis_name is not None:
            has_nan_local = jax.lax.pmax(has_nan_local, axis_name)
        group_has_nan = has_nan_local > 0
    else:
        group_has_nan = None
    qs = np.atleast_1d(np.asarray(q, dtype=np.float64))
    scalar_q = np.ndim(q) == 0
    # on a mesh shard only the counting bisection distributes (the sort
    # path would sort shard-locally and select wrong elements)
    sel = axis_name is not None or _quantile_impl_choice(data, size) == "select"

    if sel:
        sorted_data = data  # only its shape/dtype are consulted below
        off_b = None
    else:
        _, sorted_data, _ = _group_sort(codes, data)
        full_counts = _counts(codes, size)  # (size,)
        offsets = jnp.cumsum(full_counts) - full_counts  # exclusive, (size,)
        # broadcast offsets across trailing dims; keep them INTEGER — only
        # the within-group position goes through float, so gather indices
        # stay exact even when the total length exceeds float32's integer
        # range.
        off_b = offsets.reshape((size,) + (1,) * (sorted_data.ndim - 1))
    nn = _counts(codes, size, mask=mask)  # non-NaN counts, (size, ...) or (size,)
    if axis_name is not None:
        nn = jax.lax.psum(nn, axis_name)  # global group sizes
    nn_full = jnp.broadcast_to(
        _bcast_present(nn, sorted_data[:1]), (size,) + sorted_data.shape[1:]
    )

    alpha, beta = _quantile_alpha_beta(method)

    outs = []
    nmax = sorted_data.shape[0]
    # index arithmetic in f32/f64, never the data dtype: bf16 cannot even
    # represent odd counts above 256, which would select wrong elements
    idx_dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    nnf = nn_full.astype(idx_dtype)

    def _pos_ranks(qi):
        pos = qi * (nnf + 1 - alpha - beta) + (alpha - 1)  # within-group, float
        pos = jnp.clip(pos, 0, jnp.maximum(nnf - 1, 0))
        return pos, jnp.floor(pos).astype(jnp.int32), jnp.ceil(pos).astype(jnp.int32)

    if sel:
        ranks, meta = _quantile_rank_sets(qs, nnf, method, alpha, beta)
        selected = _radix_select(data, codes, size, ranks, mask, axis_name=axis_name)

    for k, qi in enumerate(qs):
        if sel:
            val = _quantile_interp_value(method, meta[k], selected, sorted_data.dtype)
        else:
            pos, lo_in, hi_in = _pos_ranks(qi)
            lo_c = jnp.clip(off_b + lo_in, 0, nmax - 1)
            hi_c = jnp.clip(off_b + hi_in, 0, nmax - 1)
            v_lo = jnp.take_along_axis(sorted_data, lo_c, axis=0)
            v_hi = jnp.take_along_axis(sorted_data, hi_c, axis=0)
            frac = (pos - lo_in).astype(sorted_data.dtype)
            if method == "lower":
                val = v_lo
            elif method == "higher":
                val = v_hi
            elif method == "nearest":
                # np.quantile rounds the virtual index half-to-even
                nr = jnp.clip(off_b + jnp.round(pos).astype(jnp.int32), 0, nmax - 1)
                val = jnp.take_along_axis(sorted_data, nr, axis=0)
            elif method == "midpoint":
                val = (v_lo + v_hi) / 2
            else:  # all continuous families: linear interpolation at h
                val = v_lo + frac * (v_hi - v_lo)
        empty = nn_full <= 0
        fv = fill_value if fill_value is not None else jnp.nan
        val = jnp.where(empty, jnp.asarray(fv).astype(val.dtype), val)
        if group_has_nan is not None:
            val = jnp.where(
                _bcast_present(group_has_nan, val), jnp.asarray(jnp.nan, val.dtype), val
            )
        outs.append(_from_leading(val))
    if scalar_q:
        return outs[0]
    return jnp.stack(outs, axis=0)


def quantile(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, q, method="linear", axis_name=None, **kw):
    return _quantile_impl(group_idx, array, size=size, fill_value=fill_value, dtype=dtype, q=q, skipna=False, method=method, axis_name=axis_name)


def nanquantile(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, q, method="linear", axis_name=None, **kw):
    return _quantile_impl(group_idx, array, size=size, fill_value=fill_value, dtype=dtype, q=q, skipna=True, method=method, axis_name=axis_name)


def median(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, axis_name=None, **kw):
    return _quantile_impl(group_idx, array, size=size, fill_value=fill_value, dtype=dtype, q=0.5, skipna=False, axis_name=axis_name)


def nanmedian(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, axis_name=None, **kw):
    return _quantile_impl(group_idx, array, size=size, fill_value=fill_value, dtype=dtype, q=0.5, skipna=True, axis_name=axis_name)


def _mode_impl(group_idx, array, *, size, fill_value, skipna):
    codes = _safe_codes(group_idx, size)
    data = _to_leading(array)
    mask = _nan_mask(data)
    sorted_codes, sorted_data, _ = _group_sort(codes, data)
    smask = None
    if mask is not None:
        smask = ~jnp.isnan(sorted_data)
    n = sorted_data.shape[0]
    iota = _iota_like(sorted_data)
    val_same = sorted_data[1:] == sorted_data[:-1]
    if smask is not None and not skipna:
        # scipy.stats.mode "propagate" (scipy >= 1.11, via np.unique's
        # equal_nan): NaNs count as ONE candidate value with their full
        # multiplicity. The sort parks NaNs last within each group, so
        # merging adjacent NaN lanes makes them a single run.
        val_same = val_same | (~smask[1:] & ~smask[:-1])
    prev_same = jnp.concatenate(
        [
            jnp.zeros((1,) + sorted_data.shape[1:], bool),
            val_same & (sorted_codes[1:] == sorted_codes[:-1]),
        ]
    )
    # run start index per position: cumulative max of start markers
    start_marker = jnp.where(prev_same, -1, iota)
    run_start = jax.lax.cummax(start_marker, axis=0)
    next_diff = jnp.concatenate(
        [prev_same[1:], jnp.zeros((1,) + sorted_data.shape[1:], bool)]
    )
    end_marker = jnp.where(next_diff, n, iota)
    run_end = jax.lax.cummin(end_marker[::-1], axis=0)[::-1]
    run_len = run_end - run_start + 1
    if smask is not None and skipna:
        run_len = jnp.where(smask, run_len, -1)
    # codes are identical across trailing columns; segment ids must be 1-D
    codes1d = sorted_codes if sorted_codes.ndim == 1 else sorted_codes[(slice(None),) + (0,) * (sorted_codes.ndim - 1)]
    best_len = _seg("max", run_len, codes1d, size)
    best_per_elem = jnp.take(
        jnp.concatenate([best_len, jnp.zeros((1,) + best_len.shape[1:], best_len.dtype)]),
        codes1d,
        axis=0,
    )
    cand = jnp.where((run_len == best_per_elem) & (run_len > 0), iota, _BIG)
    pos = _seg("min", cand, codes1d, size)
    valid = pos < _BIG
    out = jnp.take_along_axis(sorted_data, jnp.clip(pos, 0, n - 1), axis=0)
    fv = fill_value if fill_value is not None else (jnp.nan if jnp.issubdtype(out.dtype, jnp.floating) else 0)
    out = _promote_for_nan_fill(out, fv)
    out = jnp.where(valid, out, jnp.asarray(fv).astype(out.dtype))
    return _from_leading(out)


def mode(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
    return _mode_impl(group_idx, array, size=size, fill_value=fill_value, skipna=False)


def nanmode(group_idx, array, *, axis=-1, size, fill_value=None, dtype=None, **kw):
    return _mode_impl(group_idx, array, size=size, fill_value=fill_value, skipna=True)


# ---------------------------------------------------------------------------
# grouped scans: segmented associative_scan (log-depth on device).
#
# The segmented-scan operator ``((v1,f1),(v2,f2)) -> (f2 ? v2 : v1⊕v2, f1|f2)``
# is associative for any associative ⊕; flags mark group-run starts after a
# stable sort by code. The same operator drives the cross-shard Blelloch
# combine in parallel/scan.py (reference analogue: aggregations.py:792-846).
# ---------------------------------------------------------------------------


def _segmented_scan(values, flags, op, reverse=False):
    def combine(a, b):
        va, fa = a
        vb, fb = b
        return jnp.where(fb, vb, op(va, vb)), fa | fb

    out, _ = jax.lax.associative_scan(combine, (values, flags), axis=0, reverse=reverse)
    return out


def _grouped_scan_setup(group_idx, array):
    """Stable-sort by code; return permutation machinery + flags."""
    codes = jnp.asarray(group_idx).astype(jnp.int32).reshape(-1)
    data = _to_leading(array)
    perm = jnp.argsort(codes, stable=True)
    inv = jnp.argsort(perm)
    sorted_codes = codes[perm]
    sorted_data = jnp.take(data, perm, axis=0)
    starts = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_codes[1:] != sorted_codes[:-1]]
    )
    flags = jnp.broadcast_to(
        starts.reshape((starts.shape[0],) + (1,) * (data.ndim - 1)), data.shape
    )
    return sorted_codes, sorted_data, flags, inv


def _cumsum_impl(group_idx, array, *, size, dtype, skipna, nat=False):
    if not nat:
        data = _to_leading(array)
        cast = _maybe_cast(data, dtype)
        if _scan_impl_choice(cast, size) == "pallas":
            from .pallas_kernels import segment_cumsum_pallas

            codes = jnp.asarray(group_idx).astype(jnp.int32).reshape(-1)
            out = segment_cumsum_pallas(
                cast, codes, size, skipna=skipna, interpret=not _on_tpu()
            )
            return _from_leading(out)
    _, sorted_data, flags, inv = _grouped_scan_setup(group_idx, array)
    # nat: int64-viewed datetimes/timedeltas, missing = INT64_MIN. Unlike
    # floats (where NaN propagates through + arithmetically), the sentinel
    # must be masked out of the running sum and, for the non-skipna scan,
    # re-poisoned from the first missing position onward (numpy cumsum of a
    # NaT timedelta is NaT thereafter).
    mask = _nan_mask(sorted_data, nat) if (skipna or nat) else None
    vals = sorted_data if mask is None else jnp.where(mask, sorted_data, jnp.zeros((), sorted_data.dtype))
    vals = _maybe_cast(vals, dtype)
    out_dtype = vals.dtype
    if jnp.issubdtype(vals.dtype, jnp.floating) and _acc_dtype(vals.dtype) != vals.dtype:
        vals = vals.astype(_acc_dtype(vals.dtype))  # bf16 running sums saturate
    scanned = _segmented_scan(vals, flags, jnp.add)
    if nat and not skipna and mask is not None:
        seen_missing = _segmented_scan((~mask).astype(jnp.int32), flags, jnp.maximum)
        scanned = jnp.where(seen_missing > 0, jnp.asarray(_NAT_INT, scanned.dtype), scanned)
    if scanned.dtype != out_dtype:
        scanned = scanned.astype(out_dtype)
    return _from_leading(jnp.take(scanned, inv, axis=0))


def cumsum(group_idx, array, *, axis=-1, size=None, fill_value=None, dtype=None, **kw):
    return _cumsum_impl(group_idx, array, size=size, dtype=dtype, skipna=False, nat=kw.get("nat", False))


def nancumsum(group_idx, array, *, axis=-1, size=None, fill_value=None, dtype=None, **kw):
    return _cumsum_impl(group_idx, array, size=size, dtype=dtype, skipna=True, nat=kw.get("nat", False))


def _ffill_impl(group_idx, array, *, reverse, nat=False):
    codes = jnp.asarray(group_idx).astype(jnp.int32).reshape(-1)
    data = _to_leading(array)
    if reverse:
        codes = codes[::-1]
        data = data[::-1]
    sorted_codes, sorted_data, flags, inv = _grouped_scan_setup(codes, _from_leading(data))
    mask = _nan_mask(sorted_data, nat)
    if mask is None:
        out = sorted_data
    else:
        iota = _iota_like(sorted_data)
        valid_idx = jnp.where(mask, iota, -1)
        last_valid = _segmented_scan(valid_idx, flags, jnp.maximum)
        gathered = jnp.take_along_axis(sorted_data, jnp.clip(last_valid, 0, None), axis=0)
        # "no prior valid" stays missing: NaT for int64-viewed datetimes
        missing = (
            jnp.asarray(_NAT_INT, sorted_data.dtype)
            if nat and jnp.issubdtype(sorted_data.dtype, jnp.signedinteger)
            else jnp.asarray(jnp.nan, sorted_data.dtype)
        )
        out = jnp.where(last_valid >= 0, gathered, missing)
    out = jnp.take(out, inv, axis=0)
    if reverse:
        out = out[::-1]
    return _from_leading(out)


def ffill(group_idx, array, *, axis=-1, size=None, fill_value=None, dtype=None, **kw):
    return _ffill_impl(group_idx, array, reverse=False, nat=kw.get("nat", False))


def bfill(group_idx, array, *, axis=-1, size=None, fill_value=None, dtype=None, **kw):
    return _ffill_impl(group_idx, array, reverse=True, nat=kw.get("nat", False))


# ---------------------------------------------------------------------------
# registry + dispatch
# ---------------------------------------------------------------------------

KERNELS: dict[str, Callable[..., Any]] = {
    "sum": sum_,
    "nansum": nansum,
    "prod": prod,
    "nanprod": nanprod,
    "max": max_,
    "nanmax": nanmax,
    "min": min_,
    "nanmin": nanmin,
    "mean": mean,
    "nanmean": nanmean,
    "var": var,
    "nanvar": nanvar,
    "std": std,
    "nanstd": nanstd,
    "var_chunk": var_chunk,
    "count": nanlen,
    "nanlen": nanlen,
    "len": len_,
    "all": all_,
    "any": any_,
    "argmax": argmax,
    "argmin": argmin,
    "nanargmax": nanargmax,
    "nanargmin": nanargmin,
    "first": first,
    "last": last,
    "nanfirst": nanfirst,
    "nanlast": nanlast,
    "median": median,
    "nanmedian": nanmedian,
    "quantile": quantile,
    "nanquantile": nanquantile,
    "mode": mode,
    "nanmode": nanmode,
    "sum_of_squares": sum_of_squares,
    "nansum_of_squares": nansum_of_squares,
    "cumsum": cumsum,
    "nancumsum": nancumsum,
    "ffill": ffill,
    "bfill": bfill,
}


def generic_kernel(func: str, group_idx, array, **kwargs):
    """Engine entry point for the 'jax' engine (plugin-boundary parity with
    generic_aggregate, aggregations.py:60-133)."""
    try:
        fn = KERNELS[func]
    except KeyError:
        raise NotImplementedError(f"jax engine has no kernel for {func!r}") from None
    from . import telemetry

    if telemetry.detailed():
        # under jit this fires at TRACE time, so per-kernel counts are a
        # retrace signal (executions are fused into compiled programs);
        # eager (jit=False) calls count once per execution
        telemetry.METRICS.inc(f"kernel.trace.{func}")
    with jax.named_scope(KERNEL_SCOPE):
        return fn(group_idx, array, **kwargs)


# ---------------------------------------------------------------------------
# sort engine: present-groups execution (the high-cardinality regime).
#
# Every kernel above is dense over the static label universe ``size`` — the
# right shape contract for XLA, and an OOM machine when ``size`` is millions
# while each call touches a few thousand groups (user IDs, geohashes,
# station IDs). The sort engine is the TPU-native analogue of the
# reference's sort+``ufunc.reduceat`` engine (aggregate_flox.py:133-192):
# unique-ify the codes once (a sort), relabel them into the compact
# [0, n_present) domain, run the UNCHANGED dense kernels over a small
# banded capacity, and scatter back to the dense layout only where the
# caller asks for it — so accumulator bytes track the data, not the label
# universe. Element order is never permuted (only the codes are relabeled,
# monotonically), so every kernel family — including float sums, order
# statistics and position-tracking argreductions — is bit-identical to the
# dense path on the present groups WHEN both domains resolve to the same
# segment-op lowering. Off-TPU (the tier-1 surface) that is always true
# (auto = scatter at every size); on TPU the compact domain may cross the
# pallas/radixbin/matmul size gates the dense domain did not, reassociating
# float sums within the documented accuracy of those lowerings — the same
# caveat any segment_sum_impl flip has always carried (docs/engines.md).
# ---------------------------------------------------------------------------


from .cache import LRUCache

#: host-side memo of present-group tables: the serve/pipeline hot loops
#: re-reduce over the same factorized codes many times, and the O(N log N)
#: unique pass is pure overhead after the first call. Keyed on a content
#: fingerprint (not object identity — factorize_cached may rebuild equal
#: codes). Registered in cache.clear_all / cache.stats ("present_tables").
_PRESENT_CACHE: LRUCache = LRUCache(maxsize=64)

#: capacity bands are powers of two so repeated calls with drifting
#: present-group counts reuse the same compiled programs (the same reason
#: resilience's OOM ladder re-stages on a power-of-two ladder)
_PRESENT_CAP_MIN = 8


def _codes_fingerprint(codes: "np.ndarray", size: int) -> tuple:
    """Cheap content key for the present-table memo: blake2b over the raw
    code bytes (a few ms/1e6 codes — an order cheaper than the unique pass
    it saves) + shape/dtype/size."""
    import hashlib

    h = hashlib.blake2b(np.ascontiguousarray(codes).view(np.uint8), digest_size=16)
    return (h.hexdigest(), codes.shape, str(codes.dtype), int(size))


def present_groups(codes: "np.ndarray", size: int) -> "np.ndarray":
    """Sorted unique valid codes of a host code array (the "present" table).

    ``codes``: integer codes with -1 meaning "missing label". Memoized on
    content (see :data:`_PRESENT_CACHE`).
    """
    codes = np.asarray(codes).reshape(-1)
    fingerprint = _codes_fingerprint(codes, size)
    hit = _PRESENT_CACHE.get(fingerprint)
    if hit is not None:
        return hit
    present = np.unique(codes[codes >= 0]).astype(np.int64, copy=False)
    _PRESENT_CACHE[fingerprint] = present
    return present


def compact_codes(codes: "np.ndarray", present: "np.ndarray") -> "np.ndarray":
    """Relabel ``codes`` into the compact [0, n_present) domain.

    Monotone (present is sorted), order-preserving, and -1 (missing) maps
    to -1 — so per-group element order, and therefore every accumulation
    order, is exactly the dense path's.
    """
    codes = np.asarray(codes).reshape(-1)
    out = np.searchsorted(present, codes).astype(np.int32)
    out[codes < 0] = -1
    return np.ascontiguousarray(out)


def present_cap(n_present: int, size: int) -> int:
    """Banded compact-domain capacity: the next power of two above
    ``n_present``, with at least one empty pad slot whenever the dense
    universe has absent groups. The pad slot is load-bearing for
    bit-identity: it makes the compact reduction contain an empty group
    exactly when the dense one does, so the empty-fill dtype promotions
    (``_promote_for_nan_fill``) and ``_astype_final``'s NaN-carrying
    downcast guard fire identically on both paths — and its value is
    byte-for-byte the dense path's empty-group value, which the dense
    scatter-back reuses as its fill.
    """
    n_present = int(n_present)
    if n_present >= size:
        return max(1, n_present)
    want = max(_PRESENT_CAP_MIN, n_present + 1)
    cap = 1 << (want - 1).bit_length()
    return min(cap, size)


def scatter_present_dense(result_c, present: "np.ndarray", size: int):
    """Expand a compact (..., cap) result to the dense (..., size) layout.

    Host-side by design: the dense layout exists only in host RAM, never as
    a device allocation — that is the whole point of the engine. Absent
    groups take the value of the compact result's first pad slot (an empty
    group that went through the identical kernel/finalize pipeline), so the
    fill is bit-identical to the dense path's empty-group value for every
    aggregation family, min_count mask and datetime round-trip included.
    Thin wrapper over :class:`multiarray.PresentGroups` — the container
    every runtime's compact layer rides to the host boundary.
    """
    from .multiarray import PresentGroups

    return PresentGroups(present, np.asarray(result_c), size).scatter_dense()


def sort_segment_reduce(op: str, data, codes, *, ncap: int):
    """Device-side present-groups segment reduction: ONE stable lex-sort of
    ``(codes, position)`` bins the rows by group, run boundaries on the
    sorted codes yield compact segment ids, and a single segment-``op``
    over ``ncap`` segments reduces each run.

    This is the jit-safe sibling of the host unique+compact orchestration
    (``present_groups``/``compact_codes``) for callers whose codes are
    traced. No shipped runtime needs it yet — every current flow's codes
    are host-known before tracing, so compaction happens once up front —
    but traced-codes callers (a fully-fused serve program, per-shard
    re-compaction) get the same shape contract from it, tested directly.
    ``ncap`` must be a static upper bound on the number of distinct
    present groups (overflowing runs are dropped, so size the cap from
    host knowledge).

    ``data``: (N, ...) leading layout; ``codes``: (N,) int, -1 missing.
    Returns ``(present, out, n_present)``: the sorted present codes padded
    with -1 to (ncap,), the per-present-group reductions (ncap, ...), and
    the scalar count of distinct present groups.

    The position key makes the sort stable, so within a group the data
    keeps stream order and additive reductions accumulate in exactly the
    dense scatter path's order (bit-identity, not just equality).
    """
    codes = jnp.asarray(codes).astype(jnp.int32).reshape(-1)
    n = codes.shape[0]
    data = jnp.asarray(data)
    safe = jnp.where(codes < 0, _BIG, codes)
    iota = jnp.arange(n, dtype=jnp.int32)
    sorted_codes, perm = jax.lax.sort((safe, iota), dimension=0, num_keys=2)
    data_s = jnp.take(data, perm, axis=0)
    valid = sorted_codes != _BIG
    boundary = jnp.concatenate(
        [valid[:1], valid[1:] & (sorted_codes[1:] != sorted_codes[:-1])]
    )
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1  # -1 until the first run
    n_present = jnp.sum(boundary.astype(jnp.int32))
    # invalid rows (missing labels) and cap overflow park in segment ncap
    seg = jnp.where(valid & (seg >= 0) & (seg < ncap), seg, ncap)
    out = _seg_op_dense(op, data_s, seg, ncap)
    present = jax.ops.segment_max(
        jnp.where(valid, sorted_codes, -1), seg, num_segments=ncap + 1
    )[:ncap]
    present = jnp.where(present < 0, -1, present)  # empty segment_max -> INT_MIN
    return present, out, n_present


def _seg_op_dense(op: str, data_s, seg, ncap: int):
    """The segment-reduce leg of :func:`sort_segment_reduce` (split out so
    the radix-binning Pallas path can swap in below it)."""
    if op == "sum" and jnp.issubdtype(data_s.dtype, jnp.floating):
        acc = _acc_dtype(data_s.dtype)
        if data_s.dtype != acc:
            data_s = data_s.astype(acc)
    fn = {
        "sum": jax.ops.segment_sum,
        "prod": jax.ops.segment_prod,
        "max": jax.ops.segment_max,
        "min": jax.ops.segment_min,
    }[op]
    return fn(data_s, seg, num_segments=ncap + 1)[:ncap]


def sort_kernel(func: str, group_idx, array, *, axis=-1, size, fill_value=None,
                dtype=None, **kwargs):
    """Engine entry point for the 'sort' engine: host unique + compact
    relabel, the unchanged dense kernel over the banded capacity, then the
    dense scatter-back (this per-kernel form keeps the dense (..., size)
    return contract of ``generic_aggregate``; the memory-saving flows —
    eager/mesh/streaming orchestration in core/streaming — compact once
    per call and scatter once at the very end instead).

    Traced codes cannot be uniqued host-side; those calls fall back to the
    dense jax kernel (the mesh/fused programs compact before tracing).
    """
    if not isinstance(group_idx, np.ndarray):
        return generic_kernel(
            func, group_idx, array, axis=axis, size=size,
            fill_value=fill_value, dtype=dtype, **kwargs
        )
    present = present_groups(group_idx, size)
    ncap = present_cap(len(present), size)
    ccodes = compact_codes(group_idx, present)
    out = generic_kernel(
        func, ccodes, array, axis=axis, size=ncap,
        fill_value=fill_value, dtype=dtype, **kwargs
    )
    return scatter_present_dense(np.asarray(out), present, size)
