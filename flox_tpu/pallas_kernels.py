"""Pallas TPU kernels for the hot segment reductions (L1, below kernels.py).

Why a custom kernel: XLA lowers ``segment_sum`` to scatter-add, which
serializes on the VPU; the one-hot GEMM path (kernels._seg_matmul_sum) rides
the MXU but pays extra HBM traffic for its exactness marker columns. This
kernel gets both: the data streams HBM→VMEM exactly once, and each tile's
contribution is an **in-VMEM** one-hot matmul on the MXU — the one-hot and
the marker masks never touch HBM.

Layout: the kernel reads ``data`` in its natural trailing-reduce layout
(K, N) — i.e. the transpose of the (N, K) logical view ``_seg`` passes in.
Because every caller reaches ``_seg`` through ``_to_leading`` (a lazy
``moveaxis(-1, 0)``), the two transposes cancel under XLA and the HBM
buffer is consumed **in place**: no transposed copy, which at benchmark
scale (~7 GB) is the difference between running and OOM. The data is NOT
padded either — TPU Pallas supports non-divisible block shapes (edge-block
out-of-bounds reads are undefined), and undefined values are harmless here:
out-of-range N columns carry the sentinel code (all-zero one-hot row, so
they contract to exactly 0.0 against every group) and out-of-range K rows
are sliced off the output. Only ``codes`` (tiny) is padded, with the
sentinel.

Grid = (k_tiles, n_tiles) with the output block revisited across the n axis
(sequential TPU grid → accumulate with an init at n==0, the standard
reduction pattern). Non-finite values are zero-filled in VMEM and NaN/±inf
markers accumulate in three extra outputs so IEEE propagation is re-applied
exactly.

Reference analogue: the numpy_groupies bincount kernels this replaces
(aggregate_npg.py:7-126) — but tiled for the memory hierarchy the guide
describes (pallas_guide.md: HBM→VMEM→MXU).
"""

from __future__ import annotations

import functools

import numpy as np

__all__ = [
    "segment_sum_pallas",
    "segment_sum_raw_pallas",
    "segment_minmax_pallas",
    "segment_multistat_pallas",
    "pallas_available",
]


def pallas_available() -> bool:
    try:
        import jax.experimental.pallas  # noqa: F401

        return True
    except ImportError:  # pragma: no cover
        return False


def _two_sum(a, b):
    """Error-free transformation (Knuth): s + err == a + b exactly, with s
    the rounded f32 sum. Branch-free, 6 VPU flops; relies on XLA not
    reassociating floating-point (it does not, absent fast-math)."""
    s = a + b
    bb = s - a
    err = (a - (s - bb)) + (b - bb)
    return s, err


def _kernel(
    codes_ref, data_ref, out_ref, nan_ref, pos_ref, neg_ref, comp_ref=None,
    *, size_p, n_tile, accum,
):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    j = pl.program_id(1)  # position along the reduced (N) axis

    @pl.when(j == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)
        nan_ref[:] = jnp.zeros_like(nan_ref)
        pos_ref[:] = jnp.zeros_like(pos_ref)
        neg_ref[:] = jnp.zeros_like(neg_ref)
        if accum != "plain":
            comp_ref[:] = jnp.zeros_like(comp_ref)

    codes = codes_ref[0, :]  # (n_tile,)
    isnan, ispos, isneg, zeroed = _split_nonfinite(data_ref[:])  # (k_tile, n_tile)
    nonfinite = isnan | ispos | isneg
    onehot = (
        codes[:, None] == jax.lax.broadcasted_iota(jnp.int32, (n_tile, size_p), 1)
    ).astype(zeroed.dtype)  # (n_tile, size_p) — lives only in VMEM

    def contract(tile, precision):
        # (n_tile, size_p)ᵀ-contract-(k_tile, n_tile) -> (size_p, k_tile).
        # Edge-block garbage in `tile` multiplies a zero one-hot row (its
        # column carries the sentinel code), contributing exactly 0.0.
        return jax.lax.dot_general(
            onehot,
            tile,
            dimension_numbers=(((0,), (1,)), ((), ())),
            preferred_element_type=out_ref.dtype,
            precision=precision,
        )

    _accum_update(out_ref, comp_ref, onehot, zeroed, contract, accum)

    # Marker contracts are the MXU-bound tail: at HIGHEST each costs as much
    # as the sums pass (f32 = multi-pass bf16 on the MXU) and they triple the
    # kernel's FLOPs. Two savings: (1) 0/1 masks are exact in bf16 and the
    # MXU accumulates into f32 natively, so DEFAULT precision (single pass)
    # loses nothing; (2) all-finite tiles — the overwhelmingly common case —
    # skip the contracts entirely on a data-dependent scalar branch.
    @pl.when(jnp.any(nonfinite))
    def _markers():
        import jax as _jax

        d = _jax.lax.Precision.DEFAULT
        nan_ref[:] += contract(isnan.astype(zeroed.dtype), d)
        pos_ref[:] += contract(ispos.astype(zeroed.dtype), d)
        neg_ref[:] += contract(isneg.astype(zeroed.dtype), d)


def _split_nonfinite(data):
    """NaN / +inf / -inf masks of a data tile and the tile, widened to f32,
    with them zeroed. bf16 tiles run in f32 inside the kernel (exact):
    Mosaic on the v5e refused both the packed masks of a bf16 comparison
    and the bf16 one-hot contraction."""
    import jax.numpy as jnp

    wide = data.astype(jnp.float32)
    isnan = jnp.isnan(wide)
    ispos = jnp.isposinf(wide)
    isneg = jnp.isneginf(wide)
    zeroed = jnp.where(isnan | ispos | isneg, jnp.zeros((), wide.dtype), wide)
    return isnan, ispos, isneg, zeroed


def _accum_update(out_ref, comp_ref, onehot, zeroed, contract, accum):
    """Cross-tile accumulation of one tile's contraction into the running
    (out_ref, comp_ref) state, under the selected discipline — shared by
    the dense megakernel grid and the radix-binning blocked grid."""
    import jax
    import jax.numpy as jnp

    if accum == "kahan":
        # Kahan summation across the sequential n-grid: recovers most of the
        # bits a plain f32 running sum loses over many tiles — the accuracy
        # story on TPUs, where float64 hardware does not exist (the eager
        # CPU path gets true f64 via jax_enable_x64 instead).
        y = contract(zeroed, jax.lax.Precision.HIGHEST) - comp_ref[:]
        t = out_ref[:] + y
        comp_ref[:] = (t - out_ref[:]) - y
        out_ref[:] = t
    elif accum == "dd":
        # Double-double: the running sum is an unevaluated (hi, lo) f32
        # pair (out_ref, comp_ref), ~49 effective mantissa bits. Two error
        # sources are attacked separately:
        #  * intra-tile — each value is Dekker-split into a 12-bit-mantissa
        #    high part and an exact low remainder; the one-hot products are
        #    exact (x·1), so each contraction accumulates far fewer
        #    significant bits per addend and the two partial sums together
        #    carry (nearly) the full per-tile sum;
        #  * cross-tile — the partial sums merge into the (hi, lo) carry
        #    through error-free two_sum transforms, never dropping a
        #    rounding remainder on the floor.
        acc = out_ref.dtype
        z = zeroed.astype(acc)
        c = z * jnp.asarray(4097.0, acc)  # 2**12 + 1: split 24 -> 12 + 12
        z_hi = c - (c - z)
        z_lo = z - z_hi
        # the split constant overflows for |x| > f32max/4097 ≈ 8.3e34; such
        # values keep their low bits in the high part (intra-tile rounding
        # at that magnitude is the documented reordered-summation boundary)
        huge = jnp.abs(z) > jnp.asarray(8e34, acc)
        z_hi = jnp.where(huge, z, z_hi)
        z_lo = jnp.where(huge, jnp.zeros((), acc), z_lo)
        onehot_a = onehot.astype(acc)

        def contract_a(tile):
            return jax.lax.dot_general(
                onehot_a, tile,
                dimension_numbers=(((0,), (1,)), ((), ())),
                preferred_element_type=acc,
                precision=jax.lax.Precision.HIGHEST,
            )

        s, e1 = _two_sum(contract_a(z_hi), contract_a(z_lo))
        hi, e2 = _two_sum(out_ref[:], s)
        lo = comp_ref[:] + (e1 + e2)
        # renormalize so hi is the best single-f32 representation; Knuth
        # two_sum, not Fast2Sum — after catastrophic cross-tile
        # cancellation |lo| can exceed |hi| and Fast2Sum would drop the
        # carry's low-order bits exactly where they matter most
        hi2, lo2 = _two_sum(hi, lo)
        out_ref[:] = hi2
        comp_ref[:] = lo2
    else:
        out_ref[:] += contract(zeroed, jax.lax.Precision.HIGHEST)


@functools.lru_cache(maxsize=128)
def _build(
    k_pad: int, n_pad: int, size_p: int, dtype_str: str, acc_str: str, n_tile: int,
    k_tile: int, interpret: bool, accum: str,
):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kern = functools.partial(_kernel, size_p=size_p, n_tile=n_tile, accum=accum)
    k_tiles = k_pad // k_tile
    grid = (k_tiles, n_pad // n_tile)
    # Accumulator blocks are ``acc_str`` (f32 for bf16 data): the data tile
    # streams HBM→VMEM at its narrow width and the MXU contracts bf16×bf16
    # into f32 natively — a bf16 running sum would saturate at 256.
    acc = jnp.dtype(acc_str)
    # the Kahan compensation / double-double lo term rides as a 5th output
    # block (revisited per k-tile like the sums); pallas scratch does not
    # persist across the k grid axis, an output block does. Plain builds
    # skip it entirely.
    n_out = 4 if accum == "plain" else 5
    # outputs are padded to the block grid (they are tiny — size_p rows);
    # the data input is not (see module docstring).
    out_shape = [jax.ShapeDtypeStruct((size_p, k_pad), acc)] * n_out

    fn = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n_tile), lambda i, j: (0, j)),  # codes
            pl.BlockSpec((k_tile, n_tile), lambda i, j: (i, j)),  # data (K, N)
        ],
        out_specs=[pl.BlockSpec((size_p, k_tile), lambda i, j: (0, i))] * n_out,
        out_shape=out_shape,
        interpret=interpret,
        name="flox_segment_sum",
    )
    return jax.jit(fn)


def _tiles(n: int, k: int, size: int, k_tile_max: int = 512):
    """Shared tiling: lane-axis tiles are multiples of 128 (n for the data
    blocks, k for the output blocks), sublane rows multiples of 8.

    Every block must fit v5e's 16 MiB of scoped VMEM, which the compiler
    enforces and interpret mode does not: past 256 resident group rows the
    (rows, k_tile) accumulator blocks — up to five, double-buffered — halve
    k_tile (the dense sum at its 512-group cap needed 18 MiB at 512); the
    scan passes a lower ``k_tile_max`` for its (k_tile, n_tile) temps."""
    n_tile = 512 if n >= 512 else max(128, -(-n // 128) * 128)
    size_p = max(8, ((size + 7) // 8) * 8)
    if size_p > 256:
        k_tile_max = min(k_tile_max, 256)
    k_tile = min(k_tile_max, max(128, -(-k // 128) * 128))
    n_pad = -(-n // n_tile) * n_tile
    k_pad = -(-k // k_tile) * k_tile
    return n_tile, k_tile, n_pad, k_pad, size_p


def _minmax_identity(op: str, dtype):
    from .kernels import minmax_identity  # single source of truth

    return minmax_identity(op, dtype)


def _minmax_kernel(codes_ref, data_ref, out_ref, *, size, op, skip_nan=False):
    """Per-tile grouped min/max on the VPU: one select + lane-reduce per
    group (MXU cannot do the (max, ·) tropical semiring). VPU work scales
    with ``size``, which is why the policy gates on a group-count cap —
    below it the kernel stays HBM-bound where scatter serializes.

    The groups are a ``fori_loop``, not a Python unroll: Mosaic expands
    each statement over every vreg of the block, so an unrolled loop made
    compile time grow with ``size × k_tile × n_tile`` (24 s for 12 groups
    at 512×512 blocks on v5e, and ten times that at the 128-group cap);
    the loop compiles in under a second at any group count.

    ``skip_nan`` parks NaN lanes at the op's identity — the megakernel's
    mode, which stages RAW data once for every statistic; the propagating
    variants re-inject NaN outside from its NaN marker counts. The plain
    kernel's callers pre-map NaN to absorbing elements themselves."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    j = pl.program_id(1)
    ident = jnp.asarray(_minmax_identity(op, out_ref.dtype), out_ref.dtype)

    @pl.when(j == 0)
    def _init():
        out_ref[:] = jnp.full_like(out_ref, ident)

    codes = codes_ref[0, :]  # (n_tile,)
    # widened like the accumulator: bf16 min/max runs in f32 (exact), as
    # Mosaic cannot store single packed bf16 rows at a dynamic offset
    data = data_ref[:].astype(out_ref.dtype)  # (k_tile, n_tile)
    if skip_nan:
        data = jnp.where(jnp.isnan(data), ident, data)
    combine = jnp.maximum if op == "max" else jnp.minimum
    reduce_ = jnp.max if op == "max" else jnp.min

    def one_group(g, carry):
        # edge-block garbage lanes carry the sentinel code -> identity
        masked = jnp.where((codes == g)[None, :], data, ident)
        row = reduce_(masked, axis=1)[None, :]  # (1, k_tile)
        out_ref[pl.ds(g, 1), :] = combine(out_ref[pl.ds(g, 1), :], row)
        return carry

    # rows past ``size`` keep the identity from _init (sliced off outside)
    jax.lax.fori_loop(0, size, one_group, 0)


@functools.lru_cache(maxsize=128)
def _build_minmax(
    k_pad: int, n_pad: int, size: int, size_p: int, acc_str: str, n_tile: int,
    k_tile: int, interpret: bool, op: str,
):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kern = functools.partial(_minmax_kernel, size=size, op=op)
    fn = pl.pallas_call(
        kern,
        grid=(k_pad // k_tile, n_pad // n_tile),
        in_specs=[
            pl.BlockSpec((1, n_tile), lambda i, j: (0, j)),  # codes
            pl.BlockSpec((k_tile, n_tile), lambda i, j: (i, j)),  # data (K, N)
        ],
        out_specs=pl.BlockSpec((size_p, k_tile), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((size_p, k_pad), jnp.dtype(acc_str)),
        interpret=interpret,
        name="flox_minmax",
    )
    return jax.jit(fn)


def segment_minmax_pallas(data, codes, size: int, op: str, *, interpret: bool = False):
    """Segment-min/max ``data`` (N, K...) by ``codes`` (N,) -> (size, K...).

    Missing labels drop out; empty groups return the op's identity (the
    caller's ``_fill_empty`` handles presentation, exactly as for scatter).
    Callers pre-map NaN/NaT to absorbing elements (kernels._make_minmax), so
    no NaN ever reaches this kernel. Same in-place (K, N) consumption as
    ``segment_sum_pallas``.
    """
    import jax.numpy as jnp

    data = jnp.asarray(data)
    orig_shape = data.shape
    n = data.shape[0]
    flat = data.reshape(n, -1)
    k = flat.shape[1]
    flat_t = flat.T  # (K, N) — cancels the caller's moveaxis; no copy

    n_tile, k_tile, n_pad, k_pad, size_p = _tiles(n, k, size)

    codes = jnp.asarray(codes).astype(jnp.int32).reshape(-1)
    codes = jnp.where((codes < 0) | (codes >= size), size_p, codes)
    codes_p = jnp.pad(codes, (0, n_pad - n), constant_values=size_p).reshape(1, n_pad)

    from .kernels import _acc_dtype

    # the accumulator block is the widened dtype (f32 for bf16; exact)
    fn = _build_minmax(
        k_pad, n_pad, size, size_p, str(jnp.dtype(_acc_dtype(flat.dtype))),
        n_tile, k_tile, interpret, op,
    )
    out = fn(codes_p, flat_t)
    return out[:size, :k].reshape((size,) + orig_shape[1:]).astype(flat.dtype)


def _multistat_kernel(
    codes_ref, data_ref, out_ref, nan_ref, pos_ref, neg_ref, min_ref, max_ref,
    comp_ref=None, *, size, size_p, n_tile, accum,
):
    """The fused multi-statistic megakernel: ONE HBM→VMEM pass per tile
    feeds (a) the compensated one-hot sum contraction with its NaN/±inf
    marker outputs (:func:`_kernel`, verbatim — the sums are bit-identical
    to ``segment_sum_pallas`` at the same tiling) and (b) the VPU
    select-reduce grouped min AND max. Every accumulator — sums,
    compensation, markers, min, max — is an output block revisited across
    the sequential n grid, i.e. resident in VMEM for the whole pass; the
    data is read from HBM exactly once for the entire statistic set."""
    _kernel(
        codes_ref, data_ref, out_ref, nan_ref, pos_ref, neg_ref, comp_ref,
        size_p=size_p, n_tile=n_tile, accum=accum,
    )
    _minmax_kernel(codes_ref, data_ref, min_ref, size=size, op="min", skip_nan=True)
    _minmax_kernel(codes_ref, data_ref, max_ref, size=size, op="max", skip_nan=True)


@functools.lru_cache(maxsize=128)
def _build_multistat(
    k_pad: int, n_pad: int, size: int, size_p: int, dtype_str: str, acc_str: str,
    n_tile: int, k_tile: int, interpret: bool, accum: str,
):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kern = functools.partial(
        _multistat_kernel, size=size, size_p=size_p, n_tile=n_tile, accum=accum
    )
    grid = (k_pad // k_tile, n_pad // n_tile)
    acc = jnp.dtype(acc_str)
    # sums + 3 markers + min/max, all in the accumulator dtype (min/max of
    # bf16 data run widened, see _minmax_kernel), then the optional
    # Kahan/double-double compensation block
    out_shape = [jax.ShapeDtypeStruct((size_p, k_pad), acc)] * (6 if accum == "plain" else 7)
    fn = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n_tile), lambda i, j: (0, j)),  # codes
            pl.BlockSpec((k_tile, n_tile), lambda i, j: (i, j)),  # data (K, N)
        ],
        out_specs=[pl.BlockSpec((size_p, k_tile), lambda i, j: (0, i))] * len(out_shape),
        out_shape=out_shape,
        interpret=interpret,
        name="flox_multistat",
    )
    return jax.jit(fn)


def segment_multistat_pallas(
    data, codes, size: int, *, interpret: bool = False, accum: str | None = None,
):
    """One-pass multi-statistic segment reduction: ``data`` (N, K...) by
    ``codes`` (N,) -> ``(sums, nan_c, pos_c, neg_c, mins, maxs)``, each
    ``(size, K...)``.

    Sums are raw zero-filled compensated totals (apply
    ``utils.reapply_nonfinite`` per skipna mode — one kernel pass serves
    sum AND nansum); min/max are NaN-skipping with empty groups at the
    op's identity (re-inject NaN from ``nan_c`` for the propagating
    variants). Same tiling as ``segment_sum_pallas``, so the sums are
    bit-identical to it; f32/bf16 only.
    """
    import jax.numpy as jnp

    from .options import OPTIONS, VALID_ACCUMS

    if accum is None:
        accum = OPTIONS["pallas_accum"]
    if accum not in VALID_ACCUMS:
        raise ValueError(f"accum must be one of {VALID_ACCUMS}; got {accum!r}")

    data = jnp.asarray(data)
    orig_shape = data.shape
    n = data.shape[0]
    flat = data.reshape(n, -1)
    k = flat.shape[1]
    flat_t = flat.T  # (K, N) — cancels the caller's moveaxis; no copy

    n_tile, k_tile, n_pad, k_pad, size_p = _tiles(n, k, size)

    codes = jnp.asarray(codes).astype(jnp.int32).reshape(-1)
    codes = jnp.where((codes < 0) | (codes >= size), size_p, codes)
    codes_p = jnp.pad(codes, (0, n_pad - n), constant_values=size_p).reshape(1, n_pad)

    from .kernels import _acc_dtype

    fn = _build_multistat(
        k_pad, n_pad, size, size_p, str(flat.dtype),
        str(jnp.dtype(_acc_dtype(flat.dtype))), n_tile, k_tile, interpret,
        str(accum),
    )
    sums, nan_c, pos_c, neg_c, mins, maxs, *_comp = fn(codes_p, flat_t)

    def crop(x):
        return x[:size, :k].reshape((size,) + orig_shape[1:])

    return (
        crop(sums), crop(nan_c), crop(pos_c), crop(neg_c),
        crop(mins).astype(flat.dtype), crop(maxs).astype(flat.dtype),
    )


def _scan_kernel(
    codes_ref, data_ref, out_ref, carry_ref, *marker_refs,
    size_p, n_tile, skipna,
):
    """Grouped cumulative sum, one HBM pass.

    Per tile the grouped prefix is ONE matmul on the MXU:
    ``out = x @ T`` with ``T[l, m] = [l <= m] · [code_l == code_m]`` — the
    triangular-masked group-equality matrix, built in VMEM from the codes
    lane vector (data-independent, shared by every k row). Cross-tile state
    is a per-group running-sum block revisited along the n grid axis, read
    into each lane by a one-hot gather matmul and updated by a one-hot
    contraction — so the cost is independent of the group count (the
    sort-based XLA path this replaces pays an argsort plus a log-depth
    scan, each materialized through HBM).

    Nonfinite handling: ALL nonfinite values (NaN and ±inf) are zero-filled
    before the matmuls — any of them would otherwise poison other groups
    through the masked zeros (inf × 0 = NaN), and undefined edge-block
    garbage with an inf bit pattern would corrupt real outputs. IEEE
    prefix semantics are re-applied from 0/1 seen-marker prefixes computed
    with the same T (DEFAULT precision — exact on 0/1) plus per-group
    marker carry rows: a lane is NaN if its group's prefix saw a NaN
    (non-skipna only) or both +inf and -inf; else ±inf if it saw that
    inf; else the finite sum. The skipna variant (nancumsum) skips only
    the NaN poisoning — inf still propagates, as in ``np.nancumsum`` —
    and carries no NaN-marker row at all. A running group sum that
    OVERFLOWS is folded into the markers and the carry entry reset to 0,
    so the overflowing group reports ±inf from then on while the finite
    carry keeps the gather matmul poison-free.

    Known boundary: overflow detection reflects the MXU contraction's
    reduction order, not the sequential order. Mixed-sign values within a
    tile-width factor of float32 max can make a partial sum overflow where
    the true sequential prefix stays finite (or vice versa) — inherent to
    every reordered summation (pairwise included), not specific to this
    kernel. Data living at that scale belongs on the segmented XLA path
    (``scan_impl="segmented"``) or the x64 CPU engine.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if skipna:
        pcarry_ref, mcarry_ref = marker_refs
        ncarry_ref = None
    else:
        ncarry_ref, pcarry_ref, mcarry_ref = marker_refs

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        carry_ref[:] = jnp.zeros_like(carry_ref)
        if not skipna:
            ncarry_ref[:] = jnp.zeros_like(ncarry_ref)
        pcarry_ref[:] = jnp.zeros_like(pcarry_ref)
        mcarry_ref[:] = jnp.zeros_like(mcarry_ref)

    codes = codes_ref[0, :]  # (n_tile,) — sentinel ``size`` for missing,
    # ``size_p`` for padding (no one-hot column, no T-equality with real lanes)
    data = data_ref[:]  # (k_tile, n_tile)
    acc = carry_ref.dtype
    x = data.astype(acc)
    isnan = jnp.isnan(x)
    ispos = jnp.isposinf(x)
    isneg = jnp.isneginf(x)
    nonfinite = isnan | ispos | isneg
    x = jnp.where(nonfinite, jnp.zeros((), acc), x)

    lane = jax.lax.broadcasted_iota(jnp.int32, (n_tile, n_tile), 0)
    lane_t = jax.lax.broadcasted_iota(jnp.int32, (n_tile, n_tile), 1)
    tri_eq = ((codes[:, None] == codes[None, :]) & (lane <= lane_t)).astype(acc)
    onehot = (
        codes[:, None] == jax.lax.broadcasted_iota(jnp.int32, (n_tile, size_p), 1)
    ).astype(acc)  # (n_tile, size_p)

    hi = jax.lax.Precision.HIGHEST
    # 0/1 marker masks are exact at single-pass precision
    d = jax.lax.Precision.DEFAULT

    def mm(a, b, dims, prec):
        return jax.lax.dot_general(
            a, b, dimension_numbers=(dims, ((), ())),
            preferred_element_type=acc, precision=prec,
        )

    # in-tile grouped prefix + carried-in per-group offset per lane
    prefix = mm(x, tri_eq, ((1,), (0,)), hi)  # (k_tile, n_tile)
    carried = mm(carry_ref[:], onehot, ((0,), (1,)), hi)  # (k_tile, n_tile)
    out = prefix + carried

    def carried_marks():
        # markers seen by this lane's group in EARLIER tiles — gathered
        # before any update below, so this tile's own lanes are untouched
        # by its own value-infs (those enter via tri_eq prefixes below)
        cn = None
        if not skipna:
            cn = mm(ncarry_ref[:], onehot, ((0,), (1,)), d)  # (k_tile, n_tile)
        cp = mm(pcarry_ref[:], onehot, ((0,), (1,)), d)
        cm = mm(mcarry_ref[:], onehot, ((0,), (1,)), d)
        return cn, cp, cm

    def seen_in_tile():
        # value markers at-or-before each lane, plus the group-carry updates
        carried_n, carried_p, carried_m = carried_marks()
        posf = ispos.astype(acc)
        negf = isneg.astype(acc)
        sp = mm(posf, tri_eq, ((1,), (0,)), d) + carried_p
        sm = mm(negf, tri_eq, ((1,), (0,)), d) + carried_m
        pcarry_ref[:] = pcarry_ref[:] + mm(onehot, posf, ((0,), (1,)), d)
        mcarry_ref[:] = mcarry_ref[:] + mm(onehot, negf, ((0,), (1,)), d)
        if skipna:
            return None, sp, sm
        nanf = isnan.astype(acc)
        sn = mm(nanf, tri_eq, ((1,), (0,)), d) + carried_n
        ncarry_ref[:] = ncarry_ref[:] + mm(onehot, nanf, ((0,), (1,)), d)
        return sn, sp, sm

    def finish(seen_n, seen_p, seen_m, with_ovf):
        # IEEE prefix semantics per lane: NaN beats inf; +inf and -inf
        # together make NaN; a lone inf sign wins over any finite sum.
        if with_ovf:
            # Arithmetic OVERFLOW of the zero-filled running sum shows up as
            # ±inf in `out`. An event is genuine only if no value marker has
            # reached its lane (after one, the zero-filled arithmetic is
            # meaningless: a true ±inf running sum absorbs finite addends
            # and cannot re-overflow) AND no opposite-sign overflow happened
            # earlier in the tile (first sign wins, same absorb principle —
            # the cross-tile analogue is `nonfin` in _fold_overflow).
            # Genuine events feed the group markers so later tiles see them,
            # and stick to later in-tile lanes via tri_eq.
            seen_any = seen_p + seen_m
            if seen_n is not None:
                seen_any = seen_any + seen_n
            fresh = seen_any == 0
            o_p_raw = (fresh & jnp.isposinf(out)).astype(acc)
            o_m_raw = (fresh & jnp.isneginf(out)).astype(acc)
            # the prefix matmul's tree reduction can emit NaN directly
            # (opposite-sign inf partials from mixed-sign values near f32
            # max, with no inf lane): a first-class overflow event — else
            # the lane shows a transient NaN that later tiles silently
            # revert, breaking the sticky-group-state model (ADVICE r3)
            o_n_raw = (fresh & jnp.isnan(out)).astype(acc)
            s_p_raw = mm(o_p_raw, tri_eq, ((1,), (0,)), d)
            s_m_raw = mm(o_m_raw, tri_eq, ((1,), (0,)), d)
            s_n_raw = mm(o_n_raw, tri_eq, ((1,), (0,)), d)
            # first event wins per group (absorb principle); a lane never
            # suppresses itself because each lane is exactly one of
            # +inf / -inf / NaN
            o_p = o_p_raw * ((s_m_raw == 0) & (s_n_raw == 0)).astype(acc)
            o_m = o_m_raw * ((s_p_raw == 0) & (s_n_raw == 0)).astype(acc)
            o_n = o_n_raw * ((s_p_raw == 0) & (s_m_raw == 0)).astype(acc)
            if seen_n is None:
                # skipna carries no NaN row: degrade the NaN event to a
                # both-sign marker, mirroring raw_nan in _fold_overflow
                o_p = o_p + o_n
                o_m = o_m + o_n
            pcarry_ref[:] = pcarry_ref[:] + mm(onehot, o_p, ((0,), (1,)), d)
            mcarry_ref[:] = mcarry_ref[:] + mm(onehot, o_m, ((0,), (1,)), d)
            seen_p = seen_p + mm(o_p, tri_eq, ((1,), (0,)), d)
            seen_m = seen_m + mm(o_m, tri_eq, ((1,), (0,)), d)
            if seen_n is not None:
                ncarry_ref[:] = ncarry_ref[:] + mm(onehot, o_n, ((0,), (1,)), d)
                seen_n = seen_n + mm(o_n, tri_eq, ((1,), (0,)), d)
        nan_mask = (seen_p > 0) & (seen_m > 0)
        if seen_n is not None:
            nan_mask = nan_mask | (seen_n > 0)
        res = jnp.where(seen_p > 0, jnp.asarray(jnp.inf, acc), out)
        res = jnp.where(seen_m > 0, jnp.asarray(-jnp.inf, acc), res)
        res = jnp.where(nan_mask, jnp.asarray(jnp.nan, acc), res)
        out_ref[:] = res.astype(out_ref.dtype)

    # Flattened branch matrix (no nested conds — keeps the Mosaic control
    # flow at the shape already proven on hardware). The common clean tile
    # (no nonfinite values, no overflow, no marker ever recorded — checked
    # by a cheap VPU any-reduce over the tiny carry blocks) writes the sums
    # directly and pays zero marker matmuls.
    has_nf = jnp.any(nonfinite)
    # ~isfinite, not isinf: the prefix matmul's tree reduction can produce
    # NaN with no inf lane; such a tile must take an overflow branch so
    # finish() records the event instead of _clean emitting a transient NaN
    has_oinf = jnp.any(~jnp.isfinite(out))
    has_marks = jnp.any(pcarry_ref[:] > 0) | jnp.any(mcarry_ref[:] > 0)
    if not skipna:
        has_marks = has_marks | jnp.any(ncarry_ref[:] > 0)

    @pl.when(~has_nf & ~has_oinf & ~has_marks)
    def _clean():
        out_ref[:] = out.astype(out_ref.dtype)

    @pl.when(~has_nf & ~has_oinf & has_marks)
    def _marked():
        finish(*carried_marks(), False)

    @pl.when(~has_nf & has_oinf)
    def _ovf_only():
        finish(*carried_marks(), True)

    @pl.when(has_nf & ~has_oinf)
    def _vals_only():
        finish(*seen_in_tile(), False)

    @pl.when(has_nf & has_oinf)
    def _vals_ovf():
        finish(*seen_in_tile(), True)

    # New running totals: old carry + this tile's per-group sums. Both
    # addends are finite, but the sum can OVERFLOW — to ±inf, or even to
    # NaN when the matmul's tree reduction forms opposite-sign inf partials
    # from mixed-sign large values. Any nonfinite carry entry would poison
    # every group on the next tile's gather (nonfinite × one-hot 0 = NaN).
    # Keep the carry finite; backstop-record the event as a marker for
    # groups with no nonfinite state yet (an overflow after any marker —
    # including a reset-carry re-overflow — is an artifact: the group's
    # true state is already ±inf/NaN and absorbs finite addends).
    new_carry = carry_ref[:] + mm(onehot, x, ((0,), (1,)), hi)
    raw_p = jnp.isposinf(new_carry)
    raw_m = jnp.isneginf(new_carry)
    raw_nonfin = ~jnp.isfinite(new_carry)
    raw_nan = raw_nonfin & ~raw_p & ~raw_m

    @pl.when(jnp.any(raw_nonfin))
    def _fold_overflow():
        nonfin = (pcarry_ref[:] > 0) | (mcarry_ref[:] > 0)
        if not skipna:
            nonfin = nonfin | (ncarry_ref[:] > 0)
        pcarry_ref[:] = pcarry_ref[:] + (raw_p & ~nonfin).astype(acc)
        mcarry_ref[:] = mcarry_ref[:] + (raw_m & ~nonfin).astype(acc)
        if skipna:
            # no NaN row to record into: a tree-reduction NaN (order-lost
            # mixed-sign overflow) degrades to NaN via both inf markers
            pcarry_ref[:] = pcarry_ref[:] + (raw_nan & ~nonfin).astype(acc)
            mcarry_ref[:] = mcarry_ref[:] + (raw_nan & ~nonfin).astype(acc)
        else:
            ncarry_ref[:] = ncarry_ref[:] + (raw_nan & ~nonfin).astype(acc)

    carry_ref[:] = jnp.where(raw_nonfin, jnp.zeros((), acc), new_carry)


@functools.lru_cache(maxsize=128)
def _build_scan(
    k: int, n: int, n_pad: int, size_p: int, dtype_str: str, acc_str: str,
    n_tile: int, k_tile: int, interpret: bool, skipna: bool,
):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kern = functools.partial(
        _scan_kernel, size_p=size_p, n_tile=n_tile, skipna=skipna
    )
    k_tiles = -(-k // k_tile)
    grid = (k_tiles, n_pad // n_tile)
    acc = jnp.dtype(acc_str)
    fn = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n_tile), lambda i, j: (0, j)),  # codes
            pl.BlockSpec((k_tile, n_tile), lambda i, j: (i, j)),  # data (K, N)
        ],
        out_specs=[
            pl.BlockSpec((k_tile, n_tile), lambda i, j: (i, j)),  # out (K, N)
        ]
        # carry + marker carries: ±inf always, NaN only when it can poison
        + [pl.BlockSpec((size_p, k_tile), lambda i, j: (0, i))] * (3 if skipna else 4),
        out_shape=[jax.ShapeDtypeStruct((k, n), jnp.dtype(dtype_str))]
        + [jax.ShapeDtypeStruct((size_p, k_tiles * k_tile), acc)] * (3 if skipna else 4),
        interpret=interpret,
        name="flox_segment_cumsum",
    )
    return jax.jit(fn)


def segment_cumsum_pallas(data, codes, size: int, *, skipna: bool, interpret: bool = False):
    """Grouped cumulative sum of ``data`` (N, K...) by ``codes`` (N,), same
    shape out. Missing labels (code outside [0, size)) scan among themselves
    as one extra group — matching the sort-based kernel. f32/bf16; bf16
    accumulates in f32 and is cast back per element."""
    import jax.numpy as jnp

    data = jnp.asarray(data)
    orig_shape = data.shape
    n = data.shape[0]
    flat = data.reshape(n, -1)
    k = flat.shape[1]
    flat_t = flat.T  # (K, N) — cancels the caller's moveaxis; no copy

    # one extra carry row for the missing-label group (sentinel == size);
    # the scan holds about a dozen (k_tile, n_tile) temporaries in VMEM,
    # which at k_tile=512 overflowed the 16 MiB limit (19 MiB), and its
    # five branches compile in time proportional to the block (k_tile=256:
    # ~10 s at the group cap; 128: ~3 s)
    n_tile, k_tile, n_pad, _k_pad, size_p = _tiles(n, k, size + 1, k_tile_max=128)

    codes = jnp.asarray(codes).astype(jnp.int32).reshape(-1)
    codes = jnp.where((codes < 0) | (codes >= size), size, codes)
    codes_p = jnp.pad(codes, (0, n_pad - n), constant_values=size_p).reshape(1, n_pad)

    from .kernels import _acc_dtype

    fn = _build_scan(
        k, n, n_pad, size_p, str(flat.dtype), str(jnp.dtype(_acc_dtype(flat.dtype))),
        n_tile, k_tile, interpret, bool(skipna),
    )
    out, *_carries = fn(codes_p, flat_t)
    return out.T.reshape(orig_shape)


def segment_sum_pallas(
    data, codes, size: int, *, interpret: bool = False, accum: str | None = None,
    skipna: bool = False, return_nan_counts: bool = False,
):
    """Segment-sum ``data`` (N, K...) by ``codes`` (N,) -> (size, K...).

    Exact IEEE semantics (NaN/±inf propagate per group+column); missing
    labels (code outside [0, size)) drop out. f32/bf16 only. bf16 data
    accumulates — and returns — f32 (the MXU's native accumulate mode;
    see kernels._acc_dtype). ``accum`` (default: the ``pallas_accum``
    option) selects the cross-tile accumulation discipline: "plain",
    "kahan" (compensated), or "dd" (double-double with Dekker-split
    contractions — the strict-accuracy mode chasing the f64 oracle).

    The (N, K) logical view is consumed through its (K, N) transpose so a
    caller-side ``moveaxis(-1, 0)`` cancels and the kernel streams the
    original HBM buffer with no transposed copy.
    """
    sums, nan_c, pos_c, neg_c = segment_sum_raw_pallas(
        data, codes, size, interpret=interpret, accum=accum
    )
    from .utils import reapply_nonfinite

    out = reapply_nonfinite(sums, nan_c, pos_c, neg_c, skipna=skipna)
    if return_nan_counts:
        return out, nan_c
    return out


def segment_sum_raw_pallas(
    data, codes, size: int, *, interpret: bool = False, accum: str | None = None,
):
    """The kernel pass of :func:`segment_sum_pallas` without the IEEE
    re-application: raw zero-filled compensated sums plus the NaN/±inf
    marker counts, each ``(size, K...)`` — one pass can serve both the
    sum and nansum legs of a fused multi-statistic plan."""
    import jax.numpy as jnp

    from .options import OPTIONS, VALID_ACCUMS

    if accum is None:
        accum = OPTIONS["pallas_accum"]
    if accum not in VALID_ACCUMS:
        # same whitelist as the set_options validator: a typo like "khan"
        # must not silently select plain accumulation at lower accuracy
        raise ValueError(f"accum must be one of {VALID_ACCUMS}; got {accum!r}")

    data = jnp.asarray(data)
    orig_shape = data.shape
    n = data.shape[0]
    flat = data.reshape(n, -1)
    k = flat.shape[1]
    flat_t = flat.T  # (K, N) — cancels the caller's moveaxis; no copy

    n_tile, k_tile, n_pad, k_pad, size_p = _tiles(n, k, size)

    codes = jnp.asarray(codes).astype(jnp.int32).reshape(-1)
    # out-of-range codes (missing labels, padding) match no one-hot column
    codes = jnp.where((codes < 0) | (codes >= size), size_p, codes)
    codes_p = jnp.pad(codes, (0, n_pad - n), constant_values=size_p).reshape(1, n_pad)

    from .kernels import _acc_dtype

    # cache key uses k_pad: the program depends only on the tile grid, not
    # the exact trailing size (that enters via the final [:k] slice below)
    fn = _build(
        k_pad, n_pad, size_p, str(flat.dtype), str(jnp.dtype(_acc_dtype(flat.dtype))),
        n_tile, k_tile, interpret, str(accum),
    )
    sums, nan_c, pos_c, neg_c, *_comp = fn(codes_p, flat_t)

    def crop(x):
        return x[:size, :k].reshape((size,) + orig_shape[1:])

    return crop(sums), crop(nan_c), crop(pos_c), crop(neg_c)


# ---------------------------------------------------------------------------
# radix-binning segment sum: the high-cardinality sibling of the kernel
# above. The dense megakernel holds ONE (size_p, k_tile) accumulator block
# in VMEM, which caps it at ~pallas_num_groups_max groups; here the group
# axis is partitioned into g_tile-wide blocks and the grid walks
# (k_tiles, g_blocks, n_tiles) — each (g, i) accumulator tile stays
# VMEM-resident across its whole n sweep and is written back to HBM exactly
# once per pass, so VMEM holds only (n_tile x g_tile) one-hot +
# (g_tile, k_tile) accumulator blocks regardless of the group count.
#
# Intended input is the sort engine's compact domain with rows SORTED by
# code (kernels.sort_segment_reduce's binning pass): each data tile then
# intersects exactly one group block, every other (g, j) step skips the
# MXU contraction on a scalar branch, and consecutive skipped steps cost
# only the tile DMA. Unsorted input stays correct (out-of-block codes
# contract against zero one-hot rows) but pays the full g_blocks x MXU
# sweep.
# ---------------------------------------------------------------------------


def _radixbin_kernel(
    codes_ref, data_ref, out_ref, nan_ref, pos_ref, neg_ref, comp_ref=None,
    *, g_tile, n_tile, accum,
):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    g = pl.program_id(1)  # group-block position
    j = pl.program_id(2)  # position along the reduced (N) axis

    @pl.when(j == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)
        nan_ref[:] = jnp.zeros_like(nan_ref)
        pos_ref[:] = jnp.zeros_like(pos_ref)
        neg_ref[:] = jnp.zeros_like(neg_ref)
        if accum != "plain":
            comp_ref[:] = jnp.zeros_like(comp_ref)

    local = codes_ref[0, :] - g * g_tile  # (n_tile,) block-local codes
    inblock = (local >= 0) & (local < g_tile)
    data = data_ref[:]  # (k_tile, n_tile)

    @pl.when(jnp.any(inblock))
    def _contribute():
        # sentinel g_tile matches no one-hot column: out-of-block rows (and
        # the caller's missing/pad sentinel) contract to exactly 0.0
        codes = jnp.where(inblock, local, g_tile)
        isnan, ispos, isneg, zeroed = _split_nonfinite(data)
        nonfinite = isnan | ispos | isneg
        onehot = (
            codes[:, None]
            == jax.lax.broadcasted_iota(jnp.int32, (n_tile, g_tile), 1)
        ).astype(zeroed.dtype)  # (n_tile, g_tile) — lives only in VMEM

        def contract(tile, precision):
            return jax.lax.dot_general(
                onehot,
                tile,
                dimension_numbers=(((0,), (1,)), ((), ())),
                preferred_element_type=out_ref.dtype,
                precision=precision,
            )

        _accum_update(out_ref, comp_ref, onehot, zeroed, contract, accum)

        # same two marker savings as the dense kernel, with the gate
        # narrowed to non-finite values that actually fall in this block
        @pl.when(jnp.any(nonfinite & inblock[None, :]))
        def _markers():
            d = jax.lax.Precision.DEFAULT
            nan_ref[:] += contract(isnan.astype(zeroed.dtype), d)
            pos_ref[:] += contract(ispos.astype(zeroed.dtype), d)
            neg_ref[:] += contract(isneg.astype(zeroed.dtype), d)


@functools.lru_cache(maxsize=128)
def _build_radixbin(
    k_pad: int, n_pad: int, size_p: int, g_tile: int, dtype_str: str,
    acc_str: str, n_tile: int, k_tile: int, interpret: bool, accum: str,
):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kern = functools.partial(
        _radixbin_kernel, g_tile=g_tile, n_tile=n_tile, accum=accum
    )
    grid = (k_pad // k_tile, size_p // g_tile, n_pad // n_tile)
    acc = jnp.dtype(acc_str)
    n_out = 4 if accum == "plain" else 5
    out_shape = [jax.ShapeDtypeStruct((size_p, k_pad), acc)] * n_out

    fn = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n_tile), lambda i, g, j: (0, j)),  # codes
            pl.BlockSpec((k_tile, n_tile), lambda i, g, j: (i, j)),  # data (K, N)
        ],
        out_specs=[pl.BlockSpec((g_tile, k_tile), lambda i, g, j: (g, i))] * n_out,
        out_shape=out_shape,
        interpret=interpret,
        name="flox_radixbin",
    )
    return jax.jit(fn)


#: group-block width: lane-width multiple for the one-hot's minor axis and
#: sublane multiple for the accumulator block — one 512-wide block holds
#: the whole dense-kernel regime, more blocks scale the group axis
_RADIXBIN_G_TILE = 512


def segment_sum_radixbin_pallas(
    data, codes, size: int, *, interpret: bool = False, accum: str | None = None,
    skipna: bool = False,
):
    """Segment-sum ``data`` (N, K...) by ``codes`` (N,) -> (size, K...) via
    the radix-binning blocked grid (see the section comment above): exact
    IEEE semantics and accumulation disciplines identical to
    :func:`segment_sum_pallas`, with the group count bounded by the
    ``segment_sum_radixbin_num_groups_max`` option instead of VMEM."""
    import jax.numpy as jnp

    from .options import OPTIONS, VALID_ACCUMS

    if accum is None:
        accum = OPTIONS["pallas_accum"]
    if accum not in VALID_ACCUMS:
        raise ValueError(f"accum must be one of {VALID_ACCUMS}; got {accum!r}")

    data = jnp.asarray(data)
    orig_shape = data.shape
    n = data.shape[0]
    flat = data.reshape(n, -1)
    k = flat.shape[1]
    flat_t = flat.T  # (K, N) — cancels the caller's moveaxis; no copy

    n_tile, k_tile, n_pad, k_pad, _ = _tiles(n, k, size)
    g_tile = min(_RADIXBIN_G_TILE, max(8, ((size + 7) // 8) * 8))
    size_p = -(-size // g_tile) * g_tile

    codes = jnp.asarray(codes).astype(jnp.int32).reshape(-1)
    # out-of-range codes (missing labels, padding) fall outside every block
    codes = jnp.where((codes < 0) | (codes >= size), size_p, codes)
    codes_p = jnp.pad(codes, (0, n_pad - n), constant_values=size_p).reshape(1, n_pad)

    from .kernels import _acc_dtype

    fn = _build_radixbin(
        k_pad, n_pad, size_p, g_tile, str(flat.dtype),
        str(jnp.dtype(_acc_dtype(flat.dtype))), n_tile, k_tile, interpret,
        str(accum),
    )
    sums, nan_c, pos_c, neg_c, *_comp = fn(codes_p, flat_t)

    def crop(x):
        return x[:size, :k].reshape((size,) + orig_shape[1:])

    from .utils import reapply_nonfinite

    return reapply_nonfinite(
        crop(sums), crop(nan_c), crop(pos_c), crop(neg_c), skipna=skipna
    )
