"""Compile every Pallas kernel an ``auto`` policy can select for a described
TPU v5e — no chip needed, no chip time spent.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: blocks past the 16 MiB of scoped VMEM, unaligned slices.
These cases compile at ``chip_smoke.py``'s widths and at each policy's
boundary, so a kernel the chip would refuse fails here first. The topology
is described inside a fixture, never at import time (on-chip-measurement
guide §2): only the worker that runs this file loads the TPU compiler.
"""

import os
import time

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from flox_tpu import pallas_kernels as pk  # noqa: E402
from flox_tpu.options import OPTIONS  # noqa: E402

NTIME, NCELL = 24 * (365 * 3 + 1), 181 * 360  # chip_smoke.py's headline widths
#: a kernel compiles in a few seconds; one past this is a regression worth
#: seeing (the unrolled min/max took 24 s, the multistat 50 s)
COMPILE_S_MAX = 30.0


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to an enabled persistent
    # cache but cannot be read back without one: keep the cache out of it.
    # The suite runs with x64 on; the chip runs without it (no f64 on v5e).
    saved = {k: getattr(jax.config, k) for k in ("jax_enable_compilation_cache", "jax_enable_x64")}
    for k in saved:
        jax.config.update(k, False)
    yield SingleDeviceSharding(topo.devices[0])
    for k, v in saved.items():
        jax.config.update(k, v)


CASES = {
    # name: (kernel call, N, K, groups)
    "sum-smoke": (lambda x, c, g: pk.segment_sum_pallas(x, c, g), NTIME, NCELL, 12),
    "sum-cap": (lambda x, c, g: pk.segment_sum_pallas(x, c, g), NTIME, 4096, OPTIONS["pallas_num_groups_max"]),
    "minmax-smoke": (lambda x, c, g: pk.segment_minmax_pallas(x, c, g, "max"), NTIME, NCELL, 12),
    "minmax-cap": (
        lambda x, c, g: pk.segment_minmax_pallas(x, c, g, "min"), NTIME, 4096,
        OPTIONS["pallas_minmax_num_groups_max"],
    ),
    "multistat-smoke": (lambda x, c, g: pk.segment_multistat_pallas(x, c, g), NTIME, NCELL, 12),
    "multistat-cap": (
        lambda x, c, g: pk.segment_multistat_pallas(x, c, g), NTIME, 4096,
        min(OPTIONS["pallas_num_groups_max"], OPTIONS["pallas_minmax_num_groups_max"]),
    ),
    "nancumsum-smoke": (
        lambda x, c, g: pk.segment_cumsum_pallas(x, c, g, skipna=True), NTIME, 4096, 12,
    ),
    "cumsum-cap": (
        # the scan carries one extra row for missing labels: size + 1 <= cap
        lambda x, c, g: pk.segment_cumsum_pallas(x, c, g, skipna=False), NTIME, 4096,
        OPTIONS["pallas_scan_num_groups_max"] - 1,
    ),
    "radixbin-past-dense": (
        lambda x, c, g: pk.segment_sum_radixbin_pallas(x, c, g), NTIME, 4096,
        OPTIONS["pallas_num_groups_max"] + 1,
    ),
    "radixbin-cap": (
        lambda x, c, g: pk.segment_sum_radixbin_pallas(x, c, g), NTIME, 4096,
        OPTIONS["radixbin_num_groups_max"],
    ),
    "radixbin-highcard-smoke": (
        # chip_smoke's sort-engine drive: 8 rows, 2^20 values, 4096-slot domain
        lambda x, c, g: pk.segment_sum_radixbin_pallas(x, c, g), 2**20, 8, 4096,
    ),
}
#: the policies admit bf16 wherever they admit f32 (the chip refused the
#: bf16 sum/min-max/multistat/radixbin kernels until they ran widened)
BF16 = ("sum-smoke", "minmax-cap", "multistat-smoke", "nancumsum-smoke", "radixbin-past-dense")


@pytest.mark.parametrize(
    "case, dtype", [(c, "float32") for c in sorted(CASES)] + [(c, "bfloat16") for c in BF16]
)
def test_kernel_compiles_for_v5e(one_chip, case, dtype):
    fn, n, k, groups = CASES[case]
    data = jax.ShapeDtypeStruct((n, k), jnp.dtype(dtype), sharding=one_chip)
    codes = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    t0 = time.perf_counter()
    compiled = jax.jit(lambda x, c: fn(x, c, groups)).lower(data, codes).compile()
    took = time.perf_counter() - t0
    assert "tpu_custom_call" in compiled.as_text()
    assert took < COMPILE_S_MAX, f"{case}: compile took {took:.1f} s"


#: the kernel name each Pallas call gives its custom call, which the chip's
#: trace shows as the op's name
KERNEL_NAMES = {
    "sum-smoke": "flox_segment_sum",
    "minmax-smoke": "flox_minmax",
    "multistat-smoke": "flox_multistat",
    "nancumsum-smoke": "flox_segment_cumsum",
    "radixbin-past-dense": "flox_radixbin",
}


@pytest.mark.parametrize("case", sorted(KERNEL_NAMES))
def test_kernel_carries_its_name(one_chip, case):
    fn, _, _, groups = CASES[case]
    data = jax.ShapeDtypeStruct((1024, 256), jnp.float32, sharding=one_chip)
    codes = jax.ShapeDtypeStruct((1024,), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda x, c: fn(x, c, groups)).lower(data, codes).as_text()
    assert f'kernel_name = "{KERNEL_NAMES[case]}"' in text


def test_span_blocked_program_fits_beside_the_array(one_chip, monkeypatch):
    """The NWM county program at a month's share over four chips, (180,
    1800, 4500) float32 reduced by (1800, 4500) codes into 842 groups (the
    benchmark's cell runs 120 steps), compiled as the chip builds it (the
    ``auto`` policies' TPU branch): its temporaries stay under a quarter of
    the input, where flattening the span first writes a relayouted copy of
    the whole array."""
    import numpy as np
    from jax.experimental.layout import Format, Layout

    from flox_tpu import core, kernels
    from flox_tpu.aggregations import _initialize_aggregation

    for name in ("_on_tpu", "_pallas_runtime_ok", "_pallas_radixbin_runtime_ok"):
        monkeypatch.setattr(kernels, name, lambda: True)
    # row-major arguments, as the chip holds them; left to itself the
    # described compile picks a column-major parameter and adds a copy
    arr = jax.ShapeDtypeStruct((180, 1800, 4500), jnp.float32, sharding=Format(Layout((0, 1, 2)), one_chip))
    codes = jax.ShapeDtypeStruct((1800, 4500), jnp.int32, sharding=Format(Layout((0, 1)), one_chip))
    agg = _initialize_aggregation("nanmean", None, np.dtype("float32"), None, 0, None)
    rows = core._span_block_rows(arr, codes.shape, agg)
    assert 0 < rows < 1800
    t0 = time.perf_counter()
    compiled = core._span_program(agg, size=842, rows=rows).lower(codes, arr).compile()
    took = time.perf_counter() - t0
    input_bytes = 180 * 1800 * 4500 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 0.25 * input_bytes
    assert "flox_radixbin" in compiled.as_text()  # 842 groups: past the dense kernel
    assert took < 2 * COMPILE_S_MAX, f"compile took {took:.1f} s"
