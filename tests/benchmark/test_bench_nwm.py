"""The NWM Midwest county cell: its labels, its bytes, its files, and the
``kernels.span_roofline`` reader on a planted trace."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import harness, program_trace as pt, trace, traffic  # noqa: E402
from benchmark import data  # noqa: E402

CELL = "nwm_midwest.county_nanmean"
MS = 1_000_000  # ns


@pytest.fixture(scope="module")
def cell():
    return harness.Cell.load(CELL)


def test_county_raster(cell):
    """asv's raster at its published size, as asv builds it: 842 expected
    groups, 297 of them present, group 0 the first row and column of
    counties."""
    by = data.labels(cell.config, "county")
    expected = data.expected_groups(cell.config, "county")
    x, y = np.repeat(np.arange(30), 150), np.repeat(np.arange(30), 60)
    np.testing.assert_array_equal(by, x[np.newaxis, :] * y[:, np.newaxis])
    np.testing.assert_array_equal(expected, np.arange(842))
    assert np.unique(by).size == 297
    assert np.count_nonzero(by == 0) == 531_000
    assert data.label_dims(cell.config, "county") == ["latitude", "longitude"]


def test_logical_bytes(cell):
    ngroups = traffic.ngroups(cell.config, cell.mix)
    assert ngroups == 842
    assert traffic.logical_bytes(cell.config, cell.mix, ngroups) == (3_888_000_000, 120 * 842 * 4)


def test_cell_resolves_every_file(cell):
    assert (cell.chips, data.shape(cell.config)) == (1, (120, 1800, 4500))
    assert traffic.funcs(cell.mix) == ["nanmean"] and cell.mix["labels"] == "county"
    assert [m["name"] for m in cell.metrics] == ["gbps_per_chip", "call_p95_ms", "setup_s"]
    traced = harness.Cell.load(CELL, traced=True)
    names = [m["name"] for m in traced.metrics]
    assert names == ["host.dispatch_ms", "kernels_roofline", "device.idle_share", "host.factorize_ms",
                     "host.blocking_ms", "kernels.glue_share", "kernels.span_roofline"]
    for name in names + [m["name"] for m in cell.metrics]:
        assert callable(harness.reader(name))


KERNEL = '%flox_radixbin.4 = f32[8]{0} custom-call(f32[8]{0} %p), custom_call_target="tpu_custom_call"'
COPY = "%copy.5 = f32[8]{0} copy(f32[8]{0} %p)"
DIVIDE = "%divide.2 = f32[8]{0} divide(f32[8]{0} %p, f32[8]{0} %q)"
STACKS = {KERNEL: "jit(flox_spanblock)/flox.spanblock/while/body/flox.kernel/pallas_call:",
          COPY: "jit(flox_spanblock)/flox.spanblock/while/body/reshape:",
          DIVIDE: "jit(flox_spanblock)/flox.finalize/div:"}


def _run(stacks=STACKS, counters=None):
    """Two calls of 10 ms on one chip. Under ``flox.spanblock``: a slab copy
    2-3 ms and the kernel 3-6 ms in the first call, the kernel 12-17 ms in
    the second; the finalize's divide 6-7 ms is outside it."""
    ops = {0: [(2 * MS, 3 * MS, COPY), (3 * MS, 6 * MS, KERNEL), (6 * MS, 7 * MS, DIVIDE),
               (12 * MS, 17 * MS, KERNEL)]}
    spans = []
    for s in (0, 10 * MS):
        spans += [(s, s + 10 * MS, trace.STEP), (s, s + 2 * MS, trace.CALL), (s + 2 * MS, s + 10 * MS, trace.WAIT)]
    tr = trace.Trace(ops, sorted(spans))
    tr.program = pt.Program(scopes={0: {(s, n): stacks[n] for s, _, n in ops[0]}})
    cell = harness.Cell(CELL, 1, {}, {}, [])
    return harness.Run(cell, 1.0, [(0.0, 0.01, True), (0.01, 0.02, True)], 8 * 10**6, 2 * 10**6,
                       {"hbm_gbps": 1000.0}, tr, {"reduce.span_blocks": [23.0, 23.0]} if counters is None else counters)


def test_span_roofline_reads_a_planted_trace():
    read = harness.reader("kernels.span_roofline")
    # least time a call: 10 MB at 1000 GB/s = 10 us; 9 ms under the scope in two calls
    assert read(_run()) == pytest.approx(100 * 2 * 1e-5 / 9e-3)


def test_span_roofline_silent_without_the_path():
    read = harness.reader("kernels.span_roofline")
    unscoped = {k: v.replace("flox.spanblock/", "") for k, v in STACKS.items()}
    assert read(_run(stacks=unscoped)) is None
    assert read(_run(counters={})) is None  # a program without the counter
    assert read(_run(counters={"reduce.span_blocks": [0.0, 0.0]})) is None
    run = _run()
    run.trace = None
    assert read(run) is None
