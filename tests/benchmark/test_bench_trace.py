"""The benchmark's trace reduction on a small hand-built trace."""

import math
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import trace  # noqa: E402

MS = 1_000_000  # ns


def _trace():
    """Two chips, two steps of 10 ms. Chip 0 runs ops 1-4 ms and 5-8 ms in
    step 1 (one collective, 6-7 ms, overlapping the second op) and 12-18 ms
    in step 2; chip 1 runs 2-9 ms and 13-17 ms. The host is in the entry
    point for the first 1 ms of each step and waits for the rest."""
    ops = {
        0: [(1 * MS, 4 * MS, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"),
            (5 * MS, 8 * MS, '%run.2 = f32[8]{0} custom-call(f32[8]{0} %p), custom_call_target="tpu_custom_call"'),
            (6 * MS, 7 * MS, "%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %x)"),
            (12 * MS, 18 * MS, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)")],
        1: [(2 * MS, 9 * MS, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"),
            (13 * MS, 17 * MS, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)")],
    }
    spans = []
    for s in (0, 10 * MS):
        spans += [(s, s + 10 * MS, trace.STEP), (s, s + 1 * MS, trace.CALL), (s + 1 * MS, s + 10 * MS, trace.WAIT)]
    return trace.Trace(ops, sorted(spans))


def test_merge_and_cover():
    assert trace.merge([(5, 8), (1, 4), (3, 6), (10, 11)]) == [(1, 8), (10, 11)]
    assert trace.covered([(1, 8), (10, 11)], 2, 10) == 6


def test_busy_and_idle_share():
    tr = _trace()
    assert tr.window() == (0, 20 * MS)
    # chip 0 busy 3 + 3 + 6 = 12 ms, chip 1 busy 7 + 4 = 11 ms, of 20 ms
    assert trace.busy_ns(tr, 0, *tr.window()) == 12 * MS
    assert trace.busy_share(tr) == pytest.approx((12 / 20 + 11 / 20) / 2)
    assert trace.step_busy_ns(tr, 1) == 11 * MS


def test_collective_share():
    tr = _trace()
    # chip 0: 1 ms of collective over 12 ms busy; chip 1 has none
    assert trace.collective_share(tr) == pytest.approx(1 / 12)
    no_coll = trace.Trace({0: [(0, MS, "%fusion.1 = f32[8]{0} fusion()")]}, [(0, 2 * MS, trace.STEP)])
    assert trace.collective_share(no_coll) is None


def test_dispatch_gap():
    tr = _trace()
    # step 1: first op at 1 ms (chip 0); step 2: at 12 ms (chip 0) -> 2 ms
    assert trace.dispatch_gaps_ns(tr) == [1 * MS, 2 * MS]


def test_idle_gaps_named_by_host_span():
    gaps = trace.idle_gaps(_trace(), 0)
    # 0-1 ms in the entry point; 4-5 waiting; 8-12 spans the step boundary,
    # its middle (10 ms) in step 2's entry point; 18-20 waiting
    assert gaps == [(trace.CALL, 1 * MS), (trace.WAIT, 1 * MS), (trace.CALL, 4 * MS), (trace.WAIT, 2 * MS)]
    bd = trace.breakdown(_trace(), top=2)
    assert bd["device_ops"][0] == ["fusion.1 fusion", pytest.approx((3 + 6 + 7 + 4) / 2 / 1e3)]
    assert bd["device_ops"][1][0] == "run.2 custom-call:tpu_custom_call"
    assert bd["idle_gaps"] == [[trace.CALL, 4e-3], [trace.WAIT, 2e-3]]


def test_readers_on_the_trace():
    """The per-layer readers, on the hand-built trace of a 2-chip cell."""
    from benchmark import harness

    cell = harness.Cell("c", 2, {}, {}, [])
    run = harness.Run(cell, 1.0, [(0.0, 0.01, True), (0.01, 0.02, True)], 8 * 10**6, 2 * 10**6,
                      {"hbm_gbps": 1000.0}, _trace())
    idle = harness.reader("device.idle_share")(run)
    assert idle == pytest.approx(100 * (1 - (12 / 20 + 11 / 20) / 2))
    assert harness.reader("host.dispatch_ms")(run) == pytest.approx(1.5)
    assert harness.reader("mesh.collective_share")(run) == pytest.approx(100 / 12)
    # least time per chip per call: 5 MB at 1000 GB/s = 5 us; two calls
    roof = harness.reader("kernels_roofline")(run)
    assert roof == pytest.approx(100 * ((2 * 5e-6) / 12e-3 + (2 * 5e-6) / 11e-3) / 2)
    untraced = harness.Run(cell, 1.0, run.steps, 8 * 10**6, 2 * 10**6, {"hbm_gbps": 1000.0})
    for name in ("device.idle_share", "host.dispatch_ms", "mesh.collective_share", "kernels_roofline"):
        assert harness.reader(name)(untraced) is None
    assert harness.reader("gbps_per_chip")(run) == pytest.approx(2 * 8e6 / 0.02 / 2 / 1e9)
    assert harness.reader("setup_s")(run) == 1.0
    assert math.isclose(harness.reader("call_p95_ms")(run), 10.0)


def test_ops_that_hold_others_count_as_busy_but_not_as_work():
    events = [(0, 10, "%while.1 = f32[8]{0} while(f32[8]{0} %p)"), (1, 4, "%a.1 = f32[8]{0} fusion()"),
              (5, 9, "%b.1 = f32[8]{0} fusion()"), (12, 13, "%c.1 = f32[8]{0} fusion()")]
    leaves, outer = trace.split_outer(events)
    assert [e[2][:7] for e in leaves] == ["%a.1 = ", "%b.1 = ", "%c.1 = "]
    assert [e[0] for e in outer] == [0]
    tr = trace.Trace({0: leaves}, [(0, 20, trace.STEP)], {0: outer})
    assert trace.busy_ns(tr, 0, 0, 20) == 11
    assert [op for op, _ in trace.breakdown(tr)["device_ops"]] == ["b.1 fusion", "a.1 fusion", "c.1 fusion"]
