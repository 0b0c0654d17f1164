"""The program's spans and scopes in the trace: read from an ``.xplane.pb``
(a CPU capture, and a hand-made TPU plane), and reduced on a hand-built
trace."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import harness, program_trace as pt, trace  # noqa: E402

MS = 1_000_000  # ns
FUSION = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"
KERNEL = '%flox_segment_sum.2 = f32[8]{0} custom-call(f32[8]{0} %p), custom_call_target="tpu_custom_call"'
SLICE = "%slice.3 = f32[8]{0} slice(f32[16]{0} %p), slice={[0:8]}"
REDUCE = "%all-reduce.4 = f32[8]{0} all-reduce(f32[8]{0} %x)"


def _trace():
    """One chip, two calls of 10 ms. The host is in the entry point for the
    first 2 ms of each call (the call's span 0.1-1.9 ms around factorize
    0.2-0.6 ms and dispatch 1-1.8 ms, with a 0.5 ms staging of 64 bytes) and
    waits for the rest. The chip runs a row-block slice 2-4 ms, the kernel
    4-7 ms, a fusion under no scope 7-8 ms and a combine 8-9 ms in the first
    call, and the kernel 13-18 ms in the second."""
    ops = {0: [(2 * MS, 4 * MS, SLICE), (4 * MS, 7 * MS, KERNEL), (7 * MS, 8 * MS, FUSION),
               (8 * MS, 9 * MS, REDUCE), (13 * MS, 18 * MS, KERNEL)]}
    spans = []
    prog = pt.Program()
    for s in (0, 10 * MS):
        spans += [(s, s + 10 * MS, trace.STEP), (s, s + 2 * MS, trace.CALL), (s + 2 * MS, s + 10 * MS, trace.WAIT)]
        prog.spans += [
            (s + MS // 10, s + 19 * MS // 10, "flox.groupby_reduce", {"func": "nanmean"}),
            (s + MS // 5, s + 3 * MS // 5, "flox.factorize", {}),
            (s + MS, s + 9 * MS // 5, "flox.dispatch", {}),
            (s + MS, s + 3 * MS // 2, "flox.h2d", {"bytes": 64}),
        ]
    prog.spans.sort(key=lambda sp: sp[:3])
    stacks = {SLICE: "jit(flox_bundle)/flox.rowblock/slice:", KERNEL: "jit(flox_bundle)/flox.rowblock/flox.kernel/pallas_call:",
              FUSION: "", REDUCE: "jit(flox_mesh_cohorts)/flox.combine/psum:"}
    prog.scopes = {0: {(s, n): stacks[n] for s, _, n in ops[0]}}
    return trace.Trace(ops, sorted(spans)), prog


def test_host_numbers():
    tr, prog = _trace()
    assert pt.factorize_ms(tr, prog) == pytest.approx(0.4)
    assert pt.h2d_bytes(tr, prog) == 64
    # the program's spans cover 0.1-1.9 ms of each call, and the chip is
    # busy from 2 ms (call 1) and 3 ms (call 2): idle under them 1.8 ms
    assert pt.blocking_ms(tr, prog) == pytest.approx(1.8)


def test_device_shares():
    tr, prog = _trace()
    # busy inside the calls: 7 ms (call 1) + 5 ms (call 2); kernel 3 + 5 ms
    assert pt.glue_share(tr, prog) == pytest.approx(100 * (1 - 8 / 12))
    assert pt.combine_share(tr, prog) == pytest.approx(100 * 1 / 12)
    # every op but the unscoped fusion (1 ms) is under some flox scope
    assert pt.scoped_share(tr, prog) == pytest.approx(100 * 11 / 12)


def test_nothing_to_read_gives_none():
    tr, _ = _trace()
    empty = pt.Program()
    for fn in (pt.factorize_ms, pt.blocking_ms, pt.glue_share, pt.combine_share, pt.scoped_share,
               pt.h2d_bytes, pt.bare_call_idle_share):
        assert fn(tr, empty) is None, fn.__name__
    # spans but no name stacks (a CPU capture), or name stacks with no
    # flox scope (a program without them): the host numbers only
    _, prog = _trace()
    for stack in ("", "jit(run)/pallas_call:"):
        prog.scopes = {0: {k: stack for k in prog.scopes[0]}}
        assert pt.glue_share(tr, prog) is None
        assert pt.combine_share(tr, prog) is None
    assert pt.factorize_ms(tr, prog) == pytest.approx(0.4)


def test_clock_alignment():
    """A chip whose clock reads late is moved onto the host's: the fastest
    of its runs starts as the host enqueues it."""
    tr, prog = _trace()
    # runs enqueued at 1.5 and 11.5 ms (host clock), started at 2.5 and 13.5 ms (chip clock)
    prog.enqueued = {0: {1: 3 * MS // 2, 2: 23 * MS // 2}}
    prog.started = {0: {1: 5 * MS // 2, 2: 27 * MS // 2}}
    assert pt.clock_offsets(prog) == {0: -MS}
    tr2, prog2 = pt.align(tr, prog)
    assert tr2.ops[0][0][:2] == (MS, 3 * MS)
    assert prog2.scope(0, tr2.ops[0][0]) == prog.scope(0, tr.ops[0][0]) != ""
    assert tr.ops[0][0][:2] == (2 * MS, 4 * MS)  # the input is left as it was
    assert pt.numbers(tr, prog)["device.clock_offset_us"] == -1000.0
    # with no run matched the clocks are taken as they are
    assert pt.clock_offsets(pt.Program(started={0: {1: 5}})) == {0: 0}


def test_idle_named_by_program_spans():
    tr, prog = _trace()
    by = pt.idle_by_span(tr, prog, 0)
    # call 1 idles 0-2 ms and 9-10 ms; call 2 idles 10-13 ms and 18-20 ms
    assert sum(by.values()) == 2 * MS + MS + 3 * MS + 2 * MS
    assert by[trace.WAIT] == MS + MS + 2 * MS  # 9-10, 12-13 and 18-20 ms
    assert by[trace.CALL] == 4 * MS // 10  # 0-0.1 and 1.9-2 ms, twice
    assert by["flox.groupby_reduce"] == 12 * MS // 10  # 0.1-0.2, 0.6-1 and 1.8-1.9 ms, twice
    assert by["flox.factorize"] == 8 * MS // 10
    assert by["flox.h2d"] == MS
    assert by["flox.dispatch"] == 6 * MS // 10
    assert pt.bare_call_idle_share(tr, prog) == pytest.approx(100 * by[trace.CALL] / sum(by.values()))
    # a gap is named by the innermost span open at its middle: 0-2 ms at 1 ms
    # and 9-13 ms at 11 ms fall in a staging, 18-20 ms in a wait
    assert pt.idle_gaps(tr, prog, 0) == [("flox.h2d", 2 * MS), ("flox.h2d", 4 * MS), (trace.WAIT, 2 * MS)]
    line = pt.timeline(tr, prog)
    assert line["flox.factorize"] == [pytest.approx(200), pytest.approx(600)]
    assert line["device.first_op"][0] == pytest.approx((2000 + 3000) / 2)


def test_breakdown_labels_ops_by_scope():
    tr, prog = _trace()
    bd = pt.breakdown(tr, prog)
    assert bd["device_ops"][0] == ["flox_segment_sum.2 custom-call:tpu_custom_call [flox.kernel]",
                                   pytest.approx(8e-3)]
    assert ["fusion.1 fusion", pytest.approx(1e-3)] in bd["device_ops"]
    assert bd["busy_by_scope"] == {"flox.kernel": pytest.approx(8e-3), "flox.rowblock": pytest.approx(2e-3),
                                   "(none)": pytest.approx(1e-3), "flox.combine": pytest.approx(1e-3)}
    assert pt.innermost("jit(x)/flox.rowblock/while/body/flox.kernel/add:") == "flox.kernel"
    assert pt.innermost("jit(x)/add:") == ""


def test_existing_readers_untouched():
    """The program's spans and scopes sit beside the trace: the accepted
    readers and the breakdown read the same numbers with them computed."""
    tr, prog = _trace()
    cell = harness.Cell("c", 1, {}, {}, [])
    run = harness.Run(cell, 1.0, [(0.0, 0.01, True), (0.01, 0.02, True)], 8 * 10**6, 2 * 10**6,
                      {"hbm_gbps": 1000.0}, tr)
    names = ("device.idle_share", "host.dispatch_ms", "mesh.collective_share", "kernels_roofline")
    before = [harness.reader(n)(run) for n in names] + [trace.breakdown(tr)]
    pt.numbers(tr, prog)
    pt.breakdown(tr, prog)
    assert [harness.reader(n)(run) for n in names] + [trace.breakdown(tr)] == before


def _xspace(stack_by_ref: bool) -> bytes:
    """A serialized XSpace with one TPU plane (two ops, one name stack given
    as a string, the other by reference or as a string) and one host plane."""
    space = pt._schema()()
    dev = space.planes.add(name=b"/device:TPU:0")
    dev.stat_metadata.add(key=1, value={"name": b"tf_op"})
    dev.stat_metadata.add(key=2, value={"name": b"jit(f)/flox.combine/psum:"})
    dev.event_metadata.add(key=10, value={"name": REDUCE.encode()})
    dev.event_metadata.add(key=11, value={"name": KERNEL.encode()})
    stat = {"metadata_id": 1, "ref_value": 2} if stack_by_ref else {"metadata_id": 1, "str_value": b"jit(f)/flox.combine/psum:"}
    dev.event_metadata[0].value.stats.add(**stat)
    dev.event_metadata[1].value.stats.add(metadata_id=1, str_value=b"jit(f)/flox.kernel/pallas_call:")
    dev.stat_metadata.add(key=4, value={"name": b"run_id"})
    dev.event_metadata.add(key=12, value={"name": b"jit_flox_bundle(1)"})
    runs = dev.lines.add(name=b"XLA Modules", timestamp_ns=1000)
    runs.events.add(metadata_id=12, offset_ps=4_000_000, duration_ps=6_000_000, stats=[{"metadata_id": 4, "int64_value": 7}])
    ops = dev.lines.add(name=b"XLA Ops", timestamp_ns=1000)
    ops.events.add(metadata_id=10, offset_ps=5_000_000, duration_ps=2_000_000)
    ops.events.add(metadata_id=11, offset_ps=8_000_000, duration_ps=1_000_000)
    host = space.planes.add(name=b"/host:CPU")
    host.stat_metadata.add(key=3, value={"name": b"bytes"})
    host.event_metadata.add(key=20, value={"name": b"flox.h2d"})
    host.event_metadata.add(key=21, value={"name": b"bench.call"})
    host.event_metadata.add(key=22, value={"name": b"DoEnqueueProgram"})
    host.stat_metadata.add(key=5, value={"name": b"run_id"})
    host.stat_metadata.add(key=6, value={"name": b"device_ordinal"})
    runtime = host.lines.add(name=b"pjrt-tpu-tasks", timestamp_ns=500)
    runtime.events.add(metadata_id=22, offset_ps=4_000_000, duration_ps=10_000,
                       stats=[{"metadata_id": 5, "int64_value": 7}, {"metadata_id": 6, "int64_value": 0}])
    line = host.lines.add(name=b"python", timestamp_ns=500)
    line.events.add(metadata_id=20, offset_ps=1_000_000, duration_ps=3_000_000, stats=[{"metadata_id": 3, "int64_value": 96}])
    line.events.add(metadata_id=21, offset_ps=0, duration_ps=9_000_000)
    return space.SerializeToString()


@pytest.mark.parametrize("by_ref", [False, True])
def test_load_reads_name_stacks_and_program_spans(tmp_path, by_ref):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(by_ref))
    prog = pt.load(path, chips=1)
    assert prog.spans == [(1500, 4500, "flox.h2d", {"bytes": 96})]
    assert prog.scopes[0] == {(6000, REDUCE): "jit(f)/flox.combine/psum:",
                              (9000, KERNEL): "jit(f)/flox.kernel/pallas_call:"}
    assert prog.started == {0: {7: 5000}} and prog.enqueued == {0: {7: 4500}}
    assert pt.clock_offsets(prog) == {0: -500}
    assert pt.load(path, chips=0).scopes == {}


def test_load_of_a_cpu_capture(tmp_path):
    """A CPU capture of the program: its phase spans reach the Program, not
    the Trace, and a CPU trace carries no name stacks."""
    import jax
    import numpy as np

    import flox_tpu

    vals = jax.numpy.asarray(np.random.default_rng(0).normal(size=(4, 64)))
    labels = np.arange(64) % 6
    flox_tpu.groupby_reduce(vals, labels, func="nanmean")
    trace.capture_start(tmp_path)
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation(trace.STEP):
                with jax.profiler.TraceAnnotation(trace.CALL):
                    out = flox_tpu.groupby_reduce(vals, labels, func="nanmean")[0]
                with jax.profiler.TraceAnnotation(trace.WAIT):
                    jax.block_until_ready(out)
    finally:
        xplane = trace.capture_stop(tmp_path)
    prog = pt.load(xplane, chips=1)
    names = {n for _, _, n, _ in prog.spans}
    assert {"flox.groupby_reduce", "flox.factorize", "flox.dispatch", "flox.h2d", "flox.finalize"} <= names
    codes_bytes = jax.numpy.asarray(labels).nbytes  # the codes, as staged
    assert all(int(a["bytes"]) == codes_bytes for _, _, n, a in prog.spans if n == "flox.h2d")
    tr = trace.load(xplane, chips=1)
    assert {n for _, _, n in tr.spans} == set(trace.SPANS)
    assert len(tr.steps()) == 2
    assert pt.factorize_ms(tr, prog) > 0
    assert pt.h2d_bytes(tr, prog) == codes_bytes
    assert pt.glue_share(tr, prog) is None
