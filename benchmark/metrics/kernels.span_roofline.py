"""Least time over the device-busy time of the span-blocked reduction,
summed over the traced calls, per chip and averaged over the chips. Least
time is the call's logical bytes (input read once, answers written once;
the chip's share of them) over the peak HBM bandwidth; the busy time is the
union of the ops under the program's ``flox.spanblock`` scope inside the
calls, each chip's clock aligned by its program runs. Nothing where no op
carries that scope, or the counted calls counted no ``reduce.span_blocks``
(the path did not run)."""

import statistics

SCOPE = "flox.spanblock"
COUNTER = "reduce.span_blocks"


def read(run):
    from benchmark import program_trace, trace

    if run.trace is None or run.trace.program is None or not run.peak or not any(run.counters.get(COUNTER, [])):
        return None
    tr, prog = program_trace.align(run.trace, run.trace.program)
    least_s = (run.input_bytes + run.output_bytes) / run.cell.chips / (run.peak["hbm_gbps"] * 1e9)
    steps = tr.steps()
    shares = []
    for d in trace.devices(tr):
        ops = tr.ops[d] + tr.outer.get(d, [])
        mine = trace.merge((s, e) for s, e, n in ops if program_trace.in_scope(prog.scope(d, (s, e, n)), SCOPE))
        busy_ns = sum(trace.covered(mine, lo, hi) for lo, hi in steps)
        if busy_ns:
            shares.append(len(steps) * least_s / (busy_ns / 1e9))
    return 100.0 * statistics.fmean(shares) if shares else None
