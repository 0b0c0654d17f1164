"""Least time over device-busy time, summed over the traced calls, per chip
and averaged over the chips. Least time is the call's logical bytes (input
read once, answers written once; the chip's share of them) over the peak
HBM bandwidth; busy time is the union of every device op inside the calls,
whichever ops implement them, so the count does not go stale when a change
swaps a kernel or removes a copy."""

import statistics


def read(run):
    from benchmark import trace

    tr = run.trace
    if tr is None or not run.peak or not any(tr.ops.values()):
        return None
    least_s = (run.input_bytes + run.output_bytes) / run.cell.chips / (run.peak["hbm_gbps"] * 1e9)
    calls = len(tr.steps())
    shares = [calls * least_s / (trace.step_busy_ns(tr, d) / 1e9) for d in trace.devices(tr) if trace.step_busy_ns(tr, d)]
    return 100.0 * statistics.fmean(shares) if shares else None
