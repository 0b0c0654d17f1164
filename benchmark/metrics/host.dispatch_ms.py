"""Median over the traced calls of the time from the call's start to its
first device op: the host's entry point, factorize and dispatch."""

import statistics


def read(run):
    from benchmark import trace

    if run.trace is None:
        return None
    gaps = trace.dispatch_gaps_ns(run.trace)
    return statistics.median(gaps) / 1e6 if gaps else None
