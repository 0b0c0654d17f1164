"""Median over the traced calls of the time the program spends in its
``flox.factorize`` span: the labels turned into integer codes on the host.
Nothing where the program opened no such span."""


def read(run):
    from benchmark import program_trace

    if run.trace is None or run.trace.program is None:
        return None
    return program_trace.factorize_ms(*program_trace.align(run.trace, run.trace.program))
