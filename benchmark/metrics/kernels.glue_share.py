"""Device-busy time in ops under no ``flox.kernel`` scope (copies, slices,
selects, row-block moves), in % of the busy time inside the traced calls,
mean over the chips. Nothing where no op carries a ``flox.*`` scope."""


def read(run):
    from benchmark import program_trace

    if run.trace is None or run.trace.program is None:
        return None
    return program_trace.glue_share(*program_trace.align(run.trace, run.trace.program))
