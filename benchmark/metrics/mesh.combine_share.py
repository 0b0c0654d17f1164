"""Device-busy time in ops under the ``flox.combine`` scope (the mesh
combine's collectives and merges), in % of the busy time inside the traced
calls, mean over the chips. Nothing where no op carries a ``flox.*`` scope."""


def read(run):
    from benchmark import program_trace

    if run.trace is None or run.trace.program is None:
        return None
    return program_trace.combine_share(*program_trace.align(run.trace, run.trace.program))
