"""Share of device-busy time in collective ops (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all, their start/done pairs
included), mean over the chips. Nothing where no collective ran."""


def read(run):
    from benchmark import trace

    if run.trace is None:
        return None
    share = trace.collective_share(run.trace)
    return None if share is None else 100.0 * share
