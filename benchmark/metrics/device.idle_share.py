"""1 - busy / window over the traced stretch, mean over the chips."""


def read(run):
    from benchmark import trace

    if run.trace is None:
        return None
    busy = trace.busy_share(run.trace)
    return None if busy is None else 100.0 * (1.0 - busy)
