"""95th percentile, by nearest rank, of the calls completed in the window,
each timed from entering the entry point to its answers being ready."""

import math


def read(run):
    d = sorted(run.ok_durations())
    if not d:
        return None
    return d[math.ceil(0.95 * len(d)) - 1] * 1e3
