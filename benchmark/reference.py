"""The plain reference: a float64 numpy groupby.

It imports nothing of flox_tpu. Series are columns: for each group, its
steps (rows) are gathered and reduced along axis 0, sums in float64, over
blocks of columns in a few threads (numpy releases the interpreter lock in
its loops). The ``nan``-prefixed statistics skip NaN; the others propagate
it, as numpy does. ``var``/``std`` are two-pass with ddof 0 (flox's
default); ``min``/``max`` are exact in the input's dtype.
"""

from __future__ import annotations

import warnings
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: float32 bytes of one block of columns
BLOCK_BYTES = 2**26
WORKERS = 4


def _one_block(x: np.ndarray, order: np.ndarray, bounds, funcs) -> dict:
    out = {f: np.empty((len(bounds), x.shape[1])) for f in funcs}
    with np.errstate(all="ignore"), warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        for k, (a, b) in enumerate(bounds):
            seg = x[order[a:b]]
            nan = np.isnan(seg)
            size = b - a
            count = size - np.count_nonzero(nan, axis=0)
            nansum = np.where(nan, 0, seg).sum(axis=0, dtype=np.float64)
            has_nan = count < size
            for f in funcs:
                skip = f.startswith("nan")
                base = f.removeprefix("nan")
                n = count if skip else size
                if base == "count":
                    r = count
                elif base == "sum":
                    r = nansum
                elif base == "mean":
                    r = nansum / n
                elif base in ("var", "std"):
                    dev = np.where(nan, 0.0, seg - nansum / n)
                    r = np.einsum("ij,ij->j", dev, dev) / n
                    r = np.sqrt(r) if base == "std" else r
                elif base in ("min", "max"):
                    reduce = {("min", True): np.fmin.reduce, ("min", False): np.min,
                              ("max", True): np.fmax.reduce, ("max", False): np.max}[base, skip]
                    r = reduce(seg, axis=0)
                else:
                    raise ValueError(f"the reference has no statistic {f!r}")
                out[f][k] = r if skip or base == "count" else np.where(has_nan, np.nan, r)
    return out


def groupby(series: np.ndarray, labels: np.ndarray, funcs) -> tuple[np.ndarray, dict]:
    """``(groups, {func: (n_series, n_groups) float64})`` for ``series``, a
    (steps, n_series) array whose columns are grouped by ``labels``."""
    labels = np.asarray(labels)
    order = np.argsort(labels, kind="stable")
    groups, starts = np.unique(labels[order], return_index=True)
    bounds = list(zip(starts, np.append(starts[1:], labels.size)))
    cols = max(1, BLOCK_BYTES // (series.itemsize * series.shape[0]))
    blocks = [series[:, j : j + cols] for j in range(0, series.shape[1], cols)]
    with ThreadPoolExecutor(WORKERS) as pool:
        parts = list(pool.map(lambda blk: _one_block(blk, order, bounds, funcs), blocks))
    return groups, {f: np.concatenate([p[f] for p in parts], axis=1).T for f in funcs}


def groupby_bf16(series: np.ndarray, labels: np.ndarray, funcs) -> tuple[np.ndarray, dict]:
    """The control: the same reference over the inputs rounded to bfloat16,
    the precision below float32. Accumulation stays exact, so this is the
    mildest bfloat16 path a change could take (bfloat16 storage with wide
    sums); any coarser one reads worse."""
    import ml_dtypes

    return groupby(series.astype(ml_dtypes.bfloat16).astype(np.float32), labels, funcs)


def fill(func: str) -> float:
    """A statistic's answer for a group with no member: 0 for ``count`` and
    ``sum``, NaN for the others."""
    return 0.0 if func.removeprefix("nan") in ("count", "sum") else np.nan


def over(expected: np.ndarray, groups: np.ndarray, answers: dict) -> tuple[np.ndarray, dict]:
    """``groupby``'s answers laid out over the ``expected`` groups, each one
    that no label names given its statistic's :func:`fill`."""
    expected = np.asarray(expected)
    order = np.argsort(expected, kind="stable")
    pos = order[np.minimum(np.searchsorted(expected, groups, sorter=order), expected.size - 1)]
    if not np.array_equal(expected[pos], groups):
        raise ValueError("the labels name groups that are not among the expected ones")
    out = {}
    for f, a in answers.items():
        out[f] = np.full((a.shape[0], expected.size), fill(f))
        out[f][:, pos] = a
    return expected, out
