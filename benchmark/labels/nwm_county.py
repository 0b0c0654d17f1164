"""flox's asv ``NWMMidwest`` county raster (``asv_bench/benchmarks/cohorts.py``):
30 columns of counties along ``x`` and 30 rows along ``y``, each label the
product of the two, so ``by = x[None, :] * y[:, None]``. At the published
(1800, 4500) raster that is 842 expected groups, 297 of them present, and
group 0 holds the first row and column of counties (6.6 % of the cells)."""

import numpy as np

#: counties along each axis of the raster, as asv builds it
COUNTIES = 30


def _county(n):
    """Each of ``n`` cells' county: asv's ``repeat(arange(30), n // 30)``
    where 30 divides ``n``, the same even split where it does not."""
    return np.arange(n) * COUNTIES // n


def labels(config, spec):
    """The raster over the widths of the spec's two dims (y, then x), and
    ``arange(by.max() + 1)`` as the expected groups."""
    ny, nx = (int(config["n_" + d]) for d in spec["dims"])
    by = _county(nx)[np.newaxis, :] * _county(ny)[:, np.newaxis]
    return by, np.arange(by.max() + 1)
