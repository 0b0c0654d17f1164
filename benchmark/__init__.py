"""The on-chip benchmark of flox_tpu, driven from data.

``BENCHMARK.json`` at the repository root names the cells; each cell pairs
a configuration (``configs/<name>.json``: the array's shape, calendar and
dtype, and any labels it names with their source in ``labels/<module>.py``)
with a traffic mix (``traffic/<name>.json``: the public entry point and
arguments a closed loop calls). Every metric is a reader of its own in
``metrics/<name>.py``. ``run.py`` runs one cell once and names none of them.
"""
