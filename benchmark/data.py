"""A configuration's data and labels, made from the seed.

The array is made on the device in one jitted call, block by block in place
(the peak is the array plus about two blocks), and with ``shard_dim`` each
chip makes its own shard. The values are ERA5-like 2 m temperature,
280 + 10 N(0, 1) K, with NaN in every 17th grid cell at every 997th step
from step 5 (as ``chip_smoke.make_data``), so NaN handling is exercised.

A traffic mix names its labels. A name in the configuration's ``labels``
table, ``{"<name>": {"source": "<module>", "dims": [...]}}``, is made by
``labels/<module>.py``: its ``labels(config, spec)`` returns the label array
over ``dims`` and the expected groups, or ``None`` for the labels' own
unique values. Any other name is a field of the configuration's calendar
over ``time``. The labels' dims are the array's trailing dims, the ones a
call reduces; a "cell" is one flat index over the dims it keeps.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

#: where a configuration's label sources are found by name
LABELS = Path(__file__).resolve().parent / "labels"

#: largest block the generator fills at once: one ``random.normal`` call
#: needs about twice its output
BLOCK_BYTES = 2**30
NAN_CELL_EVERY, NAN_STEP_EVERY, NAN_STEP_FROM = 17, 997, 5
MEAN, STD = 280.0, 10.0


def shape(config: dict) -> tuple[int, ...]:
    return tuple(int(config["n_" + d]) for d in config["dims"])


def seed_words(seed: int) -> tuple[int, int]:
    """Two 32-bit words from a seed of any size (the driver's exceed 2**31)."""
    lo, hi = np.random.SeedSequence(int(seed)).generate_state(2)
    return int(lo), int(hi)


def calendar(config: dict):
    import pandas as pd

    return pd.date_range(config["time_start"], periods=int(config["n_time"]), freq=config["time_freq"])


def label_dims(config: dict, name: str) -> list[str]:
    """The dims the labels ``name`` span: the table's, or ``time`` for a
    calendar field. They have to be the array's trailing dims, since those
    are what ``groupby_reduce``'s default ``axis`` reduces."""
    spec = config.get("labels", {}).get(name)
    dims = list(spec["dims"]) if spec else ["time"]
    if not dims or list(config["dims"][-len(dims):]) != dims:
        raise ValueError(f"labels {name!r} span {dims}, not trailing dims of the array's {config['dims']}")
    return dims


def _source(config: dict, name: str) -> tuple[np.ndarray, np.ndarray | None]:
    """``(labels, expected groups or None)`` from the table's source."""
    spec = config["labels"][name]
    path = LABELS / f"{spec['source']}.py"
    mod_spec = importlib.util.spec_from_file_location(f"benchmark.labels.{spec['source']}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    values, expected = mod.labels(config, spec)
    values = np.asarray(values)
    want = tuple(int(config["n_" + d]) for d in label_dims(config, name))
    if values.shape != want:
        raise ValueError(f"{path} made labels {name!r} of shape {values.shape}; their dims are {want}")
    return values, None if expected is None else np.asarray(expected)


def labels(config: dict, name: str) -> np.ndarray:
    """The labels a user groups by, over their dims: a calendar field
    (``time.month``, ``time.dayofyear``) as the integer array ``.values``
    gives, or what the configuration's source for ``name`` makes."""
    if name not in config.get("labels", {}):
        return np.asarray(getattr(calendar(config), name))
    return _source(config, name)[0]


def expected_groups(config: dict, name: str) -> np.ndarray | None:
    """The groups a call is told to expect, where the source gives them."""
    if name not in config.get("labels", {}):
        return None
    return _source(config, name)[1]


def _block_rows(local: tuple[int, ...], itemsize: int) -> int:
    """Rows of the leading dimension per block: the largest divisor of it
    whose block stays within BLOCK_BYTES."""
    row = int(np.prod(local[1:])) * itemsize
    n0 = local[0]
    return max(r for r in range(1, n0 + 1) if n0 % r == 0 and r * row <= BLOCK_BYTES)


def make_array(config: dict, seed: int, devices):
    """The configuration's array on its devices, from ``seed``."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P, SingleDeviceSharding

    full = shape(config)
    dtype = jnp.dtype(config["dtype"])
    dims = list(config["dims"])
    sdim = config.get("shard_dim")
    chips = int(config["chips"])
    axis = dims.index(sdim) if sdim else None
    local = list(full)
    if axis is not None:
        if full[axis] % chips:
            raise ValueError(f"{sdim}={full[axis]} does not split over {chips} chips")
        local[axis] //= chips
    local = tuple(local)
    rows = _block_rows(local, dtype.itemsize)

    def fill(words, shard):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.key(words[0]), words[1]), shard)
        blk_shape = (rows,) + local[1:]
        iota = [lax.broadcasted_iota(jnp.int32, blk_shape, d) for d in range(len(blk_shape))]
        offset = [0] * len(local)
        if axis is not None:
            offset[axis] = shard * local[axis]

        def body(i, buf):
            blk = (MEAN + STD * jax.random.normal(jax.random.fold_in(key, i), blk_shape, jnp.float32)).astype(dtype)
            idx = [iota[d] + offset[d] for d in range(len(local))]
            idx[0] = idx[0] + i * rows
            cell = idx[0]
            for d in range(1, len(local) - 1):
                cell = cell * full[d] + idx[d]
            step = idx[-1]
            nan = (cell % NAN_CELL_EVERY == 0) & (step % NAN_STEP_EVERY == NAN_STEP_FROM)
            blk = jnp.where(nan, jnp.asarray(jnp.nan, dtype), blk)
            return lax.dynamic_update_slice(buf, blk, (i * rows,) + (0,) * (len(local) - 1))

        return lax.fori_loop(0, local[0] // rows, body, jnp.zeros(local, dtype))

    if axis is None:
        gen = jax.jit(lambda w: fill(w, 0), out_shardings=SingleDeviceSharding(devices[0]))
    else:
        mesh = Mesh(np.asarray(devices[:chips]), ("data",))
        spec = P(*[("data" if d == axis else None) for d in range(len(full))])
        gen = jax.jit(
            jax.shard_map(
                lambda w: fill(w, lax.axis_index("data")), mesh=mesh, in_specs=P(), out_specs=spec,
                check_vma=False,
            )
        )
    # the seed is an argument, not a constant: one program serves every
    # seed, so the persistent cache holds it after a cell's first run
    return gen(np.asarray(seed_words(seed), dtype=np.uint32)).block_until_ready()


def sample_cells(config: dict, seed: int, budget_bytes: int = 2**30, reduced: int = 1) -> np.ndarray:
    """Cells (flat indices over the dims a call keeps, all but the last
    ``reduced``) whose answers are compared with the reference: drawn from
    the seed, as many as ``budget_bytes`` of their series hold, sorted."""
    full = shape(config)
    ncell = int(np.prod(full[:-reduced]))
    per_cell = int(np.prod(full[-reduced:])) * np.dtype(config["dtype"]).itemsize
    n = min(ncell, max(1, budget_bytes // per_cell))
    rng = np.random.default_rng([int(seed), 1])
    return np.sort(rng.choice(ncell, n, replace=False))


def gather(v, i, columns: bool = False, reduced: int = 1):
    """``v``'s series at the flat cells ``i`` (traced; see :func:`take_cells`)."""
    rows = v.reshape(-1, int(np.prod(v.shape[-reduced:])))[i]
    return rows.T if columns else rows


def take_cells(a, cells: np.ndarray, columns: bool = False, reduced: int = 1) -> np.ndarray:
    """``a``'s series at the flat ``cells``, on the host, each over its last
    ``reduced`` dims flattened: (len(cells), series), or with ``columns``
    (series, len(cells))."""
    import functools

    import jax
    import jax.numpy as jnp

    taken = jax.jit(functools.partial(gather, columns=columns, reduced=reduced))(a, jnp.asarray(cells, dtype=jnp.int32))
    return np.asarray(jax.device_get(taken))
