"""The general generator: a traffic mix's file turned into the call a closed
loop makes, and the logical bytes that call moves.

A mix names a public entry point of flox_tpu (``entry``), the keyword
arguments a user passes (``kwargs``), the labels the array is grouped by
(``labels``: a calendar field, or a name the configuration's ``labels``
table gives a source; see ``data``) and whether the call runs over the
cell's chips as a mesh (``mesh``). ``limits`` holds the comparison's
limits.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from . import data


def load(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("loop") != "closed" or int(mix.get("clients", 1)) != 1:
        raise ValueError(f"{path}: only a closed loop with one client is generated")
    return mix


def funcs(mix: dict) -> list[str]:
    """The statistics one call answers."""
    kw = mix["kwargs"]
    return list(kw["funcs"]) if "funcs" in kw else [kw["func"]]


def make_call(mix: dict, config: dict, array, devices):
    """``call(block=True) -> (groups, {func: answer})``: the answers stay on
    the device as the entry point returns them, every one ready unless
    ``block`` is false."""
    import jax

    import flox_tpu

    entry = getattr(flox_tpu, mix["entry"])
    by = data.labels(config, mix["labels"])
    kwargs = dict(mix["kwargs"])
    expected = data.expected_groups(config, mix["labels"])
    if expected is not None:
        kwargs["expected_groups"] = expected
    if mix.get("mesh"):
        from flox_tpu.parallel.mesh import make_mesh

        kwargs["mesh"] = make_mesh(int(config["chips"]))
    names = funcs(mix)

    def call(block: bool = True):
        result, *groups = entry(array, by, **kwargs)
        answers = result if isinstance(result, dict) else {names[0]: result}
        if block:
            jax.block_until_ready(answers)
        return groups[0], answers

    return call


def logical_bytes(config: dict, mix: dict, ngroups: int) -> tuple[int, int]:
    """``(input, output)`` bytes one call needs at the least: the input read
    once and each statistic's answers written once (one per kept cell and
    group), whatever it computes."""
    shape = data.shape(config)
    itemsize = np.dtype(config["dtype"]).itemsize
    cells = int(np.prod(shape[: -len(data.label_dims(config, mix["labels"]))]))
    return int(np.prod(shape)) * itemsize, cells * ngroups * itemsize * len(funcs(mix))


def ngroups(config: dict, mix: dict) -> int:
    """The groups a call answers: the expected ones where the configuration
    gives them, else the labels' unique values."""
    expected = data.expected_groups(config, mix["labels"])
    if expected is not None:
        return int(expected.size)
    return int(np.unique(data.labels(config, mix["labels"])).size)
