"""Labels and reduced dims from the configuration.

The existing cells read what they read before (calendar labels over the
last dim: the pre-change formulas, restated here, pin the sample and the
gather), and a configuration whose labels are a 2-D raster over trailing
dims, with expected groups, runs from new files alone.
"""

import json
import sys
import warnings
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import compare, data, harness, reference, traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
EXISTING = ["era5_hourly_025", "perfect_monthly", "era5_hourly_025_x4"]
SEEDS = [0, 7, 2**31 + 5, 4_000_000_007]


def _config(name):
    c = next(c for c in BENCH["configs"] if c["name"] == name)
    return json.loads((ROOT / c["file"]).read_text())


def _old_sample_cells(config, seed, budget_bytes=2**30):
    full = data.shape(config)
    ncell = int(np.prod(full[:-1]))
    n = min(ncell, max(1, budget_bytes // (full[-1] * 4)))
    rng = np.random.default_rng([int(seed), 1])
    return np.sort(rng.choice(ncell, n, replace=False))


def _old_gather(columns):
    def gather(v, i):
        rows = v.reshape(-1, v.shape[-1])[i]
        return rows.T if columns else rows
    return gather


@pytest.mark.parametrize("name", EXISTING)
def test_existing_configs_sample_and_gather_as_before(name):
    """At the real shapes: the same cells for every seed, and the same
    program (its jaxpr) gathering their series and their answers."""
    import functools

    import jax
    import jax.numpy as jnp

    config = _config(name)
    assert data.label_dims(config, "month") == ["time"]
    for seed in SEEDS:
        np.testing.assert_array_equal(data.sample_cells(config, seed), _old_sample_cells(config, seed))
    cells = data.sample_cells(config, SEEDS[0])
    idx = jax.ShapeDtypeStruct(cells.shape, jnp.int32)
    for shape in (data.shape(config), data.shape(config)[:-1] + (366,)):
        arr = jax.ShapeDtypeStruct(shape, jnp.float32)
        for columns in (False, True):
            new = jax.make_jaxpr(functools.partial(data.gather, columns=columns, reduced=1))(arr, idx)
            assert str(new) == str(jax.make_jaxpr(_old_gather(columns))(arr, idx))


def test_take_cells_values():
    a = np.arange(2 * 3 * 5, dtype=np.float32).reshape(2, 3, 5)
    cells = np.array([1, 4])
    np.testing.assert_array_equal(data.take_cells(a, cells), a.reshape(6, 5)[cells])
    np.testing.assert_array_equal(data.take_cells(a, cells, columns=True), a.reshape(6, 5)[cells].T)
    np.testing.assert_array_equal(data.take_cells(a, np.array([1]), columns=True, reduced=2), a.reshape(2, 15)[[1]].T)


RASTER = '''
import numpy as np


def labels(config, spec):
    """flox's asv NWMMidwest raster at a small size: x[None, :] * y[:, None]."""
    x = np.repeat(np.arange(spec["nx"]), config["n_x"] // spec["nx"])
    y = np.repeat(np.arange(spec["ny"]), config["n_y"] // spec["ny"])
    by = x[None, :] * y[:, None]
    return by, np.arange(by.max() + 1)
'''


def _raster_cell(tmp_path, monkeypatch, funcs):
    """A cell made from files in a temporary directory only: a configuration
    whose labels come from a 2-D raster source over (y, x), with expected
    groups of which several are absent, and a traffic mix."""
    (tmp_path / "labels").mkdir()
    (tmp_path / "labels" / "raster.py").write_text(RASTER)
    monkeypatch.setattr(data, "LABELS", tmp_path / "labels")
    config = {"dims": ["time", "y", "x"], "n_time": 6, "n_y": 12, "n_x": 10, "dtype": "float32",
              "chips": 1, "shard_dim": None,
              "labels": {"county": {"source": "raster", "dims": ["y", "x"], "nx": 5, "ny": 4}}}
    mix = {"loop": "closed", "clients": 1, "entry": "groupby_aggregate_many", "kwargs": {"funcs": funcs},
           "labels": "county", "mesh": False,
           "limits": {"groups": 0, "nan_mismatch": 0, **{f"rel_err.{f}": 1e-6 for f in funcs}}}
    (tmp_path / "zonal.json").write_text(json.dumps(config))
    (tmp_path / "mix.json").write_text(json.dumps(mix))
    return harness.Cell("raster.zonal", 1, json.loads((tmp_path / "zonal.json").read_text()),
                        traffic.load(tmp_path / "mix.json"), [])


def _brute(x, by, expected, func):
    """One time step and one group at a time, with numpy's own reductions."""
    out = np.full((x.shape[0], expected.size), np.nan)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        for t in range(x.shape[0]):
            for k, g in enumerate(expected):
                v = x[t][by == g].astype(np.float64)
                if v.size:
                    out[t, k] = getattr(np, func)(v)
    return out


def test_raster_labels_from_new_files(tmp_path, monkeypatch):
    import jax

    funcs = ["nanmean", "nanmax"]
    cell = _raster_cell(tmp_path, monkeypatch, funcs)
    config, mix = cell.config, cell.mix
    by, expected = data.labels(config, "county"), data.expected_groups(config, "county")
    assert by.shape == (12, 10) and expected.size == 13
    absent = np.setdiff1d(expected, by)
    assert absent.size == 4  # 5, 7, 10 and 11 are no product of 0..4 and 0..3
    assert data.label_dims(config, "county") == ["y", "x"]
    assert traffic.ngroups(config, mix) == 13
    assert traffic.logical_bytes(config, mix, 13) == (6 * 12 * 10 * 4, 6 * 13 * 4 * 2)

    devices = jax.devices()[:1]
    x = data.make_array(config, 2**31 + 11, devices)
    host = np.asarray(x)
    assert np.isnan(host).any()
    groups, answers = traffic.make_call(mix, config, x, devices)()
    np.testing.assert_array_equal(np.asarray(groups), expected)
    for f in funcs:
        want = _brute(host, by, expected, f)
        have = np.asarray(answers[f], dtype=np.float64)
        np.testing.assert_array_equal(np.isnan(have), np.isnan(want))
        np.testing.assert_allclose(have, want, rtol=1e-6)

    got = harness.readings(cell, x, 2**31 + 11, [(groups, answers)])
    assert got["groups"] == 0 and got["nan_mismatch"] == 0
    assert all(got[f"rel_err.{f}"] < 1e-6 for f in funcs), got
    assert compare.verdict(got, mix["limits"])[0]

    wrong = dict(answers, nanmean=answers["nanmean"].at[..., int(absent[0])].set(280.0))
    bad = harness.readings(cell, x, 2**31 + 11, [(groups, wrong)])
    assert bad["nan_mismatch"] == 6  # the absent group, at every time step
    assert not compare.verdict(bad, mix["limits"])[0]

    result = harness.run(cell, 2**31 + 12, 0.1, False, devices, age=lambda: 0.0)
    assert result["correct"], result["checks"]


def test_label_dims_must_be_trailing():
    config = {"dims": ["time", "y", "x"], "labels": {"a": {"source": "s", "dims": ["time", "y"]},
                                                      "b": {"source": "s", "dims": []}}}
    for name in ("a", "b"):
        with pytest.raises(ValueError, match="trailing"):
            data.label_dims(config, name)
    with pytest.raises(ValueError, match="trailing"):
        data.label_dims(config, "month")  # a calendar field spans time, which is not last here


def test_reference_over_expected_groups():
    groups = np.array([1, 4])
    answers = {"nanmean": np.array([[2.0, 3.0]]), "count": np.array([[5.0, 6.0]]),
               "nansum": np.array([[1.0, 1.0]]), "max": np.array([[7.0, 8.0]])}
    expected, out = reference.over(np.arange(6), groups, answers)
    np.testing.assert_array_equal(expected, np.arange(6))
    np.testing.assert_array_equal(out["nanmean"], [[np.nan, 2.0, np.nan, np.nan, 3.0, np.nan]])
    np.testing.assert_array_equal(out["count"], [[0, 5.0, 0, 0, 6.0, 0]])
    np.testing.assert_array_equal(out["nansum"], [[0, 1.0, 0, 0, 1.0, 0]])
    assert np.isnan(out["max"][0, [0, 2, 3, 5]]).all()
    with pytest.raises(ValueError, match="expected"):
        reference.over(np.array([0, 1, 2]), np.array([1, 9]), {"nanmean": np.zeros((1, 2))})
