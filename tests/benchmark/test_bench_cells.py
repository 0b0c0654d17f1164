"""Each cell's run at tiny shapes on the CPU: it comes out correct, and
comes out not correct with the control in the program's place and with
each fault the cell can have planted under the timed path.

The harness's look for a chip is skipped (``run.py`` refuses the CPU); the
rest of a run is driven as it is: data from the seed, the closed loop
through flox_tpu's public entry point, the comparison with the reference.
"""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import flox_tpu  # noqa: E402
from benchmark import compare, data, harness, reference, traffic  # noqa: E402

CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2**31 + 99  # past 32 signed bits, as the driver's seeds are
SECONDS = 0.2


def tiny(name: str, traced: bool = False) -> harness.Cell:
    """The cell at 3 x 8 grid cells; an hourly calendar keeps one leap year,
    so every month and day of year is present."""
    cell = harness.Cell.load(name, traced=traced)
    cell.config.update(n_latitude=3, n_longitude=8)
    if cell.config["time_freq"] == "h":
        cell.config["n_time"] = 24 * 366
    return cell


def run(cell: harness.Cell) -> dict:
    import jax

    return harness.run(cell, SEED, SECONDS, False, jax.devices()[: cell.chips], age=lambda: 0.0)


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct(name):
    cell = tiny(name)
    result = run(cell)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {m["name"] for m in cell.metrics}
    assert result["device"]["count"] == cell.chips
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_in_bfloat16_is_not_correct(name):
    """The control: the float64 reference over bfloat16-rounded inputs, in
    the program's place, fails the cell's limits."""
    import jax

    cell = tiny(name)
    x = data.make_array(cell.config, SEED, jax.devices()[: cell.chips])
    cells = data.sample_cells(cell.config, SEED)
    series = data.take_cells(x, cells, columns=True)
    labels = data.labels(cell.config, cell.mix["labels"])
    funcs = traffic.funcs(cell.mix)
    groups, exact = reference.groupby(series, labels, funcs)
    _, low = reference.groupby_bf16(series, labels, funcs)
    correct, checks = compare.verdict(compare.numbers(groups, low, groups, exact), cell.mix["limits"])
    assert not correct, checks


def _broken(monkeypatch, entry_name, wrap):
    real = getattr(flox_tpu, entry_name)
    monkeypatch.setattr(flox_tpu, entry_name, wrap(real))


def _every_other_step(real):
    """Half of the batch left out: the statistic over every other step."""
    def call(x, by, **kw):
        return real(x[..., ::2], by[::2], **kw)
    return call


def _answer_altered(real):
    """An answer altered where it is produced: the first group's, by 1e-3."""
    def alter(a):
        return a.at[..., 0].multiply(1.001)

    def call(x, by, **kw):
        result, *groups = real(x, by, **kw)
        result = {k: alter(v) for k, v in result.items()} if isinstance(result, dict) else alter(result)
        return (result, *groups)
    return call


def _no_exchange(real):
    """The exchange between chips left out: the first chip's shard alone."""
    def call(x, by, mesh=None, method=None, **kw):
        n = x.shape[-1] // len(mesh.devices.flat)
        return real(np.asarray(x[..., :n]), by[:n], expected_groups=np.unique(by), **kw)
    return call


FAULTS = [(name, f) for name in CELLS for f in (_every_other_step, _answer_altered)]
FAULTS += [(name, _no_exchange) for name in CELLS if tiny(name).chips > 1]


@pytest.mark.parametrize("name, fault", FAULTS, ids=[f"{n}-{f.__name__.strip('_')}" for n, f in FAULTS])
def test_fault_under_the_timed_path_is_not_correct(name, fault, monkeypatch):
    cell = tiny(name)
    _broken(monkeypatch, cell.mix["entry"], fault)
    result = run(cell)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_is_correct(name):
    """A ``--trace 1`` run: the same comparison, the per-layer metrics that
    a CPU trace can give, and the device's traced window."""
    import jax

    cell = tiny(name, traced=True)
    result = harness.run(cell, SEED, SECONDS, True, jax.devices()[: cell.chips], age=lambda: 0.0)
    assert result["correct"], result["checks"]
    assert "host.factorize_ms" in result["metrics"]
    assert set(result["metrics"]) <= {m["name"] for m in cell.metrics}
    assert result["device"]["window_s"] > 0 and set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_counters_come_from_calls_after_the_traced_stretch(monkeypatch):
    """``Run.counters`` carries ``mesh.local_onepass``, which the program
    counts as it builds the mesh's local stage (the Pallas sum kernel, in
    interpret mode here), from the calls a traced run makes with telemetry
    on once its traced stretch has ended; every call before them ran with
    telemetry off."""
    import jax

    from flox_tpu.options import OPTIONS

    cell = tiny("era5_hourly_025_x4.doy_cohorts_nanmean", traced=True)
    telemetry_at_call = []
    real = flox_tpu.groupby_reduce

    def spy(*args, **kw):
        telemetry_at_call.append(OPTIONS["telemetry"])
        return real(*args, **kw)

    monkeypatch.setattr(flox_tpu, "groupby_reduce", spy)
    runs = []
    read = harness.reader
    monkeypatch.setattr(harness, "reader", lambda n: lambda run: runs.append(run) or read(n)(run))
    with flox_tpu.set_options(telemetry=False, segment_sum_impl="pallas"):
        result = harness.run(cell, SEED, SECONDS, True, jax.devices()[: cell.chips], age=lambda: 0.0)
    assert result["correct"], result["checks"]
    counters = runs[0].counters
    assert counters["mesh.local_onepass"][0] >= 1 and "mesh.local_perleg" not in counters
    assert counters["cache.program_misses"] == [1.0, 0.0] and counters["cache.program_hits"] == [0.0, 1.0]
    assert result["metrics"]["mesh.onepass_share"]["value"] == 100.0
    n = harness.COUNTED_CALLS
    assert telemetry_at_call[-n:] == [True] * n and not any(telemetry_at_call[:-n])
