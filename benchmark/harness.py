"""One run of one cell: set-up, the measured window, the comparison with the
reference, and the result line.

Everything a cell needs is found by name: its configuration and traffic mix
from ``BENCHMARK.json``, its labels' source in ``labels/<module>.py`` where
the configuration names one, each metric's reader in ``metrics/<name>.py``.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import compare, data, reference, trace, traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_DIR = HERE / ".trace"
#: a traced run skips this many steps, then traces at least TRACE_STEPS
#: steps and TRACE_SECONDS seconds (or the whole --seconds, if shorter)
TRACE_SKIP, TRACE_STEPS, TRACE_SECONDS = 2, 3, 2.0
#: calls a traced run makes with flox_tpu's telemetry on, once the traced
#: stretch has ended: the first builds its programs again, the second does not
COUNTED_CALLS = 2


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    metrics: list[dict]

    @classmethod
    def load(cls, name: str, traced: bool = False) -> "Cell":
        """The cell named ``name`` in ``BENCHMARK.json``; with ``traced`` its
        per-layer metrics, else its end-to-end ones."""
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        w = cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
        mix = traffic.load(HERE / "traffic" / f"{w['traffic']}.json")
        data.label_dims(config, mix["labels"])  # raises where they are not the array's trailing dims
        e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
        if not traced:
            metrics = e2e
        else:
            moved = {m["name"] for m in e2e}
            metrics = [
                m for m in bench["per_layer"]
                if (name in m["workloads"] if "workloads" in m else m["moves"] in moved)
            ]
        return cls(name, int(w["chips"]), config, mix, metrics)


@dataclass
class Run:
    """What a metric reader sees of one run."""

    cell: Cell
    setup_s: float
    #: (start, end, ok) of every call in the window, host clock seconds
    steps: list[tuple[float, float, bool]]
    input_bytes: int
    output_bytes: int
    peak: dict
    trace: trace.Trace | None = None
    #: flox_tpu's telemetry registry (counters, and gauges): name -> its
    #: change in each call of a traced run's counted stretch (``counted``);
    #: empty in an untraced run
    counters: dict[str, list[float]] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.steps[-1][1] - self.steps[0][0]

    def ok_durations(self) -> list[float]:
        return [e - s for s, e, ok in self.steps if ok]


def reader(name: str):
    """``read(run) -> float | None`` from ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"benchmark.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``): set-up counts
    the interpreter and JAX's start too, not only what follows ``main``."""
    ticks = os.sysconf("SC_CLK_TCK")
    start = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19]) / ticks
    return float(Path("/proc/uptime").read_text().split()[0]) - start


def compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` where
    set, else ``<checkout>/.jax_cache``; every program is kept, however fast
    it compiled, so a second run of a cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chips_present(chips: int) -> list:
    """The first ``chips`` TPU devices, or NoChip."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"jax found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; jax found {len(devs)}")
    return devs[:chips]


class CompileCount:
    """Programs built since ``reset`` (jax.monitoring events), and how many
    of them the persistent cache held."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.n = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, *_args, **_kw) -> None:
        self.n += event == self.BUILD

    def _on_event(self, event: str, **_kw) -> None:
        self.hits += event == self.HIT

    def reset(self) -> tuple[int, int]:
        """``(programs built, of them loaded from the persistent cache)``."""
        out = (self.n, self.hits)
        self.n = self.hits = 0
        return out


def log(line: str) -> None:
    print(line, flush=True)


def _loop(call, seconds: float, min_steps: int = 1, annotate: bool = False):
    """Closed loop of one caller until ``seconds`` have passed and
    ``min_steps`` calls ended. Returns (steps, first answers, last answers,
    errors)."""
    import jax

    steps, errors = [], []
    first = last = None
    t_start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        try:
            if annotate:
                with jax.profiler.TraceAnnotation(trace.STEP):
                    with jax.profiler.TraceAnnotation(trace.CALL):
                        groups, answers = call(block=False)
                    with jax.profiler.TraceAnnotation(trace.WAIT):
                        jax.block_until_ready(answers)
            else:
                groups, answers = call()
            ok = True
        except Exception as exc:  # noqa: BLE001 — a failed call is counted, and printed
            errors.append(repr(exc))
            ok = False
        t1 = time.perf_counter()
        steps.append((t0, t1, ok))
        if ok:
            if first is None:
                first = (groups, answers)
            else:
                last = (groups, answers)
        if t1 - t_start >= seconds and len(steps) >= min_steps:
            return steps, first, last, errors


def counted(call, calls: int = COUNTED_CALLS) -> dict[str, list[float]]:
    """The change of each of flox_tpu's telemetry counters (and gauges) in
    each of ``calls`` calls made with telemetry on. The program caches are cleared first, so the first call
    builds its programs again and what the program counts as it builds them
    (``mesh.local_onepass``) shows beside what it counts on every call."""
    import flox_tpu
    from flox_tpu import cache, telemetry

    cache.clear_all()
    out: dict[str, list[float]] = {}
    with flox_tpu.set_options(telemetry=True):
        for i in range(calls):
            before = telemetry.METRICS.snapshot()
            call()
            for name, value in telemetry.METRICS.snapshot().items():
                if value != before.get(name, 0):
                    out.setdefault(name, [0.0] * calls)[i] = float(value - before.get(name, 0))
    return out


def memory_peak(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def sampled_series(cell: Cell, x, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """``(cells, series)``: the seed's sample of the cells a call keeps, and
    their series over the reduced dims as the columns of one array."""
    reduced = len(data.label_dims(cell.config, cell.mix["labels"]))
    cells = data.sample_cells(cell.config, seed, reduced=reduced)
    return cells, data.take_cells(x, cells, columns=True, reduced=reduced)


def reference_of(cell: Cell, series: np.ndarray, groupby=reference.groupby) -> tuple[np.ndarray, dict]:
    """``groupby`` over ``series`` by the cell's labels, its answers laid
    out over the expected groups where the configuration gives them."""
    name = cell.mix["labels"]
    groups, answers = groupby(series, data.labels(cell.config, name).ravel(), traffic.funcs(cell.mix))
    expected = data.expected_groups(cell.config, name)
    return (groups, answers) if expected is None else reference.over(expected, groups, answers)


def readings(cell: Cell, x, seed: int, answered: list) -> dict[str, float]:
    """The compared numbers of the answers of the window's first and last
    calls, at the seed's sample of cells, against the float64 reference
    (each number's worst over the calls)."""
    t0 = time.perf_counter()
    funcs = traffic.funcs(cell.mix)
    cells, series = sampled_series(cell, x, seed)
    ref_groups, ref = reference_of(cell, series)
    got = [
        compare.numbers(groups, {f: data.take_cells(answers[f], cells) for f in funcs if f in answers},
                        ref_groups, ref)
        for groups, answers in answered
    ]
    log(f"check: {len(cells)} cells of {len(answered)} calls against the float64 reference "
        f"in {time.perf_counter() - t0:.3f} s")
    return compare.worst(got) if got else {"no_answer": math.inf}


def run(cell: Cell, seed: int, seconds: float, traced: bool, devices, age=process_age_s) -> dict:
    """One run of ``cell`` on ``devices``: the result line's dict, with the
    compared numbers last under ``checks``."""
    counter = CompileCount()
    t0 = age()
    x = data.make_array(cell.config, seed, devices)
    t1 = age()
    call = traffic.make_call(cell.mix, cell.config, x, devices)
    ngroups = traffic.ngroups(cell.config, cell.mix)
    in_bytes, out_bytes = traffic.logical_bytes(cell.config, cell.mix, ngroups)
    call()  # warm-up: compiles (or loads) every program the window runs
    setup_s = age()
    compiles, hits = counter.reset()
    log(f"setup: {setup_s:.3f} s = start {t0:.3f} s + data {t1 - t0:.3f} s + warm-up {setup_s - t1:.3f} s; "
        f"{compiles} programs built, {hits} of them from the persistent cache; data {x.shape} {x.dtype} "
        f"{x.nbytes / 1e9:.3f} GB on {len(devices)} chip(s)")

    tr = None
    if traced:
        import shutil

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        _loop(call, 0.0, TRACE_SKIP)
        trace.capture_start(TRACE_DIR)
        try:
            steps, first, last, errors = _loop(call, min(seconds, TRACE_SECONDS), TRACE_STEPS, annotate=True)
        finally:
            xplane = trace.capture_stop(TRACE_DIR)
        tr = trace.load(xplane, cell.chips)
        log(f"trace: {xplane.stat().st_size} bytes, {sum(len(v) for v in tr.ops.values())} device ops")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        steps, first, last, errors = _loop(call, seconds)
    compiles, _ = counter.reset()
    peak = memory_peak(devices)
    counters = {}
    if traced:
        counters = counted(call)
        built, hits = counter.reset()
        log(f"counters: {COUNTED_CALLS} calls with telemetry on from cleared program caches, "
            f"{built} programs built, {hits} of them from the persistent cache; {counters}")
    kind = devices[0].device_kind
    ok = [s for s in steps if s[2]]
    ms = sorted((e - s) * 1e3 for s, e, _ in steps)
    log(f"window: {len(steps)} calls, {len(steps) - len(ok)} failed, {compiles} programs built, "
        f"{steps[-1][1] - steps[0][0]:.3f} s; call ms min {ms[0]:.3f} median {ms[len(ms) // 2]:.3f} "
        f"max {ms[-1]:.3f}, first three {[round((e - s) * 1e3, 3) for s, e, _ in steps[:3]]}; "
        f"memory_peak_bytes {peak}")
    slow = sorted(range(len(steps)), key=lambda i: steps[i][0] - steps[i][1])[:3]
    log("slowest calls (index, s into the window, ms): " + ", ".join(
        f"({i}, {steps[i][0] - steps[0][0]:.3f}, {(steps[i][1] - steps[i][0]) * 1e3:.3f})" for i in slow))
    for e in errors[:5]:
        print(f"failed call: {e}", file=sys.stderr, flush=True)

    r = Run(cell, setup_s, steps, in_bytes, out_bytes, peaks(kind) if devices[0].platform == "tpu" else {}, tr,
            counters)
    metrics = {}
    for m in cell.metrics:
        value = reader(m["name"])(r)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    answered = [a for a in (first, last) if a is not None]
    correct, checks = compare.verdict(readings(cell, x, seed, answered), cell.mix["limits"])
    correct = correct and not errors
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(steps), "failed": len(steps) - len(ok),
              "metrics": metrics, "device": device}
    if tr is not None:
        lo, hi = tr.window()
        busy = trace.busy_share(tr)
        device["window_s"] = (hi - lo) / 1e9
        device["busy_s"] = (busy or 0.0) * (hi - lo) / 1e9
        result["breakdown"] = trace.breakdown(tr)
    result["checks"] = checks
    return result


def finite(v):
    """JSON has no infinity or NaN: such a number is written as a string."""
    return v if isinstance(v, (int, float)) and math.isfinite(v) else str(v)


def emit(result: dict) -> None:
    """The compared numbers as the last lines of standard error, and the
    result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = {n: {"value": finite(c["value"]), "limit": finite(c["limit"])} for n, c in result["checks"].items()}
    print(json.dumps(result), flush=True)
