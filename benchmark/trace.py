"""The profiler's trace, reduced to device op intervals and the benchmark's
own host spans, and the arithmetic every per-layer reader shares.

A traced run wraps each call of the loop in host spans
(``jax.profiler.TraceAnnotation``): ``bench.step`` around the whole call,
``bench.call`` while the entry point runs on the host, ``bench.wait`` while
the host waits for the answers. The device side is each chip's "XLA Ops"
line: one interval per operation the chip ran, a wait for an asynchronous
copy or collective included (its ``-done`` op). An op that holds others
(a ``while`` around its body) counts in the busy time but not in the
breakdown, which lists the ops that did the work.

``load`` also reads the program's own spans and scopes from the same file
(``program_trace.Program``, as ``Trace.program``); a reader puts them on the
host's clock with ``program_trace.align(trace, trace.program)``.
"""

from __future__ import annotations

import re
import statistics
from dataclasses import dataclass, field
from pathlib import Path
from typing import TYPE_CHECKING

if TYPE_CHECKING:
    from .program_trace import Program

STEP, CALL, WAIT = "bench.step", "bench.call", "bench.wait"
SPANS = (STEP, CALL, WAIT)
#: what the host was doing in a gap that no benchmark span covers
BETWEEN = "bench.loop"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: an op's trace name is its HLO text: "%name = shape opcode(...), custom_call_target=..."
HLO = re.compile(r"^%([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
TARGET = re.compile(r'custom_call_target="([\w.\-]+)"')
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


@dataclass
class Trace:
    """``ops``: device id -> sorted ``(start_ns, end_ns, name)`` of the ops
    that hold no other op; ``outer``: the same for ops that do;
    ``spans``: sorted ``(start_ns, end_ns, name)`` of the benchmark's spans;
    ``program``: the program's spans and scopes, where they were read."""

    ops: dict[int, list[tuple[int, int, str]]]
    spans: list[tuple[int, int, str]]
    outer: dict[int, list[tuple[int, int, str]]] = field(default_factory=dict)
    merged: dict[int, list[tuple[int, int]]] = field(default_factory=dict)
    program: Program | None = None

    def __post_init__(self):
        self.merged = {
            d: merge((s, e) for s, e, _ in v + self.outer.get(d, [])) for d, v in self.ops.items()
        }

    def steps(self) -> list[tuple[int, int]]:
        return [(s, e) for s, e, n in self.spans if n == STEP]

    def window(self) -> tuple[int, int]:
        steps = self.steps()
        if not steps:
            raise ValueError("the trace holds no benchmark step")
        return steps[0][0], steps[-1][1]


def load(xplane: Path, chips: int) -> Trace:
    """Read an ``.xplane.pb``: the ops of TPU devices 0..chips-1, the spans,
    and the program's spans and scopes."""
    from jax.profiler import ProfileData

    from . import program_trace

    prof = ProfileData.from_file(str(xplane))
    ops: dict[int, list] = {}
    spans = []
    for plane in prof.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and int(m.group(1)) < chips and line.name == OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    (int(e.start_ns), int(e.end_ns), e.name) for e in line.events
                )
            elif not m:
                spans.extend((int(e.start_ns), int(e.end_ns), e.name) for e in line.events if e.name in SPANS)
    split = {d: split_outer(v) for d, v in ops.items()}
    return Trace({d: v[0] for d, v in split.items()}, sorted(spans), {d: v[1] for d, v in split.items()},
                 program=program_trace.load(xplane, chips))


def split_outer(events) -> tuple[list, list]:
    """``(leaves, outer)``: sorted events split by whether one holds another."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    outer = set()
    stack: list[int] = []
    for i, (s, e, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            outer.add(stack[-1])
        stack.append(i)
    leaves = [ev for i, ev in enumerate(events) if i not in outer]
    return sorted(leaves), sorted(events[i] for i in outer)


def capture_start(log_dir: Path) -> None:
    """Start the profiler with the Python tracer off (it would slow the
    loop); the host tracer keeps the benchmark's spans."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def capture_stop(log_dir: Path) -> Path:
    """Stop the profiler; the newest ``.xplane.pb`` it wrote."""
    import jax

    jax.profiler.stop_trace()
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"the profiler wrote no .xplane.pb under {log_dir}")
    return found[-1]


def op_label(text: str) -> str:
    """A short stable name for an op: its HLO name and opcode, and the
    custom call's target (a Pallas kernel is ``tpu_custom_call``)."""
    m = HLO.match(text)
    if not m:
        return text[:80]
    t = TARGET.search(text)
    return f"{m.group(1)} {m.group(2)}" + (f":{t.group(1)}" if t else "")


def merge(intervals) -> list[tuple[int, int]]:
    """The union of intervals, as sorted disjoint intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: list[tuple[int, int]], lo: int, hi: int) -> int:
    """Length of ``[lo, hi)`` that the disjoint ``merged`` intervals cover."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


def busy_ns(tr: Trace, dev: int, lo: int, hi: int) -> int:
    return covered(tr.merged.get(dev, []), lo, hi)


def devices(tr: Trace) -> list[int]:
    return sorted(tr.ops)


def busy_share(tr: Trace) -> float | None:
    """Busy over the window, mean over the chips: None with no device op."""
    if not any(tr.ops.values()):
        return None
    lo, hi = tr.window()
    return statistics.fmean(busy_ns(tr, d, lo, hi) / (hi - lo) for d in devices(tr))


def step_busy_ns(tr: Trace, dev: int) -> int:
    """Device-busy time inside the benchmark's steps."""
    return sum(busy_ns(tr, dev, s, e) for s, e in tr.steps())


def collective_share(tr: Trace) -> float | None:
    """Share of busy time in collective ops (start/done pairs included),
    mean over the chips: None where no collective ran."""
    lo, hi = tr.window()
    shares = []
    for d in devices(tr):
        coll = merge((s, e) for s, e, n in tr.ops[d] if COLLECTIVE.search(n))
        busy = busy_ns(tr, d, lo, hi)
        if coll and busy:
            shares.append(covered(coll, lo, hi) / busy)
    return statistics.fmean(shares) if shares else None


def dispatch_gaps_ns(tr: Trace) -> list[int]:
    """Per step, from its start to the first device op of that step on any chip."""
    gaps = []
    for s, e in tr.steps():
        first = [o[0] for d in devices(tr) for o in tr.ops[d] if s <= o[0] < e]
        if first:
            gaps.append(min(first) - s)
    return gaps


def idle_gaps(tr: Trace, dev: int) -> list[tuple[str, int]]:
    """Idle stretches of one chip inside the window, each named by the
    innermost benchmark span open at its middle (``bench.loop`` if none)."""
    lo, hi = tr.window()
    busy = [(max(s, lo), min(e, hi)) for s, e in tr.merged.get(dev, []) if e > lo and s < hi]
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    out = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            mid = (a + b) // 2
            open_ = [(e - s, n) for s, e, n in tr.spans if s <= mid < e]
            out.append((min(open_)[1] if open_ else BETWEEN, b - a))
    return out


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The ops that took most device time (seconds, mean over the chips) and
    the longest idle gaps of chip 0 by what the host was doing."""
    lo, hi = tr.window()
    per_op: dict[str, int] = {}
    for d in devices(tr):
        for s, e, n in tr.ops[d]:
            t = max(0, min(e, hi) - max(s, lo))
            if t:
                k = op_label(n)
                per_op[k] = per_op.get(k, 0) + t
    n_dev = max(1, len(devices(tr)))
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(tr, devices(tr)[0]), key=lambda g: -g[1])[:top] if devices(tr) else []
    return {
        "device_ops": [[n, t / n_dev / 1e9] for n, t in ops],
        "idle_gaps": [[n, t / 1e9] for n, t in gaps],
    }
