"""The program's own spans and scopes in the profiler's trace, and the
per-layer numbers they give.

While a profiler session collects, flox_tpu's phase spans
(``telemetry.span``) are host events named ``flox.<phase>``: the call
(``flox.groupby_reduce`` / ``flox.groupby_aggregate_many``) around
``flox.factorize``, ``flox.dispatch`` (around ``flox.h2d``, the inputs'
host-to-device staging, with its ``bytes``), ``flox.combine``,
``flox.finalize`` and, on the mesh, ``flox.program-build`` or
``flox.mesh-dispatch``. Inside the programs, ``jax.named_scope`` puts
``flox.kernel``, ``flox.rowblock``, ``flox.combine`` and ``flox.finalize``
into each op's name stack, which the TPU's trace keeps as the ``tf_op`` stat
of the op's event metadata (the CPU's trace does not carry it).

``trace.load`` keeps neither, and ``jax.profiler.ProfileData`` does not show
event metadata, so :func:`load` reads the same ``.xplane.pb`` through a
minimal schema of its protobuf. A :class:`Program` sits beside a
``trace.Trace`` and is joined to its ops by start and name.

    python3 -m benchmark.program_trace --workload <name> --seed <n> [--seconds <s>]

makes one traced run of a cell, as ``run.py --trace 1`` does, prints the
harness's result line, then one JSON line with the numbers below and the
breakdown by the program's spans and scopes.
"""

from __future__ import annotations

import argparse
import bisect
import functools
import json
import statistics
import sys
from dataclasses import dataclass, field
from pathlib import Path

from . import trace

PREFIX = "flox."
FACTORIZE, H2D = "flox.factorize", "flox.h2d"
KERNEL, COMBINE = "flox.kernel", "flox.combine"
NAME_STACK = "tf_op"
#: the runtime's host event that hands a program run to a chip, and the
#: device line of program runs; both carry the run's ``run_id``
ENQUEUE, MODULES_LINE = "DoEnqueueProgram", "XLA Modules"


@dataclass
class Program:
    """``spans``: sorted ``(start_ns, end_ns, name, args)`` of the host
    events named ``flox.*``; ``scopes``: device id -> ``(start_ns, op
    name)`` -> the op's name stack; ``enqueued`` / ``started``: device id ->
    run id -> when the host handed the program run to the chip (host clock)
    / when the chip started it (device clock)."""

    spans: list[tuple[int, int, str, dict]] = field(default_factory=list)
    scopes: dict[int, dict[tuple[int, str], str]] = field(default_factory=dict)
    enqueued: dict[int, dict[int, int]] = field(default_factory=dict)
    started: dict[int, dict[int, int]] = field(default_factory=dict)

    def scope(self, dev: int, op: tuple[int, int, str]) -> str:
        return self.scopes.get(dev, {}).get((op[0], op[2]), "")

    def scoped(self) -> bool:
        """Whether any op of the trace is under a ``flox.*`` scope."""
        return any(innermost(st) for v in self.scopes.values() for st in v.values())


def _schema():
    """The message class of an XSpace with only the fields read here (the
    field numbers are those of tsl's ``xplane.proto``; maps are repeated
    key/value entries on the wire; strings are read as bytes)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    messages = {
        "XSpace": [("planes", 1, many, "XPlane")],
        "XPlane": [("name", 2, one, F.TYPE_BYTES), ("lines", 3, many, "XLine"),
                   ("event_metadata", 4, many, "EventEntry"), ("stat_metadata", 5, many, "StatEntry")],
        "EventEntry": [("key", 1, one, F.TYPE_INT64), ("value", 2, one, "XEventMetadata")],
        "StatEntry": [("key", 1, one, F.TYPE_INT64), ("value", 2, one, "XStatMetadata")],
        "XLine": [("name", 2, one, F.TYPE_BYTES), ("timestamp_ns", 3, one, F.TYPE_INT64),
                  ("events", 4, many, "XEvent")],
        "XEvent": [("metadata_id", 1, one, F.TYPE_INT64), ("offset_ps", 2, one, F.TYPE_INT64),
                   ("duration_ps", 3, one, F.TYPE_INT64), ("stats", 4, many, "XStat")],
        "XStat": [("metadata_id", 1, one, F.TYPE_INT64), ("double_value", 2, one, F.TYPE_DOUBLE),
                  ("uint64_value", 3, one, F.TYPE_UINT64), ("int64_value", 4, one, F.TYPE_INT64),
                  ("str_value", 5, one, F.TYPE_BYTES), ("ref_value", 7, one, F.TYPE_UINT64)],
        "XEventMetadata": [("name", 2, one, F.TYPE_BYTES), ("stats", 5, many, "XStat")],
        "XStatMetadata": [("name", 2, one, F.TYPE_BYTES)],
    }
    fd = descriptor_pb2.FileDescriptorProto(name="flox_bench_xplane.proto", package="flox_bench")
    for name, fields in messages.items():
        msg = fd.message_type.add(name=name)
        for fname, number, label, kind in fields:
            f = msg.field.add(name=fname, number=number, label=label)
            if isinstance(kind, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".flox_bench.{kind}"
            else:
                f.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("flox_bench.XSpace"))


def _stat(stat, names: dict[int, str]):
    if stat.str_value:
        return stat.str_value.decode(errors="replace")
    if stat.ref_value:
        return names.get(stat.ref_value, "")
    return stat.int64_value or stat.uint64_value or stat.double_value


def _args(stats, names: dict[int, str]) -> dict:
    return {names.get(s.metadata_id, ""): _stat(s, names) for s in stats}


def load(xplane: Path, chips: int) -> Program:
    """The ``flox.*`` host events, the program runs' enqueues and starts,
    and the name stacks of the ops of TPU devices 0..chips-1 in an
    ``.xplane.pb``."""
    space = _schema()()
    space.ParseFromString(Path(xplane).read_bytes())
    prog = Program()
    for plane in space.planes:
        names = {e.key: e.value.name.decode(errors="replace") for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        m = trace.DEVICE_PLANE.match(plane.name.decode(errors="replace"))
        dev = int(m.group(1)) if m else None
        for line in plane.lines:
            lname = line.name.decode(errors="replace")
            if dev is not None and dev < chips and lname == MODULES_LINE:
                started = prog.started.setdefault(dev, {})
                for ev in line.events:
                    run = _args(ev.stats, names).get("run_id")
                    if run is not None:
                        started[int(run)] = line.timestamp_ns + ev.offset_ps // 1000
            elif dev is not None and dev < chips and lname == trace.OPS_LINE:
                stacks = {key: next((str(_stat(s, names)) for s in md.stats if names.get(s.metadata_id) == NAME_STACK), "")
                          for key, md in meta.items()}
                scopes = prog.scopes.setdefault(dev, {})
                for ev in line.events:
                    start = line.timestamp_ns + ev.offset_ps // 1000
                    scopes[(start, meta[ev.metadata_id].name.decode(errors="replace"))] = stacks[ev.metadata_id]
            elif dev is None:
                for ev in line.events:
                    name = meta[ev.metadata_id].name.decode(errors="replace")
                    if not (name.startswith(PREFIX) or name == ENQUEUE):
                        continue
                    start = line.timestamp_ns + ev.offset_ps // 1000
                    args = _args(ev.stats, names)
                    if name == ENQUEUE:
                        if "run_id" in args:
                            runs = prog.enqueued.setdefault(int(args.get("device_ordinal", 0)), {})
                            runs[int(args["run_id"])] = min(start, runs.get(int(args["run_id"]), start))
                    else:
                        prog.spans.append((start, start + ev.duration_ps // 1000, name, args))
    prog.spans.sort(key=lambda s: s[:3])
    return prog


def clock_offsets(prog: Program) -> dict[int, int]:
    """Per chip, the shift (ns) that puts its clock on the host's: the
    profiler aligns them only to about a millisecond, and a chip cannot start
    a program run before the host enqueued it, so the shift makes the
    fastest of the trace's runs start as it is enqueued (0 where no run of
    the chip is matched to its enqueue)."""
    out = {}
    for dev, started in prog.started.items():
        enq = prog.enqueued.get(dev, {})
        lags = [t - enq[run] for run, t in started.items() if run in enq]
        out[dev] = -min(lags) if lags else 0
    return out


def align(tr: trace.Trace, prog: Program) -> tuple[trace.Trace, Program]:
    """Copies of the trace and the program with each chip's ops moved onto
    the host's clock by :func:`clock_offsets`."""
    off = clock_offsets(prog)

    def moved(ops, d):
        return [(s + off.get(d, 0), e + off.get(d, 0), n) for s, e, n in ops]

    tr2 = trace.Trace({d: moved(v, d) for d, v in tr.ops.items()}, tr.spans,
                      {d: moved(v, d) for d, v in tr.outer.items()})
    scopes = {d: {(s + off.get(d, 0), n): st for (s, n), st in v.items()} for d, v in prog.scopes.items()}
    return tr2, Program(prog.spans, scopes, prog.enqueued, prog.started)


@functools.lru_cache(maxsize=None)
def _parts(stack: str) -> frozenset[str]:
    return frozenset(stack.split("/"))


def in_scope(stack: str, scope: str) -> bool:
    return scope in _parts(stack)


@functools.lru_cache(maxsize=None)
def innermost(stack: str) -> str:
    """The innermost ``flox.*`` scope of a name stack, or ``""``."""
    return next((p for p in reversed(stack.split("/")) if p.startswith(PREFIX)), "")


def _clip(spans, lo: int, hi: int, name: str | None = None):
    return [(max(s, lo), min(e, hi)) for s, e, n, _ in spans if s < hi and e > lo and (name is None or n == name)]


def factorize_ms(tr: trace.Trace, prog: Program) -> float | None:
    """Median over the traced calls of the ``flox.factorize`` time inside
    each call; nothing where the program opened no such span."""
    if not any(n == FACTORIZE for _, _, n, _ in prog.spans):
        return None
    per = [sum(e - s for s, e in _clip(prog.spans, lo, hi, FACTORIZE)) for lo, hi in tr.steps()]
    return statistics.median(per) / 1e6 if per else None


class _Busy:
    """One chip's busy time between any two instants, by bisection over
    the prefix sums of its merged op intervals."""

    def __init__(self, merged: list[tuple[int, int]]):
        self.merged = merged
        self.starts = [s for s, _ in merged]
        self.before = [0]
        for s, e in merged:
            self.before.append(self.before[-1] + e - s)

    def upto(self, t: int) -> int:
        i = bisect.bisect_right(self.starts, t)
        if not i:
            return 0
        s, e = self.merged[i - 1]
        return self.before[i - 1] + min(e, t) - s

    def between(self, lo: int, hi: int) -> int:
        return self.upto(hi) - self.upto(lo)


def blocking_ms(tr: trace.Trace, prog: Program) -> float | None:
    """Median over the traced calls of the chip's idle time inside the call
    that falls under an open ``flox.*`` span (mean over the chips): the time
    the program's host code holds the chip."""
    if not prog.spans or not any(tr.ops.values()):
        return None
    busy = [_Busy(tr.merged.get(d, [])) for d in trace.devices(tr)]
    per = []
    for lo, hi in tr.steps():
        cover = trace.merge(_clip(prog.spans, lo, hi))
        per.append(statistics.fmean(sum(e - s - b.between(s, e) for s, e in cover) for b in busy))
    return statistics.median(per) / 1e6 if per else None


def busy_share_where(tr: trace.Trace, prog: Program, keep) -> float | None:
    """Share of the device-busy time inside the calls that ops whose name
    stack ``keep`` accepts take, mean over the chips; nothing where no op is
    under a ``flox.*`` scope (a CPU trace, or a program without them)."""
    if not prog.scoped():
        return None
    shares = []
    for d in trace.devices(tr):
        ops = tr.ops[d] + tr.outer.get(d, [])
        mine = trace.merge((s, e) for s, e, n in ops if keep(prog.scope(d, (s, e, n))))
        busy = trace.step_busy_ns(tr, d)
        if busy:
            shares.append(sum(trace.covered(mine, lo, hi) for lo, hi in tr.steps()) / busy)
    return statistics.fmean(shares) if shares else None


def glue_share(tr: trace.Trace, prog: Program) -> float | None:
    """Busy time in ops under no ``flox.kernel`` scope (copies, slices,
    selects, row-block moves), in % of the busy time inside the calls."""
    share = busy_share_where(tr, prog, lambda st: in_scope(st, KERNEL))
    return None if share is None else 100.0 * (1.0 - share)


def combine_share(tr: trace.Trace, prog: Program) -> float | None:
    """Busy time in ops under ``flox.combine``, in % of the busy time inside
    the calls."""
    share = busy_share_where(tr, prog, lambda st: in_scope(st, COMBINE))
    return None if share is None else 100.0 * share


def scoped_share(tr: trace.Trace, prog: Program) -> float | None:
    """Busy time in ops under any ``flox.*`` scope, in % of the busy time
    inside the calls."""
    share = busy_share_where(tr, prog, lambda st: bool(innermost(st)))
    return None if share is None else 100.0 * share


def h2d_bytes(tr: trace.Trace, prog: Program) -> int | None:
    """Median over the traced calls of the bytes staged host to device."""
    if not any(n == H2D for _, _, n, _ in prog.spans):
        return None
    per = [sum(int(a.get("bytes", 0)) for s, e, n, a in prog.spans if n == H2D and lo <= s < hi)
           for lo, hi in tr.steps()]
    return int(statistics.median(per)) if per else None


def _all_spans(tr: trace.Trace, prog: Program) -> list[tuple[int, int, str]]:
    return sorted(tr.spans + [(s, e, n) for s, e, n, _ in prog.spans])


def _innermost_at(spans, t: int) -> str:
    open_ = [(e - s, n) for s, e, n in spans if s <= t < e]
    return min(open_)[1] if open_ else trace.BETWEEN


def idle_gaps(tr: trace.Trace, prog: Program, dev: int) -> list[tuple[str, int]]:
    """``trace.idle_gaps``, each gap named by the innermost benchmark or
    program span open at its middle."""
    lo, hi = tr.window()
    spans = _all_spans(tr, prog)
    busy = [(max(s, lo), min(e, hi)) for s, e in tr.merged.get(dev, []) if e > lo and s < hi]
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(_innermost_at(spans, (a + b) // 2), b - a) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def idle_by_span(tr: trace.Trace, prog: Program, dev: int) -> dict[str, int]:
    """One chip's idle time inside the calls, each stretch of it given to
    the innermost benchmark or program span open there (a gap that spans
    several is split between them)."""
    spans = _all_spans(tr, prog)
    busy = _Busy(tr.merged.get(dev, []))
    out: dict[str, int] = {}
    for lo, hi in tr.steps():
        cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e) if lo < t < hi})
        for a, b in zip(cuts, cuts[1:]):
            idle = b - a - busy.between(a, b)
            if idle:
                name = _innermost_at(spans, a)
                out[name] = out.get(name, 0) + idle
    return out


def timeline(tr: trace.Trace, prog: Program) -> dict[str, list[float]]:
    """Median over the traced calls of where, from the call's start, each
    span first opens and closes, and where the call's first and last device
    ops run on chip 0 (microseconds)."""
    spans = _all_spans(tr, prog)
    dev = trace.devices(tr)[0] if trace.devices(tr) else None
    per: dict[str, list[tuple[int, int]]] = {}
    for lo, hi in tr.steps():
        seen: dict[str, tuple[int, int]] = {}
        for s, e, n in spans:
            if lo <= s < hi and n not in seen:
                seen[n] = (s - lo, e - lo)
        ops = [o for o in tr.ops.get(dev, []) if lo <= o[0] < hi]
        if ops:
            seen["device.first_op"] = (ops[0][0] - lo, ops[0][1] - lo)
            seen["device.last_op"] = (ops[-1][0] - lo, max(o[1] for o in ops) - lo)
        for n, v in seen.items():
            per.setdefault(n, []).append(v)
    med = {n: [statistics.median(v[0] for v in vs) / 1e3, statistics.median(v[1] for v in vs) / 1e3]
           for n, vs in per.items()}
    return dict(sorted(med.items(), key=lambda kv: kv[1]))


def bare_call_idle_share(tr: trace.Trace, prog: Program) -> float | None:
    """Chip 0's idle time inside the calls under ``bench.call`` and no
    program span, in % of its idle time inside the calls."""
    if not prog.spans or not trace.devices(tr):
        return None
    by = idle_by_span(tr, prog, trace.devices(tr)[0])
    total = sum(by.values())
    return 100.0 * by.get(trace.CALL, 0) / total if total else None


def op_label(text: str, stack: str) -> str:
    """``trace.op_label`` with the op's innermost ``flox.*`` scope in
    brackets, where it has one."""
    scope = innermost(stack)
    return trace.op_label(text) + (f" [{scope}]" if scope else "")


def breakdown(tr: trace.Trace, prog: Program, top: int = 12) -> dict:
    """The ops with most device time, labelled by scope, and the busy time
    by innermost scope (seconds, mean over the chips); chip 0's idle time
    inside the calls by span, its longest gaps, and the median call's
    timeline, on the host's clock."""
    tr, prog = align(tr, prog)
    lo, hi = tr.window()
    n_dev = max(1, len(trace.devices(tr)))
    per_op: dict[str, int] = {}
    per_scope: dict[str, int] = {}
    label = functools.lru_cache(maxsize=None)(op_label)
    for d in trace.devices(tr):
        for op in tr.ops[d]:
            t = max(0, min(op[1], hi) - max(op[0], lo))
            if t:
                stack = prog.scope(d, op)
                k = label(op[2], stack)
                per_op[k] = per_op.get(k, 0) + t
                s = innermost(stack) or "(none)"
                per_scope[s] = per_scope.get(s, 0) + t
    dev0 = trace.devices(tr)[0] if trace.devices(tr) else None
    gaps = idle_gaps(tr, prog, dev0) if dev0 is not None else []
    by_span = idle_by_span(tr, prog, dev0) if dev0 is not None else {}
    return {
        "device_ops": [[n, t / n_dev / 1e9] for n, t in sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "busy_by_scope": {n: t / n_dev / 1e9 for n, t in sorted(per_scope.items(), key=lambda kv: -kv[1])},
        "idle_by_span": {n: t / 1e9 for n, t in sorted(by_span.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [[n, t / 1e9] for n, t in sorted(gaps, key=lambda g: -g[1])[:top]],
        "timeline_us": timeline(tr, prog),
    }


def numbers(tr: trace.Trace, prog: Program) -> dict:
    """Every number this module reads, by name (``None`` where the trace
    does not carry what it needs), on the host's clock."""
    off = clock_offsets(prog)
    tr, prog = align(tr, prog)
    return {
        "device.clock_offset_us": statistics.fmean(off.values()) / 1e3 if off else None,
        "host.factorize_ms": factorize_ms(tr, prog),
        "host.blocking_ms": blocking_ms(tr, prog),
        "kernels.glue_share": glue_share(tr, prog),
        "mesh.combine_share": combine_share(tr, prog),
        "program.scoped_share": scoped_share(tr, prog),
        "host.h2d_bytes": h2d_bytes(tr, prog),
        "program.bare_call_idle_share": bare_call_idle_share(tr, prog),
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    import flox_tpu  # noqa: F401 — the system under test must be beside the benchmark

    from . import harness

    cell = harness.Cell.load(args.workload, traced=True)
    harness.compile_cache()
    try:
        devices = harness.chips_present(cell.chips)
    except harness.NoChip as exc:
        print(f"program_trace: {exc}; nothing was run", file=sys.stderr)
        return 2
    read = trace.load
    kept = {}

    def read_both(xplane, chips):
        kept["program"] = load(xplane, chips)
        kept["trace"] = read(xplane, chips)
        return kept["trace"]

    trace.load = read_both  # the harness deletes the file once read
    try:
        result = harness.run(cell, args.seed, args.seconds, True, devices)
    finally:
        trace.load = read
    harness.emit(result)
    tr, prog = kept["trace"], kept["program"]
    print(json.dumps({"program": numbers(tr, prog), "breakdown": breakdown(tr, prog)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
