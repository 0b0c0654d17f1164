"""The readings the comparison's limits are set from (not part of a run).

    python3 benchmark/control.py --workload <name> --seeds 1 2 ... [--control 3] [--calls 3]

In one process, for each seed: the cell's data, ``--calls`` calls of the
timed path at the cell's own size, and the compared numbers of the first
and the last call (the program's readings, which set each limit's lower
end). For the first ``--control`` seeds it also reads the control: the
float64 reference over the inputs rounded to bfloat16, in the program's
place (which sets the upper end). Prints one JSON line per seed and a
summary line with each number's largest program reading and smallest
control reading.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3, help="seeds that also read the control")
    ap.add_argument("--calls", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from benchmark import compare, data, harness, reference, traffic

    cell = harness.Cell.load(args.workload)
    harness.compile_cache()
    try:
        devices = harness.chips_present(cell.chips)
    except harness.NoChip as exc:
        print(f"control: {exc}; nothing was run", file=sys.stderr)
        return 2
    program, control = [], []
    for i, seed in enumerate(args.seeds):
        x = data.make_array(cell.config, seed, devices)
        call = traffic.make_call(cell.mix, cell.config, x, devices)
        answered = [call() for _ in range(args.calls)]
        line = {"seed": seed, "program": harness.readings(cell, x, seed, [answered[0], answered[-1]])}
        program.append(line["program"])
        if i < args.control:
            _, series = harness.sampled_series(cell, x, seed)
            groups, exact = harness.reference_of(cell, series)
            _, low = harness.reference_of(cell, series, reference.groupby_bf16)
            line["control"] = compare.numbers(groups, low, groups, exact)
            control.append(line["control"])
        print(json.dumps(line), flush=True)
        del x, call, answered
    names = sorted({k for r in program + control for k in r})
    print(json.dumps({
        "workload": cell.name,
        "seeds": len(program),
        "program_max": {n: max(r[n] for r in program) for n in names if all(n in r for r in program)},
        "control_min": {n: min(r[n] for r in control) for n in names if control and all(n in r for r in control)},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
