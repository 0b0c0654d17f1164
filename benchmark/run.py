"""Run one cell of the benchmark once, on the chips of this machine.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` a short stretch of the loop is traced and they are its
per-layer metrics. The last line of standard output is the result as one
JSON object; the compared numbers, each beside its limit, are the last
lines of standard error. With no TPU, or fewer chips than the cell asks
for, it exits with code 2 and prints no result.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
EXIT_NO_CHIP = 2


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    import flox_tpu  # noqa: F401 — the system under test must be beside the benchmark

    from benchmark import harness

    cell = harness.Cell.load(args.workload, traced=bool(args.trace))
    harness.compile_cache()
    try:
        devices = harness.chips_present(cell.chips)
    except harness.NoChip as exc:
        print(f"benchmark: {exc}; nothing was run", file=sys.stderr)
        return EXIT_NO_CHIP
    harness.emit(harness.run(cell, args.seed, args.seconds, bool(args.trace), devices))
    return 0


if __name__ == "__main__":
    sys.exit(main())
