#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``<name>`` is a workload of ``BENCHMARK.json``.  The run makes its inputs
from ``--seed``, warms up every shape the cell's traffic uses (counted as
set-up), measures for ``--seconds``, and compares what the timed path
produced with the plain reference.  With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics read from
counters, the benchmark's spans and a profiler trace.  The last line of
standard output is the result JSON; see ``bench/harness.py``.

Exits 3, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for; exits 2 when the cell or its files cannot be found.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.Cell(args.workload, harness.load_spec())
        cell.readers(bool(args.trace))
        cell.kind()
    except (OSError, ImportError, KeyError, ValueError) as e:
        print(f"bench: cannot resolve workload {args.workload!r}: {e}",
              file=sys.stderr)
        return 2
    try:
        device = harness.device_info(cell.chips)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.log(f"[device] {device}")
    try:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          T_START, device)
    except Exception:  # noqa: BLE001 — report it and fail without a result
        traceback.print_exc()
        return 1
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
