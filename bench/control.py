#!/usr/bin/env python3
"""Controls of the correctness check: runs that must come out not correct.

    python3 bench/control.py --workload <name> --seeds 11,12,13 --seconds 30 --mode band0
    python3 bench/control.py --workload <name> --seeds 11,12,13 --seconds 30 --mode f32

``band0`` is the program's own lower-precision path: the fused loop with no
certification band, deciding every step in the chip's emulated float64
(``fused.device_band`` patched to 0).  ``f32`` is the plain reference
computed in float32, the precision below the configuration's float64, put in
the program's place.  Each seed is one full run (set-up, window at the
cell's own size, check) in this one process; the numbers compared are
printed per seed, and the last stdout line is a JSON summary.  The
benchmark's own runs never run a control.  ``band0`` needs the chip;
``f32`` runs anywhere (the benchmark's tests run it on the CPU at a small
size).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402
from bench.reference import campaign as ref_campaign  # noqa: E402


class _Experiment:
    """The fields of the program's ``ExperimentResult`` that are compared."""

    def __init__(self, res: dict):
        self.n_pairs = res["n_pairs"]
        self.curves = res["curves"]
        self.thresholds = res["thresholds"]


def f32_run_campaign(cfg):
    """``run_campaign`` computed by the plain reference in float32."""
    from bench.kinds.campaign import _instances

    def run_campaign(exps, n, p, n_pairs, n_bounds, seed0, h4_iters,
                     include_h4, backend):
        inst = _instances(dict(cfg, n=n, p=p), exps, n_pairs, seed0)
        res = ref_campaign.run(inst, n_bounds, h4_iters, include_h4,
                               dtype=np.float32)
        return {f: _Experiment(r) for f, r in res.items()}

    return run_campaign


@contextlib.contextmanager
def control(mode: str, cell):
    """Put the control in the program's place for the duration."""
    from repro.core import fused
    from repro.sim import experiments

    saved = [(fused, "device_band", fused.device_band),
             (experiments, "run_campaign", experiments.run_campaign)]
    if mode == "band0":
        fused.device_band = lambda: 0.0
    else:
        experiments.run_campaign = f32_run_campaign(cell.config)
    try:
        yield
    finally:
        for mod, name, val in saved:
            setattr(mod, name, val)


def run_controls(cell, seeds, seconds, mode, device=None) -> list:
    if str(harness.ROOT / "src") not in sys.path:
        sys.path.insert(0, str(harness.ROOT / "src"))
    out = []
    with control(mode, cell):
        for seed in seeds:
            t0 = time.perf_counter()
            res = harness.run(cell, seed, seconds, False, t0, device)
            harness.log(f"[control] {cell.name} mode={mode} seed={seed} "
                        f"correct={res['correct']} checked="
                        f"{json.dumps(res['checked'])}")
            out.append({"seed": seed, "correct": res["correct"],
                        "checked": res["checked"]})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--mode", choices=("band0", "f32"), required=True)
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, harness.load_spec())
    device = None
    if args.mode == "band0":
        try:
            device = harness.device_info(cell.chips)
        except harness.NoAccelerator as e:
            print(f"bench: {e}", file=sys.stderr)
            return 3
    seeds = [int(s) for s in args.seeds.split(",")]
    out = run_controls(cell, seeds, args.seconds, args.mode, device)
    print(json.dumps({"workload": cell.name, "mode": args.mode,
                      "device": device, "runs": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
