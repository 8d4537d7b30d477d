#!/usr/bin/env python3
"""The program's own spans in a traced stretch, and a breakdown run of one
campaign cell that reads them.

:func:`reduce` turns the events of the host thread that ran the stretch
(:data:`bench.trace.STRETCH`) into, for each program span (``fused.*`` and
``campaign.*``, listed in ``repro.core.spans.SPANS``), its seconds clipped to
the stretch and its count, and ``campaign_driver_s``: the time inside
``campaign.*`` spans that no ``fused.*`` span covers.  :func:`metrics` turns
that into per-dispatch times and shares of the stretch.  It works on the
plain planes of :func:`bench.trace.read_planes`, so the tests check it on a
synthesised trace.

    python3 bench/spans.py --workload <campaign cell> --seed <n>

runs the cell's set-up with its phases timed apart (process start to
set-up: imports and the device; the pool's yardstick check; the warm-up
campaign), then one campaign of the window three times with the same seed0:
untraced, traced, untraced.  It prints, as its last line, a JSON object with
the set-up phases, the three campaign lengths (the cost of tracing), the
device reduction of :mod:`bench.trace`, the spans, the metrics and the bytes
per dispatch.  Exits 3 without a TPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402

_PROGRAM = ("fused.", "campaign.")


def _measure(intervals) -> float:
    return sum(e - s for s, e in trace.union(intervals))


def _minus(a, b) -> float:
    """Length of the union of ``a`` outside the union of ``b``."""
    a, b = trace.union(a), trace.union(b)
    return _measure(a) - _measure([(max(s, bs), min(e, be))
                                   for s, e in a for bs, be in b
                                   if min(e, be) > max(s, bs)])


def reduce(planes):
    """``{"window_s", "spans": {name: [seconds, count]},
    "campaign_driver_s"}`` of the stretch, or None where the trace holds no
    stretch."""
    thread = trace._stretch_line(planes)
    stretch = [(s, s + d) for s, d, name in thread if name == trace.STRETCH]
    if not stretch:
        return None
    lo, hi = stretch[0]
    ivs = {}
    for s, d, name in thread:
        if name.startswith(_PROGRAM):
            ivs.setdefault(name, []).extend(trace._clip([(s, s + d)], lo, hi))
    ns = 1e-9

    def of(prefix):
        return [iv for name, v in ivs.items() if name.startswith(prefix)
                for iv in v]

    return {"window_s": (hi - lo) * ns,
            "spans": {name: [_measure(v) * ns, len(v)]
                      for name, v in sorted(ivs.items())},
            "campaign_driver_s": _minus(of("campaign."), of("fused.")) * ns}


def metrics(spans: dict, dispatches: int) -> dict:
    """The per-dispatch times (ms) of the dispatch's three phases and the
    shares (%) of the stretch in host replay, parked steps and the campaign
    driver, from :func:`reduce` over a stretch of ``dispatches`` dispatches.
    A metric reads None where the stretch lacks any of its spans (a program
    without them, or one that renamed them), never 0."""
    sp, w = spans["spans"], spans["window_s"]

    def total(*names):
        if not all(name in sp for name in names):
            return None
        return sum(sp[name][0] for name in names)

    def over(x, by, scale):
        return scale * x / by if x is not None and by > 0 else None

    driver = (spans["campaign_driver_s"]
              if any(name.startswith("campaign.") for name in sp) else None)
    out = {f"{phase}_ms_per_dispatch.campaign":
           over(total(f"fused.{phase}"), dispatches, 1e3)
           for phase in ("launch", "wait", "fetch")}
    out["host_replay_share.campaign"] = over(
        total("fused.replay", "fused.record"), w, 100.0)
    out["parked_step_share.campaign"] = over(
        total("fused.parked_step"), w, 100.0)
    out["campaign_driver_share.campaign"] = over(driver, w, 100.0)
    return out


def profile_campaign(cell, seed: int) -> dict:
    """Set-up, then one campaign untraced, traced and untraced again (same
    seed0), and the traced one's breakdown."""
    from bench.kinds import campaign as kind
    from bench.traffic import generators
    from repro.core import fused

    st = kind.State(cell, seed)
    t0 = time.perf_counter()
    before_setup = t0 - T_START
    st.yardstick = sum(kind._yardstick_differences(st.cfg, st.families,
                                                   st.pairs, seed0)
                       for seed0 in generators.pool_seeds(st.traffic))
    t1 = time.perf_counter()
    st.campaign(generators.warmup_seed(st.traffic), pairs=1)
    t2 = time.perf_counter()
    print(f"[setup] yardstick check {t1 - t0!r} s ({st.yardstick} instances "
          f"differ), warm-up campaign {t2 - t1!r} s", flush=True)
    seed0 = next(st.seeds)
    plain = []

    def untraced():
        c0 = time.perf_counter()
        st.campaign(seed0)
        plain.append(time.perf_counter() - c0)

    untraced()
    fused.reset_dispatch_count()
    tracer = trace.Tracer()
    tracer.start()
    c0 = time.perf_counter()
    with trace.span("bench.campaign"):
        st.campaign(seed0)
    traced_s = time.perf_counter() - c0
    tracer.stop()
    dispatches = fused.dispatch_count()
    # a program from before the transfer counter has none to read
    moved = getattr(fused, "transfer_bytes", dict)()
    decisions = fused.decision_counts()
    try:
        files = sorted(pathlib.Path(tracer.dir).rglob("*.xplane.pb"))
        planes = trace.read_planes(files[-1])
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    untraced()
    spans = reduce(planes)
    out = {"seed0": seed0, "before_setup_s": before_setup,
           "yardstick_s": t1 - t0, "warmup_s": t2 - t1,
           "untraced_s": plain, "traced_s": traced_s,
           "dispatches": dispatches,
           "decisions": decisions,
           "kib_per_dispatch": {k: v / dispatches / 1024
                                for k, v in moved.items()},
           "trace": trace.reduce(planes), "spans": spans,
           "metrics": metrics(spans, dispatches) if spans else {}}
    return out


def main(argv=None) -> int:
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, harness.load_spec())
    try:
        device = harness.device_info(cell.chips)
    except harness.NoAccelerator as e:
        print(f"spans: {e}", file=sys.stderr)
        return 3
    if str(harness.ROOT / "src") not in sys.path:
        sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.core import fused

    print(f"[setup] compile cache: {fused.enable_persistent_cache()}",
          flush=True)
    out = profile_campaign(cell, args.seed)
    out = dict(workload=args.workload, seed=args.seed, device=device,
               process_s=time.perf_counter() - T_START, **out)
    print(json.dumps(out, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
