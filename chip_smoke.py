#!/usr/bin/env python3
"""Chip smoke test: drive the main paths once on a TPU and check the results.

    python chip_smoke.py             # one chip: campaigns, fleet, kernels
    python chip_smoke.py --chips 4   # four chips: sharded vs fused campaign

Phases (one chip), in this order:

  pallas          both split-score kernels compiled for the chip at n=160
                  widths against the numpy kernels.
  campaign_paper  Section-5 campaign E1-E4 at the ``paper_sim --full`` size
                  (n=40, p=100, 50 pairs, 12 bounds, H4) through
                  ``run_campaign(backend="fused")`` and ``backend="numpy"``.
  fleet           the standard fleet trace (16 groups x 16 replicas, n=12,
                  p=6, 30 ticks) through ``ReplanService(backend="fused")``
                  and ``backend="numpy"``.
  campaign_large  the campaign at the ``paper_sim --large-grid`` size
                  (n=160, p=1000, 6 pairs, 8 bounds, H4).

With ``--chips 4`` the only phase is ``sharded_large``: the large-grid
campaign through ``backend="sharded"`` on a mesh of all four devices against
``backend="fused"``.

The campaigns and the fleet must agree EXACTLY with the numpy reference
(``summarize_experiment`` text and every float per family,
``fleet_digest()``); the fused
fleet must also publish without a single scalar fallback or quarantine.  The
kernels score in float32 on the chip and are held to the tolerance stated at
``KERNEL_RTOL``.  Every phase prints its observations on lines of its own;
the last line of standard output is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``.  The script exits
non-zero when any phase fails, and at once when JAX finds no TPU: it has no
CPU path.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

EXPS = ("E1", "E2", "E3", "E4")
PAPER = dict(n=40, p=100, n_pairs=50, n_bounds=12)      # paper_sim --full
LARGE = dict(n=160, p=1000, n_pairs=6, n_bounds=8)      # paper_sim --large-grid
FLEET = dict(n_groups=16, replicas=16, n=12, p=6, fleet_seed=2007,
             num_ticks=30, trace_seed=42, burst_prob=0.6)  # fleet_bench STANDARD
# Compiled kernels score in float32 (unit roundoff 2^-24 ~ 6e-8).  Each output
# is a handful of rounded operations, two of them differences of nearby
# inputs, so its error is held to 2^-16 ~ 1.5e-5 of the largest magnitude of
# that output over the live lanes: ~256 float32 ulps of the output's scale.
KERNEL_RTOL = 2.0 ** -16

_COMPILE_S = [0.0]


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _count_compiles() -> None:
    """Sum the backend compile time of every program compiled from here on
    (persistent-cache hits are loads, not compiles, and do not count)."""
    import jax

    def on_event(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE_S[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise RuntimeError(f"no TPU: JAX found {info}; this smoke has no "
                           "CPU path")
    if info["count"] < chips:
        raise RuntimeError(f"--chips {chips} but JAX found {info['count']}")
    return info


# ---------------------------------------------------------------------------
# campaigns
# ---------------------------------------------------------------------------

def _max_rel_diff(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, dtype=float), np.asarray(b, dtype=float)
    both = np.isfinite(a) & np.isfinite(b)
    if (np.isnan(a) != np.isnan(b)).any():
        return float("inf")
    if not both.any():
        return 0.0
    d = np.abs(a[both] - b[both]) / np.maximum(np.abs(b[both]), 1e-300)
    return float(d.max())


def compare_campaigns(phase: str, got: dict, want: dict, label: str) -> bool:
    """Exact comparison of two ``run_campaign`` results family by family:
    ``summarize_experiment`` text (the contract) and the raw float arrays.
    On a difference, print the first differing summary row and the largest
    relative difference over the curves and thresholds."""
    import numpy as np

    from repro.sim.experiments import summarize_experiment

    ok = True
    for exp in want:
        g, w = got[exp], want[exp]
        sg, sw = summarize_experiment(g), summarize_experiment(w)
        pairs = [(np.asarray(x), np.asarray(y)) for c in w.curves
                 for x, y in zip(g.curves[c], w.curves[c])]
        pairs += [(np.asarray(g.thresholds[c]), np.asarray(w.thresholds[c]))
                  for c in w.thresholds]
        raw = all(np.array_equal(x, y, equal_nan=True) for x, y in pairs)
        rel = max(_max_rel_diff(x, y) for x, y in pairs)
        rows = [(x, y) for x, y in zip(sg.splitlines(), sw.splitlines())
                if x != y]
        log(phase, f"{exp}: summary_equal={sg == sw} differing_rows="
                   f"{len(rows)} raw_equal={raw} raw_max_rel_diff={rel!r} "
                   f"({label})")
        if rows:
            log(phase, f"{exp}: first differing row: {rows[0][0]!r} vs "
                       f"{rows[0][1]!r}")
        ok &= sg == sw and raw
    return ok


def _campaign_pair(phase: str, engine: str, ref_engine: str, cfg: dict):
    """Run one campaign size through ``engine`` (cold, then warm) and
    ``ref_engine``; print traces, dispatches, compile seconds, wall times."""
    from repro.core import fused, sharded
    from repro.sim.experiments import run_campaign

    kw = dict(n_pairs=cfg["n_pairs"], n_bounds=cfg["n_bounds"],
              include_h4=True)
    n, p = cfg["n"], cfg["p"]
    for mod in (fused, sharded):
        mod.reset_trace_count()
        mod.reset_dispatch_count()
    c0 = _COMPILE_S[0]
    t0 = time.perf_counter()
    run_campaign(EXPS, n, p, backend=engine, **kw)
    cold = time.perf_counter() - t0
    compile_s = _COMPILE_S[0] - c0
    traces = fused.trace_count() + sharded.trace_count()
    dispatches = fused.dispatch_count() + sharded.dispatch_count()
    decisions = fused.decision_counts()
    t0 = time.perf_counter()
    got = run_campaign(EXPS, n, p, backend=engine, **kw)
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    want = run_campaign(EXPS, n, p, backend=ref_engine, **kw)
    ref = time.perf_counter() - t0
    log(phase, f"n={n} p={p} pairs={cfg['n_pairs']} bounds={cfg['n_bounds']}"
               f" engine={engine}: band={fused.device_band()!r} traces="
               f"{traces} dispatches={dispatches} splits_decided_on_device="
               f"{decisions['device']} steps_decided_on_host="
               f"{decisions['host']} compile_s={compile_s!r} cold_wall_s="
               f"{cold!r} warm_wall_s={warm!r} {ref_engine}_wall_s={ref!r}")
    return got, want


def phase_campaign(phase: str, cfg: dict) -> bool:
    got, want = _campaign_pair(phase, "fused", "numpy", cfg)
    return compare_campaigns(phase, got, want, "fused vs numpy")


def phase_sharded(phase: str, cfg: dict, chips: int) -> bool:
    from repro.core import sharded

    got, want = _campaign_pair(phase, "sharded", "fused", cfg)
    spans = sharded.output_devices()
    log(phase, f"mesh devices={sharded.device_count()} "
               f"output_sharding_devices={spans}")
    ok = compare_campaigns(phase, got, want, "sharded vs fused")
    return ok and spans == chips


# ---------------------------------------------------------------------------
# fleet
# ---------------------------------------------------------------------------

def phase_fleet(phase: str, cfg: dict) -> bool:
    from repro.core import fused
    from repro.fleet import ReplanService, gen_burst_trace, make_fleet

    pairs, groups = make_fleet(cfg["n_groups"], cfg["replicas"], cfg["n"],
                               cfg["p"], seed=cfg["fleet_seed"])
    trace = gen_burst_trace(groups, cfg["num_ticks"], seed=cfg["trace_seed"],
                            n_stages=cfg["n"], initial_pods=cfg["p"],
                            burst_prob=cfg["burst_prob"])
    out = {}
    for backend in ("fused", "numpy"):
        fused.reset_trace_count()
        fused.reset_dispatch_count()
        c0 = _COMPILE_S[0]
        t0 = time.perf_counter()
        svc = ReplanService(pairs, backend=backend)
        m = svc.run_trace(trace)
        wall = time.perf_counter() - t0
        out[backend] = svc
        log(phase, f"backend={backend}: requests={m.requests} solves="
                   f"{m.solves} fallback_solves={m.fallback_solves} "
                   f"quarantined_requests={m.quarantined_requests} "
                   f"quarantined={len(svc.quarantined)} wall_s={wall!r}")
        if backend == "fused":
            shapes = fused.traced_shapes()
            dec = fused.decision_counts()
            log(phase, f"backend=fused: distinct (n, p) programs compiled="
                       f"{len(shapes)} {shapes} traces={fused.trace_count()}"
                       f" compile_s={_COMPILE_S[0] - c0!r} dispatches="
                       f"{fused.dispatch_count()} splits_decided_on_device="
                       f"{dec['device']} steps_decided_on_host={dec['host']}")
            if svc.last_solve_error is not None:
                log(phase, f"backend=fused: last batched-solve error: "
                           f"{svc.last_solve_error!r}")
    f, r = out["fused"], out["numpy"]
    same = f.fleet_digest() == r.fleet_digest()
    log(phase, f"digest fused={f.fleet_digest()} numpy={r.fleet_digest()} "
               f"equal={same}")
    clean = (f.metrics.fallback_solves == 0
             and f.metrics.quarantined_requests == 0 and not f.quarantined)
    return same and clean


# ---------------------------------------------------------------------------
# pallas kernels
# ---------------------------------------------------------------------------

def phase_pallas(phase: str, n: int = 160) -> bool:
    import numpy as np

    from repro.core.heuristics import (_PERMS3, score_2way_kernel,
                                       score_3way_kernel)
    from repro.kernels import split_score

    interpret = split_score._interpret()
    log(phase, f"interpret={interpret}")
    if interpret:
        return False
    rng = np.random.default_rng(23)
    ok = True

    A, K = 64, n - 1                              # every cut of a span-n row
    pre = np.sort(rng.uniform(0.0, 100.0, (A, K + 2)), axis=1)
    delta = rng.uniform(0.0, 50.0, (A, K + 2))
    args = (pre[:, :1], pre[:, 1:-1], pre[:, -1:],
            delta[:, :1], delta[:, 1:-1], delta[:, -1:], 10.0,
            rng.uniform(0.05, 2.0, (A, 1)), rng.uniform(0.05, 2.0, (A, 1)))
    need = rng.integers(1, K + 1, A)
    got = split_score.score_2way_pallas(*args, need=need, interpret=False)
    want = score_2way_kernel(*args, xp=np)
    live = np.concatenate([np.arange(K)[None, :] < need[:, None]] * 2, axis=1)
    masks = [live] * 3
    names = ["2way.cyc1", "2way.cyc2", "2way.dlat"]

    A3 = 16
    o1, o2 = np.triu_indices(n - 1, k=1)          # every (c1, c2) of span n
    Kp = o1.size
    dI = rng.uniform(0.0, 10.0, (A3, 3, Kp))
    W3 = rng.uniform(0.1, 100.0, (A3, 3, Kp))
    dO = rng.uniform(0.0, 10.0, (A3, 3, Kp))
    invp = rng.uniform(0.05, 2.0, (A3, 3))[:, np.asarray(_PERMS3)][..., None]
    base = rng.uniform(1.0, 50.0, (A3, 1, 1))
    spans = rng.integers(3, n + 1, A3)
    got3 = split_score.score_3way_pallas(
        dI[:, None], W3[:, None], dO[:, None], invp, base,
        need=split_score.pair_need(spans, n), interpret=False)
    want3 = score_3way_kernel(dI[:, None], W3[:, None], dO[:, None], invp,
                              base, xp=np)
    live_l = o2[None, :] <= (spans - 2)[:, None]
    for w in want3:
        shape = (live_l[:, None, None, :] if w.ndim == 4
                 else live_l[:, None, :])
        masks.append(np.broadcast_to(shape, w.shape))
    names += ["3way.cyc", "3way.dlat", "3way.mx"]

    for name, g, w, lv in zip(names, list(got) + list(got3),
                              list(want) + list(want3), masks):
        g = np.asarray(g)
        err = np.abs(g[lv].astype(np.float64) - w[lv])
        scale = np.abs(w[lv]).max()
        rel = float(err.max() / scale)
        good = bool(np.isfinite(g[lv]).all()) and rel <= KERNEL_RTOL
        log(phase, f"{name}: shape={g.shape} dtype={g.dtype} live_lanes="
                   f"{int(lv.sum())} max_rel_err={rel!r} "
                   f"(tolerance {KERNEL_RTOL!r}) ok={good}")
        ok &= good
    return ok


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-vs-fused campaign on a "
                         "mesh of four chips")
    args = ap.parse_args(argv)
    device = None
    results = {}
    try:
        device = device_info(args.chips)
        log("device", json.dumps(device))
        from repro.core.fused import enable_persistent_cache

        log("device", f"compile cache: {enable_persistent_cache()}")
        _count_compiles()
        if args.chips == 4:
            phases = [("sharded_large",
                       lambda ph: phase_sharded(ph, LARGE, args.chips))]
        else:
            phases = [("pallas", phase_pallas),
                      ("campaign_paper", lambda ph: phase_campaign(ph, PAPER)),
                      ("fleet", lambda ph: phase_fleet(ph, FLEET)),
                      ("campaign_large", lambda ph: phase_campaign(ph, LARGE))]
        for name, fn in phases:
            t0 = time.perf_counter()
            try:
                results[name] = bool(fn(name))
            except Exception:  # noqa: BLE001 — report it; the run still fails
                traceback.print_exc()
                results[name] = False
            log(name, f"{'PASS' if results[name] else 'FAIL'} in "
                      f"{time.perf_counter() - t0!r} s")
    except Exception:  # noqa: BLE001 — no device, no package: fail
        traceback.print_exc()
    ok = device is not None and bool(results) and all(results.values())
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
