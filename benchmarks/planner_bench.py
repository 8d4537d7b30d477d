"""Planner quality + speed: heuristic optimality gap vs the exact solver on
small/medium instances, runtime scaling, the vectorized candidate-evaluation
speedup, and the batched-vs-fused campaign-engine comparison (warm, cold,
and cold-with-persistent-compilation-cache).

Prints ``name,us_per_call,derived`` CSV rows and writes them as
machine-readable ``BENCH_planner.json`` at the repo root so the perf
trajectory is tracked across PRs.  Rows additionally carry STRUCTURED fields
(``speedup``, ``dispatches``, ``cold_us``, ...) next to the human-readable
``derived`` string — ``benchmarks/bench_gate.py`` parses those to fail CI on
perf regressions.  Quality-only rows (optimality gaps) carry no
``us_per_call`` — gaps are reported in ``derived``/``gap`` only.

    PYTHONPATH=src python benchmarks/planner_bench.py [--quick]
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np

from repro.core import (Objective, PlanRequest, auto_request, evaluate,
                        evaluate_batch, exact_min_period, make_platform,
                        make_workload, pareto_exact, period, plan_request,
                        solve)
from repro.sim.experiments import run_campaign, run_experiment, summarize_experiment
from repro.sim.generators import gen_instance

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH_JSON = REPO_ROOT / "BENCH_planner.json"


def _cpu_child_env() -> dict:
    """Environment for a benchmark child process: ``src/`` on its path and
    JAX pinned to the CPU, so the child never contends for a chip that this
    process may already hold."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(REPO_ROOT / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env["JAX_PLATFORMS"] = "cpu"
    return env


def optimality_gaps(n_inst: int = 20, seed: int = 0) -> dict:
    """Mean period gap (heuristic / exact - 1) on instances small enough for
    the exact bitmask solver (n<=14, p<=9)."""
    rng = np.random.default_rng(seed)
    gaps = {c: [] for c in ("H1", "H2", "H3", "auto")}
    for _ in range(n_inst):
        n = int(rng.integers(4, 14))
        p = int(rng.integers(3, 9))
        wl = make_workload(rng.integers(1, 21, n).astype(float),
                           rng.integers(1, 51, n + 1).astype(float))
        pf = make_platform(rng.integers(1, 21, p).astype(float), 10.0)
        opt = period(wl, pf, exact_min_period(wl, pf))
        for code in ("H1", "H2", "H3"):
            # run to exhaustion: an unreachable period bound minimizes period
            c = solve(code, wl, pf, Objective("latency", bound=0.0))
            gaps[code].append(c.period / opt - 1)
        rep = plan_request(auto_request(wl, pf, Objective("period")))
        gaps["auto"].append(rep.plan.period / opt - 1)
    return {c: float(np.mean(v)) for c, v in gaps.items()}


def timing(reps: int = 10) -> list:
    """us_per_call for each solver at the paper's largest size (n=40, p=100),
    plus the full request/report portfolio."""
    rows = []
    wl, pf = gen_instance("E2", 40, 100, seed=1)
    for code in ("H1", "H2", "H3", "H5", "H6"):
        obj = (Objective("latency", bound=0.0) if code in ("H1", "H2", "H3")
               else Objective("period", bound=1e18))
        t0 = time.perf_counter()
        for _ in range(reps):
            solve(code, wl, pf, obj)
        us = (time.perf_counter() - t0) / reps * 1e6
        rows.append((f"heuristic_{code}_n40_p100", us, ""))
    t0 = time.perf_counter()
    plan_request(auto_request(wl, pf, Objective("period")))
    rows.append(("planner_auto_n40_p100", (time.perf_counter() - t0) * 1e6, ""))
    t0 = time.perf_counter()
    plan_request(PlanRequest(wl, pf, Objective("period")))
    rows.append(("plan_request_n40_p100", (time.perf_counter() - t0) * 1e6, ""))
    return rows


def vectorized_eval(reps: int = 5, seed: int = 3) -> list:
    """The tentpole perf claim: batch candidate evaluation vs the per-mapping
    Python loop, on the full mapping enumeration of a small instance (the
    workload of portfolio tables, sweeps, and pareto_exact)."""
    import itertools

    from repro.core import Mapping, all_interval_partitions

    rng = np.random.default_rng(seed)
    n, p = 8, 5
    wl = make_workload(rng.integers(1, 21, n).astype(float),
                       rng.integers(1, 51, n + 1).astype(float))
    pf = make_platform(rng.integers(1, 21, p).astype(float), 10.0)
    mappings = [Mapping(iv, procs)
                for m in range(1, min(n, p) + 1)
                for iv in all_interval_partitions(n, m)
                for procs in itertools.permutations(range(p), m)]

    t0 = time.perf_counter()
    for _ in range(reps):
        loop = np.array([evaluate(wl, pf, mp) for mp in mappings])
    us_loop = (time.perf_counter() - t0) / reps * 1e6
    t0 = time.perf_counter()
    for _ in range(reps):
        batch = evaluate_batch(wl, pf, mappings)
    us_batch = (time.perf_counter() - t0) / reps * 1e6
    assert np.allclose(loop, batch)

    t0 = time.perf_counter()
    for _ in range(reps):
        pareto_exact(wl, pf)
    us_pex = (time.perf_counter() - t0) / reps * 1e6

    k = len(mappings)
    return [
        (f"evaluate_loop_{k}_mappings", us_loop, ""),
        (f"evaluate_batch_{k}_mappings", us_batch,
         f"speedup={us_loop / us_batch:.1f}x"),
        (f"pareto_exact_n{n}_p{p}", us_pex, "vectorized enumeration"),
    ]


def _engine_comparison_rows(exps, points, kw, row_prefix) -> list:
    """Time a family set through all three engines (scalar reference, numpy
    lockstep, fused cold + warm), asserting byte-identical outputs, and emit
    ``{row_prefix}{scalar,batched,fused}_<tag>`` rows."""
    t0 = time.perf_counter()
    scal = {(e, n, p): run_experiment(e, n, p, engine="scalar", **kw)
            for n, p in points for e in exps}
    us_scal = (time.perf_counter() - t0) * 1e6

    def run_engine(backend):
        t0 = time.perf_counter()
        out = {}
        for n, p in points:
            camp = run_campaign(exps, n, p, backend=backend, **kw)
            for e in exps:
                out[(e, n, p)] = camp[e]
        return out, (time.perf_counter() - t0) * 1e6

    batc, us_batc = run_engine("numpy")
    fusd, us_cold = run_engine("fused")    # includes jit traces
    _, us_fusd = run_engine("fused")       # warm: traces cached
    for key in scal:
        assert summarize_experiment(scal[key]) == summarize_experiment(batc[key]), key
        assert summarize_experiment(scal[key]) == summarize_experiment(fusd[key]), key
    tag = (f"{exps[0]}-{exps[-1]}_"
           + "_".join(f"n{n}p{p}" for n, p in points))
    return [
        (f"{row_prefix}scalar_{tag}", us_scal, "per-instance reference path"),
        (f"{row_prefix}batched_{tag}", us_batc,
         f"speedup={us_scal / us_batc:.1f}x vs scalar, identical outputs",
         {"speedup_vs_scalar": us_scal / us_batc, "identical_outputs": True}),
        (f"{row_prefix}fused_{tag}", us_fusd,
         f"warm; speedup={us_scal / us_fusd:.1f}x vs scalar, "
         f"cold_with_traces_us={us_cold:.0f}, identical outputs",
         {"speedup_vs_scalar": us_scal / us_fusd, "cold_us": us_cold,
          "vs_batched": us_batc / us_fusd, "identical_outputs": True}),
    ]


def campaign_speedup(quick: bool = False) -> list:
    """The batched and fused campaign engines vs the per-instance reference
    path on a representative Section-5 slice (all four experiment families,
    paper batch size, small and large (n, p) points), asserting identical
    outputs while timing all three.  The fused engine is timed twice: cold
    (including its one-off jit traces) and warm (the steady-state cost every
    further campaign of the same shapes pays)."""
    if quick:
        points = ((10, 10),)
        kw = dict(n_pairs=4, n_bounds=4, h4_iters=4, include_h4=True)
    else:
        points = ((10, 10), (20, 100), (40, 100))
        kw = dict(n_pairs=50, n_bounds=12, h4_iters=10, include_h4=True)
    return _engine_comparison_rows(("E1", "E2", "E3", "E4"), points, kw,
                                   "campaign_")


def fused_large_grid(quick: bool = False) -> list:
    """The n in {80, 160}, p = 1000 follow-up families under the (now
    span-bucketed) fused engine — the campaign shape whose static-grid tax
    was steepest (PR-4 warm: 23.5 s at n=160 vs 2.2 s numpy) — asserting
    byte-identical outputs vs the numpy lockstep path.  Row names are stable
    across PRs so the bucketing win shows on the same rows."""
    from repro.core import fused

    if quick:
        points, n_pairs = ((80, 1000),), 2
    else:
        points, n_pairs = ((80, 1000), (160, 1000)), 4
    exps = ("E1", "E2", "E3", "E4")
    kw = dict(n_pairs=n_pairs, n_bounds=8, h4_iters=6, include_h4=True)
    rows = []
    for n, p in points:
        t0 = time.perf_counter()
        ref = run_campaign(exps, n, p, backend="numpy", **kw)
        us_np = (time.perf_counter() - t0) * 1e6
        fused.reset_bucket_trace_count()
        t0 = time.perf_counter()
        run_campaign(exps, n, p, backend="fused", **kw)   # cold: jit traces
        us_cold = (time.perf_counter() - t0) * 1e6
        buckets = fused.bucket_trace_count()
        t0 = time.perf_counter()
        fus = run_campaign(exps, n, p, backend="fused", **kw)
        us_warm = (time.perf_counter() - t0) * 1e6
        for e in exps:
            assert summarize_experiment(ref[e]) == summarize_experiment(fus[e]), (e, n)
        rows.append((f"campaign_fused_largegrid_E1-E4_n{n}p{p}", us_warm,
                     f"warm, span-bucketed; numpy_batched_us={us_np:.0f}, "
                     f"cold_with_traces_us={us_cold:.0f}, "
                     f"bucket_traces={buckets}, identical outputs",
                     {"numpy_batched_us": us_np, "cold_us": us_cold,
                      "vs_batched": us_np / us_warm, "bucket_traces": buckets,
                      "bucket_trace_budget": fused.trace_budget(n),
                      "identical_outputs": True}))
    return rows


def fused_bucketed_cold_start(quick: bool = False) -> list:
    """The span-bucketed fused engine's cold-start story, measured in FRESH
    subprocesses: cold with the persistent compilation cache turned off,
    cold with a warmed cache (compile replaced by cache load), and the
    in-process warm steady state.  The children are pinned to the CPU: they
    measure host cold starts and must never race this process for a chip."""
    from repro.core import fused

    n, p, pairs, nb = (9, 7, 3, 4) if quick else (20, 100, 8, 6)
    tag = f"E1-E4_n{n}p{p}"
    exps = ("E1", "E2", "E3", "E4")
    child = (
        "import time, sys\n"
        "from repro.core import fused\n"
        "import jax\n"
        "if sys.argv[1] == 'cache':\n"
        "    fused.enable_persistent_cache()\n"
        "else:\n"
        "    jax.config.update('jax_enable_compilation_cache', False)\n"
        "from repro.sim.experiments import run_campaign\n"
        "t0 = time.perf_counter()\n"
        f"run_campaign({exps!r}, {n}, {p}, n_pairs={pairs}, n_bounds={nb},\n"
        f"             h4_iters=4, backend='fused')\n"
        "print('ELAPSED_US=%.0f' % ((time.perf_counter() - t0) * 1e6))\n"
    )

    def run_child(cache_arg):
        env = _cpu_child_env()
        out = subprocess.run([sys.executable, "-c", child, cache_arg],
                             capture_output=True, text=True, env=env,
                             check=True)
        for line in out.stdout.splitlines():
            if line.startswith("ELAPSED_US="):
                return float(line.split("=", 1)[1])
        raise RuntimeError(f"no timing in child output: {out.stdout!r}")

    us_nocache = run_child("none")
    run_child("cache")                   # populate the cache
    us_cached = run_child("cache")       # fresh process, warm cache

    # in-process warm steady state of the same campaign shape
    kw = dict(n_pairs=pairs, n_bounds=nb, h4_iters=4, include_h4=True)
    run_campaign(exps, n, p, backend="fused", **kw)
    t0 = time.perf_counter()
    run_campaign(exps, n, p, backend="fused", **kw)
    us_warm = (time.perf_counter() - t0) * 1e6
    return [
        (f"campaign_fused_bucketed_warm_{tag}", us_warm,
         "in-process warm steady state (traces cached)",
         {"buckets_k1": len(fused.bucket_sizes(n, 1)),
          "buckets_k2": len(fused.bucket_sizes(n, 2))}),
        (f"campaign_fused_bucketed_cold_nocache_{tag}", us_nocache,
         "fresh process, no persistent compilation cache (full jit traces)"),
        (f"campaign_fused_bucketed_cold_cache_{tag}", us_cached,
         f"fresh process, warm persistent compilation cache "
         f"(cache_speedup={us_nocache / us_cached:.1f}x vs no-cache cold)",
         {"cache_speedup": us_nocache / us_cached,
          "nocache_cold_us": us_nocache}),
    ]


def split_score_pallas(quick: bool = False) -> list:
    """The pallas split-scoring kernels vs the shared numpy kernels on a
    lockstep-representative candidate grid (identical floats on every live
    lane, asserted).  On CPU the pallas path runs in interpret mode — the
    honest number here is its overhead factor; the compiled TPU/GPU path is
    what the kernels exist for."""
    from repro.core.heuristics import _PERMS3, score_2way_kernel, score_3way_kernel
    from repro.kernels import split_score

    rng = np.random.default_rng(23)
    A, K = (16, 64) if quick else (64, 160)
    reps = 3 if quick else 20
    pre = np.sort(rng.uniform(0.0, 100.0, (A, K + 2)), axis=1)
    delta = rng.uniform(0.0, 50.0, (A, K + 2))
    args = (pre[:, :1], pre[:, 1:-1], pre[:, -1:],
            delta[:, :1], delta[:, 1:-1], delta[:, -1:], 10.0,
            rng.uniform(0.05, 2.0, (A, 1)), rng.uniform(0.05, 2.0, (A, 1)))
    need = rng.integers(1, K + 1, A)

    t0 = time.perf_counter()
    for _ in range(reps):
        want = score_2way_kernel(*args, xp=np)
    us_np = (time.perf_counter() - t0) / reps * 1e6
    got = split_score.score_2way_pallas(*args, need=need)   # traces
    t0 = time.perf_counter()
    for _ in range(reps):
        got = split_score.score_2way_pallas(*args, need=need)
    us_pl = (time.perf_counter() - t0) / reps * 1e6
    live = np.concatenate([np.arange(K)[None, :] < need[:, None]] * 2, axis=1)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g)[live], w[live])
    mode = "interpret" if split_score._interpret() else "compiled"
    rows = [
        (f"split_score_2way_numpy_A{A}K{K}", us_np, "shared numpy kernel"),
        (f"split_score_2way_pallas_A{A}K{K}", us_pl,
         f"{mode} mode; identical floats on live lanes, "
         f"numpy_us={us_np:.0f}",
         {"vs_numpy": us_np / us_pl, "interpret": split_score._interpret(),
          "identical_live_lanes": True}),
    ]

    span = 12 if quick else 24
    o1, o2 = np.triu_indices(span - 1, k=1)
    Kp = o1.size
    dI = rng.uniform(0.0, 10.0, (A, 3, Kp))
    W3 = rng.uniform(0.1, 100.0, (A, 3, Kp))
    dO = rng.uniform(0.0, 10.0, (A, 3, Kp))
    invp = rng.uniform(0.05, 2.0, (A, 3))[:, np.asarray(_PERMS3)][:, :, :, None]
    base = rng.uniform(1.0, 50.0, (A, 1, 1))
    spans = rng.integers(3, span + 1, A)
    need3 = split_score.pair_need(spans, span)
    t0 = time.perf_counter()
    for _ in range(reps):
        want3 = score_3way_kernel(dI[:, None], W3[:, None], dO[:, None],
                                  invp, base, xp=np)
    us_np3 = (time.perf_counter() - t0) / reps * 1e6
    got3 = split_score.score_3way_pallas(dI[:, None], W3[:, None],
                                         dO[:, None], invp, base, need=need3)
    t0 = time.perf_counter()
    for _ in range(reps):
        got3 = split_score.score_3way_pallas(dI[:, None], W3[:, None],
                                             dO[:, None], invp, base,
                                             need=need3)
    us_pl3 = (time.perf_counter() - t0) / reps * 1e6
    live_l = o2[None, :] <= (spans - 2)[:, None]
    for g, w in zip(got3, want3):
        lv = (np.broadcast_to(live_l[:, None, None, :], w.shape)
              if w.ndim == 4 else np.broadcast_to(live_l[:, None, :], w.shape))
        assert np.array_equal(np.asarray(g)[lv], w[lv])
    rows += [
        (f"split_score_3way_numpy_A{A}span{span}", us_np3,
         "shared numpy kernel"),
        (f"split_score_3way_pallas_A{A}span{span}", us_pl3,
         f"{mode} mode; identical floats on live lanes, "
         f"numpy_us={us_np3:.0f}",
         {"vs_numpy": us_np3 / us_pl3, "interpret": split_score._interpret(),
          "identical_live_lanes": True}),
    ]
    return rows


def image_family_campaign(quick: bool = False) -> list:
    """The image-processing follow-up families (I1-I4: JPEG encoder profile,
    bimodal, correlated comm∝comp, uniform-wide) through the campaign
    engines, asserting byte-identical outputs across scalar/batched/fused."""
    if quick:
        points = ((10, 10),)
        kw = dict(n_pairs=4, n_bounds=4, h4_iters=4, include_h4=True)
    else:
        points = ((10, 10), (20, 100))
        kw = dict(n_pairs=50, n_bounds=12, h4_iters=10, include_h4=True)
    return _engine_comparison_rows(("I1", "I2", "I3", "I4"), points, kw,
                                   "image_family_")


def sharded_campaign(quick: bool = False) -> list:
    """The shard_map SPMD campaign engine (``backend="sharded"``) vs the
    fused single-device engine it wraps, bit-identity asserted both times.

    Two measurements:

    - ``campaign_sharded_1dev_*`` — in-process on the default mesh (usually
      one device): the degenerate mesh must stay bit-identical to fused and
      close to it in warm time.
    - ``campaign_sharded_8dev_*`` — a FRESH subprocess under
      ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the README
      "scaling out" recipe) timing the same campaign warm through both
      engines.  On forced host devices every shard shares the host's
      compute, so IDEAL 8-device scaling is elapsed time equal to the fused
      single-program time; ``scaling_efficiency = fused_warm / sharded_warm``
      is the fraction of that ideal the engine achieves — what it loses to
      SPMD overhead (batch padding, per-shard dispatch, per-shard while-loop
      divergence).  On real multi-chip hardware the same per-shard programs
      run concurrently, so efficiency e here reads as e x D throughput
      scaling.  ``bench_gate.py`` floors the 8-device efficiency at 0.6 and
      requires ``identical_outputs`` on both rows.
    """
    exps = ("E1", "E2", "E3", "E4")

    # in-process, default mesh; warm BOTH engines at this exact campaign
    # signature before timing (the grids campaign_speedup traced use
    # different pair/bound counts, so its cache entries don't apply)
    from repro.core import sharded as sharded_mod

    n1, p1 = 10, 10
    kw1 = dict(n_pairs=4, n_bounds=4, h4_iters=4, include_h4=True)
    run_campaign(exps, n1, p1, backend="fused", **kw1)     # cold: traces
    run_campaign(exps, n1, p1, backend="sharded", **kw1)   # cold: traces
    t0 = time.perf_counter()
    ref = run_campaign(exps, n1, p1, backend="fused", **kw1)
    us_f1 = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    shd = run_campaign(exps, n1, p1, backend="sharded", **kw1)
    us_s1 = (time.perf_counter() - t0) * 1e6
    for e in exps:
        assert summarize_experiment(ref[e]) == summarize_experiment(shd[e]), e
    d1 = sharded_mod.device_count()

    # fresh subprocess with 8 forced host devices; warm best-of-reps both
    # engines back to back so they see the same machine state
    n, p = 20, 100
    pairs, nb, iters, reps = (12, 8, 6, 3) if quick else (24, 8, 6, 5)
    child = (
        "import time\n"
        "import jax\n"
        "from repro.sim.experiments import run_campaign, summarize_experiment\n"
        f"exps = {exps!r}\n"
        f"kw = dict(n_pairs={pairs}, n_bounds={nb}, h4_iters={iters},\n"
        "          include_h4=True)\n"
        f"n, p = {n}, {p}\n"
        "run_campaign(exps, n, p, backend='fused', **kw)\n"
        "run_campaign(exps, n, p, backend='sharded', **kw)\n"
        "tf = ts = float('inf')\n"
        f"for _ in range({reps}):\n"
        "    t0 = time.perf_counter()\n"
        "    f = run_campaign(exps, n, p, backend='fused', **kw)\n"
        "    tf = min(tf, time.perf_counter() - t0)\n"
        "    t0 = time.perf_counter()\n"
        "    s = run_campaign(exps, n, p, backend='sharded', **kw)\n"
        "    ts = min(ts, time.perf_counter() - t0)\n"
        "ident = all(summarize_experiment(f[e]) == summarize_experiment(s[e])\n"
        "            for e in exps)\n"
        "print('DEVICES=%d' % len(jax.devices()))\n"
        "print('FUSED_US=%.0f' % (tf * 1e6))\n"
        "print('SHARDED_US=%.0f' % (ts * 1e6))\n"
        "print('IDENTICAL=%d' % ident)\n"
    )
    env = _cpu_child_env()
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    out = subprocess.run([sys.executable, "-c", child], capture_output=True,
                         text=True, env=env, check=True)
    vals = dict(line.split("=", 1) for line in out.stdout.splitlines()
                if "=" in line)
    devices = int(vals["DEVICES"])
    us_f8, us_s8 = float(vals["FUSED_US"]), float(vals["SHARDED_US"])
    identical = bool(int(vals["IDENTICAL"]))
    eff = us_f8 / us_s8
    return [
        (f"campaign_sharded_1dev_E1-E4_n{n1}p{p1}", us_s1,
         f"warm, {d1}-device mesh; fused_us={us_f1:.0f}, identical outputs",
         {"devices": d1, "fused_us": us_f1, "vs_fused": us_f1 / us_s1,
          "identical_outputs": True}),
        (f"campaign_sharded_8dev_E1-E4_n{n}p{p}", us_s8,
         f"warm, {devices} forced host devices; fused_us={us_f8:.0f}, "
         f"scaling_efficiency={eff:.3f} of ideal (shards share the host), "
         f"identical outputs",
         {"devices": devices, "fused_us": us_f8, "scaling_efficiency": eff,
          "identical_outputs": identical}),
    ]


def fused_h4_bisection(quick: bool = False) -> list:
    """The fused ``lax.scan`` H4 bisection (one dispatch per row-chunk for
    the WHOLE binary search) vs the host-driven probe loop it replaced
    (~iters+1 dispatches), identical outputs — dispatch counts recorded in
    ``derived`` so the O(1) contract is tracked across PRs."""
    from repro.core import batched, fused
    from repro.core.metrics import period, single_processor_mapping
    from repro.sim import gen_instance_batch

    n, p = (10, 10) if quick else (20, 100)
    B = 12 if quick else 48
    iters = 10
    batch = gen_instance_batch("E2", n, p, range(100, 100 + B))
    pb = batched._as_problem_batch(batch)
    fracs = np.tile([0.05, 0.2, 0.4, 0.6, 0.8, 1.0], B)[:B]
    bounds = np.array(
        [period(wl, pf, single_processor_mapping(wl, pf.fastest())) * f
         for (wl, pf), f in zip(batch, fracs)])
    lo, hi = batched.h4_search_bounds(pb)

    batched.batched_sp_bi_p(pb, bounds, iters=iters,
                            backend="fused")  # cold: traces
    fused.reset_dispatch_count()
    t0 = time.perf_counter()
    rs_scan = batched.batched_sp_bi_p(pb, bounds, iters=iters, backend="fused")
    us_scan = (time.perf_counter() - t0) * 1e6
    d_scan = fused.dispatch_count()

    fused.reset_dispatch_count()
    t0 = time.perf_counter()
    rs_loop = batched._sp_bi_p_rowwise(pb, bounds, iters, "fused",
                                       lo.copy(), hi.copy(), True)
    us_loop = (time.perf_counter() - t0) * 1e6
    d_loop = fused.dispatch_count()

    for a, b in zip(rs_scan, rs_loop):
        assert (a.mapping == b.mapping and a.period == b.period
                and a.latency == b.latency and a.feasible == b.feasible
                and a.splits == b.splits)
    assert d_loop >= 2 * d_scan, (d_loop, d_scan)
    return [
        (f"campaign_fused_h4scan_n{n}p{p}_B{B}", us_scan,
         f"dispatches={d_scan} vs {d_loop} probe-loop "
         f"({d_loop / d_scan:.0f}x fewer), identical outputs",
         {"dispatches": d_scan, "identical_outputs": True}),
        (f"campaign_fused_h4probe_loop_n{n}p{p}_B{B}", us_loop,
         f"PR-3 style host-driven bisection, dispatches={d_loop}",
         {"dispatches": d_loop}),
    ]


def deal_speedup(quick: bool = False) -> list:
    """Satellite before/after: the deal extension's candidate enumeration as
    per-mapping ``_deal_metrics`` Python loops vs the stacked-numpy
    ``_DealState.candidate_metrics`` batch, on identical enumerations."""
    from repro.core import Mapping
    from repro.core.deal import _DealState, _deal_metrics

    rng = np.random.default_rng(7)
    n, p = 24, 64
    wl = make_workload(rng.integers(1, 21, n).astype(float),
                       rng.integers(1, 51, n + 1).astype(float))
    pf = make_platform(rng.integers(1, 21, p).astype(float), 10.0)
    m = 8
    cuts = sorted(rng.choice(np.arange(2, n), size=m - 1, replace=False))
    iv, prev = [], 1
    for c in list(cuts) + [n]:
        iv.append((prev, int(c)))
        prev = int(c) + 1
    mapping = Mapping(tuple(iv), tuple(range(m)))
    free = list(range(m, p))
    st = _DealState(wl, pf, mapping)
    j = 0
    reps = 20 if quick else 200

    t0 = time.perf_counter()
    for _ in range(reps):
        loop = np.array([
            _deal_metrics(wl, pf, mapping,
                          [[u] if t != j else [u, cand]
                           for t, u in enumerate(mapping.alloc)])
            for cand in free])
    us_loop = (time.perf_counter() - t0) / reps * 1e6
    t0 = time.perf_counter()
    for _ in range(reps):
        batch = st.candidate_metrics(j, pf.s[np.asarray(free)])
    us_batch = (time.perf_counter() - t0) / reps * 1e6
    assert np.array_equal(loop, batch)
    k = len(free)
    return [
        (f"deal_enum_loop_{k}_candidates", us_loop,
         "per-candidate _deal_metrics Python loops"),
        (f"deal_enum_batched_{k}_candidates", us_batch,
         f"speedup={us_loop / us_batch:.1f}x, identical metrics"),
    ]


def run(quick: bool = False) -> list:
    # the in-process cold rows below must measure real trace+compile cost
    # every run (a warm cache would silently turn them into cache loads), so
    # the cache is placed as usual but turned off in this process; its
    # cross-process win is measured explicitly by fused_bucketed_cold_start
    import jax

    from repro.core.fused import enable_persistent_cache

    enable_persistent_cache()
    jax.config.update("jax_enable_compilation_cache", False)
    rows = timing(reps=2 if quick else 10)
    rows += vectorized_eval(reps=2 if quick else 5)
    rows += campaign_speedup(quick=quick)
    rows += fused_large_grid(quick=quick)
    rows += image_family_campaign(quick=quick)
    rows += sharded_campaign(quick=quick)
    rows += fused_h4_bisection(quick=quick)
    rows += fused_bucketed_cold_start(quick=quick)
    rows += split_score_pallas(quick=quick)
    rows += deal_speedup(quick=quick)
    gaps = optimality_gaps(n_inst=4 if quick else 20)
    for c, g in gaps.items():
        # quality-only rows: no us_per_call, the gap lives in `derived`
        rows.append((f"gap_vs_exact_{c}", None, f"gap={g:.4f}", {"gap": g}))
    return rows


def write_bench_json(rows, path: pathlib.Path = BENCH_JSON,
                     mode: str = "full") -> None:
    """Persist benchmark rows as {name: {us_per_call, derived, ...}} JSON.

    Rows are (name, us, derived) or (name, us, derived, extra): ``extra`` is
    a dict of STRUCTURED fields (numeric speedups, dispatch counts, cache
    deltas) merged into the row object — ``benchmarks/bench_gate.py`` reads
    those, so regressions fail CI on numbers, not string parsing.
    ``_meta.mode`` records quick vs full so cross-PR comparisons never mix
    the two (they use different reps/instance counts under the same names).
    """
    payload = {}
    for row in rows:
        name, us, derived = row[0], row[1], row[2]
        entry = {"us_per_call": us, "derived": derived}
        if len(row) > 3 and row[3]:
            entry.update(row[3])
        payload[name] = entry
    payload["_meta"] = {"mode": mode}
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")


def format_row(name, us, derived, extra=None) -> str:
    return f"{name},{'' if us is None else f'{us:.1f}'},{derived}"


def main() -> None:
    quick = "--quick" in sys.argv
    rows = run(quick=quick)
    for row in rows:
        print(format_row(*row))
    write_bench_json(rows, mode="quick" if quick else "full")
    print(f"# wrote {BENCH_JSON}")


if __name__ == "__main__":
    main()
