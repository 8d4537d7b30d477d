"""Plain reference of a Section-5 campaign: one instance at a time.

A copy of the scalar path of ``src/repro/sim/experiments.py``
(``run_experiment(engine="scalar")``: trajectories for H1-H4, the H4 binary
search per feasible bound, H5/H6 per bound with the mapping's metrics, then
the per-family means) and of its ``summarize_experiment`` text, over the
heuristics of :mod:`bench.reference.scalar`.  Imports nothing of the program.

A campaign result is ``{family: {"curves": {code: (mean_period,
mean_latency, feasible_frac)}, "thresholds": {code: (mean, max)},
"n_pairs": ...}}`` with numpy arrays of length ``n_bounds``.
"""

from __future__ import annotations

import math

import numpy as np

from bench.reference import scalar


def _from_trajectory(traj, p_fix):
    for per, lat in traj:
        if per <= p_fix + 1e-12:
            return per, lat
    return None


def run(instances_by_family: dict, n_bounds: int, h4_iters: int,
        include_h4: bool = True, dtype=np.float64) -> dict:
    """``instances_by_family`` maps a family to its list of ``(w, delta, s,
    b)`` tuples, in seed order."""
    period_fracs = np.geomspace(0.04, 1.0, n_bounds)
    latency_mults = np.linspace(1.0, 3.0, n_bounds)
    codes_p = ["H1", "H2", "H3"] + (["H4"] if include_h4 else [])
    out = {}
    for fam, rows in instances_by_family.items():
        acc = {c: [[] for _ in range(n_bounds)] for c in codes_p + ["H5", "H6"]}
        thr = {c: [] for c in acc}
        for w, delta, s, b in rows:
            inst = scalar.Instance(w, delta, s, b, dtype=dtype)
            hi = scalar.period(inst, *scalar.single_processor(inst))
            l_opt = scalar.optimal_latency(inst)
            pgrid = hi * period_fracs
            lgrid = l_opt * latency_mults
            trajs = {c: scalar.split_trajectory(c, inst) for c in codes_p}
            for c in ("H1", "H2", "H3"):
                thr[c].append(min(per for per, _ in trajs[c]))
                for bi, pb in enumerate(pgrid):
                    r = _from_trajectory(trajs[c], pb)
                    if r is not None:
                        acc[c][bi].append(r)
            if include_h4:
                thr["H4"].append(min(per for per, _ in trajs["H4"]))
                for bi, pb in enumerate(pgrid):
                    if _from_trajectory(trajs["H4"], pb) is None:
                        continue
                    r = scalar.sp_bi_p(inst, pb, h4_iters)
                    if r.feasible:
                        acc["H4"][bi].append((r.period, r.latency))
            for c, mode in (("H5", "mono"), ("H6", "bi")):
                thr[c].append(l_opt)
                for bi, lb in enumerate(lgrid):
                    r = scalar.fixed_latency(inst, mode, float(lb))
                    if r.intervals is None:
                        continue
                    per = scalar.period(inst, r.intervals, r.alloc)
                    lat = scalar.latency(inst, r.intervals, r.alloc)
                    if (math.isfinite(per) and math.isfinite(lat)
                            and lat <= float(lb) + 1e-12):
                        acc[c][bi].append((per, lat))
        n_pairs = len(rows)
        curves = {}
        for c, cols in acc.items():
            curves[c] = (
                np.array([np.mean([a for a, _ in col]) if col else np.nan
                          for col in cols]),
                np.array([np.mean([z for _, z in col]) if col else np.nan
                          for col in cols]),
                np.array([len(col) / n_pairs for col in cols]))
        out[fam] = {"n_pairs": n_pairs, "curves": curves,
                    "thresholds": {c: (float(np.mean(v)), float(np.max(v)))
                                   for c, v in thr.items()}}
    return out


def summary(fam: str, n: int, p: int, res: dict) -> str:
    """The ``summarize_experiment`` text of one family."""
    lines = [f"# {fam} n={n} p={p} pairs={res['n_pairs']}",
             "heuristic,bound_idx,mean_period,mean_latency,feasible_frac"]
    for c, (mp, ml, fr) in sorted(res["curves"].items()):
        for i in range(len(mp)):
            lines.append(f"{c},{i},{mp[i]:.6g},{ml[i]:.6g},{fr[i]:.3f}")
    lines.append("heuristic,threshold_mean,threshold_max")
    for c, (m, mx) in sorted(res["thresholds"].items()):
        lines.append(f"{c},{m:.6g},{mx:.6g}")
    return "\n".join(lines)
