"""Campaign cells: Section-5 campaigns back to back through
``repro.sim.experiments.run_campaign(..., backend="fused")``.

The traffic file names a fixed pool of campaigns; every run deals it in an
order shuffled from its seed, so every seed does the same work.  Set-up
checks that the program generates the same instances as the benchmark's copy
of the generators for every campaign of the pool, then runs one campaign of
one pair per family from seeds outside the pool, which reaches every program
the window uses (both split arities at the cell's (n, p); the shapes do not
depend on the row count).  The window starts campaigns back to back until
``--seconds`` have passed and ends when the last one it started ends.  A
traced run profiles the window's first campaign.

The check draws one campaign of the window from the seed and compares it
with the plain reference (``bench/reference/campaign.py``): every
``summarize_experiment`` row and every float of the curves and thresholds,
exactly.
"""

from __future__ import annotations

import time

import numpy as np

from bench import trace as tracing
from bench.reference import campaign as reference
from bench.traffic import generators


def traffic_ok(traffic: dict) -> bool:
    """Whether a traffic file gives what a campaign cell deals: at least one
    family and a pool of at least one campaign."""
    return (bool(traffic.get("families"))
            and traffic.get("pool_campaigns", 0) > 0)


def _instances(cfg, families, pairs, seed0):
    return {f: [generators.gen_instance(cfg["families"][f], cfg["n"],
                                        cfg["p"], cfg["speeds"], seed0 + k)
                + (cfg["b"],) for k in range(pairs)] for f in families}


def _yardstick_differences(cfg, families, pairs, seed0) -> int:
    """Instances of one campaign where the program's generator and the
    benchmark's copy disagree: a moved yardstick."""
    from repro.sim.generators import gen_instance_batch

    ours = _instances(cfg, families, pairs, seed0)
    bad = 0
    for f in families:
        theirs = gen_instance_batch(f, cfg["n"], cfg["p"],
                                    [seed0 + k for k in range(pairs)])
        for k, (w, delta, s, b) in enumerate(ours[f]):
            same = (np.array_equal(theirs.w[k], w)
                    and np.array_equal(theirs.delta[k], delta)
                    and np.array_equal(theirs.s[k], s) and theirs.b == b)
            bad += not same
    return bad


class State:
    def __init__(self, cell, seed):
        self.cfg, self.traffic = cell.config, cell.traffic
        self.families = list(self.traffic["families"])
        self.pairs = int(self.traffic["pairs_per_family"])
        self.seed = seed
        self.seeds = generators.campaign_seeds(self.traffic, seed)
        self.results = []
        self.seed0s = []
        self.yardstick = 0

    def campaign(self, seed0, pairs=None):
        from repro.sim import experiments

        cfg = self.cfg
        return experiments.run_campaign(
            self.families, cfg["n"], cfg["p"],
            n_pairs=self.pairs if pairs is None else pairs,
            n_bounds=cfg["n_bounds"], seed0=seed0, h4_iters=cfg["h4_iters"],
            include_h4=cfg["include_h4"], backend="fused")


def setup(cell, seed):
    st = State(cell, seed)
    st.yardstick = sum(_yardstick_differences(st.cfg, st.families, st.pairs,
                                              seed0)
                       for seed0 in generators.pool_seeds(st.traffic))
    print(f"[setup] instances of the pool that differ from the benchmark's "
          f"generator: {st.yardstick}", flush=True)
    st.campaign(generators.warmup_seed(st.traffic), pairs=1)
    return st


def window(st, seconds, tracer):
    from repro.core import fused

    fused.reset_dispatch_count()
    fused.reset_trace_count()
    times = []
    traced_dispatches = None
    t0 = time.perf_counter()
    end = t0
    while end - t0 < seconds:
        seed0 = next(st.seeds)
        traced = tracer is not None and not times
        if traced:
            d0 = fused.dispatch_count()
            tracer.start()
        c0 = time.perf_counter()
        with tracing.span("bench.campaign"):
            res = st.campaign(seed0)
        end = time.perf_counter()
        if traced:
            tracer.stop()
            traced_dispatches = fused.dispatch_count() - d0
        times.append(end - c0)
        st.results.append(res)
        st.seed0s.append(seed0)
    dec = fused.decision_counts()
    rows = st.pairs * len(st.families)
    print(f"[window] campaigns={len(times)} rows_per_campaign={rows} "
          f"campaign_s={[round(t, 4) for t in times]} "
          f"median_campaign_s={float(np.median(times))!r} "
          f"dispatches={fused.dispatch_count()} traces={fused.trace_count()} "
          f"decided_on_device={dec['device']} decided_on_host={dec['host']}",
          flush=True)
    return {"window_s": end - t0, "campaigns": len(times),
            "instances": len(times) * rows, "attempted": len(times) * rows,
            "failed": 0, "dispatches": fused.dispatch_count(),
            "decided_on_host": dec["host"], "decided_on_device": dec["device"],
            "traced_dispatches": traced_dispatches}


def program_result(res: dict) -> dict:
    """A ``run_campaign`` result in the reference's plain form."""
    return {f: {"n_pairs": r.n_pairs,
                "curves": {c: tuple(np.asarray(a) for a in v)
                           for c, v in r.curves.items()},
                "thresholds": {c: tuple(v) for c, v in r.thresholds.items()}}
            for f, r in res.items()}


def compare(cfg, got: dict, want: dict) -> tuple:
    """(summary rows that differ, floats that differ) over every family."""
    rows = floats = 0
    for f in want:
        g, w = got.get(f), want[f]
        sw = reference.summary(f, cfg["n"], cfg["p"], w).splitlines()
        if g is None:
            rows += len(sw)
            floats += sum(a.size for v in w["curves"].values() for a in v)
            continue
        sg = reference.summary(f, cfg["n"], cfg["p"], g).splitlines()
        rows += sum(a != b for a, b in zip(sg, sw)) + abs(len(sg) - len(sw))
        for c, wv in w["curves"].items():
            gv = g["curves"].get(c)
            for k, wa in enumerate(wv):
                ga = (np.asarray(gv[k], dtype=float) if gv is not None
                      else np.full(wa.shape, np.inf))
                if ga.shape != wa.shape:
                    floats += wa.size
                    continue
                floats += int(np.sum(~((ga == wa)
                                       | (np.isnan(ga) & np.isnan(wa)))))
        for c, wt in w["thresholds"].items():
            gt = g["thresholds"].get(c, (np.inf, np.inf))
            floats += sum(a != b for a, b in zip(gt, wt))
    return rows, floats


def check(st, record, dtype=np.float64):
    """Compare one campaign of the window, drawn from the seed, with the
    plain reference.  ``dtype`` is the reference's precision."""
    n = len(st.results)
    j = int(np.random.default_rng([st.seed, 1]).integers(n)) if n else 0
    t0 = time.perf_counter()
    rows = floats = 0
    if n:
        want = reference.run(_instances(st.cfg, st.families, st.pairs,
                                        st.seed0s[j]),
                             st.cfg["n_bounds"], st.cfg["h4_iters"],
                             st.cfg["include_h4"], dtype=dtype)
        rows, floats = compare(st.cfg, program_result(st.results[j]), want)
    print(f"[check] campaign {j} of {n} (seed0={st.seed0s[j] if n else None})"
          f" against the plain reference in {time.perf_counter() - t0!r} s",
          flush=True)
    return [("yardstick_instances_differing", st.yardstick, 0),
            ("campaigns_checked_missing", int(n == 0), 0),
            ("summary_rows_differing", rows, 0),
            ("floats_differing", floats, 0)]
