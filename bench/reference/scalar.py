"""Plain scalar reference of the paper's six heuristics (Section 4).

A copy of the scalar engine of ``src/repro/core/heuristics.py`` (its numpy
fast paths, the 2-stage generator fallback of the 3-way split, the splitting
loop, H1-H6, the min-period portfolio and the split trajectories) and of the
Eq. (1)/(2) metrics of ``src/repro/core/metrics.py``.  It imports nothing of
the program: an instance is ``(w, delta)`` and a platform ``(s, b)``, plain
numpy arrays.  A later change to the program cannot move it.

``dtype`` sets the precision of every array the heuristics compute with.
``float64`` is the configuration's precision; ``float32`` is the control,
the nearest precision below it, which the comparison must reject.

Processors are 0-indexed, intervals 1-indexed inclusive ``(d, e)`` as in the
paper.  A result is ``Result(intervals, alloc, period, latency, feasible)``.
"""

from __future__ import annotations

import itertools
import math
from typing import NamedTuple, Optional

import numpy as np

EPS = 1e-12


class Instance:
    """One (workload, platform) pair: ``w (n,)``, ``delta (n+1,)``, speeds
    ``s (p,)`` and bandwidth ``b``, all held in ``dtype``."""

    def __init__(self, w, delta, s, b, dtype=np.float64):
        self.w = np.asarray(w, dtype=dtype)
        self.delta = np.asarray(delta, dtype=dtype)
        self.s = np.asarray(s, dtype=dtype)
        self.b = dtype(b) if dtype is not np.float64 else float(b)
        self.n = len(self.w)
        self.p = len(self.s)
        self.prefix = np.concatenate([np.zeros(1, dtype=dtype),
                                      np.cumsum(self.w)])

    def sorted_indices(self) -> np.ndarray:
        """Processors by non-increasing speed, ties by index."""
        return np.lexsort((np.arange(self.p), -self.s))

    def fastest(self) -> int:
        return int(self.sorted_indices()[0])


class Result(NamedTuple):
    intervals: Optional[tuple]
    alloc: Optional[tuple]
    period: float
    latency: float
    feasible: bool


def failure() -> Result:
    return Result(None, None, math.inf, math.inf, False)


# ---------------------------------------------------------------------------
# Eq. (1) and (2) over a mapping
# ---------------------------------------------------------------------------

def interval_cycle_times(inst: Instance, intervals, alloc) -> np.ndarray:
    w, delta, b = inst.w, inst.delta, inst.b
    sp = inst.s[np.asarray(alloc, dtype=np.int64)]
    out = np.empty(len(intervals), dtype=w.dtype)
    for j, (d, e) in enumerate(intervals):
        out[j] = delta[d - 1] / b + w[d - 1:e].sum() / sp[j] + delta[e] / b
    return out


def period(inst: Instance, intervals, alloc) -> float:
    return float(interval_cycle_times(inst, intervals, alloc).max())


def latency(inst: Instance, intervals, alloc) -> float:
    w, delta, b = inst.w, inst.delta, inst.b
    sp = inst.s[np.asarray(alloc, dtype=np.int64)]
    tot = 0.0
    for j, (d, e) in enumerate(intervals):
        tot += delta[d - 1] / b + w[d - 1:e].sum() / sp[j]
    return float(tot + delta[inst.n] / b)


def single_processor(inst: Instance) -> tuple:
    return ((1, inst.n),), (inst.fastest(),)


def optimal_latency(inst: Instance) -> float:
    """Lemma 1: the whole chain on the fastest processor."""
    return latency(inst, *single_processor(inst))


# ---------------------------------------------------------------------------
# Splitting state
# ---------------------------------------------------------------------------

class _State:
    def __init__(self, inst: Instance):
        self.inst = inst
        self.order = inst.sorted_indices()
        self.next_idx = 1
        fastest = int(self.order[0])
        self.items = [[1, inst.n, fastest]]
        t0 = self.latency_term(1, inst.n, fastest)
        self._cycles = [self.cycle(1, inst.n, fastest)]
        self._lat_terms = [t0]
        self._lat_sum = t0
        self._tail = inst.delta[inst.n] / inst.b

    def interval_w(self, d, e):
        return self.inst.prefix[e] - self.inst.prefix[d - 1]

    def cycle(self, d, e, proc):
        i = self.inst
        return (i.delta[d - 1] / i.b + self.interval_w(d, e) / i.s[proc]
                + i.delta[e] / i.b)

    def latency_term(self, d, e, proc):
        i = self.inst
        return i.delta[d - 1] / i.b + self.interval_w(d, e) / i.s[proc]

    def period(self) -> float:
        return float(max(self._cycles))

    def latency(self) -> float:
        return float(self._lat_sum + self._tail)

    def worst_index(self) -> int:
        return self._cycles.index(max(self._cycles))

    def peek_procs(self, k):
        if self.next_idx + k > len(self.order):
            return None
        return [int(self.order[self.next_idx + i]) for i in range(k)]

    def replace(self, idx, parts):
        self.items[idx:idx + 1] = [list(p) for p in parts]
        new_terms = [self.latency_term(d, e, u) for d, e, u in parts]
        new_cycles = [self.cycle(d, e, u) for d, e, u in parts]
        add = 0.0
        for t in new_terms:
            add += t
        self._lat_sum = self._lat_sum - self._lat_terms[idx] + add
        self._lat_terms[idx:idx + 1] = new_terms
        self._cycles[idx:idx + 1] = new_cycles

    def result(self, feasible: bool) -> Result:
        return Result(tuple((d, e) for d, e, _ in self.items),
                      tuple(u for _, _, u in self.items),
                      self.period(), self.latency(), feasible)


# ---------------------------------------------------------------------------
# Candidate choice
# ---------------------------------------------------------------------------

def _three_way_pairs(st, idx, jp, jpp):
    """The 2-stage fallback of a 3-way split: 2-way splits over ordered
    pairs of {j, jp, jpp}, in ``itertools.permutations`` order."""
    d, e, j = st.items[idx]
    base = st.latency_term(d, e, j)
    for pa, pb in itertools.permutations((j, jp, jpp), 2):
        parts = [(d, d, pa), (d + 1, e, pb)]
        cyc = [st.cycle(*p) for p in parts]
        dlat = sum(st.latency_term(*p) for p in parts) - base
        yield parts, cyc, dlat


def _pick(candidates, mode, old_cycle, lat_limit, cur_lat):
    best, best_key = None, None
    for parts, cyc, dlat in candidates:
        mx = max(cyc)
        if mx >= old_cycle - EPS:
            continue
        if cur_lat + dlat > lat_limit + EPS:
            continue
        if mode == "mono":
            key = (mx, dlat, parts[0][1])
        else:
            ratio = max(dlat / max(old_cycle - c, EPS) for c in cyc)
            key = (ratio, mx, parts[0][1])
        if best_key is None or key < best_key:
            best, best_key = (parts, cyc, dlat), key
    return best


def _best_2way(st, idx, jp, mode, old_cycle, lat_limit, cur_lat):
    d, e, j = st.items[idx]
    if e == d:
        return None
    inst = st.inst
    pre, delta, b, s = inst.prefix, inst.delta, inst.b, inst.s
    C = np.arange(d, e)
    W1 = pre[C] - pre[d - 1]
    W2 = pre[e] - pre[C]
    dIn, dMid, dOut = delta[d - 1] / b, delta[C] / b, delta[e] / b
    inv_j, inv_p = 1.0 / s[j], 1.0 / s[jp]
    cyc1 = np.concatenate([dIn + W1 * inv_j + dMid, dIn + W1 * inv_p + dMid])
    cyc2 = np.concatenate([dMid + W2 * inv_p + dOut, dMid + W2 * inv_j + dOut])
    dlat = np.concatenate([dMid + W2 * (inv_p - inv_j),
                           dMid + W1 * (inv_p - inv_j)])
    cuts = np.concatenate([C, C])
    order = np.concatenate([np.zeros(len(C)), np.ones(len(C))])
    mx = np.maximum(cyc1, cyc2)
    okay = (mx < old_cycle - EPS) & (cur_lat + dlat <= lat_limit + EPS)
    if not okay.any():
        return None
    ix = np.nonzero(okay)[0]
    if mode == "mono":
        keys = (mx[ix], dlat[ix], cuts[ix], order[ix])
    else:
        den1 = np.maximum(old_cycle - cyc1[ix], EPS)
        den2 = np.maximum(old_cycle - cyc2[ix], EPS)
        keys = (np.maximum(dlat[ix] / den1, dlat[ix] / den2), mx[ix],
                cuts[ix], order[ix])
    best = ix[np.lexsort(keys[::-1])[0]]
    c = int(cuts[best])
    if order[best] == 0:
        parts = [(d, c, j), (c + 1, e, jp)]
    else:
        parts = [(d, c, jp), (c + 1, e, j)]
    return parts, [float(cyc1[best]), float(cyc2[best])], float(dlat[best])


_PERMS3 = [(0, 1, 2), (0, 2, 1), (1, 0, 2), (1, 2, 0), (2, 0, 1), (2, 1, 0)]


def _best_3way(st, idx, jp, jpp, mode, old_cycle, lat_limit, cur_lat):
    d, e, j = st.items[idx]
    if e - d + 1 < 3:
        return _pick(_three_way_pairs(st, idx, jp, jpp), mode, old_cycle,
                     lat_limit, cur_lat)
    inst = st.inst
    pre, delta, b, s = inst.prefix, inst.delta, inst.b, inst.s
    procs = np.array([j, jp, jpp])
    inv = 1.0 / s[procs]
    c1, c2 = np.meshgrid(np.arange(d, e - 1), np.arange(d + 1, e),
                         indexing="ij")
    valid = c2 > c1
    c1, c2 = c1[valid], c2[valid]
    W = np.stack([pre[c1] - pre[d - 1], pre[c2] - pre[c1], pre[e] - pre[c2]])
    dI = np.stack([np.full_like(c1, delta[d - 1], dtype=delta.dtype),
                   delta[c1], delta[c2]]) / b
    dO = np.stack([delta[c1], delta[c2],
                   np.full_like(c1, delta[e], dtype=delta.dtype)]) / b
    base = delta[d - 1] / b + (pre[e] - pre[d - 1]) / s[j]
    best_choice, best_key = None, None
    for pi, perm in enumerate(_PERMS3):
        invp = inv[list(perm)][:, None]
        comp = dI + W * invp
        cyc = comp + dO
        dlat = (comp[0] + comp[1] + comp[2]) - base
        mx = cyc.max(axis=0)
        okay = (mx < old_cycle - EPS) & (cur_lat + dlat <= lat_limit + EPS)
        if not okay.any():
            continue
        ix = np.nonzero(okay)[0]
        if mode == "mono":
            keys = (mx[ix], dlat[ix], c1[ix].astype(float),
                    c2[ix].astype(float))
        else:
            ratio = (dlat[ix] / np.maximum(old_cycle - cyc[:, ix], EPS)
                     ).max(axis=0)
            keys = (ratio, mx[ix], c1[ix].astype(float), c2[ix].astype(float))
        first = np.lexsort(keys[::-1])[0]
        o = ix[first]
        key = tuple(float(k[first]) for k in keys) + (pi,)
        if best_key is None or key < best_key:
            u = [procs[q] for q in perm]
            spans = [(d, int(c1[o])), (int(c1[o]) + 1, int(c2[o])),
                     (int(c2[o]) + 1, e)]
            parts = [(a, z, int(uu)) for (a, z), uu in zip(spans, u)]
            best_choice = (parts, [float(v) for v in cyc[:, o]],
                           float(dlat[o]))
            best_key = key
    return best_choice


def _splitting_loop(st, k, mode, stop=-math.inf, lat_limit=math.inf,
                    on_split=None) -> int:
    """Split the worst interval with the next ``k`` fastest unused
    processors until the period is at most ``stop`` or no split helps."""
    splits = 0
    while True:
        if st.period() <= stop + EPS:
            break
        idx = st.worst_index()
        d, e, j = st.items[idx]
        if e == d:
            break
        new = st.peek_procs(k)
        if new is None:
            break
        old_cycle = st.cycle(d, e, j)
        cur_lat = st.latency()
        if k == 1:
            choice = _best_2way(st, idx, new[0], mode, old_cycle, lat_limit,
                                cur_lat)
        else:
            choice = _best_3way(st, idx, new[0], new[1], mode, old_cycle,
                                lat_limit, cur_lat)
        if choice is None:
            break
        parts = choice[0]
        st.replace(idx, parts)
        used = {u for _, _, u in parts} - {j}
        st.next_idx += k if len(used) == k else len(used)
        splits += 1
        if on_split is not None:
            on_split(st)
    return splits


# ---------------------------------------------------------------------------
# H1-H6
# ---------------------------------------------------------------------------

def fixed_period(inst: Instance, k: int, mode: str, p_fix: float) -> Result:
    """H1 (k=1, mono), H2 (k=2, mono), H3 (k=2, bi)."""
    st = _State(inst)
    _splitting_loop(st, k, mode, stop=p_fix)
    return st.result(st.period() <= p_fix + EPS)


def _bi_under_latency(inst, p_fix, lat_limit) -> Result:
    st = _State(inst)
    _splitting_loop(st, 1, "bi", stop=p_fix, lat_limit=lat_limit)
    return st.result(st.period() <= p_fix + EPS
                     and st.latency() <= lat_limit + EPS)


def sp_bi_p(inst: Instance, p_fix: float, iters: int) -> Result:
    """H4: binary search over the authorized latency."""
    lat_opt = _State(inst).latency()
    s_min = float(inst.s.min())
    lat_ub = float(inst.delta[:-1].sum() / inst.b + float(inst.w.sum()) / s_min
                   + inst.delta[-1] / inst.b)
    lo, hi = lat_opt, max(lat_ub, lat_opt)
    probe = _bi_under_latency(inst, p_fix, hi)
    if not probe.feasible:
        return probe._replace(feasible=False)
    best = probe
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        probe = _bi_under_latency(inst, p_fix, mid)
        if probe.feasible:
            hi = mid
            if probe.latency < best.latency - EPS or (
                    abs(probe.latency - best.latency) <= EPS
                    and probe.period < best.period):
                best = probe
        else:
            lo = mid
    return best._replace(feasible=True)


def fixed_latency(inst: Instance, mode: str, l_fix: float) -> Result:
    """H5 (mono) and H6 (bi): minimize the period under ``l_fix``."""
    st = _State(inst)
    if st.latency() > l_fix + EPS:
        return failure()
    _splitting_loop(st, 1, mode, lat_limit=l_fix)
    return st.result(True)


def min_period_exhaustive(inst: Instance) -> Result:
    """The unbounded min-period portfolio: the four splitting strategies run
    to exhaustion, the lexicographically best (period, latency, strategy
    order) wins."""
    runs = (fixed_latency(inst, "mono", math.inf),
            fixed_latency(inst, "bi", math.inf),
            fixed_period(inst, 2, "mono", -math.inf),
            fixed_period(inst, 2, "bi", -math.inf))
    best = min(range(4), key=lambda i: (runs[i].period, runs[i].latency, i))
    return runs[best]._replace(feasible=True)


TRAJECTORY_STRATEGY = {"H1": (1, "mono"), "H2": (2, "mono"),
                       "H3": (2, "bi"), "H4": (1, "bi")}


def split_trajectory(code: str, inst: Instance) -> list:
    """(period, latency) after 0, 1, 2, ... accepted splits of the
    exhaustion run of a fixed-period heuristic (H4: its inner splitter)."""
    k, mode = TRAJECTORY_STRATEGY[code]
    st = _State(inst)
    traj = [(st.period(), st.latency())]
    _splitting_loop(st, k, mode,
                    on_split=lambda s: traj.append((s.period(), s.latency())))
    return traj
