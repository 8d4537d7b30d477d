"""Batched campaign engine: the paper's heuristics over stacked instances.

The Section-5 simulation study evaluates six heuristics on hundreds of random
(workload, platform) pairs.  The scalar path (:mod:`repro.core.heuristics`)
runs each pair through a Python while-loop, so a campaign is dominated by
interpreter overhead.  This module runs B homogeneously-shaped problems in
*lockstep* with structure-of-arrays state — one numpy (or JAX) call evaluates
a whole batch of worst-interval selections, split scorings, and state updates
per iteration, with per-problem masks tracking convergence.

Equivalence contract: with the numpy backend every float this engine produces
is **bit-for-bit identical** to the per-instance path (asserted by
tests/test_batched.py).  That holds because both paths evaluate candidates
through the shared kernels ``score_2way_kernel``/``score_3way_kernel`` of
:mod:`repro.core.heuristics` and apply state updates with the same elementwise
expressions in the same order.

Public surface:

  - :func:`stack_instances` / :class:`ProblemBatch` — SoA instance stacking
  - :func:`batched_trajectories` — H1-H4 exhaustion trajectories (the sweep
    primitive of ``repro.sim.experiments``)
  - :func:`batched_fixed_latency` — H5/H6 over a per-problem bound grid in
    one lockstep pass
  - :func:`batched_sp_bi_p` — H4 whose binary search probes all B problems
    per bisection step

Backends: ``backend="numpy"`` (default, bit-exact), ``backend="jax"``
(scoring kernels under ``jax.jit`` with x64 enabled), ``backend="pallas"``
(scoring through the masked-tile ``pl.pallas_call`` kernels of
:mod:`repro.kernels.split_score` — interpret mode on CPU, compiled on
TPU/GPU), ``backend="fused"`` (the ENTIRE lockstep loop as one jitted
``lax.while_loop`` — :mod:`repro.core.fused` — with span-bucketed candidate
grids and O(1) host dispatches per heuristic arity), or ``backend="sharded"``
(the fused loop as one ``shard_map`` SPMD program with the instance axis
sharded across every device — :mod:`repro.core.sharded`).  All jit backends carry
the kernels' runtime-zero FMA guard, so their split trajectories AND floats
match the numpy reference exactly on all tested instances; numpy remains the
contractual bit-exact reference.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional, Sequence

import numpy as np

from .heuristics import (_EPS, _PERMS3, HeuristicResult, _pick_bi, _pick_mono,
                         _three_way_candidates, score_2way_kernel,
                         score_3way_kernel, score_kernels)
from .metrics import Mapping

__all__ = [
    "ProblemBatch", "stack_instances", "batched_trajectories",
    "batched_trajectory_sets", "batched_fixed_latency", "batched_min_period",
    "batched_sp_bi_p", "h4_search_bounds",
]


# ---------------------------------------------------------------------------
# Problem stacking
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProblemBatch:
    """B equally-shaped problems as stacked arrays (one row per problem).

    ``prefix`` is the stage-work prefix sum (``Workload.prefix_w`` per row)
    and ``order`` the speed-sorted processor indices
    (``Platform.sorted_indices`` per row) — precomputed once per campaign.
    """

    w: np.ndarray        # (B, n)
    delta: np.ndarray    # (B, n+1)
    s: np.ndarray        # (B, p)
    b: float
    prefix: np.ndarray   # (B, n+1)
    order: np.ndarray    # (B, p) int

    @property
    def B(self) -> int:
        return self.w.shape[0]

    @property
    def n(self) -> int:
        return self.w.shape[1]

    @property
    def p(self) -> int:
        return self.s.shape[1]

    def take(self, rows) -> "ProblemBatch":
        """Sub-batch of the given rows (with repetition allowed — used to tile
        instances across a bound grid)."""
        rows = np.asarray(rows)
        return ProblemBatch(self.w[rows], self.delta[rows], self.s[rows],
                            self.b, self.prefix[rows], self.order[rows])

    def packed(self) -> np.ndarray:
        """[delta | prefix | s] concatenated per row (cached): lets the hot
        loops fetch several per-interval quantities in one fancy-index."""
        cached = getattr(self, "_packed", None)
        if cached is None:
            cached = np.concatenate([self.delta, self.prefix, self.s], axis=1)
            object.__setattr__(self, "_packed", cached)
        return cached

    @classmethod
    def from_arrays(cls, w, delta, s, b: float) -> "ProblemBatch":
        """Build a batch straight from stacked arrays — the entry point for
        callers that already hold heterogeneous platform rows (e.g. the fleet
        service's observed per-pod speeds) and should not have to materialize
        B Workload/Platform objects just to stack them again.  ``prefix`` and
        ``order`` are derived exactly like ``Workload.prefix_w`` /
        ``Platform.sorted_indices`` so downstream results stay bit-identical
        to the object path."""
        w = np.asarray(w, dtype=np.float64)
        delta = np.asarray(delta, dtype=np.float64)
        s = np.asarray(s, dtype=np.float64)
        if w.ndim != 2 or s.ndim != 2 or s.shape[0] != w.shape[0]:
            raise ValueError(f"need 2-D stacked rows, got w{w.shape} s{s.shape}")
        if delta.shape != (w.shape[0], w.shape[1] + 1):
            raise ValueError(f"need delta shape (B, n+1), got {delta.shape}")
        B = w.shape[0]
        prefix = np.concatenate([np.zeros((B, 1)), np.cumsum(w, axis=1)], axis=1)
        order = np.lexsort((np.broadcast_to(np.arange(s.shape[1]), s.shape), -s),
                           axis=-1)
        return cls(w=w, delta=delta, s=s, b=float(b), prefix=prefix, order=order)

    @classmethod
    def concat(cls, batches: Sequence) -> "ProblemBatch":
        """Stack several same-shape batches (ProblemBatch or any batch-like
        with the same array attributes) row-wise into one ProblemBatch."""
        pbs = [_as_problem_batch(b) for b in batches]
        if not pbs:
            raise ValueError("empty batch list")
        if len(pbs) == 1:
            return pbs[0]
        first = pbs[0]
        for pb in pbs[1:]:
            if pb.n != first.n or pb.p != first.p or pb.b != first.b:
                raise ValueError("all batches must share n, p, and b")
        return cls(
            w=np.concatenate([pb.w for pb in pbs]),
            delta=np.concatenate([pb.delta for pb in pbs]),
            s=np.concatenate([pb.s for pb in pbs]),
            b=first.b,
            prefix=np.concatenate([pb.prefix for pb in pbs]),
            order=np.concatenate([pb.order for pb in pbs]),
        )


def stack_instances(pairs: Sequence) -> ProblemBatch:
    """Stack (Workload, Platform) pairs of identical shape into a ProblemBatch."""
    if not len(pairs):
        raise ValueError("empty batch")
    n = pairs[0][0].n
    p = pairs[0][1].p
    b = float(pairs[0][1].b)
    for wl, pf in pairs:
        if wl.n != n or pf.p != p or float(pf.b) != b:
            raise ValueError("all instances in a batch must share n, p, and b")
    return ProblemBatch(
        w=np.stack([wl.w for wl, _ in pairs]),
        delta=np.stack([wl.delta for wl, _ in pairs]),
        s=np.stack([pf.s for _, pf in pairs]),
        b=b,
        prefix=np.stack([wl.prefix_w() for wl, _ in pairs]),
        order=np.stack([pf.sorted_indices() for _, pf in pairs]),
    )


def _as_problem_batch(batch) -> ProblemBatch:
    if isinstance(batch, ProblemBatch):
        return batch
    if hasattr(batch, "w") and hasattr(batch, "prefix") and hasattr(batch, "order"):
        return ProblemBatch(batch.w, batch.delta, batch.s, float(batch.b),
                            batch.prefix, batch.order)
    return stack_instances(list(batch))


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------

class _Backend:
    """Kernel-implementation backend for the lockstep loop: resolves the
    shared scoring kernels through ``heuristics.score_kernels`` ("numpy",
    "jax", or "pallas" — the Pallas kernels are span-aware: the hot loops
    hand them each row's live-lane bound so masked tiles skip compute)."""

    def __init__(self, name: str):
        self.name = name
        if name not in ("numpy", "jax", "pallas"):
            raise ValueError(f"unknown backend {name!r}; use 'numpy', 'jax', "
                             "'pallas', 'fused', or 'sharded'")
        self.score2, self.score3 = score_kernels(name)
        self.span_aware = name == "pallas"


_BACKENDS: dict = {}


def _get_backend(name: str) -> _Backend:
    if name not in _BACKENDS:
        _BACKENDS[name] = _Backend(name)
    return _BACKENDS[name]


# ---------------------------------------------------------------------------
# Lockstep splitting state
# ---------------------------------------------------------------------------

class _BatchState:
    """SoA mirror of ``heuristics._State`` across B problems.

    Items (1-indexed intervals + processor) live in chain order in a padded
    (B, n, 5) float array ``arr`` together with each item's cycle time and
    latency term (padding cycle -inf); ``m`` counts valid items per row.
    The metric fields are maintained incrementally exactly like the scalar
    state's ``_cycles``/``_lat_terms``; d/e/proc are small integers, exactly
    represented in float64.
    """

    # arr field layout: 0=d, 1=e, 2=proc (exactly-represented ints), 3=cycle,
    # 4=latency term.  ``packed`` concatenates [delta | prefix | s] per row so
    # the hot paths fetch several per-interval quantities in ONE fancy-index.
    F_D, F_E, F_U, F_CYC, F_TERM = range(5)

    def __init__(self, pb: ProblemBatch, active: Optional[np.ndarray] = None):
        B, n = pb.B, pb.n
        self.pb = pb
        self.packed = pb.packed()
        self.off_pre = n + 1
        self.off_s = 2 * (n + 1)
        rows = np.arange(B)
        fastest = pb.order[:, 0]
        self.arr = np.zeros((B, n, 5))
        self.arr[:, :, self.F_CYC] = -np.inf
        term0 = pb.delta[:, 0] / pb.b + (pb.prefix[:, n] - pb.prefix[:, 0]) / pb.s[rows, fastest]
        self.tail = pb.delta[:, n] / pb.b
        self.arr[:, 0, self.F_D] = 1
        self.arr[:, 0, self.F_E] = n
        self.arr[:, 0, self.F_U] = fastest
        self.arr[:, 0, self.F_CYC] = term0 + self.tail
        self.arr[:, 0, self.F_TERM] = term0
        self.m = np.ones(B, dtype=np.int64)
        self.next_idx = np.ones(B, dtype=np.int64)
        self.lat_sum = term0.copy()
        self.active = np.ones(B, dtype=bool) if active is None else active.copy()
        self.splits = np.zeros(B, dtype=np.int64)

    def period(self) -> np.ndarray:
        return self.arr[:, :, self.F_CYC].max(axis=1)

    def latency(self) -> np.ndarray:
        return self.lat_sum + self.tail

    def items_int(self, i: int) -> np.ndarray:
        """(m, 3) int items of row i: (d, e, proc) in chain order."""
        return self.arr[i, : int(self.m[i]), :3].astype(np.int64)

    def mapping(self, i: int) -> Mapping:
        items = self.items_int(i)
        return Mapping(intervals=tuple((int(d), int(e)) for d, e, _ in items),
                       alloc=tuple(int(u) for _, _, u in items))


def _mapping_from_rows(items_row, m: int) -> Mapping:
    return Mapping(intervals=tuple((int(items_row[t, 0]), int(items_row[t, 1]))
                                   for t in range(m)),
                   alloc=tuple(int(items_row[t, 2]) for t in range(m)))


# ---------------------------------------------------------------------------
# Batched candidate selection
# ---------------------------------------------------------------------------

def _lex_argmin(keys: Sequence[np.ndarray], mask: np.ndarray):
    """Per-row index of the lexicographically smallest key tuple among masked
    candidates — the batched equivalent of the scalar paths'
    ``lexsort(keys[::-1])[0]``.  Returns (choice_index (A,), has_any (A,))."""
    has = mask.any(axis=1)
    n_has = np.count_nonzero(has)
    m = mask.copy()
    for i, key in enumerate(keys):
        key = np.broadcast_to(key, m.shape)
        kmin = np.where(m, key, np.inf).min(axis=1)
        m &= key == kmin[:, None]
        # later keys only break ties; stop once every row is decided (each
        # has-row keeps >= 1 candidate, so total count == n_has means unique)
        if i + 1 < len(keys) and np.count_nonzero(m) == n_has:
            break
    return np.argmax(m, axis=1), has


def _split_by_span(spans: np.ndarray) -> Optional[np.ndarray]:
    """When one row's interval is much wider than the median, lane-compacted
    scoring wastes (max_span - span) lanes on every other row.  Returns a
    boolean 'small rows' partition mask (process the two groups separately),
    or None when partitioning isn't worth the extra call."""
    if spans.size < 16:
        return None
    med = int(np.median(spans))
    if int(spans.max()) < 2 * med:
        return None
    small = spans <= med
    if not small.any() or small.all():
        return None
    return small


def _merge_choices(small, outs_small, outs_large):
    merged = []
    for a, b in zip(outs_small, outs_large):
        full_shape = (small.size,) + a.shape[1:]
        m = np.empty(full_shape, dtype=a.dtype)
        m[small] = a
        m[~small] = b
        merged.append(m)
    return tuple(merged)


def _choose_2way(state, rows, d, e, j, jp, bi_mode, old_cycle, cur_lat, lat_lim, be):
    """Best 2-way split per row, or none.  Mirrors ``_best_split_2way_fast``.

    Cut lanes are compacted to the current *maximum* interval span across the
    rows (cut c = d + offset): spans shrink geometrically as splitting
    proceeds, so later lockstep iterations touch far fewer lanes than a
    global 1..n-1 grid would.  Invalid lanes are masked; key values use the
    absolute cut position so selection is identical to the scalar path.
    (Unlike the 3-way pair grid, lane count is only linear in the span here,
    so span-skew partitioning would cost more in extra calls than it saves.)
    """
    pb = state.pb
    n = pb.n
    A = rows.size
    rowc = rows[:, None]
    K = int((e - d).max())                       # lanes: cuts d .. d+K-1
    c_abs = d[:, None] + np.arange(K)[None, :]
    valid = c_abs < e[:, None]
    c_idx = np.minimum(c_abs, n - 1)             # in-range gather for masked lanes
    # interval-end quantities via ONE packed gather
    gidx = np.empty((A, 6), dtype=np.int64)
    gidx[:, 0] = state.off_pre + (d - 1)         # prefix[d-1]
    gidx[:, 1] = state.off_pre + e               # prefix[e]
    gidx[:, 2] = d - 1                           # delta[d-1]
    gidx[:, 3] = e                               # delta[e]
    gidx[:, 4] = state.off_s + j                 # s[j]
    gidx[:, 5] = state.off_s + jp                # s[jp]
    g = state.packed[rowc, gidx]
    cidx2 = np.empty((A, 2, K), dtype=np.int64)
    cidx2[:, 0] = state.off_pre + c_idx          # prefix[c]
    cidx2[:, 1] = c_idx                          # delta[c]
    gc = state.packed[rows[:, None, None], cidx2]
    # span-aware kernels (pallas) take each row's live-cut count so tiles
    # beyond every row's span skip compute
    kw = {"need": e - d} if be.span_aware else {}
    cyc1, cyc2, dlat = be.score2(
        g[:, 0][:, None], gc[:, 0], g[:, 1][:, None],
        g[:, 2][:, None], gc[:, 1], g[:, 3][:, None],
        pb.b, (1.0 / g[:, 4])[:, None], (1.0 / g[:, 5])[:, None], **kw)
    if be.name != "numpy":
        cyc1, cyc2, dlat = np.asarray(cyc1), np.asarray(cyc2), np.asarray(dlat)
    mx = np.maximum(cyc1, cyc2)
    okay = (mx < old_cycle[:, None] - _EPS)
    okay &= (cur_lat[:, None] + dlat <= lat_lim[:, None] + _EPS)
    okay &= np.concatenate([valid, valid], axis=1)
    # (cut, placement-order) tie-break as ONE exactly-represented integer key
    cutorder = np.concatenate([c_abs * 2, c_abs * 2 + 1], axis=1).astype(float)
    any_bi = bool(bi_mode.any())
    if not any_bi:
        keys = [mx, dlat, cutorder]
    else:
        den1 = np.maximum(old_cycle[:, None] - cyc1, _EPS)
        den2 = np.maximum(old_cycle[:, None] - cyc2, _EPS)
        ratio = np.maximum(dlat / den1, dlat / den2)
        if bi_mode.all():
            keys = [ratio, mx, cutorder]
        else:
            # mixed batch: per-row key columns (each row sees exactly the
            # key tuple its own mode would use)
            bc = bi_mode[:, None]
            keys = [np.where(bc, ratio, mx), np.where(bc, mx, dlat), cutorder]
    q, has = _lex_argmin(keys, okay)
    c = d + (q % K)
    swapped = q >= K
    pa = np.where(swapped, jp, j)
    pb2 = np.where(swapped, j, jp)
    return has, c, pa, pb2


@functools.lru_cache(maxsize=None)
def _offset_pair_grid(span: int):
    """All cut-offset pairs 0 <= o1 < o2 <= span-2 as flat (K,) int arrays
    (cut c_i = d + o_i for an interval of ``span`` stages starting at d)."""
    i, jj = np.triu_indices(span - 1, k=1)
    return i, jj


_PERM_ARR = np.array(_PERMS3)          # (6, 3)


def _choose_3way(state, rows, d, e, j, jp, jpp, bi_mode, old_cycle, cur_lat, lat_lim, be):
    """Best 3-way split per row (all >= 3-stage worst intervals).  Mirrors
    ``_best_split_3way_fast``: per-perm scoring via the shared kernel, global
    lexmin over (keys..., perm index).  Like ``_choose_2way``, cut-pair lanes
    are compacted to the rows' maximum interval span and span-skewed batches
    are partitioned (the pair grid grows quadratically in the span)."""
    small = _split_by_span(e - d + 1)
    if small is not None:
        lg = ~small
        return _merge_choices(
            small,
            _choose_3way(state, rows[small], d[small], e[small], j[small],
                         jp[small], jpp[small], bi_mode[small],
                         old_cycle[small], cur_lat[small], lat_lim[small], be),
            _choose_3way(state, rows[lg], d[lg], e[lg], j[lg], jp[lg], jpp[lg],
                         bi_mode[lg], old_cycle[lg], cur_lat[lg],
                         lat_lim[lg], be))
    A = rows.size
    span_max = int((e - d + 1).max())
    K_est = (span_max - 1) * (span_max - 2) // 2
    # The scoring arrays are (rows, 6 perms, 3 parts, K pairs): chunk rows so
    # the working set stays cache-sized — on wide intervals the batch would
    # otherwise lose to memory bandwidth what it wins in call overhead.
    if A > 16 and A * K_est > 30_000:
        step = max(16, 30_000 // max(K_est, 1))
        outs = [_choose_3way(state, rows[i:i + step], d[i:i + step],
                             e[i:i + step], j[i:i + step], jp[i:i + step],
                             jpp[i:i + step], bi_mode[i:i + step],
                             old_cycle[i:i + step], cur_lat[i:i + step],
                             lat_lim[i:i + step], be)
                for i in range(0, A, step)]
        return tuple(np.concatenate([o[f] for o in outs]) for f in range(4))
    pb = state.pb
    n = pb.n
    o1g, o2g = _offset_pair_grid(span_max)
    K = o1g.size
    c1 = d[:, None] + o1g[None, :]
    c2 = d[:, None] + o2g[None, :]
    valid = c2 <= (e - 1)[:, None]
    c1i = np.minimum(c1, n - 1)
    c2i = np.minimum(c2, n - 1)
    gidx = np.empty((A, 7), dtype=np.int64)
    gidx[:, 0] = state.off_pre + (d - 1)         # prefix[d-1]
    gidx[:, 1] = state.off_pre + e               # prefix[e]
    gidx[:, 2] = d - 1                           # delta[d-1]
    gidx[:, 3] = e                               # delta[e]
    gidx[:, 4] = state.off_s + j                 # s[j]
    gidx[:, 5] = state.off_s + jp                # s[jp]
    gidx[:, 6] = state.off_s + jpp               # s[jpp]
    g = state.packed[rows[:, None], gidx]
    cidx = np.empty((A, 4, K), dtype=np.int64)
    cidx[:, 0] = state.off_pre + c1i             # prefix[c1]
    cidx[:, 1] = state.off_pre + c2i             # prefix[c2]
    cidx[:, 2] = c1i                             # delta[c1]
    cidx[:, 3] = c2i                             # delta[c2]
    gc = state.packed[rows[:, None, None], cidx]
    pre_d1 = g[:, 0][:, None]
    pre_e = g[:, 1][:, None]
    pre_c1, pre_c2, delta_c1, delta_c2 = gc[:, 0], gc[:, 1], gc[:, 2], gc[:, 3]
    W = np.stack([pre_c1 - pre_d1, pre_c2 - pre_c1, pre_e - pre_c2], axis=1)   # (A, 3, K)
    dI = np.stack([np.broadcast_to(g[:, 2][:, None], (A, K)), delta_c1, delta_c2], axis=1) / pb.b
    dO = np.stack([delta_c1, delta_c2, np.broadcast_to(g[:, 3][:, None], (A, K))], axis=1) / pb.b
    procs = np.stack([j, jp, jpp], axis=1)                                     # (A, 3)
    inv = 1.0 / g[:, 4:7]
    base_term = (g[:, 2] / pb.b + (g[:, 1] - g[:, 0]) / g[:, 4])[:, None, None]
    # all 6 permutations in one kernel call: perm axis 1, parts axis 2
    invp = inv[:, _PERM_ARR][:, :, :, None]                                    # (A, 6, 3, 1)
    if be.span_aware:
        from ..kernels.split_score import pair_need

        # per-row last-valid-lane bound of the r1-major pair layout, so the
        # pallas kernel's out-of-band tiles skip compute
        kw = {"need": pair_need(e - d + 1, span_max)}
    else:
        kw = {}
    cyc, dlat, mx = be.score3(dI[:, None], W[:, None], dO[:, None], invp,
                              base_term, **kw)
    if be.name != "numpy":
        cyc, dlat, mx = np.asarray(cyc), np.asarray(dlat), np.asarray(mx)
    any_bi = bool(bi_mode.any())
    ratio_all = None
    if any_bi:
        ratio_all = (dlat[:, :, None, :]
                     / np.maximum(old_cycle[:, None, None, None] - cyc, _EPS)).max(axis=2)
    mx_f = mx.reshape(A, 6 * K)
    dlat_f = dlat.reshape(A, 6 * K)
    okay = mx_f < old_cycle[:, None] - _EPS
    okay &= cur_lat[:, None] + dlat_f <= lat_lim[:, None] + _EPS
    okay &= np.broadcast_to(valid[:, None, :], (A, 6, K)).reshape(A, 6 * K)
    # (c1, c2, perm index) tie-break as ONE exactly-represented integer key,
    # matching the scalar path's per-perm (.., c1, c2) lexsort + cross-perm
    # (keys..., pi) comparison.
    ccp = ((c1 * (n + 1) + c2)[:, None, :] * 6
           + np.arange(6)[None, :, None]).astype(float).reshape(A, 6 * K)
    if not any_bi:
        keys = [mx_f, dlat_f, ccp]
    elif bi_mode.all():
        keys = [ratio_all.reshape(A, 6 * K), mx_f, ccp]
    else:
        bc = bi_mode[:, None]
        ratio_f = ratio_all.reshape(A, 6 * K)
        keys = [np.where(bc, ratio_f, mx_f), np.where(bc, mx_f, dlat_f), ccp]
    q, has = _lex_argmin(keys, okay)
    pi = q // K
    kk = q % K
    c1b = d + o1g[kk]
    c2b = d + o2g[kk]
    u_parts = np.take_along_axis(procs, _PERM_ARR[pi], axis=1)                 # (A, 3)
    return has, c1b, c2b, u_parts


class _RowView:
    """Minimal scalar-state shim over one batch row, so the 2-stage 3-way
    fallback reuses ``_three_way_candidates``/``_pick_*`` verbatim."""

    __slots__ = ("pre", "delta", "s", "b", "items")

    def __init__(self, pre, delta, s, b, d, e, j):
        self.pre, self.delta, self.s, self.b = pre, delta, s, b
        self.items = [[d, e, j]]

    def cycle(self, d, e, u):
        return self.delta[d - 1] / self.b + (self.pre[e] - self.pre[d - 1]) / self.s[u] + self.delta[e] / self.b

    def latency_term(self, d, e, u):
        return self.delta[d - 1] / self.b + (self.pre[e] - self.pre[d - 1]) / self.s[u]


# ---------------------------------------------------------------------------
# Lockstep loop
# ---------------------------------------------------------------------------

def _apply_splits(state: _BatchState, rows, idx, pd, pe, pu, nparts, consumed):
    """Replace item ``idx`` of each row with its 2 or 3 parts: shift the item
    arrays, scatter the parts, and update cycle/term/lat_sum incrementally
    with the same division-based expressions as the scalar ``replace``."""
    pb = state.pb
    n = pb.n
    R = rows.size
    arR = np.arange(R)
    rowc = rows[:, None]
    # per-part latency terms and cycles via ONE packed gather (lane 2 is
    # garbage for 2-part rows — indices are in-range and never scattered)
    gidx = np.empty((R, 3, 5), dtype=np.int64)
    gidx[:, :, 0] = pd - 1                       # delta[pd-1]
    gidx[:, :, 1] = state.off_pre + pe           # prefix[pe]
    gidx[:, :, 2] = state.off_pre + (pd - 1)     # prefix[pd-1]
    gidx[:, :, 3] = state.off_s + pu             # s[pu]
    gidx[:, :, 4] = pe                           # delta[pe]
    g = state.packed[rows[:, None, None], gidx]
    t_parts = g[:, :, 0] / pb.b + (g[:, :, 1] - g[:, :, 2]) / g[:, :, 3]
    c_parts = t_parts + g[:, :, 4] / pb.b
    old_term = state.arr[rows, idx, state.F_TERM]
    add = t_parts[:, 0] + t_parts[:, 1]
    three = nparts == 3
    add = np.where(three, add + t_parts[:, 2], add)
    new_lat = (state.lat_sum[rows] - old_term) + add
    sh = (nparts - 1)[:, None]
    # the shift only touches the first max(m)+2 item columns — the rest is
    # padding on every row and stays put
    mm = min(n, int(state.m[rows].max()) + 2)
    col = np.arange(mm)[None, :]
    idxc = idx[:, None]
    src = np.where(col <= idxc, col, np.where(col <= idxc + sh, idxc, col - sh))
    parts = np.empty((R, 3, 5))
    parts[:, :, state.F_D] = pd
    parts[:, :, state.F_E] = pe
    parts[:, :, state.F_U] = pu
    parts[:, :, state.F_CYC] = c_parts
    parts[:, :, state.F_TERM] = t_parts
    sub = state.arr[rowc, src]
    sub[arR, idx] = parts[:, 0]
    sub[arR, idx + 1] = parts[:, 1]
    if three.any():
        sub[arR[three], idx[three] + 2] = parts[three, 2]
    state.arr[rowc, col] = sub
    state.m[rows] += nparts - 1
    state.next_idx[rows] += consumed
    state.splits[rows] += 1
    state.lat_sum[rows] = new_lat


def _run_loop(state: _BatchState, k: int, bi_mode: np.ndarray, stop: np.ndarray,
              lat_limit: np.ndarray, backend: str = "numpy",
              record: Optional[Callable] = None) -> None:
    """The paper's splitting loop in lockstep: mirrors ``_splitting_loop``
    per row (stop-bound check, worst interval, candidate choice, update),
    deactivating rows as they converge.  ``bi_mode`` selects each row's
    candidate-choice rule (False = mono-criterion, True = bi-criteria), so
    heuristics sharing a split arity run together in one pass.
    ``record(rows, periods, latencies)`` is invoked after each lockstep apply
    with the rows that accepted a split.

    ``backend="fused"`` hands the whole loop to the device-resident traced
    engine (:mod:`repro.core.fused`): one jitted ``lax.while_loop`` executes
    every iteration on-device and this function returns after a single
    dispatch per row-chunk, instead of O(iterations) host round-trips.
    ``backend="sharded"`` runs the same traced loop as one ``shard_map``
    SPMD program with the row axis sharded across every device
    (:mod:`repro.core.sharded`).
    """
    if backend == "fused":
        from . import fused

        fused.run_fused(state, k, np.asarray(bi_mode, dtype=bool),
                        np.asarray(stop, dtype=float),
                        np.asarray(lat_limit, dtype=float), record)
        return
    if backend == "sharded":
        from . import sharded

        sharded.run_sharded(state, k, np.asarray(bi_mode, dtype=bool),
                            np.asarray(stop, dtype=float),
                            np.asarray(lat_limit, dtype=float), record)
        return
    _numpy_loop(state, np.nonzero(state.active)[0], k, bi_mode, stop,
                lat_limit, backend, record)


def _numpy_loop(state: _BatchState, rows: np.ndarray, k: int,
                bi_mode: np.ndarray, stop: np.ndarray, lat_limit: np.ndarray,
                backend: str = "numpy", record: Optional[Callable] = None,
                max_iters: Optional[int] = None) -> None:
    """The host lockstep loop of :func:`_run_loop` over the active ``rows``,
    for at most ``max_iters`` iterations (rows still splitting after them
    stay active)."""
    pb = state.pb
    be = _get_backend(backend)
    it = 0
    while rows.size and (max_iters is None or it < max_iters):
        it += 1
        # 1. natural stop: period bound already satisfied.  Only the first
        # max(m) item columns are live (cycle padding is -inf beyond).
        mm = int(state.m[rows].max())
        cyc_sub = state.arr[rows, :mm, state.F_CYC]
        per = cyc_sub.max(axis=1)
        keep = per > stop[rows] + _EPS
        if not keep.all():
            state.active[rows[~keep]] = False
            rows = rows[keep]
            cyc_sub = cyc_sub[keep]
            if rows.size == 0:
                break
        # 2./3. worst interval must be splittable and processors available
        widx = np.argmax(cyc_sub, axis=1)
        worst = state.arr[rows, widx, :3].astype(np.int64)   # (R, 3): d, e, proc
        d, e, j = worst[:, 0], worst[:, 1], worst[:, 2]
        ok = (e > d) & (state.next_idx[rows] + k <= pb.p)
        if not ok.all():
            state.active[rows[~ok]] = False
            sel = np.nonzero(ok)[0]
            rows, widx, d, e, j = rows[sel], widx[sel], d[sel], e[sel], j[sel]
            cyc_sub = cyc_sub[sel]
            if rows.size == 0:
                continue
        old_cycle = cyc_sub[np.arange(rows.size), widx]
        cur_lat = state.lat_sum[rows] + state.tail[rows]
        lat_lim = lat_limit[rows]
        jp = pb.order[rows, state.next_idx[rows]]
        R = rows.size
        # all three part lanes are written (or the row is filtered by `has`)
        # before any use, so uninitialized memory is fine here
        pd = np.empty((R, 3), dtype=np.int64)
        pe = np.empty((R, 3), dtype=np.int64)
        pu = np.empty((R, 3), dtype=np.int64)
        nparts = np.full(R, 2, dtype=np.int64)
        consumed = np.ones(R, dtype=np.int64)
        if k == 1:
            has, c, pa, pb2 = _choose_2way(state, rows, d, e, j, jp,
                                           bi_mode[rows], old_cycle, cur_lat,
                                           lat_lim, be)
            pd[:, 0], pe[:, 0], pu[:, 0] = d, c, pa
            pd[:, 1], pe[:, 1], pu[:, 1] = c + 1, e, pb2
            pd[:, 2], pe[:, 2], pu[:, 2] = c + 1, e, pb2       # in-range filler
        else:
            jpp = pb.order[rows, state.next_idx[rows] + 1]
            has = np.zeros(R, dtype=bool)
            big = e - d + 1 >= 3
            if big.any():
                bi = np.nonzero(big)[0]
                hb, c1, c2, u_parts = _choose_3way(
                    state, rows[bi], d[bi], e[bi], j[bi], jp[bi], jpp[bi],
                    bi_mode[rows[bi]], old_cycle[bi], cur_lat[bi],
                    lat_lim[bi], be)
                has[bi] = hb
                pd[bi, 0], pe[bi, 0] = d[bi], c1
                pd[bi, 1], pe[bi, 1] = c1 + 1, c2
                pd[bi, 2], pe[bi, 2] = c2 + 1, e[bi]
                pu[bi] = u_parts
                nparts[bi] = 3
                consumed[bi] = 2
            # 2-stage worst interval: the scalar fast path falls back to the
            # readable generator; do exactly that, row by row (rare + tiny).
            for t in np.nonzero(~big)[0]:
                i = rows[t]
                view = _RowView(pb.prefix[i], pb.delta[i], pb.s[i], pb.b,
                                int(d[t]), int(e[t]), int(j[t]))
                pick = _pick_bi if bi_mode[i] else _pick_mono
                choice = pick(_three_way_candidates(view, 0, int(jp[t]), int(jpp[t])),
                              float(old_cycle[t]), float(lat_lim[t]), float(cur_lat[t]))
                if choice is None:
                    continue
                parts, _, _ = choice
                has[t] = True
                for q, (pd_, pe_, pu_) in enumerate(parts):
                    pd[t, q], pe[t, q], pu[t, q] = pd_, pe_, pu_
                pd[t, 2], pe[t, 2], pu[t, 2] = pd[t, 1], pe[t, 1], pu[t, 1]
                nparts[t] = len(parts)
                used = {pu_ for _, _, pu_ in parts} - {int(j[t])}
                consumed[t] = k if len(used) == k else len(used)
        # 4. rows with no improving candidate are done
        if not has.all():
            state.active[rows[~has]] = False
            sel = np.nonzero(has)[0]
            rows, widx = rows[sel], widx[sel]
            pd, pe, pu = pd[sel], pe[sel], pu[sel]
            nparts, consumed = nparts[sel], consumed[sel]
            if rows.size == 0:
                continue
        # 5. apply accepted splits
        _apply_splits(state, rows, widx, pd, pe, pu, nparts, consumed)
        if record is not None:
            record(rows, state.arr[rows, :int(state.m[rows].max()), state.F_CYC].max(axis=1),
                   state.lat_sum[rows] + state.tail[rows])


# ---------------------------------------------------------------------------
# Public engine API
# ---------------------------------------------------------------------------

_TRAJ_CONFIG = {"H1": ("mono", 1), "H2": ("mono", 2), "H3": ("bi", 2), "H4": ("bi", 1)}


def batched_trajectories(code: str, batch, backend: str = "numpy") -> list:
    """Per-problem (period, latency) exhaustion trajectories — the batched
    ``split_trajectory`` (see its docstring for why one run covers every
    period bound).  Returns a list of B trajectories."""
    if code not in _TRAJ_CONFIG:
        raise KeyError(f"trajectories are for fixed-period heuristics, not {code}")
    return batched_trajectory_sets([code], batch, backend)[code]


def batched_trajectory_sets(codes, batch, backend: str = "numpy") -> dict:
    """Trajectories for several heuristic codes in as few lockstep runs as
    possible: codes sharing a split arity (H1+H4 2-way, H2+H3 3-way) run
    TOGETHER as extra batch rows distinguished only by their per-row choice
    mode.  Returns {code: [trajectory per problem]}."""
    pb = _as_problem_batch(batch)
    B = pb.B
    out = {}
    by_k: dict = {}
    for code in codes:
        mode, k = _TRAJ_CONFIG[code]
        by_k.setdefault(k, []).append((code, mode))
    for k, group in by_k.items():
        tiled = pb if len(group) == 1 else pb.take(np.tile(np.arange(B), len(group)))
        bi_mode = np.concatenate([np.full(B, mode == "bi") for _, mode in group])
        st = _BatchState(tiled)
        trajs = [[(float(p), float(l))] for p, l in zip(st.period(), st.latency())]

        def rec(rows, pers, lats):
            for i, p, l in zip(rows, pers, lats):
                trajs[i].append((float(p), float(l)))

        _run_loop(st, k, bi_mode, np.full(tiled.B, -np.inf),
                  np.full(tiled.B, np.inf), backend, record=rec)
        for gi, (code, _) in enumerate(group):
            out[code] = trajs[gi * B:(gi + 1) * B]
    return out


_FIXED_LAT = {"H5": ("mono", "Sp mono L"), "H6": ("bi", "Sp bi L")}


def _fixed_latency_state(code: str, pb: ProblemBatch, bounds: np.ndarray,
                         backend: str):
    """Run the H5/H6 splitting loop; returns (state, initially_failed mask)."""
    bi_mode = np.full(pb.B, _FIXED_LAT[code][0] == "bi")
    st = _BatchState(pb)
    failed = st.latency() > bounds + _EPS
    st.active[failed] = False
    _run_loop(st, 1, bi_mode, np.full(pb.B, -np.inf), bounds, backend)
    return st, failed


def batched_fixed_latency(code: str, batch, bounds, backend: str = "numpy") -> list:
    """H5/H6 (min period s.t. latency <= bound) for B problems at once, each
    with its own bound — a whole (instance x bound-grid) campaign in one
    lockstep pass.  Returns per-problem HeuristicResults identical to
    ``sp_mono_l``/``sp_bi_l``."""
    pb = _as_problem_batch(batch)
    bounds = np.asarray(bounds, dtype=float)
    name = _FIXED_LAT[code][1]
    st, failed = _fixed_latency_state(code, pb, bounds, backend)
    per, lat = st.period(), st.latency()
    return [HeuristicResult.failure(name) if failed[i]
            else HeuristicResult(st.mapping(i), float(per[i]), float(lat[i]),
                                 True, int(st.splits[i]), name)
            for i in range(pb.B)]


# Strategy order mirrors heuristics.min_period_exhaustive: (name, arity, bi)
_MIN_PERIOD_STRATEGIES = (
    ("Sp mono L", 1, False),
    ("Sp bi L", 1, True),
    ("3-Explo mono", 2, False),
    ("3-Explo bi", 2, True),
)


def batched_min_period(batch, backend: str = "numpy") -> list:
    """Unbounded min-period portfolio for B problems at once — the batched
    ``heuristics.min_period_exhaustive``.

    Two lockstep runs cover all four exhaustion strategies: each run tiles the
    batch x2 with per-row choice mode (mono rows then bi rows), one run per
    split arity.  The per-problem winner is the lexicographically smallest
    (period, latency, strategy order), with the same strict float comparisons
    as the scalar tuple-min — so every returned float and mapping is
    bit-identical to the scalar portfolio (asserted in tests/test_fleet.py).
    This is the fleet replanning service's solve primitive.
    """
    pb = _as_problem_batch(batch)
    B = pb.B
    rows2 = np.tile(np.arange(B), 2)
    bi_mode = np.concatenate([np.zeros(B, dtype=bool), np.ones(B, dtype=bool)])
    states = []
    for k in (1, 2):
        st = _BatchState(pb.take(rows2))
        _run_loop(st, k, bi_mode, np.full(2 * B, -np.inf),
                  np.full(2 * B, np.inf), backend)
        states.append(st)
    st1, st2 = states
    per1, lat1 = st1.period(), st1.latency()
    per2, lat2 = st2.period(), st2.latency()
    per = np.stack([per1[:B], per1[B:], per2[:B], per2[B:]])   # (4, B)
    lat = np.stack([lat1[:B], lat1[B:], lat2[:B], lat2[B:]])
    strat = np.broadcast_to(np.arange(4)[:, None], per.shape)
    win = np.lexsort((strat, lat, per), axis=0)[0]
    out = []
    for i in range(B):
        wi = int(win[i])
        st = st1 if wi < 2 else st2
        row = i + (wi % 2) * B
        out.append(HeuristicResult(st.mapping(row), float(per[wi, i]),
                                   float(lat[wi, i]), True,
                                   int(st.splits[row]),
                                   _MIN_PERIOD_STRATEGIES[wi][0]))
    return out


def evaluate_state_rows(workloads, platforms, state: "_BatchState",
                        skip=None) -> np.ndarray:
    """(period, latency) of each row's final mapping through the *metrics*
    layer — bit-identical to ``metrics.evaluate(wl, pf, mapping)`` per row
    (same per-interval expressions, including the ``w[d-1:e].sum()`` reduction
    evaluate uses), but without materializing Mapping objects, computing each
    interval's work sum once instead of twice, and reusing the previous row's
    result when it holds the same instance and final mapping (bound grids
    produce long runs of identical outcomes).  Rows with ``skip`` set are
    left as NaN.  Returns (B, 2)."""
    B = state.pb.B
    out = np.full((B, 2), np.nan)
    prev = -1
    for i in range(B):
        if skip is not None and skip[i]:
            continue
        m = int(state.m[i])
        if (prev >= 0 and workloads[i] is workloads[prev]
                and platforms[i] is platforms[prev]
                and int(state.m[prev]) == m
                and np.array_equal(state.arr[i, :m, :3], state.arr[prev, :m, :3])):
            out[i] = out[prev]
            prev = i
            continue
        items = state.items_int(i)
        wl, pf = workloads[i], platforms[i]
        w, delta, b, s = wl.w, wl.delta, pf.b, pf.s
        per = -math.inf
        tot = 0.0
        for t in range(m):
            d, e, a = items[t]
            lat_term = delta[d - 1] / b + w[d - 1:e].sum() / s[a]
            cyc = lat_term + delta[e] / b
            if cyc > per:
                per = cyc
            tot += lat_term
        out[i, 0] = per
        out[i, 1] = tot + delta[wl.n] / b
        prev = i
    return out


def h4_search_bounds(pb: ProblemBatch, groups=None) -> tuple:
    """Initial (lo, hi) authorized-latency bounds of the H4 binary search:
    lo = the optimal latency (all-on-fastest), hi = every stage its own
    interval on the slowest processor — the exact per-row mirror of
    ``sp_bi_p``'s scalar formulas.  Rows sharing a ``groups`` key (same
    instance tiled across a bound grid) compute the bound once.  Shared by
    every bisection flavor (host probe loops, the fused scan, benchmarks),
    so they all provably search the same interval."""
    B = pb.B
    lat_opt = _BatchState(pb).latency()
    if groups is None:
        groups = np.arange(B)
    groups = np.asarray(groups)
    lat_ub = np.empty(B)
    seen: dict = {}
    for i in range(B):            # scalar formulas per row (once per instance)
        gkey = int(groups[i])
        if gkey in seen:
            lat_ub[i] = lat_ub[seen[gkey]]
            continue
        seen[gkey] = i
        s_min = float(pb.s[i].min())
        lat_ub[i] = float(pb.delta[i, :-1].sum() / pb.b
                          + pb.w[i].sum() / s_min
                          + pb.delta[i, -1] / pb.b)
    return lat_opt, np.maximum(lat_ub, lat_opt)


def batched_sp_bi_p(batch, bounds, iters: int = 40, backend: str = "numpy",
                    with_mappings: bool = True, groups=None) -> list:
    """H4 'Sp bi P' for B problems at once: ONE binary search whose every
    bisection step probes all still-searching problems in lockstep, instead
    of B independent searches.  Identical results to ``sp_bi_p``.
    ``with_mappings=False`` skips Mapping materialization (metrics-only
    campaigns).  ``groups`` (optional, metrics-only) marks rows that share an
    instance — probe runs are then deduplicated across each instance's period
    bounds (see ``_sp_bi_p_grouped``)."""
    pb = _as_problem_batch(batch)
    p_fix = np.asarray(bounds, dtype=float)
    B = pb.B
    if groups is None:
        groups = np.arange(B)
    groups = np.asarray(groups)
    lo, hi = h4_search_bounds(pb, groups)
    from . import fused

    if (backend in ("fused", "sharded") and min(pb.n - 1, pb.p - 1) > 0
            and not fused.device_band()):
        # the bisection itself is fused (one probe0 + lax.scan program per
        # row-chunk — sharded over the device mesh for backend="sharded");
        # probe-run dedup is pointless when probes are free, so `groups`
        # is ignored — results are identical either way.  Where the device's
        # float64 is not IEEE the probes run the certified loop one by one,
        # and the bisection's own arithmetic stays on the host.
        return _sp_bi_p_fused(pb, p_fix, iters, lo, hi, with_mappings,
                              backend)
    if not with_mappings:
        return _sp_bi_p_grouped(pb, p_fix, groups, iters, backend, lo, hi)
    return _sp_bi_p_rowwise(pb, p_fix, iters, backend, lo, hi, with_mappings)


def _sp_bi_p_fused(pb, p_fix, iters, lo, hi, with_mappings,
                   backend: str = "fused"):
    """H4 with the binary search fused into one jitted program per row-chunk
    (:func:`repro.core.fused.run_fused_bisection`, or its ``shard_map`` SPMD
    twin :func:`repro.core.sharded.run_sharded_bisection`): O(1) host
    dispatches per campaign instead of ~iters+1, outputs identical to the
    host-driven probe-loop paths (asserted by
    tests/test_engine_equivalence.py)."""
    if backend == "sharded":
        from . import sharded

        r = sharded.run_sharded_bisection(pb, p_fix, lo, hi, iters)
    else:
        from . import fused

        r = fused.run_fused_bisection(pb, p_fix, lo, hi, iters)
    out = []
    for i in range(pb.B):
        if not r["feas0"][i]:
            mp = (_mapping_from_rows(r["items0"][i], int(r["m0"][i]))
                  if with_mappings else None)
            out.append(HeuristicResult(mp, float(r["per0"][i]),
                                       float(r["lat0"][i]), False,
                                       int(r["sp0"][i]), "Sp bi P"))
        else:
            mp = (_mapping_from_rows(r["items"][i], int(r["m"][i]))
                  if with_mappings else None)
            out.append(HeuristicResult(mp, float(r["per"][i]),
                                       float(r["lat"][i]), True,
                                       int(r["sp"][i]), "Sp bi P"))
    return out


def _sp_bi_p_rowwise(pb, p_fix, iters, backend, lo, hi, with_mappings):
    """One lockstep probe row per (problem): keeps full state for mappings."""
    B = pb.B

    all_bi = np.ones(B, dtype=bool)

    def probe(limits, act):
        st = _BatchState(pb, active=act)
        _run_loop(st, 1, all_bi, p_fix, limits, backend)
        per, lat = st.period(), st.latency()
        feas = (per <= p_fix + _EPS) & (lat <= limits + _EPS)
        return st, per, lat, feas

    # Ensure feasibility at the upper end first.
    st0, per0, lat0, feas0 = probe(hi, np.ones(B, dtype=bool))
    fail_maps = [st0.mapping(i) if with_mappings and not feas0[i] else None
                 for i in range(B)]
    fail_per, fail_lat, fail_splits = per0.copy(), lat0.copy(), st0.splits.copy()
    best_items = st0.arr[:, :, :3].copy()
    best_m, best_splits = st0.m.copy(), st0.splits.copy()
    best_per, best_lat = per0.copy(), lat0.copy()
    alive = feas0.copy()
    for _ in range(iters):
        if not alive.any():
            break
        mid = 0.5 * (lo + hi)
        st, per, lat, feas = probe(mid, alive)
        good = alive & feas
        hi = np.where(good, mid, hi)
        lo = np.where(alive & ~feas, mid, lo)
        better = good & ((lat < best_lat - _EPS) |
                         ((np.abs(lat - best_lat) <= _EPS) & (per < best_per)))
        if better.any():
            best_items[better] = st.arr[better, :, :3]
            best_m[better] = st.m[better]
            best_splits[better] = st.splits[better]
            best_per[better] = per[better]
            best_lat[better] = lat[better]
    out = []
    for i in range(B):
        if not feas0[i]:
            out.append(HeuristicResult(fail_maps[i], float(fail_per[i]),
                                       float(fail_lat[i]), False,
                                       int(fail_splits[i]), "Sp bi P"))
        else:
            mp = (_mapping_from_rows(best_items[i], int(best_m[i]))
                  if with_mappings else None)
            out.append(HeuristicResult(mp, float(best_per[i]), float(best_lat[i]),
                                       True, int(best_splits[i]), "Sp bi P"))
    return out


def _sp_bi_p_grouped(pb, p_fix, groups, iters, backend, lo, hi):
    """Metrics-only H4 with probe-run deduplication.

    A probe's split *choices* never depend on its period stop-bound — only
    the stopping point does (the ``split_trajectory`` argument, applied to
    the latency-limited loop).  So per bisection step, ONE latency-limited
    exhaustion run per unique (instance, latency-limit) pair is recorded as a
    (period, latency)-per-split trajectory, and every period bound sharing
    that pair reads its probe result off the shared trajectory: the first
    state with ``period <= bound + eps`` (or the final state).  Rows of the
    same instance share limits until their feasibility histories diverge, so
    this collapses each instance's whole bound grid into a handful of runs.
    """
    B = pb.B

    def probe(limits, act):
        alive_rows = np.nonzero(act)[0]
        key_arr = np.empty((alive_rows.size, 2), dtype=np.int64)
        key_arr[:, 0] = groups[alive_rows]
        key_arr[:, 1] = limits[alive_rows].view(np.int64)
        uniq, inv = np.unique(key_arr, axis=0, return_inverse=True)
        inv = inv.ravel()
        R = len(uniq)
        exemplar = np.empty(R, dtype=np.int64)
        exemplar[inv[::-1]] = alive_rows[::-1]      # first occurrence wins
        sub = pb.take(exemplar)
        st = _BatchState(sub)
        init_per, init_lat = st.period(), st.latency()
        recs = []
        _run_loop(st, 1, np.ones(R, dtype=bool), np.full(R, -np.inf),
                  limits[exemplar], backend,
                  record=lambda rows, pers, lats: recs.append((rows, pers, lats)))
        # assemble per-run trajectories; step index == split count because an
        # active row accepts a split at every lockstep iteration
        T = len(recs) + 1
        per_tr = np.full((R, T), np.inf)            # +inf padding: never a stop
        lat_tr = np.zeros((R, T))
        lengths = np.ones(R, dtype=np.int64)
        per_tr[:, 0] = init_per
        lat_tr[:, 0] = init_lat
        for s, (rws, pers, lats) in enumerate(recs, start=1):
            per_tr[rws, s] = pers
            lat_tr[rws, s] = lats
            lengths[rws] = s + 1
        # vectorized scan over all dependent rows
        bnd = p_fix[alive_rows] + _EPS
        hit = per_tr[inv] <= bnd[:, None]
        has_hit = hit.any(axis=1)
        t_idx = np.where(has_hit, np.argmax(hit, axis=1), lengths[inv] - 1)
        per = np.empty(B)
        lat = np.empty(B)
        sp = np.zeros(B, dtype=np.int64)
        feas = np.zeros(B, dtype=bool)
        per[alive_rows] = per_tr[inv, t_idx]
        lat[alive_rows] = lat_tr[inv, t_idx]
        sp[alive_rows] = t_idx
        feas[alive_rows] = ((per[alive_rows] <= p_fix[alive_rows] + _EPS)
                            & (lat[alive_rows] <= limits[alive_rows] + _EPS))
        return per, lat, sp, feas

    per0, lat0, sp0, feas0 = probe(hi, np.ones(B, dtype=bool))
    best_per, best_lat, best_sp = per0.copy(), lat0.copy(), sp0.copy()
    alive = feas0.copy()
    for _ in range(iters):
        if not alive.any():
            break
        mid = 0.5 * (lo + hi)
        per, lat, sp, feas = probe(mid, alive)
        good = alive & feas
        hi = np.where(good, mid, hi)
        lo = np.where(alive & ~feas, mid, lo)
        better = good & ((lat < best_lat - _EPS) |
                         ((np.abs(lat - best_lat) <= _EPS) & (per < best_per)))
        best_per[better] = per[better]
        best_lat[better] = lat[better]
        best_sp[better] = sp[better]
    return [HeuristicResult(None, float(per0[i]), float(lat0[i]), False,
                            int(sp0[i]), "Sp bi P") if not feas0[i]
            else HeuristicResult(None, float(best_per[i]), float(best_lat[i]),
                                 True, int(best_sp[i]), "Sp bi P")
            for i in range(B)]
