"""Fused device-resident campaign engine: the whole lockstep loop under jit.

The batched engine (:mod:`repro.core.batched`) runs B problems in lockstep,
but only the inner scoring kernels run under ``jax.jit`` — every iteration
still round-trips through Python for worst-interval selection, candidate-grid
construction, and state updates, so a campaign issues O(iterations) host
dispatches and cannot live on an accelerator.  This module traces the ENTIRE
splitting loop — stop checks, worst-interval argmax, masked candidate scoring
through the shared ``score_2way_kernel``/``score_3way_kernel``, exact
lexicographic tie-breaks, and structure-of-arrays state updates — into one
``jax.jit``-compiled ``lax.while_loop``, so a whole campaign run is O(1)
host dispatches per (shape, heuristic-arity) pair.

Design differences from the numpy lockstep loop (same *choices*, fixed shape):

  - Candidate grids are SPAN-BUCKETED: instead of one static worst-case grid
    (all cuts ``1..n-1`` / all pairs ``c1 < c2`` — the "static-grid tax" that
    made every iteration pay O(n) / O(n^2) lanes even for a 2-stage worst
    interval), each lockstep iteration routes to the smallest geometric
    (power-of-two) bucket covering the live rows' worst-interval span, via a
    ``lax.switch`` over per-bucket scoring branches (:func:`bucket_sizes`).
    Cut lanes are interval-relative (cut ``c = d + offset``) with validity
    masks and clamped gathers, exactly like the numpy engine's span
    compaction; tie-break keys use absolute positions, so selection is
    identical lane-layout notwithstanding.  Evaluated lanes shrink from
    O(n * S) toward the live span while the branch count — and therefore the
    per-program bucket-trace count (:func:`bucket_trace_count`) — stays
    O(log n) per arity (:func:`trace_budget`, asserted by the tests).
  - The 2-stage 3-way fallback (scalar generator in the numpy engine) is six
    extra static lanes with the scalar path's enumeration-order tie-break,
    shared across buckets.
  - Convergence is a per-row mask; the loop exits when every row is done,
    recording per-iteration (period, latency, accepted) into fixed (T, S)
    buffers (T = max possible splits) for trajectory assembly on the host.
  - Batches are padded to a fixed chunk size S per (n, arity), so EVERY call
    of a campaign — trajectories, H4 bisection probes on shrinking subsets,
    H5/H6 bound-grid runs — reuses one trace per arity.  The carried SoA
    state buffers (items array, item counts, latency sums, split counts) are
    donated to the jitted program where it returns them, so XLA reuses their
    device buffers for the outputs instead of allocating fresh ones per call.

Equivalence contract: split trajectories — the accepted splits AND their
(period, latency) floats — are identical to the numpy engine on all tested
instances (asserted by tests/test_engine_equivalence.py).  This requires
defeating two XLA rewrites that would drift by an ulp and flip exact ties:
FMA contraction of ``a * b + c`` chains (neutralized by the kernels' runtime-
``zero`` guard: ``fma(a, b, 0) == round(a * b)``) and reduction reordering
(the kernels sum the 3-part axis with explicit left-associated adds; max/min
reductions are order-exact).  The numpy engine remains the contractual
bit-exact reference; the fused engine is validated against it per test grid.

Devices without IEEE float64: a TPU emulates float64 with float32 pairs
(about 2^-44 relative per operation on a v5e), so the traced floats cannot
match numpy there, and exact ties of the integer-valued Section-5 instances
break differently.  There the loop runs CERTIFIED (:func:`device_band`,
:func:`_build_loop`): the device decides every step whose outcome is settled
by more than the error band, parks the rows whose step is not, and the host
replays the device's decisions and makes the parked ones in float64 with the
numpy engine's own code (:func:`run_loop`) — same trajectories and floats,
at the price of one more dispatch round per parked step.  The certified
program takes its inputs as two packed blocks, one float64 and one int64
(:func:`_pack_inputs`), so each dispatch makes two uploads, and returns only
those decisions, one packed int32 record a dispatch.
The H4 bisection then probes through this loop from the host
(``batched.batched_sp_bi_p``).

Cold starts amortize across processes through JAX's persistent compilation
cache (:func:`enable_persistent_cache` — benchmarks enable it by default).

Use via ``backend="fused"`` on any :mod:`repro.core.batched` entry point (the
lockstep runner dispatches here), or ``engine="fused"`` in
``repro.sim.experiments`` / ``benchmarks/paper_sim.py``.
"""

from __future__ import annotations

import functools
import os
import pathlib
from typing import Callable, Optional

import numpy as np

from .heuristics import _EPS, score_2way_kernel, score_3way_kernel
from .spans import recording, span

__all__ = ["fused_available", "run_fused", "run_fused_bisection",
           "trace_count", "reset_trace_count", "traced_shapes",
           "decision_counts", "device_band", "run_loop",
           "dispatch_count", "reset_dispatch_count", "transfer_bytes",
           "fetch_copies", "launch_copies",
           "bucket_trace_count", "reset_bucket_trace_count",
           "bucket_sizes", "bucket_index", "trace_budget",
           "enable_persistent_cache"]

# number of traced (compiled) variants of the fused programs since the last
# reset; incremented from inside the traced wrappers, which Python-execute
# only while jax is tracing — so this counts actual traces, not dispatches.
_TRACES = [0]
# (n, p) shapes of the programs behind those traces
_SHAPES: set = set()
# number of traced bucket BRANCHES since the last reset: each program trace
# traces every bucket of its arity exactly once (lax.switch compiles all
# branches), so this counter realizes the O(log n)-buckets-per-arity cap.
_BUCKET_TRACES = [0]
# number of jitted-program dispatches (host -> device calls) since the last
# reset: one per row-chunk for the lockstep loop, one per row-chunk for the
# WHOLE H4 bisection (probe-at-hi + the lax.scan over probe iterations).
_DISPATCHES = [0]
# decisions of certified loops since the last dispatch-count reset: splits
# the device decided, and parked row-steps the host re-decided in float64
_DECISIONS = {"device": 0, "host": 0}
# bytes handed to this engine's jitted programs (every argument of every
# dispatch) and copied back (every output), the host arrays uploaded and the
# device-to-host copies, since the last dispatch-count reset
_TRANSFER = {"to_device": 0, "to_host": 0, "uploads": 0, "copies": 0}

# Relative error bound, against each row's magnitude scale, that certified
# loops allow the device arithmetic.  TPU float64 is emulated with float32
# pairs: single operations measured within 2^-44 of IEEE float64 on a v5e
# (add, mul, div, reciprocal), so a decision chain of a few dozen operations
# plus the latency sum carried over <= n iterations stays below 2^-35, a
# factor of 2^7 inside this band.
TPU_BAND = 2.0 ** -28

# per-iteration decision record: worst-interval index, the (up to 3) parts'
# first stage, last stage and processor, part count, processors consumed
DEC_FIELDS = ("widx", "d0", "d1", "d2", "e0", "e1", "e2", "u0", "u1", "u2",
              "nparts", "consumed")
# fields of the certified loop's packed record, int32 of shape
# (T + 1, len(REC_FIELDS), S): at t < T iteration t's decision and whether it
# was accepted; at T each row's parked flag (field 0) and the loop's
# iteration count (field 1)
REC_FIELDS = DEC_FIELDS + ("accept",)

# lane budget per jitted call: rows_per_chunk * candidate_lanes is held under
# this so the 3-way pair grid of large n stays cache-/memory-sized.  Sized
# against the TOP bucket (the full grid) — smaller buckets only use less.
_LANE_BUDGET = 4_000_000
_MAX_CHUNK = 128

_PERMS3 = np.array([(0, 1, 2), (0, 2, 1), (1, 0, 2), (1, 2, 0), (2, 0, 1),
                    (2, 1, 0)])
# the scalar 2-stage fallback's candidate order: permutations((j,jp,jpp), 2)
_FB_A = np.array([0, 0, 1, 1, 2, 2])
_FB_B = np.array([1, 2, 0, 2, 0, 1])


def fused_available() -> bool:
    try:
        import jax  # noqa: F401
    except Exception:  # pragma: no cover - jax is baked into the image
        return False
    return True


def trace_count() -> int:
    """Traces of the fused programs since the last :func:`reset_trace_count`."""
    return _TRACES[0]


def reset_trace_count() -> None:
    _TRACES[0] = 0
    _SHAPES.clear()


def traced_shapes() -> list:
    """Sorted distinct ``(n, p)`` of the programs traced since the last
    :func:`reset_trace_count` — each is a separate compile on a device."""
    return sorted(_SHAPES)


def bucket_trace_count() -> int:
    """Bucket-branch traces since :func:`reset_bucket_trace_count` — the
    O(log n)-buckets-per-arity cap is asserted on this counter (each program
    trace traces every bucket of its arity once; see :func:`trace_budget`)."""
    return _BUCKET_TRACES[0]


def reset_bucket_trace_count() -> None:
    _BUCKET_TRACES[0] = 0


def dispatch_count() -> int:
    """Jitted-program dispatches since :func:`reset_dispatch_count` — the
    O(1)-dispatch contract is asserted on this counter by the tests."""
    return _DISPATCHES[0]


def reset_dispatch_count() -> None:
    _DISPATCHES[0] = 0
    _DECISIONS.update(device=0, host=0)
    _TRANSFER.update(to_device=0, to_host=0, uploads=0, copies=0)


def transfer_bytes() -> dict:
    """Bytes over the host link since :func:`reset_dispatch_count`:
    ``to_device`` the ``nbytes`` of every argument of every call of this
    engine's programs (:func:`run_fused`, :func:`run_fused_bisection`),
    ``to_host`` those of every output, so that bytes over
    :func:`dispatch_count` are per call.  The sharded engine counts its own
    (``sharded.transfer_bytes``)."""
    return {k: _TRANSFER[k] for k in ("to_device", "to_host")}


def fetch_copies() -> int:
    """Device-to-host copies since :func:`reset_dispatch_count`, one per
    output fetched (the certified loop's one packed record, the other
    programs' every output); the sharded engine counts its own
    (``sharded.fetch_copies``)."""
    return _TRANSFER["copies"]


def launch_copies() -> int:
    """Host arrays uploaded since :func:`reset_dispatch_count`, one per
    argument of every call: two for the certified loop's packed blocks, 17
    for the uncertified loop, ten for the bisection; the sharded engine
    counts its own (``sharded.launch_copies``)."""
    return _TRANSFER["uploads"]


def decision_counts() -> dict:
    """Certified-loop decisions since :func:`reset_dispatch_count`:
    ``device`` splits decided on the device, ``host`` parked row-steps
    re-decided on the host (both 0 where :func:`device_band` is 0)."""
    return dict(_DECISIONS)


def device_band() -> float:
    """Relative error band of the device's float64 for the fused loop: 0 on
    backends with IEEE float64 (CPU, GPU), whose decisions are exact as
    traced; :data:`TPU_BAND` on a TPU, where the loop is certified and
    parks what it cannot decide (see :func:`_build_loop`)."""
    import jax

    return TPU_BAND if jax.default_backend() == "tpu" else 0.0


@functools.lru_cache(maxsize=None)
def bucket_sizes(n: int, k: int) -> tuple:
    """Geometric (power-of-two) candidate-grid buckets for stage count ``n``.

    For arity ``k == 1`` the sizes count candidate CUTS of the worst interval
    (``1 <= e - d <= n - 1``); for ``k == 2`` they count its SPAN
    (``3 <= e - d + 1 <= n`` — 2-stage intervals score through the static
    fallback lanes instead, shared across buckets).  Sizes double from a
    small floor and the top bucket is clamped to the exact maximum, so there
    are at most ``ceil(log2(n)) + 1`` buckets; each is traced once per fused
    program, which is the O(log n)-traces-per-arity cap asserted in tests.
    """
    if k == 1:
        lo, hi = 2, n - 1
    else:
        if n < 3:
            return ()
        lo, hi = 4, n
    if hi <= 0:
        return ()
    sizes = []
    s = lo
    while s < hi:
        sizes.append(s)
        s *= 2
    sizes.append(hi)
    return tuple(sizes)


def bucket_index(need: int, sizes) -> int:
    """Index of the smallest bucket in ``sizes`` covering ``need`` lanes.
    The traced loop evaluates the same expression on-device per iteration
    (``sum(need > sizes[:-1])``), so this host mirror is what the
    bucket-routing property test pins down."""
    sizes = np.asarray(sizes)
    return int(np.sum(np.asarray(need) > sizes[:-1]))


def trace_budget(n: int) -> int:
    """Upper bound on bucket-branch traces for one campaign at stage count
    ``n``: one bucket set per traced k=1 program (the lockstep loop AND the
    bisection's inlined loop) plus one per traced k=2 program."""
    return 2 * len(bucket_sizes(n, 1)) + len(bucket_sizes(n, 2))


#: Compile-cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: a fixed path inside the checkout (the path is part of the cache key, so a
#: directory that moves between runs never hits).
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Point JAX at an on-disk compilation cache so fused-program cold starts
    are paid once per machine, not once per process.  Idempotent; returns the
    cache directory: ``JAX_COMPILATION_CACHE_DIR`` when set, otherwise
    :data:`DEFAULT_CACHE_DIR`.  Entry points call this before their first
    compile."""
    import jax

    path = str(os.environ.get("JAX_COMPILATION_CACHE_DIR")
               or DEFAULT_CACHE_DIR)
    pathlib.Path(path).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # persist only compiles that meaningfully cost (the fused programs take
    # seconds); trivial sub-second compiles would otherwise accumulate in an
    # uneviected cache directory forever
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path


def chunk_rows(n: int, k: int) -> int:
    """Fixed rows-per-call for shape (n, arity k) — deterministic so every
    call of a campaign pads to the same chunk shape and shares one trace.
    Sized against the TOP span bucket (the worst-case grid)."""
    if k == 1:
        lanes = max(2 * (n - 1), 1)
    else:
        lanes = 18 * ((n - 1) * (n - 2) // 2) + 6
    return int(max(1, min(_MAX_CHUNK, _LANE_BUDGET // max(lanes, 1))))


def _lex_argmin_traced(xp, keys, mask):
    """Traced mirror of ``batched._lex_argmin``: per-row first index of the
    lexicographically smallest key tuple among masked lanes (no early exit —
    extra key passes only re-filter ties, so the winner is identical)."""
    has = mask.any(axis=1)
    m = mask
    for key in keys:
        kmin = xp.where(m, key, xp.inf).min(axis=1)
        m = m & (key == kmin[:, None])
    return xp.argmax(m, axis=1), has


def _build_loop(n: int, p: int, k: int, T: int, S: int,
                band: float = 0.0) -> tuple:
    """Build the UNJITTED fused loop for static shape (n, p, k).

    Returns ``(init_state, loop)``:

        init_state(delta, s, b, prefix, order) -> (arr, m, nx, lat, sp)
        loop(delta, s, b, zero, prefix, order, bi_mode, stop, lat_limit,
             active0, arr0, m0, nx0, lat0, sp0, scale, sbits)
          -> (arr, m, next_idx, lat_sum, splits, parked,
              per_rec, lat_rec, acc_rec, dec_rec, t)      (band == 0)
          -> rec                                          (band > 0)

    with ``arr`` (S, n, 5) in the ``_BatchState`` field layout, the records
    (T, S) per lockstep iteration and ``dec_rec`` (T, S, 12) the decision of
    each iteration (:data:`DEC_FIELDS`).  The certified loop returns its
    decisions alone, packed into one int32 array ``rec`` (:data:`REC_FIELDS`):
    the host replays the state itself, so it needs nothing else.  Callers
    jit the loop (:func:`_get_loop`) or inline it into a larger traced
    program (:func:`_get_bisect`).  Candidate scoring runs through a
    ``lax.switch`` over the geometric span buckets of :func:`bucket_sizes`.

    ``band > 0`` builds the CERTIFIED loop for devices whose float64 is not
    IEEE (see :func:`device_band`): ``scale`` (S,) bounds each row's
    magnitudes and ``sbits`` (S, p) holds the speeds' exact bit patterns.
    Every decision whose outcome could differ under exact float64 — a stop
    test, worst-interval pick, feasibility mask or key-1 winner within
    ``band * scale`` (relative error bound of the device arithmetic) of the
    other outcome — PARKS the row instead (``parked``); the host then makes
    that one decision in float64 (:func:`run_loop`).  Lanes whose keys are
    equal because their speeds are bit-equal are exact ties on both sides and
    resolve by the (exact) position key, so they never park.  ``band == 0``
    (IEEE float64 devices) leaves every row unparked.
    """
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    from jax import lax

    col = jnp.arange(n)[None, :]
    sizes = bucket_sizes(n, k)
    thresholds = np.asarray(sizes[:-1], dtype=np.int64)
    fb_key = np.arange(6, dtype=float)[None, :]

    def take1(A, idx):
        return jnp.take_along_axis(A, idx[:, None], axis=1)[:, 0]

    def feasible(mx, lat, old_cycle, lat_lim, base, gam, gam_lat):
        """Lanes with ``mx < old - eps`` and ``lat <= limit + eps`` among
        ``base``, and per row whether any lane's feasibility lies within the
        error band (always False when ``band == 0``)."""
        old = old_cycle[:, None] - _EPS
        lim = lat_lim[:, None] + _EPS
        okay = (mx < old) & (lat <= lim) & base
        if not band:
            return okay, jnp.zeros(okay.shape[0], dtype=bool)
        g, gl = gam[:, None], gam_lat[:, None]
        sure = (mx < old - g) & (lat <= lim - gl) & base
        maybe = (mx < old + g) & (lat <= lim + gl) & base
        return sure, (maybe & ~sure).any(axis=1)

    def ratio_band(ratio, den_min, g):
        """Error bound of ``dlat / max(old - cyc, eps)`` keys: numerator and
        denominators are each off by at most ``g``."""
        return g * (1.0 + jnp.abs(ratio)) / jnp.maximum(den_min - g, _EPS)

    def conflict(key1, band1, okay, q, dup):
        """Rows whose key-1 winner ``q`` is not certified: a feasible lane
        that is not an exact duplicate of ``q`` (``dup`` holds ``q``) lies
        within the two lanes' error bands of it."""
        hi = take1(key1, q) + take1(band1, q)
        return (okay & ~dup & (key1 - band1 <= hi[:, None])).any(axis=1)

    def make_choose_2way(L: int) -> Callable:
        """Scoring/selection branch over the L-cut bucket: interval-relative
        cut lanes ``c = d + offset`` (same compaction as the numpy engine's
        ``_choose_2way``), absolute-position tie-break keys."""
        off = np.arange(L)

        def choose(ops):
            _BUCKET_TRACES[0] += 1  # Python-executes once per branch trace
            (prefix, delta, b, zero, d, e, j, jp_, bi, old_cycle, cur_lat,
             lat_lim, live, pre_d1, pre_e, del_d1, del_e, inv_j, inv_p,
             gam, gam_lat, same) = ops
            c = d[:, None] + off[None, :]
            valid = c < e[:, None]
            ci = jnp.minimum(c, n - 1)           # in-range gather, masked lanes
            pre_C = jnp.take_along_axis(prefix, ci, axis=1)
            del_C = jnp.take_along_axis(delta, ci, axis=1)
            cyc1, cyc2, dlat = score_2way_kernel(
                pre_d1[:, None], pre_C, pre_e[:, None],
                del_d1[:, None], del_C, del_e[:, None], b,
                inv_j[:, None], inv_p[:, None], xp=jnp, zero=zero)
            mx = jnp.maximum(cyc1, cyc2)
            okay, unc = feasible(
                mx, cur_lat[:, None] + dlat, old_cycle, lat_lim,
                jnp.concatenate([valid, valid], axis=1) & live[:, None],
                gam, gam_lat)
            ratio = jnp.maximum(
                dlat / jnp.maximum(old_cycle[:, None] - cyc1, _EPS),
                dlat / jnp.maximum(old_cycle[:, None] - cyc2, _EPS))
            cf = c.astype(jnp.float64)
            cutorder = jnp.concatenate([cf * 2.0, cf * 2.0 + 1.0], axis=1)
            bc = bi[:, None]
            keys = [jnp.where(bc, ratio, mx), jnp.where(bc, mx, dlat),
                    cutorder]
            q, has = _lex_argmin_traced(jnp, keys, okay)
            if band:
                # the other placement order of q's cut ties q exactly when
                # the two speeds are bit-equal
                g = gam[:, None]
                dmin = jnp.minimum(old_cycle[:, None] - cyc1,
                                   old_cycle[:, None] - cyc2)
                band1 = jnp.where(bc, ratio_band(ratio, dmin, g), g)
                lane = jnp.arange(2 * L)[None, :]
                dup = ((lane % L == (q % L)[:, None])
                       & ((lane == q[:, None]) | same[:, None]))
                unc |= has & conflict(keys[0], band1, okay, q, dup)
            cw = d + (q % L)
            swapped = q >= L
            pa = jnp.where(swapped, jp_, j)
            pb2 = jnp.where(swapped, j, jp_)
            pd = jnp.stack([d, cw + 1, cw + 1], axis=1)
            pe = jnp.stack([cw, e, e], axis=1)
            pu = jnp.stack([pa, pb2, pb2], axis=1)
            nparts = jnp.full((S,), 2, dtype=jnp.int64)
            consumed = jnp.ones((S,), dtype=jnp.int64)
            return has, pd, pe, pu, nparts, consumed, unc

        return choose

    def make_choose_3way(L: Optional[int]) -> Callable:
        """Scoring/selection branch over the L-span bucket: all relative cut
        pairs ``0 <= r1 < r2 <= L-2`` (``c_i = d + r_i``) x 6 permutations,
        concatenated with the shared 2-stage fallback lanes for the joint
        exact lex tie-break.  ``L=None`` (n < 3) keeps fallback lanes only."""
        if L is not None:
            r1, r2 = np.triu_indices(L - 1, k=1)
            K = int(r1.size)
        else:
            r1 = r2 = None
            K = 0

        def choose(ops):
            _BUCKET_TRACES[0] += 1  # Python-executes once per branch trace
            (prefix, delta, b, zero, d, e, bi, old_cycle, cur_lat, lat_lim,
             live, span2, pre_d1, pre_e, del_d1, del_e, invp, base_term,
             procs3, mx_fb, dlat_fb, ratio_fb, okay_fb, unc, dmin_fb,
             gam, gam_lat, cls3) = ops
            bc = bi[:, None]
            if K:
                c1 = d[:, None] + r1[None, :]
                c2 = d[:, None] + r2[None, :]
                valid = c2 <= (e - 1)[:, None]
                c1i = jnp.minimum(c1, n - 1)
                c2i = jnp.minimum(c2, n - 1)
                pre_c1 = jnp.take_along_axis(prefix, c1i, axis=1)
                pre_c2 = jnp.take_along_axis(prefix, c2i, axis=1)
                del_c1 = jnp.take_along_axis(delta, c1i, axis=1)
                del_c2 = jnp.take_along_axis(delta, c2i, axis=1)
                W = jnp.stack([pre_c1 - pre_d1[:, None], pre_c2 - pre_c1,
                               pre_e[:, None] - pre_c2], axis=1)  # (S, 3, K)
                dI = jnp.stack([jnp.broadcast_to(del_d1[:, None], (S, K)),
                                del_c1, del_c2], axis=1) / b
                dO = jnp.stack([del_c1, del_c2,
                                jnp.broadcast_to(del_e[:, None], (S, K))],
                               axis=1) / b
                cyc, dlat, mx = score_3way_kernel(
                    dI[:, None], W[:, None], dO[:, None], invp,
                    base_term[:, None, None], xp=jnp, zero=zero)
                ratio = (dlat[:, :, None, :]
                         / jnp.maximum(old_cycle[:, None, None, None] - cyc,
                                       _EPS)).max(axis=2)
                mx_f = mx.reshape(S, 6 * K)
                dlat_f = dlat.reshape(S, 6 * K)
                ratio_f = ratio.reshape(S, 6 * K)
                okay3, unc3 = feasible(
                    mx_f, cur_lat[:, None] + dlat_f, old_cycle, lat_lim,
                    jnp.broadcast_to(valid[:, None, :], (S, 6, K)
                                     ).reshape(S, 6 * K)
                    & (live & ~span2)[:, None], gam, gam_lat)
                unc |= unc3
                # (c1, c2, perm) tie-break as ONE exactly-represented integer
                # key — absolute positions, so bucket layout cannot matter
                ccp = ((c1 * (n + 1) + c2)[:, None, :] * 6
                       + np.arange(6)[None, :, None]
                       ).astype(jnp.float64).reshape(S, 6 * K)
                key1 = jnp.concatenate(
                    [jnp.where(bc, ratio_f, mx_f),
                     jnp.where(bc, ratio_fb, mx_fb)], axis=1)
                key2 = jnp.concatenate(
                    [jnp.where(bc, mx_f, dlat_f),
                     jnp.where(bc, mx_fb, dlat_fb)], axis=1)
                key3 = jnp.concatenate(
                    [ccp, jnp.broadcast_to(fb_key, (S, 6))], axis=1)
                okay = jnp.concatenate([okay3, okay_fb], axis=1)
            else:
                key1 = jnp.where(bc, ratio_fb, mx_fb)
                key2 = jnp.where(bc, mx_fb, dlat_fb)
                key3 = jnp.broadcast_to(fb_key, (S, 6))
                okay = okay_fb
            q, has = _lex_argmin_traced(jnp, [key1, key2, key3], okay)

            fb = q >= 6 * K
            # grid winner
            pi = jnp.minimum(q // max(K, 1), 5)
            kk = q % max(K, 1)
            if K:
                c1b = d + jnp.take(jnp.asarray(r1), kk, mode="clip")
                c2b = d + jnp.take(jnp.asarray(r2), kk, mode="clip")
            else:
                c1b = c2b = d
            perm = jnp.asarray(_PERMS3)[pi]                          # (S, 3)
            u_grid = jnp.take_along_axis(procs3, perm, axis=1)
            pd_g = jnp.stack([d, c1b + 1, c2b + 1], axis=1)
            pe_g = jnp.stack([c1b, c2b, e], axis=1)
            # fallback winner
            qf = jnp.where(fb, q - 6 * K, 0)
            ia = jnp.asarray(_FB_A)[qf]
            ib = jnp.asarray(_FB_B)[qf]
            pu0 = jnp.take_along_axis(procs3, ia[:, None], axis=1)[:, 0]
            pu1 = jnp.take_along_axis(procs3, ib[:, None], axis=1)[:, 0]
            pd_f = jnp.stack([d, d + 1, d + 1], axis=1)
            pe_f = jnp.stack([d, e, e], axis=1)
            pu_f = jnp.stack([pu0, pu1, pu1], axis=1)
            cons_f = jnp.where((ia != 0) & (ib != 0), 2, 1).astype(jnp.int64)

            if band:
                # lanes whose permuted speeds are bit-equal to q's tie q
                # exactly: same cut pair (grid) or same part speeds (fallback)
                g = gam[:, None]
                band1 = jnp.where(bc, ratio_band(ratio_fb, dmin_fb, g), g)
                ca, cb = cls3[:, _FB_A], cls3[:, _FB_B]
                dup = ((ca == take1(ca, qf)[:, None])
                       & (cb == take1(cb, qf)[:, None]) & fb[:, None])
                if K:
                    dmin = (old_cycle[:, None, None, None] - cyc).min(axis=2)
                    band3 = jnp.where(
                        bc, ratio_band(ratio_f, dmin.reshape(S, 6 * K), g), g)
                    cp = cls3[:, _PERMS3]                             # (S,6,3)
                    cq = jnp.take_along_axis(cp, pi[:, None, None], axis=1)
                    dup3 = ((cp == cq).all(axis=2)[:, :, None]
                            & (jnp.arange(K)[None, None, :]
                               == kk[:, None, None]))
                    band1 = jnp.concatenate([band3, band1], axis=1)
                    dup = jnp.concatenate(
                        [dup3.reshape(S, 6 * K) & ~fb[:, None], dup], axis=1)
                unc |= has & conflict(key1, band1, okay, q, dup)

            fbc = fb[:, None]
            pd = jnp.where(fbc, pd_f, pd_g)
            pe = jnp.where(fbc, pe_f, pe_g)
            pu = jnp.where(fbc, pu_f, u_grid)
            nparts = jnp.where(fb, 2, 3).astype(jnp.int64)
            consumed = jnp.where(fb, cons_f, 2).astype(jnp.int64)
            return has, pd, pe, pu, nparts, consumed, unc

        return choose

    if k == 1:
        branches = [make_choose_2way(L) for L in sizes]
    else:
        branches = ([make_choose_3way(L) for L in sizes]
                    if sizes else [make_choose_3way(None)])

    def init_state(delta, s, b, prefix, order):
        """The optimal-latency starting state (all stages on the fastest
        processor) — same expressions as ``batched._BatchState.__init__``."""
        fastest = order[:, 0]
        term0 = delta[:, 0] / b + (prefix[:, n] - prefix[:, 0]) / take1(s, fastest)
        tail = delta[:, n] / b
        arr = jnp.full((S, n, 5), 0.0).at[:, :, 3].set(-jnp.inf)
        arr = arr.at[:, 0, 0].set(1.0)
        arr = arr.at[:, 0, 1].set(float(n))
        arr = arr.at[:, 0, 2].set(fastest.astype(jnp.float64))
        arr = arr.at[:, 0, 3].set(term0 + tail)
        arr = arr.at[:, 0, 4].set(term0)
        m0 = jnp.ones(S, dtype=jnp.int64)
        nx0 = jnp.ones(S, dtype=jnp.int64)
        sp0 = jnp.zeros(S, dtype=jnp.int64)
        return arr, m0, nx0, term0, sp0

    def loop(delta, s, b, zero, prefix, order, bi_mode, stop, lat_limit,
             active0, arr0, m0, nx0, lat0, sp0, scale, sbits):
        tail = delta[:, n] / b
        if band:
            gam = band * scale
            gam_lat = band * (scale + jnp.where(jnp.isfinite(lat_limit),
                                                jnp.abs(lat_limit), 0.0))
            gam_stop = band * (scale + jnp.where(jnp.isfinite(stop),
                                                 jnp.abs(stop), 0.0))
            recs = (jnp.zeros((T + 1, len(REC_FIELDS), S), dtype=jnp.int32),)
        else:
            gam = gam_lat = gam_stop = jnp.zeros(S)
            recs = (jnp.zeros((T, S)), jnp.zeros((T, S)),
                    jnp.zeros((T, S), dtype=bool),
                    jnp.zeros((T, S, len(DEC_FIELDS)), dtype=jnp.int64))

        def cond(carry):
            t, active = carry[0], carry[5]
            return (t < T) & active.any()

        def body(carry):
            (t, arr, m, next_idx, lat_sum, active, splits, parked,
             recs) = carry
            cyc = arr[:, :, 3]
            per = cyc.max(axis=1)
            live = active & (per > stop + _EPS)
            widx = jnp.argmax(cyc, axis=1)
            unc = jnp.zeros(S, dtype=bool)
            if band:
                # stop test and worst-interval pick within the error band
                second = jnp.where(col == widx[:, None], -jnp.inf,
                                   cyc).max(axis=1)
                unc = active & ((jnp.abs(per - (stop + _EPS)) <= gam_stop)
                                | (live & (second >= per - gam)))
                live &= ~unc
            item = jnp.take_along_axis(arr, widx[:, None, None], axis=1)[:, 0, :]
            d = jnp.clip(item[:, 0].astype(jnp.int64), 1, n)
            e = jnp.clip(item[:, 1].astype(jnp.int64), 1, n)
            j = jnp.clip(item[:, 2].astype(jnp.int64), 0, p - 1)
            live &= (item[:, 1] > item[:, 0]) & (next_idx + k <= p)
            old_cycle = item[:, 3]
            old_term = item[:, 4]
            cur_lat = lat_sum + tail
            jp_ = take1(order, jnp.clip(next_idx, 0, p - 1))

            # shared per-row interval-end quantities (bucket-independent)
            pre_d1 = take1(prefix, d - 1)
            pre_e = take1(prefix, e)
            del_d1 = take1(delta, d - 1)
            del_e = take1(delta, e)

            if k == 1:
                inv_j = 1.0 / take1(s, j)
                inv_p = 1.0 / take1(s, jp_)
                need = e - d                      # candidate cuts per row
                cur = jnp.max(jnp.where(live, need, 0))
                same = take1(sbits, j) == take1(sbits, jp_)
                ops = (prefix, delta, b, zero, d, e, j, jp_, bi_mode,
                       old_cycle, cur_lat, lat_limit, live,
                       pre_d1, pre_e, del_d1, del_e, inv_j, inv_p,
                       gam, gam_lat, same)
            else:
                jpp = take1(order, jnp.clip(next_idx + 1, 0, p - 1))
                sj = take1(s, j)
                s3 = jnp.stack([sj, take1(s, jp_), take1(s, jpp)], axis=1)
                invp = (1.0 / s3)[:, _PERMS3][:, :, :, None]         # (S,6,3,1)
                base_term = del_d1 / b + (pre_e - pre_d1) / sj
                procs3 = jnp.stack([j, jp_, jpp], axis=1)            # (S, 3)
                span2 = (e - d + 1) == 2

                # 2-stage fallback lanes (division-based like the scalar
                # generator): span-independent, computed once outside the
                # bucket switch and fed to every branch's joint tie-break.
                pre_dd = take1(prefix, jnp.minimum(d, n))
                del_dd = take1(delta, jnp.minimum(d, n))
                W1 = (pre_dd - pre_d1)[:, None]
                W2 = (pre_e - pre_dd)[:, None]
                spa = s3[:, _FB_A]
                spb = s3[:, _FB_B]
                t1 = del_d1[:, None] / b + W1 / spa
                cyc1_fb = t1 + del_dd[:, None] / b
                t2 = del_dd[:, None] / b + W2 / spb
                cyc2_fb = t2 + del_e[:, None] / b
                dlat_fb = (t1 + t2) - base_term[:, None]
                mx_fb = jnp.maximum(cyc1_fb, cyc2_fb)
                okay_fb, unc_fb = feasible(
                    mx_fb, cur_lat[:, None] + dlat_fb, old_cycle, lat_limit,
                    (live & span2)[:, None], gam, gam_lat)
                ratio_fb = jnp.maximum(
                    dlat_fb / jnp.maximum(old_cycle[:, None] - cyc1_fb, _EPS),
                    dlat_fb / jnp.maximum(old_cycle[:, None] - cyc2_fb, _EPS))
                dmin_fb = jnp.minimum(old_cycle[:, None] - cyc1_fb,
                                      old_cycle[:, None] - cyc2_fb)
                cls3 = jnp.stack([take1(sbits, j), take1(sbits, jp_),
                                  take1(sbits, jpp)], axis=1)

                span = e - d + 1
                cur = jnp.max(jnp.where(live & ~span2, span, 0))
                ops = (prefix, delta, b, zero, d, e, bi_mode, old_cycle,
                       cur_lat, lat_limit, live, span2, pre_d1, pre_e,
                       del_d1, del_e, invp, base_term, procs3,
                       mx_fb, dlat_fb, ratio_fb, okay_fb, unc_fb, dmin_fb,
                       gam, gam_lat, cls3)
            if len(branches) > 1:
                bidx = jnp.sum(cur > jnp.asarray(thresholds))
                (has, pd, pe, pu, nparts, consumed,
                 unc_c) = lax.switch(bidx, branches, ops)
            else:
                has, pd, pe, pu, nparts, consumed, unc_c = branches[0](ops)
            unc |= live & unc_c
            accept = live & has & ~unc

            # apply splits (same division-based expressions as _apply_splits)
            pdc = jnp.clip(pd, 1, n)
            pec = jnp.clip(pe, 1, n)
            puc = jnp.clip(pu, 0, p - 1)
            del_pd1 = jnp.take_along_axis(delta, pdc - 1, axis=1)
            pre_pe = jnp.take_along_axis(prefix, pec, axis=1)
            pre_pd1 = jnp.take_along_axis(prefix, pdc - 1, axis=1)
            s_pu = jnp.take_along_axis(s, puc, axis=1)
            del_pe = jnp.take_along_axis(delta, pec, axis=1)
            t_parts = del_pd1 / b + (pre_pe - pre_pd1) / s_pu
            c_parts = t_parts + del_pe / b
            add = t_parts[:, 0] + t_parts[:, 1]
            add = jnp.where(nparts == 3, add + t_parts[:, 2], add)
            new_lat = (lat_sum - old_term) + add
            sh = (nparts - 1)[:, None]
            idxc = widx[:, None]
            src = jnp.where(col <= idxc, col,
                            jnp.where(col <= idxc + sh, idxc, col - sh))
            new_arr = jnp.take_along_axis(arr, src[:, :, None], axis=1)
            parts5 = jnp.stack([pdc.astype(jnp.float64),
                                pec.astype(jnp.float64),
                                puc.astype(jnp.float64), c_parts, t_parts],
                               axis=2)                               # (S, 3, 5)
            m0_ = (col == idxc)[:, :, None]
            m1_ = (col == idxc + 1)[:, :, None]
            m2_ = ((col == idxc + 2) & (nparts == 3)[:, None])[:, :, None]
            new_arr = jnp.where(m0_, parts5[:, 0][:, None, :], new_arr)
            new_arr = jnp.where(m1_, parts5[:, 1][:, None, :], new_arr)
            new_arr = jnp.where(m2_, parts5[:, 2][:, None, :], new_arr)

            acc3 = accept[:, None, None]
            arr = jnp.where(acc3, new_arr, arr)
            m = m + jnp.where(accept, nparts - 1, 0)
            next_idx = next_idx + jnp.where(accept, consumed, 0)
            lat_sum = jnp.where(accept, new_lat, lat_sum)
            splits = splits + accept.astype(jnp.int64)

            if band:
                # the decision as int32 rows, rows of the batch along lanes
                (rec,) = recs
                fields = [widx[None], pdc.T, pec.T, puc.T, nparts[None],
                          consumed[None], accept[None]]
                recs = (rec.at[t].set(jnp.concatenate(
                    [f.astype(jnp.int32) for f in fields], axis=0)),)
            else:
                per_rec, lat_rec, acc_rec, dec_rec = recs
                recs = (per_rec.at[t].set(arr[:, :, 3].max(axis=1)),
                        lat_rec.at[t].set(lat_sum + tail),
                        acc_rec.at[t].set(accept),
                        dec_rec.at[t].set(jnp.concatenate(
                            [widx[:, None], pdc, pec, puc, nparts[:, None],
                             consumed[:, None]], axis=1).astype(jnp.int64)))
            return (t + 1, arr, m, next_idx, lat_sum, accept, splits,
                    parked | unc, recs)

        init = (jnp.int64(0), arr0, m0, nx0, lat0, active0, sp0,
                jnp.zeros(S, dtype=bool), recs)
        (t, arr, m, next_idx, lat_sum, _active, splits, parked,
         recs) = lax.while_loop(cond, body, init)
        if band:
            trailer = jnp.zeros((len(REC_FIELDS), S), dtype=jnp.int32)
            trailer = (trailer.at[0].set(parked.astype(jnp.int32))
                       .at[1].set(t.astype(jnp.int32)))
            return recs[0].at[T].set(trailer)
        return (arr, m, next_idx, lat_sum, splits, parked, *recs, t)

    return init_state, loop


def _loop_params(n: int, p: int) -> tuple:
    """The arguments of :func:`_build_loop`'s loop in order: name, dtype and
    the shape of one row's value (None for the two scalars, ``b`` and
    ``zero``)."""
    f, i, b = np.float64, np.int64, np.bool_
    return (("delta", f, (n + 1,)), ("s", f, (p,)), ("b", f, None),
            ("zero", f, None), ("prefix", f, (n + 1,)), ("order", i, (p,)),
            ("bi_mode", b, ()), ("stop", f, ()), ("lat_limit", f, ()),
            ("active", b, ()), ("arr", f, (n, 5)), ("m", i, ()),
            ("next_idx", i, ()), ("lat_sum", f, ()), ("splits", i, ()),
            ("scale", f, ()), ("sbits", i, (p,)))


@functools.lru_cache(maxsize=None)
def _input_columns(n: int, p: int) -> tuple:
    """Where each loop argument lies in the two packed input blocks:
    ``({name: (block, column slice)}, (float columns, int columns))``.
    Block 0 is float64 and holds the float arguments, block 1 int64 the
    others, bools as 0/1; a scalar fills one column, repeated down it."""
    cols, ends = {}, [0, 0]
    for name, dtype, shape in _loop_params(n, p):
        blk = 0 if dtype is np.float64 else 1
        width = int(np.prod(shape or ()))
        cols[name] = (blk, slice(ends[blk], ends[blk] + width))
        ends[blk] += width
    return cols, tuple(ends)


def _fill_inputs(blocks: tuple, n: int, p: int, **values) -> None:
    """Write ``values`` (loop arguments by name, row-leading) into their
    columns of the host ``blocks``."""
    cols, _widths = _input_columns(n, p)
    for name, value in values.items():
        blk, sl = cols[name]
        rows = blocks[blk].shape[0]
        blocks[blk][:, sl] = (np.reshape(value, (rows, -1))
                              if np.ndim(value) else value)


def _pack_inputs(n: int, p: int, *args) -> tuple:
    """The loop's 17 arguments (:func:`_loop_params`) as two host blocks,
    float64 (S, F) and int64 (S, I): the certified program's inputs."""
    _cols, (F, I) = _input_columns(n, p)
    S = np.shape(args[0])[0]
    blocks = (np.empty((S, F)), np.empty((S, I), dtype=np.int64))
    _fill_inputs(blocks, n, p,
                 **{name: a for (name, _, _), a in zip(_loop_params(n, p),
                                                       args)})
    return blocks


def _unpack_inputs(n: int, p: int, fblock, iblock) -> tuple:
    """The loop's 17 arguments from the two packed blocks, by static
    slices, on the device or the host: floats and ints as they lie, bools
    as ``!= 0``, each scalar as the first element of its column."""
    cols, _widths = _input_columns(n, p)
    args = []
    for name, dtype, shape in _loop_params(n, p):
        blk, sl = cols[name]
        x = (fblock, iblock)[blk][:, sl]
        x = x[0, 0] if shape is None else x.reshape((x.shape[0],) + shape)
        args.append(x != 0 if dtype is np.bool_ else x)
    return tuple(args)


def _packed_inputs(loop: Callable, n: int, p: int) -> Callable:
    """``loop`` taking its arguments as the two blocks of
    :func:`_pack_inputs`: one upload each in place of 17.  The uncertified
    loop keeps its separate arguments, whose state buffers it donates."""
    def packed(fblock, iblock):
        return loop(*_unpack_inputs(n, p, fblock, iblock))

    return packed


@functools.lru_cache(maxsize=None)
def _get_loop(n: int, p: int, k: int, T: int, S: int,
              band: float = 0.0) -> Callable:
    """The jitted fused loop for static shape (n, p, k), cached per shape.
    Where it returns the state (``band == 0``), the five carried SoA state
    buffers (arr, m, next_idx, lat_sum, splits) are donated: XLA reuses
    their device buffers for the outputs.  The certified loop (``band >
    0``) takes the two packed blocks of :func:`_pack_inputs`."""
    import jax

    _init_state, loop = _build_loop(n, p, k, T, S, band)
    if band:
        loop = _packed_inputs(loop, n, p)
    return jax.jit(_named(f"fused_loop_k{k}", n, p, loop),
                   donate_argnums=_donated(band))


def _donated(band: float) -> tuple:
    """Arguments of the loop whose buffers its outputs reuse: the SoA state
    where the loop returns the state, none where it returns the record."""
    return () if band else (10, 11, 12, 13, 14)


def _named(name: str, n: int, p: int, fn: Callable) -> Callable:
    """``fn`` as the traced program ``name``: the jitted function carries
    the name (so its XLA module and dispatch events in a profile tell the
    programs apart) and its operations sit under ``jax.named_scope(name)``.
    Each trace is counted (:func:`trace_count`, :func:`traced_shapes`)."""
    import jax

    def program(*args):
        _TRACES[0] += 1  # Python-executes only while tracing
        _SHAPES.add((n, p))
        with jax.named_scope(name):
            return fn(*args)

    program.__name__ = program.__qualname__ = name
    return program


def _build_bisect(n: int, p: int, T: int, S: int, iters: int) -> Callable:
    """Build the UNJITTED fused H4 bisection for static shape (n, p): the
    probe at the upper latency bound plus a ``lax.scan`` over ``iters`` probe
    iterations — each probe an inline :func:`_build_loop` run — carrying the
    per-row (lo, hi) bound state and the best-so-far probe outcome.  One
    dispatch replaces the ~iters+1 per-probe dispatches of the host-driven
    binary search, with bit-identical updates: ``mid = 0.5 * (lo + hi)``,
    feasibility ``(period <= p_fix + eps) & (latency <= mid + eps)``, and the
    (latency, then period) best-probe tie-break all mirror
    ``batched._sp_bi_p_rowwise`` expression for expression.

    Returned callable (jitted by :func:`_get_bisect`, or sharded over the
    row axis by ``repro.core.sharded``):
        fn(delta, s, b, zero, prefix, order, p_fix, lo0, hi0, active0)
        -> (items0, m0, sp0, per0, lat0, feas0,
            best_items, best_m, best_sp, best_per, best_lat)
    with items* (S, n, 3) in the ``_BatchState`` (d, e, proc) layout.
    """
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    from jax import lax

    init_state, loop = _build_loop(n, p, 1, T, S)

    def fn(delta, s, b, zero, prefix, order, p_fix, lo0, hi0, active0):
        all_bi = jnp.ones(S, dtype=bool)
        tail = delta[:, n] / b

        def probe(limits, act):
            st0 = init_state(delta, s, b, prefix, order)
            arr, m, _nx, lat_sum, splits, *_rest = loop(
                delta, s, b, zero, prefix, order, all_bi, p_fix, limits,
                act, *st0, jnp.zeros(S), jnp.zeros((S, p), dtype=jnp.int64))
            per = arr[:, :, 3].max(axis=1)
            lat = lat_sum + tail
            feas = (per <= p_fix + _EPS) & (lat <= limits + _EPS)
            return arr, m, splits, per, lat, feas

        # Ensure feasibility at the upper end first (the rowwise path's
        # probe0); its state seeds both the failure outputs and `best`.
        arr0, m0, sp0, per0, lat0, feas0 = probe(hi0, active0)
        alive = feas0 & active0

        def body(carry, _):
            lo, hi, b_it, b_m, b_sp, b_per, b_lat = carry
            mid = 0.5 * (lo + hi)
            arr, m, sp, per, lat, feas = probe(mid, alive)
            good = alive & feas
            hi = jnp.where(good, mid, hi)
            lo = jnp.where(alive & ~feas, mid, lo)
            better = good & ((lat < b_lat - _EPS)
                             | ((jnp.abs(lat - b_lat) <= _EPS)
                                & (per < b_per)))
            bc = better[:, None, None]
            return (lo, hi, jnp.where(bc, arr[:, :, :3], b_it),
                    jnp.where(better, m, b_m), jnp.where(better, sp, b_sp),
                    jnp.where(better, per, b_per),
                    jnp.where(better, lat, b_lat)), None

        init = (lo0, hi0, arr0[:, :, :3], m0, sp0, per0, lat0)
        (_lo, _hi, b_it, b_m, b_sp, b_per, b_lat), _ = lax.scan(
            body, init, None, length=iters)
        return (arr0[:, :, :3], m0, sp0, per0, lat0, feas0,
                b_it, b_m, b_sp, b_per, b_lat)

    return fn


@functools.lru_cache(maxsize=None)
def _get_bisect(n: int, p: int, T: int, S: int, iters: int) -> Callable:
    """The jitted fused H4 bisection, cached per shape (see
    :func:`_build_bisect` for the program's contract)."""
    import jax

    return jax.jit(_named("fused_bisect", n, p,
                          _build_bisect(n, p, T, S, iters)))


def run_fused(state, k: int, bi_mode: np.ndarray, stop: np.ndarray,
              lat_limit: np.ndarray, record: Optional[Callable] = None) -> None:
    """Run the fused loop over ``state`` (a ``batched._BatchState``) on the
    default device — a drop-in replacement for the numpy ``_run_loop`` body
    with O(1) dispatches where the device's float64 is IEEE."""
    n, p = state.pb.n, state.pb.p
    S = chunk_rows(n, k)
    band = device_band()
    run_loop(state, k, bi_mode, stop, lat_limit, record, S,
             lambda T: _get_loop(n, p, k, T, S, band), band, _DISPATCHES,
             _TRANSFER)


def run_loop(state, k: int, bi_mode, stop, lat_limit, record, S: int,
             get_program: Callable, band: float, dispatches: list,
             transfer: dict) -> None:
    """Host driver shared by the fused and sharded engines: run the traced
    loop ``get_program(T)`` over ``state``'s active rows in chunks of ``S``,
    counting each dispatch in ``dispatches[0]``, and the bytes of its
    arguments and outputs, its uploads and its copies back in ``transfer``
    (the engine's own counters).

    ``band == 0``: one dispatch per chunk, 17 arguments; the device's final
    state and per-iteration records are written back as they are.  ``band >
    0`` (certified loop): the program takes a chunk's inputs as two packed
    blocks (:func:`_pack_inputs`), its rows gathered from blocks packed once
    a call with their current state written in, and returns its decisions
    alone, one packed int32 record (:data:`REC_FIELDS`) fetched in one copy;
    they are replayed on the host in float64 with the numpy engine's own
    ``_apply_splits``, so every float is the numpy engine's; rows the device
    parked take one float64 step of the numpy loop and go back to the
    device, until no row is active.  Either way ``record`` sees the numpy
    engine's lockstep sequence.

    Each dispatch runs under the spans ``fused.launch`` and ``fused.fetch``
    (with ``fused.wait`` split off while a profiler records), the host's
    use of its outputs under ``fused.replay``, each parked round under
    ``fused.parked_step`` and the final replay under ``fused.record``
    (:mod:`repro.core.spans`).
    """
    from .batched import _apply_splits, _numpy_loop

    pb = state.pb
    B, n, p = pb.B, pb.n, pb.p
    T = min(n - 1, p - 1)
    if T <= 0 or not state.active.any():
        state.active[:] = False
        return
    fn = get_program(T)
    b = np.float64(pb.b)
    bi_mode = np.asarray(bi_mode, dtype=bool)
    stop = np.asarray(stop, dtype=np.float64)
    lat_limit = np.asarray(lat_limit, dtype=np.float64)
    scale = np.zeros(B)
    sbits = np.zeros((B, p), dtype=np.int64)
    if band:
        # bounds every period/latency quantity of the row: all work on the
        # slowest processor plus every transfer twice
        scale = ((pb.prefix[:, n] - pb.prefix[:, 0]) / pb.s.min(axis=1)
                 + (n + 2) * pb.delta.max(axis=1) / b)
        sbits = np.ascontiguousarray(pb.s).view(np.int64)
        # every row's inputs packed once a call; a chunk gathers its rows
        # and writes in their current state
        static = _pack_inputs(n, p, pb.delta, pb.s, b, 0.0, pb.prefix,
                              pb.order, bi_mode, stop, lat_limit,
                              state.active, state.arr, state.m,
                              state.next_idx, state.lat_sum, state.splits,
                              scale, sbits)
    base = state.splits.copy()
    steps = []       # (rows, split index per row, period, latency)

    def note(rows, pers, lats):
        steps.append((rows, state.splits[rows] - base[rows] - 1, pers, lats))

    todo = np.nonzero(state.active)[0]
    while todo.size:
        parked = []
        for lo in range(0, todo.size, S):
            with span("fused.launch"):
                rows = todo[lo:lo + S]
                r = rows.size
                sel = np.concatenate([rows, np.repeat(rows[:1], S - r)])
                act = np.zeros(S, dtype=bool)
                act[:r] = True
                dispatches[0] += 1
                if band:
                    blocks = (static[0][sel], static[1][sel])
                    _fill_inputs(blocks, n, p, active=act,
                                 arr=state.arr[sel], m=state.m[sel],
                                 next_idx=state.next_idx[sel],
                                 lat_sum=state.lat_sum[sel],
                                 splits=state.splits[sel])
                    out = _call(fn, transfer, *blocks)
                else:
                    # the SoA state slices are fresh fancy-index copies,
                    # safe to donate
                    out = _call(fn, transfer, pb.delta[sel], pb.s[sel], b,
                                np.float64(0.0), pb.prefix[sel],
                                pb.order[sel].astype(np.int64), bi_mode[sel],
                                stop[sel], lat_limit[sel], act,
                                state.arr[sel], state.m[sel],
                                state.next_idx[sel], state.lat_sum[sel],
                                state.splits[sel], scale[sel], sbits[sel])
            if not band:
                (arr, m, next_idx, lat_sum, splits, _park, per_rec, lat_rec,
                 acc_rec, _dec, t_used) = _fetch(out, transfer)
                with span("fused.replay"):
                    state.active[rows] = False
                    state.arr[rows] = arr[:r]
                    state.m[rows] = m[:r]
                    state.next_idx[rows] = next_idx[:r]
                    state.lat_sum[rows] = lat_sum[:r]
                    state.splits[rows] = splits[:r]
                    for t in range(int(t_used.max())):
                        a = acc_rec[t, :r]
                        if a.any():
                            steps.append((rows[a], np.full(a.sum(), t),
                                          per_rec[t, :r][a],
                                          lat_rec[t, :r][a]))
                continue
            (rec,) = _fetch((out,), transfer)
            with span("fused.replay"):
                state.active[rows] = False
                rec = rec[:, :, :r].transpose(0, 2, 1)   # (T + 1, r, fields)
                for t in range(int(rec[T, :, 1].max())):
                    a = rec[t, :, -1] != 0
                    if not a.any():
                        continue
                    acc = rows[a]
                    dec = rec[t, a, :-1].astype(np.int64)
                    _DECISIONS["device"] += acc.size
                    _apply_splits(state, acc, dec[:, 0], dec[:, 1:4],
                                  dec[:, 4:7], dec[:, 7:10], dec[:, 10],
                                  dec[:, 11])
                    note(acc, state.arr[acc, :, 3].max(axis=1),
                         state.lat_sum[acc] + state.tail[acc])
                parked.append(rows[rec[T, :, 0] != 0])
        todo = np.concatenate(parked) if parked else todo[:0]
        if todo.size:
            # the decisions the device could not certify, made in float64
            with span("fused.parked_step"):
                _DECISIONS["host"] += todo.size
                state.active[todo] = True
                _numpy_loop(state, todo, k, bi_mode, stop, lat_limit,
                            "numpy", note, max_iters=1)
                todo = todo[state.active[todo]]
    if record is None or not steps:
        return
    # Replay in the numpy engine's lockstep order: a row's s-th accepted
    # split lands at iteration s, whichever chunk, round or side decided it.
    with span("fused.record"):
        rows = np.concatenate([x[0] for x in steps])
        it = np.concatenate([x[1] for x in steps])
        pers = np.concatenate([x[2] for x in steps])
        lats = np.concatenate([x[3] for x in steps])
        order = np.lexsort((rows, it))
        rows, it, pers, lats = (rows[order], it[order], pers[order],
                                lats[order])
        for t in np.unique(it):
            sel = it == t
            record(rows[sel], pers[sel], lats[sel])


def _call(fn: Callable, transfer: dict, *args):
    """``fn(*args)``, the ``nbytes`` of its arguments counted into
    ``transfer["to_device"]`` and their number, one upload each, into
    ``transfer["uploads"]``; the host arrays live only for the call, as
    when they are passed to ``fn`` directly."""
    transfer["to_device"] += sum(a.nbytes for a in args)
    transfer["uploads"] += len(args)
    return fn(*args)


def _fetch(out, transfer: dict) -> list:
    """A program call's outputs on the host (``fused.fetch``), their
    ``nbytes`` counted into ``transfer["to_host"]`` and their number into
    ``transfer["copies"]``.  While a profiler records
    (:func:`repro.core.spans.recording`) the wait for the device is split off
    first as ``fused.wait``; otherwise the first conversion waits, as a plain
    ``np.asarray`` does, and the hot path is unchanged."""
    if recording():
        import jax

        with span("fused.wait"):
            jax.block_until_ready(out)
    with span("fused.fetch"):
        host = [np.asarray(o) for o in out]
    transfer["to_host"] += sum(h.nbytes for h in host)
    transfer["copies"] += len(host)
    return host


def run_fused_bisection(pb, p_fix: np.ndarray, lo: np.ndarray, hi: np.ndarray,
                        iters: int) -> dict:
    """Run the ENTIRE H4 binary search device-resident: one jitted
    probe0 + ``lax.scan`` program per row-chunk (O(1) host dispatches per
    campaign instead of ~iters+1), bit-identical to the host-driven search.

    ``pb`` is a ``batched.ProblemBatch``; returns per-row numpy arrays:
    ``items0/m0/sp0/per0/lat0/feas0`` (the probe-at-``hi`` state — the
    failure outputs) and ``items/m/sp/per/lat`` (the best feasible probe).
    The caller (``batched._sp_bi_p_fused``) assembles HeuristicResults.
    """
    B, n, p = pb.B, pb.n, pb.p
    T = min(n - 1, p - 1)
    if T <= 0:
        raise ValueError("unsplittable shape: caller should use the host path")
    S = chunk_rows(n, 1)
    fn = _get_bisect(n, p, T, S, int(iters))
    b = np.float64(pb.b)
    p_fix = np.asarray(p_fix, dtype=np.float64)
    lo = np.asarray(lo, dtype=np.float64)
    hi = np.asarray(hi, dtype=np.float64)
    out = {
        "items0": np.zeros((B, n, 3)), "m0": np.zeros(B, dtype=np.int64),
        "sp0": np.zeros(B, dtype=np.int64), "per0": np.zeros(B),
        "lat0": np.zeros(B), "feas0": np.zeros(B, dtype=bool),
        "items": np.zeros((B, n, 3)), "m": np.zeros(B, dtype=np.int64),
        "sp": np.zeros(B, dtype=np.int64), "per": np.zeros(B),
        "lat": np.zeros(B),
    }
    names = ("items0", "m0", "sp0", "per0", "lat0", "feas0",
             "items", "m", "sp", "per", "lat")
    for lo_i in range(0, B, S):
        rows = np.arange(lo_i, min(lo_i + S, B))
        pad = S - rows.size
        sel = (np.concatenate([rows, np.zeros(pad, dtype=np.int64)])
               if pad else rows)
        act = np.zeros(S, dtype=bool)
        act[:rows.size] = True
        _DISPATCHES[0] += 1
        with span("fused.launch"):
            res = _call(fn, _TRANSFER, pb.delta[sel], pb.s[sel], b,
                        np.float64(0.0), pb.prefix[sel],
                        pb.order[sel].astype(np.int64), p_fix[sel], lo[sel],
                        hi[sel], act)
        for name, val in zip(names, _fetch(res, _TRANSFER)):
            out[name][rows] = val[:rows.size]
    return out
