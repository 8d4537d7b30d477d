"""Pallas split-scoring kernels: candidate-grid evaluation as masked tiles.

The chains-to-chains split scoring at the heart of the H1-H6 heuristics is a
masked-tile reduction: per batch row, a contiguous band of candidate lanes
(cuts of the worst interval) is live and everything beyond it is padding —
exactly the shape the repo's attention kernels handle with ``pl.when``
tile skipping.  This module implements the shared scoring kernels of
:mod:`repro.core.heuristics` as real ``pl.pallas_call`` kernels:

  - :func:`score_2way_pallas` — every 2-way split of the worst interval,
    both placement orders.  Lanes are (row, cut) tiles of ``block_a x
    block_k``; a per-row ``need`` column (the row's live cut count — 2-way
    cut lanes are span-prefix-valid) lets whole tiles beyond every row's
    span skip compute and zero-fill via ``pl.when``, mirroring the fused
    engine's span bucketing at tile granularity.
  - :func:`score_3way_pallas` — all (c1, c2) cut pairs x 6 processor
    permutations.  Pair lanes are laid out r1-major (the caller's triu
    order), so ``need`` carries the per-row last-valid-lane bound
    (:func:`pair_need`) and out-of-band tiles skip the same way.

Equivalence contract: inside the live lanes the kernels evaluate the SAME
expressions as ``score_2way_kernel``/``score_3way_kernel`` — including the
runtime-``zero`` FMA guard and the left-associated 3-part latency sum — so
in interpret mode (CPU; op-by-op float64 execution) outputs are bit-identical
to the numpy kernels on every live lane.  Skipped tiles are zero-filled;
callers mask them out of candidate selection by the same validity masks that
already exclude them on the numpy path, so heuristic outputs are identical
(asserted by the ``pallas`` column of tests/test_engine_equivalence.py).
Out of interpret mode the kernels compile for TPU/GPU and score in float32
(Mosaic has no float64), so the float64 contract is out of scope there; the
per-row ``need`` bounds and every block index are int32 on both paths, which
is what lets them compile while the process has ``jax_enable_x64`` on.

Selected behind ``repro.core.heuristics.score_kernels("pallas")`` —
``repro.core.batched`` exposes it as ``backend="pallas"``.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _interpret() -> bool:
    """Interpret (emulate) off-device: CPU runs op-by-op in float64, which is
    what the bit-identity contract is asserted on."""
    return jax.default_backend() not in ("tpu", "gpu")


def _score_dtype(interpret: bool, dtype):
    """Dtype the kernels score in: the caller's (float64) in interpret mode,
    float32 when compiled for a device."""
    return dtype if interpret else jnp.float32


# block indices must be int32 for Mosaic: a bare literal 0 in an index map
# would trace as int64 under jax_enable_x64
_I0 = np.int32(0)


def _ensure_x64() -> None:
    """The bit-identity contract is float64: callers may invoke these kernels
    before anything else has flipped jax's x64 switch."""
    jax.config.update("jax_enable_x64", True)


def _ceil_to(a: int, m: int) -> int:
    return -(-a // m) * m


def pair_need(span, lanes: int):
    """Last-valid-lane bound (exclusive) per row for the r1-major (c1, c2)
    pair layout of ``lanes``-span grids: a row of span ``s`` has its last
    valid pair (r1, r2) = (s-3, s-2) at index ``(s-3)(L-2) - (s-3)(s-4)/2``
    (pairs are prefix-dense in r1-groups).  Rows with span < 3 need 0 lanes.
    """
    span = np.asarray(span, dtype=np.int64)
    o1 = np.maximum(span - 3, 0)
    need = o1 * (lanes - 2) - o1 * (o1 - 1) // 2 + 1
    return np.where(span >= 3, need, 0)


# ---------------------------------------------------------------------------
# 2-way kernel
# ---------------------------------------------------------------------------

def _score2_kernel(pre_d1_ref, pre_C_ref, pre_e_ref, del_d1_ref, del_C_ref,
                   del_e_ref, inv_j_ref, inv_p_ref, b_ref, zero_ref, need_ref,
                   cyc1a_ref, cyc1b_ref, cyc2a_ref, cyc2b_ref,
                   dlata_ref, dlatb_ref, *, block_k: int):
    lane0 = pl.program_id(1) * block_k
    # live-lane bound of this row tile: cut lanes are span-prefix-valid, so
    # tiles starting at or past every row's span carry only masked lanes
    tile_need = jnp.max(need_ref[...])

    @pl.when(lane0 < tile_need)
    def _compute():
        b = b_ref[0, 0]
        zero = zero_ref[0, 0]
        W1 = pre_C_ref[...] - pre_d1_ref[...]
        W2 = pre_e_ref[...] - pre_C_ref[...]
        dIn = del_d1_ref[...] / b
        dMid = del_C_ref[...] / b
        dOut = del_e_ref[...] / b
        inv_j = inv_j_ref[...]
        inv_p = inv_p_ref[...]
        # order A: first part stays on j; order B: swapped.  Same guarded
        # expressions as heuristics.score_2way_kernel, element for element.
        cyc1a_ref[...] = dIn + (W1 * inv_j + zero) + dMid
        cyc1b_ref[...] = dIn + (W1 * inv_p + zero) + dMid
        cyc2a_ref[...] = dMid + (W2 * inv_p + zero) + dOut
        cyc2b_ref[...] = dMid + (W2 * inv_j + zero) + dOut
        dlata_ref[...] = dMid + (W2 * (inv_p - inv_j) + zero)
        dlatb_ref[...] = dMid + (W1 * (inv_p - inv_j) + zero)

    @pl.when(lane0 >= tile_need)
    def _masked():
        for ref in (cyc1a_ref, cyc1b_ref, cyc2a_ref, cyc2b_ref,
                    dlata_ref, dlatb_ref):
            ref[...] = jnp.zeros_like(ref)


@functools.partial(jax.jit, static_argnames=("interpret", "block_a", "block_k"))
def _score2_call(pre_d1, pre_C, pre_e, del_d1, del_C, del_e, b, inv_j, inv_p,
                 zero, need, interpret, block_a, block_k):
    A, K = pre_C.shape
    Ap, Kp = _ceil_to(A, block_a), _ceil_to(K, block_k)
    pad_l = ((0, Ap - A), (0, Kp - K))
    pad_c = ((0, Ap - A), (0, 0))
    lanes = [jnp.pad(x, pad_l) for x in (pre_C, del_C)]
    cols = [jnp.pad(jnp.broadcast_to(x, (A, 1)), pad_c)
            for x in (pre_d1, pre_e, del_d1, del_e, inv_j, inv_p)]
    need_p = jnp.pad(need.reshape(A, 1), pad_c)
    scal = [jnp.reshape(x, (1, 1)) for x in (b, zero)]
    lanespec = pl.BlockSpec((block_a, block_k), lambda i, j: (i, j))
    colspec = pl.BlockSpec((block_a, 1), lambda i, j: (i, _I0))
    scalspec = pl.BlockSpec((1, 1), lambda i, j: (_I0, _I0))
    outs = pl.pallas_call(
        functools.partial(_score2_kernel, block_k=block_k),
        grid=(Ap // block_a, Kp // block_k),
        in_specs=[colspec, lanespec, colspec, colspec, lanespec, colspec,
                  colspec, colspec, scalspec, scalspec, colspec],
        out_specs=[lanespec] * 6,
        out_shape=[jax.ShapeDtypeStruct((Ap, Kp), pre_C.dtype)] * 6,
        interpret=interpret,
    )(cols[0], lanes[0], cols[1], cols[2], lanes[1], cols[3], cols[4],
      cols[5], *scal, need_p)
    cyc1a, cyc1b, cyc2a, cyc2b, dlata, dlatb = (o[:A, :K] for o in outs)
    return (jnp.concatenate([cyc1a, cyc1b], axis=-1),
            jnp.concatenate([cyc2a, cyc2b], axis=-1),
            jnp.concatenate([dlata, dlatb], axis=-1))


def score_2way_pallas(pre_d1, pre_C, pre_e, delta_d1, delta_C, delta_e, b,
                      inv_j, inv_p, *, zero=0.0, need=None, interpret=None,
                      block_a: int = 8, block_k: int = 128):
    """Pallas mirror of ``heuristics.score_2way_kernel`` (batched shapes:
    lanes (A, K), interval-end columns (A, 1)).  ``need`` is the per-row
    live-cut count (``e - d``); lanes at or past it sit in skippable tiles.
    Returns ``(cyc1, cyc2, dlat)`` with both placement orders concatenated
    along the last axis, exactly like the shared kernel."""
    _ensure_x64()
    pre_C = jnp.asarray(pre_C)
    A, K = pre_C.shape
    if interpret is None:
        interpret = _interpret()
    if need is None:
        need = np.full(A, K)
    dt = _score_dtype(interpret, pre_C.dtype)
    f = functools.partial(jnp.asarray, dtype=dt)
    return _score2_call(f(pre_d1), f(pre_C), f(pre_e), f(delta_d1),
                        f(delta_C), f(delta_e), f(b), f(inv_j), f(inv_p),
                        f(zero), jnp.asarray(need, jnp.int32), interpret,
                        int(block_a), int(block_k))


# ---------------------------------------------------------------------------
# 3-way kernel
# ---------------------------------------------------------------------------

def _score3_kernel(dI_ref, W_ref, dO_ref, invp_ref, base_ref, zero_ref,
                   need_ref, cyc_ref, dlat_ref, mx_ref, *, block_k: int):
    lane0 = pl.program_id(1) * block_k
    tile_need = jnp.max(need_ref[...])

    @pl.when(lane0 < tile_need)
    def _compute():
        zero = zero_ref[0, 0]
        dI = dI_ref[...][:, None, :, :]          # (BA, 1, 3, BK)
        W = W_ref[...][:, None, :, :]
        dO = dO_ref[...][:, None, :, :]
        invp = invp_ref[...][:, :, :, None]      # (BA, 6, 3, 1)
        base = base_ref[...][:, :, None]         # (BA, 1, 1)
        # same guarded expressions as heuristics.score_3way_kernel: the part
        # sum is spelled left-associated so traced reductions keep numpy's
        # element order
        comp = dI + (W * invp + zero)
        cyc = comp + dO
        cyc_ref[...] = cyc
        dlat_ref[...] = (comp[..., 0, :] + comp[..., 1, :]
                         + comp[..., 2, :]) - base
        mx_ref[...] = cyc.max(axis=-2)

    @pl.when(lane0 >= tile_need)
    def _masked():
        for ref in (cyc_ref, dlat_ref, mx_ref):
            ref[...] = jnp.zeros_like(ref)


@functools.partial(jax.jit, static_argnames=("interpret", "block_a", "block_k"))
def _score3_call(dI, W, dO, invp, base_term, zero, need, interpret,
                 block_a, block_k):
    A, _, K = dI.shape
    Ap, Kp = _ceil_to(A, block_a), _ceil_to(K, block_k)
    pad_l = ((0, Ap - A), (0, 0), (0, Kp - K))
    lanes = [jnp.pad(x, pad_l) for x in (dI, W, dO)]
    invp_p = jnp.pad(invp, ((0, Ap - A), (0, 0), (0, 0)))
    base_p = jnp.pad(base_term.reshape(A, 1), ((0, Ap - A), (0, 0)))
    need_p = jnp.pad(need.reshape(A, 1), ((0, Ap - A), (0, 0)))
    lanespec = pl.BlockSpec((block_a, 3, block_k), lambda i, j: (i, _I0, j))
    permspec = pl.BlockSpec((block_a, 6, 3), lambda i, j: (i, _I0, _I0))
    colspec = pl.BlockSpec((block_a, 1), lambda i, j: (i, _I0))
    scalspec = pl.BlockSpec((1, 1), lambda i, j: (_I0, _I0))
    outs = pl.pallas_call(
        functools.partial(_score3_kernel, block_k=block_k),
        grid=(Ap // block_a, Kp // block_k),
        in_specs=[lanespec, lanespec, lanespec, permspec, colspec, scalspec,
                  colspec],
        out_specs=[
            pl.BlockSpec((block_a, 6, 3, block_k),
                         lambda i, j: (i, _I0, _I0, j)),
            pl.BlockSpec((block_a, 6, block_k), lambda i, j: (i, _I0, j)),
            pl.BlockSpec((block_a, 6, block_k), lambda i, j: (i, _I0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Ap, 6, 3, Kp), dI.dtype),
            jax.ShapeDtypeStruct((Ap, 6, Kp), dI.dtype),
            jax.ShapeDtypeStruct((Ap, 6, Kp), dI.dtype),
        ],
        interpret=interpret,
    )(*lanes, invp_p, base_p, jnp.reshape(zero, (1, 1)), need_p)
    cyc, dlat, mx = outs
    return cyc[:A, :, :, :K], dlat[:A, :, :K], mx[:A, :, :K]


def score_3way_pallas(dI, W, dO, invp, base_term, *, zero=0.0, need=None,
                      interpret=None, block_a: int = 8, block_k: int = 128):
    """Pallas mirror of ``heuristics.score_3way_kernel`` for the batched
    call shapes: ``dI``/``W``/``dO`` (A, 1, 3, K) carrying the three parts on
    axis -2 and the r1-major (c1, c2) pair lanes on axis -1, ``invp``
    (A, 6, 3, 1), ``base_term`` (A, 1, 1).  ``need`` is the per-row
    last-valid-lane bound (:func:`pair_need`).  Returns ``(cyc, dlat, mx)``
    shaped (A, 6, 3, K) / (A, 6, K) / (A, 6, K) like the shared kernel."""
    _ensure_x64()
    dI = jnp.asarray(dI)
    A = dI.shape[0]
    K = dI.shape[-1]
    if interpret is None:
        interpret = _interpret()
    if need is None:
        need = np.full(A, K)
    dt = _score_dtype(interpret, dI.dtype)
    f = functools.partial(jnp.asarray, dtype=dt)
    return _score3_call(f(dI).reshape(A, 3, K), f(W).reshape(A, 3, K),
                        f(dO).reshape(A, 3, K), f(invp).reshape(A, 6, 3),
                        f(base_term), f(zero), jnp.asarray(need, jnp.int32),
                        interpret, int(block_a), int(block_k))
