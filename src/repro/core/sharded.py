"""Multi-device sharded campaign engine: the fused loop under ``shard_map``.

The fused engine (:mod:`repro.core.fused`) runs the entire H1–H6 lockstep
splitting loop as one jitted ``lax.while_loop`` — O(1) host dispatches — but
on a single device.  A campaign over a replication study (seed banks x
families x bound grids) is embarrassingly parallel across stacked instances,
so this module shards the INSTANCE axis of that same loop across every
available device via ``jax.sharding.Mesh`` + ``shard_map``: one SPMD program
where each device runs the identical fused loop over its local rows.

Design:

  - The traced program is literally ``fused._build_loop``'s loop, wrapped in
    ``shard_map`` over a 1-D device mesh along the row axis.  No collectives
    are needed: rows never interact, and the only cross-row expressions in
    the loop — the bucket-routing ``max(need)`` and the ``active.any()``
    exit test — are intentionally evaluated PER SHARD.  Bucket choice cannot
    change results (every bucket covering a row's span scores the same valid
    lanes, and tie-break keys use absolute positions — see fused.py), so a
    shard routing to a smaller bucket than its neighbors is pure savings,
    and a shard whose rows all converge simply exits its while-loop early.
  - Batches are padded to a device multiple with INERT rows: padding rows
    replicate row 0's instance data but start inactive (``active0=False``),
    so ``live`` is False for them in every iteration, they accept no splits,
    and their state is discarded on write-back — the same trick the fused
    engine already uses for its row-chunk padding (property-tested in
    tests/test_engine_properties.py).
  - Per-device rows-per-dispatch reuses :func:`fused.chunk_rows`, so the
    per-shard lane budget matches the single-device engine and the global
    chunk is ``chunk_rows(n, k) * num_devices``.

Equivalence contract: bit-identical (``==``, not approx) to
``backend="fused"`` — and therefore to the numpy/scalar reference — because
each row's floats are produced by the exact same traced expressions on
per-row data, with the same FMA guard and left-associated reductions; the
device mesh only changes WHERE a row is computed, never what is computed.
Asserted across the full differential harness by
tests/test_engine_equivalence.py and on multi-device meshes by the CI job
running under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Use via ``backend="sharded"`` on any :mod:`repro.core.batched` entry point,
``engine="sharded"`` in ``repro.sim.experiments``, or
``ReplanService(backend="sharded")`` in the fleet layer.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import numpy as np

from . import fused
from .fused import chunk_rows
from .spans import span

__all__ = ["sharded_available", "device_count", "run_sharded",
           "run_sharded_bisection", "trace_count", "reset_trace_count",
           "dispatch_count", "reset_dispatch_count", "transfer_bytes",
           "fetch_copies", "launch_copies", "output_devices"]

# traces / dispatches of the SPMD programs, mirroring fused.py's counters
# (the shared bucket branches still count into fused._BUCKET_TRACES).
_TRACES = [0]
_DISPATCHES = [0]
# bytes of every argument and output of the SPMD programs' calls, their
# uploads and their copies back, as fused.transfer_bytes,
# fused.launch_copies and fused.fetch_copies count the fused engine's
_TRANSFER = {"to_device": 0, "to_host": 0, "uploads": 0, "copies": 0}
# devices the outputs of the latest dispatch are laid out over
_OUT_DEVICES = [0]


def trace_count() -> int:
    """Traces of the sharded SPMD programs since the last reset."""
    return _TRACES[0]


def reset_trace_count() -> None:
    _TRACES[0] = 0


def dispatch_count() -> int:
    """SPMD-program dispatches since the last reset — one per global
    row-chunk, independent of device count (the O(1)-dispatch contract
    carries over from the fused engine)."""
    return _DISPATCHES[0]


def reset_dispatch_count() -> None:
    _DISPATCHES[0] = 0
    _TRANSFER.update(to_device=0, to_host=0, uploads=0, copies=0)


def transfer_bytes() -> dict:
    """``to_device`` and ``to_host`` bytes of the SPMD programs' calls
    since :func:`reset_dispatch_count`, as :func:`fused.transfer_bytes`
    counts the fused engine's: over :func:`dispatch_count`, per call."""
    return {k: _TRANSFER[k] for k in ("to_device", "to_host")}


def fetch_copies() -> int:
    """Device-to-host copies of the SPMD programs' outputs since
    :func:`reset_dispatch_count`, as :func:`fused.fetch_copies` counts the
    fused engine's."""
    return _TRANSFER["copies"]


def launch_copies() -> int:
    """Host arrays uploaded to the SPMD programs since
    :func:`reset_dispatch_count`, as :func:`fused.launch_copies` counts the
    fused engine's."""
    return _TRANSFER["uploads"]


def output_devices() -> int:
    """Devices spanned by the outputs of the latest SPMD dispatch (0 before
    the first): the check that a campaign really ran across the mesh."""
    return _OUT_DEVICES[0]


def sharded_available() -> bool:
    try:
        import jax
    except Exception:  # pragma: no cover - jax is baked into the image
        return False
    return hasattr(jax, "shard_map")


def device_count() -> int:
    """Devices in the default mesh (respects
    ``--xla_force_host_platform_device_count`` on CPU)."""
    import jax

    return len(jax.devices())


@functools.lru_cache(maxsize=None)
def _mesh():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), ("i",))


def _shard_wrap(fn: Callable, band: float, mesh) -> Callable:
    """Wrap ``fused._build_loop``'s unjitted loop (built at ``band``) in
    ``shard_map`` over the row axis; certified, the loop takes the two
    packed blocks of ``fused._pack_inputs``, sharded along rows as well.

    Uncertified (``band == 0``) it returns ``(*state..., *records..., t)``:
    six row-leading state outputs, four (T, S_local, ...) records and a
    per-shard scalar ``t``, which comes back broadcast per row so the host
    can take the global max.  Certified it returns the packed record
    (T + 1, fields, S_local), whose trailer already holds ``t`` per row.
    Scalar inputs (0-d) are replicated; every other input is sharded along
    its leading axis.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    row = P("i")
    out_specs = (P(None, None, "i") if band
                 else (row,) * 6 + (P(None, "i"),) * 4 + (row,))

    def local(*args):
        out = fn(*args)
        if band:
            return out
        t_rows = jnp.full((out[0].shape[0],), out[-1], dtype=jnp.int64)
        return (*out[:-1], t_rows)

    def specs_for(args):
        return tuple(P() if np.ndim(a) == 0 else row for a in args)

    def wrapped(*args):
        _TRACES[0] += 1  # Python-executes only while tracing
        body = jax.shard_map(local, mesh=mesh, in_specs=specs_for(args),
                             out_specs=out_specs, check_vma=False)
        return body(*args)

    return wrapped


@functools.lru_cache(maxsize=None)
def _get_sharded_loop(n: int, p: int, k: int, T: int, S_local: int,
                      band: float = 0.0) -> Callable:
    """The jitted SPMD fused loop for static shape (n, p, k): per-shard rows
    ``S_local``, global rows ``S_local * device_count()``.  SoA state buffers
    donated where the loop returns the state, and the two packed input
    blocks taken where it is certified, exactly like ``fused._get_loop``."""
    import jax

    _init_state, loop = fused._build_loop(n, p, k, T, S_local, band)
    if band:
        loop = fused._packed_inputs(loop, n, p)
    jitted = jax.jit(_shard_wrap(loop, band, _mesh()),
                     donate_argnums=fused._donated(band))

    def run(*args):
        out = jitted(*args)
        _OUT_DEVICES[0] = len((out if band else out[0]).sharding.device_set)
        return out

    return run


@functools.lru_cache(maxsize=None)
def _get_sharded_bisect(n: int, p: int, T: int, S_local: int,
                        iters: int) -> Callable:
    """The jitted SPMD H4 bisection (probe0 + ``lax.scan``) — the per-shard
    program is ``fused._build_bisect``'s, sharded over the row axis."""
    import jax
    from jax.sharding import PartitionSpec as P

    fn = fused._build_bisect(n, p, T, S_local, iters)
    mesh = _mesh()
    row = P("i")

    def wrapped(*args):
        _TRACES[0] += 1  # Python-executes only while tracing
        in_specs = tuple(P() if np.ndim(a) == 0 else row for a in args)
        body = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=(row,) * 11, check_vma=False)
        return body(*args)

    return jax.jit(wrapped)


def run_sharded(state, k: int, bi_mode: np.ndarray, stop: np.ndarray,
                lat_limit: np.ndarray, record: Optional[Callable] = None) -> None:
    """Run the fused loop over ``state`` (a ``batched._BatchState``) as one
    SPMD program per global row-chunk, sharded across all devices.  Drop-in
    replacement for :func:`fused.run_fused` — same driver
    (:func:`fused.run_loop`), same write-back and record replay,
    bit-identical floats on any device count.  Padding rows of a chunk
    start INACTIVE, so they are live in no iteration and their state is
    never written back.
    """
    n, p = state.pb.n, state.pb.p
    D = device_count()
    S_local = chunk_rows(n, k)
    band = fused.device_band()
    fused.run_loop(state, k, bi_mode, stop, lat_limit, record, S_local * D,
                   lambda T: _get_sharded_loop(n, p, k, T, S_local, band),
                   band, _DISPATCHES, _TRANSFER)


def run_sharded_bisection(pb, p_fix: np.ndarray, lo: np.ndarray,
                          hi: np.ndarray, iters: int) -> dict:
    """The fused H4 binary search (probe0 + ``lax.scan``) as one SPMD
    program per global row-chunk — :func:`fused.run_fused_bisection` sharded
    across the device mesh, same outputs bit-for-bit."""
    B, n, p = pb.B, pb.n, pb.p
    T = min(n - 1, p - 1)
    if T <= 0:
        raise ValueError("unsplittable shape: caller should use the host path")
    D = device_count()
    S_local = chunk_rows(n, 1)
    S = S_local * D
    fn = _get_sharded_bisect(n, p, T, S_local, int(iters))
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
            res = fused._call(fn, _TRANSFER, pb.delta[sel], pb.s[sel], b,
                              np.float64(0.0), pb.prefix[sel],
                              pb.order[sel].astype(np.int64), p_fix[sel],
                              lo[sel], hi[sel], act)
        _OUT_DEVICES[0] = len(res[0].sharding.device_set)
        for name, val in zip(names, fused._fetch(res, _TRANSFER)):
            out[name][rows] = val[:rows.size]
    return out
