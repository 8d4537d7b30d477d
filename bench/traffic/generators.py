"""The benchmark's traffic generator for campaign cells, driven by the
parameters of a traffic file and the run's seed.

Copied from ``src/repro/sim/generators.py`` (``gen_instance``,
``uniform_comp``, ``uniform_comm``, ``constant_comm``, and the draw of
``jpeg_profile_comp``/``jpeg_profile_comm``), so that a later change of the
program's generators cannot move the yardstick; the families are given as data
in the configuration file.  A fixed chain, such as a model's layers, is a
``profile``: its per-stage values written out in full.  Instances are plain
arrays ``(w, delta, s)``.  Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np


def _sampler(spec, rng, size):
    """``size`` values of a sampler given as data: ``["uniform_int", lo,
    hi]``, ``["uniform", lo, hi]``, ``["constant", v]``, or ``["profile",
    values]`` and ``["profile", values, jitter]``.  A profile is taken as it
    stands and draws nothing; with ``jitter`` each value is scaled by one
    draw of ``rng.uniform(1 - jitter, 1 + jitter, size)``.  A profile of
    another length than ``size`` raises: it is neither tiled nor padded."""
    kind, *args = spec
    if kind == "uniform_int":
        lo, hi = args
        return rng.integers(int(lo), int(hi) + 1, size).astype(float)
    if kind == "uniform":
        lo, hi = args
        return rng.uniform(lo, hi, size)
    if kind == "constant":
        return np.full(size, float(args[0]))
    if kind == "profile":
        values, *jitter = args
        base = np.asarray(values, dtype=float)
        if base.shape != (size,):
            raise ValueError(f"profile of {base.size} values where {size} "
                             "are drawn")
        if not jitter:
            return base
        (j,) = jitter
        return base * rng.uniform(1.0 - j, 1.0 + j, size)
    raise ValueError(f"unknown sampler {kind!r}")


def gen_instance(family: dict, n: int, p: int, speeds: list, seed: int):
    """One ``(w, delta, s)`` of a family given as ``{"comp": sampler,
    "comm": sampler}``.  Draw order (works, volumes, speeds) is the seed
    contract of the Section-5 generators."""
    rng = np.random.default_rng(seed)
    w = np.asarray(_sampler(family["comp"], rng, n), dtype=float)
    delta = np.asarray(_sampler(family["comm"], rng, n + 1), dtype=float)
    s = _sampler(speeds, rng, p).astype(float)
    return w, delta, s


def pool_seeds(traffic: dict) -> list:
    """Seed0 of each campaign of the traffic's fixed pool: ``pool_campaigns``
    disjoint ranges of ``pairs_per_family`` instance seeds from
    ``pool_seed0``.  Every run draws its campaigns from this pool, so every
    seed does the same work in another order."""
    pairs = int(traffic["pairs_per_family"])
    return [int(traffic["pool_seed0"]) + k * pairs
            for k in range(int(traffic["pool_campaigns"]))]


def campaign_seeds(traffic: dict, seed: int):
    """Seed0 of the campaigns of a run, back to back: the pool dealt
    forever, each pass in a fresh order shuffled from ``seed``."""
    pool = pool_seeds(traffic)
    rng = np.random.default_rng(seed)
    while True:
        for i in rng.permutation(len(pool)):
            yield pool[i]


def warmup_seed(traffic: dict) -> int:
    """An instance seed that no campaign of the pool uses."""
    pools = pool_seeds(traffic)
    return pools[-1] + int(traffic["pairs_per_family"])
