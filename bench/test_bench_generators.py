"""The benchmark's copies of the generators draw what the program's
generators draw from the same seeds: a later change to the program's data
shows here as a moved yardstick."""

import json

import numpy as np
import pytest

from bench import harness
from bench.traffic import generators

ROOT = harness.ROOT


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def _traffic(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("family", ["E1", "E2", "E3", "E4"])
def test_campaign_instances_equal_the_programs(family):
    from repro.sim.generators import gen_instance_batch

    cfg = _config("paper_e1e4_n40_p100")
    seed0 = next(generators.campaign_seeds(_traffic("paper_mixed"),
                                           2 ** 31 + 11))
    seeds = [seed0 + k for k in range(5)]
    theirs = gen_instance_batch(family, cfg["n"], cfg["p"], seeds)
    for k, sd in enumerate(seeds):
        w, delta, s = generators.gen_instance(cfg["families"][family],
                                              cfg["n"], cfg["p"],
                                              cfg["speeds"], sd)
        assert np.array_equal(w, theirs.w[k])
        assert np.array_equal(delta, theirs.delta[k])
        assert np.array_equal(s, theirs.s[k])
    assert theirs.b == cfg["b"]


def test_every_seed_deals_the_same_pool_in_another_order():
    tr = _traffic("paper_mixed")
    pool = generators.pool_seeds(tr)
    pairs = tr["pairs_per_family"]
    assert len(pool) == tr["pool_campaigns"]
    assert [b - a for a, b in zip(pool, pool[1:])] == [pairs] * (len(pool) - 1)
    assert generators.warmup_seed(tr) >= pool[-1] + pairs
    orders = []
    for seed in (5, 2 ** 31 + 9):
        it = generators.campaign_seeds(tr, seed)
        dealt = [next(it) for _ in range(3 * len(pool))]
        for k in range(0, len(dealt), len(pool)):
            assert sorted(dealt[k:k + len(pool)]) == pool
        again = generators.campaign_seeds(tr, seed)
        assert [next(again) for _ in dealt] == dealt
        orders.append(dealt)
    assert orders[0] != orders[1]


# The JPEG encoder profile of arXiv 0801.1772 as the program's I1 family
# states it: each stage's relative compute and the volume that leaves it,
# with the raw image entering the first stage.
JPEG_COMP = [4.0, 6.0, 2.0, 1.0, 12.0, 3.0, 8.0]
JPEG_OUT = [16.0, 16.0, 8.0, 8.0, 8.0, 8.0, 2.0]
JPEG_IN_RAW = 16.0


def _tiled(values, size):
    return [values[k % len(values)] for k in range(size)]


def test_i1_written_as_data_equals_the_programs():
    from repro.sim.generators import gen_instance_batch

    n, p = 40, 100
    family = {"comp": ["profile", _tiled(JPEG_COMP, n), 0.2],
              "comm": ["profile", [JPEG_IN_RAW] + _tiled(JPEG_OUT, n), 0.2]}
    seeds = [1234 + k for k in range(5)] + [2 ** 31 + 11, 2 ** 33 + 1]
    theirs = gen_instance_batch("I1", n, p, seeds)
    for k, sd in enumerate(seeds):
        w, delta, s = generators.gen_instance(family, n, p,
                                              ["uniform_int", 1, 20], sd)
        assert np.array_equal(w, theirs.w[k])
        assert np.array_equal(delta, theirs.delta[k])
        assert np.array_equal(s, theirs.s[k])


def test_a_profile_without_jitter_draws_nothing():
    n, p, seed = 53, 32, 2 ** 31 + 3
    comp = [1.0 + (k % 4) for k in range(n)]
    comm = [0.5 * k for k in range(n + 1)]
    w, delta, s = generators.gen_instance(
        {"comp": ["profile", comp], "comm": ["profile", comm]}, n, p,
        ["uniform_int", 1, 20], seed)
    assert np.array_equal(w, comp) and np.array_equal(delta, comm)
    assert np.array_equal(
        s, np.random.default_rng(seed).integers(1, 21, p).astype(float))


def test_a_profile_with_jitter_draws_once():
    rng, again = np.random.default_rng(7), np.random.default_rng(7)
    got = generators._sampler(["profile", [1.0, 2.0, 4.0], 0.1], rng, 3)
    want = np.array([1.0, 2.0, 4.0]) * again.uniform(1.0 - 0.1, 1.0 + 0.1, 3)
    assert np.array_equal(got, want)
    assert rng.random() == again.random()


@pytest.mark.parametrize("spec", [["profile", [1.0, 2.0]],
                                  ["profile", [1.0, 2.0, 3.0, 4.0]],
                                  ["profile", [1.0, 2.0], 0.2]])
def test_a_profile_of_another_length_raises(spec):
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError, match="profile of"):
        generators._sampler(spec, rng, 3)
    assert rng.random() == np.random.default_rng(0).random()
