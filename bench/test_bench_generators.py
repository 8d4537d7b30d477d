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
