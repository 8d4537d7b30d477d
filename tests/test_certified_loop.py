"""The certified fused loop: exact float64 results from inexact devices.

On a TPU, float64 is emulated and is not IEEE, so the fused loop runs
CERTIFIED there (``fused.device_band() > 0``): every decision within the
error band of another outcome parks its row, the host re-decides it in
float64, and every float is replayed on the host with the numpy engine's
own update.  These tests force the TPU band on the CPU and hold the result
to the numpy engine exactly — also when the device's inputs are perturbed
far beyond the TPU's float64 error, and when the band is so wide that the
host decides nearly every step.  The ``band`` fixture is in
``conftest.py``.
"""

import numpy as np
import pytest

from repro.core import fused, sharded
from repro.core.batched import (batched_min_period, batched_sp_bi_p,
                                batched_trajectories)
from repro.core.metrics import single_processor_mapping
from repro.core import period
from repro.sim import EXPERIMENTS, gen_instance_batch
from repro.sim.experiments import run_campaign

SEEDS = range(7100, 7106)


def _campaigns_equal(a, b) -> bool:
    for exp in b:
        if a[exp].thresholds != b[exp].thresholds:
            return False
        for c in b[exp].curves:
            for x, y in zip(a[exp].curves[c], b[exp].curves[c]):
                if not np.array_equal(x, y, equal_nan=True):
                    return False
    return True


@pytest.mark.parametrize("p", [10, 100])
@pytest.mark.parametrize("exp", tuple(EXPERIMENTS))
def test_certified_trajectories_match_numpy(band, exp, p):
    batch = gen_instance_batch(exp, 12, p, SEEDS)
    for code in ("H1", "H2", "H3", "H4"):
        assert (batched_trajectories(code, batch, backend="fused")
                == batched_trajectories(code, batch, backend="numpy")), code
    dec = fused.decision_counts()
    assert dec["device"] > 0


@pytest.mark.parametrize("exp", ["E1", "E3", "I2"])
def test_certified_bisection_matches_numpy(band, exp):
    batch = gen_instance_batch(exp, 10, 100, SEEDS)
    bounds = [period(wl, pf, single_processor_mapping(wl, pf.fastest())) * f
              for (wl, pf), f in zip(batch, [0.05, 0.2, 0.4, 0.6, 0.8, 1.0])]
    got = batched_sp_bi_p(batch, bounds, iters=8, backend="fused")
    want = batched_sp_bi_p(batch, bounds, iters=8, backend="numpy")
    assert got == want


def test_certified_campaign_matches_numpy_and_parks_ties(band):
    exps = ("E1", "E2", "E3", "E4")
    got = run_campaign(exps, 20, 100, n_pairs=6, n_bounds=6,
                       backend="fused")
    dec = fused.decision_counts()
    want = run_campaign(exps, 20, 100, n_pairs=6, n_bounds=6,
                        backend="numpy")
    assert _campaigns_equal(got, want)
    # integer stage works and speeds make exact ties common: some steps park
    assert dec["device"] > 0 and dec["host"] > 0


def _perturbed_loops(monkeypatch, eps: float) -> None:
    """Make every device dispatch see its float inputs and carried state
    off by up to ``eps`` relative: an inexact device, simulated on the CPU."""
    rng = np.random.default_rng(0)
    get = fused._get_loop

    def noisy(x):
        x = np.asarray(x, dtype=float)
        return x * (1.0 + eps * rng.uniform(-1.0, 1.0, x.shape))

    def perturbed(n, p, k, T, S, band=0.0):
        fn = get(n, p, k, T, S, band)

        def run(delta, s, b, zero, prefix, order, bi, stop, lim, act, arr,
                m, nx, lat, sp, scale, sbits):
            arr = arr.copy()
            arr[:, :, 3:] = noisy(arr[:, :, 3:])
            return fn(noisy(delta), noisy(s), b, zero, noisy(prefix), order,
                      bi, stop, lim, act, arr, m, nx, noisy(lat), sp, scale,
                      sbits)
        return run

    monkeypatch.setattr(fused, "_get_loop", perturbed)


@pytest.mark.parametrize("certified", [True, False],
                         ids=["certified", "uncertified"])
def test_inexact_device_is_exact_only_when_certified(band, monkeypatch,
                                                     certified):
    """Inputs off by 1e-9 (the TPU's float64 is good to ~1e-14): the
    certified loop still reproduces numpy exactly; with a band far below
    the perturbation the same run departs, so the perturbation bites."""
    band(fused.TPU_BAND if certified else 2.0 ** -60)
    _perturbed_loops(monkeypatch, 1e-9)
    exps = ("E1", "E2", "E3", "E4")
    got = run_campaign(exps, 10, 12, n_pairs=6, n_bounds=6, backend="fused")
    want = run_campaign(exps, 10, 12, n_pairs=6, n_bounds=6,
                        backend="numpy")
    assert _campaigns_equal(got, want) == certified


def test_wide_band_hands_steps_to_host_and_stays_exact(band):
    band(2.0 ** -8)
    batch = gen_instance_batch("E2", 12, 10, SEEDS)
    got = batched_trajectories("H3", batch, backend="fused")
    dec = fused.decision_counts()
    assert got == batched_trajectories("H3", batch, backend="numpy")
    assert dec["host"] > dec["device"]


def test_certified_min_period_matches_numpy(band):
    batch = gen_instance_batch("E2", 12, 6, range(40))
    got = batched_min_period(batch, backend="fused")
    want = batched_min_period(batch, backend="numpy")
    assert [(r.mapping, r.period, r.latency) for r in got] == \
        [(r.mapping, r.period, r.latency) for r in want]


def test_certified_sharded_matches_numpy(band):
    batch = gen_instance_batch("E4", 12, 100, SEEDS)
    sharded.reset_dispatch_count()
    for code in ("H1", "H2"):
        assert (batched_trajectories(code, batch, backend="sharded")
                == batched_trajectories(code, batch, backend="numpy")), code
    assert sharded.dispatch_count() > 0
    assert sharded.output_devices() == sharded.device_count()


@pytest.mark.parametrize("engine", ["fused", "sharded"])
@pytest.mark.parametrize("k", [1, 2])
def test_certified_program_returns_one_packed_int32_record(band, monkeypatch,
                                                           engine, k):
    """Each certified call returns exactly one int32 array of (T + 1) x 13 x
    S: the decisions the device accepted, each as the uncertified program
    makes it from the same arguments, then each row's parked flag and the
    iteration count."""
    import jax

    mod, name = ((fused, "_get_loop") if engine == "fused"
                 else (sharded, "_get_sharded_loop"))
    get = getattr(mod, name)
    calls = []

    def spy(*shape):
        fn = get(*shape)

        def run(*args):
            out = fn(*args)
            calls.append((shape, args, out))
            return out
        return run

    monkeypatch.setattr(mod, name, spy)
    n, p = 12, 10
    batch = gen_instance_batch("E1", n, p, SEEDS)
    batched_trajectories("H1" if k == 1 else "H3", batch, backend=engine)
    assert calls
    F = len(fused.REC_FIELDS)
    for (n_, p_, k_, T, S_local, b), args, rec in calls:
        assert (n_, p_, k_, b) == (n, p, k, fused.TPU_BAND)
        S = args[0].shape[0]
        assert isinstance(rec, jax.Array)
        assert rec.dtype == np.int32 and rec.shape == (T + 1, F, S)
        rec = np.asarray(rec)
        park, t = rec[T, 0], rec[T, 1]
        assert set(np.unique(park)) <= {0, 1} and (rec[T, 2:] == 0).all()
        assert (t == t.max()).all() if engine == "fused" else t.max() <= T
        out = get(n_, p_, k_, T, S_local, 0.0)(*args)
        acc_rec, dec_rec = np.asarray(out[8]), np.asarray(out[9])
        acc = rec[:T, -1].astype(bool)                       # (T, S)
        assert not acc[t.max():].any()
        assert (acc <= acc_rec).all()
        assert np.array_equal(rec[:T, :-1].transpose(0, 2, 1)[acc],
                              dec_rec[acc])
