"""The correctness check of each cell, driven through a whole run on the CPU
at a small size with the chip check skipped: sound runs come out correct,
the float32 control and every fault a cell can have come out not correct.

The faults are planted in the timed path underneath the run: an answer
altered where it is produced, half of the batch left out with the mean taken
over the rest, and a step that returns its state unchanged.  The exchange
between chips does not exist in these one-chip cells."""

import time

import numpy as np
import pytest

from bench import control, harness

SPEC = harness.load_spec()
SEED = 2 ** 31 + 12345


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    from repro.core import fused

    monkeypatch.setattr(fused, "enable_persistent_cache", lambda: "off")


def _campaign_cell(name="campaign_paper"):
    cell = harness.Cell(name, SPEC)
    cell.config = dict(cell.config, n=8, p=10, n_bounds=4, h4_iters=4)
    cell.traffic = dict(cell.traffic, pairs_per_family=3)
    return cell


def _run(cell, trace=False, seconds=0.4):
    return harness.run(cell, SEED, seconds, trace, time.perf_counter())


def _bad(out):
    return {k: v["value"] for k, v in out["checked"].items()
            if v["value"] > v["limit"]}


@pytest.mark.parametrize("name", ["campaign_paper", "campaign_e1"])
def test_campaign_sound_run_is_correct(name):
    cell = _campaign_cell(name)
    cell.traffic = dict(cell.traffic,
                        pairs_per_family=3 if name == "campaign_paper" else 8)
    out = _run(cell)
    assert out["correct"], out["checked"]
    assert set(out["metrics"]) == {"campaign_instances_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checked"


def test_campaign_traced_run_reads_counters():
    out = _run(_campaign_cell(), trace=True)
    assert out["correct"]
    # the CPU has no device plane: the trace metrics are left out
    assert set(out["metrics"]) == {"dispatches_per_campaign"}


def test_campaign_float32_control_is_not_correct():
    runs = control.run_controls(_campaign_cell(), [SEED, SEED + 1], 0.3,
                                "f32")
    for r in runs:
        assert not r["correct"], r
        assert r["checked"]["floats_differing"]["value"] > 0


def _alter_answer(monkeypatch):
    from repro.sim import experiments

    orig = experiments.run_campaign

    def altered(*a, **k):
        res = orig(*a, **k)
        mp = next(iter(res.values())).curves["H1"][0]
        i = int(np.flatnonzero(np.isfinite(mp))[0])
        mp[i] = np.nextafter(mp[i], np.inf)
        return res

    monkeypatch.setattr(experiments, "run_campaign", altered)


def _half_batch(monkeypatch):
    from repro.sim import experiments

    orig = experiments.run_campaign

    def half(exps, n, p, n_pairs, **k):
        res = orig(exps, n, p, n_pairs=max(1, n_pairs // 2), **k)
        for r in res.values():
            r.n_pairs = n_pairs
        return res

    monkeypatch.setattr(experiments, "run_campaign", half)


def _state_unchanged(monkeypatch):
    from repro.core import fused

    def stuck(state, *a, **k):
        state.active[:] = False

    monkeypatch.setattr(fused, "run_loop", stuck)


CAMPAIGN_FAULTS = {"answer_altered": _alter_answer,
                   "half_batch_left_out": _half_batch,
                   "state_unchanged": _state_unchanged}


@pytest.mark.parametrize("fault", sorted(CAMPAIGN_FAULTS))
def test_campaign_fault_is_not_correct(fault, monkeypatch):
    CAMPAIGN_FAULTS[fault](monkeypatch)
    out = _run(_campaign_cell(), seconds=0.2)
    assert not out["correct"]
    assert _bad(out)
