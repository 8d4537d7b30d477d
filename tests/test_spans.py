"""The program's spans and transfer counters on the campaign's hot path.

A small fused campaign runs under ``jax.profiler`` on the CPU, uncertified
and certified (the TPU's band forced, so that steps park), and the trace is
reduced by the benchmark's span reduction: every span of the path appears,
each dispatch has one launch, one wait and one fetch, and each round with
parked rows one parked step.  The transfer counters equal the bytes of the
programs' arguments and outputs, computed here from the shapes, for the
fused and the sharded engine apart.
"""

import pathlib
import shutil

import numpy as np
import pytest

from bench import spans as bench_spans
from bench import trace
from repro.core import batched, fused, sharded
from repro.core.batched import batched_trajectories
from repro.core.spans import SPANS
from repro.sim import gen_instance_batch
from repro.sim.experiments import run_campaign

EXPS = ("E1", "E2", "E3", "E4")


def _traced(fn):
    """``fn()`` under the profiler, inside the benchmark's stretch span;
    returns the span reduction of the trace."""
    tracer = trace.Tracer()
    tracer.start()
    try:
        fn()
    finally:
        tracer.stop()
    try:
        files = sorted(pathlib.Path(tracer.dir).rglob("*.xplane.pb"))
        return bench_spans.reduce(trace.read_planes(files[-1]))
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)


@pytest.mark.parametrize("certified", [False, True],
                         ids=["uncertified", "certified"])
def test_spans_of_a_fused_campaign(band, monkeypatch, certified):
    band(fused.TPU_BAND if certified else 0.0)
    rounds = []
    numpy_loop = batched._numpy_loop

    def counted(state, rows, *a, max_iters=None, **k):
        rounds.append(max_iters)
        return numpy_loop(state, rows, *a, max_iters=max_iters, **k)

    monkeypatch.setattr(batched, "_numpy_loop", counted)
    fused.reset_dispatch_count()
    out = _traced(lambda: run_campaign(EXPS, 10, 12, n_pairs=3, n_bounds=4,
                                       backend="fused"))
    got = out["spans"]
    want = set(SPANS) - (set() if certified else {"fused.parked_step"})
    assert want <= set(got) <= set(SPANS)
    for phase in ("fused.launch", "fused.wait", "fused.fetch"):
        assert got[phase][1] == fused.dispatch_count() > 0, phase
    parked = rounds.count(1)
    assert got.get("fused.parked_step", [0.0, 0])[1] == parked
    assert (parked > 0) == certified
    for name in SPANS:
        if name.startswith("campaign."):
            assert got[name][1] == 1, name
    assert 0 < out["campaign_driver_s"] < out["window_s"]


def _loop_bytes(n, p, S, T, t_rows=1, certified=False):
    """Argument and output bytes of one ``fused_loop`` call, from the
    program's contract (``fused._build_loop``): float64 and int64 are 8
    bytes, int32 4, bool 1.  The sharded program returns ``t`` per row
    (``t_rows=S``).  The certified program returns one int32 record of
    (T + 1) x 13 x S."""
    f8, i4, b1 = 8, 4, 1
    args = (2 * S * (n + 1) * f8          # delta, prefix
            + 3 * S * p * f8              # s, order, sbits
            + 2 * f8                      # b, zero
            + 2 * S * b1                  # bi_mode, active
            + 3 * S * f8                  # stop, lat_limit, scale
            + S * n * 5 * f8              # arr
            + 4 * S * f8)                 # m, next_idx, lat_sum, splits
    outs = (S * n * 5 * f8 + 4 * S * f8   # arr, m, next_idx, lat_sum, splits
            + S * b1                      # parked
            + 2 * T * S * f8 + T * S * b1  # per_rec, lat_rec, acc_rec
            + T * S * 12 * f8 + t_rows * f8)  # dec_rec, t
    if certified:
        outs = (T + 1) * len(fused.REC_FIELDS) * S * i4
    return args, outs


def _bisect_bytes(n, p, S):
    """The same for one ``fused_bisect`` call (``fused._build_bisect``)."""
    f8, b1 = 8, 1
    args = (2 * S * (n + 1) * f8 + 2 * S * p * f8 + 2 * f8
            + 3 * S * f8 + S * b1)        # p_fix, lo, hi; active
    outs = 2 * (S * n * 3 * f8 + 4 * S * f8) + S * b1
    return args, outs


@pytest.mark.parametrize("engine", ["fused", "sharded"])
@pytest.mark.parametrize("code,certified", [
    ("H1", False), ("H3", False), ("bisection", False),
    ("H1", True), ("H3", True)],
    ids=["H1", "H3", "bisection", "H1-certified", "H3-certified"])
def test_transfer_bytes_match_the_shapes(band, engine, code, certified):
    """Each engine counts its own calls, beside its own dispatch counter:
    eleven copies a call of the uncertified loop and of the bisection, one
    of the certified loop's packed record (which calls again for the rows
    it parks)."""
    band(fused.TPU_BAND if certified else 0.0)
    n, p, B = 8, 6, 5
    batch = gen_instance_batch("E2", n, p, range(B))
    T = min(n - 1, p - 1)
    mine, other = ((fused, sharded) if engine == "fused"
                   else (sharded, fused))
    D = sharded.device_count() if engine == "sharded" else 1
    fused.reset_dispatch_count()
    sharded.reset_dispatch_count()
    if code == "bisection":
        pb = batched._as_problem_batch(batch)
        bisect = (fused.run_fused_bisection if engine == "fused"
                  else sharded.run_sharded_bisection)
        bisect(pb, np.full(B, 1e9), np.zeros(B), np.full(B, 1e9), 3)
        want = _bisect_bytes(n, p, fused.chunk_rows(n, 1) * D)
    else:
        batched_trajectories(code, batch, backend=engine)
        k = batched._TRAJ_CONFIG[code][1]
        S = fused.chunk_rows(n, k) * D
        want = _loop_bytes(n, p, S, T, S if engine == "sharded" else 1,
                           certified)
    calls = mine.dispatch_count()
    assert calls >= 1 if certified else calls == 1
    got = mine.transfer_bytes()
    assert (got["to_device"], got["to_host"]) == (calls * want[0],
                                                  calls * want[1])
    assert mine.fetch_copies() == calls * (1 if certified else 11)
    assert other.dispatch_count() == 0
    assert other.transfer_bytes() == {"to_device": 0, "to_host": 0}
    assert other.fetch_copies() == 0
    mine.reset_dispatch_count()
    assert mine.transfer_bytes() == {"to_device": 0, "to_host": 0}
    assert mine.fetch_copies() == 0


def test_no_explicit_wait_without_a_profiler(monkeypatch):
    """Untraced, the first conversion of a call's outputs waits for the
    device, as a plain ``np.asarray`` does; only a recording profiler splits
    the wait off (``fused.wait``)."""
    import jax

    waits = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waits.append(x) or x)
    batch = gen_instance_batch("E2", 8, 6, range(3))
    fused.reset_dispatch_count()
    batched_trajectories("H1", batch, backend="fused")
    assert fused.dispatch_count() > 0 and waits == []
    out = _traced(lambda: batched_trajectories("H1", batch, backend="fused"))
    assert len(waits) == out["spans"]["fused.wait"][1] > 0


def test_copies_reader(monkeypatch):
    """``fetch_copies_per_dispatch.campaign`` reads the fused engine's copy
    counter over its dispatches; nothing where the trace found no device
    plane or the program has no copy counter (the parent's)."""
    from bench import harness

    mod = harness.load_module(harness.BENCH / "layers"
                              / "fetch_copies_per_dispatch.campaign.py")
    monkeypatch.setattr(fused, "_DISPATCHES", [4])
    monkeypatch.setattr(fused, "_TRANSFER",
                        {"to_device": 0, "to_host": 0, "copies": 44})
    traced = {"trace": {"busy_s": 1.0, "window_s": 2.0}}
    assert mod.read(traced) == 11.0
    assert mod.read({"trace": None}) is None
    monkeypatch.setattr(fused, "_DISPATCHES", [0])
    assert mod.read(traced) is None
    monkeypatch.delattr(fused, "fetch_copies")
    assert mod.read(traced) is None
