"""The span reduction of ``bench/spans.py`` on a synthesised trace, the
transfer readers, and the breakdown run on the CPU at a small size."""

import pytest

from bench import harness, spans, trace

MS = 1e6  # nanoseconds
SPEC = harness.load_spec()
SIX = {"launch_ms_per_dispatch.campaign", "wait_ms_per_dispatch.campaign",
       "fetch_ms_per_dispatch.campaign", "host_replay_share.campaign",
       "parked_step_share.campaign", "campaign_driver_share.campaign"}


def _planes():
    """A stretch of [0, 100] ms: ``bench.campaign`` over [5, 100] ms, and
    inside it two campaign phases around one dispatch of the fused loop and
    a parked step; one JAX event nested in the fetch; a second dispatch
    that starts before the stretch and one that ends after it."""
    host = ("/host:CPU", [
        ("python", [
            (-10 * MS, 12 * MS, "fused.launch"),      # clipped to [0, 2]
            (0.0, 100 * MS, trace.STRETCH),
            (5 * MS, 95 * MS, "bench.campaign"),
            (10 * MS, 40 * MS, "campaign.trajectories"),
            (12 * MS, 3 * MS, "fused.launch"),
            (15 * MS, 10 * MS, "fused.wait"),
            (25 * MS, 8 * MS, "fused.fetch"),
            (26 * MS, 6 * MS, "np.asarray(jax.Array)"),
            (33 * MS, 4 * MS, "fused.replay"),
            (40 * MS, 5 * MS, "fused.parked_step"),
            (45 * MS, 2 * MS, "fused.record"),
            (60 * MS, 30 * MS, "campaign.h5h6"),
            (70 * MS, 2 * MS, "fused.launch"),
            (72 * MS, 40 * MS, "fused.wait"),         # clipped to [72, 100]
        ]),
        ("other thread", [(0.0, 100 * MS, "fused.wait")]),
    ])
    device = ("/device:TPU:0", [("XLA Ops", [(16 * MS, 8 * MS, "while")])])
    return [host, device]


def test_spans_are_clipped_to_the_stretch_and_counted():
    out = spans.reduce(_planes())
    assert out["window_s"] == pytest.approx(0.100)
    got = {k: (pytest.approx(v[0]), v[1]) for k, v in out["spans"].items()}
    assert got == {
        "fused.launch": (0.002 + 0.003 + 0.002, 3),
        "fused.wait": (0.010 + 0.028, 2),
        "fused.fetch": (0.008, 1), "fused.replay": (0.004, 1),
        "fused.parked_step": (0.005, 1), "fused.record": (0.002, 1),
        "campaign.trajectories": (0.040, 1), "campaign.h5h6": (0.030, 1)}


def test_driver_time_excludes_fused_spans_and_the_stretch_adds_up():
    out = spans.reduce(_planes())
    sp = {k: v[0] for k, v in out["spans"].items()}
    # trajectories [10, 50] less the fused spans in it (32 ms), h5h6
    # [60, 90] less the dispatch in it, [70, 100] (20 ms)
    assert out["campaign_driver_s"] == pytest.approx(0.008 + 0.010)
    fused = sum(v for k, v in sp.items() if k.startswith("fused."))
    phases = sum(v for k, v in sp.items() if k.startswith("campaign."))
    # fused time outside every campaign phase: [0, 2] before bench.campaign
    # and [90, 100] inside it
    campaign_own = 0.095 - phases - 0.010       # [5, 10] and [50, 60]
    stretch_own = 0.005 - 0.002                 # [2, 5]
    assert (fused + out["campaign_driver_s"] + campaign_own
            + stretch_own) == pytest.approx(out["window_s"])


def test_metrics_per_dispatch_and_shares():
    m = spans.metrics(spans.reduce(_planes()), dispatches=3)
    assert m == pytest.approx({
        "launch_ms_per_dispatch.campaign": 7.0 / 3,
        "wait_ms_per_dispatch.campaign": 38.0 / 3,
        "fetch_ms_per_dispatch.campaign": 8.0 / 3,
        "host_replay_share.campaign": 6.0,
        "parked_step_share.campaign": 5.0,
        "campaign_driver_share.campaign": 18.0})


@pytest.mark.parametrize("absent,silent", [
    # the parent program: no span of its own in the trace
    (("fused.", "campaign."), SIX),
    # a renamed wait, or a record replay that did not run
    (("fused.wait",), {"wait_ms_per_dispatch.campaign"}),
    (("fused.record",), {"host_replay_share.campaign"}),
    (("campaign.",), {"campaign_driver_share.campaign"}),
], ids=["no-program-spans", "no-wait", "no-record", "no-campaign-phases"])
def test_an_absent_span_reads_none_not_zero(absent, silent):
    host, device = _planes()
    thread = [ev for ev in host[1][0][1] if not ev[2].startswith(absent)]
    out = spans.reduce([("/host:CPU", [("python", thread)]), device])
    m = spans.metrics(out, dispatches=3)
    assert {k for k, v in m.items() if v is None} == silent
    assert all(v > 0 for v in m.values() if v is not None)


def test_no_stretch_reads_nothing():
    host, device = _planes()
    thread = [ev for ev in host[1][0][1] if ev[2] != trace.STRETCH]
    assert spans.reduce([("/host:CPU", [("python", thread)]), device]) is None


@pytest.mark.parametrize("name,key", [
    ("launch_kib_per_dispatch.campaign", "to_device"),
    ("fetch_kib_per_dispatch.campaign", "to_host")])
def test_transfer_readers(monkeypatch, name, key):
    from repro.core import fused

    mod = harness.load_module(harness.BENCH / "layers" / f"{name}.py")
    monkeypatch.setattr(fused, "_DISPATCHES", [4])
    monkeypatch.setattr(fused, "_TRANSFER",
                        {"to_device": 4 * 2048, "to_host": 4 * 3072})
    traced = {"trace": {"busy_s": 1.0, "window_s": 2.0}}
    assert mod.read(traced) == {"to_device": 2.0, "to_host": 3.0}[key]
    # no device plane in the trace, or a program with no transfer counter
    assert mod.read({"trace": None}) is None
    monkeypatch.delattr(fused, "transfer_bytes")
    assert mod.read(traced) is None


def test_breakdown_of_a_small_campaign_on_the_cpu():
    cell = harness.Cell("campaign_e1", SPEC)
    cell.config = dict(cell.config, n=8, p=10, n_bounds=4, h4_iters=4)
    cell.traffic = dict(cell.traffic, pairs_per_family=3)
    out = spans.profile_campaign(cell, 2 ** 31 + 5)
    assert min(out["before_setup_s"], out["yardstick_s"],
               out["warmup_s"]) > 0
    assert len(out["untraced_s"]) == 2 and out["traced_s"] > 0
    assert out["trace"] is None           # the CPU is no device plane
    sp = out["spans"]["spans"]
    assert sp["fused.launch"][1] == out["dispatches"] > 0
    assert set(out["metrics"]) == SIX
    # the CPU's float64 is IEEE: the loop runs uncertified and parks nothing
    assert {k for k, v in out["metrics"].items() if v is None} == {
        "parked_step_share.campaign"}
    assert out["kib_per_dispatch"]["to_host"] > 0


def test_breakdown_command_prints_one_json_line(monkeypatch, capsys):
    import json

    from repro.core import fused

    monkeypatch.setattr(harness, "device_info",
                        lambda chips: {"platform": "tpu", "count": chips})
    monkeypatch.setattr(fused, "enable_persistent_cache", lambda: "off")
    monkeypatch.setattr(spans, "profile_campaign",
                        lambda cell, seed: {"seed0": 1, "trace": None,
                                            "metrics": {}})
    assert spans.main(["--workload", "campaign_paper", "--seed", "7"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["device"] == {"platform": "tpu", "count": 1}
    assert out["workload"] == "campaign_paper" and out["seed0"] == 1
