"""The trace reduction, on a synthesised trace and on one recorded on the
CPU (which has no device plane)."""

import pytest

from bench import trace

MS = 1e6  # nanoseconds


def _planes():
    host = ("/host:CPU", [
        ("python", [
            (0.0, 100 * MS, trace.STRETCH),
            (6 * MS, 39 * MS, "bench.campaign"),
            (10 * MS, 20 * MS, "bench.replay"),
            (60 * MS, 30 * MS, "bench.campaign"),
        ]),
        ("other thread", [(0.0, 100 * MS, "background")]),
    ])
    device = ("/device:TPU:0", [
        ("XLA Modules", [(10 * MS, 20 * MS, "jit_counted")]),
        ("XLA Ops", [
            (10 * MS, 5 * MS, "fusion.1"),
            (12 * MS, 6 * MS, "fusion.2"),      # overlaps fusion.1
            (20 * MS, 10 * MS, "while"),
            (95 * MS, 10 * MS, "fusion.1"),     # runs past the stretch
            (-5 * MS, 3 * MS, "copy"),          # before the stretch
        ]),
    ])
    return [host, device, ("/host:metadata", [])]


def test_busy_idle_and_gaps_of_a_synthesised_trace():
    out = trace.reduce(_planes())
    # busy: [10, 18] + [20, 30] + [95, 100] inside the [0, 100] ms stretch
    assert out["window_s"] == pytest.approx(0.100)
    assert out["busy_s"] == pytest.approx(0.023)
    ops = dict(out["device_ops"])
    assert ops == pytest.approx({"fusion.1": 0.010, "fusion.2": 0.006,
                                 "while": 0.010})
    gaps = dict(out["idle_gaps"])
    # [0,10] stretch only, [18,20] replay, [30,95] campaign at 60..90 midpoint 62.5
    assert gaps == pytest.approx({trace.STRETCH: 0.010, "bench.replay": 0.002,
                                  "bench.campaign": 0.065})
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(0.100)


def test_every_device_plane_counts_toward_busy():
    host, device, meta = _planes()
    second = ("/device:TPU:1", [("XLA Ops", [(0.0, 50 * MS, "fusion.9")])])
    out = trace.reduce([host, device, second, meta])
    assert out["busy_s"] == pytest.approx((0.023 + 0.050) / 2)


def test_no_device_plane_reads_nothing():
    host, _, meta = _planes()
    assert trace.reduce([host, meta]) is None
    assert trace.reduce([host, ("/device:TPU:0", [("XLA Ops", [])])]) is None


def test_union_and_timeline():
    assert trace.union([(3, 4), (0, 2), (1, 3), (6, 7)]) == [[0, 4], [6, 7]]
    times, names = trace._timeline([(0, 10, "a"), (2, 3, "b"), (6, 1, "c")])
    assert times == [0, 2, 5, 6, 7, 10]
    assert names == ["a", "b", "a", "c", "a", None]


def test_recorded_cpu_trace_has_the_stretch_and_no_device():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tr = trace.Tracer()
    tr.start()
    with trace.span("bench.campaign"):
        f(x).block_until_ready()
    tr.stop()
    files = sorted(__import__("pathlib").Path(tr.dir).rglob("*.xplane.pb"))
    planes = trace.read_planes(files[-1])
    assert any(ev[2] == trace.STRETCH for ev in trace._stretch_line(planes))
    assert tr.reduce() is None           # the CPU is no device plane
