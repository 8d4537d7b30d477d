"""Profiler trace of a stretch of the window, and its reduction to device
metrics.

:class:`Tracer` records the stretch a cell's kind marks (``start``/``stop``)
with ``jax.profiler`` into a temporary directory, under a host span named
:data:`STRETCH`.  :func:`reduce` turns the planes of the trace into:

- ``busy_s``: the union of the intervals in which an operation ran on the
  device, inside the stretch, averaged over the device planes;
- ``window_s``: the length of the stretch;
- ``device_ops``: the ten device operations with the most time;
- ``idle_gaps``: idle time between device operations, summed by what the
  host was doing in it (the innermost host event around each gap's
  midpoint), the ten largest.

The reduction reads device planes by their kind (``/device:TPU:<n>``) and
the operation lines by name, never by program names, and works on plain
``(name, [(line name, [(start_ns, duration_ns, name), ...]), ...])``
planes, so the tests can check it on a synthesised trace.
"""

from __future__ import annotations

import bisect
import pathlib
import re
import shutil
import tempfile

STRETCH = "bench.traced"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
# lines of a device plane that hold the operations themselves; the others
# summarise them (modules, steps) or hold no device work
_OP_LINES = ("XLA Ops",)
# an operation's name is its HLO text; its head names the op and its shape
_NAME_CHARS = 100
_SUMMARY_LINES = ("XLA Modules", "Steps", "XLA TraceMe", "Framework Ops",
                  "Framework Name Scope", "Source code", "Launch Stats")


class Tracer:
    """Profiles one stretch of the window per run."""

    def __init__(self):
        self.dir = None
        self._span = None

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(STRETCH)
        self._span.__enter__()

    def stop(self) -> None:
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self):
        """The stretch's device metrics, or None where nothing was traced
        or no device plane holds an operation."""
        if self.dir is None:
            return None
        try:
            files = sorted(pathlib.Path(self.dir).rglob("*.xplane.pb"))
            if not files:
                return None
            return reduce(read_planes(files[-1]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    """A host span on the profiler's clock (cheap when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def read_planes(path) -> list:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = []
    for pl in data.planes:
        lines = []
        for ln in pl.lines:
            lines.append((ln.name, [(float(e.start_ns), float(e.duration_ns),
                                     e.name) for e in ln.events]))
        planes.append((pl.name, lines))
    return planes


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def device_op_events(lines) -> list:
    """The operation events of one device plane: its ``XLA Ops`` lines,
    or, where it has none, every line that is not a summary line."""
    ops = [ev for name, evs in lines if name in _OP_LINES for ev in evs]
    if ops:
        return ops
    return [ev for name, evs in lines if name not in _SUMMARY_LINES
            for ev in evs]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(planes, top: int = 10):
    devices = [lines for name, lines in planes if _DEVICE_PLANE.match(name)]
    per_device = [device_op_events(lines) for lines in devices]
    if not any(per_device):
        return None
    thread = _stretch_line(planes)
    stretch = [(s, s + d) for s, d, name in thread if name == STRETCH]
    if stretch:
        lo, hi = stretch[0]
    else:
        lo = min(s for evs in per_device for s, _, _ in evs)
        hi = max(s + d for evs in per_device for s, d, _ in evs)
    busy, op_time, gaps = [], {}, {}
    for i, evs in enumerate(per_device):
        merged = union(_clip([(s, s + d) for s, d, _ in evs], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        for s, d, name in evs:
            t = min(s + d, hi) - max(s, lo)
            if t > 0:
                name = name[:_NAME_CHARS]
                op_time[name] = op_time.get(name, 0.0) + t
        if i:
            continue
        times, names = _timeline(thread)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                k = bisect.bisect_right(times, 0.5 * (s + e)) - 1
                what = names[k] if k >= 0 and names[k] else "no host event"
                gaps[what] = gaps.get(what, 0.0) + (e - s)
    ns = 1e-9

    def rank(d):
        return sorted(([k, v * ns] for k, v in d.items()),
                      key=lambda kv: -kv[1])[:top]

    return {"busy_s": sum(busy) / len(busy) * ns, "window_s": (hi - lo) * ns,
            "device_ops": rank(op_time), "idle_gaps": rank(gaps)}


def _stretch_line(planes) -> list:
    """The events of the host thread that ran the traced stretch."""
    for name, lines in planes:
        if _DEVICE_PLANE.match(name):
            continue
        for _, evs in lines:
            if any(ev[2] == STRETCH for ev in evs):
                return evs
    return []


def _timeline(events) -> tuple:
    """Boundary times and the innermost event name from each boundary on,
    for the properly nested events of one host thread (``None`` where no
    event is open)."""
    times, names, stack = [], [], []

    def close_until(t):
        while stack and stack[-1][0] <= t:
            end, _ = stack.pop()
            times.append(end)
            names.append(stack[-1][1] if stack else None)

    for s, d, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close_until(s)
        times.append(s)
        names.append(name)
        stack.append((s + d, name))
    close_until(float("inf"))
    return times, names
