"""Named host spans of the campaign's hot path, on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation``: when a profiler records,
it lands in the trace beside the device's planes, on the same clock, nested
by time on the one host thread that runs the campaign; when none records it
costs about a microsecond to enter and leave.  The profiler is the only
store: nothing here keeps, counts or exports spans.

:data:`SPANS` lists every name the program emits, so that a trace reduction
and the tests use one list:

- ``fused.launch``: gathering one chunk's argument slices, and the call of
  the jitted program (upload and enqueue), of the fused or the sharded
  engine (both run ``repro.core.fused.run_loop`` and the same fetch);
- ``fused.wait``: the host blocked until the device finishes that call,
  split off only while a profiler records (:func:`recording`), so that the
  untraced hot path waits inside the first copy, as a plain conversion does;
- ``fused.fetch``: the call's outputs copied to the host (in a trace, after
  the wait: transfer and host re-layout only);
- ``fused.replay``: the device's decisions applied on the host (the
  certified loop's float64 replay, or the uncertified write-back);
- ``fused.parked_step``: one float64 step of the numpy loop on the rows the
  certified loop parked;
- ``fused.record``: the final lockstep replay into the caller's ``record``;
- ``campaign.*``: the phases of ``repro.sim.experiments.run_campaign``.
"""

from __future__ import annotations

__all__ = ["SPANS", "recording", "span"]

SPANS = ("fused.launch", "fused.wait", "fused.fetch", "fused.replay",
         "fused.parked_step", "fused.record",
         "campaign.instances", "campaign.trajectories",
         "campaign.h4_bisection", "campaign.h5h6", "campaign.assemble")


def span(name: str):
    """A context manager marking ``name`` (one of :data:`SPANS`) in the
    profiler's trace."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


def recording() -> bool:
    """Whether a profiler is recording this process's host trace, so that
    the spans land somewhere."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation.is_enabled()
