"""Host-to-device uploads per dispatch over the window: one per argument
array of every call, ``fused.launch_copies()`` over
``fused.dispatch_count()``.  Both counters are read after the window (the
check runs none of the program), and only where the traced run found a
device plane: on a backend without one nothing crosses a host link.
Nothing to read where the program has no upload counter."""


def read(record):
    from repro.core import fused

    if not record.get("trace") or not hasattr(fused, "launch_copies"):
        return None
    d = fused.dispatch_count()
    return fused.launch_copies() / d if d else None
