"""KiB copied back from the fused programs per dispatch over the window:
the ``nbytes`` of every output of every call, ``fused.transfer_bytes()
["to_host"]`` over ``fused.dispatch_count()``.  Both counters are read after
the window (the check runs none of the program), and only where the traced
run found a device plane: on a backend without one the outputs cross no
host link.  Nothing to read where the program has no transfer counter."""


def read(record):
    from repro.core import fused

    if not record.get("trace") or not hasattr(fused, "transfer_bytes"):
        return None
    d = fused.dispatch_count()
    return fused.transfer_bytes()["to_host"] / d / 1024 if d else None
