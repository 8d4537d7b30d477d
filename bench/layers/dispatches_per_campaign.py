"""Jitted-program dispatches of ``fused.run_loop`` per campaign
(``fused.dispatch_count`` over the window)."""


def read(record):
    if not record.get("campaigns"):
        return None
    return record["dispatches"] / record["campaigns"]
