"""Device busy time of the traced campaign per dispatch of the fused loop
in it: the time one ``fused._get_loop`` program keeps the chip busy."""


def read(record):
    tr = record.get("trace")
    if not tr or not record.get("traced_dispatches"):
        return None
    return 1e3 * tr["busy_s"] / record["traced_dispatches"]
