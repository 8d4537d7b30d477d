"""Share of the certified loop's decisions made on the host: parked
row-steps re-decided in float64 over all decisions (``fused.decision_counts``
over the window).  Nothing to read where the loop is not certified."""


def read(record):
    total = record["decided_on_host"] + record["decided_on_device"]
    return 100.0 * record["decided_on_host"] / total if total else None
