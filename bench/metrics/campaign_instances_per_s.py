"""Instances taken through every heuristic and every bound, over the time
from the window's start to the end of the last campaign that started in it."""


def read(record):
    if not record.get("campaigns"):
        return None
    return record["instances"] / record["window_s"]
