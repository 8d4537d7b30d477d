"""Process start to the window's start: imports, the device, instances,
warm-up, and in a checkout's first run the compiles."""


def read(record):
    return record["setup_s"]
