"""Process start to the first array on the device (host clock)."""


def read(run):
    return run["host"].get("reach_chip_s")
