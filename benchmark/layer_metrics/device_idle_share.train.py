"""1 - the union of the device operations' intervals over the traced window."""
from benchmark.harness import readers


def read(run):
    return readers.idle_share(run)
