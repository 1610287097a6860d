"""Median time between the fences of consecutive steps in the window."""
from benchmark.harness import readers


def read(run):
    return readers.median_step_ms(run)
