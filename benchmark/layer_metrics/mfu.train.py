"""The whole step's share of the chip's bf16 peak over the traced steps."""
from benchmark.harness import readers


def read(run):
    return readers.train_mfu(run, (run["host"].get("traced") or {}).get("steps"))
