"""Share of the engine's wall time charged to decode (serve ledger, window)."""
from benchmark.harness import readers


def read(run):
    return readers.serve_bucket_share(run, "decode")
