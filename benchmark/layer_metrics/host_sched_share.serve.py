"""Share of the engine's wall time charged to host scheduling (serve ledger)."""
from benchmark.harness import readers


def read(run):
    return readers.serve_bucket_share(run, "host_sched")
