"""Share of the run in which the loop waited for a batch: the program's
`data.host_wait_s` gauges, summed, over the time its recorder was on."""
from benchmark.harness import readers


def read(run):
    waits = readers.program_events(run, "gauge", "data.host_wait_s")
    if len(waits) < 2:
        return None
    span = waits[-1]["ts"] - waits[0]["ts"]
    return 100.0 * sum(e["value"] for e in waits[1:]) / span if span > 0 else None
