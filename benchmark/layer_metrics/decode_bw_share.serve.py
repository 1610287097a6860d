"""Bytes the traced decode steps had to read (the family's count: weights
once a step, live keys and values once) over the memory peak, over the
decode program's device time in the trace."""
# The engine jits `functools.partial`s, so the profiler names every one of
# its programs `jit__unknown`: the decode program is told apart as the one
# that takes most device time (PERF.md, Open questions: name them).
ENGINE_PROGRAMS = "jit__unknown"


def read(run):
    tr = (run.get("traced") or {}).get("trace")
    if not tr:
        return None
    mods = [v for k, v in tr["modules"].items() if k.startswith(ENGINE_PROGRAMS)]
    if not mods:
        return None
    decode = max(mods, key=lambda v: v["s"])
    seconds, calls = decode["s"], decode["calls"]
    if seconds <= 0 or calls < 2:
        return None
    h = run["host"]
    util = (h.get("counters") or {}).get("close", {}).get("decode_utilization") or 0.0
    live_tokens = int(util * h["max_slots"] * h["mean_live_context"])
    cell = run["cell"]
    per_step = cell["family"].decode_step_bytes(cell["config"]["model"], live_tokens)
    least_s = calls * h["decode_block"] * per_step / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
