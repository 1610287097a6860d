"""Seconds of set-up under JAX's trace and lowering: the union of the
program's `trace` and `lower` spans (one per jaxpr traced, one per module
lowered; the compile listener's, PR 39) that end before the window opens.
A jit traced inside another's trace counts once."""
from benchmark.harness import scopes, setup_clock
from benchmark.harness.runner import log


def read(run):
    t_open = scopes.window_open(run)
    stages = [e for e in scopes.spans(run) if e["name"] in setup_clock.TRACE_LOWER]
    if t_open is None or not stages:
        return None
    before = [e for e in stages if e["mono"] + e["dur_s"] <= t_open]
    rows = {}
    for e in before:
        key = f"{e['name']} {e.get('program')}"
        n, s = rows.get(key, (0, 0.0))
        rows[key] = (n + 1, s + e["dur_s"])
    late = [e for e in stages if e["name"] == "trace" and e["mono"] >= t_open]
    union = setup_clock.covered([(e["mono"], e["mono"] + e["dur_s"]) for e in before])
    log("trace and lower before the window, by program, the ten with most seconds "
        "(a nested trace counts in its own row too): "
        + "; ".join(f"{k} {n} x, {s:.3f} s"
                    for k, (n, s) in sorted(rows.items(), key=lambda kv: -kv[1][1])[:10])
        + f"; {len(before)} spans, {union:.3f} s in their union; traces that started after "
        f"the window opened: {len(late)}, {sum(e['dur_s'] for e in late):.3f} s")
    return union
