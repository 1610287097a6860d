"""Sum of the program's `compile` spans (one per backend compile or load
from the persistent cache) that end before the window opens."""
from benchmark.harness import scopes
from benchmark.harness.runner import log


def read(run):
    t_open = scopes.window_open(run)
    spans = scopes.spans(run)
    compiles = [e for e in spans if e["name"] == "compile"]
    if t_open is None or not compiles:
        return None
    before = [e for e in compiles if e["mono"] + e["dur_s"] <= t_open]
    self_s = scopes.self_times(spans)
    rows = {}
    for e in spans:
        if e["mono"] + e["dur_s"] > t_open:
            continue
        if e["name"] == "compile":
            key = "compile (cache hit)" if e.get("cache_hit") else "compile (miss or no cache)"
        elif e["name"] in ("state.init", "serve.warmup"):
            key = e["name"] + " (self)"
        else:
            continue
        n, s = rows.get(key, (0, 0.0))
        rows[key] = (n + 1, s + self_s[e["span"]])
    log("set-up by span, before the window: "
        + "; ".join(f"{k} {n} x, {s:.2f} s" for k, (n, s) in sorted(rows.items()))
        + f"; compile spans after the window opened: {len(compiles) - len(before)}")
    return sum(e["dur_s"] for e in before)
