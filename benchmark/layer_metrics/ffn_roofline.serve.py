"""The least time the traced decode blocks' dense feed-forward could take
over the device time under `ffn` inside `serve.decode`. The least time is
the larger of two bounds from the family's functions: `ffn_step_bytes` of
the forward passes those blocks ran (`passes`: every layer's three
matrices read once a pass) over the memory peak, and `ffn_step_flops` of
the live rows they moved on (`state_rows`, summed over the passes) over
the bf16 peak. The traced blocks are the `serve.decode` spans of the
window's last `trace_seconds`, placed on the program's clock to a request
gap. A program without them gives nothing to read."""
from benchmark.harness import scopes


def read(run):
    red = scopes.device(run)
    traced_s = (run.get("traced") or {}).get("window_s")
    req = scopes.serve_requests(run)
    if red is None or not traced_s or req is None:
        return None
    seconds = scopes.seconds_under(red, ("ffn",), all_of=("serve.decode",))
    t_close = req["window"][1]
    blocks = [
        e for e in scopes.spans(run)
        if e["name"] == "serve.decode" and "passes" in e and "state_rows" in e
        and t_close - traced_s <= e["mono"] < t_close
    ]
    steps = sum(e["passes"] for e in blocks)
    rows = sum(e["state_rows"] for e in blocks)
    if not seconds or not steps:
        return None
    cell = run["cell"]
    fam, m, chips = cell["family"], cell["config"]["model"], run["device"]["count"]
    least_s = max(
        fam.ffn_step_bytes(m, steps) / (run["peaks"]["hbm_bytes_per_s"] * chips),
        fam.ffn_step_flops(m, rows) / (run["peaks"]["bf16_flops_per_s"] * chips),
    )
    return 100.0 * least_s / seconds
