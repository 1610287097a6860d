"""Bytes the routed-expert products of the traced decode blocks had to read
(the family's `moe_step_bytes` of the experts those blocks touched: each
one's matrices once, whatever implements the products) over the memory peak,
over the device time under `moe_experts` inside `serve.decode`. The traced
blocks are the `serve.decode` spans of the window's last `trace_seconds`,
placed on the program's clock to a request gap."""
from benchmark.harness import scopes


def read(run):
    red = scopes.device(run)
    traced_s = (run.get("traced") or {}).get("window_s")
    req = scopes.serve_requests(run)
    if red is None or not traced_s or req is None:
        return None
    seconds = scopes.seconds_under(red, ("moe_experts",), all_of=("serve.decode",))
    t_close = req["window"][1]
    touched = sum(
        e["experts_touched"] for e in scopes.spans(run)
        if e["name"] == "serve.decode" and "experts_touched" in e
        and t_close - traced_s <= e["mono"] < t_close
    )
    if not seconds or not touched:
        return None
    cell = run["cell"]
    least_s = cell["family"].moe_step_bytes(cell["config"]["model"], touched) / (
        run["peaks"]["hbm_bytes_per_s"] * run["device"]["count"]
    )
    return 100.0 * least_s / seconds
