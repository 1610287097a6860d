"""Bytes the recurrent state of the traced decode blocks had to move (the
family's `state_step_bytes` of the `state_rows` those blocks sowed: every
Gated DeltaNet layer's S and conv state read once and written once, per
live row and step) over the memory peak, over the device time under
`gdn_core` inside `serve.decode`. The traced blocks are the `serve.decode`
spans of the window's last `trace_seconds`, placed on the program's clock
to a request gap. A program without them gives nothing to read."""
from benchmark.harness import scopes


def read(run):
    red = scopes.device(run)
    traced_s = (run.get("traced") or {}).get("window_s")
    req = scopes.serve_requests(run)
    if red is None or not traced_s or req is None:
        return None
    seconds = scopes.seconds_under(red, ("gdn_core",), all_of=("serve.decode",))
    t_close = req["window"][1]
    rows = sum(
        e["state_rows"] for e in scopes.spans(run)
        if e["name"] == "serve.decode" and "state_rows" in e
        and t_close - traced_s <= e["mono"] < t_close
    )
    if not seconds or not rows:
        return None
    cell = run["cell"]
    least_s = cell["family"].state_step_bytes(cell["config"]["model"], rows) / (
        run["peaks"]["hbm_bytes_per_s"] * run["device"]["count"]
    )
    return 100.0 * least_s / seconds
