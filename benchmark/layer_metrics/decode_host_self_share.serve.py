"""Share of the window the engine spent on the host for decoding: its
`serve.decode` spans less their `serve.decode.fence` children (the one
place that waits for the device), plus `serve.harvest`."""
from benchmark.harness import scopes


def read(run):
    req = scopes.serve_requests(run)
    if req is None:
        return None
    t_open, t_close = req["window"]
    spans = [e for e in scopes.spans(run) if t_open <= e["mono"] < t_close]
    decode = {e["span"]: e["dur_s"] for e in spans if e["name"] == "serve.decode"}
    if not decode:
        return None
    for e in spans:
        if e["name"] == "serve.decode.fence" and e.get("parent") in decode:
            decode[e["parent"]] -= e["dur_s"]
    harvest = sum(e["dur_s"] for e in spans if e["name"] == "serve.harvest")
    return 100.0 * (sum(decode.values()) + harvest) / (t_close - t_open)
