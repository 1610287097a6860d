"""Tokens emitted over live rows x forward passes of the window's decode
calls: the `tokens`, `slots` and `passes` attributes of its `serve.decode`
spans. One-token decode reads 1 (less what rows that end inside a call
leave unused); generation by diffusion over blocks of L with S denoise
passes and a commit reads L / (S + 1). A program whose spans carry no
`passes` gives nothing to read."""
from benchmark.harness import scopes


def read(run):
    req = scopes.serve_requests(run)
    if req is None:
        return None
    t_open, t_close = req["window"]
    calls = [
        e for e in scopes.spans(run)
        if e["name"] == "serve.decode" and "passes" in e and "tokens" in e
        and t_open <= e["mono"] < t_close
    ]
    row_passes = sum(e["slots"] * e["passes"] for e in calls)
    if not row_passes:
        return None
    return sum(e["tokens"] for e in calls) / row_passes
