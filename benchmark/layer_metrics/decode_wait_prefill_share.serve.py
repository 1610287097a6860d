"""Over the counted requests, the part of first token -> done that lies
under `serve.admit`, `serve.prefill` or `serve.insert` spans of *other*
requests, over the sum of first token -> done: what a request in flight
waits while the engine admits others."""
from benchmark.harness import scopes


def read(run):
    req = scopes.serve_requests(run)
    if req is None or not req["intervals"]:
        return None
    owned = [
        (e["mono"], e["mono"] + e["dur_s"], e.get("request"))
        for e in scopes.spans(run) if e["name"] in scopes.ADMISSION_SPANS
    ]
    if not owned:
        return None
    share = scopes.others_share(req["intervals"], owned)
    return None if share is None else 100.0 * share
