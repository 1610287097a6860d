"""Mean distinct routed experts a layer a decode step touched, over the
experts a layer holds: from the `experts_touched` attribute of the window's
`serve.decode` spans (summed over a block's steps and routed layers)."""
from benchmark.harness import scopes


def read(run):
    req = scopes.serve_requests(run)
    if req is None:
        return None
    t_open, t_close = req["window"]
    blocks = [
        e for e in scopes.spans(run)
        if e["name"] == "serve.decode" and "experts_touched" in e
        and t_open <= e["mono"] < t_close
    ]
    cell = run["cell"]
    steps = len(blocks) * run["host"]["decode_block"]
    routed = cell["family"].routed_experts_held(cell["config"]["model"])
    if not steps or not routed:
        return None
    return 100.0 * sum(e["experts_touched"] for e in blocks) / (steps * routed)
