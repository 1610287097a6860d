"""Share of the traced window's device busy time under the routed layers'
feed-forward (`router`, `moe_experts`, `moe_shared`) inside `serve.decode`."""
from benchmark.harness import scopes


def read(run):
    return scopes.share_under(
        run, ("router", "moe_experts", "moe_shared"), all_of=("serve.decode",)
    )
