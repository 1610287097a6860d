"""Share of the traced window's device busy time under the dense
feed-forward (`ffn`) inside `serve.decode`. A program without that scope
gives nothing to read."""
from benchmark.harness import scopes


def read(run):
    return scopes.share_under(run, ("ffn",), all_of=("serve.decode",))
