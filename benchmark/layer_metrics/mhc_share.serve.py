"""Share of the traced window's device busy time under the hyper-connections
(`mhc`: coefficients, Sinkhorn, mixing) inside `serve.decode`."""
from benchmark.harness import scopes


def read(run):
    return scopes.share_under(run, ("mhc",), all_of=("serve.decode",))
