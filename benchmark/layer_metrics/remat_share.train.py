"""Share of the traced steps' device busy time under
`checkpoint/rematted_computation`: the forward pass computed again."""
from benchmark.harness import scopes


def read(run):
    return scopes.share_under(run, (scopes.REMAT,))
