"""Share of the traced window's device busy time under `kv_read` inside
`serve.decode`: the gathers of each slot's pages from the pool."""
from benchmark.harness import scopes


def read(run):
    return scopes.share_under(run, ("kv_read",), all_of=("serve.decode",))
