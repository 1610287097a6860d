"""Share of the traced steps' device busy time under the `attn_core` scope:
the score, softmax and value products, forward, recomputed and backward."""
from benchmark.harness import scopes


def read(run):
    return scopes.share_under(run, ("attn_core",))
