"""Share of the traced steps' device busy time under `lm_head` or `loss`:
the tied head's product and the cross-entropy, forward and backward."""
from benchmark.harness import scopes


def read(run):
    return scopes.share_under(run, ("lm_head", "loss"))
