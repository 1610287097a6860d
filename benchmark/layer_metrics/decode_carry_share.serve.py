"""Share of the traced window's device busy time under `serve.decode` and
under none of the scopes the family lists inside a block and after it
(attention, the cache's gathers and scatters, the block's modules, the
head, sampling): what the decode program's scans carry and copy."""
from benchmark.harness import scopes


def read(run):
    block = run["cell"]["family"].BLOCK_SCOPES
    return scopes.share_under(run, ("serve.decode",), none_of=block)
