"""Share of the traced window's device busy time under `serve.decode` and
under none of the block's scopes (attention, the cache's gathers and
scatters, the Dense and LayerNorm modules, the head, sampling): what the
decode program's scans carry and copy."""
from benchmark.harness import scopes


def read(run):
    return scopes.share_under(run, ("serve.decode",), none_of=scopes.BLOCK_SCOPES)
