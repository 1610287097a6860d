"""Share of the traced window's device busy time under the latent reads and
the attention over them (`kv_read`, `attn_core`) inside `serve.decode`."""
from benchmark.harness import scopes


def read(run):
    return scopes.share_under(run, ("kv_read", "attn_core"), all_of=("serve.decode",))
