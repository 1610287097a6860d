"""Share of the traced window's device busy time under the Gated DeltaNet
layers' mixer (`gdn_in`, `gdn_conv`, `gdn_core`, `gdn_out`) inside
`serve.decode`. A program without those scopes gives nothing to read."""
from benchmark.harness import scopes


def read(run):
    return scopes.share_under(
        run, ("gdn_in", "gdn_conv", "gdn_core", "gdn_out"), all_of=("serve.decode",)
    )
