"""Share of the traced window's device busy time under `denoise.commit`
inside `serve.decode`: the pass that ends a block of a block-diffusion
engine, which runs the finished block once more to write its keys and values
and computes no head. A program without that scope gives nothing to read."""
from benchmark.harness import scopes


def read(run):
    return scopes.share_under(run, ("denoise.commit",), all_of=("serve.decode",))
