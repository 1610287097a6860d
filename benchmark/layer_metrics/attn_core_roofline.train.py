"""The least attention has to do in the traced steps (the family's count;
what remat computes again is not counted), over the
device time under `attn_core`, over the chips' bf16 peak."""
from benchmark.harness import scopes


def read(run):
    red = scopes.device(run)
    steps = (run["host"].get("traced") or {}).get("steps")
    if red is None or not steps:
        return None
    seconds = scopes.seconds_under(red, ("attn_core",))
    if not seconds:
        return None
    cell = run["cell"]
    tr = cell["traffic"]
    flops = cell["family"].attention_flops(
        cell["config"]["model"], int(tr["batch_size"]), int(tr["seq_len"]), steps
    )
    peak = run["peaks"]["bf16_flops_per_s"] * run["device"]["count"]
    return 100.0 * flops / seconds / peak
