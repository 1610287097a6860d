"""The family's forward operations per token (2·N) times (prompt tokens
computed + tokens generated) per second of the window over the bf16 peak. Prompt tokens that the prefix cache served (its
pages hit over the window, times the page size) are not computed and not
counted. Decode is bound by memory, so this reads low."""
from benchmark.harness import readers


def read(run):
    h = run["host"]
    cached = (readers.serve_counter_delta(run, "prefix_hits") or 0) * (h.get("page_size") or 0)
    tokens = max(h.get("prompt_tokens", 0) - cached, 0) + h.get("output_tokens", 0)
    if not tokens or not h.get("window_s"):
        return None
    cell = run["cell"]
    per_token = cell["family"].forward_flops_per_token(cell["config"]["model"])
    peak = run["peaks"]["bf16_flops_per_s"] * run["device"]["count"]
    return 100.0 * per_token * tokens / h["window_s"] / peak
