"""Arithmetic the per-layer readers share. A reader takes the run (what the
loop measured on the host clock, the program's events and counters, the
reduced trace, the cell and the peaks) and returns a number, or None where
it finds nothing to read."""

from __future__ import annotations

import statistics


def median_step_ms(run):
    steps = run["host"].get("step_s") or []
    return statistics.median(steps) * 1e3 if steps else None


def idle_share(run):
    tr = (run.get("traced") or {}).get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / run["traced"]["window_s"])


def train_mfu(run, steps: float):
    """The family's operations per token times tokens/s of the traced
    sub-window, over the chips' bf16 peak."""
    traced = run["host"].get("traced") or {}
    if not traced.get("s") or not steps:
        return None
    cell = run["cell"]
    per_token = cell["family"].train_flops_per_token(cell["config"]["model"])
    tokens_per_s = steps * run["host"]["tokens_per_step"] / traced["s"]
    peak = run["peaks"]["bf16_flops_per_s"] * run["device"]["count"]
    return 100.0 * per_token * tokens_per_s / peak


def program_events(run, kind: str, name: str) -> list[dict]:
    events = (run.get("traced") or {}).get("program_events") or []
    return [e for e in events if e.get("kind") == kind and e.get("name") == name]


def serve_counter_delta(run, key: str):
    c = run["host"].get("counters") or {}
    if "open" not in c or "close" not in c:
        return None
    a, b = c["open"][key], c["close"][key]
    if isinstance(a, dict):
        return {k: b[k] - a[k] for k in a}
    return b - a


def serve_bucket_share(run, bucket: str):
    d = serve_counter_delta(run, "buckets")
    if not d or sum(d.values()) <= 0:
        return None
    return 100.0 * d[bucket] / sum(d.values())
