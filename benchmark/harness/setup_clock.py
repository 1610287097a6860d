"""Set-up on the program's clock (PR 39): where the part of set-up that the
program can see begins and ends, and the seconds its spans cover there.

The program's recorder writes `obs.start` when it is made, which the
benchmark does right after `reach_chip_s` is taken; its spans then cover
the engine's or the state's construction, each warmed program, and every
trace, lowering and compile (JAX's own events). Set-up ends where a
serving run's open loop makes its first scheduler step (`serve.step`: the
first `serve.admit` comes later, when the first request is due, and the
loop sleeps until then), and where a training run dispatches its first
step after the warm-up. The loader waits once a step (`data.wait`), and
the last waits are the window's steps (`attempted`) and, before them,
the traced steps: counting back over both finds that step exactly, where
`scopes.window_open` would also count the profiler's start and stop
between the traced steps and the window (3.5 s on the chip, PR 39)."""

from __future__ import annotations

from benchmark.harness import scopes
from benchmark.harness import trace as trace_mod

TRACE_LOWER = ("trace", "lower")
COMPILE_STAGES = ("trace", "lower", "compile")


def start(run: dict) -> float | None:
    """The recorder's `obs.start`, or None for a program from before PR 39."""
    events = (run.get("traced") or {}).get("program_events") or []
    marks = [e["mono"] for e in events if e.get("name") == "obs.start" and "mono" in e]
    return min(marks) if marks else None


def end(run: dict) -> float | None:
    """The end of set-up on the program's clock (see the module's text),
    or None where the events do not show it."""
    spans = scopes.spans(run)
    steps = [e["mono"] for e in spans if e["name"] == "serve.step"]
    if steps:
        return min(steps)
    waits = sorted(e["mono"] for e in spans if e["name"] == "data.wait")
    traced = ((run.get("host") or {}).get("traced") or {}).get("steps", 0)
    after = int(run.get("attempted") or 0) + int(traced)
    return waits[-after] if 0 < after <= len(waits) else None


def clipped(spans: list[dict], t0: float, t1: float) -> list[tuple[float, float]]:
    """The spans' intervals inside [t0, t1]."""
    out = []
    for e in spans:
        s, f = max(e["mono"], t0), min(e["mono"] + e["dur_s"], t1)
        if f > s:
            out.append((s, f))
    return out


def covered(intervals: list[tuple[float, float]]) -> float:
    """Seconds under the union of `intervals`: what nests counts once."""
    return sum(f - s for s, f in trace_mod._union(intervals))


def gaps(intervals: list[tuple[float, float]], t0: float, t1: float) -> list[tuple[float, float]]:
    """The parts of [t0, t1] under none of `intervals`."""
    out, at = [], t0
    for s, f in trace_mod._union(intervals):
        if s > at:
            out.append((at, s))
        at = max(at, f)
    if t1 > at:
        out.append((at, t1))
    return out
