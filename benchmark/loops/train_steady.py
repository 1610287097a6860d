"""`kind: train_steady`: steps through the loader, nothing else in the
window. Open on the fence of the last warm-up step, close on the fence of
the last step dispatched."""

from __future__ import annotations

import time

from benchmark.harness import windows
from benchmark.harness.runner import device_line, log_times
from benchmark.loops._train import CHECK_STEPS, TrainRig, judge_train


def run(cell: dict, *, seed: int, seconds: float, tracer, t_start: float) -> dict:
    tr = cell["traffic"]
    rig = TrainRig(cell, seed)
    prog = rig.first_steps()
    handle = None
    for _ in range(max(int(tr["warmup_steps"]) - CHECK_STEPS, 1)):
        handle = rig.dispatch()
    rig.fence(handle)

    trace_steps = int(tr.get("trace_steps", 0)) if tracer.on else 0
    traced = {"steps": 0}

    def dispatch():
        with tracer.annotate("bench.train_step_dispatch"):
            return rig.dispatch()

    def fence(h):
        with tracer.annotate("bench.step_fence"):
            rig.fence(h)

    clock = time.monotonic
    setup_s = clock() - t_start
    if trace_steps:
        # The traced sub-window: whole steps between two fences.
        tracer.start()
        sub = windows.steady_window(
            clock, float("inf"), dispatch, fence, depth=rig.depth, max_steps=trace_steps
        )
        tracer.stop()
        traced = {"steps": sub["steps"], "s": sub["close"] - sub["open"]}
    win = windows.steady_window(clock, seconds, dispatch, fence, depth=rig.depth)
    tokens = win["steps"] * rig.batch * rig.seq
    window_s = win["close"] - win["open"]
    fences = win["fences"]
    step_s = [b - a for a, b in zip(fences, fences[1:])]
    log_times(f"steps (the window's first fence came {fences[0] - win['open']:.4f} s "
              f"after it opened, {win['steps']} steps in {window_s:.4f} s)", step_s)
    device = device_line()
    reduced = tracer.finish()
    rig.free()
    correct, compared = judge_train(rig, prog, cell)
    return {
        "correct": correct,
        "compared": compared,
        "attempted": win["steps"],
        "failed": 0,
        "device": device,
        "end_to_end": {
            "train_tokens_per_s": tokens / window_s,
            "setup_s": setup_s,
        },
        "host": {
            "step_s": step_s,
            "window_s": window_s,
            "tokens_per_step": rig.batch * rig.seq,
            "traced": traced,
        },
        "traced": reduced,
    }
