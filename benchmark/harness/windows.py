"""The three windows, as plain loops over an injected clock.

Each takes callables for the work and a clock, so that the same code runs
on the chip and under a fake clock in the tests. An edge never splits a
step or a cycle: a window opens with nothing in flight (or, for cycles, in
the state in which it will close) and closes on a fence.
"""

from __future__ import annotations

import collections
from typing import Callable


def steady_window(clock: Callable[[], float], seconds: float,
                  dispatch: Callable[[], object], fence: Callable[[object], None],
                  depth: int = 2, max_steps: int | None = None) -> dict:
    """Dispatch steps while the clock is inside `seconds`, at most `depth`
    in flight (and, with `max_steps`, until that many are dispatched); close on
    the fence of the last step dispatched. The caller
    has fenced the last warm-up step, so nothing is in flight at the open.

    Returns steps, open, close and the time of every fence."""
    pending: collections.deque = collections.deque()
    fences: list[float] = []
    t_open = clock()
    steps = 0
    while clock() - t_open < seconds and (max_steps is None or steps < max_steps):
        pending.append(dispatch())
        steps += 1
        if len(pending) >= depth:
            fence(pending.popleft())
            fences.append(clock())
    while pending:
        fence(pending.popleft())
        fences.append(clock())
    return {"steps": steps, "open": t_open, "close": fences[-1], "fences": fences}


def cycle_window(clock: Callable[[], float], seconds: float,
                 run_cycle: Callable[[], None], expected_s: float,
                 max_cycles: int | None = None) -> dict:
    """Whole cycles only. `run_cycle` returns when its save call returns; the
    caller has just run a warm-up cycle, so the window opens right after a
    save call returned with its write in flight, the state in which it will
    close. A cycle starts while it is expected, from the cycles so far (the
    warm-up cycle's `expected_s` before any), to end inside `seconds`; one
    cycle always runs, and no more than `max_cycles` (a cell whose cycles
    write to disk caps what one run may write)."""
    t_open = clock()
    ends: list[float] = []
    while True:
        done = len(ends)
        mean = (ends[-1] - t_open) / done if done else expected_s
        if done and ((ends[-1] - t_open) + mean > seconds or done == max_cycles):
            break
        run_cycle()
        ends.append(clock())
    return {"cycles": len(ends), "open": t_open, "close": ends[-1], "ends": ends}


def open_loop(clock: Callable[[], float], sleep: Callable[[float], None],
              due: list[float], submit: Callable[[int], None],
              step: Callable[[], bool], observe: Callable[[float], int],
              drain_s: float) -> dict:
    """Send request i when `due[i]` has come, whether or not earlier ones
    have finished; between sends, drive the engine one step at a time and
    let `observe(now)` stamp what became visible (it returns how many
    requests are still unfinished). After the last arrival, drain for at
    most `drain_s`. Times are seconds from the loop's start.

    Returns the start, each request's submit time and how late it was."""
    t0 = clock()
    n = len(due)
    sent: list[float] = []
    i = 0
    unfinished = 0
    while True:
        now = clock() - t0
        while i < n and due[i] <= now:
            submit(i)
            sent.append(now)
            i += 1
        did = step()
        now = clock() - t0
        unfinished = observe(now)
        if i >= n and (unfinished == 0 or now > due[-1] + drain_s):
            break
        if not did and i < n:
            wait = due[i] - (clock() - t0)
            if wait > 0:
                sleep(wait)
    late = [s - d for s, d in zip(sent, due)]
    return {"t0": t0, "sent": sent, "late": late, "unfinished": unfinished,
            "end": clock() - t0}


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    v = sorted(values)
    k = max(int(-(-q * len(v) // 100)) - 1, 0)
    return float(v[min(k, len(v) - 1)])
