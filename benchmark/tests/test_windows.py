"""Window arithmetic on a fake clock: an edge never splits a step or a
cycle, and a stall inside the window moves the rate and the tail."""

from benchmark.harness import windows


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def steady(seconds, step_s, stall_at=None, stall_s=0.0, depth=2):
    clock = FakeClock()
    done = {"n": 0, "fenced": 0}

    def dispatch():
        done["n"] += 1
        return done["n"]

    def fence(h):
        # the device runs steps back to back: fencing step h ends it
        clock.t += step_s + (stall_s if h == stall_at else 0.0)
        done["fenced"] = h

    win = windows.steady_window(clock, seconds, dispatch, fence, depth=depth)
    return win, done


def test_steady_window_counts_whole_steps_between_fences():
    win, done = steady(seconds=10.0, step_s=0.7)
    assert done["fenced"] == win["steps"] == done["n"]  # closed on the last dispatched
    assert abs((win["close"] - win["open"]) - win["steps"] * 0.7) < 1e-9
    assert win["close"] - win["open"] >= 10.0  # never a step cut at the deadline
    assert len(win["fences"]) == win["steps"]


def test_steady_window_rate_is_the_same_whatever_the_deadline_cuts():
    rates = []
    for seconds in (10.0, 10.3, 10.69, 11.1):
        win, _ = steady(seconds=seconds, step_s=0.7)
        rates.append(win["steps"] / (win["close"] - win["open"]))
    assert max(rates) - min(rates) < 1e-9


def test_steady_window_stall_moves_the_rate():
    base, _ = steady(seconds=10.0, step_s=0.7)
    stalled, _ = steady(seconds=10.0, step_s=0.7, stall_at=5, stall_s=2.0)
    r0 = base["steps"] / (base["close"] - base["open"])
    r1 = stalled["steps"] / (stalled["close"] - stalled["open"])
    assert r1 < r0 * 0.9


def test_steady_window_max_steps():
    clock = FakeClock()
    win = windows.steady_window(
        clock, float("inf"), lambda: 1, lambda h: clock.sleep(0.5), max_steps=7
    )
    assert win["steps"] == 7 and abs(win["close"] - win["open"] - 3.5) < 1e-9


def cycles(seconds, cycle_s, stall_in=None, stall_s=0.0):
    clock = FakeClock()
    n = {"c": 0}

    def run_cycle():
        n["c"] += 1
        clock.t += cycle_s + (stall_s if n["c"] == stall_in else 0.0)

    return windows.cycle_window(clock, seconds, run_cycle, expected_s=cycle_s)


def test_cycle_window_holds_whole_cycles_inside_the_deadline():
    win = cycles(seconds=51.0, cycle_s=9.5)
    assert win["cycles"] == 5
    assert abs((win["close"] - win["open"]) - 5 * 9.5) < 1e-9
    assert win["close"] - win["open"] <= 51.0


def test_cycle_window_rate_does_not_depend_on_where_the_deadline_falls():
    rates = []
    for seconds in (40.0, 45.0, 47.4, 51.0):
        win = cycles(seconds=seconds, cycle_s=9.5)
        rates.append(win["cycles"] / (win["close"] - win["open"]))
    assert max(rates) - min(rates) < 1e-12


def test_cycle_window_stall_moves_the_rate_and_one_cycle_always_runs():
    base = cycles(seconds=51.0, cycle_s=9.5)
    stalled = cycles(seconds=51.0, cycle_s=9.5, stall_in=2, stall_s=3.0)
    assert stalled["cycles"] / (stalled["close"] - stalled["open"]) < base["cycles"] / (
        base["close"] - base["open"]
    )
    assert cycles(seconds=1.0, cycle_s=9.5)["cycles"] == 1


def open_loop(step_s, stall_at=None, stall_s=0.0, n=40, gap=0.5, service_steps=2):
    clock = FakeClock()
    due = [gap * (i + 1) for i in range(n)]
    left = {}
    first = {}
    steps = {"n": 0}

    def submit(i):
        left[i] = service_steps

    def step():
        if not left:
            return False
        steps["n"] += 1
        clock.t += step_s + (stall_s if steps["n"] == stall_at else 0.0)
        for i in list(left):
            left[i] -= 1
        return True

    def observe(now):
        for i in list(left):
            if left[i] < service_steps:
                first.setdefault(i, now)
            if left[i] <= 0:
                del left[i]
        return len(left)

    loop = windows.open_loop(clock, clock.sleep, due, submit, step, observe, drain_s=60.0)
    ttft = [first[i] - due[i] for i in range(n)]
    return loop, ttft


def test_open_loop_times_from_due_and_a_stall_moves_the_tail():
    loop, ttft = open_loop(step_s=0.1)
    assert loop["unfinished"] == 0 and len(loop["sent"]) == 40
    assert all(late >= 0 for late in loop["late"])
    base = windows.percentile(ttft, 90)
    _, stalled = open_loop(step_s=0.1, stall_at=30, stall_s=3.0)
    # requests that came due during the stall waited for it: counted from due
    assert windows.percentile(stalled, 90) > base + 0.5
    assert max(stalled) >= 2.5


def test_open_loop_gives_up_after_the_drain():
    clock = FakeClock()

    def step():
        clock.t += 1.0
        return True

    loop = windows.open_loop(clock, clock.sleep, [0.5], lambda i: None, step,
                             lambda now: 1, drain_s=5.0)
    assert loop["unfinished"] == 1 and loop["end"] > 5.0


def test_percentile_is_nearest_rank():
    assert windows.percentile(list(range(1, 101)), 90) == 90
    assert windows.percentile([5.0], 90) == 5.0
    assert windows.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == 9


def test_cycle_window_is_capped():
    clock = FakeClock()
    win = windows.cycle_window(clock, 51.0, lambda: clock.sleep(2.0), expected_s=2.0, max_cycles=4)
    assert win["cycles"] == 4 and abs(win["close"] - win["open"] - 8.0) < 1e-9
