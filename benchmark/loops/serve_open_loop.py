"""`kind: serve_open_loop`: requests sent on a schedule drawn from the seed,
whether or not earlier ones have finished, into the program's `ServeEngine`.
A pre-roll at the same rate and mix is served and not counted; every request
due inside the window is counted and timed from when it was due."""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark.harness import check, traffic, windows
from benchmark.harness.reference import seed_key
from benchmark.harness.runner import device_line, log

WORST_S = 1e6  # a request that never finished counts as the worst latency
WATCH_INTERVAL_S = 0.005  # how often the watching thread looks at the handles


def run(cell: dict, *, seed: int, seconds: float, tracer, t_start: float) -> dict:
    import jax

    from tpuflow.infer.serve import ServeEngine

    cfg, tr, fam = cell["config"], cell["traffic"], cell["family"]
    m = cfg["model"]
    model = fam.module(m)
    params = jax.jit(lambda k: fam.make_params(m, k))(seed_key(seed))
    engine = ServeEngine(model, params, buckets=list(tr["buckets"]), **cfg["serve"])
    stats = engine.warmup()
    log(f"engine warm: {stats}")
    sched = traffic.serve_schedule(tr, seed, seconds, fam.vocabulary(m), fam.positions(m))
    due = [float(d) for d in sched["due"]]
    n = len(due)
    preroll, span = sched["preroll_s"], sched["span_s"]
    handles: list = [None] * n
    t_first = [None] * n
    t_last = [None] * n
    open_ids: list[int] = []
    window_counters: dict = {}
    clock = time.monotonic
    t0_box: list[float] = []
    watching = threading.Event()

    def watch() -> None:
        """Stamp, on the benchmark's own clock, when a request's first token
        and its last became visible in its handle: the engine produces them
        inside `step()`, so a second thread looks every few milliseconds."""
        while not watching.is_set():
            if t0_box:
                now = clock() - t0_box[0]
                for i in list(open_ids):
                    h = handles[i]
                    if h is None:
                        continue
                    if t_first[i] is None and h.tokens:
                        t_first[i] = now
                    if t_last[i] is None and h.done:
                        t_last[i] = now
            watching.wait(WATCH_INTERVAL_S)

    def submit(i: int) -> None:
        with tracer.annotate("bench.submit"):
            handles[i] = engine.submit(sched["prompts"][i], max_new_tokens=sched["max_new"][i])
        open_ids.append(i)

    def step() -> bool:
        with tracer.annotate("bench.engine_step"):
            return engine.step()

    def snapshot() -> dict:
        led = engine.ledger.snapshot()
        return {
            "prefix_hits": engine.pool.prefix_hits if engine.pool else 0,
            "prefix_lookups": engine.pool.prefix_lookups if engine.pool else 0,
            "buckets": dict(led["buckets"]),
            "decode_utilization": led["decode_utilization"],
        }

    def observe(now: float) -> int:
        if not t0_box:
            t0_box.append(clock() - now)
        for i in list(open_ids):
            if t_last[i] is not None:
                open_ids.remove(i)
        if "open" not in window_counters and now >= preroll:
            window_counters["open"] = snapshot()
        if tracer.on and now >= span - float(tr["trace_seconds"]):
            tracer.start()
        if "close" not in window_counters and now >= span:
            window_counters["close"] = snapshot()
            tracer.stop()
        return len(open_ids)

    compiles_before = engine.compile_stats()
    # A traced run traces the window's last `trace_seconds`: starting the
    # profiler is cheap, stopping it stalls the loop for about as long as it
    # traced, so the stop comes at the close and only the drain waits for it.
    watcher = threading.Thread(target=watch, name="bench-watch", daemon=True)
    watcher.start()
    setup_s = (clock() - t_start) + preroll  # the window opens after the pre-roll
    try:
        loop = windows.open_loop(
            clock, time.sleep, due, submit, step, observe, drain_s=float(tr["drain_s"])
        )
    finally:
        watching.set()
        watcher.join(timeout=5.0)
    window_counters.setdefault("close", snapshot())
    compiled_in_window = engine.compile_stats() != compiles_before
    device = device_line()
    reduced = tracer.finish()

    counted = [i for i in range(n) if sched["counted"][i]]
    ttft, tpot, queue = [], [], []
    failed = 0
    for i in counted:
        h = handles[i]
        if h is None or not h.done or t_first[i] is None:
            failed += 1
            ttft.append(WORST_S)
            tpot.append(WORST_S)
            continue
        ttft.append(t_first[i] - due[i])
        queue.append(h.t_admit - loop["t0"] - due[i])  # the engine's own stamp
        if len(h.tokens) > 1:
            tpot.append((t_last[i] - t_first[i]) / (len(h.tokens) - 1))
    late = [loop["late"][i] for i in counted if i < len(loop["late"])]
    backlog = sum(1 for i in range(n) if due[i] < span and (t_last[i] is None or t_last[i] > span))
    log(f"{len(counted)} requests due in the window, {failed} failed; generator ran "
        f"late by p50 {windows.percentile(late, 50) * 1e3:.1f} ms, "
        f"max {max(late) * 1e3:.1f} ms; unfinished at the close {backlog}; "
        f"drain ended {loop['end'] - span:.1f}s after it")

    # A sample of the finished requests, the longest among them, for the
    # reference; the engine's state is freed first.
    done = [i for i in counted if handles[i] is not None and handles[i].done]
    rng = np.random.default_rng((int(seed), 3))
    done.sort(key=lambda i: -(len(sched["prompts"][i]) + len(handles[i].tokens)))
    pick = done[:1] + list(rng.permutation(done[1:])[: int(tr["check_requests"]) - 1])
    samples = [(sched["prompts"][i], np.asarray(handles[i].tokens, np.int32)) for i in pick]
    prompt_tokens = sum(len(sched["prompts"][i]) for i in counted)
    out_tokens = sum(len(handles[i].tokens) for i in counted if handles[i] is not None)
    live_ctx = [
        len(sched["prompts"][i]) + 0.5 * len(handles[i].tokens)
        for i in counted if handles[i] is not None
    ]
    decode_block, max_slots = engine.decode_block, engine.max_slots
    page_size = engine.pool.page_size if engine.pool else None
    first = {
        "ttft_mean_ms": float(np.mean(ttft)) * 1e3,
        "ttft_p50_ms": windows.percentile(ttft, 50) * 1e3,
        "ttft_p90_ms": windows.percentile(ttft, 90) * 1e3,
    }
    # No end-to-end metric of the first token yet (PERF.md section 7): what
    # admission and the prefix cache do is printed here, in every run.
    close, opened = window_counters["close"], window_counters.get("open", {})
    lookups = close["prefix_lookups"] - opened.get("prefix_lookups", 0)
    hits = close["prefix_hits"] - opened.get("prefix_hits", 0)
    log(f"first token, due to visible, over {len(ttft)} requests: {first}; due to admitted "
        f"p50 {windows.percentile(queue, 50) * 1e3 if queue else None} ms, "
        f"p90 {windows.percentile(queue, 90) * 1e3 if queue else None} ms; prompt pages from "
        f"the prefix cache {hits} of {lookups}")
    del engine, params, handles
    t0 = clock()
    gaps = fam.serve_gaps(m, seed, samples)
    log(f"reference over {len(samples)} requests, {gaps['tokens']} served tokens, "
        f"in {clock() - t0:.1f}s")
    numbers = {
        "widest_logit_gap": gaps["widest_gap"],
        "requests_failed": failed,
        "compiled_in_window": int(compiled_in_window),
    }
    correct, compared = check.judge(numbers, cell["limits"])
    return {
        "correct": correct,
        "compared": compared,
        "attempted": len(counted),
        "failed": failed,
        "device": device,
        "end_to_end": {
            "tpot_p90_ms": windows.percentile(tpot, 90) * 1e3,
            "setup_s": setup_s,
        },
        "host": {
            "ttft_s": ttft,
            "queue_wait_s": queue,
            "late_s": late,
            "window_s": float(seconds),
            "counters": window_counters,
            "prompt_tokens": prompt_tokens,
            "output_tokens": out_tokens,
            "mean_live_context": float(np.mean(live_ctx)) if live_ctx else 0.0,
            "decode_block": decode_block,
            "page_size": page_size,
            "max_slots": max_slots,
            "backlog_at_close": backlog,
        },
        "traced": reduced,
    }
