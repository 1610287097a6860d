"""What every kind of cell shares: platform set-up, the device line,
tracing on and off, readers, and the result line."""

from __future__ import annotations

import glob
import importlib
import json
import os
import shutil
import sys
import time

from benchmark.harness import manifest, peaks
from benchmark.harness import trace as trace_mod

RUN_DIR = os.path.join(manifest.BENCH_DIR, ".run")


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def log_times(what: str, seconds: list[float]) -> None:
    """Every time of a window on one line, in milliseconds: a run that reads
    far off shows here which step or cycle it was."""
    ms = [round(t * 1e3, 1) for t in seconds]
    log(f"{what}: n {len(ms)}, min {min(ms, default=None)}, max {max(ms, default=None)}; all {ms}")


def device_line() -> dict:
    import jax

    devs = jax.local_devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak,
    }


def scratch_dir(name: str) -> str:
    """A directory under the checkout, emptied."""
    path = os.path.join(RUN_DIR, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


class Tracer:
    """The profiler around a sub-window of a traced run, and the program's
    own telemetry beside it. Off (`on=False`) it does nothing."""

    def __init__(self, on: bool, name: str):
        self.on = on
        self.dir = scratch_dir(f"trace-{name}") if on else None
        self.obs_dir = scratch_dir(f"obs-{name}") if on else None
        self.started = self.stopped = None
        if on:
            from tpuflow import obs

            obs.configure(self.obs_dir)

    def start(self) -> None:
        if self.on and self.started is None:
            import jax

            # No Python tracer: it makes starting and stopping take seconds
            # and the trace large; the benchmark's own annotations stay.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.started = time.monotonic()

    def stop(self) -> None:
        if self.on and self.started is not None and self.stopped is None:
            import jax

            self.stopped = time.monotonic()
            jax.profiler.stop_trace()

    def annotate(self, name: str):
        if self.on:
            import jax

            return jax.profiler.TraceAnnotation(name)
        import contextlib

        return contextlib.nullcontext()

    def finish(self) -> dict | None:
        """Stop, close the program's recorder, and reduce both."""
        if not self.on:
            return None
        self.stop()
        from tpuflow import obs

        obs.configure(None)
        events = []
        for path in glob.glob(os.path.join(self.obs_dir, "*.jsonl")):
            with open(path) as f:
                events += [json.loads(line) for line in f if line.strip()]
        out = {"program_events": events, "trace": None, "window_s": None}
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if files and self.started is not None:
            out["trace"] = trace_mod.reduce_trace(files[0])
            out["window_s"] = self.stopped - self.started
        return out


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             t_start: float, reach_chip_s: float | None = None,
             rehearse: bool = False) -> dict:
    """Drive one cell once. `rehearse` is for the tests: it skips nothing
    but the look for a chip, and the result then carries no device metric."""
    import jax

    from tpuflow import dist

    dist.maybe_enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    kind = cell["traffic"]["kind"]
    loop = importlib.import_module(f"benchmark.loops.{kind}")
    tracer = Tracer(trace, cell["name"])
    run = loop.run(cell, seed=seed, seconds=seconds, tracer=tracer, t_start=t_start)
    run["cell"] = cell
    run["host"]["reach_chip_s"] = reach_chip_s
    device = run["device"]
    if not rehearse:
        run["peaks"] = peaks.peaks_for(device["kind"])
    metrics = {}
    # A rehearsal (a CPU run) reports no device metric, under any name.
    if not rehearse and not trace:
        for m in cell["end_to_end"]:
            if m["name"] in run["end_to_end"]:
                metrics[m["name"]] = {"value": run["end_to_end"][m["name"]], "unit": m["unit"]}
    elif not rehearse:
        reduced = run.get("traced") or {}
        tr = reduced.get("trace") or {}
        if tr.get("busy_s"):
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = reduced["window_s"]
        for m in cell["per_layer"]:
            value = manifest.load_reader(m["name"])(run)
            if value is None:
                continue
            if m["unit"] == "%" and not value <= 105.0:
                log(f"share {m['name']} reads {value} > 100: counted too high")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": bool(run["correct"]),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if rehearse:
        result["rehearsal"] = True
    if trace and not rehearse and run.get("traced") and run["traced"].get("trace"):
        tr = run["traced"]["trace"]
        result["breakdown"] = {
            "device_ops": tr["device_ops"][:10], "idle_gaps": tr["idle_gaps"][:10],
        }
    result["compared"] = run["compared"]
    return result


def emit(result: dict) -> None:
    """Each number compared beside its limit as the last lines of standard
    error, then the result as the last line of standard output."""
    sys.stdout.flush()
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
