"""The knee sweep of a serving cell: the same mix at several offered rates,
each in a process of its own on the chip (this parent never touches JAX).
Prints, per rate, the tails, the backlog at the close and how late the
generator ran.

    chiprun -- python benchmark/tools/sweep.py --workload <cell> --rates 1.2 1.6 2.0
"""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def one(cell: dict, rate: float, seconds: float, seed: int) -> dict:
    """The cell's mix at another rate, once."""
    from benchmark.harness import runner, windows
    from benchmark.loops import serve_open_loop

    cell = {**cell, "traffic": {**cell["traffic"], "rate_per_s": rate}}
    run = serve_open_loop.run(
        cell, seed=seed, seconds=seconds, tracer=runner.Tracer(False, "sweep"),
        t_start=time.monotonic(),
    )
    h = run["host"]
    return {
        "rate_per_s": rate, "seconds": seconds, "attempted": run["attempted"],
        "failed": run["failed"], **run["end_to_end"],
        "ttft_p90_ms": windows.percentile(h["ttft_s"], 90) * 1e3,
        "queue_wait_p90_ms": windows.percentile(h["queue_wait_s"], 90) * 1e3,
        "backlog_at_close": h["backlog_at_close"],
        "late_p50_ms": windows.percentile(h["late_s"], 50) * 1e3,
        "late_max_ms": max(h["late_s"]) * 1e3,
        "tokens_per_s": h["output_tokens"] / seconds,
        "decode_utilization": h["counters"]["close"]["decode_utilization"],
        "correct": run["correct"], "widest_logit_gap": run["compared"]["widest_logit_gap"][0],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2468013579)
    ap.add_argument("--child", action="store_true")
    args = ap.parse_args()
    if args.child:
        from benchmark.harness import manifest
        from tpuflow import dist

        dist.maybe_enable_compile_cache()
        cell = manifest.load_cell(args.workload, ROOT)
        print("SWEEP " + json.dumps(one(cell, args.rates[0], args.seconds, args.seed)),
              flush=True)
        return
    out = os.path.join(ROOT, "chiprun_out", f"sweep-{args.workload}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    for rate in args.rates:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", "--workload", args.workload,
             "--rates", str(rate), "--seconds", str(args.seconds), "--seed", str(args.seed)],
            capture_output=True, text=True,
        )
        rows = [ln[6:] for ln in proc.stdout.splitlines() if ln.startswith("SWEEP ")]
        if not rows:
            print(f"rate {rate}: no result (exit {proc.returncode}): {proc.stderr[-600:]}")
            continue
        print(rows[-1], flush=True)
        with open(out, "a") as f:
            f.write(rows[-1] + "\n")


if __name__ == "__main__":
    main()
