"""One run of one cell: `python benchmark/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>`. A new process each time; fails where JAX finds
no TPU or fewer chips than the cell asks for; the last line of standard
output is one JSON object."""

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import manifest, runner

    cell = manifest.load_cell(args.workload, ROOT)
    if not os.path.isdir(os.path.join(ROOT, "tpuflow")):
        print("benchmark: no program here (tpuflow/ is missing)", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(
            f"benchmark: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
            f"JAX found {len(devices)} x {devices[0].platform}",
            file=sys.stderr,
        )
        return 3
    import jax.numpy as jnp

    jnp.zeros(()).block_until_ready()
    reach_chip_s = time.monotonic() - T_PROCESS_START
    result = runner.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_PROCESS_START, reach_chip_s=reach_chip_s,
    )
    runner.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
