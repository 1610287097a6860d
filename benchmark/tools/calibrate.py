"""Readings for the limits of `correct`, taken on the chip at a cell's own
size: the control (the reference computed in float8, put in the program's
place) and the faults a cell can have, planted in the reference. The
benchmark's own runs never run this.

    chiprun -- python benchmark/tools/calibrate.py --workload <cell> --seeds 11 12 13

Training cells: per seed, the reference, the float8 control and the
half-batch fault follow the first three steps over the batches the cell's
loader would feed; each is compared with the reference as the program is.
Serving cells: a short window at the cell's own load, then the reference
and the control over the same prompts and served tokens.
One JSON line per seed goes to chiprun_out/calibrate-<cell>.jsonl.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def train_readings(cell: dict, seed: int) -> dict:
    import numpy as np

    from benchmark.harness import check, traffic

    fam = cell["family"]
    m, opt, tr = cell["config"]["model"], cell["config"]["optimizer"], cell["traffic"]
    corpus = traffic.lm_corpus(seed, int(tr["corpus_rows"]), int(tr["seq_len"]), fam.vocabulary(m))
    order = np.random.default_rng((seed, 0)).permutation(len(corpus))
    b = int(tr["batch_size"])
    batches = [
        (corpus[order[i * b:(i + 1) * b], :-1], corpus[order[i * b:(i + 1) * b], 1:])
        for i in range(3)
    ]
    rows = int(tr.get("reference_rows_per_block", 1))
    out = {"seed": seed}
    t0 = time.monotonic()
    ref = fam.train_reference(m, opt, seed, batches, rows_per_block=rows)
    out["reference_s"] = time.monotonic() - t0
    for name, kw in (("control_fp8", {"quant": "fp8"}), ("fault_half_batch", {"fault": "half_batch"})):
        other = fam.train_reference(m, opt, seed, batches, rows_per_block=rows, **kw)
        out[name] = check.compare_train(other, ref)
    return out


def serve_readings(cell: dict, seed: int, seconds: float) -> dict:
    from benchmark.harness import runner

    got = {}
    fam = cell["family"]
    plain = fam.serve_gaps

    def both(m, seed, samples, **_):
        got.update(plain(m, seed, samples, quant="fp8"))
        return got

    fam.serve_gaps = both  # on the cell's own family object, for this run
    try:
        res = runner.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                              t_start=time.monotonic(), rehearse=True)
    finally:
        del fam.serve_gaps
    return {"seed": seed, "program_widest_gap": got["widest_gap"],
            "control_fp8_widest_gap": got["widest_gap_low"], "tokens": got["tokens"],
            "failed": res["failed"], "attempted": res["attempted"]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args()
    from benchmark.harness import manifest
    from tpuflow import dist

    dist.maybe_enable_compile_cache()
    cell = manifest.load_cell(args.workload, ROOT)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"calibrate-{args.workload}.jsonl"), "a") as f:
        for seed in args.seeds:
            if cell["traffic"]["kind"] == "serve_open_loop":
                row = serve_readings(cell, seed, args.seconds)
            else:
                row = train_readings(cell, seed)
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
