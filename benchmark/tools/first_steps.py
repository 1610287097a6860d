"""The look behind a limit of a training cell: per seed, in one process, the
program's first three steps (the cell's own rig, as a run drives it), the
plain reference over the same batches, and two witnesses that are the
reference again: with every matrix product's operands and gradients rounded
to bfloat16, the precision the configuration states, and in float32 with
the rows summed in blocks of two, which changes nothing but the order of
float32 sums. Each is compared with the reference as a run compares the
program. The benchmark's own runs never run this.

    chiprun -- python benchmark/tools/first_steps.py --workload <cell> --seeds 11 12 13

One JSON line per seed goes to chiprun_out/first-steps-<cell>.jsonl: every
loss, every number compared, and every leaf's gap.
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def leaf_gaps(prog: dict, ref: dict) -> dict:
    med = statistics.median(ref.values())
    return {n: abs(prog[n] - r) / max(r, med) for n, r in ref.items()}


def readings(cell: dict, seed: int, witnesses: list[str]) -> dict:
    from benchmark.harness import check
    from benchmark.loops._train import TrainRig

    rig = TrainRig(cell, seed)
    prog = rig.first_steps()
    batches = rig.batches_for_reference()
    rig.free()
    m, opt, fam = rig.m, rig.opt, rig.family
    del rig
    rows = int(cell["traffic"].get("reference_rows_per_block", 1))
    t0 = time.monotonic()
    ref = fam.train_reference(m, opt, seed, batches, rows_per_block=rows)
    out = {"seed": seed, "reference_s": time.monotonic() - t0,
           "reference_losses": ref["losses"],
           "reference_grad_norms": ref["grad_norms"],
           "reference_dparam_norms": ref["dparam_norms"]}
    runs = {"program": prog}
    if "bf16" in witnesses:
        runs["bf16"] = fam.train_reference(
            m, opt, seed, batches, rows_per_block=rows, quant="bf16")
    if "reorder" in witnesses:
        runs["reorder"] = fam.train_reference(
            m, opt, seed, batches, rows_per_block=2 * rows)
    for name, run in runs.items():
        out[name] = check.compare_train(run, ref)
        out[name]["losses"] = run["losses"]
        out[name]["grad_leaf_gaps"] = leaf_gaps(run["grad_norms"], ref["grad_norms"])
        out[name]["dparam_leaf_gaps"] = leaf_gaps(run["dparam_norms"], ref["dparam_norms"])
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--witnesses", nargs="*", default=["bf16"], choices=["bf16", "reorder"])
    ap.add_argument("--reorder-seeds", type=int, nargs="*", default=[],
                    help="seeds that also get the float32 reordered witness")
    args = ap.parse_args()
    from benchmark.harness import manifest
    from tpuflow import dist

    dist.maybe_enable_compile_cache()
    cell = manifest.load_cell(args.workload, ROOT)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    keys = ("loss1_gap", "loss2_gap", "loss3_gap", "grad_gap", "grad_gap_leaf",
            "dparam_gap", "dparam_gap_leaf")
    with open(os.path.join(out_dir, f"first-steps-{args.workload}.jsonl"), "a") as f:
        for seed in args.seeds:
            wit = list(args.witnesses) + (["reorder"] if seed in args.reorder_seeds else [])
            try:
                row = readings(cell, seed, wit)
            except Exception as e:  # one seed lost is not the call lost
                row = {"seed": seed, "error": repr(e)[:500]}
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps({"seed": seed, "ref_losses": row.get("reference_losses"), **{
                who: {k: row[who][k] for k in keys} | {"losses": row[who]["losses"]}
                for who in ("program", "bf16", "reorder") if who in row
            }, **({"error": row["error"]} if "error" in row else {})}), flush=True)


if __name__ == "__main__":
    main()
