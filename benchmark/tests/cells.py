"""Cells at the `test` width for the CPU rehearsals: same kinds, same
loops, tiny sizes. They are not cells of BENCHMARK.json. The configuration
and the limits that rounding sets are the family's (`test_config()`), so a
later family's rehearsals arrive with its file; the traffic and the exact
limits are the kind's."""

import glob
import os

from benchmark.harness import manifest

# Per kind: the group of a configuration it runs on beside `model`, which of
# the family's limits it is held to, its exact limits, and its traffic.
KINDS = {
    "train_steady": {
        "group": "optimizer", "limits": "train", "exact": {},
        "traffic": {"kind": "train_steady", "batch_size": 4, "seq_len": 64,
                    "corpus_rows": 64, "warmup_steps": 4, "trace_steps": 2,
                    "reference_rows_per_block": 2},
    },
    "train_ckpt": {
        "group": "optimizer", "limits": "train",
        "exact": {"ckpt_steps_missing": 0, "ckpt_leaves_mismatched": 0},
        "traffic": {"kind": "train_ckpt", "batch_size": 4, "seq_len": 64,
                    "corpus_rows": 64, "steps_per_cycle": 4, "max_to_keep": 2, "max_cycles": 3,
                    "trace_cycles": 1, "reference_rows_per_block": 2},
    },
    "serve_open_loop": {
        "group": "serve", "limits": "serve",
        "exact": {"requests_failed": 0, "compiled_in_window": 0},
        "traffic": {"kind": "serve_open_loop", "rate_per_s": 8.0, "preroll_s": 0.5,
                    "drain_s": 30.0, "arrival_seed": 1,
                    "system_prompts": {"count": 2, "tokens": 32, "zipf_s": 1.0},
                    "user_tokens": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
                    "output_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
                    "buckets": [48, 64], "check_requests": 6, "trace_seconds": 0.5},
    },
}


def _on_disk(directory: str) -> list[str]:
    files = glob.glob(os.path.join(manifest.BENCH_DIR, directory, "*.py"))
    names = (os.path.splitext(os.path.basename(f))[0] for f in files)
    return sorted(n for n in names if not n.startswith("_"))


def families() -> list[str]:
    """The families found under `benchmark/families/`."""
    return _on_disk("families")


def pairs() -> list[tuple[str, str]]:
    """(family, kind) for every family on disk and every loop kind on disk
    whose group the family's `test_config()` holds."""
    out = []
    for family in families():
        config = manifest.load_family(family).test_config()
        out += [(family, kind) for kind in _on_disk("loops") if KINDS[kind]["group"] in config]
    return out


def cell(kind: str, family: str) -> dict:
    fam = manifest.load_family(family)
    config = fam.test_config()
    spec = KINDS[kind]
    return {"name": "test-" + kind, "chips": 1, "config_name": family + "-test",
            "traffic_name": kind, "config": config, "family": fam,
            "traffic": dict(spec["traffic"]),
            "limits": {**config["limits"][spec["limits"]], **spec["exact"]},
            "end_to_end": [], "per_layer": [], "run_seconds": 2}
