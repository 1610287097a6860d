"""Cells at the `test` width for the CPU rehearsals: same kinds, same
loops, tiny sizes. They are not cells of BENCHMARK.json."""

MODEL = {"vocab_size": 512, "n_ctx": 128, "n_embd": 64, "n_layer": 2, "n_head": 4,
         "dropout": 0.0, "ln_eps": 1e-05, "attn_impl": "auto", "dtype": "bfloat16",
         "remat": True, "scan_layers": True}
CONFIG = {
    "model": MODEL,
    "optimizer": {"learning_rate": 3e-4, "optimizer": "adamw", "weight_decay": 1e-4,
                  "schedule": "constant"},
    "serve": {"max_slots": 4, "paged": True, "prefix_cache": True, "speculative": 0,
              "quant": None, "decode_block": 4},
}
# Set as the cells' own are, from readings at this size on the CPU: the
# program's bf16 path reads loss gaps of 3e-6 and a gradient gap of 0.002;
# the float8 control reads 8e-5 to 2e-4 on the first loss; half a batch
# reads 0.4 to 0.5 on the gradient (the control 0.024 to 0.033); a state left unchanged reads 1.
# The third loss is not compared, as in the cells' own files: at the cells'
# size it carries the rounding of two Adam updates (PERF.md section 6).
TRAIN_LIMITS = {"loss1_gap": 3e-5, "loss2_gap": 3e-5, "grad_gap": 0.01, "dparam_gap": 0.3}


def cell(kind: str) -> dict:
    traffic = {
        "train_steady": {"kind": "train_steady", "batch_size": 4, "seq_len": 64,
                         "corpus_rows": 64, "warmup_steps": 4, "trace_steps": 2,
                         "reference_rows_per_block": 2},
        "train_ckpt": {"kind": "train_ckpt", "batch_size": 4, "seq_len": 64,
                       "corpus_rows": 64, "steps_per_cycle": 4, "max_to_keep": 2, "max_cycles": 3,
                       "trace_cycles": 1, "reference_rows_per_block": 2},
        "serve_open_loop": {"kind": "serve_open_loop", "rate_per_s": 8.0, "preroll_s": 0.5,
                            "drain_s": 30.0, "arrival_seed": 1,
                            "system_prompts": {"count": 2, "tokens": 32, "zipf_s": 1.0},
                            "user_tokens": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
                            "output_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
                            "buckets": [48, 64], "check_requests": 6, "trace_seconds": 0.5},
    }[kind]
    limits = {
        "train_steady": TRAIN_LIMITS,
        "train_ckpt": {**TRAIN_LIMITS, "ckpt_steps_missing": 0, "ckpt_leaves_mismatched": 0},
        "serve_open_loop": {"widest_logit_gap": 0.05, "requests_failed": 0,
                            "compiled_in_window": 0},
    }[kind]
    return {"name": "test-" + kind, "chips": 1, "config_name": "gpt2-test",
            "traffic_name": kind, "config": CONFIG, "traffic": traffic, "limits": limits,
            "end_to_end": [], "per_layer": [], "run_seconds": 2}
