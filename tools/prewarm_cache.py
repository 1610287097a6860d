#!/usr/bin/env python
"""AOT compile-cache prewarm: pay the jit compiles BEFORE the run starts.

A large share of a cold start is XLA compiling programs whose shapes were
known before anything was scheduled. This tool AOT-lowers
(``jit(...).lower(...).compile()``) the signatures a run will execute —
the train step, the serving engine's decode-block program (fp and, with
``--quant``, the int8 twin), every bucket-prefill program, and the slot
insert — with JAX's persistent compilation cache on, so the compiled
executables land on disk without running a single step.

It writes straight into the ONE cache directory the run will read
(``dist.maybe_enable_compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` where
set, else ``.compile_cache`` in the checkout). JAX hashes the directory
into every entry's key, so entries copied in from another directory never
hit; set the same ``JAX_COMPILATION_CACHE_DIR`` for the prewarm and the
run. The rest of the key is HLO + compile options: entries hit only when
the shapes, mesh/sharding, and jax/XLA versions match the run — prewarm
on the same host image with the run's real ``--preset``/``--batch``/
``--seq-len``. A mismatch is harmless (the run compiles normally).
The train signature here is an UNSHARDED state outside any mesh, which is
not the program ``train_gpt`` builds (born-sharded state, batch sharding,
``with mesh``): it prewarms ``Trainer``-style single-device steps only.

Usage::

    python tools/prewarm_cache.py --preset gpt2 --batch 8 --seq-len 512 \
        [--no-train] [--no-serve] \
        [--quant] [--spec K] [--slots 8] [--buckets 16,32,64] \
        [--page-size 16] [--pages N]

CPU note: the persistent cache is OFF on CPU by default (the XLA:CPU
AOT loader can abort reloading entries across machine-feature changes —
see ``maybe_enable_compile_cache``); ``--allow-cpu`` force-enables it
for tests and dry runs of this tool.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Runnable from anywhere: put the repo root on sys.path like the other
# standalone tools.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--preset", default="test",
                   help="GPT2Config.from_preset name (test|gpt2|medium)")
    p.add_argument("--batch", type=int, default=2,
                   help="train-step global batch rows")
    p.add_argument("--seq-len", type=int, default=64,
                   help="train-step sequence length")
    p.add_argument("--no-train", action="store_true",
                   help="skip the train-step signature")
    p.add_argument("--accum-steps", type=int, default=1,
                   help="also AOT-lower the comm-overlapped FSDP "
                        "accumulation train step at this depth (ISSUE "
                        "10: the per-microbatch reduce-scatter program "
                        "is a DIFFERENT jit key than the plain step — "
                        "without this twin a gang arming "
                        "TPUFLOW_COMM_OVERLAP pays its compile cold)")
    p.add_argument("--no-serve", action="store_true",
                   help="skip the serving decode/prefill/insert signatures")
    p.add_argument("--quant", action="store_true",
                   help="also prewarm the int8 (fused-native) serving twin")
    p.add_argument("--spec", type=int, default=None, metavar="K",
                   help="arm per-request speculative decode at draft "
                        "length K and prewarm the verify-block "
                        "signature(s) (ISSUE 11: a spec-armed gang "
                        "would otherwise pay the verify compile cold)")
    p.add_argument("--page-size", type=int, default=None,
                   help="paged-KV page size (default TPUFLOW_SERVE_"
                        "PAGE_SIZE/16)")
    p.add_argument("--pages", type=int, default=None,
                   help="paged-KV pool size (default slots * n_ctx / "
                        "page_size + 1)")
    p.add_argument("--slots", type=int, default=None,
                   help="serving slots (default TPUFLOW_SERVE_SLOTS/8)")
    p.add_argument("--buckets", default=None,
                   help="comma prefill bucket widths (default ladder)")
    p.add_argument("--decode-block", type=int, default=None,
                   help="serving decode-block tokens")
    p.add_argument("--allow-cpu", action="store_true",
                   help="force-enable the persistent cache on CPU (tests)")
    return p.parse_args(argv)


def prewarm(args) -> dict:
    # Env staging must precede backend-touching imports/config.
    if args.allow_cpu:
        os.environ["TPUFLOW_COMPILE_CACHE_CPU"] = "1"

    import jax
    import jax.numpy as jnp

    from tpuflow.dist import maybe_enable_compile_cache

    cache_dir = maybe_enable_compile_cache()
    if cache_dir is None:
        raise SystemExit(
            "[prewarm] persistent compile cache is disabled here "
            "(TPUFLOW_COMPILE_CACHE=0, or a CPU platform without "
            "--allow-cpu) — nothing to prewarm into"
        )
    # Prewarm wants EVERY program persisted, including ones under the
    # default min-compile-time threshold (the whole point is that the
    # run skips even the small compiles).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from tpuflow.models.gpt2 import GPT2, GPT2Config
    from tpuflow.obs import device as device_mod

    # Device observatory (ISSUE 15): the prewarm pass holds every
    # compiled executable anyway — record the same per-program
    # compile/cost/memory ledger a live run writes, so an operator sees
    # program footprints (and the static HBM budget verdict) BEFORE any
    # gang launches.
    ledger = device_mod.ProgramLedger(source="prewarm")

    t0 = time.monotonic()
    cfg = GPT2Config.from_preset(args.preset, seq_len=args.seq_len)
    model = GPT2(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init(
        rng, jnp.zeros((1, min(8, cfg.n_ctx)), jnp.int32)
    )["params"]
    programs = 0

    if not args.no_train:
        from tpuflow.train.optim import make_optimizer
        from tpuflow.train.step import create_train_state, make_train_step

        state = create_train_state(
            model, rng, jnp.zeros((1, args.seq_len), jnp.int32),
            make_optimizer(3e-4),
        )
        batch = {
            "x": jnp.zeros((args.batch, args.seq_len), jnp.int32),
            "y": jnp.zeros((args.batch, args.seq_len), jnp.int32),
        }
        step = jax.jit(make_train_step(), donate_argnums=(0,))
        # lower().compile() goes through the same backend compile path
        # the hot loop's first step would — the executable lands in the
        # persistent cache without executing anything.
        t_step = time.monotonic()
        ledger.note_compiled(
            "train.step",
            step.lower(state, batch, rng).compile(),
            compile_s=time.monotonic() - t_step,
        )
        programs += 1
        if args.accum_steps > 1:
            # The comm-overlapped accumulation signature (ISSUE 10):
            # FSDP-sharded state + per-microbatch grad reduce-scatter —
            # the program train_gpt runs when accum_steps > 1 and
            # TPUFLOW_COMM_OVERLAP is armed. Mesh/shardings mirror the
            # FSDP leg's defaults on this host's device count; as with
            # every prewarm signature, a mismatch with the real run is
            # harmless (it just compiles normally).
            from tpuflow import dist
            from tpuflow.parallel import create_sharded_state
            from tpuflow.train.step import TrainState
            from tpuflow.train.optim import make_optimizer

            if args.batch % args.accum_steps:
                raise SystemExit(
                    f"[prewarm] --batch {args.batch} does not split "
                    f"into --accum-steps {args.accum_steps} equal "
                    "microbatches"
                )
            mesh = dist.make_mesh({"fsdp": len(jax.devices())})
            tx = make_optimizer(3e-4)

            def init_fn(rng):
                p = model.init(
                    rng, jnp.zeros((1, min(8, cfg.n_ctx)), jnp.int32)
                )["params"]
                return TrainState.create(
                    apply_fn=model.apply, params=p, tx=tx
                )

            with mesh:
                sstate, shardings = create_sharded_state(
                    init_fn, mesh, jax.random.PRNGKey(0), fsdp=True
                )
                ostep = make_train_step(
                    accum_steps=args.accum_steps,
                    grad_shardings=shardings.params,
                    comm_overlap=True,
                )
                bspec = jax.sharding.NamedSharding(
                    mesh,
                    jax.sharding.PartitionSpec(("data", "fsdp"), None),
                )
                obatch = {
                    k: jax.ShapeDtypeStruct(
                        (args.batch, args.seq_len), jnp.int32,
                        sharding=bspec,
                    )
                    for k in ("x", "y")
                }
                t_step = time.monotonic()
                ledger.note_compiled(
                    "train.step.overlap",
                    ostep.lower(sstate, obatch, rng).compile(),
                    compile_s=time.monotonic() - t_step,
                )
                programs += 1
            del sstate

    if not args.no_serve:
        from tpuflow.infer.serve import ServeEngine

        buckets = (
            [int(b) for b in args.buckets.split(",")]
            if args.buckets else None
        )
        engine = ServeEngine(
            model, params,
            max_slots=args.slots,
            buckets=buckets,
            decode_block=args.decode_block,
            quant="fused_native" if args.quant else None,
            page_size=args.page_size,
            n_pages=args.pages,
            speculative=args.spec,
        )
        # The engine owns its AOT signature list (decode block, verify
        # block, page insert, bucket prefills, int8 twins) so this
        # tool can never drift from the programs the scheduler replays
        # — ISSUE 11 moved the per-signature lowering into
        # ServeEngine.aot_lower when the paged/spec programs landed.
        programs += engine.aot_lower(ledger=ledger)

    # Program ledger + static HBM budget verdict beside the cache (the
    # operator's pre-launch footprint view; budget ratios absent off-TPU
    # where memory_stats is None).
    ledger.budget_check()
    ledger_path = ledger.write(os.path.join(cache_dir, "programs.json"))

    try:
        entries = len([
            f for f in os.listdir(cache_dir)
            if os.path.isfile(os.path.join(cache_dir, f))
        ])
    except OSError:
        entries = 0
    rec = {
        "cache_dir": cache_dir,
        "programs_compiled": programs,
        "cache_entries": entries,
        "wall_s": round(time.monotonic() - t0, 2),
        "backend": jax.default_backend(),
        "preset": args.preset,
    }
    if ledger_path:
        rec["programs_ledger_path"] = ledger_path
        if ledger.budget:
            rec["resident_bytes"] = ledger.budget.get("resident_bytes")
    return rec


def main(argv=None) -> int:
    rec = prewarm(_parse(argv if argv is not None else sys.argv[1:]))
    print(json.dumps(rec))
    print(
        f"[prewarm] {rec['programs_compiled']} programs -> "
        f"{rec['cache_entries']} cache entries in {rec['cache_dir']} "
        f"({rec['wall_s']}s)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
