"""`kind: train_ckpt`: cycles of steps and one asynchronous save of the full
train state through the program's `CheckpointManager`. A window holds whole
cycles, from the return of one save call to the return of the next."""

from __future__ import annotations

import os
import re
import time

import numpy as np

from benchmark.harness import windows
from benchmark.harness.runner import device_line, log, log_times, scratch_dir
from benchmark.loops._train import TrainRig, judge_train


STEP_DIR = re.compile(r"^step_(\d+)$")  # a staging directory ends in .tmp


def _checksums(tree):
    """Per leaf, a 32-bit hash of its bits and their places: every element's
    bits are mixed with its index (murmur3's finalizer) and the results
    summed, so a leaf read back with an element altered, or with rows or
    shards in another order, reads another number."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def mix(v):
        v = (v ^ (v >> 16)) * jnp.uint32(0x85EBCA6B)
        v = (v ^ (v >> 13)) * jnp.uint32(0xC2B2AE35)
        return v ^ (v >> 16)

    def one(a):
        flat = a.ravel()
        word = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[flat.dtype.itemsize]
        bits = lax.bitcast_convert_type(flat, word).astype(jnp.uint32)
        place = lax.iota(jnp.uint32, flat.size) * jnp.uint32(0x9E3779B1)
        return jnp.sum(mix(bits + place), dtype=jnp.uint32)

    return jax.jit(lambda t: jax.tree_util.tree_map(one, t))(tree)


def committed_steps(directory: str) -> set[int]:
    """The steps that stand committed in the directory, by the benchmark's
    own look: the program stages a save in `step_<n>.tmp` and publishes it by
    one rename. No wait (`CheckpointManager.all_steps()` would wait for the
    write in flight and make the save synchronous)."""
    return {int(m.group(1)) for m in map(STEP_DIR.match, os.listdir(directory)) if m}


def run(cell: dict, *, seed: int, seconds: float, tracer, t_start: float) -> dict:
    import jax

    from tpuflow import _native
    from tpuflow.ckpt import CheckpointManager

    tr = cell["traffic"]
    per_cycle = int(tr["steps_per_cycle"])
    rig = TrainRig(cell, seed)
    ckpt_dir = scratch_dir(f"ckpt-{cell['name']}")
    with rig.mesh:
        mgr = CheckpointManager(ckpt_dir, max_to_keep=int(tr["max_to_keep"]))
    log(f"checkpoint format {mgr.format}, shard writer "
        f"{'native' if _native.lib() is not None else 'numpy'}")
    prog = rig.first_steps()
    saves: list[dict] = []
    step_s: list[float] = []
    seen_committed: set[int] = set()
    clock = time.monotonic

    def payload():
        return {"step": rig.state.step, "params": rig.state.params,
                "opt_state": rig.state.opt_state}

    def run_cycle(first_steps_done: int = 0):
        with tracer.annotate("bench.train_steps"):
            win = windows.steady_window(
                clock, float("inf"), rig.dispatch, rig.fence, depth=rig.depth,
                max_steps=per_cycle - first_steps_done,
            )
        f = win["fences"]
        step_s.extend(b - a for a, b in zip(f[1:], f[2:]))  # steady steps only
        jax.block_until_ready(rig.state.params)
        step = rig.steps_done
        sums = _checksums(payload())
        t0 = clock()
        with tracer.annotate("bench.ckpt_save_call"), rig.mesh:
            mgr.save(
                step, payload(), metrics={"train_loss": float(rig.last_loss)},
                data_state=rig.loader.state_dict(step % len(rig.loader)),
            )
        saves.append({"step": step, "stall_s": clock() - t0, "sums": sums})
        # The save before this one has committed by now (the call waits for
        # it), and retention has not yet taken it away.
        seen_committed.update(committed_steps(ckpt_dir))

    t0 = clock()
    run_cycle(first_steps_done=len(prog["losses"]))  # the warm-up cycle
    warm_cycle_s = clock() - t0
    step_s.clear()
    n_warm = len(saves)
    setup_s = clock() - t_start
    if tracer.on:
        tracer.start()
    traced = {}

    def cycle():
        run_cycle()
        if tracer.on and tracer.stopped is None and len(saves) - n_warm >= int(tr.get("trace_cycles", 1)):
            tracer.stop()
            traced.update(cycles=len(saves) - n_warm, s=tracer.stopped - tracer.started)

    win = windows.cycle_window(
        clock, seconds, cycle, expected_s=warm_cycle_s, max_cycles=int(tr["max_cycles"])
    )
    window_s = win["close"] - win["open"]
    tokens = win["cycles"] * per_cycle * rig.batch * rig.seq
    log_times("cycles", [b - a for a, b in zip([win["open"]] + win["ends"], win["ends"])])
    log_times("save calls", [s["stall_s"] for s in saves[n_warm:]])
    log_times("steps between saves", step_s)
    with rig.mesh:
        mgr.wait_until_finished()
    device = device_line()
    reduced = tracer.finish()

    # Every step saved has to have stood committed in the directory: looked
    # for after each save call returned, and now. Those that retention kept
    # are read back, into an abstract sharded target, and every leaf's bits
    # held to what was saved.
    seen_committed.update(committed_steps(ckpt_dir))
    kept = mgr.all_steps()
    never_committed = sorted({s["step"] for s in saves} - seen_committed)
    expect_kept = [s["step"] for s in saves][-int(tr["max_to_keep"]):]
    tmpl = jax.tree_util.tree_map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        payload(),
        {"step": rig.shardings.step, "params": rig.shardings.params,
         "opt_state": rig.shardings.opt_state},
    )
    by_step = {s["step"]: s for s in saves}
    mismatched = 0
    restore_s = []
    rig.free()
    for step in kept:
        t0 = clock()
        with rig.mesh:
            restored = mgr.restore(step, abstract_state=tmpl)
            jax.block_until_ready(restored)
        restore_s.append(clock() - t0)
        got = jax.tree_util.tree_leaves(_checksums(restored))
        want = jax.tree_util.tree_leaves(by_step[step]["sums"]) if step in by_step else []
        mismatched += abs(len(got) - len(want))
        mismatched += sum(int(g) != int(w) for g, w in zip(got, want))
        del restored
    log(f"steps saved {[s['step'] for s in saves]}, seen committed {sorted(seen_committed)}, "
        f"kept {kept}; restore {[round(s, 3) for s in restore_s]} s each "
        f"(resume_s is an open question; this is the read-back alone)")
    mgr.close()
    in_window = saves[n_warm:]
    extra = {
        "ckpt_steps_missing": len(set(never_committed) | (set(expect_kept) - set(kept))),
        "ckpt_leaves_mismatched": mismatched,
    }
    correct, compared = judge_train(rig, prog, cell, extra)
    state_bytes = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tmpl)
    )
    return {
        "correct": correct,
        "compared": compared,
        "attempted": len(in_window),
        "failed": extra["ckpt_steps_missing"],
        "device": device,
        "end_to_end": {
            "ckpt_tokens_per_s": tokens / window_s,
            "setup_s": setup_s,
        },
        "host": {
            "step_s": step_s,
            "window_s": window_s,
            "tokens_per_step": rig.batch * rig.seq,
            "steps_per_cycle": per_cycle,
            "stall_s": [s["stall_s"] for s in in_window],
            "state_bytes": state_bytes,
            "traced": traced,
            "restore_s": restore_s,
        },
        "traced": reduced,
    }
