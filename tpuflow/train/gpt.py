"""GPT-family training recipes: FSDP (+ tensor/sequence/expert parallel)
and GPipe pipeline legs, as library functions.

The flows stay reference-sized shells (reference train_flow.py is a
~100-line wrapper over its library stack; its counterpart here just binds
CLI parameters to ``GptTrainConfig`` and records artifacts) — everything
a new model family or dataset would want to reuse lives in this module:
mesh/sharding setup, resume, the epoch loop with held-out validation,
checkpointing with retention/best, EMA, and post-train sampling.

Covers BASELINE.md config 5 ("GPT-2-medium FSDP → pjit fully-sharded
checkpoint, multi-host v5e-32") with the framework's idioms: parameters
and optimizer state born sharded over ('fsdp','data') (optionally
tensor-parallel over 'tensor', sequence-parallel over 'seq',
expert-parallel over 'expert'), per-epoch async sharded checkpoints, and
full-state resume.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import time
from typing import Any

from tpuflow.utils import knobs
from tpuflow.utils.preempt import (
    Preempted,
    emergency_save_advised,
    launch_attempt,
    preemption_requested,
)


def _resume_cursor(
    data_state: dict | None,
    step: int,
    steps_per_epoch: int,
    epochs: int,
    seed: int,
) -> tuple[int, int]:
    """(start_epoch, batches_to_skip) for a resume/rollback landing on
    ``step``.

    With a persisted loader cursor (ISSUE 5: checkpoint metadata
    ``data_state``) whose shuffle seed matches, the resume lands
    mid-epoch and replays exactly the epoch's unconsumed tail — no batch
    trained twice, none dropped. Without one (pre-cursor checkpoints, or
    a reseeded loader whose permutation no longer matches) fall back to
    the epoch head the step floors to, as before."""
    if data_state and int(data_state.get("seed", -1)) == int(seed):
        epoch = min(int(data_state.get("epoch", 0)), epochs)
        skip = max(int(data_state.get("batch_index", 0)), 0)
        if skip >= steps_per_epoch:
            # Drained exactly at the epoch boundary: next epoch, no skip.
            return min(epoch + 1, epochs), 0
        return epoch, skip
    return min(step // steps_per_epoch, epochs), 0


def _apply_remat_selector(model_cfg, selector: str):
    """Map a remat selector onto a GPT2Config (ISSUE 10).

    ``none``  — remat OFF: every activation saved, including the flash
                custom_vjp residuals (outputs + lse) — the backward runs
                ZERO recompute. The fastest step when HBM admits it.
    ``dots``  — remat ON with the 'dots' policy: MXU dot outputs and the
                attention kernels' o and lse saved, cheap elementwise
                recomputed, no kernel re-run (the TPU-standard middle
                ground).
    ``full``  — remat ON, a block keeps its input and, where the Pallas
                attention kernels ran, their o and lse (2 x (B, T, C) a
                layer where the input alone was 1; nothing extra under any
                other attention): the recompute holds every Dense product
                and no kernel. The full-size default.
    Any other value: treated as a literal jax.checkpoint_policies name
    (validated by the caller), remat ON; ``nothing_saveable`` is the
    block's input alone, the kernel's forward recomputed too (minimum
    memory).
    """
    if selector == "none":
        return dataclasses.replace(
            model_cfg, remat=False, remat_policy=None
        )
    if selector == "full":
        return dataclasses.replace(
            model_cfg, remat=True, remat_policy=None
        )
    # 'dots' or an explicit checkpoint_policies name: a policy only
    # means anything under remat — asking for one turns remat on
    # (otherwise the knob is silently inert on presets that default
    # remat off, like 'test').
    return dataclasses.replace(
        model_cfg, remat=True, remat_policy=selector
    )


def active_remat_policy(model_cfg) -> str:
    """The resolved selector string for telemetry: 'none' when remat is
    off, 'full' for policy-less remat, else the policy name."""
    if not model_cfg.remat:
        return "none"
    return model_cfg.remat_policy or "full"


def remat_stamp(model_cfg) -> dict:
    """The ``train.remat_policy`` event's account of the leg's remat: the
    resolved selector and ``saves``, the named values a rematerialised
    block keeps beside its input (the attention kernels' residual under
    'full' and 'dots', empty otherwise)."""
    from tpuflow.models.gpt2 import remat_saves

    return {
        "policy": active_remat_policy(model_cfg),
        "saves": remat_saves(model_cfg),
    }


@dataclasses.dataclass
class GptTrainConfig:
    """Everything the GPT training recipes need; flows bind CLI parameters
    straight onto this (defaults match the flow defaults)."""

    preset: str = "test"            # test | gpt2 | medium
    epochs: int = 2
    steps_per_epoch: int = 16
    batch_size: int = 8             # global
    seq_len: int = 64
    learning_rate: float = 3e-4
    data_axis: int = 2
    fsdp_axis: int = 2
    tensor_axis: int = 1
    seq_axis: int = 1
    expert_axis: int = 1
    experts: int = 0                # Switch-MoE experts per block (0=dense)
    stage_axis: int = 1             # >1 = GPipe pipeline mode
    microbatches: int = 2
    attn_impl: str = "auto"         # auto | xla | flash | ring | ulysses
    dataset: str = "lm_synth"       # lm_synth | lm_text
    text_path: str | None = None    # pin the lm_text corpus file
    sample_tokens: int = 0
    accum_steps: int = 1
    optimizer_name: str = "adamw"   # adamw | sgd | adafactor | lion
    lr_schedule: str = "constant"
    warmup_steps: int = 0
    grad_clip: float = 0.0
    weight_decay: float = 1e-4
    ema_decay: float = 0.0
    ckpt_dtype: str | None = None
    decay_steps: int = 0            # 0 = this run's epochs*steps
    # Selective-remat policy for the full-size presets (which remat by
    # default): '' = full remat, else a jax.checkpoint_policies name,
    # e.g. 'dots_with_no_batch_dims_saveable' (save MXU outputs,
    # recompute the cheap elementwise bulk).
    remat_policy: str = ""
    # Activation dtype: '' = f32, 'bfloat16' = the standard TPU
    # mixed-precision recipe (bf16 MXU operands, f32 master weights +
    # optimizer state + loss head — checkpoints are unchanged).
    dtype: str = ""

    def model_config(self):
        import jax.numpy as jnp

        from tpuflow.models.gpt2 import GPT2Config

        act_dtype = None
        if self.dtype:
            if self.dtype not in ("bfloat16", "float16", "float32"):
                raise ValueError(
                    f"unknown dtype {self.dtype!r}; supported: bfloat16, "
                    "float16, float32"
                )
            act_dtype = jnp.dtype(self.dtype)
        cfg = GPT2Config.from_preset(
            self.preset,
            attn_impl=self.attn_impl,
            seq_len=self.seq_len,
            stage_axis=self.stage_axis,
            n_experts=self.experts,
            dtype=act_dtype,
        )
        if self.remat_policy:
            import jax

            if self.remat_policy not in (
                "full", "dots", "none"
            ) and not hasattr(jax.checkpoint_policies, self.remat_policy):
                # Fail at config time, not at first jit trace inside an
                # already-provisioned training job.
                raise ValueError(
                    f"unknown remat_policy {self.remat_policy!r}; valid "
                    "names are full|dots|none or the "
                    "jax.checkpoint_policies attributes "
                    "(e.g. dots_with_no_batch_dims_saveable)"
                )
            cfg = _apply_remat_selector(cfg, self.remat_policy)
        # The env selector (ISSUE 10) beats the config: a provisioned
        # run flips its memory/recompute trade per launch without a
        # config edit — the MFU-push knob for remat-off training, where
        # the flash custom_vjp residuals (outputs + lse) are SAVED from
        # the forward instead of re-running every block's kernels.
        env_sel = knobs.raw("TPUFLOW_REMAT_POLICY", "").strip()
        if env_sel:
            if env_sel not in ("full", "dots", "none"):
                # Config-time failure, same contract as a bad
                # remat_policy — never a mid-provisioning trace crash.
                raise ValueError(
                    f"TPUFLOW_REMAT_POLICY={env_sel!r}; valid selectors "
                    "are full|dots|none"
                )
            cfg = _apply_remat_selector(cfg, env_sel)
        return cfg

    def optimizer(self):
        from tpuflow.train.optim import make_optimizer

        total = self.epochs * self.steps_per_epoch
        return make_optimizer(
            self.learning_rate,
            optimizer=self.optimizer_name,
            weight_decay=self.weight_decay,
            grad_clip_norm=self.grad_clip or None,
            warmup_steps=self.warmup_steps,
            decay_steps=self.decay_steps
            or max(total - self.warmup_steps, 1),
            schedule=self.lr_schedule,
        )

    def validate(self) -> None:
        """Reject incoherent knob combinations with actionable messages."""
        if self.stage_axis > 1:
            # Pipeline composes with data parallelism only.
            if (
                self.tensor_axis > 1
                or self.seq_axis > 1
                or self.expert_axis > 1
            ):
                raise ValueError(
                    "pipeline (stage_axis) composes with data_axis only"
                )
            if self.accum_steps > 1:
                raise ValueError(
                    "accum_steps applies to the FSDP/DP step only; the "
                    "pipeline schedule already microbatches via "
                    "microbatches"
                )
            if self.ema_decay > 0.0:
                raise ValueError(
                    "ema_decay is not supported in pipeline mode "
                    "(stage_axis > 1); the pipeline step tracks no EMA"
                )
        if self.experts and self.experts % self.expert_axis:
            raise ValueError(
                f"experts {self.experts} must be divisible by "
                f"expert_axis {self.expert_axis}"
            )


@dataclasses.dataclass
class GptTrainResult:
    checkpoint: Any                  # CheckpointHandle of the final save
    loss_history: list[float]
    metrics_history: list[dict]
    sample: list[int] | None = None  # greedy tokens when sample_tokens > 0


def _loaders(cfg: GptTrainConfig, vocab: int):
    from tpuflow.data.lm import make_lm_loaders

    return make_lm_loaders(
        cfg.batch_size, cfg.steps_per_epoch, cfg.seq_len, vocab,
        dataset=cfg.dataset, text_path=cfg.text_path,
    )


def train_gpt(
    cfg: GptTrainConfig, ckpt_dir: str, resume_checkpoint=None,
    log=print,
) -> GptTrainResult:
    """Run the configured GPT training leg end to end.

    ``resume_checkpoint``: a CheckpointHandle to restore FULL state from
    (step, params, opt_state, and — when ``ema_decay`` matches — the
    averaged weights). Callers that know the handle early should
    ``tpuflow.ckpt.prewarm_restore_handle`` it before calling, so the
    restore's page backing overlaps the setup work here.
    """
    cfg.validate()
    # Retried, requeued and resumed attempts load the compiled step from
    # the persistent cache instead of compiling it again.
    from tpuflow import dist as _dist

    _dist.maybe_enable_compile_cache()
    # For library callers that reach here with no backend up yet; the
    # flow CLI and gang members staged these before their first device
    # touch, and a late call logs that the flags were not applied.
    _dist.maybe_enable_async_collectives()
    # Live metrics endpoint (ISSUE 6, opt-in TPUFLOW_OBS_HTTP_PORT): gang
    # member 0 — or an in-process run, which is its own member 0 — serves
    # /metrics + /status for the duration of the leg. Idempotent; one
    # env lookup when the knob is off.
    from tpuflow.obs import export as _obs_export

    _obs_export.maybe_start_from_env()
    if cfg.stage_axis > 1:
        if cfg.fsdp_axis > 1:
            log(
                "[gpt] note: fsdp_axis does not apply in pipeline mode; "
                "params shard by layer slice over 'stage' instead"
            )
        return _train_pipeline(cfg, ckpt_dir, resume_checkpoint, log)
    return _train_fsdp(cfg, ckpt_dir, resume_checkpoint, log)


def _train_fsdp(
    cfg: GptTrainConfig, ckpt_dir: str, resume_checkpoint, log
) -> GptTrainResult:
    """FSDP leg, elastic-aware (ISSUE 7): each pass of this loop is one
    mesh GENERATION. A ``MeshReform`` unwinding the generation body (a
    pending plan seen at a step fence, or a collective that died with a
    member) has already handed state to the checkpoint; re-rendezvous and
    re-enter — the in-run resume machinery restores the state (resharded,
    bit-identical), the histories, and the mid-epoch data cursor exactly
    as it would for a requeued attempt, minus the process restart."""
    from tpuflow.dist import membership as _membership

    while True:
        try:
            return _run_fsdp_generation(
                cfg, ckpt_dir, resume_checkpoint, log
            )
        except _membership.MeshReform as rf:
            log(
                f"[gpt] mesh re-form → generation {rf.plan.generation} "
                f"({rf.plan.reason}, {rf.plan.num_processes} members)"
            )
            _membership.quiesce_and_reform(rf.plan)
            # The next generation resumes from the manager's newest
            # committed step, never the original cross-run handle.
            resume_checkpoint = None


def _run_fsdp_generation(
    cfg: GptTrainConfig, ckpt_dir: str, resume_checkpoint, log
) -> GptTrainResult:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuflow import dist
    from tpuflow.ckpt import CheckpointManager
    from tpuflow.dist import membership as _membership
    from tpuflow.models.gpt2 import GPT2
    from tpuflow.parallel import create_sharded_state, gpt2_tensor_rules
    from tpuflow.train import (
        TrainState,
        make_eval_step,
        make_train_step,
        run_validation,
    )

    model_cfg = cfg.model_config()
    axes = {
        "data": cfg.data_axis,
        "fsdp": cfg.fsdp_axis,
        "tensor": cfg.tensor_axis,
        "seq": cfg.seq_axis,
        "expert": cfg.expert_axis,
    }
    generation = (
        _membership.current_generation() if _membership.enabled() else 0
    )
    if generation > 0:
        # Post-reform world: the data axis absorbs the resize (the
        # model-parallel axes are fixed by the architecture). A world the
        # fixed axes don't divide keeps the configured shape and fails
        # loudly in make_mesh — the supervisor's requeue floor should
        # have prevented it.
        ndev = len(jax.devices())
        fixed = (
            axes["fsdp"] * axes["tensor"] * axes["seq"] * axes["expert"]
        )
        if fixed > 0 and ndev % fixed == 0:
            axes["data"] = ndev // fixed
    mesh = dist.make_mesh(axes)
    log(
        f"[gpt] mesh {dict(mesh.shape)}, preset {cfg.preset}"
        + (f", generation {generation}" if generation else "")
    )
    model = GPT2(model_cfg)
    tx = cfg.optimizer()

    def init_fn(rng):
        params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
        return TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    with mesh:
        mgr = CheckpointManager(
            ckpt_dir, max_to_keep=2, save_dtype=cfg.ckpt_dtype or None
        )
        from tpuflow import _native

        # Which shard writer this run got: the native one is built from
        # source on first use and quietly gives way to NumPy when it can't.
        log(
            f"[gpt] checkpoint format {mgr.format}, shard writer "
            f"{'native' if _native.lib() is not None else 'numpy'}"
        )
        # In-run resume (retry / preemption requeue): a previous attempt of
        # THIS run left committed checkpoints in ckpt_dir — continue from
        # the newest instead of restarting at step 0 (the manager already
        # rebuilt the full metrics history from it at construction). An
        # explicit resume_checkpoint handle (cross-run --from-run) wins.
        resume_step = (
            mgr.latest_step() if resume_checkpoint is None else None
        )
        t_phase = time.monotonic()
        state, shardings = create_sharded_state(
            init_fn,
            mesh,
            jax.random.PRNGKey(0),
            fsdp=True,
            # The rules carry BOTH tensor and expert placements and
            # self-gate on axis sizes.
            tensor_rules=gpt2_tensor_rules
            if cfg.tensor_axis > 1 or cfg.expert_axis > 1
            else None,
            # On resume the state is built ABSTRACTLY (shape eval only):
            # materializing 355M random params + zeroed moments just to
            # overwrite every leaf with the restore doubled resume wall
            # time (measured at 355M on CPU devices: 103 s fresh, 206 s
            # resuming).
            materialize=resume_checkpoint is None and resume_step is None,
        )
        resuming = resume_checkpoint is not None or resume_step is not None
        log(f"[gpt] state {'template' if resuming else 'init'}:"
            f" {time.monotonic() - t_phase:.1f}s")
        if resuming:
            # state IS the abstract template here (materialize=False
            # returns sharding-annotated ShapeDtypeStructs).
            tmpl = {
                "step": state.step,
                "params": state.params,
                "opt_state": state.opt_state,
            }
            if cfg.ema_decay > 0.0:
                # EMA runs save/restore the averaged weights too; the
                # resume run must pass the same ema_decay (the checkpoint's
                # leaf structure includes them).
                tmpl["ema_params"] = state.params
            t_phase = time.monotonic()
            if resume_checkpoint is not None:
                from tpuflow.ckpt import restore_from_handle

                restored = restore_from_handle(
                    resume_checkpoint, abstract_state=tmpl
                )
            else:
                # crc-verified; falls back to the previous committed step
                # (with a ckpt.corrupt event) if the newest is damaged.
                restored = mgr.restore(resume_step, abstract_state=tmpl)
            jax.block_until_ready(restored)
            # Direct construction — no init ran, there is no state to
            # .replace() over. batch_stats: GPT has none.
            state = TrainState(
                step=restored["step"],
                apply_fn=model.apply,
                params=restored["params"],
                tx=tx,
                opt_state=restored["opt_state"],
                batch_stats={},
                # Present exactly when the template asked for it (the raw
                # restore errors on any structure mismatch).
                ema_params=restored.get("ema_params", {}),
            )
            log(f"[gpt] full sharded state restored"
                f"{' (in-run resume)' if resume_step is not None else ''}:"
                f" {time.monotonic() - t_phase:.1f}s")

        loader, val_loader = _loaders(cfg, model_cfg.vocab_size)
        seq_spec = "seq" if cfg.seq_axis > 1 else None
        batch_sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(("data", "fsdp"), seq_spec)
        )
        if cfg.ema_decay > 0.0 and not state.ema_params:
            # Seed EMA only on fresh starts — a resume above already
            # restored the averaged weights.
            from tpuflow.train import with_ema

            state = with_ema(state)
        # Comm/compute overlap (ISSUE 10): hand the accumulation scan
        # the param shardings so each microbatch's gradients reduce-
        # scatter inside the scan body (hidden behind the next
        # microbatch's backward) instead of one exposed reduction after
        # it. Loss-bit-identical to the sequential scan (pinned by
        # tests/test_train_step.py); TPUFLOW_COMM_OVERLAP=0 recovers the
        # old program.
        from tpuflow.train.step import comm_overlap_enabled

        overlap = comm_overlap_enabled() and cfg.accum_steps > 1
        train_step = make_train_step(
            accum_steps=cfg.accum_steps,
            ema_decay=cfg.ema_decay or None,
            grad_shardings=shardings.params if overlap else None,
            comm_overlap=overlap,
        )
        eval_step = make_eval_step()
        rng = jax.random.PRNGKey(1)
        history = []
        epoch_records = []
        # In-run resume: seed the returned histories from the manager's
        # rebuilt metrics history, so the result is continuous across the
        # retry (no gap, no step-0 restart). Drain-only checkpoints (a
        # preemption's final save carries no metrics) are skipped.
        if resume_step is not None:
            for m in mgr._metrics_history:
                if "train_loss" not in m:
                    continue
                history.append(m["train_loss"])
                epoch_records.append(
                    {
                        "epoch": len(epoch_records),
                        "train_loss": m.get("train_loss"),
                        "val_loss": m.get("val_loss"),
                        "ppl": m.get("ppl"),
                        "tokens_per_s": None,
                    }
                )
        start_epoch = 0
        resume_skip = 0
        if resume_step is not None:
            start_epoch, resume_skip = _resume_cursor(
                (mgr._read_meta(resume_step) or {}).get("data_state"),
                int(state.step), cfg.steps_per_epoch, cfg.epochs,
                loader.seed,
            )
            log(
                f"[gpt] in-run resume from step {int(state.step)} "
                f"→ epoch {start_epoch}"
                + (
                    f" (replaying from batch {resume_skip})"
                    if resume_skip
                    else ""
                )
            )
        opt_step = int(state.step)
        # Telemetry (tpuflow.obs): per-step wall times + tokens ride the
        # fences the loop already pays; batch-wait rides the prefetch
        # iterator. All no-ops when obs is disabled.
        from tpuflow import obs
        from tpuflow.data.loader import prefetch_to_device
        from tpuflow.obs import goodput as goodput_mod
        from tpuflow.obs import health as health_mod
        from tpuflow.train.step import (
            DispatchWindow,
            StepClock,
            dispatch_depth,
        )

        # Training-health observatory (ISSUE 3): the monitor judges each
        # fenced step's numerics (None when TPUFLOW_HEALTH=0 — one
        # ``is not None`` check per step), the profile window wraps the
        # TPUFLOW_PROFILE step range in a jax.profiler trace.
        monitor = health_mod.HealthMonitor.from_env()
        profile = health_mod.ProfileWindow.from_env()
        lr_scale = 1.0
        fault_env = bool(knobs.raw("TPUFLOW_FAULT"))
        elastic = _membership.enabled()

        # Dispatch-ahead (ISSUE 4): up to `depth` steps run in flight;
        # the oldest step's scalars are settled (the float() host copies
        # below, which ARE the fence) only when the window fills, at
        # epoch end, and at every preemption/profile drain point — so
        # health rollback and requeue still land on a committed step
        # boundary, just observed up to depth-1 steps late.
        window = DispatchWindow(dispatch_depth())
        obs.gauge("train.dispatch_depth", float(window.depth))
        # Remat-selector + overlap provenance (ISSUE 10): one event per
        # leg so a run's memory/recompute trade and comm scheduling are
        # auditable from the stream alone.
        obs.event(
            "train.remat_policy",
            **remat_stamp(model_cfg),
            comm_overlap=bool(overlap),
            accum_steps=cfg.accum_steps,
        )
        # FSDP world for the comm roofline: the axes grads actually
        # reduce over (same rule as parallel.make_shardings).
        fsdp_world = 1
        for _ax in ("fsdp", "data"):
            if mesh.shape.get(_ax, 1) > 1:
                fsdp_world *= int(mesh.shape[_ax])

        def settle(entry) -> None:
            """Fence one matured step and run its host-side accounting
            (telemetry, health monitor). Raises _RollbackSignal when the
            monitor flags the step. ``timed`` is False for the cold
            (compile) step, which records train.compile instead of a
            train.step_s observation."""
            step_no, metrics, tokens, timed = entry
            if monitor is not None or clock.recording:
                # 4-byte host copies; the first one blocks until the
                # step's program finished (the fence).
                nf = bool(float(metrics["nonfinite"]))
                m_loss = float(metrics["loss"])
                m_gn = float(metrics["grad_norm"])
                if clock.recording:
                    if timed:
                        clock.step_done(tokens=tokens, step=step_no)
                    clock.health_done(
                        loss=m_loss,
                        grad_norm=m_gn,
                        update_norm=float(metrics["update_norm"]),
                        param_norm=float(metrics["param_norm"]),
                        nonfinite=nf,
                    )
                if monitor is not None:
                    anomaly = monitor.observe(
                        step_no, m_loss, m_gn, nonfinite=nf
                    )
                    if anomaly is not None:
                        target = health_mod.handle_anomaly(
                            monitor, anomaly, mgr
                        )
                        raise health_mod._RollbackSignal(target, anomaly)
            else:
                # No consumer for the scalars: still fence, so the
                # window bounds the in-flight dispatch queue.
                jax.block_until_ready(metrics["loss"])
                if timed:
                    clock.step_done(tokens=tokens, step=step_no)

        def drain_window() -> None:
            for entry in window.drain():
                settle(entry)

        def drain_preempt() -> None:
            # SIGTERM landed (or was injected): commit a final checkpoint
            # at the current step and hand back Preempted — gang_exec
            # converts it into the requeue exit code, and the supervisor
            # reruns the step without consuming the retry budget. The
            # window drains first so the saved step is a settled one.
            drain_window()
            payload = {
                "step": state.step,
                "params": state.params,
                "opt_state": state.opt_state,
            }
            if cfg.ema_decay > 0.0:
                payload["ema_params"] = state.ema_params
            data_state = loader.state_dict(cursor["batch"])
            if mgr.latest_step() != opt_step:
                if emergency_save_advised():
                    # Closing grace window (ISSUE 5): synchronous commit
                    # on the fastest tier, upload skipped — the requeued
                    # attempt resumes from THIS step, not the last
                    # periodic save.
                    mgr.emergency_save(
                        opt_step, payload, data_state=data_state
                    )
                else:
                    mgr.save(
                        opt_step, payload, metrics={},
                        data_state=data_state,
                    )
                    mgr.wait_until_finished()
            mgr.close()
            raise Preempted(f"drained checkpoint at step {opt_step}")

        def drain_reform(plan) -> None:
            # Mesh re-form fence (ISSUE 7): hand state to the checkpoint
            # and unwind to the generation loop. At a grow fence every
            # member is alive, so the CURRENT step commits (the
            # emergency-checkpoint-if-none-fresh clause); after a loss
            # the survivors cannot assemble a full sharded checkpoint —
            # the stranded save is abandoned and resume replays from the
            # last FULLY committed step.
            if plan.reason == "grow":
                drain_window()
                payload = {
                    "step": state.step,
                    "params": state.params,
                    "opt_state": state.opt_state,
                }
                if cfg.ema_decay > 0.0:
                    payload["ema_params"] = state.ema_params
                if mgr.latest_step() != opt_step:
                    mgr.save(
                        opt_step, payload, metrics={},
                        data_state=loader.state_dict(cursor["batch"]),
                    )
                mgr.wait_until_finished()
            else:
                window.clear()
                mgr.abandon_pending()
            mgr.close()
            raise _membership.MeshReform(plan)

        def place_batch(b):
            # Runs on the prefetch thread: host→device placement onto
            # the step's exact batch sharding overlaps device compute.
            return {
                "x": jax.device_put(b["x"], batch_sharding),
                "y": jax.device_put(b["y"], batch_sharding),
            }

        clock = StepClock()
        # Rolling-MFU feed for the live export endpoint: the dense-
        # transformer 6·N FLOP/token estimate (set AFTER the clock reset
        # the ledger). state.params is materialized by now on both the
        # fresh and the restored path.
        n_params = sum(
            int(l.size) for l in jax.tree_util.tree_leaves(state.params)
        )
        goodput_mod.live().set_model_flops_per_token(6.0 * n_params)
        cold = True
        # Loader cursor for deterministic mid-epoch resume: epoch + batches
        # consumed, persisted as checkpoint data_state and replayed by
        # skip_batches on the restoring side.
        pending_skip = resume_skip
        cursor = {"batch": 0}
        while True:
            try:
                for epoch in range(start_epoch, cfg.epochs):
                    t_epoch = time.monotonic()
                    ts_epoch = time.time()
                    loader.set_epoch(epoch)
                    cursor["batch"] = pending_skip
                    if pending_skip:
                        loader.skip_batches(pending_skip)
                        pending_skip = 0
                    losses = []
                    n_tokens = 0
                    clock.reset()
                    for batch in prefetch_to_device(
                        loader, mesh, keys=("x", "y"), place=place_batch
                    ):
                        if fault_env:
                            from tpuflow.testing import faults

                            poison = faults.grad_poison(opt_step + 1)
                            if poison is not None:
                                state = state.replace(
                                    params=jax.tree_util.tree_map(
                                        lambda p: p * poison, state.params
                                    )
                                )
                        if profile is not None:
                            profile.maybe_start(opt_step + 1)
                        t_dispatch = time.monotonic()
                        state, metrics = train_step(state, batch, rng)
                        losses.append(metrics["loss"])
                        tokens = int(np.prod(batch["y"].shape))
                        if cold:
                            # Fence out jit compilation so throughput
                            # numbers are comparable across epochs; the
                            # first batch's tokens are excluded from the
                            # rate accordingly. The cold step settles
                            # inline (never enters the window).
                            jax.block_until_ready(metrics["loss"])
                            log(
                                "[gpt] first step (trace, compile or cache "
                                "load, run) in "
                                f"{time.monotonic() - t_dispatch:.1f}s"
                            )
                            t_epoch = time.monotonic()
                            ts_epoch = time.time()
                            compile_s = clock.compile_done(
                                preset=cfg.preset
                            )
                            if compile_s is not None:
                                # Device ledger compile-fence entry
                                # (ISSUE 15): re-lowering is trace-only
                                # (no XLA compile) — cost analysis +
                                # compile wall for the step program.
                                from tpuflow.obs import device as _devmod

                                _devmod.note_jit_program(
                                    "train.step",
                                    train_step,
                                    (state, batch, rng),
                                    compile_s=compile_s,
                                )
                            cold = False
                            opt_step += 1
                            settle((opt_step, metrics, 0, False))
                        else:
                            # No-op on accelerators; blocking on the
                            # serialized host-CPU platform (at most one
                            # collective program in flight there — see
                            # dist.serialize_steps).
                            dist.step_fence(metrics["loss"])
                            n_tokens += tokens
                            opt_step += 1
                            for entry in window.push(
                                (opt_step, metrics, tokens, True)
                            ):
                                settle(entry)
                        cursor["batch"] += 1
                        if profile is not None:
                            # Keep execution inside the trace window:
                            # effectively dispatch depth 1 while the
                            # profiler is live (rare, bounded by the
                            # TPUFLOW_PROFILE step range).
                            drain_window()
                            profile.maybe_stop(opt_step)
                        if fault_env:
                            from tpuflow.testing import faults

                            faults.step_boundary(opt_step)
                        if preemption_requested():
                            drain_preempt()
                        if elastic:
                            plan = _membership.pending_reform()
                            if plan is not None:
                                drain_reform(plan)
                    # Settle the tail of the window BEFORE any epoch
                    # accounting: a flagged in-flight step must roll the
                    # epoch back, never reach the history or the save.
                    drain_window()
                    jax.block_until_ready(state.params)
                    epoch_s = time.monotonic() - t_epoch
                    tok_s = (
                        n_tokens / max(epoch_s, 1e-9) if n_tokens else None
                    )
                    epoch_loss = float(jnp.stack(losses).mean())
                    history.append(epoch_loss)
                    rec = obs.recorder()
                    if rec is not None:
                        rec.record(
                            "span", "train.epoch", ts=ts_epoch, dur_s=epoch_s,
                            epoch=epoch, loss=epoch_loss,
                            tokens_per_s=round(tok_s, 1) if tok_s else None,
                        )
                    clock.goodput_mark()
                    if n_tokens:
                        # Comm attribution at the epoch fence (ISSUE 10):
                        # mean step wall vs the compute/comm rooflines →
                        # train.exposed_comm_s / train.comm_overlap_s.
                        # No-op off-TPU (no invented attribution).
                        from tpuflow.train.step import (
                            comm_attribution,
                            emit_comm_gauges,
                        )

                        per_step_tokens = cfg.batch_size * cfg.seq_len
                        n_steps = max(n_tokens // per_step_tokens, 1)
                        emit_comm_gauges(
                            comm_attribution(
                                epoch_s / n_steps,
                                tokens=per_step_tokens,
                                n_params=n_params,
                                accum_steps=cfg.accum_steps,
                                fsdp_world=fsdp_world,
                                overlapped=bool(overlap),
                            )
                        )
                    # Held-out validation: token-level loss -> perplexity
                    # over EVERY test window (padded tail masked out). The
                    # best/retention policy keys on real val loss, matching
                    # the reference's save-best-on-val semantics
                    # (my_ray_module.py:190-201), not the train loss.
                    with obs.span("train.validation", epoch=epoch):
                        val_loss = run_validation(
                            state,
                            val_loader,
                            eval_step,
                            place=lambda x: jax.device_put(
                                x, batch_sharding
                            ),
                        )
                    ppl = math.exp(min(val_loss, 30.0))
                    epoch_records.append(
                        {
                            "epoch": epoch,
                            "train_loss": epoch_loss,
                            "val_loss": val_loss,
                            "ppl": ppl,
                            "tokens_per_s": round(tok_s, 1)
                            if tok_s
                            else None,
                        }
                    )
                    rate = f" ({tok_s:.0f} tok/s)" if tok_s else ""
                    log(
                        f"[gpt] epoch {epoch}: loss={epoch_loss:.4f} "
                        f"val_loss={val_loss:.4f} ppl={ppl:.2f}{rate}"
                    )
                    payload = {
                        "step": state.step,
                        "params": state.params,
                        "opt_state": state.opt_state,
                    }
                    if cfg.ema_decay > 0.0:
                        payload["ema_params"] = state.ema_params
                    mgr.save(
                        int(state.step),
                        payload,
                        metrics={
                            "val_loss": val_loss,
                            "train_loss": epoch_loss,
                            "ppl": ppl,
                        },
                        # Epoch boundary: the next attempt resumes at the
                        # next epoch's head.
                        data_state={
                            "epoch": epoch + 1,
                            "batch_index": 0,
                            "seed": loader.seed,
                        },
                    )
                    if launch_attempt() > 0 or generation > 0:
                        # Retried attempt or re-formed elastic generation:
                        # commit eagerly so this epoch is durable before
                        # the crashing step reruns (see
                        # utils.preempt.launch_attempt — deferred commits
                        # livelock deterministic crashes; a post-reform
                        # gang replays abandoned steps for the same
                        # reason).
                        mgr.wait_until_finished()
                break
            except health_mod.TrainingDiverged:
                # Halt path: drain in-flight saves so the failing process
                # leaves only committed checkpoints behind.
                mgr.wait_until_finished()
                raise
            except health_mod._RollbackSignal as rb:
                # Divergence auto-rollback: restore the last crc-verified
                # checkpoint (handle_anomaly picked it) and replay from
                # there — the reverse of the in-run resume path above.
                # In-flight steps past the flagged one are discarded
                # along with the state they produced.
                window.clear()
                from_step = opt_step
                if monitor.cfg.lr_backoff != 1.0:
                    # LR backoff rides a rebuilt optimizer; the schedule
                    # lives inside the compiled update, so the new tx
                    # recompiles the step — acceptable for an event that
                    # is rare by construction (max_rollbacks bounds it).
                    lr_scale *= monitor.cfg.lr_backoff
                    tx = dataclasses.replace(
                        cfg,
                        learning_rate=cfg.learning_rate * lr_scale,
                    ).optimizer()
                tmpl = {
                    "step": state.step,
                    "params": state.params,
                    "opt_state": state.opt_state,
                }
                if cfg.ema_decay > 0.0:
                    tmpl["ema_params"] = state.params
                restored = mgr.restore(rb.target, abstract_state=tmpl)
                jax.block_until_ready(restored)
                state = TrainState(
                    step=restored["step"],
                    apply_fn=model.apply,
                    params=restored["params"],
                    tx=tx,
                    opt_state=restored["opt_state"],
                    batch_stats={},
                    ema_params=restored.get("ema_params", {}),
                )
                opt_step = int(state.step)
                start_epoch, pending_skip = _resume_cursor(
                    (mgr._read_meta(rb.target) or {}).get("data_state"),
                    opt_step, cfg.steps_per_epoch, cfg.epochs, loader.seed,
                )
                # Rewind every history the replayed epochs will re-append
                # to — the save-per-epoch invariant keeps them in step.
                mgr.rewind_history(rb.target)
                history = history[:start_epoch]
                epoch_records = epoch_records[:start_epoch]
                obs.event(
                    "health.rollback",
                    step=rb.target, from_step=from_step,
                    detector=rb.anomaly.kind, lr_scale=lr_scale,
                    rollbacks=monitor.rollbacks,
                )
                log(
                    f"[gpt] health rollback: {rb.anomaly.describe()} → "
                    f"restored verified step {rb.target} "
                    f"(epoch {start_epoch}, lr_scale {lr_scale:g})"
                )
            except (_membership.MeshReform, Preempted):
                raise
            except Exception as e:
                # A collective died mid-epoch. In an elastic gang a dead
                # peer's sockets close instantly and this is the FIRST
                # place the survivor notices — classify against the
                # supervisor's re-form plan before giving up; a genuine
                # error (or a non-elastic gang) re-raises unchanged.
                if not elastic:
                    raise
                plan = _membership.reform_after_failure(e)
                if plan is None:
                    raise
                window.clear()
                mgr.abandon_pending()
                mgr.close()
                raise _membership.MeshReform(plan) from e
        if profile is not None:
            profile.close()
        mgr.wait_until_finished()
        result = GptTrainResult(
            checkpoint=mgr.checkpoint(),
            loss_history=history,
            metrics_history=epoch_records,
        )
        mgr.close()
        if cfg.sample_tokens > 0:
            result.sample = _sample_greedy(cfg, model, state.params, log)
    return result


def _sample_greedy(cfg, model, params, log) -> list[int]:
    """Demonstrate the LM inference surface on the trained model: greedy
    KV-cache decode (tpuflow.infer.generate), sharded params and all —
    GSPMD handles the gather under jit."""
    import jax.numpy as jnp

    from tpuflow.infer import generate, render_tokens

    # Byte-level corpora get a readable prompt ("The ") and a text
    # rendering of the sample; token corpora print ids.
    byte_level = cfg.dataset == "lm_text"
    prompt = (
        jnp.asarray([list(b"The ")], jnp.int32)
        if byte_level
        else jnp.zeros((1, 4), jnp.int32)
    )
    toks = generate(
        model, params, prompt,
        max_new_tokens=cfg.sample_tokens, temperature=0.0,
    )
    sample = [int(t) for t in toks[0]]
    log(
        "[gpt] greedy sample: "
        f"{render_tokens(sample, byte_level=byte_level)!r}"
    )
    return sample


def _train_pipeline(
    cfg: GptTrainConfig, ckpt_dir: str, resume_checkpoint, log
) -> GptTrainResult:
    """GPipe pipeline-parallel training over a ('data','stage') mesh:
    scan-stacked blocks shard by layer slice (tpuflow.parallel.pipeline),
    grads flow through the microbatch schedule, checkpoints carry the
    pipeline-sharded state (the raw format's shard-ownership rule covers
    any sharding, so resume works unchanged)."""
    import jax
    import jax.numpy as jnp
    import optax

    from tpuflow import dist
    from tpuflow.ckpt import CheckpointManager, restore_from_handle
    from tpuflow.models.gpt2 import GPT2
    from tpuflow.parallel import gpt2_pipeline_loss, gpt2_pipeline_shardings

    model_cfg = cfg.model_config()
    mesh = dist.make_mesh({"data": cfg.data_axis, "stage": cfg.stage_axis})
    log(
        f"[gpt] pipeline mesh {dict(mesh.shape)}, "
        f"microbatches={cfg.microbatches}"
    )
    model = GPT2(model_cfg)
    tx = cfg.optimizer()
    loss_fn = gpt2_pipeline_loss(
        model_cfg, mesh=mesh, n_microbatches=cfg.microbatches
    )

    def init_params(rng):
        return model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]

    with mesh:
        p_shapes = jax.eval_shape(init_params, jax.random.PRNGKey(0))
        shardings = gpt2_pipeline_shardings(mesh, p_shapes)
        # Optimizer state mirrors the params tree (mu/nu under the same
        # 'h' paths → 'stage'-sharded; counts are scalars → replicated),
        # so the same path rule shards it.
        opt_shape = jax.eval_shape(tx.init, p_shapes)
        opt_shardings = gpt2_pipeline_shardings(mesh, opt_shape)
        start_step = 0

        mgr = CheckpointManager(
            ckpt_dir, max_to_keep=2, save_dtype=cfg.ckpt_dtype or None
        )
        # In-run resume after a retry/requeue: continue from this run's
        # newest committed step (cross-run handles still win).
        resume_step = (
            mgr.latest_step() if resume_checkpoint is None else None
        )
        # One abstract template serves resume AND divergence rollback —
        # both restore the same pipeline-sharded {step, params, opt_state}.
        abstract = {
            "step": jax.ShapeDtypeStruct((), jnp.int32),
            "params": jax.tree_util.tree_map(
                lambda s, sh: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=sh
                ),
                p_shapes,
                shardings,
            ),
            "opt_state": jax.tree_util.tree_map(
                lambda s, sh: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=sh
                ),
                opt_shape,
                opt_shardings,
            ),
        }
        if resume_checkpoint is None and resume_step is None:
            # Params born sharded: init is jitted with the pipeline
            # shardings as out_shardings, so no host ever materializes
            # the full replicated tree. Resumes skip this entirely — the
            # restore produces every leaf (materializing random weights
            # just to overwrite them doubled resume wall time).
            # Donation audit (ISSUE 4): these one-shot resharding jits
            # (and the eager-restore device_puts below) deliberately do
            # NOT donate their inputs — the restore fallback path may
            # re-read `restored` after a corrupt-shard retry, and a
            # donated-then-freed source would alias whatever the next
            # dispatch-ahead step wrote into that buffer.
            params = jax.jit(init_params, out_shardings=shardings)(
                jax.random.PRNGKey(0)
            )
            opt_state = jax.jit(tx.init, out_shardings=opt_shardings)(params)
        else:
            if resume_checkpoint is not None:
                restored = restore_from_handle(
                    resume_checkpoint, abstract_state=abstract
                )
            else:
                restored = mgr.restore(resume_step, abstract_state=abstract)
            # Normalize placement: scalar/replicated leaves may come back
            # single-device; device_put onto the target shardings is
            # idempotent for already-placed shards.
            params = jax.device_put(restored["params"], shardings)
            opt_state = jax.device_put(restored["opt_state"], opt_shardings)
            start_step = int(restored["step"])
            log(
                "[gpt] pipeline-sharded state restored"
                + (" (in-run resume)" if resume_step is not None else "")
            )
        mgr.prewarm({"params": params, "opt_state": opt_state})

        # Donated params/opt_state: old and new state never coexist in HBM
        # (matches make_train_step's donate pattern; safe because mgr.save
        # snapshots device buffers synchronously before its async writer
        # starts, and the loop rebinds both every step). Dispatch-ahead
        # audit (ISSUE 4): with N steps in flight the only live
        # references are the loop's current params/opt_state bindings
        # (each step's donated inputs were the PREVIOUS step's outputs,
        # rebound before the next dispatch) and the window's loss/hstats
        # entries — fresh, never-donated output buffers. Nothing else
        # may retain the donated trees between dispatches. A factory so
        # the divergence LR backoff can rebuild the step around a
        # rescaled tx.
        def make_pp_step(tx):
            @functools.partial(jax.jit, donate_argnums=(0, 1))
            def pp_step(params, opt_state, x, y):
                from tpuflow.train.optim import health_stats

                loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
                updates, opt_state = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                return (
                    new_params,
                    opt_state,
                    loss,
                    health_stats(loss, grads, updates, new_params),
                )

            return pp_step

        pp_step = make_pp_step(tx)

        loader, _ = _loaders(cfg, model_cfg.vocab_size)
        data_sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("data")
        )
        history = []
        if resume_step is not None:
            # Seed continuity across the retry: each committed epoch's
            # train loss was recorded as its save metric.
            history += [
                m["val_loss"]
                for m in mgr._metrics_history
                if "val_loss" in m
            ]
        global_step = start_step
        start_epoch = 0
        resume_skip = 0
        if resume_step is not None:
            start_epoch, resume_skip = _resume_cursor(
                (mgr._read_meta(resume_step) or {}).get("data_state"),
                start_step, cfg.steps_per_epoch, cfg.epochs, loader.seed,
            )
            log(
                f"[gpt] pipeline in-run resume from step {start_step} "
                f"→ epoch {start_epoch}"
                + (
                    f" (replaying from batch {resume_skip})"
                    if resume_skip
                    else ""
                )
            )
        from tpuflow import obs
        from tpuflow.data.loader import prefetch_to_device
        from tpuflow.obs import goodput as goodput_mod
        from tpuflow.obs import health as health_mod
        from tpuflow.train.step import (
            DispatchWindow,
            StepClock,
            dispatch_depth,
        )

        monitor = health_mod.HealthMonitor.from_env()
        profile = health_mod.ProfileWindow.from_env()
        lr_scale = 1.0
        fault_env = bool(knobs.raw("TPUFLOW_FAULT"))
        from tpuflow.dist import membership as _membership

        elastic = _membership.enabled()
        clock = StepClock()
        # Rolling-MFU feed (see the FSDP leg): 6·N over the pipeline-
        # sharded params, set after the clock reset the live ledger.
        goodput_mod.live().set_model_flops_per_token(
            6.0
            * sum(int(l.size) for l in jax.tree_util.tree_leaves(params))
        )
        # Dispatch-ahead window, same contract as the FSDP leg: fences
        # (the float() copies in settle) trail dispatch by up to depth-1
        # steps; every drain point below settles to a step boundary.
        window = DispatchWindow(dispatch_depth())
        obs.gauge("train.dispatch_depth", float(window.depth))
        obs.event(
            "train.remat_policy",
            **remat_stamp(model_cfg),
            comm_overlap=False,  # the pipeline schedule microbatches itself
            accum_steps=1,
        )

        def settle(entry) -> None:
            step_no, loss, hstats, tokens, timed = entry
            if monitor is not None or clock.recording:
                nf = bool(float(hstats["nonfinite"]))
                m_loss = float(loss)
                m_gn = float(hstats["grad_norm"])
                if clock.recording:
                    if timed:
                        clock.step_done(tokens=tokens, step=step_no)
                    clock.health_done(
                        loss=m_loss,
                        grad_norm=m_gn,
                        update_norm=float(hstats["update_norm"]),
                        param_norm=float(hstats["param_norm"]),
                        nonfinite=nf,
                    )
                if monitor is not None:
                    anomaly = monitor.observe(
                        step_no, m_loss, m_gn, nonfinite=nf
                    )
                    if anomaly is not None:
                        target = health_mod.handle_anomaly(
                            monitor, anomaly, mgr
                        )
                        raise health_mod._RollbackSignal(target, anomaly)
            else:
                jax.block_until_ready(loss)
                if timed:
                    clock.step_done(tokens=tokens, step=step_no)

        def drain_window() -> None:
            for entry in window.drain():
                settle(entry)

        def drain_preempt() -> None:
            drain_window()
            payload = {
                "step": jnp.int32(global_step),
                "params": params,
                "opt_state": opt_state,
            }
            data_state = loader.state_dict(cursor["batch"])
            if mgr.latest_step() != global_step:
                if emergency_save_advised():
                    # Closing grace window: fastest-tier commit, upload
                    # skipped (see the FSDP leg's drain).
                    mgr.emergency_save(
                        global_step, payload, data_state=data_state
                    )
                else:
                    mgr.save(
                        global_step, payload, metrics={},
                        data_state=data_state,
                    )
                    mgr.wait_until_finished()
            mgr.close()
            raise Preempted(f"drained checkpoint at step {global_step}")

        def drain_reform_fallback(plan) -> None:
            # Pipeline state shards by LAYER slice over 'stage': a lost
            # member removes a pipeline STAGE, which no data-axis reshard
            # can absorb — elastic re-form degrades to the preemption
            # requeue here (the relaunched attempt re-forms at
            # generation 0 over whatever capacity remains). A grow fence
            # (everyone alive) drains and commits first; after a loss the
            # stranded save is abandoned (its commit collectives would
            # only raise again).
            if plan.reason == "grow":
                drain_preempt()  # commits + raises Preempted
            window.clear()
            mgr.abandon_pending()
            mgr.close()
            raise Preempted(
                f"mesh re-form (generation {plan.generation}) requeues "
                "the pipeline leg"
            )

        def place_batch(b):
            # Prefetch-thread placement onto the pipeline's 'data' axis.
            return {
                "x": jax.device_put(b["x"], data_sharding),
                "y": jax.device_put(b["y"], data_sharding),
            }

        first = True
        pending_skip = resume_skip
        cursor = {"batch": 0}
        while True:
            try:
                for epoch in range(start_epoch, cfg.epochs):
                    loader.set_epoch(epoch)
                    cursor["batch"] = pending_skip
                    if pending_skip:
                        loader.skip_batches(pending_skip)
                        pending_skip = 0
                    losses = []
                    clock.reset()
                    for batch in prefetch_to_device(
                        loader, mesh, keys=("x", "y"), place=place_batch
                    ):
                        if fault_env:
                            from tpuflow.testing import faults

                            poison = faults.grad_poison(global_step + 1)
                            if poison is not None:
                                params = jax.tree_util.tree_map(
                                    lambda p: p * poison, params
                                )
                        if profile is not None:
                            profile.maybe_start(global_step + 1)
                        params, opt_state, loss, hstats = pp_step(
                            params,
                            opt_state,
                            batch["x"],
                            batch["y"],
                        )
                        dist.step_fence(loss)
                        losses.append(loss)
                        tokens = int(batch["y"].size)
                        global_step += 1
                        if first:
                            jax.block_until_ready(loss)
                            compile_s = clock.compile_done(
                                mode="pipeline"
                            )
                            if compile_s is not None:
                                from tpuflow.obs import device as _devmod

                                _devmod.note_jit_program(
                                    "train.pp_step",
                                    pp_step,
                                    (params, opt_state, batch["x"],
                                     batch["y"]),
                                    compile_s=compile_s,
                                )
                            first = False
                            settle((global_step, loss, hstats, 0, False))
                        else:
                            for entry in window.push(
                                (global_step, loss, hstats, tokens, True)
                            ):
                                settle(entry)
                        cursor["batch"] += 1
                        if profile is not None:
                            drain_window()
                            profile.maybe_stop(global_step)
                        if fault_env:
                            from tpuflow.testing import faults

                            faults.step_boundary(global_step)
                        if preemption_requested():
                            drain_preempt()
                        if elastic:
                            plan = _membership.pending_reform()
                            if plan is not None:
                                drain_reform_fallback(plan)
                    drain_window()
                    jax.block_until_ready(params)
                    epoch_loss = float(jnp.stack(losses).mean())
                    history.append(epoch_loss)
                    clock.goodput_mark()
                    log(
                        f"[gpt] pipeline epoch {epoch}: "
                        f"loss={epoch_loss:.4f}"
                    )
                    mgr.save(
                        global_step,
                        {
                            "step": jnp.int32(global_step),
                            "params": params,
                            "opt_state": opt_state,
                        },
                        metrics={"val_loss": epoch_loss},
                        data_state={
                            "epoch": epoch + 1,
                            "batch_index": 0,
                            "seed": loader.seed,
                        },
                    )
                    if launch_attempt() > 0 or elastic:
                        # Retried attempt (or an elastic gang, where a
                        # re-form may strand a deferred commit): eager
                        # commit for monotonic progress (see
                        # utils.preempt.launch_attempt).
                        mgr.wait_until_finished()
                break
            except health_mod.TrainingDiverged:
                mgr.wait_until_finished()
                raise
            except health_mod._RollbackSignal as rb:
                window.clear()
                from_step = global_step
                if monitor.cfg.lr_backoff != 1.0:
                    lr_scale *= monitor.cfg.lr_backoff
                    tx = dataclasses.replace(
                        cfg,
                        learning_rate=cfg.learning_rate * lr_scale,
                    ).optimizer()
                    pp_step = make_pp_step(tx)
                restored = mgr.restore(rb.target, abstract_state=abstract)
                params = jax.device_put(restored["params"], shardings)
                opt_state = jax.device_put(
                    restored["opt_state"], opt_shardings
                )
                global_step = int(restored["step"])
                start_epoch, pending_skip = _resume_cursor(
                    (mgr._read_meta(rb.target) or {}).get("data_state"),
                    global_step, cfg.steps_per_epoch, cfg.epochs,
                    loader.seed,
                )
                mgr.rewind_history(rb.target)
                history = history[:start_epoch]
                obs.event(
                    "health.rollback",
                    step=rb.target, from_step=from_step,
                    detector=rb.anomaly.kind, lr_scale=lr_scale,
                    rollbacks=monitor.rollbacks,
                )
                log(
                    f"[gpt] pipeline health rollback: "
                    f"{rb.anomaly.describe()} → restored verified step "
                    f"{rb.target} (epoch {start_epoch})"
                )
        if profile is not None:
            profile.close()
        mgr.wait_until_finished()
        result = GptTrainResult(
            checkpoint=mgr.checkpoint(),
            loss_history=history,
            metrics_history=[
                {"epoch": i, "train_loss": l} for i, l in enumerate(history)
            ],
        )
        mgr.close()
    return result
