"""GPT-2 FSDP training flow — the fully-sharded acceptance config.

A reference-sized shell (cf. reference train_flow.py, a ~100-line wrapper
over its library stack): CLI parameters bind onto
``tpuflow.train.GptTrainConfig`` and the recipes in ``tpuflow.train.gpt``
do the work — FSDP (+ tensor/sequence/expert parallel) or GPipe pipeline
training, per-epoch async sharded checkpoints with retention/best, EMA,
full-state resume, held-out perplexity, post-train sampling.

Run:    python flows/gpt_flow.py run --preset test --steps-per-epoch 8
Medium: python flows/gpt_flow.py run --preset medium --data-axis 4 --fsdp-axis 8
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpuflow.flow import (  # noqa: E402
    FlowSpec,
    Parameter,
    Run,
    card,
    current,
    device_profile,
    retry,
    step,
    training_curve_card,
)


class TpuGptTrain(FlowSpec):
    """Train GPT-2 with FSDP (+ optional tensor/sequence/expert/pipeline
    parallelism) on LM data, checkpointing the fully-sharded state."""

    preset = Parameter("preset", default="test", help="test | gpt2 | medium")
    epochs = Parameter("epochs", default=2, help="epochs")
    steps_per_epoch = Parameter("steps_per_epoch", default=16, help="steps/epoch")
    batch_size = Parameter("batch_size", default=8, help="global batch size")
    seq_len = Parameter("seq_len", default=64, help="sequence length")
    learning_rate = Parameter("learning_rate", default=3e-4, help="adamw lr")
    data_axis = Parameter("data_axis", default=2, help="mesh 'data' size")
    fsdp_axis = Parameter("fsdp_axis", default=2, help="mesh 'fsdp' size")
    tensor_axis = Parameter("tensor_axis", default=1, help="mesh 'tensor' size")
    seq_axis = Parameter("seq_axis", default=1, help="mesh 'seq' size")
    expert_axis = Parameter(
        "expert_axis", default=1, help="mesh 'expert' size (expert parallel)"
    )
    experts = Parameter(
        "experts",
        default=0,
        help="Switch-MoE experts per block (0 = dense MLP); shard over "
        "--expert-axis",
    )
    stage_axis = Parameter(
        "stage_axis", default=1, help="mesh 'stage' size (GPipe pipeline)"
    )
    microbatches = Parameter(
        "microbatches", default=2, help="pipeline microbatches per step"
    )
    attn_impl = Parameter(
        "attn_impl",
        default="auto",
        help="auto|xla|flash|ring|ulysses (auto = flash on one TPU chip "
        "from 1,024 positions at shapes the kernels tile, else xla)",
    )
    dataset = Parameter(
        "dataset", default="lm_synth", help="lm_synth | lm_text (byte-level)"
    )
    from_run = Parameter(
        "from_run", default="", help="run pathspec to resume full state from"
    )
    sample_tokens = Parameter(
        "sample_tokens",
        default=0,
        help="greedy-decode N tokens after training (FSDP mode)",
    )
    accum_steps = Parameter(
        "accum_steps",
        default=1,
        help="gradient-accumulation microbatches per optimizer step",
    )
    optimizer = Parameter(
        "optimizer",
        default="adamw",
        help="adamw | sgd | adafactor (factored 2nd moments, O(rows+cols) "
        "state) | lion (single sign-momentum buffer)",
    )
    lr_schedule = Parameter(
        "lr_schedule", default="constant", help="constant | cosine | linear"
    )
    warmup_steps = Parameter(
        "warmup_steps", default=0, help="linear LR warmup steps"
    )
    grad_clip = Parameter(
        "grad_clip", default=0.0, help="global-norm gradient clip (0 = off)"
    )
    weight_decay = Parameter(
        "weight_decay", default=1e-4, help="adamw decoupled weight decay"
    )
    ema_decay = Parameter(
        "ema_decay",
        default=0.0,
        help="EMA decay for averaged weights (0 = off; e.g. 0.999)",
    )
    ckpt_dtype = Parameter(
        "ckpt_dtype",
        default="",
        help="reduced-precision checkpoints: bfloat16 | float16 (default "
        "bit-exact)",
    )
    decay_steps = Parameter(
        "decay_steps",
        default=0,
        help="LR decay horizon in steps (0 = this run's epochs*steps); set "
        "explicitly when extending a run via --from-run so the restored "
        "step counter lands mid-schedule, not past it",
    )
    remat_policy = Parameter(
        "remat_policy",
        default="",
        help="selective-remat policy (jax.checkpoint_policies name, e.g. "
        "dots_with_no_batch_dims_saveable); empty = full block remat on "
        "the full-size presets",
    )
    dtype = Parameter(
        "dtype",
        default="",
        help="activation dtype: bfloat16 (TPU mixed precision; params and "
        "optimizer stay f32) | float16 | float32 (default)",
    )

    def _train_config(self):
        from tpuflow.train import GptTrainConfig

        return GptTrainConfig(
            preset=self.preset,
            epochs=int(self.epochs),
            steps_per_epoch=int(self.steps_per_epoch),
            batch_size=int(self.batch_size),
            seq_len=int(self.seq_len),
            learning_rate=float(self.learning_rate),
            data_axis=int(self.data_axis),
            fsdp_axis=int(self.fsdp_axis),
            tensor_axis=int(self.tensor_axis),
            seq_axis=int(self.seq_axis),
            expert_axis=int(self.expert_axis),
            experts=int(self.experts),
            stage_axis=int(self.stage_axis),
            microbatches=int(self.microbatches),
            attn_impl=self.attn_impl,
            dataset=self.dataset,
            sample_tokens=int(self.sample_tokens),
            accum_steps=int(self.accum_steps),
            optimizer_name=self.optimizer,
            lr_schedule=self.lr_schedule,
            warmup_steps=int(self.warmup_steps),
            grad_clip=float(self.grad_clip),
            weight_decay=float(self.weight_decay),
            ema_decay=float(self.ema_decay),
            ckpt_dtype=self.ckpt_dtype or None,
            decay_steps=int(self.decay_steps),
            remat_policy=self.remat_policy,
            dtype=self.dtype,
        )

    @step
    def start(self):
        self.resume_checkpoint = None
        if self.from_run:
            self.resume_checkpoint = Run(self.from_run).data.result_checkpoint
            print(f"[gpt_flow] resuming from {self.resume_checkpoint.path}")
        self.next(self.train)

    @retry(times=3)
    @device_profile(interval=1)
    @step
    def train(self):
        from tpuflow.data.lm import lm_corpus_size, text_source_record
        from tpuflow.train import train_gpt

        cfg = self._train_config()
        cfg.validate()
        mc = cfg.model_config()
        # Artifacts a downstream eval flow needs to rebuild the model and
        # see the identical held-out split (cross-flow handoff: the
        # checkpoint handle alone carries neither the architecture nor the
        # corpus identity).
        self.model_config = {
            "vocab_size": mc.vocab_size,
            "n_ctx": mc.n_ctx,
            "n_embd": mc.n_embd,
            "n_layer": mc.n_layer,
            "n_head": mc.n_head,
            "scan_layers": mc.scan_layers,
            "n_experts": mc.n_experts,
        }
        self.dataset_used = cfg.dataset
        self.seq_len_used = cfg.seq_len
        # lm_synth's corpus (and so its test split) is sized from the run
        # parameters; an eval flow must mirror it to see the same split.
        self.synthetic_size_used = lm_corpus_size(
            cfg.batch_size, cfg.steps_per_epoch
        )
        if cfg.dataset == "lm_text":
            # Pin the corpus identity: path + content hash. The eval flow
            # loads THIS file and errors if its bytes changed — the
            # "held-out split" can never silently come from a different
            # corpus than training saw.
            self.text_source = text_source_record()
            cfg.text_path = self.text_source["path"]
        if self.resume_checkpoint is not None:
            # Back the restore's destination pages on a background thread
            # while the mesh/model/jit setup runs (ckpt.RestoreArena).
            from tpuflow.ckpt import prewarm_restore_handle

            prewarm_restore_handle(self.resume_checkpoint)
        result = train_gpt(
            cfg,
            ckpt_dir=os.path.join(current.tpu_storage_path, "checkpoints"),
            resume_checkpoint=self.resume_checkpoint,
        )
        self.result_checkpoint = result.checkpoint
        self.loss_history = result.loss_history
        self.metrics_history = result.metrics_history
        if result.sample is not None:
            self.sample = result.sample
        self.next(self.end)

    @card(type="blank")
    @step
    def end(self):
        training_curve_card(
            current.card, getattr(self, "metrics_history", None) or []
        )
        print(f"[gpt_flow] loss history: {self.loss_history}")


if __name__ == "__main__":
    TpuGptTrain.main()
