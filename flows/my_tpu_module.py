"""Parity workload module: FashionMNIST training + batch prediction on TPU.

TPU-native counterpart of the reference's ``my_ray_module.py`` — same
capabilities, SPMD architecture:

- ``train_fashion_mnist``       ↔ my_ray_module.py:216-251 (trainer driver)
- ``train_func_per_worker``     ↔ my_ray_module.py:115-213 (per-worker loop);
  runs once per host, devices are the workers, XLA emits the grad all-reduce
- ``set_weights_from_checkpoint`` ↔ my_ray_module.py:253-264 (weights-only
  warm start; optimizer state intentionally not restored — §3.2 parity; pass
  resume="full" for the corrected full-state resume)
- ``TpuPredictor``              ↔ my_ray_module.py:266-284 (stateful batch
  predictor)
- ``get_dataloaders``           ↔ my_ray_module.py:30-76 (re-exported from
  tpuflow.data with identical modes)
"""

from __future__ import annotations

import os
import sys
import time

import jax
import numpy as np
import optax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpuflow.utils import knobs  # noqa: E402

from tpuflow import dist  # noqa: E402
from tpuflow.ckpt import Checkpoint, restore_from_handle  # noqa: E402
from tpuflow.data import (  # noqa: E402
    get_dataloaders,
    get_labels_map,
    prefetch_to_device,
)
from tpuflow.infer import BatchPredictor, map_batches  # noqa: E402
from tpuflow.models import NeuralNetwork, get_model  # noqa: E402
from tpuflow.train import (  # noqa: E402
    CheckpointConfig,
    DispatchWindow,
    Result,
    RunConfig,
    ScalingConfig,
    Trainer,
    create_train_state,
    dispatch_depth,
    get_context,
    make_eval_step,
    make_train_step,
    per_worker_batch_size,
)

_TAG = "[my_tpu_module]"


def _log(msg: str) -> None:
    print(f"{_TAG} {msg}")  # parity: tagged prints, my_ray_module.py:126,208


def _state_tree(state) -> dict:
    """Checkpoint payload (↔ the torch.save dict, my_ray_module.py:183-186;
    metrics history rides in checkpoint metadata instead of the payload)."""
    tree = {
        "step": state.step,
        "params": state.params,
        "opt_state": state.opt_state,
    }
    if state.batch_stats:
        tree["batch_stats"] = state.batch_stats
    return tree


def set_weights_from_checkpoint(state, checkpoint: Checkpoint):
    """Warm-start ONLY the model weights from a checkpoint handle
    (↔ my_ray_module.py:253-264; no ``module.`` prefix strip is needed —
    params are a pytree, the prefix was a DDP-wrapper artifact)."""
    params = restore_from_handle(checkpoint, weights_only=True)
    return state.replace(params=params)


def build_model(name: str = "mlp", *, dataset: str = "fashion_mnist",
                num_classes: int | None = None, **model_kwargs):
    """Public model rebuild for consumers outside the worker loop (the
    eval flow reconstructs the producing run's model from its artifacts).
    Same pluggable zoo as training (↔ acceptance configs, BASELINE.md)."""
    return _build_model(
        {
            "model": name,
            "dataset": dataset,
            "num_classes": num_classes,
            "model_kwargs": model_kwargs or None,
        }
    )


def _build_model(config: dict):
    """Models are pluggable behind the same trainer API (the acceptance
    configs name ResNet-18/50 beyond the reference's MLP, BASELINE.md)."""
    name = config.get("model", "mlp")
    kwargs = dict(config.get("model_kwargs") or {})
    # None = size the head from the dataset registry (the worker resolves
    # it off the loader before building the model).
    kwargs.setdefault("num_classes", config.get("num_classes") or 10)
    if name in ("resnet18", "resnet50"):
        # CIFAR-sized inputs use the 3x3 stem unless told otherwise.
        kwargs.setdefault("small_inputs", config.get("dataset") != "imagenet_synth")
    return get_model(name, **kwargs)


def train_func_per_worker(config: dict) -> None:
    """Per-host training loop (↔ train_func_per_worker,
    my_ray_module.py:115-213)."""
    ctx = get_context()
    lr = config.get("lr", 1e-3)
    epochs = config.get("epochs", 3)
    batch_size = config.get("batch_size_per_worker", 8)
    dataset = config.get("dataset", "fashion_mnist")
    data_dir = config.get("data_dir")

    world = ctx.get_world_size()
    rank = ctx.get_world_rank()
    nproc = jax.process_count()
    # Per-process slice of the data; within a process, shard_batch spreads
    # the batch over the local devices of the 'data' mesh axis
    # (↔ prepare_data_loader rank-sharding, my_ray_module.py:128-129).
    train_loader, val_loader = get_dataloaders(
        batch_size * world // nproc,
        dataset=dataset,
        data_dir=data_dir,
        seed=config.get("seed", 0),
        shard_index=jax.process_index(),
        num_shards=nproc,
    )
    _log(
        f"dataloaders ready (world={world}, rank={rank}, "
        f"mesh={dict(ctx.mesh.shape)})"
    )

    # Resolve any resume source FIRST and start backing its restore
    # destination pages in the background (ckpt.RestoreArena): the model
    # build / state init below overlaps the page-backing instead of the
    # restore paying it serially.
    mgr = ctx.checkpoint_manager
    in_run_step = mgr.latest_step() if mgr is not None else None
    if in_run_step is not None:
        mgr.prewarm_restore(in_run_step)
    elif config.get("checkpoint") is not None:
        from tpuflow.ckpt import prewarm_restore_handle

        _ckpt = config["checkpoint"]
        prewarm_restore_handle(
            Checkpoint.from_json(_ckpt) if isinstance(_ckpt, dict) else _ckpt,
            # Default warm starts read only the params subtree — prewarming
            # opt-state buffers no restore will take would leak them until
            # the (reclaiming) restore drops them unused.
            weights_only=config.get("resume") != "full",
        )

    if not config.get("num_classes"):
        # Size the head from the dataset registry (carried on the loader)
        # instead of a per-call-site dataset-name table.
        config = {
            **config,
            "num_classes": getattr(train_loader, "num_classes", 10),
        }
    model = _build_model(config)
    tx = optax.sgd(lr, momentum=0.9)  # parity: my_ray_module.py:142
    sample = np.zeros(
        (1, *train_loader.split.images.shape[1:]), np.float32
    )
    state = create_train_state(
        model, jax.random.PRNGKey(config.get("seed", 0)), sample, tx
    )
    start_epoch = 0
    if in_run_step is not None:
        # In-run fault tolerance (SURVEY.md §5): a retried gang step resumes
        # FULL state from its own run's newest retained checkpoint before
        # considering cross-run warm starts — the reference's @retry
        # (train_flow.py:41) only gives a blind from-scratch rerun; with
        # per-epoch retention this loses at most one epoch.
        restored = mgr.restore(in_run_step, abstract_state=_state_tree(state))
        state = state.replace(
            step=restored["step"],
            params=restored["params"],
            opt_state=restored["opt_state"],
            batch_stats=restored.get("batch_stats", state.batch_stats),
        )
        start_epoch = int(in_run_step)
        _log(f"in-run resume: restored retained step {in_run_step} after retry")
    elif config.get("checkpoint") is not None:
        ckpt = config["checkpoint"]
        if isinstance(ckpt, dict):
            ckpt = Checkpoint.from_json(ckpt)
        if config.get("resume") == "full":
            # Corrected behavior: restore params + opt state + step.
            restored = restore_from_handle(ckpt, abstract_state=_state_tree(state))
            state = state.replace(
                step=restored["step"],
                params=restored["params"],
                opt_state=restored["opt_state"],
                batch_stats=restored.get("batch_stats", state.batch_stats),
            )
            _log("full state restored from checkpoint (params+opt+step)")
        else:
            state = set_weights_from_checkpoint(state, ckpt)
            _log("model weights warm-started from checkpoint")

    # Replicate model+opt state over the mesh (↔ DDP replicate/broadcast,
    # my_ray_module.py:135); normalizes device placement after any restore.
    state = state.replace(
        step=dist.replicate(state.step, ctx.mesh),
        params=dist.replicate(state.params, ctx.mesh),
        opt_state=dist.replicate(state.opt_state, ctx.mesh),
        batch_stats=dist.replicate(state.batch_stats, ctx.mesh),
    )
    # Background page-backing for the first save overlaps epoch-1 compute.
    ctx.prewarm_checkpoints(state)

    train_step = make_train_step()
    eval_step = make_eval_step()
    rng = jax.random.PRNGKey(config.get("seed", 0) + 1)

    start = time.monotonic()
    # Dispatch-ahead window (ISSUE 4): up to dispatch_depth() steps stay
    # in flight; the lagged block_until_ready below is the only per-step
    # synchronization on accelerators (dist.step_fence still serializes
    # the host-CPU dev platform at dispatch — see dist.serialize_steps).
    window = DispatchWindow(dispatch_depth())
    for epoch in range(start_epoch, epochs):
        epoch_start = time.monotonic()
        if world > 1:
            # parity: sampler.set_epoch only when world > 1
            # (my_ray_module.py:149-151)
            train_loader.set_epoch(epoch)
        n_batches = 0
        # Batch assembly + host→device placement run up to the prefetch
        # depth ahead on a background thread while the devices crunch:
        # the input pipeline hides behind compute.
        for placed in prefetch_to_device(
            train_loader, ctx.mesh, keys=("x", "y")
        ):
            state, train_metrics = train_step(state, placed, rng)
            dist.step_fence(train_metrics["loss"])
            for matured in window.push(train_metrics["loss"]):
                jax.block_until_ready(matured)
            n_batches += 1
        for matured in window.drain():
            jax.block_until_ready(matured)
        # Block before timing/eval: keeps host and devices in step (and on the
        # CPU dev platform avoids queueing concurrent collective programs).
        jax.block_until_ready(state.params)

        loss_sum = correct = count = 0.0
        for batch in val_loader:
            placed = dist.shard_batch(batch, ctx.mesh)
            out = eval_step(state, placed)
            loss_sum += float(out["loss_sum"])
            correct += float(out["num_correct"])
            count += float(out["count"])
        val_loss = loss_sum / max(count, 1.0)
        accuracy = correct / max(count, 1.0)
        _log(
            f"epoch {epoch}: val_loss={val_loss:.4f} accuracy={accuracy:.4f} "
            f"({n_batches} train batches, "
            f"{time.monotonic() - epoch_start:.1f}s)"
        )
        # Per-epoch metrics + async sharded checkpoint; retention and
        # best/latest policies live in the manager
        # (↔ torch.save ×2 + report, my_ray_module.py:178-205).
        ctx.report(
            {"val_loss": val_loss, "accuracy": accuracy},
            state=_state_tree(state),
            step=epoch + 1,
            # Loader cursor (ISSUE 5): this loop checkpoints at epoch
            # boundaries, so a resumed attempt starts the next epoch at
            # its head — persisted so restore tooling sees one contract
            # across loops.
            data_state={
                "epoch": epoch + 1,
                "batch_index": 0,
                "seed": int(train_loader.seed),
            },
        )
    _log(f"total training time: {time.monotonic() - start:.1f}s")


def train_model(
    num_workers: int | None = None,
    *,
    model: str = "mlp",
    model_kwargs: dict | None = None,
    num_classes: int | None = None,  # None = from the dataset registry
    checkpoint_storage_path: str | None = None,
    global_batch_size: int = 32,
    lr: float = 1e-3,
    epochs: int = 3,
    num_to_keep: int = 2,
    checkpoint: Checkpoint | dict | None = None,
    resume: str = "weights",
    dataset: str = "fashion_mnist",
    data_dir: str | None = None,
    seed: int = 0,
) -> Result:
    """Trainer driver (↔ train_fashion_mnist, my_ray_module.py:216-251),
    generalized to the model zoo: the acceptance configs run ResNet-18/
    CIFAR-10 and ResNet-50/ImageNet through this same entry point
    (BASELINE.md configs 1-2)."""
    workers = num_workers if num_workers and num_workers > 0 else len(jax.devices())
    train_config = {
        "lr": lr,
        "epochs": epochs,
        # parity batch math: global // num_workers (my_ray_module.py:230)
        "batch_size_per_worker": per_worker_batch_size(global_batch_size, workers),
        "checkpoint": checkpoint,
        "resume": resume if resume in ("weights", "full") else "weights",
        "dataset": dataset,
        "data_dir": data_dir,
        "seed": seed,
        "model": model,
        "model_kwargs": model_kwargs,
        "num_classes": num_classes,
    }
    # TPUFLOW_DCN_DATA=N: hybrid-mesh mode — the 'data' axis spans N
    # slices/hosts over DCN while each slice's local devices form an
    # ICI-side 'fsdp' axis (dist.make_hybrid_mesh; the multi-pod recipe
    # of SURVEY.md §1). batch_sharding splits batches over data x fsdp,
    # so the DP world and the loss math are unchanged vs the flat mesh.
    # An EXPLICIT num_workers argument always wins over the env knob —
    # a lingering env var must not silently discard a caller's ask.
    dcn_data = int(knobs.raw("TPUFLOW_DCN_DATA", "0") or 0)
    if dcn_data > 1 and (num_workers is None or num_workers <= 0):
        _log(f"hybrid mesh: TPUFLOW_DCN_DATA={dcn_data} (data over "
             "DCN x fsdp over ICI)")
        scaling = ScalingConfig(dcn_mesh_axes={"data": dcn_data})
    else:
        scaling = ScalingConfig(num_workers=workers)
    trainer = Trainer(
        train_func_per_worker,
        train_loop_config=train_config,
        scaling_config=scaling,
        run_config=RunConfig(
            storage_path=checkpoint_storage_path,
            checkpoint_config=CheckpointConfig(num_to_keep=num_to_keep),
            verbose=1,
        ),
    )
    result = trainer.fit()
    return result


def train_fashion_mnist(num_workers: int | None = None, **kw):
    """Parity alias (↔ train_fashion_mnist, my_ray_module.py:216)."""
    kw.setdefault("model", "mlp")
    return train_model(num_workers, **kw)


class TpuPredictor:
    """Stateful batch predictor (↔ TorchPredictor, my_ray_module.py:266-284):
    loads best weights once, then maps batches to logits + argmax."""

    def __init__(
        self,
        checkpoint: Checkpoint | dict,
        cpu_only: bool = False,
        *,
        model=None,
        sample_shape: tuple = (28, 28),
        zero_copy: bool = False,
    ):
        if isinstance(checkpoint, dict):
            checkpoint = Checkpoint.from_json(checkpoint)
        # cpu_only kept for signature parity; device choice belongs to jax.
        self._predictor = BatchPredictor.from_checkpoint(
            checkpoint,
            model if model is not None else NeuralNetwork(),
            sample_input=np.zeros((1, *sample_shape), np.float32),
            zero_copy=zero_copy,
        )

    def __call__(self, batch: dict) -> dict:
        return self._predictor(batch)


__all__ = [
    "TpuPredictor",
    "build_model",
    "get_dataloaders",
    "get_labels_map",
    "map_batches",
    "set_weights_from_checkpoint",
    "train_fashion_mnist",
    "train_func_per_worker",
    "train_model",
]


if __name__ == "__main__":
    # Standalone harness (↔ my_ray_module.py:287-288): run the trainer outside
    # any flow, all local devices.
    res = train_fashion_mnist(
        num_workers=None,
        checkpoint_storage_path=knobs.raw("TPUFLOW_STORAGE", "/tmp/tpuflow_run"),
        epochs=int(os.environ.get("EPOCHS", "3")),
    )
    print(res.to_json())
