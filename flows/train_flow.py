"""Training flow: gang-scheduled distributed FashionMNIST training on TPU.

Parity pipeline for the reference's ``train_flow.py`` (RayTorchTrain):
4-step DAG ``start → train(×N gang) → join → end`` with cron schedule record,
CLI parameters (epochs/batch_size/learning_rate, ``--from-task`` /
``--from-run`` warm start, train_flow.py:23-35), step retry ×3
(train_flow.py:41), a gang train step with formation timeout
(train_flow.py:42), device profiling (train_flow.py:51), checkpoint storage
at ``current.tpu_storage_path`` (train_flow.py:65 ray_storage_path), and the
tolerant join (train_flow.py:83-88).

Run:      python flows/train_flow.py run
Resume:   python flows/train_flow.py run --from-run TpuTrain/<id>
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tpuflow.utils import knobs  # noqa: E402

from tpuflow.flow import (  # noqa: E402
    FlowSpec,
    Parameter,
    Run,
    Task,
    current,
    device_profile,
    kubernetes,
    retry,
    schedule,
    step,
    tpu,
)

# One process owns all the chips of a host, so the gang width is the HOST
# count (↔ train_flow.py:17's two nodes); a local gang of N > 1 is the CPU
# simulation of a multi-host world and is refused on an accelerator.
N_PARALLEL = int(knobs.raw("TPUFLOW_N_PARALLEL", "1"))


@schedule(cron="*/5 * * * *")  # ↔ train_flow.py:20
class TpuTrain(FlowSpec):
    """Train an MLP on FashionMNIST with data-parallel TPU workers and
    per-epoch async sharded checkpoints."""

    epochs = Parameter("epochs", default=3, help="number of training epochs")
    batch_size = Parameter(
        "batch_size", default=32, help="global batch size (split across workers)"
    )
    learning_rate = Parameter("learning_rate", default=1e-3, help="SGD lr")
    from_task = Parameter(
        "from_task",
        default="",
        help="task pathspec Flow/run/step/task to warm-start the model from",
    )
    from_run = Parameter(
        "from_run",
        default="",
        help="run pathspec Flow/run to warm-start the model from",
    )
    dataset = Parameter("dataset", default="fashion_mnist", help="dataset name")
    model = Parameter(
        "model",
        default="mlp",
        help="mlp | resnet18 | resnet50 | vit | vit_tiny | vit_small "
        "(BASELINE configs 1-2 run the resnets through this same flow; "
        "the vit_tiny/vit_small patch-16 presets need images patch-16 "
        "divides, e.g. imagenet_synth — use 'vit' for the 28/32-pixel "
        "datasets)",
    )

    @step
    def start(self):
        self.next(self.train, num_parallel=N_PARALLEL)  # ↔ train_flow.py:39

    @retry(times=3)  # ↔ train_flow.py:41
    @tpu(all_hosts_started_timeout=60 * 5)  # ↔ train_flow.py:42 @metaflow_ray
    @kubernetes(topology=knobs.raw("TPUFLOW_TOPOLOGY", "v5e-8"))
    @device_profile(interval=1)  # ↔ train_flow.py:51 @gpu_profile
    @step
    def train(self):
        import my_tpu_module

        # Warm-start checkpoint resolution (↔ train_flow.py:68-75): task
        # pathspec first, then run pathspec; the artifact carries a handle,
        # never tensors.
        checkpoint = None
        if self.from_task:
            checkpoint = Task(self.from_task).data.result.checkpoint
        elif self.from_run:
            checkpoint = Run(self.from_run).data.result.checkpoint
        if checkpoint is not None:
            print(f"[train_flow] warm-starting from checkpoint {checkpoint.path}")
        # Recorded so consumers (and the medium-config evidence script) can
        # verify a warm start without scraping gang-subprocess stdout.
        self.warm_started = checkpoint is not None

        # Cross-flow handoff artifacts: the eval flow rebuilds THIS model
        # for THIS dataset (the checkpoint handle alone carries neither).
        self.model_used = self.model
        self.dataset_used = self.dataset
        self.result = my_tpu_module.train_model(
            num_workers=None,  # all devices of the gang's world
            model=self.model,  # head sized from the dataset registry
            checkpoint_storage_path=current.tpu_storage_path,
            global_batch_size=self.batch_size,
            lr=self.learning_rate,
            epochs=self.epochs,
            checkpoint=checkpoint,
            dataset=self.dataset,
        )
        self.next(self.join)

    @step
    def join(self, inputs):
        # Only the gang head carries a result (↔ train_flow.py:83-88).
        result = None
        for inp in inputs:
            try:
                result = inp.result
                break
            except AttributeError:
                continue
        if result is None:
            raise RuntimeError("no gang member produced a result artifact")
        self.result = result
        self.next(self.end)

    @step
    def end(self):
        print(f"[train_flow] result metrics: {self.result.metrics}")  # ↔ :95


if __name__ == "__main__":
    TpuTrain.main()
