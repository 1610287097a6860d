"""Trainer runtime: the Ray-Train-shaped API over SPMD JAX.

Replaces TorchTrainer / ScalingConfig / RunConfig / CheckpointConfig /
ray.train.report / Result as the reference exercises them
(my_ray_module.py:216-251, 149, 177, 203-205):

- ``Trainer(train_loop_per_worker, train_loop_config, scaling_config,
  run_config).fit() → Result`` — same constructor shape.
- Worker-group launch becomes SPMD: the loop body runs **once per host
  process** (one per pod-slice host, gang-launched by the flow layer), and
  the "workers" of ScalingConfig are data-parallel shards on the device mesh.
  Collectives are emitted by XLA inside the jitted step, so the per-worker
  loop contains no communication code — the same encapsulation Ray Train
  gives the reference.
- ``get_context().report(metrics, state=...)`` collects per-epoch metrics and
  drives the async sharded CheckpointManager (retention + best/latest),
  replacing report()'s upload-to-storage_path.
- ``Result`` carries final metrics, the metrics history, and checkpoint
  *handles* (path + metadata, never tensors) for cross-run/flow handoff.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from typing import Any, Callable

import jax

from tpuflow import dist, obs
from tpuflow.ckpt import Checkpoint, CheckpointManager
from tpuflow.utils.heartbeat import beat as _heartbeat
from tpuflow.utils import knobs
from tpuflow.utils.preempt import (
    Preempted,
    launch_attempt,
    preemption_requested,
)

logger = logging.getLogger("tpuflow.train")


@dataclasses.dataclass
class ScalingConfig:
    """↔ ray ScalingConfig(num_workers, use_gpu) (my_ray_module.py:240-243).
    There is no ``use_tpu``: devices are the ones JAX selects, and CPU is
    chosen with ``dist.force_cpu_platform`` / ``TPUFLOW_FORCE_CPU=1``.

    ``num_workers``: data-parallel shard count; ``None``/-1 → every device.
    ``mesh_axes``: optional full mesh spec (e.g. {'data': 4, 'tensor': 2}) for
    beyond-DP layouts; overrides num_workers.
    ``dcn_mesh_axes``: optional DCN (cross-slice / cross-host) axes for a
    hybrid mesh — e.g. ``dcn_mesh_axes={'data': 2}`` with
    ``mesh_axes={'fsdp': 4}`` puts the gradient all-reduce across hosts
    and FSDP's per-layer collectives on ICI (dist.make_hybrid_mesh). When
    set without ``mesh_axes``, the per-slice devices land on 'fsdp'.
    """

    num_workers: int | None = None
    mesh_axes: dict[str, int] | None = None
    dcn_mesh_axes: dict[str, int] | None = None
    rendezvous_timeout_s: float = 300.0  # ↔ all_nodes_started_timeout


@dataclasses.dataclass
class CheckpointConfig:
    """↔ ray CheckpointConfig(num_to_keep=2) (my_ray_module.py:222,236)."""

    num_to_keep: int | None = 2
    best_metric: str = "val_loss"
    best_mode: str = "min"
    async_save: bool = True
    # Reduced-precision checkpoints: 'bfloat16'/'float16' casts floating
    # leaves down on save (half the bytes, double the effective GB/s);
    # None = bit-exact. See CheckpointManager(save_dtype=...).
    save_dtype: str | None = None


@dataclasses.dataclass
class RunConfig:
    """↔ ray RunConfig(checkpoint_config, storage_path, verbose)
    (my_ray_module.py:235-239)."""

    storage_path: str | None = None
    checkpoint_config: CheckpointConfig = dataclasses.field(
        default_factory=CheckpointConfig
    )
    verbose: int = 1


@dataclasses.dataclass
class Result:
    """↔ ray Result (my_ray_module.py:250-251; consumed at train_flow.py:71-77,
    eval_flow.py:42-49): metrics + checkpoint handles, JSON-serializable."""

    metrics: dict[str, Any]
    metrics_history: list[dict[str, Any]]
    checkpoint: Checkpoint | None
    best_checkpoint: Checkpoint | None
    path: str | None
    # The mesh the run actually trained on (axis -> size): the structural
    # proof consumers need to verify a topology ask (e.g. the hybrid
    # DCN x ICI layout) was honored, without scraping gang-worker logs.
    mesh_axes: dict[str, int] | None = None

    def to_json(self) -> dict:
        return {
            "metrics": self.metrics,
            "metrics_history": self.metrics_history,
            "checkpoint": self.checkpoint.to_json() if self.checkpoint else None,
            "best_checkpoint": (
                self.best_checkpoint.to_json() if self.best_checkpoint else None
            ),
            "path": self.path,
            "mesh_axes": self.mesh_axes,
        }

    @classmethod
    def from_json(cls, obj: dict) -> "Result":
        return cls(
            metrics=obj.get("metrics", {}),
            metrics_history=obj.get("metrics_history", []),
            checkpoint=(
                Checkpoint.from_json(obj["checkpoint"]) if obj.get("checkpoint") else None
            ),
            best_checkpoint=(
                Checkpoint.from_json(obj["best_checkpoint"])
                if obj.get("best_checkpoint")
                else None
            ),
            path=obj.get("path"),
            mesh_axes=obj.get("mesh_axes"),
        )


class TrainContext:
    """Per-worker context (↔ ray.train.get_context() + report()).

    ``world_size``: data-parallel shard count (my_ray_module.py:149 uses it
    for the batch split); ``world_rank``: this host process's index
    (my_ray_module.py:177 uses it for logging).
    """

    def __init__(self, mesh, run_config: RunConfig):
        self.mesh = mesh
        self.run_config = run_config
        self._reported: list[dict[str, Any]] = []
        self._manager: CheckpointManager | None = None
        # Training-health watch over reported metrics (ISSUE 3): custom
        # Trainer loops own their state, so in-process rollback is not
        # ours to do — instead a diverged report SKIPS the checkpoint
        # save (the last durable step stays clean) and raises
        # TrainingDiverged; the gang @retry machinery then relaunches
        # and resumes from that clean step — rollback by requeue.
        from tpuflow.obs.health import HealthMonitor

        self._health = HealthMonitor.from_env()
        if run_config.storage_path:
            cc = run_config.checkpoint_config
            self._manager = CheckpointManager(
                os.path.join(run_config.storage_path, "checkpoints"),
                max_to_keep=cc.num_to_keep,
                best_metric=cc.best_metric,
                best_mode=cc.best_mode,
                async_save=cc.async_save,
                save_dtype=cc.save_dtype,
            )

    def get_world_size(self) -> int:
        return dist.data_axis_size(self.mesh)

    def get_world_rank(self) -> int:
        return jax.process_index()

    @property
    def checkpoint_manager(self) -> CheckpointManager | None:
        return self._manager

    def prewarm_checkpoints(self, state) -> None:
        """Start background page-backing for this state's checkpoint files.

        Call right after building the train state: the pool warmup overlaps
        epoch-1 compute so even the run's FIRST ``report(state=...)`` save
        writes onto recycled pages (see RecyclePool.prewarm). Only this
        process's addressable shard bytes are counted.
        """
        if self._manager is not None:
            self._manager.prewarm(state)

    def report(
        self,
        metrics: dict[str, Any],
        *,
        state=None,
        step: int | None = None,
        data_state: dict[str, Any] | None = None,
    ) -> None:
        """Record epoch metrics; if ``state`` is given, save it as the epoch's
        checkpoint (async, sharded). ↔ ray.train.report(metrics, checkpoint)
        (my_ray_module.py:203-205). Acts as a gang barrier like the original.

        ``data_state``: optional loader cursor (epoch, batch index, shuffle
        seed — ``ShardedLoader.state_dict``) persisted in the checkpoint's
        metadata; a resumed attempt reads it back via ``latest_data_state``
        and skips exactly the consumed batches (deterministic mid-epoch
        resume, ISSUE 5).
        """
        from tpuflow.dist import membership as _membership

        # Elastic gang (ISSUE 7): the report IS this loop's step fence —
        # a pending mesh generation unwinds the loop here, BEFORE this
        # step's metrics/save land (the re-formed loop replays it). One
        # env lookup when not in an elastic gang.
        plan = _membership.pending_reform()
        if plan is not None:
            raise _membership.MeshReform(plan)
        metrics = {
            k: (float(v) if hasattr(v, "__float__") else v)
            for k, v in metrics.items()
        }
        self._reported.append(metrics)
        save_step = step if step is not None else len(self._reported)
        # Unified telemetry: every report lands in the run's event stream
        # beside the step spans (numeric metrics only — the event must
        # stay one JSON line).
        obs.event(
            "train.report",
            step=save_step,
            **{k: v for k, v in metrics.items()
               if isinstance(v, (int, float))},
        )
        # Custom loops have no StepClock: the report cadence feeds the
        # live goodput ledger the export endpoint serves (step number +
        # last loss; rates derive from the report fences).
        from tpuflow.obs import goodput as _goodput

        _goodput.live().note_report(
            save_step,
            loss=next(
                (
                    metrics[k]
                    for k in ("loss", "train_loss", "val_loss")
                    if isinstance(metrics.get(k), (int, float))
                ),
                None,
            ),
        )
        if self._health is not None:
            loss = next(
                (
                    metrics[k]
                    for k in ("loss", "train_loss", "val_loss")
                    if isinstance(metrics.get(k), float)
                ),
                None,
            )
            if loss is not None:
                gn = metrics.get("grad_norm")
                anomaly = self._health.observe(
                    save_step, loss,
                    gn if isinstance(gn, float) else None,
                )
                if anomaly is not None:
                    from tpuflow.obs.health import TrainingDiverged

                    if self._manager is not None:
                        self._manager.wait_until_finished()
                    raise TrainingDiverged(
                        anomaly,
                        hint="report skipped the checkpoint save; the "
                        "newest committed step is clean — a gang retry "
                        "resumes from it",
                    )
        try:
            if state is not None and self._manager is not None:
                self._manager.save(
                    save_step, state, metrics=metrics, data_state=data_state
                )
                if (
                    launch_attempt() > 0
                    or _membership.current_generation() > 0
                ):
                    # Retried attempt OR re-formed elastic generation:
                    # commit THIS step before returning to the loop (see
                    # launch_attempt — the async deferred commit would
                    # otherwise livelock a deterministic crash: the dying
                    # step never becomes the resume point. A post-reform
                    # gang replays for the same reason: a shrink abandons
                    # the stranded commit, so without eager banking a
                    # deterministic crasher re-fires on the replayed step
                    # and the gang oscillates shrink/grow until the
                    # resize budget forces the requeue fallback).
                    self._manager.wait_until_finished()
            if self.run_config.storage_path and jax.process_index() == 0:
                # Observability stream (SURVEY.md §5): one JSON line per
                # report, aggregated on process 0, appendable/tail-able
                # during the run.
                with open(
                    os.path.join(
                        self.run_config.storage_path, "metrics.jsonl"
                    ),
                    "a",
                ) as f:
                    f.write(
                        json.dumps(
                            {"step": save_step, "time": time.time(), **metrics}
                        )
                        + "\n"
                    )
            if self.run_config.verbose:
                logger.info("report[%d]: %s", len(self._reported), metrics)
            dist.barrier("report")
        except Exception as e:
            # A dead gang peer closes its sockets instantly, so the save
            # drain's commit collective or the report barrier raises here
            # within milliseconds of the loss. Give the supervisor — which
            # detects the death on its own poll — a bounded window to
            # announce the re-form; a genuine error re-raises unchanged.
            plan = _membership.reform_after_failure(e)
            if plan is None:
                raise
            raise _membership.MeshReform(plan) from e
        # Step boundary: stamp this member's liveness for the gang
        # supervisor, give the fault harness its injection point, then
        # honor a pending preemption — the state just saved above IS the
        # drain checkpoint, so committing it and raising is all that's
        # left (gang_exec turns Preempted into the requeue exit code).
        # The stamp carries the step so a stall report names WHERE the
        # member stopped, not just how stale the stamp is.
        _heartbeat(save_step)
        if knobs.raw("TPUFLOW_FAULT"):
            from tpuflow.testing import faults

            faults.step_boundary(save_step)
        if preemption_requested():
            if self._manager is not None:
                self._manager.wait_until_finished()
            raise Preempted(
                f"preempted; drained checkpoint at step {save_step}"
            )

    def latest_step(self) -> int:
        """Newest committed checkpoint step, 0 when none exists yet.

        The resume point for a retried or requeued gang attempt: the
        launcher passes the attempt through ``TPUFLOW_ATTEMPT`` and the
        loop continues from ``latest_step() + 1`` instead of step 0 (the
        manager already rebuilt the metrics history from the same
        checkpoint at construction)."""
        if self._manager is None:
            return 0
        return self._manager.latest_step() or 0

    def restore_latest(self, abstract_state=None):
        """Restore the newest committed checkpoint (crc-verified, local
        tier preferred, with fallback to the persistent copy and then the
        previous step on corruption); None when no checkpoint exists —
        start from scratch."""
        if self._manager is None or self._manager.latest_step() is None:
            return None
        return self._manager.restore(abstract_state=abstract_state)

    def latest_data_state(self) -> dict[str, Any] | None:
        """Loader cursor persisted with the newest committed checkpoint
        (``report(data_state=...)``), or None. Custom loops own their
        data pipeline, so mid-epoch replay is theirs to apply: feed the
        cursor back into ``ShardedLoader.set_epoch`` + ``skip_batches``
        before iterating the resumed epoch."""
        if self._manager is None:
            return None
        latest = self._manager.latest_step()
        if latest is None:
            return None
        try:
            return self._manager.restore_metadata(latest).get("data_state")
        except FileNotFoundError:
            return None

    def latest_metrics(self) -> dict[str, Any]:
        return self._reported[-1] if self._reported else {}


_ACTIVE_CONTEXT: TrainContext | None = None


def get_context() -> TrainContext:
    """↔ ray.train.get_context() (my_ray_module.py:149,177)."""
    if _ACTIVE_CONTEXT is None:
        raise RuntimeError("get_context() called outside a Trainer.fit() run")
    return _ACTIVE_CONTEXT


class Trainer:
    """↔ TorchTrainer(...).fit() (my_ray_module.py:244-250)."""

    def __init__(
        self,
        train_loop_per_worker: Callable[[dict], None],
        *,
        train_loop_config: dict | None = None,
        scaling_config: ScalingConfig | None = None,
        run_config: RunConfig | None = None,
    ):
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = train_loop_config or {}
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()

    def _build_mesh(self):
        sc = self.scaling_config
        dist.initialize(timeout_s=sc.rendezvous_timeout_s)
        if sc.dcn_mesh_axes:
            import math

            ici = sc.mesh_axes
            if not ici:
                n_slices = math.prod(sc.dcn_mesh_axes.values())
                ndev = len(jax.devices())
                if ndev % n_slices:
                    raise ValueError(
                        f"dcn_mesh_axes {sc.dcn_mesh_axes} want {n_slices} "
                        f"slices but {ndev} devices don't divide evenly"
                    )
                ici = {"fsdp": ndev // n_slices}
            return dist.make_hybrid_mesh(sc.dcn_mesh_axes, ici)
        if sc.mesh_axes:
            return dist.make_mesh(sc.mesh_axes)
        ndev = len(jax.devices())
        n = sc.num_workers
        if n is None or n == -1:
            n = ndev
        from tpuflow.dist import membership as _membership

        if n != ndev and _membership.current_generation() > 0:
            # Re-formed elastic world (ISSUE 7): the world the caller
            # originally asked for no longer exists — the data-parallel
            # axis absorbs the resize. (Clamping, not erroring, is the
            # contract: a shrink must not crash the survivors into the
            # requeue path this machinery exists to avoid.)
            logger.info(
                "elastic generation %d: scaling num_workers %d → %d "
                "devices", _membership.current_generation(), n, ndev,
            )
            n = ndev
        if n > ndev:
            raise ValueError(f"num_workers={n} but only {ndev} devices present")
        if jax.process_count() > 1 and n != ndev:
            # A device subset on a multi-host gang would exclude some hosts'
            # devices: every process still enters the collectives (SPMD), so
            # the program would deadlock or crash inside XLA. Scale the gang
            # itself (fewer hosts / smaller slice) instead of slicing here.
            raise ValueError(
                f"num_workers={n} selects a subset of the {ndev} global "
                f"devices across {jax.process_count()} processes; device "
                "subsets are single-host only — use every gang device "
                "(num_workers=-1) or shrink the gang"
            )
        return dist.make_mesh({"data": n}, devices=jax.devices()[:n])

    def fit(self) -> Result:
        global _ACTIVE_CONTEXT
        # Retried/requeued attempts reload the compiled step from the
        # persistent cache instead of re-paying the first-compile wall.
        dist.maybe_enable_compile_cache()
        # Live goodput + metrics endpoint (ISSUE 6): restart the ledger
        # for this fit, and serve /metrics + /status when opted in via
        # TPUFLOW_OBS_HTTP_PORT (member 0 only; one env lookup when off).
        from tpuflow.obs import export as _obs_export
        from tpuflow.obs import goodput as _goodput

        _goodput.live().reset()
        _obs_export.maybe_start_from_env()
        from tpuflow.dist import membership as _membership

        start = time.monotonic()
        reported_carry: list[dict[str, Any]] = []
        reforms = 0
        while True:
            mesh = self._build_mesh()
            ctx = TrainContext(mesh, self.run_config)
            if reported_carry:
                # Metrics reported by earlier generations of this fit —
                # the loop body restarted, the run did not.
                ctx._reported = list(reported_carry)
            _ACTIVE_CONTEXT = ctx
            reform_plan = None
            try:
                with obs.span(
                    "train.fit", workers=dist.data_axis_size(mesh),
                    generation=_membership.current_generation(),
                ), mesh:
                    try:
                        self.train_loop_per_worker(
                            dict(self.train_loop_config)
                        )
                    except _membership.MeshReform as rf:
                        reform_plan = rf.plan
                    except Exception as e:
                        # The loop body's own collective died (a peer's
                        # sockets close instantly): classify against the
                        # supervisor's plan before giving up.
                        plan = _membership.reform_after_failure(e)
                        if plan is None:
                            raise
                        reform_plan = plan
            finally:
                _ACTIVE_CONTEXT = None
                if (
                    reform_plan is None
                    and ctx.checkpoint_manager is not None
                ):
                    ctx.checkpoint_manager.wait_until_finished()
            if reform_plan is None:
                break
            # Mesh re-form (ISSUE 7): hand everything to the checkpoint,
            # tear the old world down, re-rendezvous as the new
            # generation, and re-enter the loop body — which resumes via
            # ctx.latest_step() exactly like a requeued attempt, at
            # step-fence cost instead of process-lifecycle cost.
            reforms += 1
            mgr = ctx.checkpoint_manager
            if mgr is not None:
                if reform_plan.reason == "grow":
                    # Every current member is alive at a grow fence: the
                    # deferred commit completes normally, so the grown
                    # gang resumes from the CURRENT step (the "emergency
                    # checkpoint if none is fresh" clause — report-driven
                    # loops save every step, so the drain commits it).
                    try:
                        mgr.wait_until_finished()
                    except Exception:
                        mgr.abandon_pending()
                else:
                    # A peer died mid-save: its shards will never arrive;
                    # the deferred commit is unfinishable. Abandon it and
                    # resume from the last FULLY committed step.
                    mgr.abandon_pending()
                mgr.close()
            reported_carry = list(ctx._reported)
            logger.info(
                "mesh re-form: generation %d (%s, %d members)",
                reform_plan.generation, reform_plan.reason,
                reform_plan.num_processes,
            )
            _membership.quiesce_and_reform(reform_plan)
        if self.run_config.verbose:
            logger.info(
                "fit() finished in %.1fs (%d reports)",
                time.monotonic() - start,
                len(ctx._reported),
            )
        mgr = ctx.checkpoint_manager
        latest = best = None
        if mgr is not None:
            if mgr.latest_step() is not None:
                latest = mgr.checkpoint()
            if mgr.best_step() is not None:
                best = mgr.checkpoint(best=True)
            mgr.close()
        # Metrics-history continuity across retries: a retried/requeued
        # attempt re-reported only its own steps, while the manager's
        # history — rebuilt from the latest committed checkpoint at
        # construction, extended by this attempt's saves — is continuous
        # from the first attempt's first save. Prefer it when it knows
        # more (reports without ``state=`` still fall back to _reported).
        # After a mesh re-form the manager's view is also the DEDUPED one:
        # a step reported right before the loss was replayed by the next
        # generation, so _reported can carry it twice.
        if mgr is not None and mgr._metrics_history and (
            reforms or len(mgr._metrics_history) > len(ctx._reported)
        ):
            metrics_history = [dict(m) for m in mgr._metrics_history]
        else:
            metrics_history = list(ctx._reported)
        return Result(
            metrics=ctx.latest_metrics(),
            metrics_history=metrics_history,
            checkpoint=latest,
            best_checkpoint=best,
            path=self.run_config.storage_path,
            mesh_axes={k: int(v) for k, v in mesh.shape.items()},
        )
