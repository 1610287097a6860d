"""Gang member bootstrap: one host process of a gang step.

Invoked by FlowRunner._exec_gang as
``python -m tpuflow.flow.gang_exec <flow_file> <class> <step> <run_id>
<task_id> <state_path>`` with TPUFLOW_NUM_PROCESSES / TPUFLOW_PROCESS_ID /
TPUFLOW_COORDINATOR in the env. Each member joins the ``jax.distributed``
world (rendezvous with timeout ↔ @metaflow_ray's all_nodes_started_timeout,
train_flow.py:42), runs the step body SPMD, persists its artifacts to its own
task dir (head = task_id of the gang step; the join step reads all of them),
and shuts down.

On the local CPU simulation each member contributes
``TPUFLOW_GANG_LOCAL_DEVICES`` (default 1) virtual CPU devices with gloo
cross-process collectives — the dev-mode analogue of one TPU host per pod
slice."""

from __future__ import annotations

import importlib.util
import os
import pickle
import sys
from tpuflow.utils import knobs


def _bootstrap_jax() -> None:
    import jax

    if knobs.raw("TPUFLOW_FORCE_CPU") == "1":
        from tpuflow.dist import force_cpu_platform

        local = int(knobs.raw("TPUFLOW_GANG_LOCAL_DEVICES", "1"))
        force_cpu_platform(local, exact=True)
        if int(knobs.raw("TPUFLOW_NUM_PROCESSES", "1")) > 1:
            # Cross-process CPU collectives only exist for real gangs —
            # a 1-process member must not ask for gloo (jaxlib refuses to
            # build gloo collectives without a distributed client).
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
    # Comm/compute overlap (ISSUE 10): stage the async-collective libtpu
    # scheduling flags BEFORE any backend touch, so the per-microbatch
    # gradient reduce-scatters the FSDP accumulation scan issues can
    # hide behind the next microbatch's backward. One knob
    # (TPUFLOW_COMM_OVERLAP=0) turns both halves off; CPU members no-op.
    from tpuflow.dist import maybe_enable_async_collectives

    maybe_enable_async_collectives()
    # Gang members share the persistent compile cache: after one worker
    # (or a previous attempt) compiled the step, the rest load it.
    from tpuflow.dist import maybe_enable_compile_cache

    maybe_enable_compile_cache()


def _store_artifacts(flow_name: str, run_id: str, step_name: str) -> dict:
    """Artifacts of the most recently completed upstream task in the run's
    datastore — the k8s-pod replacement for the local launcher's pickled
    gang state (each step runs as its own Job against shared storage, the
    Metaflow execution model the deployer's manifests assume)."""
    from tpuflow.flow import store

    rd = store.run_dir(flow_name, run_id)
    if not os.path.isdir(rd):
        os.makedirs(rd, exist_ok=True)
        store.write_run_meta(
            flow_name, run_id, {"run_id": run_id, "status": "running"}
        )
        return {}
    best = None
    for root, _dirs, files in os.walk(rd):
        if "artifacts.json" not in files:
            continue
        # Only COMMITTED artifact saves count: the marker is written
        # strictly after artifacts.json + blobs (store.save_artifacts), so
        # a task that crashed mid-save — a failed attempt's partial
        # artifacts — can never be resurrected here by winning on mtime.
        if "artifacts.ok" not in files:
            continue
        parts = root.rstrip(os.sep).split(os.sep)
        if len(parts) < 2 or parts[-2] == step_name:
            continue  # not a task dir / the step being (re)run
        mtime = os.path.getmtime(os.path.join(root, "artifacts.ok"))
        if best is None or mtime > best[0]:
            best = (mtime, parts[-2], parts[-1])
    if best is None:
        return {}
    return store.load_artifacts(flow_name, run_id, best[1], int(best[2]))


def main(argv: list[str]) -> None:
    flow_file, class_name, step_name, run_id, task_id, state_path = argv
    # Preemption contract: SIGTERM (from the infrastructure, or from the
    # supervisor's grace-kill of a gang whose peer died) only SETS A FLAG;
    # the train loops check it at step boundaries, drain + commit a final
    # checkpoint, and raise Preempted — converted below into the requeue
    # exit code the supervisor treats as retry-without-budget.
    import signal

    from tpuflow.utils.preempt import (
        REQUEUE_EXIT_CODE,
        Preempted,
        request_preemption,
    )

    def _on_sigterm(signum, frame):
        # Flag first — the drain contract must hold even if forensics
        # fail. Then dump the flight ring: this SIGTERM may be the
        # supervisor's kill escalation (SIGKILL follows after the grace
        # window, when no further code runs), so now is the only chance
        # to leave a structured artifact; a clean preemption drain just
        # gains one extra file. dump_flight is signal-safe (ring
        # snapshot with a lock timeout) and never raises.
        request_preemption(signum, frame)
        try:
            from tpuflow.obs import flight as _flight

            _flight.dump_flight("sigterm")
        except Exception:
            pass

    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:  # not the main thread (library embedding)
        pass
    from tpuflow.testing import faults

    faults.maybe_rendezvous_delay()
    _bootstrap_jax()

    spec = importlib.util.spec_from_file_location("_tpuflow_gang_flow", flow_file)
    module = importlib.util.module_from_spec(spec)
    sys.modules["_tpuflow_gang_flow"] = module
    spec.loader.exec_module(module)
    flow_cls = getattr(module, class_name)

    if state_path == "--from-store":
        state = {
            "artifacts": _store_artifacts(flow_cls.__name__, run_id, step_name)
        }
    else:
        with open(state_path, "rb") as f:
            state = pickle.load(f)

    from tpuflow import dist
    from tpuflow.dist import membership
    from tpuflow.flow import store
    from tpuflow.flow.spec import current

    timeout = float(knobs.raw("TPUFLOW_GANG_TIMEOUT", "300"))
    if (
        membership.enabled()
        and knobs.raw("TPUFLOW_GANG_REJOIN") == "1"
    ):
        # Requeued capacity rejoining an elastic gang (ISSUE 7): skip the
        # gen-0 rendezvous entirely — request inclusion, wait for the
        # supervisor's grow plan, and enter that generation's world. The
        # survivors hit the same generation at their next step fence.
        faults.maybe_rejoin_delay()
        me = membership.member_id()
        membership.request_join(me)
        plan = membership.await_plan_including(me, timeout_s=timeout)
        membership.join_generation(plan, timeout_s=timeout)
    else:
        # dist.initialize routes elastic gangs (TPUFLOW_MEMBERSHIP_DIR
        # set by the launcher) through the teardown-capable membership
        # runtime at generation 0.
        dist.initialize(timeout_s=timeout)
    # Deliberately NO heartbeat here: the first stamp comes from the train
    # loops (fenced steps / reports), so only members that demonstrably
    # adopted the protocol are ever judged for staleness — an arbitrary
    # quiet step body must not be reaped by the default stall timeout.
    # (A member hung in rendezvous itself is bounded by dist.initialize's
    # own timeout, which exits non-zero → supervisor fail-fast.)

    import jax

    flow = flow_cls()
    for k, v in state["artifacts"].items():
        setattr(flow, k, v)

    current.flow_name = flow_cls.__name__
    current.run_id = str(run_id)
    current.step_name = step_name
    current.task_id = int(task_id)
    current.gang_index = jax.process_index()
    current.gang_size = jax.process_count()
    current.tpu_storage_path = os.path.join(
        store.run_dir(flow_cls.__name__, run_id), "tpu_storage", step_name
    )
    os.makedirs(current.tpu_storage_path, exist_ok=True)

    # The recorder self-configures from TPUFLOW_OBS_DIR/TPUFLOW_OBS_PROC
    # (set by FlowRunner._exec_gang), so each member writes its own
    # events.p<proc>.jsonl beside the head's — merged at end of run.
    from tpuflow import obs
    from tpuflow.obs import export as obs_export

    # Live metrics endpoint (ISSUE 6, opt-in TPUFLOW_OBS_HTTP_PORT):
    # gang member 0 serves /metrics + /status for the whole gang.
    obs_export.maybe_start_from_env(proc=jax.process_index())

    fn = flow_cls.steps()[step_name]
    try:
        with obs.span(
            "flow.gang_member",
            step=step_name,
            gang_index=jax.process_index(),
            gang_size=jax.process_count(),
        ):
            fn(flow)
    except Preempted as e:
        # The loop already drained and committed its final checkpoint
        # (full save, or the fast local-tier emergency save when the
        # grace window was closing); exit with the requeue code —
        # os._exit, because surviving this far with a possibly-dead peer
        # means the shutdown barrier below could hang until the
        # collective timeout.
        from tpuflow.utils.preempt import grace_remaining_s

        grace = grace_remaining_s()
        spare = f" with {grace:.1f}s grace to spare" if grace is not None else ""
        print(f"[tpuflow] gang member preempted, requeueing{spare}: {e}")
        obs.flush()
        sys.stdout.flush()
        os._exit(REQUEUE_EXIT_CODE)
    except BaseException as e:
        # Fatal path: this member is about to exit non-zero and the
        # supervisor will record flow.member_failed — leave the
        # structured forensic artifact (ring + env fingerprint + THIS
        # stack) that the event references, then let the failure
        # propagate unchanged.
        from tpuflow.obs import flight as flight_mod

        flight_mod.dump_flight("unhandled_exception", e)
        obs.flush()
        raise
    # Run registry (ISSUE 16): member 0 appends this leg's headline
    # (goodput fraction, tokens/s, HBM peak) to the cross-run registry —
    # a single knob read when TPUFLOW_REGISTRY_PATH is unarmed, and
    # never a run failure when it is.
    if jax.process_index() == 0:
        from tpuflow.obs import registry as registry_mod

        registry_mod.maybe_append_live("train")
    obs.flush()

    # Every member persists its own artifacts; the head's land at the gang
    # step's task_id and are what the flow continues with (non-head members
    # mirror the reference's artifact-less worker tasks, train_flow.py:85-88).
    store.save_artifacts(
        flow_cls.__name__, run_id, step_name, int(task_id), flow._artifacts
        if jax.process_index() == 0
        else {},
    )
    if jax.process_index() == 0:
        # Hand the step's transition back to the parent runner.
        transition = getattr(flow, "_next", None)
        if transition is not None:
            import json

            tdir = store.task_dir(flow_cls.__name__, run_id, step_name, int(task_id))
            with open(os.path.join(tdir, "next.json"), "w") as f:
                json.dump({"target": transition.target}, f)
    dist.barrier("gang-step-done")
    if membership.enabled() and membership.current_generation() > 0:
        # This world was re-formed at least once: torn-down generations
        # left deliberately-leaked runtime threads (dist.membership), so
        # ordinary interpreter teardown is unsafe — their services' exit
        # would race peers' zombie poll threads into a fatal abort. Hand
        # the supervisor a done marker (its forgiveness token for exactly
        # that race), let the leaked-runtime holder (the coordinator)
        # exit LAST, and leave via os._exit.
        me = membership.member_id()
        membership.mark_done(me)
        if membership.holds_leaked_runtime():
            plan = membership.current_plan()
            others = set(plan.roster if plan else ()) - {me}
            membership.await_done(
                others,
                timeout_s=float(knobs.raw("TPUFLOW_KILL_GRACE_S", "5")),
            )
            import time as _time

            _time.sleep(0.2)  # let peers' exits finish closing sockets
        obs.flush()
        sys.stdout.flush()
        os._exit(0)
    dist.shutdown()


if __name__ == "__main__":
    main(sys.argv[1:])
