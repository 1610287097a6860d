"""Flow execution engine + CLI.

Drives a FlowSpec DAG the way the Metaflow runtime drives the reference's
(train_flow.py, eval_flow.py): steps execute in transition order from
``start`` to ``end``; ``@retry`` reruns failures; gang steps
(``num_parallel>1`` or ``@tpu``) launch N host processes that form one
``jax.distributed`` world with a formation timeout, only the head process
persisting artifacts (the reference's @metaflow_ray head/worker split,
train_flow.py:42 + the tolerant join at train_flow.py:85-88); completed runs
append trigger events consumed by ``--triggered`` downstream flows
(eval_flow.py:19,42). CLI: ``run`` / ``show`` / ``deploy`` / ``trigger``
mirroring the reference runbook (README.md:10-45)."""

from __future__ import annotations

import inspect
import json
import os
import pickle
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import traceback
from typing import Any

from tpuflow import obs
from tpuflow.flow import store
from tpuflow.flow.cards import CardBuffer
from tpuflow.flow.client import Run
from tpuflow.flow.spec import FlowSpec, current
from tpuflow.utils.preempt import REQUEUE_EXIT_CODE
from tpuflow.utils import knobs


class StepFailed(Exception):
    pass


class StepPreempted(StepFailed):
    """A gang member exited with the requeue code (preemption drain): the
    step should rerun without consuming the @retry budget."""


class GangRefused(StepFailed):
    """The local launcher will not start this gang (see ``_exec_gang``);
    a configuration error, so @retry does not rerun it."""


# Injectable time sources: tests pin the jitter and capture the sleeps so
# backoff behavior is provable without real waiting (tier-1 has no sleeps).
_sleep = time.sleep
_random = random.random

# Supervisor poll cadence: bounds added per-gang-step latency while keeping
# fail-fast reaction in tens of milliseconds.
_GANG_POLL_S = 0.05


def _backoff_delay(
    attempt: int, backoff_s: float, max_backoff_s: float
) -> float:
    """Exponential backoff with 0.5–1.0 jitter for retry ``attempt`` (1-based)."""
    base = min(max_backoff_s, backoff_s * (2.0 ** (attempt - 1)))
    return base * (0.5 + 0.5 * _random())


class _GangInput:
    """One gang member's view passed to a join step (↔ metaflow join inputs,
    train_flow.py:83-88: non-head members lack artifacts — accessing them
    raises AttributeError, which the reference's try/except absorbs)."""

    def __init__(self, artifacts: dict[str, Any] | None):
        self._artifacts = artifacts or {}

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        try:
            return self._artifacts[name]
        except KeyError:
            raise AttributeError(f"no artifact {name!r} on this gang member") from None


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _DeviceProfiler:
    """Background sampler of per-device memory stats (↔ @gpu_profile's 1 s
    nvidia-smi polling, train_flow.py:51). Writes profile.json to the task
    dir."""

    def __init__(self, interval: float, out_path: str):
        self.interval = interval
        self.out_path = out_path
        self.samples: list[dict] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        import jax

        while not self._stop.is_set():
            entry: dict[str, Any] = {"ts": time.time(), "devices": []}
            for d in jax.local_devices():
                stats = {}
                try:
                    stats = d.memory_stats() or {}
                except Exception:
                    pass
                entry["devices"].append(
                    {
                        "id": d.id,
                        "bytes_in_use": stats.get("bytes_in_use"),
                        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    }
                )
            self.samples.append(entry)
            self._stop.wait(self.interval)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=5)
        # Record WHAT hardware was sampled, not just how much memory it
        # used: profile.json doubles as on-hardware execution evidence
        # (platform + device kinds), the TPU analogue of @gpu_profile's
        # nvidia-smi header.
        platform = None
        kinds: list[str] = []
        try:
            import jax

            platform = jax.default_backend()
            kinds = [d.device_kind for d in jax.local_devices()]
        except Exception:
            pass
        try:
            with open(self.out_path, "w") as f:
                json.dump(
                    {
                        "interval": self.interval,
                        "platform": platform,
                        "device_kinds": kinds,
                        "samples": self.samples,
                    },
                    f,
                )
        except OSError:
            pass
        # Absorb the sampler into the unified telemetry stream: the memory
        # gauges land beside the step spans so one timeline answers both
        # "where did time go" and "what did HBM do meanwhile".
        if obs.enabled():
            peaks: dict[int, int] = {}
            for entry in self.samples:
                for dev in entry["devices"]:
                    used = dev.get("bytes_in_use")
                    if used is not None:
                        obs.gauge(
                            "device.bytes_in_use", used,
                            ts=entry["ts"], device=dev["id"],
                        )
                    peak = dev.get("peak_bytes_in_use")
                    if peak is not None:
                        peaks[dev["id"]] = max(peaks.get(dev["id"], 0), peak)
            for dev_id, peak in sorted(peaks.items()):
                obs.gauge(
                    "device.peak_bytes_in_use", peak,
                    device=dev_id, platform=platform,
                )


class FlowRunner:
    def __init__(self, flow_cls: type[FlowSpec]):
        self.flow_cls = flow_cls
        self.flow_name = flow_cls.__name__

    # ----------------------------------------------------------------- run
    def run(
        self,
        params: dict[str, Any],
        *,
        triggered: bool = False,
        run_id: int | None = None,
    ) -> str:
        run_id = run_id if run_id is not None else store.new_run_id(self.flow_name)
        rdir = store.run_dir(self.flow_name, run_id)
        os.makedirs(rdir, exist_ok=True)
        from tpuflow.flow.client import default_namespace, get_namespace

        meta = {
            "flow": self.flow_name,
            "run_id": run_id,
            "status": "running",
            # Runs are produced under the active namespace; the client
            # resolves only same-namespace runs (flow.client._check_visible
            # ↔ reference eval_flow.py:32-36). A run is always produced
            # under a CONCRETE namespace — the global (None) scope is
            # read-only, so it falls back to the user default.
            "namespace": get_namespace() or default_namespace(),
            "params": {k: _jsonable(v) for k, v in params.items()},
            "started": time.time(),
            "steps": [],
            "schedule": getattr(self.flow_cls, "__schedule__", None),
            "trigger_on_finish": getattr(
                self.flow_cls, "__trigger_on_finish__", None
            ),
        }
        store.write_run_meta(self.flow_name, run_id, meta)

        flow = self.flow_cls()
        for name, value in params.items():
            setattr(flow, name, value)

        self._trigger_run = None
        if triggered:
            upstream = getattr(self.flow_cls, "__trigger_on_finish__", None)
            if upstream:
                events = [
                    e
                    for e in store.read_events(upstream)
                    if e.get("status") == "success"
                ]
                if events:
                    self._trigger_run = Run(events[-1]["run"])
                    meta["triggered_by"] = events[-1]["run"]

        steps = self.flow_cls.steps()
        if "start" not in steps or "end" not in steps:
            raise ValueError("flow must define 'start' and 'end' steps")

        step_name = "start"
        task_counter = 0
        pathspec = f"{self.flow_name}/{run_id}"
        print(f"[tpuflow] run {pathspec} starting")
        # Telemetry root for this run: the head process records here, gang
        # members inherit it via TPUFLOW_OBS_DIR (one events.p<proc>.jsonl
        # each), and the end-of-run merge produces <rdir>/events.jsonl.
        # TPUFLOW_OBS=0 disables recording entirely (README Observability).
        self._obs_dir = None
        if knobs.raw("TPUFLOW_OBS", "1") not in ("0", "false"):
            self._obs_dir = os.path.join(rdir, "obs")
            obs.configure(self._obs_dir, proc=0)
        run_span = obs.span("flow.run", flow=self.flow_name, run=str(run_id))
        run_span.__enter__()
        ran_gang = False
        try:
            while True:
                fn = steps[step_name]
                task_id = task_counter
                gang = getattr(fn, "__gang__", None)
                transition = getattr(flow, "_next", None)
                num_parallel = 1
                if transition is not None and transition.target == step_name:
                    num_parallel = transition.num_parallel
                if gang and gang.get("num_parallel"):
                    num_parallel = max(num_parallel, gang["num_parallel"])
                task_counter += num_parallel  # gang members own task_id..+N-1
                object.__setattr__(flow, "_next", None)

                retries = getattr(fn, "__retry_times__", 0)
                backoff_s = getattr(fn, "__retry_backoff_s__", 2.0)
                max_backoff_s = getattr(fn, "__retry_max_backoff_s__", 60.0)
                attempt = 0
                requeues = 0
                max_requeues = int(
                    knobs.raw("TPUFLOW_MAX_REQUEUES", "8")
                )
                while True:
                    try:
                        with obs.span(
                            "flow.step", step=step_name, task=task_id,
                            attempt=attempt, num_parallel=num_parallel,
                        ):
                            if num_parallel > 1:
                                ran_gang = True
                                gang_inputs = self._exec_gang(
                                    flow, step_name, run_id, task_id,
                                    num_parallel,
                                    timeout=(gang or {}).get("timeout", 300.0),
                                    stall_timeout=(gang or {}).get(
                                        "heartbeat_timeout"
                                    ),
                                    attempt=attempt + requeues,
                                    min_members=(gang or {}).get(
                                        "min_members"
                                    ),
                                )
                            else:
                                self._exec_local(
                                    flow, fn, step_name, run_id, task_id
                                )
                                # A following join sees this task as a
                                # 1-member gang (num_parallel=1 degenerate
                                # case).
                                gang_inputs = [
                                    _GangInput(dict(flow._artifacts))
                                ]
                        break
                    except StepPreempted:
                        # Preemption is routine, not a failure: the member
                        # drained a checkpoint and asked to be requeued, so
                        # the rerun does not consume the retry budget. A cap
                        # bounds pathological preemption storms.
                        requeues += 1
                        if requeues > max_requeues:
                            raise
                        print(
                            f"[tpuflow] step {step_name} preempted "
                            f"(requeue {requeues}/{max_requeues}), "
                            "relaunching without consuming retry budget"
                        )
                    except GangRefused:
                        raise
                    except Exception:
                        attempt += 1
                        if attempt > retries:
                            raise
                        obs.counter("flow.retry", step=step_name,
                                    attempt=attempt)
                        delay = _backoff_delay(
                            attempt, backoff_s, max_backoff_s
                        )
                        obs.gauge(
                            "flow.retry_backoff_s", delay, step=step_name,
                            attempt=attempt,
                        )
                        print(
                            f"[tpuflow] step {step_name} failed "
                            f"(attempt {attempt}/{retries}), retrying in "
                            f"{delay:.1f}s:\n"
                            f"{traceback.format_exc(limit=3)}"
                        )
                        _sleep(delay)

                meta["steps"].append(
                    {"step": step_name, "head_task": task_id, "tasks": num_parallel}
                )
                store.write_run_meta(self.flow_name, run_id, meta)

                if step_name == "end":
                    break
                transition = getattr(flow, "_next", None)
                if transition is None:
                    raise StepFailed(
                        f"step {step_name!r} did not call self.next(...)"
                    )
                next_name = transition.target
                next_fn = steps[next_name]
                # A join step (2nd positional arg) receives gang inputs.
                if gang_inputs is not None and _takes_inputs(next_fn):
                    object.__setattr__(flow, "_join_inputs", gang_inputs)
                step_name = next_name
        except Exception as e:
            meta["status"] = "failed"
            meta["error"] = repr(e)
            meta["finished"] = time.time()
            run_span.set(status="failed")
            run_span.__exit__(None, None, None)
            self._finalize_obs(rdir, pathspec, meta)
            store.write_run_meta(self.flow_name, run_id, meta)
            print(f"[tpuflow] run {pathspec} FAILED: {e!r}")
            raise
        meta["status"] = "success"
        meta["finished"] = time.time()
        run_span.set(status="success")
        run_span.__exit__(None, None, None)
        # Run registry (ISSUE 16): in-process runs append their headline
        # here, while the recorder is still open so the registry.append
        # event merges into events.jsonl; gang runs already appended
        # from member 0 (gang_exec) and must not double-record.
        if not ran_gang:
            from tpuflow.obs import registry as registry_mod

            registry_mod.maybe_append_live("train")
        self._finalize_obs(rdir, pathspec, meta)
        store.write_run_meta(self.flow_name, run_id, meta)
        store.append_event(
            {"flow": self.flow_name, "run": pathspec, "status": "success"}
        )
        print(f"[tpuflow] run {pathspec} succeeded")
        return pathspec

    def _finalize_obs(self, rdir: str, pathspec: str, meta: dict) -> None:
        """Close the run's recorder, merge gang-worker event files into
        ``<rdir>/events.jsonl``, render the timeline card, and stamp the
        headline summary (``meta["telemetry"]``) plus the training-health
        view (``meta["health"]``, when anything happened) into run.json.
        Telemetry must never fail the run."""
        meta.setdefault("telemetry", {})
        try:
            obs.configure(None)  # flush + close the head recorder
            events = obs.merge_run_events(rdir)
            if not events:
                return
            summary = obs.summarize(events)
            from tpuflow.flow.cards import timeline_card

            buf = CardBuffer()
            timeline_card(buf, events, summary=summary)
            with open(os.path.join(rdir, "timeline.html"), "w") as f:
                f.write(buf.render_html(f"{pathspec} timeline"))
            meta["telemetry"] = summary.get("headline", {})
            health = summary.get("health") or {}
            if (
                health.get("anomalies")
                or health.get("rollbacks")
                or health.get("profiles")
                or health.get("dropped_events")
            ):
                # Only stamped when noteworthy: a clean run's run.json
                # stays as small as before this section existed.
                meta["health"] = health
        except Exception as e:
            print(f"[tpuflow] telemetry finalize failed (ignored): {e!r}")

    # ----------------------------------------------------- single-task exec
    def _exec_local(
        self, flow: FlowSpec, fn, step_name: str, run_id, task_id: int
    ) -> None:
        tdir = store.task_dir(self.flow_name, run_id, step_name, task_id)
        os.makedirs(tdir, exist_ok=True)
        from tpuflow.flow.spec import _Trigger

        current.flow_name = self.flow_name
        current.run_id = str(run_id)
        current.step_name = step_name
        current.task_id = task_id
        current.trigger = (
            _Trigger(self._trigger_run) if getattr(self, "_trigger_run", None) else None
        )
        current.tpu_storage_path = os.path.join(
            store.run_dir(self.flow_name, run_id), "tpu_storage", step_name
        )
        os.makedirs(current.tpu_storage_path, exist_ok=True)
        card_type = getattr(fn, "__card__", None)
        current.card = CardBuffer() if card_type else None

        profile_cfg = getattr(fn, "__device_profile__", None)
        profiler = (
            _DeviceProfiler(
                profile_cfg["interval"], os.path.join(tdir, "profile.json")
            )
            if profile_cfg
            else None
        )
        join_inputs = getattr(flow, "_join_inputs", None)
        if join_inputs is not None:
            object.__setattr__(flow, "_join_inputs", None)
        trace_ctx = None
        if profile_cfg and profile_cfg.get("trace"):
            import contextlib

            import jax

            trace_ctx = contextlib.ExitStack()
            try:
                jax.profiler.start_trace(os.path.join(tdir, "trace"))
                trace_ctx.callback(jax.profiler.stop_trace)
            except Exception:
                trace_ctx = None
        try:
            if profiler:
                with profiler:
                    self._call_step(flow, fn, join_inputs)
            else:
                self._call_step(flow, fn, join_inputs)
            if current.card is not None:
                with obs.span("flow.card_render", step=step_name):
                    with open(os.path.join(tdir, "card.html"), "w") as f:
                        f.write(
                            current.card.render_html(
                                f"{self.flow_name}/{run_id}/{step_name}"
                            )
                        )
            store.save_artifacts(
                self.flow_name, run_id, step_name, task_id, flow._artifacts
            )
        finally:
            if trace_ctx is not None:
                trace_ctx.close()
            current.card = None

    @staticmethod
    def _call_step(flow: FlowSpec, fn, join_inputs) -> None:
        if _takes_inputs(fn):
            fn(flow, join_inputs or [])
        else:
            fn(flow)

    # ------------------------------------------------------------ gang exec
    def _exec_gang(
        self,
        flow: FlowSpec,
        step_name: str,
        run_id,
        task_id: int,
        num_parallel: int,
        *,
        timeout: float,
        stall_timeout: float | None = None,
        attempt: int = 0,
        min_members: int | None = None,
    ) -> list[_GangInput]:
        """Launch N processes running the step body as one jax.distributed
        world (local simulation of the pod-slice gang, SURVEY.md §2b D8),
        then supervise them: fail fast on the first non-zero exit, detect
        hung members via heartbeat staleness, and classify requeue exits
        (preemption drains) separately from crashes.

        Every member inherits this process's environment, so on an
        accelerator platform each would claim ALL local chips and every
        one after the first would fail or hang in backend init. One
        process owns a host's chips (``num_parallel=1`` runs in-process);
        a local gang is the CPU simulation of a multi-host world."""
        force_cpu = env_force_cpu()
        if force_cpu != "1":
            raise GangRefused(
                f"gang step {step_name!r} asks for {num_parallel} local "
                "members on an accelerator platform, where each member "
                "would claim every local chip. Run one process that owns "
                "all local chips (TPUFLOW_N_PARALLEL=1), or simulate the "
                "gang on CPU devices (TPUFLOW_FORCE_CPU=1)."
            )
        tdir = store.task_dir(self.flow_name, run_id, step_name, task_id)
        os.makedirs(tdir, exist_ok=True)
        state_path = os.path.join(tdir, "gang_state.pkl")
        for name, value in flow._artifacts.items():
            # Same contract as the datastore: device tensors never ship by
            # pickle into the gang subprocesses — only Checkpoint handles.
            if not isinstance(value, store.Checkpoint):
                store.reject_device_arrays(name, value)
        with open(state_path, "wb") as f:
            pickle.dump(
                {"artifacts": flow._artifacts, "module": self._flow_module()}, f
            )
        port = _free_port()
        # Elastic gang (ISSUE 7): with TPUFLOW_ELASTIC=1 a member loss no
        # longer kills the survivors — the supervisor announces a mesh
        # re-form through this shared membership dir (cleared per launch:
        # a previous attempt's plan must not leak into this world).
        elastic = (
            knobs.raw("TPUFLOW_ELASTIC") == "1" and num_parallel > 1
        )
        membership_dir = None
        if elastic:
            import shutil

            membership_dir = os.path.join(tdir, "membership")
            shutil.rmtree(membership_dir, ignore_errors=True)
            os.makedirs(membership_dir, exist_ok=True)
        import tpuflow

        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(tpuflow.__file__)))

        def launch_member(
            i: int, *, rejoin: bool = False
        ) -> tuple[subprocess.Popen, Any]:
            # Stale heartbeats from a previous attempt (or a lost member's
            # final stamp) would read as an instant stall — clear before
            # every launch.
            hb_path = os.path.join(tdir, f"heartbeat_{i}")
            try:
                os.unlink(hb_path)
            except FileNotFoundError:
                pass
            env = dict(os.environ)
            env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
            env.update(
                TPUFLOW_NUM_PROCESSES=str(num_parallel),
                TPUFLOW_PROCESS_ID=str(i),
                TPUFLOW_COORDINATOR=f"127.0.0.1:{port}",
                TPUFLOW_GANG_TIMEOUT=str(timeout),
                TPUFLOW_FORCE_CPU=force_cpu,
                TPUFLOW_ATTEMPT=str(attempt),
                TPUFLOW_HEARTBEAT_FILE=hb_path,
            )
            if membership_dir is not None:
                env["TPUFLOW_MEMBERSHIP_DIR"] = membership_dir
            if rejoin:
                # Requeued capacity: the member skips the gen-0 rendezvous
                # and instead requests inclusion in the next (grow)
                # generation. Same TPUFLOW_ATTEMPT as the gang launch so
                # the goodput ledger keeps ONE attempt lane (an in-place
                # resize must not read as a requeue gap).
                env["TPUFLOW_GANG_REJOIN"] = "1"
            if "TPUFLOW_PREEMPT_GRACE_S" not in env:
                # The supervisor SIGKILLs TPUFLOW_KILL_GRACE_S after
                # its SIGTERM — tell members their real termination
                # grace so the drain's emergency-save decision
                # (preempt.emergency_save_advised) counts down from
                # the budget that actually applies here. Deployed,
                # the pod spec sets TPUFLOW_PREEMPT_GRACE_S from
                # terminationGracePeriodSeconds instead.
                env["TPUFLOW_PREEMPT_GRACE_S"] = knobs.raw(
                    "TPUFLOW_KILL_GRACE_S", "5"
                )
            if getattr(self, "_obs_dir", None):
                # Each member records its own events.p<i>.jsonl in the
                # run's obs dir; the end-of-run merge unions them.
                env["TPUFLOW_OBS_DIR"] = self._obs_dir
                env["TPUFLOW_OBS_PROC"] = str(i)
            cmd = [
                sys.executable,
                "-m",
                "tpuflow.flow.gang_exec",
                self._flow_module(),
                self.flow_cls.__name__,
                step_name,
                str(run_id),
                str(task_id + i),
                state_path,
            ]
            log = open(
                os.path.join(tdir, f"gang_{i}.log"), "a" if rejoin else "w"
            )
            try:
                p = subprocess.Popen(
                    cmd, env=env, stdout=log, stderr=subprocess.STDOUT,
                    cwd=os.getcwd(),
                )
            except BaseException:
                log.close()
                raise
            return (p, log)

        procs: list[tuple[subprocess.Popen, Any]] = []
        launched = False
        try:
            for i in range(num_parallel):
                procs.append(launch_member(i))
            launched = True
        finally:
            if not launched:
                # A mid-loop launch failure must not leak already-spawned
                # members or their open log files.
                for p, log in procs:
                    try:
                        p.kill()
                        p.wait(timeout=10)
                    except (OSError, subprocess.TimeoutExpired):
                        pass
                    log.close()
        with obs.span(
            "flow.gang", step=step_name, num_parallel=num_parallel
        ) as gang_span:
            failure = self._supervise_gang(
                procs, tdir, step_name,
                timeout=timeout, stall_timeout=stall_timeout,
                membership_dir=membership_dir,
                launch_member=launch_member if elastic else None,
                min_members=min_members,
            )
            gang_span.set(failed=failure is not None)
        if failure is not None:
            kind, member, detail = failure
            if kind == "preempt":
                raise StepPreempted(
                    f"gang step {step_name!r} preempted (member {member} "
                    f"exited with requeue code {REQUEUE_EXIT_CODE})"
                )
            logs = []
            for i in range(num_parallel):
                lp = os.path.join(tdir, f"gang_{i}.log")
                if os.path.exists(lp):
                    with open(lp) as f:
                        tail = f.read()[-2000:]
                    logs.append(f"--- gang member {i} ---\n{tail}")
            raise StepFailed(
                f"gang step {step_name!r} failed ({detail}):\n"
                + "\n".join(logs)
            )
        # Load head artifacts back into the in-process flow to continue.
        head_artifacts = store.load_artifacts(
            self.flow_name, run_id, step_name, task_id
        )
        for k, v in head_artifacts.items():
            setattr(flow, k, v)
        # Recover the head's self.next(...) transition.
        next_path = os.path.join(tdir, "next.json")
        if os.path.exists(next_path):
            with open(next_path) as f:
                target = json.load(f)["target"]
            flow.next(getattr(flow, target))
        inputs = [_GangInput(head_artifacts)]
        for i in range(1, num_parallel):
            arts = store.load_artifacts(
                self.flow_name, run_id, step_name, task_id + i
            )
            inputs.append(_GangInput(arts))
        return inputs

    def _supervise_gang(
        self,
        procs: list,
        tdir: str,
        step_name: str,
        *,
        timeout: float,
        stall_timeout: float | None,
        membership_dir: str | None = None,
        launch_member=None,
        min_members: int | None = None,
    ):
        """Poll all gang members until they all exit cleanly or one fails.

        Replaces the old sequential ``p.wait()`` join, whose worst case was
        every surviving peer hanging in a dead collective until the flat
        ``timeout + 600`` deadline. Here the first non-zero exit (or a
        heartbeat stall) kills the survivors promptly — SIGTERM (so they
        can drain a checkpoint) escalating to SIGKILL after
        ``TPUFLOW_KILL_GRACE_S``.

        Elastic mode (ISSUE 7, ``membership_dir`` + ``launch_member``
        given): a non-coordinator member loss no longer fails the step —
        the supervisor converts it into a mesh re-form at step-fence
        granularity: ``flow.member_lost`` is recorded, a shrink generation
        is announced through the membership dir, and the survivors drain,
        re-rendezvous and continue. When the lost capacity is requeue-
        eligible (crash or preemption, not a ``member_lost`` fault) the
        member is relaunched and, once it requests inclusion, a grow
        generation re-adds it. Falls back to the classic requeue-the-world
        verdict when the coordinator (member 0) dies, the survivors would
        drop below the min-members floor, a re-form misses its deadline,
        or the resize budget is spent. While a re-form is in flight the
        heartbeat-stall judgment is suspended — quiesce/rendezvous
        legitimately stops step fences, so the re-form deadline (not
        ``TPUFLOW_STALL_TIMEOUT_S``) governs, and ``flow.heartbeat_stall``
        never fingers a draining survivor.

        Returns ``None`` on success or ``(kind, member, detail)`` where
        kind ∈ {"member_failed", "heartbeat_stall", "timeout", "preempt",
        "reform_timeout"}.
        """
        if stall_timeout is None:
            stall_timeout = float(
                knobs.raw("TPUFLOW_STALL_TIMEOUT_S", "600")
            )
        deadline = time.monotonic() + timeout + 600.0
        n = len(procs)
        rcs: list[int | None] = [None] * n
        failure = None
        elastic = membership_dir is not None and launch_member is not None
        roster: set[int] = set(range(n))
        generation = 0
        resizes = 0
        forming: dict | None = None  # in-flight re-form bookkeeping
        formed_at = time.monotonic()
        pending_rejoin: list[int] = []
        awaiting_join: set[int] = set()
        if elastic:
            from tpuflow.dist import membership as _ms
            from tpuflow.testing import faults as _faults

            floor = (
                int(min_members)
                if min_members
                else int(knobs.raw("TPUFLOW_GANG_MIN_MEMBERS", "2"))
            )
            reform_timeout = float(
                knobs.raw("TPUFLOW_REFORM_TIMEOUT_S", "120")
            )
            max_resizes = int(knobs.raw("TPUFLOW_MAX_RESIZES", "8"))
            try:
                # ``member_lost`` faults model PERMANENT capacity loss:
                # their requeue is suppressed so shrink is exercised
                # (``member_exit``'s relaunch exercises re-grow).
                suppressed = {
                    f.rank for f in _faults.matching("member_lost")
                }
            except ValueError:
                suppressed = set()

        def _announce(reason: str) -> None:
            nonlocal forming, generation, resizes
            generation += 1
            resizes += 1
            plan = _ms.Generation(
                generation=generation,
                roster=tuple(sorted(roster)),
                coordinator=f"127.0.0.1:{_free_port()}",
                reason=reason,
                deadline=time.time() + reform_timeout,
            )
            _ms.announce(membership_dir, plan)
            forming = {
                "plan": plan,
                "t0": time.monotonic(),
                "ts": time.time(),
                "from": len(roster) + (1 if reason == "shrink" else -1),
            }
            print(
                f"[tpuflow] gang {reason}: generation {generation} over "
                f"members {sorted(roster)} (deadline "
                f"{reform_timeout:.0f}s)"
            )

        def _elastic_loss(i: int, rc: int) -> None:
            """One roster member exited non-zero: shrink if eligible,
            else fall back to the classic requeue-the-world verdict."""
            nonlocal failure
            survivors = {
                j for j in roster if j != i and rcs[j] is None
            }
            finished_ok = {
                j for j in roster if j != i and rcs[j] == 0
            }
            eligible = (
                i != 0  # the coordinator hosts every generation's service
                and forming is None
                and resizes < max_resizes
                and len(survivors | finished_ok) >= floor
            )
            if not eligible:
                if rc == REQUEUE_EXIT_CODE:
                    failure = ("preempt", i, "requeue")
                    obs.event("flow.preempt", step=step_name, member=i)
                else:
                    failure = (
                        "member_failed", i,
                        f"member {i} exited {rc} (elastic fallback: "
                        f"{'coordinator' if i == 0 else 'floor/budget/in-flight'})",
                    )
                    attrs = {
                        "step": step_name,
                        "member": i,
                        "rc": rc,
                        "log_tail": self._log_tail(tdir, i),
                    }
                    flight = self._member_flight(i)
                    if flight:
                        attrs["flight"] = flight
                    obs.event("flow.member_failed", **attrs)
                return
            roster.discard(i)
            attrs = {
                "step": step_name,
                "member": i,
                "rc": rc,
                "survivors": len(roster),
                "log_tail": self._log_tail(tdir, i),
            }
            flight = self._member_flight(i)
            if flight:
                attrs["flight"] = flight
            obs.event("flow.member_lost", **attrs)
            _announce("shrink")
            if i not in suppressed:
                # Requeued capacity returns: crash and preemption both
                # come back (a preempted pod is rescheduled); a
                # member_lost fault stays gone.
                pending_rejoin.append(i)

        try:
            while True:
                for i, (p, log) in enumerate(procs):
                    if rcs[i] is not None:
                        continue
                    rc = p.poll()
                    if rc is None:
                        continue
                    rcs[i] = rc
                    log.close()
                    if elastic and i in awaiting_join:
                        # The relaunched member died before it could even
                        # request to rejoin: stop waiting for it (the
                        # shrunk gang is already healthy without it).
                        awaiting_join.discard(i)
                        continue
                    if rc == 0 or failure is not None:
                        continue
                    if elastic and i in _ms.done_members(membership_dir):
                        # Post-completion teardown crash of a re-formed
                        # member (leaked old-generation runtimes make
                        # interpreter teardown racy): the step body
                        # finished and its artifacts committed — forgive.
                        rcs[i] = 0
                        continue
                    if elastic and i in roster:
                        _elastic_loss(i, rc)
                    elif elastic:
                        pass  # already counted out of the roster
                    elif rc == REQUEUE_EXIT_CODE:
                        failure = ("preempt", i, "requeue")
                        obs.event(
                            "flow.preempt", step=step_name, member=i
                        )
                    else:
                        failure = (
                            "member_failed", i, f"member {i} exited {rc}"
                        )
                        attrs = {
                            "step": step_name,
                            "member": i,
                            "rc": rc,
                            "log_tail": self._log_tail(tdir, i),
                        }
                        # Crash forensics (ISSUE 6): the dying member
                        # dumped its flight ring before exiting
                        # (unhandled exception, SIGTERM, injected
                        # death) — reference the structured artifact
                        # beside the log tail.
                        flight = self._member_flight(i)
                        if flight:
                            attrs["flight"] = flight
                        obs.event("flow.member_failed", **attrs)
                if failure is not None:
                    break
                if elastic:
                    if forming is not None:
                        plan = forming["plan"]
                        if roster <= _ms.joined_members(
                            membership_dir, plan.generation
                        ):
                            dur = time.monotonic() - forming["t0"]
                            rec = obs.recorder()
                            if rec is not None:
                                rec.record(
                                    "span", "flow.gang_resize",
                                    ts=forming["ts"], dur_s=dur,
                                    step=step_name,
                                    generation=plan.generation,
                                    reason=plan.reason,
                                    from_members=forming["from"],
                                    to_members=len(roster),
                                )
                            # Reset the stall clock: a member's first
                            # post-reform fence may trail a long restore
                            # + recompile; never-stamped members are
                            # never judged.
                            for j in roster:
                                try:
                                    os.unlink(
                                        os.path.join(tdir, f"heartbeat_{j}")
                                    )
                                except OSError:
                                    pass
                            print(
                                f"[tpuflow] gang generation "
                                f"{plan.generation} formed "
                                f"({plan.reason} → {len(roster)} members, "
                                f"{dur:.1f}s)"
                            )
                            forming = None
                            formed_at = time.monotonic()
                        elif time.time() > plan.deadline:
                            failure = (
                                "reform_timeout", None,
                                f"generation {plan.generation} "
                                f"({plan.reason}) missed its "
                                f"{reform_timeout:.0f}s re-form deadline; "
                                "falling back to requeue-the-world",
                            )
                            break
                    if forming is None and pending_rejoin and (
                        # Hold the relaunch until every survivor passed a
                        # step fence in the NEW generation (their
                        # heartbeat files — cleared at formation — exist
                        # again): a grow fence arriving before the shrunk
                        # gang banked any progress makes everyone replay
                        # from scratch, where a deterministic crasher
                        # fires again. Non-stamping step bodies get a
                        # bounded hold instead.
                        all(
                            os.path.exists(
                                os.path.join(tdir, f"heartbeat_{j}")
                            )
                            for j in roster
                            if rcs[j] is None
                        )
                        or time.monotonic() - formed_at
                        > float(
                            knobs.raw("TPUFLOW_REJOIN_HOLD_S", "10")
                        )
                    ):
                        m = pending_rejoin.pop(0)
                        procs[m] = launch_member(m, rejoin=True)
                        rcs[m] = None
                        awaiting_join.add(m)
                    if forming is None and awaiting_join:
                        ready = _ms.join_requests(
                            membership_dir
                        ) & awaiting_join
                        if ready:
                            m = min(ready)
                            awaiting_join.discard(m)
                            _ms.clear_join_request(membership_dir, m)
                            roster.add(m)
                            _announce("grow")
                    if forming is None and all(
                        rcs[j] is not None for j in roster
                    ):
                        break  # every current-roster member finished
                elif all(rc is not None for rc in rcs):
                    break
                reforming = elastic and forming is not None
                if stall_timeout and stall_timeout > 0 and not reforming:
                    # Judge only members that ever stamped: arbitrary step
                    # bodies owe no heartbeats. The member with the OLDEST
                    # stamp is the culprit — its peers went silent later,
                    # blocked in collectives waiting for it. Suspended
                    # while a re-form is in flight: quiesce/rendezvous
                    # stops step fences by design, and the re-form
                    # deadline already bounds that window.
                    now = time.time()
                    stalled: list[tuple[float, int]] = []
                    for i, (p, _log) in enumerate(procs):
                        if rcs[i] is not None or (
                            elastic and i not in roster
                        ):
                            continue
                        try:
                            age = now - os.path.getmtime(
                                os.path.join(tdir, f"heartbeat_{i}")
                            )
                        except OSError:
                            continue
                        if age > stall_timeout:
                            stalled.append((age, i))
                    if stalled:
                        age, culprit = max(stalled)
                        # Heartbeats stamp the member's current step
                        # (ISSUE 6 satellite): report WHERE it stalled,
                        # not just how stale the stamp is.
                        last_step = self._heartbeat_step(tdir, culprit)
                        at = (
                            f" at step {last_step}"
                            if last_step is not None
                            else ""
                        )
                        failure = (
                            "heartbeat_stall", culprit,
                            f"member {culprit} heartbeat stalled "
                            f"{age:.1f}s (> {stall_timeout:.0f}s){at}",
                        )
                        obs.event(
                            "flow.heartbeat_stall", step=step_name,
                            member=culprit, age_s=round(age, 2),
                            last_step=(
                                last_step if last_step is not None else -1
                            ),
                            log_tail=self._log_tail(tdir, culprit),
                        )
                        break
                if time.monotonic() > deadline:
                    failure = (
                        "timeout", None,
                        f"gang deadline exceeded ({timeout:.0f}s + 600s)",
                    )
                    break
                time.sleep(_GANG_POLL_S)
        finally:
            if failure is not None or any(rc is None for rc in rcs):
                # Failure, or success with stragglers (e.g. a relaunched
                # member still waiting for a grow plan the finished gang
                # will never form): reap everything still running.
                self._kill_survivors(procs, rcs)
            for _p, log in procs:
                log.close()  # idempotent
        if failure is None and elastic and resizes:
            print(
                f"[tpuflow] elastic gang step {step_name!r} completed "
                f"after {resizes} resize(s), final generation {generation}"
            )
        if failure is not None and failure[0] == "reform_timeout":
            # The fallback verdict: surface as a plain member failure so
            # @retry requeues the world exactly as with elasticity off.
            return ("member_failed", failure[1], failure[2])
        return failure

    @staticmethod
    def _log_tail(tdir: str, member: int, limit: int = 500) -> str:
        try:
            with open(os.path.join(tdir, f"gang_{member}.log")) as f:
                return f.read()[-limit:]
        except OSError:
            return ""

    def _member_flight(self, member: int) -> str | None:
        """Path of the failed member's flight-recorder dump, if the
        member managed to write one before dying (its crash handlers run
        pre-exit, the supervisor polls post-exit — no race)."""
        obs_dir = getattr(self, "_obs_dir", None)
        if not obs_dir:
            return None
        from tpuflow.obs import flight as flight_mod

        path = flight_mod.flight_path(obs_dir, member)
        return path if os.path.exists(path) else None

    @staticmethod
    def _heartbeat_step(tdir: str, member: int) -> int | None:
        """Last step number the member stamped into its heartbeat file
        (``utils.heartbeat.beat(step=...)``), or None for a step-less /
        absent stamp."""
        try:
            with open(os.path.join(tdir, f"heartbeat_{member}")) as f:
                raw = f.read().strip()
            return int(raw) if raw else None
        except (OSError, ValueError):
            return None

    @staticmethod
    def _kill_survivors(procs: list, rcs: list) -> None:
        """SIGTERM surviving members (their preemption handler drains a
        final checkpoint), escalate to SIGKILL after the grace window."""
        grace = float(knobs.raw("TPUFLOW_KILL_GRACE_S", "5"))
        live = [i for i, rc in enumerate(rcs) if rc is None]
        for i in live:
            try:
                procs[i][0].send_signal(signal.SIGTERM)
            except OSError:
                pass
        t_end = time.monotonic() + grace
        while live and time.monotonic() < t_end:
            live = [i for i in live if procs[i][0].poll() is None]
            if live:
                time.sleep(_GANG_POLL_S)
        for i in live:
            try:
                procs[i][0].kill()
            except OSError:
                pass
        for i, rc in enumerate(rcs):
            if rc is None:
                try:
                    rcs[i] = procs[i][0].wait(timeout=10)
                except (OSError, subprocess.TimeoutExpired):
                    rcs[i] = -9

    def _flow_module(self) -> str:
        mod = inspect.getmodule(self.flow_cls)
        path = getattr(mod, "__file__", None)
        if path is None:
            raise RuntimeError("flow class must live in an importable file")
        return os.path.abspath(path)


def _takes_inputs(fn) -> bool:
    params = list(inspect.signature(fn).parameters)
    return len(params) >= 2 and params[1] not in ("args", "kwargs")


def _jsonable(v):
    try:
        json.dumps(v)
        return v
    except (TypeError, ValueError):
        return str(v)


def env_force_cpu() -> str:
    """``TPUFLOW_FORCE_CPU`` for gang subprocesses: "1" when CPU was
    requested explicitly or this process is configured for it. Decided
    from configuration alone — a launcher that initialized a backend to
    find out would hold the chip."""
    from tpuflow.dist import platform_is_cpu

    forced = knobs.raw("TPUFLOW_FORCE_CPU") == "1"
    return "1" if forced or platform_is_cpu() else "0"


# --------------------------------------------------------------------- CLI
def main(flow_cls: type[FlowSpec], argv: list[str] | None = None):
    argv = list(sys.argv[1:] if argv is None else argv)
    runner = FlowRunner(flow_cls)
    if not argv or argv[0] in ("-h", "--help", "show"):
        _show(flow_cls)
        return None
    cmd, rest = argv[0], argv[1:]
    if cmd in ("run", "trigger"):
        from tpuflow import dist

        # The run uses the platform JAX selects. CPU is an explicit
        # choice (TPUFLOW_FORCE_CPU=1, or an inherited JAX_PLATFORMS=cpu)
        # and comes with 8 virtual devices so sharded layouts execute.
        if env_force_cpu() == "1":
            dist.force_cpu_platform(8)
        # Before the first device touch of the process (the device
        # profiler samples the moment a step starts): libtpu reads its
        # flags once, at backend init.
        dist.maybe_enable_async_collectives()
        # Retry attempts, resumes and the triggered eval flow reload
        # compiled executables instead of compiling again.
        dist.maybe_enable_compile_cache()
    if cmd == "run":
        params, triggered = _parse_params(flow_cls, rest)
        return runner.run(params, triggered=triggered)
    if cmd == "deploy":
        # Materialize the decorator records (@kubernetes/@pypi/@tpu/
        # @schedule) into runnable k8s manifests — the deployer step the
        # reference delegates to `argo-workflows create` (README.md:27-45).
        from tpuflow.flow.deploy import materialize

        out_dir = None
        if "--manifest-dir" in rest:
            i = rest.index("--manifest-dir")
            if i + 1 >= len(rest):
                raise SystemExit("--manifest-dir requires a directory argument")
            out_dir = rest[i + 1]
        if out_dir is None:
            out_dir = os.path.join(
                store.home(), "deployments", flow_cls.__name__
            )
        manifests = materialize(flow_cls, out_dir)
        record = {
            "flow": flow_cls.__name__,
            "schedule": getattr(flow_cls, "__schedule__", None),
            "trigger_on_finish": getattr(flow_cls, "__trigger_on_finish__", None),
            "manifests": manifests,
            "deployed": time.time(),
        }
        path = store.write_deployment(flow_cls.__name__, record)
        print(f"[tpuflow] deployed {flow_cls.__name__}: {record} → {path}")
        for m in manifests:
            print(f"[tpuflow]   manifest: {m}")
        return path
    if cmd == "trigger":
        params, _ = _parse_params(flow_cls, rest)
        return runner.run(params, triggered=True)
    raise SystemExit(f"unknown command {cmd!r}; use run|show|deploy|trigger")


def _parse_params(flow_cls, rest: list[str]):
    specs = flow_cls.parameters()
    by_cli = {}
    for attr, p in specs.items():
        by_cli[p.name.replace("_", "-")] = (attr, p)
        by_cli[p.name] = (attr, p)
    params = {attr: p.default for attr, p in specs.items()}
    triggered = False
    i = 0
    while i < len(rest):
        arg = rest[i]
        if arg == "--triggered":
            triggered = True
            i += 1
            continue
        if not arg.startswith("--"):
            raise SystemExit(f"unexpected argument {arg!r}")
        key = arg[2:]
        if key not in by_cli:
            raise SystemExit(
                f"unknown parameter --{key}; known: "
                + ", ".join(sorted(c for c in by_cli if "-" in c or "_" not in c))
            )
        if i + 1 >= len(rest):
            raise SystemExit(f"--{key} requires a value")
        attr, p = by_cli[key]
        params[attr] = p.parse(rest[i + 1])
        i += 2
    missing = [p.name for a, p in specs.items() if p.required and params[a] is None]
    if missing:
        raise SystemExit(f"missing required parameters: {missing}")
    return params, triggered


def _show(flow_cls) -> None:
    print(f"Flow {flow_cls.__name__}")
    doc = (flow_cls.__doc__ or "").strip()
    if doc:
        print(f"  {doc.splitlines()[0]}")
    print("Steps:")
    for name, fn in flow_cls.steps().items():
        tags = []
        if getattr(fn, "__retry_times__", 0):
            tags.append(f"retry×{fn.__retry_times__}")
        if getattr(fn, "__gang__", None):
            tags.append("gang")
        if getattr(fn, "__card__", None):
            tags.append("card")
        print(f"  {name}{(' [' + ', '.join(tags) + ']') if tags else ''}")
    print("Parameters:")
    for attr, p in flow_cls.parameters().items():
        print(f"  --{p.name.replace('_', '-')} (default {p.default!r}) {p.help}")
