"""Elastic gang membership: epoch-numbered mesh generations over a file
rendezvous (ISSUE 7).

The classic gang (PR 2) treats a member loss at process-lifecycle
granularity: the supervisor kills the survivors and ``@retry`` requeues
the whole attempt from the last checkpoint. Podracer-style systems treat
preemptible capacity as the normal case instead — this module is the
mechanism that makes the gang *elastic*: on member loss the supervisor
announces a new **mesh generation** (a monotonically numbered plan naming
the surviving roster and a fresh rendezvous address); survivors drain
in-flight work at their next step fence, tear the old ``jax.distributed``
world down, re-rendezvous as the new generation with a shrunk
data-parallel axis, restore from the multi-tier checkpoint (cross-topology
restore is bit-identical), and continue. When capacity returns, a
relaunched member requests to join and the next generation grows the gang
back.

Protocol (all files live in ``TPUFLOW_MEMBERSHIP_DIR``, set by the gang
launcher to a per-step directory on storage every member shares):

- ``plan.json``              — the CURRENT generation plan, written
  atomically by the supervisor. Members poll it (one ``stat`` per step
  fence); a plan whose ``generation`` exceeds the member's current one is
  a pending re-form.
- ``gen_<g>.joined.<m>``     — member ``m`` connected generation ``g``'s
  world (written after a successful re-init; the supervisor's formation
  watch counts these).
- ``join.<m>``               — a relaunched member ``m`` asks to be
  included in the next (grow) generation.
- ``done.<m>``               — member ``m`` finished the step body
  cleanly (exit-ordering handshake + supervisor forgiveness marker).

Member identity is the ORIGINAL gang rank (``TPUFLOW_PROCESS_ID``); it
never changes across generations and keys the heartbeat file, the log
file and the telemetry ``proc``. The *dense* ``jax`` process id of a
generation is the member's index in the sorted roster — so the lowest
surviving member is always the coordinator of every generation (member 0
in practice: coordinator loss falls back to requeue-the-world, see
``flow/runner.py``).

Runtime teardown notes (the part jax does not support out of the box).
The elastic acceptance tests (tests/test_membership.py, slow tier: shrink
on member loss, shrink then regrow, in-process re-form) exercise the
leak-and-``os._exit`` design, the cache clearing and the raise-on-dead-peer
behaviour on the installed jax 0.9.0; the claims about the default
client's abort and the ``missed_heartbeat_callback`` binding come from an
older jax and were not probed again:

- The default distributed client **aborts the process** when the
  coordination service reports a peer death (``client.h:80``) and its
  Python ``missed_heartbeat_callback`` binding is unusable. Elastic gangs
  therefore build the service with an effectively-infinite
  missed-heartbeat budget — failure detection is the supervisor's and
  gloo's job (a dead peer's TCP sockets close instantly, so the blocked
  collective *raises* within milliseconds) — and the client with
  ``shutdown_on_destruction=False``.
- Dropping the Python reference to a client does NOT stop its
  heartbeat/poll threads, and destroying a service that zombie clients
  still poll aborts *them*. Old generations' clients and services are
  therefore **leaked on purpose** (module-level stash, reclaimed at
  process exit); gang members that re-formed exit via ``os._exit`` after
  a done-file handshake in which the service-holding coordinator exits
  last.
- ``xla_bridge._clear_backends()`` misses the ``process_count`` /
  ``local_devices`` lru caches; :func:`_teardown_runtime` clears them
  explicitly or the new generation inherits the old world's shape.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any
from tpuflow.utils import knobs

__all__ = [
    "Generation",
    "MeshReform",
    "MembershipTimeout",
    "enabled",
    "member_id",
    "current_generation",
    "current_plan",
    "pending_reform",
    "reform_after_failure",
    "elastic_initialize",
    "join_generation",
    "quiesce_and_reform",
    "announce",
    "read_plan",
    "joined_members",
    "await_formed",
    "request_join",
    "join_requests",
    "await_plan_including",
    "mark_done",
    "await_done",
    "holds_leaked_runtime",
    "roster_diff",
    "reset",
]

_PLAN_FILE = "plan.json"


class MembershipTimeout(TimeoutError):
    """A rendezvous (formation ack wait, plan wait) missed its deadline —
    the caller falls back to the requeue-the-world verdict."""


@dataclasses.dataclass(frozen=True)
class Generation:
    """One epoch of gang membership: who is in the world and where it
    rendezvouses. ``roster`` holds ORIGINAL member ids; the dense jax
    process id of a member is its index in the sorted roster."""

    generation: int
    roster: tuple[int, ...]
    coordinator: str            # host:port of this generation's rendezvous
    reason: str = "init"        # init | shrink | grow
    deadline: float = 0.0       # unix ts by which the re-form must complete

    def __post_init__(self):
        object.__setattr__(self, "roster", tuple(sorted(self.roster)))

    @property
    def num_processes(self) -> int:
        return len(self.roster)

    def process_id(self, member: int) -> int:
        """Dense jax process id of ``member`` in this generation."""
        return self.roster.index(member)

    def to_json(self) -> dict:
        return {
            "generation": self.generation,
            "roster": list(self.roster),
            "coordinator": self.coordinator,
            "reason": self.reason,
            "deadline": self.deadline,
        }

    @classmethod
    def from_json(cls, obj: dict) -> "Generation":
        return cls(
            generation=int(obj["generation"]),
            roster=tuple(int(m) for m in obj["roster"]),
            coordinator=str(obj["coordinator"]),
            reason=str(obj.get("reason", "init")),
            deadline=float(obj.get("deadline", 0.0)),
        )


class MeshReform(Exception):
    """Control-flow signal raised at a step fence when a new generation is
    pending: the loop must drain, hand state to the checkpoint, and let
    its reform handler tear down + re-rendezvous (mirrors the health
    observatory's ``_RollbackSignal``)."""

    def __init__(self, plan: Generation):
        self.plan = plan
        super().__init__(
            f"mesh re-form to generation {plan.generation} "
            f"({plan.reason}, {plan.num_processes} members)"
        )


def roster_diff(
    old: tuple[int, ...] | list[int], new: tuple[int, ...] | list[int]
) -> tuple[list[int], list[int]]:
    """``(lost, gained)`` members between two rosters."""
    o, n = set(old), set(new)
    return sorted(o - n), sorted(n - o)


# ----------------------------------------------------------- member state
# Per-process view of the current generation, plus the deliberately leaked
# old-generation runtime objects (see the module docstring).
_STATE: dict[str, Any] = {"plan": None, "generation": 0}
_LEAKED: list[Any] = []
_PLAN_CACHE: tuple[float, Generation | None] = (-1.0, None)


def reset() -> None:
    """Forget member-side state (test isolation; leaked runtimes stay
    leaked — they are a process-lifetime commitment)."""
    global _PLAN_CACHE
    _STATE["plan"] = None
    _STATE["generation"] = 0
    _PLAN_CACHE = (-1.0, None)


def membership_dir() -> str | None:
    return knobs.raw("TPUFLOW_MEMBERSHIP_DIR") or None


def enabled() -> bool:
    """Whether this process is a member of an elastic gang."""
    return membership_dir() is not None


def member_id() -> int:
    """This process's ORIGINAL gang rank (stable across generations)."""
    try:
        return int(knobs.raw("TPUFLOW_PROCESS_ID", "0"))
    except ValueError:
        return 0


def current_generation() -> int:
    return int(_STATE["generation"])


def current_plan() -> Generation | None:
    return _STATE["plan"]


def holds_leaked_runtime() -> bool:
    """True when this process stashed old-generation services/clients —
    it must exit LAST (its teardown closes sockets peers may still poll)."""
    return bool(_LEAKED)


# ------------------------------------------------------------- plan files
def _atomic_write(path: str, payload: dict) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)


def announce(mdir: str, plan: Generation) -> None:
    """Supervisor: publish ``plan`` as the current generation (atomic)."""
    os.makedirs(mdir, exist_ok=True)
    _atomic_write(os.path.join(mdir, _PLAN_FILE), plan.to_json())


def read_plan(mdir: str) -> Generation | None:
    try:
        with open(os.path.join(mdir, _PLAN_FILE)) as f:
            return Generation.from_json(json.load(f))
    except (OSError, ValueError, KeyError):
        return None


def pending_reform() -> Generation | None:
    """Member fence check: the current plan when it names a LATER
    generation than the one this process is in, else None. One ``stat``
    per call on the unchanged-plan fast path (the fence cadence)."""
    global _PLAN_CACHE
    mdir = membership_dir()
    if mdir is None:
        return None
    path = os.path.join(mdir, _PLAN_FILE)
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return None
    cached_mtime, cached_plan = _PLAN_CACHE
    if mtime != cached_mtime:
        cached_plan = read_plan(mdir)
        _PLAN_CACHE = (mtime, cached_plan)
    plan = cached_plan
    if plan is None or plan.generation <= current_generation():
        return None
    if member_id() not in plan.roster:
        # The supervisor counted this member out (e.g. it was judged lost
        # while alive). Nothing useful to re-form into.
        return None
    return plan


def reform_after_failure(
    exc: BaseException | None = None, timeout_s: float | None = None
) -> Generation | None:
    """Collective-failure classifier: after a collective raised (a dead
    peer's sockets close instantly, so survivors see e.g. "Gloo ...
    Connection closed by peer" within milliseconds), wait briefly for the
    supervisor — which detects the death on its own poll cadence — to
    announce the re-form plan. Returns the plan (the failure WAS a member
    loss) or None (a genuine error: the caller re-raises ``exc``)."""
    if not enabled():
        return None
    if timeout_s is None:
        timeout_s = float(knobs.raw("TPUFLOW_REFORM_WAIT_S", "10"))
    deadline = time.monotonic() + max(timeout_s, 0.0)
    while True:
        plan = pending_reform()
        if plan is not None:
            return plan
        if time.monotonic() >= deadline:
            return None
        time.sleep(0.05)


# ------------------------------------------------------- ack / done files
def _touch(mdir: str, name: str) -> None:
    try:
        os.makedirs(mdir, exist_ok=True)
        _atomic_write(os.path.join(mdir, name), {"ts": time.time()})
    except OSError:
        pass


def _present(mdir: str, prefix: str) -> set[int]:
    out: set[int] = set()
    try:
        names = os.listdir(mdir)
    except OSError:
        return out
    for n in names:
        if n.startswith(prefix) and not n.endswith(".tmp"):
            try:
                out.add(int(n[len(prefix):].partition(".")[0]))
            except ValueError:
                continue
    return out


def joined_members(mdir: str, generation: int) -> set[int]:
    return _present(mdir, f"gen_{generation}.joined.")


def await_formed(
    mdir: str, plan: Generation, *, poll_s: float = 0.05,
    now: Any = time.time,
) -> None:
    """Supervisor: block until every roster member acked joining
    ``plan``'s generation, or raise :class:`MembershipTimeout` at the
    plan's deadline (→ fall back to requeue-the-world)."""
    want = set(plan.roster)
    while True:
        if joined_members(mdir, plan.generation) >= want:
            return
        if plan.deadline and now() > plan.deadline:
            have = sorted(joined_members(mdir, plan.generation))
            raise MembershipTimeout(
                f"generation {plan.generation} missed its re-form deadline:"
                f" joined {have} of {sorted(want)}"
            )
        time.sleep(poll_s)


def request_join(member: int | None = None) -> None:
    """Relaunched member: ask the supervisor for inclusion in the next
    (grow) generation."""
    mdir = membership_dir()
    if mdir is not None:
        _touch(mdir, f"join.{member if member is not None else member_id()}")


def join_requests(mdir: str) -> set[int]:
    return _present(mdir, "join.")


def clear_join_request(mdir: str, member: int) -> None:
    try:
        os.unlink(os.path.join(mdir, f"join.{member}"))
    except OSError:
        pass


def await_plan_including(
    member: int, timeout_s: float, *, poll_s: float = 0.05
) -> Generation:
    """Relaunched member: block until the current plan's roster includes
    ``member`` (the supervisor's grow announcement)."""
    mdir = membership_dir()
    if mdir is None:
        raise MembershipTimeout("no membership dir")
    deadline = time.monotonic() + timeout_s
    while True:
        plan = read_plan(mdir)
        if plan is not None and member in plan.roster:
            return plan
        if time.monotonic() > deadline:
            raise MembershipTimeout(
                f"no generation included member {member} within "
                f"{timeout_s:.0f}s"
            )
        time.sleep(poll_s)


def mark_done(member: int | None = None) -> None:
    """Member: the step body finished cleanly. Doubles as the supervisor's
    forgiveness marker (post-completion teardown crashes of a re-formed
    member must not fail the step) and the exit-ordering handshake."""
    mdir = membership_dir()
    if mdir is not None:
        _touch(mdir, f"done.{member if member is not None else member_id()}")


def done_members(mdir: str) -> set[int]:
    return _present(mdir, "done.")


def await_done(members: set[int], timeout_s: float) -> bool:
    """Leaked-runtime holder: wait (bounded) for the given members' done
    markers before exiting — its exit closes the old services' sockets,
    which must happen after every zombie-client peer is gone."""
    mdir = membership_dir()
    if mdir is None:
        return True
    deadline = time.monotonic() + timeout_s
    while not members <= done_members(mdir):
        if time.monotonic() > deadline:
            return False
        time.sleep(0.05)
    return True


# -------------------------------------------------- runtime (re)lifecycle
def _distributed_state():
    from jax._src import distributed as jdist

    return jdist.global_state


def elastic_initialize(plan: Generation, *, timeout_s: float = 300.0) -> None:
    """Bring up generation ``plan``'s ``jax.distributed`` world for this
    member with a teardown-capable runtime (see the module docstring):
    the coordinator (dense id 0) hosts a coordination service whose
    missed-heartbeat budget is effectively infinite (failure detection
    belongs to the supervisor + gloo), every member's client skips the
    shutdown-on-destruction barrier. Emits the ``dist.mesh_generation``
    gauge. Single-member generations skip the runtime entirely."""
    from tpuflow import obs

    me = member_id()
    pid = plan.process_id(me)
    gs = _distributed_state()
    if plan.num_processes > 1:
        from jax._src.lib import _jax

        # Heartbeat timeouts are seconds; ~4 months is "never".
        never = 10_000_000
        if pid == 0:
            svc = _jax.get_distributed_runtime_service(
                "[::]:" + plan.coordinator.rsplit(":", 1)[1],
                plan.num_processes,
                heartbeat_timeout=never,
            )
            _LEAKED.append(svc)
            gs.service = svc
        cli = _jax.get_distributed_runtime_client(
            plan.coordinator,
            pid,
            init_timeout=int(max(timeout_s, 1.0)),
            heartbeat_timeout=never,
            shutdown_on_destruction=False,
            use_compression=True,
        )
        cli.connect()
        _LEAKED.append(cli)
        gs.client = cli
    gs.process_id = pid
    gs.num_processes = plan.num_processes
    gs.coordinator_address = plan.coordinator
    _STATE["plan"] = plan
    _STATE["generation"] = plan.generation
    from tpuflow.dist import mesh as _mesh

    _mesh._initialized_multihost = plan.num_processes > 1
    obs.gauge(
        "dist.mesh_generation",
        float(plan.generation),
        members=plan.num_processes,
        reason=plan.reason,
    )


def join_generation(plan: Generation, *, timeout_s: float = 300.0) -> None:
    """Relaunched member: enter ``plan``'s world (fresh process — no old
    runtime to tear down) and ack the join."""
    elastic_initialize(plan, timeout_s=timeout_s)
    mdir = membership_dir()
    if mdir is not None:
        _touch(mdir, f"gen_{plan.generation}.joined.{member_id()}")


def _teardown_runtime() -> None:
    """Abandon the current generation's runtime WITHOUT collective
    shutdown barriers (peers may be dead): stash the client/service so
    their threads keep a live referent (zombie threads outlive the Python
    reference — see module docstring), then clear every backend cache a
    re-initialization consults. All device arrays become invalid; callers
    must have handed state to the checkpoint already."""
    import jax
    from jax._src import xla_bridge

    gs = _distributed_state()
    gs.preemption_sync_manager = None
    if gs.client is not None:
        _LEAKED.append(gs.client)
        gs.client = None
    if gs.service is not None:
        _LEAKED.append(gs.service)
        gs.service = None
    xla_bridge._clear_backends()
    # _clear_backends misses these lru caches; stale entries would make
    # the new generation report the OLD world's process count/devices.
    for cached in ("process_count", "local_devices"):
        fn = getattr(xla_bridge, cached, None)
        if hasattr(fn, "cache_clear"):
            fn.cache_clear()
    jax.clear_caches()
    from tpuflow.dist import mesh as _mesh

    _mesh._initialized_multihost = False


def quiesce_and_reform(plan: Generation) -> None:
    """Member-side re-form: tear the old world down and join ``plan``.

    The caller (the train loop's ``MeshReform`` handler) has already
    drained in-flight work and handed state to the checkpoint — every
    device array dies here. The join is acked for the supervisor's
    formation watch; connect() itself is the rendezvous barrier (it
    retries until the new coordinator's service is up, bounded by the
    plan deadline).

    A single-process world re-forming into a single-process generation
    (the degenerate case in-process tests exercise) keeps its backend:
    there is no distributed runtime to replace, and clearing backends
    would invalidate device arrays held elsewhere in the process."""
    timeout = max(plan.deadline - time.time(), 5.0) if plan.deadline else 120.0
    gs = _distributed_state()
    if plan.num_processes == 1 and gs.client is None:
        _STATE["plan"] = plan
        _STATE["generation"] = plan.generation
        from tpuflow import obs

        obs.gauge(
            "dist.mesh_generation",
            float(plan.generation),
            members=1,
            reason=plan.reason,
        )
        mdir = membership_dir()
        if mdir is not None:
            _touch(mdir, f"gen_{plan.generation}.joined.{member_id()}")
        return
    _teardown_runtime()
    join_generation(plan, timeout_s=timeout)
