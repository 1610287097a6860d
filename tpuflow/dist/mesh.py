"""Mesh construction, multi-host gang initialization, and sharding helpers.

Capability parity map (reference `outerbounds/ray-torch-distributed-checkpoint`):

- ``initialize``    ↔ Ray Train's rendezvous + torch.distributed process-group
  init done before the worker loop runs (reference my_ray_module.py:149,177 and
  the @metaflow_ray gang barrier with ``all_nodes_started_timeout``,
  train_flow.py:42). Here it is ``jax.distributed.initialize`` over DCN with an
  initialization timeout.
- ``make_mesh``     ↔ the implicit world of DDP ranks. A named
  ``jax.sharding.Mesh`` with axes ``('data','fsdp','tensor','seq')`` so DP,
  FSDP, tensor and sequence/context parallelism are all layouts on one object.
- ``batch_sharding``/``replicated``/``shard_batch`` ↔ prepare_data_loader's
  rank-sharding + DDP's replicate-and-allreduce (my_ray_module.py:128-135):
  sharding the batch along 'data' while params are replicated makes GSPMD emit
  the gradient all-reduce over ICI inside the jitted step.
- ``barrier``       ↔ the implicit per-epoch barrier in ray.train.report()
  (my_ray_module.py:203).
"""

from __future__ import annotations

import logging
import math
import os
import threading
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from tpuflow.utils import knobs

logger = logging.getLogger("tpuflow.dist")

# Canonical mesh axis names. DP shards batches on 'data'; FSDP shards params &
# optimizer state on ('data','fsdp'); tensor parallelism shards weight matrices
# on 'tensor'; ring/all-to-all sequence parallelism shards the sequence
# dimension on 'seq'.
AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_TENSOR = "tensor"
AXIS_SEQ = "seq"
AXIS_EXPERT = "expert"

_DEFAULT_AXES = (AXIS_DATA, AXIS_FSDP, AXIS_TENSOR, AXIS_SEQ, AXIS_EXPERT)

_initialized_multihost = False


def platform_is_cpu() -> bool:
    """Whether this process is configured for XLA:CPU, decided from the
    platform selection alone (``jax.config.jax_platforms``, else
    ``JAX_PLATFORMS``) and never by initializing a backend: callers run
    before the first device touch on purpose (the gang launcher must not
    take the chip its members need; libtpu flags are staged pre-init).
    No selection means JAX's default platform — the accelerator where
    there is one — and reports False."""
    selected = jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS", "")
    return selected.split(",")[0].strip().lower() == "cpu"


# The one persistent compile cache of a checkout, used when
# JAX_COMPILATION_CACHE_DIR does not place it elsewhere. JAX hashes the
# cache directory into every entry's key, so a directory that moves (a
# fresh TPUFLOW_HOME, a run id, a temp name) never hits: it is a fixed
# path beside the package, git-ignored, read by nothing but JAX.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".compile_cache",
)


# JAX's own monitoring events: a jaxpr's trace, its lowering to an MLIR
# module, and one backend compile-or-load, inside which the persistent
# cache says hit or miss.
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_cache_outcome = threading.local()  # the compiling thread's last hit/miss
_compile_listener_on = False


def _on_cache_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _cache_outcome.hit = True
    elif event == _CACHE_MISS:
        _cache_outcome.hit = False


def _on_compile_stage(event: str, duration_secs: float, **kw) -> None:
    """One span per stage JAX times on the way to an executable: ``trace``
    (a jaxpr traced: a jitted function, or a primitive's own jit inside
    it, so traces nest), ``lower`` (a jaxpr to an MLIR module) and
    ``compile`` (a backend compile, or its load from the persistent cache,
    with whether the cache served it: None where it was not asked). Each
    carries the program's name as JAX gives it and is parented to the
    span open on the thread that did the work."""
    if event not in (_TRACE, _LOWER, _BACKEND_COMPILE):
        return
    if event == _BACKEND_COMPILE:
        hit = getattr(_cache_outcome, "hit", None)
        _cache_outcome.hit = None
    from tpuflow import obs
    from tpuflow.obs.recorder import ended_span

    rec = obs.recorder()
    if rec is None:
        return
    program = kw.get("fun_name")
    if event == _TRACE:
        rec.record("span", "trace", **ended_span(duration_secs), program=program)
    elif event == _LOWER:
        rec.record("span", "lower", **ended_span(duration_secs), program=program)
    else:
        rec.record(
            "span", "compile", **ended_span(duration_secs), cache_hit=hit,
            program=program,
        )


def _register_compile_listener() -> None:
    """Once per process: traces, lowerings and compiles (and loads from
    the persistent cache) become spans of the recorder whenever it is
    on."""
    global _compile_listener_on
    if _compile_listener_on:
        return
    _compile_listener_on = True
    jax.monitoring.register_event_listener(_on_cache_event)
    jax.monitoring.register_event_duration_secs_listener(_on_compile_stage)


def maybe_enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; return its directory.

    The first compile of a training step on a TPU costs tens of seconds;
    with the persistent cache every LATER process (retry attempt, resumed
    run, the eval flow, a restarted server) loads the executable instead.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses that
    directory: nothing is set in code and that path is returned. Where it
    is not, the cache is ``COMPILE_CACHE_DIR``. ``TPUFLOW_COMPILE_CACHE=0``
    (false/off) disables. Returns None when disabled. Safe to call any
    number of times and before or after backend init — every train entry
    point calls it, so the cache is on without caller wiring.

    CPU platforms are excluded: jaxlib's XLA:CPU AOT loader re-checks
    LLVM machine features when it deserializes a cached executable, and
    XLA's tuning pseudo-features (+prefer-no-scatter/+prefer-no-gather)
    never appear in the host feature probe — reloads warn about a machine
    mismatch and can abort the process outright (observed: deterministic
    SIGABRT in the pipeline-parallel acceptance test when its step
    reloaded from cache). CPU compiles are seconds, so the cache buys
    nothing there; ``TPUFLOW_COMPILE_CACHE_CPU=1`` force-enables it.
    """
    _register_compile_listener()
    if not knobs.get_bool("TPUFLOW_COMPILE_CACHE"):
        return None
    if platform_is_cpu() and knobs.raw("TPUFLOW_COMPILE_CACHE_CPU") != "1":
        return None
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


# libtpu scheduling flags that let the TPU compiler's latency-hiding
# scheduler run collectives ASYNCHRONOUSLY and slide them behind compute
# (ISSUE 10 comm/compute overlap — the other half of the per-microbatch
# reduce-scatter the accumulation scan issues; without these the
# collective still serializes after its producer). The MaxText-style
# staging: appended to LIBTPU_INIT_ARGS, which libtpu reads ONCE at
# backend init — call any time before the first jax device touch.
_ASYNC_COLLECTIVE_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_all_gather=true",
)


def maybe_enable_async_collectives() -> bool:
    """Stage the async-collective libtpu flags into ``LIBTPU_INIT_ARGS``.

    Returns True when the flags are staged and will apply. No-op —
    returns False — on CPU platforms (libtpu never loads; the env var
    would be inert noise in test processes) and under
    ``TPUFLOW_COMM_OVERLAP=0`` (the same knob that turns off the
    per-microbatch reduce-scatter in ``train.step.make_train_step``, so
    one switch governs the whole overlap story). Flags already present —
    e.g. an operator's own LIBTPU_INIT_ARGS — are never duplicated or
    overridden: an explicit
    ``--xla_tpu_enable_async_collective_fusion=false`` wins.

    libtpu reads the variable ONCE, at backend init. Entry points call
    this before their first device touch (the flow CLI in
    ``flow.runner.main``, gang members in ``flow.gang_exec``); a call
    that finds a backend already up with flags still to add leaves the
    environment alone, says so in the log, and returns False.
    """
    if knobs.raw("TPUFLOW_COMM_OVERLAP", "1").lower() in (
        "0", "false", "off",
    ):
        return False
    if platform_is_cpu():
        return False
    current = os.environ.get("LIBTPU_INIT_ARGS", "")
    # A flag the operator already took a position on is left as it is.
    added = [
        flag for flag in _ASYNC_COLLECTIVE_FLAGS
        if flag.split("=", 1)[0] not in current
    ]
    if added and jax._src.xla_bridge.backends_are_initialized():
        logger.warning(
            "async-collective libtpu flags NOT applied: a JAX backend was "
            "initialized before they could be staged (call "
            "dist.maybe_enable_async_collectives() before the first "
            "device touch)"
        )
        return False
    if added:
        os.environ["LIBTPU_INIT_ARGS"] = " ".join(
            ([current] if current else []) + added
        )
    return True


def force_cpu_platform(n_devices: int = 8, *, exact: bool = False) -> None:
    """Select an n-device host-CPU JAX platform, if backends aren't up yet.

    Shared bootstrap for every entry point that must not touch real chips
    (tests, dryruns, CPU drives, gang subprocesses): sets the platform env
    var for child processes, then applies the config updates that take
    effect before backend initialization. ``exact`` pins the device count
    even when the inherited config asks for more (gang subprocesses own a
    fixed per-process slice of the virtual world). If a backend is already
    initialized the updates are skipped silently — callers that need a
    device-count guarantee should assert on ``len(jax.devices())``.
    """
    try:
        jax.config.update("jax_platforms", "cpu")
        if exact or jax.config.jax_num_cpu_devices < n_devices:
            jax.config.update("jax_num_cpu_devices", n_devices)
    except RuntimeError:
        # Backends already initialized: leave the parent's platform AND the
        # env untouched so subprocesses don't silently diverge from it.
        return
    os.environ["JAX_PLATFORMS"] = "cpu"


def is_initialized() -> bool:
    """True if multi-host ``jax.distributed`` was initialized by us."""
    return _initialized_multihost


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    *,
    timeout_s: float = 300.0,
) -> None:
    """Gang-initialize the multi-host runtime (no-op for a single process).

    Parity: the @metaflow_ray cluster formation barrier with
    ``all_nodes_started_timeout=60*5`` (reference train_flow.py:42) — all
    processes must join within ``timeout_s`` or initialization fails (and the
    flow layer's retry wrapper reruns the step).

    Arguments may also come from the standard env vars consumed by
    ``jax.distributed.initialize`` (auto-detection on TPU pod slices).
    """
    global _initialized_multihost
    if _initialized_multihost:
        return
    env_world = knobs.raw("TPUFLOW_NUM_PROCESSES")
    if num_processes is None and env_world is not None:
        num_processes = int(env_world)
        coordinator_address = coordinator_address or knobs.raw(
            "TPUFLOW_COORDINATOR", "127.0.0.1:42042"
        )
        process_id = (
            process_id
            if process_id is not None
            else int(knobs.raw("TPUFLOW_PROCESS_ID", "0"))
        )
    if (
        num_processes is not None
        and num_processes > 1
        and knobs.raw("TPUFLOW_MEMBERSHIP_DIR")
    ):
        # Elastic gang (ISSUE 7): generation 0 comes up through the
        # membership runtime — a teardown-capable client/service pair —
        # so a later member loss can re-form the mesh in place instead of
        # requeueing the world. Same rendezvous semantics, same timeout.
        from tpuflow.dist import membership

        plan = membership.Generation(
            generation=0,
            roster=tuple(range(num_processes)),
            coordinator=coordinator_address or "127.0.0.1:42042",
            reason="init",
        )
        membership.elastic_initialize(plan, timeout_s=timeout_s)
        _initialized_multihost = True
        logger.info(
            "elastic gang initialized: process %d/%d (generation 0)",
            jax.process_index(),
            jax.process_count(),
        )
        return
    if num_processes is None or num_processes <= 1:
        if num_processes is None and _looks_multihost():
            # Real pod slice with no explicit config: let jax auto-detect the
            # cluster (TPU metadata / Cloud env) rather than silently running
            # N disconnected single-host jobs.
            jax.distributed.initialize(initialization_timeout=int(timeout_s))
            _initialized_multihost = True
            return
        # Single-process (possibly multi-device) — nothing to rendezvous.
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        initialization_timeout=int(timeout_s),
    )
    _initialized_multihost = True
    logger.info(
        "gang initialized: process %d/%d, %d global devices",
        jax.process_index(),
        jax.process_count(),
        jax.device_count(),
    )


def _looks_multihost() -> bool:
    """Heuristic: are we one worker of a multi-host TPU pod slice? Checked
    only when the caller gave no explicit gang config."""
    for var in ("TPU_WORKER_ID", "CLOUD_TPU_TASK_ID", "MEGASCALE_SLICE_ID"):
        if var in os.environ:
            hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
            return "," in hostnames or var != "TPU_WORKER_ID"
    return False


def shutdown() -> None:
    """Tear down the multi-host runtime if we started it.

    An elastic gang that re-formed at least once never reaches here — its
    members exit via the membership done-handshake + ``os._exit`` (zombie
    runtime threads from torn-down generations make ordinary interpreter
    teardown unsafe; see ``dist.membership``). A generation-0 elastic
    world shuts down like any other: every member is alive, so the
    client's shutdown barrier completes normally."""
    global _initialized_multihost
    if _initialized_multihost:
        jax.distributed.shutdown()
        _initialized_multihost = False


def process_index() -> int:
    """This host's rank (↔ get_world_rank at host granularity)."""
    return jax.process_index()


def process_count() -> int:
    """Number of host processes in the gang."""
    return jax.process_count()


def make_mesh(
    axes: Mapping[str, int] | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a named device mesh.

    ``axes`` maps axis name → size; a size of ``-1`` (at most one) is inferred
    from the device count. Default: all devices on the 'data' axis — the pure
    data-parallel layout matching the reference's DDP world
    (reference my_ray_module.py:240-243 ScalingConfig(num_workers)).

    Unlisted canonical axes are appended with size 1 so sharding rules that
    mention e.g. 'fsdp' or 'tensor' always resolve against any tpuflow mesh.
    """
    devices = list(devices if devices is not None else jax.devices())
    ndev = len(devices)
    if axes is None:
        axes = {AXIS_DATA: ndev}
    axes = dict(axes)
    unknown = [k for k, v in axes.items() if v == -1]
    if len(unknown) > 1:
        raise ValueError(f"at most one axis may be -1, got {unknown}")
    known = math.prod(v for v in axes.values() if v != -1)
    if unknown:
        if ndev % known:
            raise ValueError(f"{ndev} devices not divisible by {known}")
        axes[unknown[0]] = ndev // known
    total = math.prod(axes.values())
    if total != ndev:
        raise ValueError(
            f"mesh {dict(axes)} wants {total} devices but {ndev} are available"
        )
    for name in _DEFAULT_AXES:
        axes.setdefault(name, 1)
    names = tuple(axes.keys())
    shape = tuple(axes[n] for n in names)
    try:
        # Topology-aware assignment: on a real slice this lays mesh axes onto
        # the ICI torus (nearest-neighbor links for the inner axes) instead of
        # whatever order the flat device list happens to have.
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(
            shape, devices=devices, allow_split_physical_axes=True
        )
    except Exception:  # non-TPU platforms / unusual topologies
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, names)


def make_hybrid_mesh(
    dcn_axes: Mapping[str, int],
    ici_axes: Mapping[str, int],
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Multi-slice mesh: ``dcn_axes`` partition across TPU slices (traffic
    rides the data-center network), ``ici_axes`` partition within each slice
    (traffic rides the chip interconnect).

    The standard multi-pod recipe — e.g. 2× v5e-16 slices as
    ``make_hybrid_mesh({"data": 2}, {"fsdp": 16})``: the gradient all-reduce
    crosses DCN once per step (bandwidth-tolerant), while FSDP's per-layer
    all-gathers/reduce-scatters stay on ICI (latency-critical) — the axis
    placement SURVEY.md §1's scaling model prescribes. Axis sizes must
    multiply to the slice count and per-slice device count respectively;
    canonical axes missing from either map are appended at size 1 (on the
    ICI side) so every tpuflow sharding rule resolves.

    Slices are identified by ``device.slice_index`` (TPU runtimes expose
    it); on a multi-process CPU gang — the dev-mode analogue of pod
    slices over DCN, where every CPU device reports slice 0 —
    ``device.process_index`` stands in, so one host == one slice and the
    DCN axes partition across the gang's processes. On single-slice or
    CPU platforms a DCN product of 1 degrades to exactly ``make_mesh``
    semantics.
    """
    devices = list(devices if devices is not None else jax.devices())

    def _slice_id(d) -> int:
        return getattr(d, "slice_index", 0) or 0
    dcn_axes = dict(dcn_axes)
    ici_axes = dict(ici_axes)
    overlap = set(dcn_axes) & set(ici_axes)
    if overlap:
        raise ValueError(f"axes {sorted(overlap)} appear in both dcn and ici maps")
    n_slices = math.prod(dcn_axes.values()) if dcn_axes else 1
    if n_slices == 1:
        return make_mesh({**dcn_axes, **ici_axes}, devices=devices)
    if any(v == -1 for v in (*dcn_axes.values(), *ici_axes.values())):
        raise ValueError(
            "-1 axis inference is not supported in multi-slice hybrid "
            "meshes; specify every axis size explicitly"
        )

    slice_ids = sorted({_slice_id(d) for d in devices})
    if len(slice_ids) != n_slices and all(
        getattr(d, "platform", "") == "cpu" for d in devices
    ):
        # Multi-process CPU gang (the dev-mode analogue of pod slices
        # over DCN): every CPU device reports slice_index 0, so the
        # process becomes the slice — one host == one slice, DCN axes
        # partition across the gang's processes.
        def _slice_id(d) -> int:  # noqa: F811 — deliberate rebind
            return getattr(d, "process_index", 0)

        slice_ids = sorted({_slice_id(d) for d in devices})
    if len(slice_ids) != n_slices:
        raise ValueError(
            f"dcn axes {dict(dcn_axes)} want {n_slices} slices but the "
            f"devices span {len(slice_ids)} (slice ids {slice_ids})"
        )
    per_slice = [d for d in devices if _slice_id(d) == slice_ids[0]]
    n_ici = math.prod(ici_axes.values())
    if any(
        sum(1 for d in devices if _slice_id(d) == s) != len(per_slice)
        for s in slice_ids
    ) or n_ici != len(per_slice):
        raise ValueError(
            f"ici axes {dict(ici_axes)} want {n_ici} devices per slice; "
            f"slices are uneven or sized differently"
        )
    for name in _DEFAULT_AXES:
        if name not in dcn_axes:
            ici_axes.setdefault(name, 1)
    names = tuple(dcn_axes.keys()) + tuple(ici_axes.keys())
    shape = tuple(dcn_axes.values()) + tuple(ici_axes.values())
    try:
        from jax.experimental import mesh_utils

        # create_hybrid_device_mesh takes same-length per-axis (ici, dcn)
        # shapes whose elementwise product is the mesh shape: our DCN axes
        # are ici-size 1 and vice versa, giving DCN axes outermost
        # (contiguous slices) and ICI axes laid onto each slice's torus.
        dev_array = mesh_utils.create_hybrid_device_mesh(
            (1,) * len(dcn_axes) + tuple(ici_axes.values()),
            tuple(dcn_axes.values()) + (1,) * len(ici_axes),
            devices=devices,
            allow_split_physical_axes=True,
        )
    except Exception as e:
        # Fallback: group by slice id (outer = DCN), flat order within.
        # Correct slice placement, but the ICI axes lose torus-aware layout
        # — say so instead of silently degrading collective locality.
        logger.warning(
            "create_hybrid_device_mesh failed (%s); falling back to "
            "slice-grouped flat device order — ICI collectives may not be "
            "nearest-neighbor",
            e,
        )
        by_slice = [
            [d for d in devices if _slice_id(d) == s]
            for s in slice_ids
        ]
        dev_array = np.asarray(by_slice).reshape(shape)
    return Mesh(dev_array, names)


def data_axis_size(mesh: Mesh) -> int:
    """Number of data-parallel shards (the reference's world size,
    my_ray_module.py:149)."""
    size = 1
    for name in (AXIS_DATA, AXIS_FSDP):
        if name in mesh.shape:
            size *= mesh.shape[name]
    return size


def batch_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """Sharding for a batch: leading dim split over the data(+fsdp) axes.

    Parity: DistributedSampler's each-rank-sees-1/world slice
    (reference my_ray_module.py:128-129), expressed as a layout instead of a
    sampler wrapper.
    """
    data_axes = tuple(n for n in (AXIS_DATA, AXIS_FSDP) if n in mesh.shape)
    spec = P(data_axes if data_axes else None, *([None] * (ndim - 1)))
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (parity: DDP's replicated parameters and the
    rank-0 broadcast at wrap time, reference my_ray_module.py:135)."""
    return NamedSharding(mesh, P())


def shard_batch(batch, mesh: Mesh):
    """Place a host-local pytree of numpy arrays onto the mesh, sharded on the
    batch dimension.

    Single-process: a plain device_put with the batch sharding. Multi-host:
    each process contributes its local shard
    (``jax.make_array_from_process_local_data``), the TPU-native analogue of
    per-rank DataLoader shards (reference my_ray_module.py:128-129).

    Batches whose leading dim does not divide by the data-shard count (e.g.
    a 2-row debug batch on an 8-way mesh — a case the reference's per-worker
    batch math ``global//num_workers``, my_ray_module.py:230, never produces)
    are REPLICATED instead: every device computes the full batch, the
    data-axis grad reduction averages identical values, so the numerics are
    unchanged and only the parallel speedup is lost. Multi-host raises,
    since a replicated global array cannot be assembled from distinct
    per-host shards.
    """
    nshard = data_axis_size(mesh)
    nproc = jax.process_count()
    # Multi-host: each process feeds its local slice, which must divide by
    # the shards this process contributes (global shards / processes).
    if nproc > 1 and nshard % nproc != 0:
        raise ValueError(
            f"{nshard}-way data sharding cannot be fed evenly by {nproc} "
            "processes; make the mesh data axes a multiple of the process "
            "count"
        )
    local_shards = nshard // nproc if nproc > 1 else nshard

    def _put(x):
        x = np.asarray(x)
        if x.ndim == 0:
            # Scalar leaves (loss weights, epoch ids) have no batch dim.
            sharding = replicated(mesh)
        elif nproc > 1:
            if x.shape[0] % local_shards != 0:
                raise ValueError(
                    f"local batch dim {x.shape[0]} is not divisible by the "
                    f"{local_shards} data shards this process contributes "
                    f"({nshard}-way sharding over {nproc} processes); pad "
                    "the batch (see data.ShardedLoader) or shrink the mesh"
                )
            sharding = batch_sharding(mesh, x.ndim)
        elif x.shape[0] % nshard != 0:
            if (x.shape[0], nshard) not in _warned_replicate:
                _warned_replicate.add((x.shape[0], nshard))
                logger.warning(
                    "batch dim %d not divisible by %d-way data sharding; "
                    "replicating (correct but unparallelized)",
                    x.shape[0],
                    nshard,
                )
            sharding = replicated(mesh)
        else:
            sharding = batch_sharding(mesh, x.ndim)
        if nproc > 1:
            return jax.make_array_from_process_local_data(sharding, x)
        return jax.device_put(x, sharding)

    return jax.tree_util.tree_map(_put, batch)


_warned_replicate: set = set()


def replicate(tree, mesh: Mesh):
    """Place a pytree fully-replicated on the mesh (parity: DDP's replicated
    params + rank-0 broadcast at wrap time, reference my_ray_module.py:135).
    Also normalizes mixed/committed device placements after a restore."""
    sharding = replicated(mesh)
    if jax.process_count() == 1:
        return jax.device_put(tree, sharding)

    # Multi-host: device_put rejects shardings that span non-addressable
    # (remote-host) devices. Host leaves become global replicated arrays
    # from the identical per-process copies (same mechanism shard_batch
    # uses for scalar leaves); already-global arrays — e.g. a multi-host
    # restore's output — reshard through a jitted identity, which XLA
    # lowers to whatever collective the move needs.
    def place(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            return jax.jit(lambda a: a, out_shardings=sharding)(x)
        return jax.make_array_from_process_local_data(sharding, np.asarray(x))

    return jax.tree_util.tree_map(place, tree)


def serialize_steps() -> bool:
    """True when a hot loop must block each step before dispatching the next.

    XLA:CPU's collective rendezvous (rendezvous.cc) *terminates the
    process* when a participant thread fails to arrive within 40 s. On an
    oversubscribed host-CPU simulation (8 virtual devices on a 1-core dev
    box) asynchronously queued train-step programs plus the Python
    dispatch loop starve the per-device executor threads long enough to
    trip exactly that: the first epoch of the MLP flow died with
    "Expected 8 threads to join the rendezvous, but only 7 of them
    arrived" at op_id=1. Blocking per step keeps at most one collective
    program in flight and parks the Python thread, which is precisely
    the regime every test and bench leg already runs green. Accelerator
    platforms return False and keep fully async dispatch.
    """
    return jax.default_backend() == "cpu" and len(jax.devices()) > 1


def step_fence(x):
    """Block on ``x`` when :func:`serialize_steps` says the platform needs
    serialized dispatch; a no-op pass-through on accelerators. Hot loops
    call this unconditionally on each step's output so the decision (and
    its rationale, above) lives in exactly one place."""
    if serialize_steps():
        jax.block_until_ready(x)
    return x


def barrier(name: str = "tpuflow") -> None:
    """Block until all processes reach this point (parity: the collective
    behavior of ray.train.report, reference my_ray_module.py:203-205)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)
