"""Parameter/optimizer sharding rules: DP, FSDP, tensor parallelism.

The reference's only strategy is DDP (replicated params, sharded batch —
my_ray_module.py:135); its acceptance configs add "FSDP → pjit fully-sharded"
(BASELINE.md config 5). Here both are *layouts on the same named mesh*, not
wrappers:

- **DP**: params replicated, batch on ('data','fsdp') — the default of
  tpuflow.dist.
- **FSDP / ZeRO-3**: every param (and its mirrored optimizer moments) sharded
  along its largest divisible dimension over the fsdp(+data) axes; XLA GSPMD
  inserts the all-gathers before use and reduce-scatters for grads.
- **Tensor parallel**: per-layer PartitionSpecs over the 'tensor' axis
  (Megatron-style column/row splits for GPT-2 blocks), composable with FSDP.

Shardings are computed *by leaf path and shape* over the abstract TrainState,
so optimizer state (whose leaves mirror param shapes and paths) is sharded
consistently without optimizer-specific code. ``create_sharded_state`` jits
the init with ``out_shardings`` so parameters are **born sharded** — no
single-host materialization, which is what makes multi-host GPT-2-medium
init and the sharded-checkpoint path work.
"""

from __future__ import annotations

from typing import Callable

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpuflow.dist import (
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_TENSOR,
    data_axis_size,
)


def _path_names(path) -> tuple[str, ...]:
    names = []
    for entry in path:
        if hasattr(entry, "key"):
            names.append(str(entry.key))
        elif hasattr(entry, "name"):
            names.append(str(entry.name))
        elif hasattr(entry, "idx"):
            names.append(str(entry.idx))
    return tuple(names)


def gpt2_tensor_rules(names: tuple[str, ...], shape: tuple[int, ...]):
    """Megatron-style tensor-parallel placements for GPT-2 params (and their
    mirrored optimizer moments — paths contain the same layer names).

    Column-parallel (shard output dim): c_attn qkv, mlp_fc.
    Row-parallel (shard input dim): c_proj, mlp_proj.
    Embeddings: vocab dim sharded. LayerNorms/biases: replicated.
    """
    if not names or len(shape) == 0:
        return None
    leaf = names[-1]
    parent = names[-2] if len(names) >= 2 else ""
    # Dense kernels are (in, out) — or (layers, in, out) when the layer stack
    # is nn.scan'd (GPT2Config.scan_layers): the split dim shifts right.
    if leaf == "kernel" and len(shape) in (2, 3):
        col, row = len(shape) - 1, len(shape) - 2
        if parent in ("c_attn", "mlp_fc"):
            return {col: AXIS_TENSOR}  # column parallel
        if parent in ("c_proj", "mlp_proj"):
            return {row: AXIS_TENSOR}  # row parallel
    if leaf in ("wte", "wpe") and len(shape) == 2:
        return {0: AXIS_TENSOR}
    # MoE expert stacks: w1 (E, C, F) / w2 (E, F, C) — or with a scanned
    # layer stack (L, E, ...). Experts shard over 'expert'; the FFN dim also
    # splits over 'tensor' (column for w1, row for w2), composing EP × TP.
    if parent == "moe" and leaf in ("w1", "w2") and len(shape) in (3, 4):
        expert_dim = len(shape) - 3
        placed = {expert_dim: AXIS_EXPERT}
        placed[len(shape) - 1 if leaf == "w1" else len(shape) - 2] = AXIS_TENSOR
        return placed
    if parent == "moe" and leaf in ("b1", "b2") and len(shape) in (2, 3):
        return {len(shape) - 2: AXIS_EXPERT}
    return None


def make_shardings(
    abstract_tree,
    mesh: Mesh,
    *,
    fsdp: bool = True,
    tensor_rules: Callable | None = None,
    min_shard_elems: int = 2**12,
):
    """Compute a NamedSharding per leaf of ``abstract_tree``.

    Per leaf: apply ``tensor_rules`` (dim → 'tensor' axis) first, then — if
    ``fsdp`` — shard the largest remaining dimension divisible by the fsdp
    world over ('fsdp','data'). Small leaves (< ``min_shard_elems``) and
    scalars stay replicated: gathering tiny tensors costs more than storing
    them.
    """
    fsdp_axes = tuple(a for a in (AXIS_FSDP, AXIS_DATA) if mesh.shape.get(a, 1) > 1)
    fsdp_size = int(np.prod([mesh.shape[a] for a in fsdp_axes])) if fsdp_axes else 1

    def _axis_size(axis) -> int:
        names = axis if isinstance(axis, tuple) else (axis,)
        return int(np.prod([mesh.shape.get(a, 1) for a in names]))

    def one(path, leaf):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        spec: list = [None] * len(shape)
        if shape and int(np.prod(shape)) >= min_shard_elems:
            names = _path_names(path)
            placed = tensor_rules(names, shape) if tensor_rules else None
            if placed:
                # Each rule names its own mesh axis ('tensor', 'expert', …);
                # apply it when that axis exists non-trivially and divides.
                for dim, axis in placed.items():
                    size = _axis_size(axis)
                    if size > 1 and shape[dim] % size == 0:
                        spec[dim] = axis
            if fsdp and fsdp_size > 1:
                # Largest free dim divisible by the fsdp world.
                candidates = [
                    (shape[d], d)
                    for d in range(len(shape))
                    if spec[d] is None and shape[d] % fsdp_size == 0
                ]
                if candidates:
                    _, dim = max(candidates)
                    spec[dim] = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, abstract_tree)


def active_mesh():
    """The mesh of the enclosing ``jax.set_mesh(mesh)`` or legacy
    ``with mesh:`` context, or None outside any."""
    # Under an active jit trace get_mesh() refuses to run; the abstract
    # mesh carries the axis structure (devices are bound at lowering).
    try:
        mesh = jax.sharding.get_mesh()
    except ValueError:
        mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty:
        return mesh
    # Legacy `with mesh:` (what the train legs use): neither call above
    # sees it, only thread_resources, which jax 0.9 still serves through
    # this alias behind a DeprecationWarning.
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        from jax.interpreters.pxla import thread_resources

    mesh = thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def pin_batch(x):
    """Constrain ``x``'s leading (batch) dim to the active mesh's data
    axes, every other dim left to the partitioner; a no-op outside a mesh
    or when the batch does not divide (``model.init`` traces batch 1).

    GSPMD otherwise lets a PARAMETER's layout leak into the activations:
    GPT-2's ``wte`` is 50257 x 768, FSDP cannot split 50257 so it splits
    the hidden axis, ``wte[tokens]`` comes out split on hidden over the
    same mesh axis the batch is split on, and the whole step then runs on
    the full batch on every chip (compiled for v5e 2x2: ``bf16[8,1024,768]``
    x337 and 2.25 GB of temporaries per chip, against ``bf16[2,1024,768]``
    and 0.91 GB with the pin). One pin after the embedding is enough."""
    mesh = active_mesh()
    if mesh is None:
        return x
    shards = data_axis_size(mesh)
    if shards == 1 or x.shape[0] % shards:
        return x
    # The same axes dist.batch_sharding splits a batch over.
    axes = tuple(a for a in (AXIS_DATA, AXIS_FSDP) if a in mesh.shape)
    free = [P.UNCONSTRAINED] * (x.ndim - 1)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(axes, *free))
    )


def has_sharded_leaf(shardings, axis: str | None = None) -> bool:
    """True if any leaf of a shardings pytree is actually partitioned
    (optionally: on the named ``axis``). Guards equivalence checks that
    would pass vacuously if a rules/threshold regression silently returned
    fully replicated shardings (used by tests and the multichip dryrun)."""
    for s in jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec")
    ):
        for part in s.spec:
            if part is None:
                continue
            if axis is None:
                return True
            names = part if isinstance(part, tuple) else (part,)
            if axis in names:
                return True
    return False


def create_sharded_state(
    init_fn: Callable,
    mesh: Mesh,
    *init_args,
    fsdp: bool = True,
    tensor_rules: Callable | None = None,
    materialize: bool = True,
):
    """Initialize a TrainState (or any pytree) *born sharded*.

    ``init_fn(*init_args)`` is evaluated abstractly to compute per-leaf
    shardings, then jitted with those as ``out_shardings`` — each device
    materializes only its shard (the pjit initialization idiom; no
    host-memory spike for GPT-2-medium-sized states).

    Returns (state, shardings). With ``materialize=False`` the init is
    ONLY shape-evaluated — ``state`` is the abstract pytree
    (ShapeDtypeStructs carrying their shardings, so it serves directly
    as a restore template) and nothing executes on devices. Checkpoint
    resumes use this: running the real initializer just to overwrite
    every leaf with restored values doubles startup for nothing (a
    355M-param init materializes ~4 GiB of random weights + zeroed adamw
    moments that the restore immediately discards).
    """
    from tpuflow import obs

    abstract = jax.eval_shape(init_fn, *init_args)
    shardings = make_shardings(
        abstract, mesh, fsdp=fsdp, tensor_rules=tensor_rules
    )
    if not materialize:
        abstract = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            abstract,
            shardings,
        )
        return abstract, shardings
    # Trace, compile-or-load (a `compile` span of its own inside this
    # one) and dispatch of the initializer; its execution is not awaited.
    with obs.span("state.init"):
        state = jax.jit(init_fn, out_shardings=shardings)(*init_args)
    return state, shardings
