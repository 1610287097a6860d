"""Distributed communication backend facade (mesh, gang init, shardings).

TPU-native replacement for the reference stack's NCCL/Gloo + torch.distributed
process-group runtime (exercised at reference my_ray_module.py:135,149,177 via
ray.train.torch.prepare_model / get_context): rendezvous is
``jax.distributed.initialize`` over DCN, collectives are XLA's over ICI, and
data-parallel gradient allreduce is emitted by the compiler from shardings —
there is no user-visible collective API, same encapsulation as the reference.
"""

from tpuflow.dist import membership
from tpuflow.dist.membership import Generation, MembershipTimeout, MeshReform
from tpuflow.dist.mesh import (
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_SEQ,
    AXIS_TENSOR,
    COMPILE_CACHE_DIR,
    barrier,
    batch_sharding,
    data_axis_size,
    force_cpu_platform,
    initialize,
    is_initialized,
    maybe_enable_async_collectives,
    maybe_enable_compile_cache,
    make_hybrid_mesh,
    make_mesh,
    platform_is_cpu,
    process_count,
    process_index,
    replicate,
    replicated,
    serialize_steps,
    step_fence,
    shard_batch,
    shutdown,
)

__all__ = [
    "AXIS_DATA",
    "Generation",
    "MembershipTimeout",
    "MeshReform",
    "membership",
    "AXIS_EXPERT",
    "AXIS_FSDP",
    "AXIS_SEQ",
    "AXIS_TENSOR",
    "COMPILE_CACHE_DIR",
    "barrier",
    "batch_sharding",
    "data_axis_size",
    "force_cpu_platform",
    "initialize",
    "is_initialized",
    "make_hybrid_mesh",
    "maybe_enable_async_collectives",
    "maybe_enable_compile_cache",
    "make_mesh",
    "platform_is_cpu",
    "process_count",
    "process_index",
    "replicate",
    "replicated",
    "serialize_steps",
    "step_fence",
    "shard_batch",
    "shutdown",
]
