"""Ring attention: sequence/context parallelism over the 'seq' mesh axis.

Long-context strategy (first-class per the framework mandate; absent from the
reference, which has no attention — SURVEY.md §5): the sequence dimension of
q/k/v is sharded over 'seq'. Each shard keeps its Q block resident and
computes blockwise (online-softmax) attention against the KV block it
currently holds, then rotates KV around the ring with
``jax.lax.ppermute`` — after ``seq`` steps every Q block has seen every KV
block, with peak memory O(T/shards) per device and the permute riding
nearest-neighbor ICI links. Causality is applied from global block offsets;
the update math matches tpuflow.ops.blockwise_attention exactly, so ring
output equals single-device attention bit-for-near-bit.

Differentiable end-to-end (pure jnp + ppermute inside shard_map), so it
drops into the training step as ``attn_impl='ring'`` on GPT2Config.
"""

from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tpuflow.dist import AXIS_DATA, AXIS_FSDP, AXIS_SEQ

_NEG_INF = -1e30


def _ring_shard_fn(q, k, v, *, causal: bool, axis_name: str):
    """Per-shard body (inside shard_map). q,k,v: (B, T_local, H, D)."""
    B, Tl, H, D = q.shape
    size = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    q32 = q.astype(jnp.float32)
    q_pos = my_idx * Tl + jnp.arange(Tl)

    def body(carry, step):
        m, l, acc, k_cur, v_cur = carry
        src_idx = (my_idx - step) % size  # whose KV block we hold now
        k_pos = src_idx * Tl + jnp.arange(Tl)
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q32, k_cur.astype(jnp.float32)
        ) * scale
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_cur.astype(jnp.float32)
        )
        # Rotate KV to the next ring neighbor (nearest-neighbor ICI hop).
        perm = [(i, (i + 1) % size) for i in range(size)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (m_new, l_new, acc_new, k_nxt, v_nxt), None

    m0 = jnp.full((B, H, Tl), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tl), jnp.float32)
    acc0 = jnp.zeros((B, H, Tl, D), jnp.float32)
    (m, l, acc, _, _), _ = jax.lax.scan(
        body, (m0, l0, acc0, k, v), jnp.arange(size)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _current_mesh():
    from tpuflow.parallel.sharding import active_mesh

    mesh = active_mesh()
    if mesh is None:
        raise RuntimeError(
            "ring_attention needs an active mesh: run under `with mesh:` or "
            "`jax.set_mesh(mesh)` (Trainer.fit does this automatically), or "
            "pass mesh= explicitly"
        )
    return mesh


def ring_attention(q, k, v, *, causal: bool = True, axis_name: str = AXIS_SEQ,
                   mesh=None):
    """Sequence-parallel attention. q,k,v: (B, T, H, D) with T sharded over
    ``axis_name``; output sharded the same way. Requires T % seq_shards == 0.
    With a trivial 'seq' axis (size 1) this degrades to blockwise attention
    in one shard — same math, no communication.
    """
    mesh = mesh if mesh is not None else _current_mesh()
    seq_shards = mesh.shape.get(axis_name, 1)
    if seq_shards == 1 or q.shape[1] % seq_shards:
        # Trivial ring, or T not divisible by the ring size: same math with
        # no rotation — blockwise attention (GSPMD lays it out from the
        # ambient shardings). Defined behavior instead of a shard_map error.
        from tpuflow.ops.flash_attention import blockwise_attention

        return blockwise_attention(q, k, v, causal=causal)
    return seq_shard_map(
        lambda q, k, v: _ring_shard_fn(
            q, k, v, causal=causal, axis_name=axis_name
        ),
        mesh,
        axis_name,
        batch=q.shape[0],
    )(q, k, v)


def seq_shard_map(body, mesh, axis_name, *, batch: int):
    """shard_map wrapper shared by the sequence-parallel attentions (ring and
    ulysses): batch dim over the data-like axes when it divides, sequence dim
    over ``axis_name``, heads/head_dim replicated. ``check_vma=False`` —
    the bodies' carries/collectives manage their own device variance."""
    batch_axes = tuple(
        a for a in (AXIS_DATA, AXIS_FSDP) if mesh.shape.get(a, 1) > 1
    )
    batch_size = (
        int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    )
    if batch_axes and batch % batch_size != 0:
        batch_axes = ()  # e.g. model.init traces with batch 1: replicate it
    spec = P(batch_axes if batch_axes else None, axis_name, None, None)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
