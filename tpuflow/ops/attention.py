"""Attention implementations with one call signature, selectable per model.

``attention(q, k, v, *, causal, impl)`` with q/k/v shaped (B, T, H, D).
Implementations:

- ``"xla"``   — plain einsum softmax attention; XLA fuses it well for short
  sequences and it runs on any backend (the CPU test mesh included).
- ``"flash"`` — Pallas TPU blockwise (flash) attention kernel, O(T) memory
  (tpuflow.ops.flash_attention).
- ``"ring"``  — ring attention over the 'seq' mesh axis for long-context
  sequence parallelism (tpuflow.parallel.ring_attention): KV blocks rotate
  around the ring via collective-permute while each shard computes blockwise
  attention — the TPU-native long-context strategy (absent from the reference,
  which has no attention at all; SURVEY.md §5 long-context).
- ``"ulysses"`` — all-to-all sequence parallelism over 'seq'
  (tpuflow.parallel.ulysses): all_to_alls swap q/k/v to full-sequence /
  head-sharded layout, attention runs locally with plain causal masking,
  one more all_to_all swaps the output back — 4 collectives per call vs
  ring's s-step rotation.

The reference has no attention op anywhere (its model is an image MLP,
my_ray_module.py:94-112); these exist for the GPT-2 acceptance config and the
framework's first-class long-context support.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def xla_attention(q, k, v, *, causal: bool = True):
    """Reference einsum attention. q,k,v: (B, T, H, D) → (B, T, H, D).

    Softmax statistics in float32 regardless of input dtype (bf16-safe on the
    MXU: the matmuls stay bf16, the normalization doesn't lose precision).
    """
    B, T, H, D = q.shape
    scale = 1.0 / jnp.sqrt(D).astype(q.dtype)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, k.shape[1]), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# The shortest row at which `auto` takes the flash kernels, from chip calls
# 93 and 95 of PR 31 (PERF.md §6; TPU v5e, bf16, causal, heads of 64, the
# kernels against `xla_attention` in one process). The crossover is not a
# length: XLA's attention is fast while the (B, H, T, T) scores stay on the
# chip and slow once they go through HBM, and the kernels' time follows
# B·H·T. Up to 21M score elements XLA is level or ahead, from 42M the kernels
# are (forward + backward at 8,192 tokens of 20 heads: T=512 3.44 against
# 1.30 ms, T=1024 6.64 against 1.59). A threshold on T cannot say that, so
# this is the shortest row at which no measured shape lost, forward alone or
# with its backward: level at one row of 12 to 20 heads, 3.2 to 4.2 times
# ahead from four.
_FLASH_MIN_SEQ = 1024


def resolve_attention_impl(
    impl: str, q_shape, tk: int, *, causal: bool = True,
    backend: str | None = None, v_dim: int | None = None,
) -> str:
    """The whole choice of attention kernel. A named ``impl`` passes
    through. ``'auto'`` is ``'flash'`` exactly when the kernels can run
    this call where it is traced and are known to win there, else
    ``'xla'``: on the TPU backend (off it flash is interpret mode, for
    tests only); on one device (a Mosaic kernel is opaque to the
    partitioner, and jax refuses to lower one inside a program partitioned
    over several devices: "wrap the call in a shard_map"); on a row of at
    least ``_FLASH_MIN_SEQ`` positions; at a shape the kernels tile
    (``flash_tiles``: a 600-token prefill does not tile the 256-row score
    tiles, gpt2-xl's 25 heads of 64 do not pair into 128-lane tiles).
    ``q_shape`` is (B, Tq, H, D) and ``tk`` the K/V length; ``v_dim`` is
    the values' head size where it is not the queries' (latent attention:
    192-wide queries and keys, 128-wide values), which the kernels, one
    head size throughout, do not run. Resolved at trace time: under jit
    the choice is part of the compiled program."""
    if impl != "auto":
        return impl
    backend = backend if backend is not None else jax.default_backend()
    _, tq, h, d = q_shape
    if backend != "tpu" or tq < _FLASH_MIN_SEQ:
        return "xla"
    if v_dim is not None and v_dim != d:
        return "xla"
    from tpuflow.ops.flash_attention import flash_tiles
    from tpuflow.parallel.sharding import active_mesh

    mesh = active_mesh()
    if mesh is not None and mesh.size > 1:
        return "xla"
    return "flash" if flash_tiles(tq, tk, h, d, causal=causal) else "xla"


def attention(q, k, v, *, causal: bool = True, impl: str = "xla"):
    """Dispatch to the selected implementation (see the module docstring;
    ``impl='auto'``: ``resolve_attention_impl``)."""
    impl = resolve_attention_impl(
        impl, q.shape, k.shape[1], causal=causal, v_dim=v.shape[-1]
    )
    if impl == "xla":
        fn = xla_attention
    elif impl == "flash":
        from tpuflow.ops.flash_attention import flash_attention as fn
    elif impl == "ring":
        from tpuflow.parallel.ring_attention import ring_attention as fn
    elif impl == "ulysses":
        from tpuflow.parallel.ulysses import ulysses_attention as fn
    else:
        raise KeyError(
            f"unknown attention impl {impl!r}; use xla|flash|ring|ulysses"
        )
    # One scope whatever the implementation: the profiler's device time
    # under `attn_core` follows attention across a change of kernel.
    with jax.named_scope("attn_core"):
        return fn(q, k, v, causal=causal)
