"""Pipeline parallelism: GPipe-style microbatch pipeline over a 'stage' mesh
axis, TPU-idiomatic (shard_map + lax.ppermute over ICI neighbors).

The reference exercises no pipeline parallelism (its model is a 3-layer MLP,
my_ray_module.py:94-112; SURVEY.md §2c marks PP absent) — this exists so the
framework's parallelism matrix (dp/fsdp/tp/sp/ep/**pp**) is complete and the
mesh design demonstrably does not preclude it.

Design (SPMD, compiler-friendly):

- The model's repeated blocks are stacked along a leading layer axis (the
  ``scan_layers=True`` parameter layout of ``tpuflow.models.gpt2.GPT2``) and
  sharded over ``stage``: each stage owns ``n_layer / n_stages`` contiguous
  layers. Nothing is "sent" at schedule time except activations.
- The batch is split into M microbatches. One ``lax.scan`` runs
  ``M + S - 1`` ticks; at each tick every stage applies its layer slice to
  its current activation and passes the result to the next stage with a
  single ``lax.ppermute`` — nearest-neighbor ICI traffic, no host logic, no
  dynamic shapes. The first/last ticks are the classic GPipe bubble; their
  garbage activations are masked out of the loss.
- The embedding runs where stage 0 ingests a microbatch and the loss head
  where the last stage emits one; under SPMD every device executes both and
  a ``where(stage_id == ...)`` selects the real value (the textbook
  single-program pipeline; the redundant compute is bubble-shaped and small
  next to the block stack for deep models).
- ``jax.grad`` differentiates straight through: the transpose of
  ``ppermute`` is the reverse permute, so the backward schedule is the
  mirrored pipeline — no hand-written backward pass.

Composes with data parallelism: run on a ``{'data': D, 'stage': S}`` mesh —
the batch shards over 'data', losses psum over both axes.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_STAGE = "stage"


def make_pipeline_loss(
    block_apply: Callable[[Any, jax.Array], tuple[jax.Array, jax.Array]],
    embed: Callable[[Any, jax.Array], jax.Array],
    head_loss: Callable[[Any, jax.Array, jax.Array], jax.Array],
    *,
    mesh: Mesh,
    n_microbatches: int,
    data_axis: str = "data",
) -> Callable[[Any, Any, jax.Array, jax.Array], jax.Array]:
    """Build ``loss(stacked_block_params, other_params, tokens, targets)``.

    - ``block_apply(block_params, x) -> (x, aux)``: one repeated block,
      given one layer's params (a slice of the stacked tree along its
      leading axis), plus a scalar auxiliary loss (0.0 for plain blocks;
      the sown MoE load-balance term for expert blocks).
    - ``embed(other_params, tokens) -> x``: the stage-0 ingress computation.
    - ``head_loss(other_params, x, targets) -> scalar``: the last-stage
      egress computation (mean loss over the microbatch's tokens).

    Auxiliary losses are accumulated per stage only at VALID ticks (stage
    ``s`` processes real microbatch data at ticks ``t in [s, s+M)``; bubble
    and re-ingested activations are masked out), psum'd across stages, and
    averaged over microbatches — the per-microbatch analogue of the
    non-pipelined step's sown-loss sum (train/step.py:101-103).

    The returned callable is jit-compatible and differentiable; its result
    is the mean loss over all microbatches, replicated on every device.
    """
    S = mesh.shape[AXIS_STAGE]
    D = mesh.shape.get(data_axis, 1)
    M = n_microbatches
    if S < 2:
        raise ValueError(f"pipeline needs >=2 stages, mesh has {S}")

    def spmd(blocks_local, other, tokens, targets):
        # blocks_local: this stage's (n_layer/S, ...) slice of every leaf.
        # tokens/targets: this data-shard's (B/D, T) slice.
        sid = jax.lax.axis_index(AXIS_STAGE)
        Bd, T = tokens.shape
        if Bd % M:
            raise ValueError(f"per-data-shard batch {Bd} not divisible by M={M}")
        x_mb = tokens.reshape(M, Bd // M, T)
        y_mb = targets.reshape(M, Bd // M, T)

        def apply_stage(x):
            out, auxs = jax.lax.scan(
                lambda h, lp: block_apply(lp, h), x, blocks_local
            )
            return out, jnp.sum(auxs)

        # Shape/dtype of the inter-stage activation buffer.
        probe = jax.eval_shape(lambda t: embed(other, t), x_mb[0])
        state0 = jnp.zeros(probe.shape, probe.dtype)

        right = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            state, loss_acc, aux_acc = carry
            # Stage 0 ingests microbatch t while ingress ticks remain.
            ingress = embed(other, x_mb[jnp.clip(t, 0, M - 1)])
            x = jnp.where(sid == 0, ingress, state)
            y, aux = apply_stage(x)
            # This stage holds REAL data exactly at ticks [sid, sid+M):
            # before that, bubble zeros; after, re-ingested/stale input.
            stage_valid = (t >= sid) & (t < sid + M)
            aux_acc = aux_acc + jnp.where(stage_valid, aux, 0.0)
            # Last stage emits microbatch t-(S-1) once the pipe has filled.
            emit_t = jnp.clip(t - (S - 1), 0, M - 1)
            mb_loss = head_loss(other, y, y_mb[emit_t])
            valid = (sid == S - 1) & (t >= S - 1)
            loss_acc = loss_acc + jnp.where(valid, mb_loss, 0.0)
            # Hand activations to the right neighbor (ICI nearest-neighbor);
            # stage S-1's output leaves the pipe (no wraparound edge).
            state = jax.lax.ppermute(y, AXIS_STAGE, right)
            return (state, loss_acc, aux_acc), None

        # Accumulators are rank-1 ((1,) not scalar) so each shard's
        # contribution reshapes straight into its (1, 1) grid cell below.
        (_, loss_acc, aux_acc), _ = jax.lax.scan(
            tick,
            (
                state0,
                jnp.zeros((1,), jnp.float32),
                jnp.zeros((1,), jnp.float32),
            ),
            jnp.arange(M + S - 1),
        )
        # Only the last stage accumulated task losses; every stage holds
        # its own layers' aux. Each shard emits its CONTRIBUTION as one
        # cell of an (S, D) grid; the replicated global mean is taken
        # OUTSIDE the shard_map (sum over a sharded array is an ordinary
        # XLA reduction), which transposes cleanly under grad.
        return (loss_acc + aux_acc).reshape(1, 1)

    def loss_fn(stacked_blocks, other, tokens, targets):
        # check_vma=False: the scan carries (activation buffer, loss
        # accumulator) start as replicated zeros and become device-varying
        # on the first tick — intended here, the contributions grid out
        # spec declares the output varying.
        f = shard_map(
            spmd,
            mesh=mesh,
            in_specs=(P(AXIS_STAGE), P(), P(data_axis), P(data_axis)),
            out_specs=P(AXIS_STAGE, data_axis),
            check_vma=False,
        )
        contrib = f(stacked_blocks, other, tokens, targets)  # (S, D)
        # Mean over microbatches and data shards (the stage dimension is a
        # sum by construction: only valid cells accumulated anything).
        return jnp.sum(contrib) / (D * M)

    return loss_fn


# --------------------------------------------------------- GPT-2 adapter
def gpt2_pipeline_loss(
    config,
    *,
    mesh: Mesh,
    n_microbatches: int,
) -> Callable[[Any, jax.Array, jax.Array], jax.Array]:
    """Pipeline-parallel LM loss for ``GPT2(config, scan_layers=True)``
    params (the stacked-block layout), split as embed | blocks | ln_f+head.

    Dropout is off (inference-mode blocks): pipeline training runs the
    deterministic path, matching ``train=False`` semantics. Params keep the
    exact GPT2 pytree, so checkpoints interchange with the non-pipelined
    scan model. Cites the non-pipelined loss (train/step.py:89-105) as the
    numerical reference; ``tests/test_pipeline.py`` asserts equivalence.
    """
    from tpuflow.models.gpt2 import Block
    from tpuflow.models.losses import cross_entropy_loss

    cfg = config
    if not cfg.scan_layers:
        raise ValueError("pipeline params require GPT2Config(scan_layers=True)")
    if cfg.n_layer % mesh.shape[AXIS_STAGE]:
        raise ValueError(
            f"n_layer={cfg.n_layer} not divisible by "
            f"stage={mesh.shape[AXIS_STAGE]}"
        )
    block = Block(cfg)

    if cfg.n_experts > 0:
        # MoE blocks sow their load-balance aux into 'losses'; collect it
        # per layer so the schedule can mask/accumulate it (pipeline × EP
        # composition). Note the per-microbatch aux is computed on B/M rows
        # — the GPipe analogue of the full-batch statistic, equal up to
        # microbatch routing covariance.
        from tpuflow.models.losses import sum_sown_losses

        def block_apply(layer_params, x):
            out, updates = block.apply(
                {"params": layer_params}, x, False, mutable=["losses"]
            )
            return out, sum_sown_losses(updates)
    else:

        def block_apply(layer_params, x):
            return block.apply({"params": layer_params}, x, False), jnp.float32(0.0)

    def embed(other, tokens):
        T = tokens.shape[1]
        x = other["wte"][tokens].astype(cfg.dtype)
        return x + other["wpe"][:T].astype(cfg.dtype)

    def head_loss(other, x, targets):
        import flax.linen as nn

        x = nn.LayerNorm(dtype=cfg.dtype).apply({"params": other["ln_f"]}, x)
        logits = jnp.einsum(
            "btc,vc->btv", x, other["wte"].astype(cfg.dtype)
        ).astype(jnp.float32)
        # The canonical LM loss — same helper as the non-pipelined step.
        return cross_entropy_loss(logits, targets)

    pipe = make_pipeline_loss(
        block_apply, embed, head_loss, mesh=mesh, n_microbatches=n_microbatches
    )

    def loss_fn(params, tokens, targets):
        other = {k: v for k, v in params.items() if k != "h"}
        return pipe(params["h"]["block"], other, tokens, targets)

    return loss_fn


def gpt2_pipeline_shardings(mesh: Mesh, params):
    """NamedShardings for a GPT2 scan-layout param tree under pipeline
    parallelism: the stacked blocks split over 'stage', the rest replicated."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: NamedSharding(
            mesh,
            P(AXIS_STAGE)
            if any(getattr(k, "key", None) == "h" for k in path)
            else P(),
        ),
        params,
    )
