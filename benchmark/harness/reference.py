"""What every family's plain reference shares: the roundings that turn a
reference into its control, the key a seed makes, AdamW's arithmetic, and
the two drivers that run a family's reference at a cell's own size (the
first training steps in blocks of rows; served tokens, teacher-forced).

The architecture itself (the block, its gradient, the weights, the counts)
lives in `benchmark/families/<family>.py`, found by the name a
configuration's file gives. Nothing here imports anything of the program.

`quant` turns a reference into the control: every matrix product's
operands are rounded to float8 on the way in (e4m3, per-tensor scale) and
their gradients on the way back (e5m2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F8_MAX = 448.0
F8_E5M2_MAX = 57344.0


def _identity(a):
    return a


def _round_to(a, dtype, top):
    """Round to a float8 type and back, with a per-tensor scale."""
    s = jnp.max(jnp.abs(a)) / top
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def fp8_round(a):
    """An operand as a float8 matrix product sees it: e4m3 on the way in,
    and its gradient e5m2 on the way back (the usual float8 recipe)."""
    return _round_to(a, jnp.float8_e4m3fn, F8_MAX)


fp8_round.defvjp(
    lambda a: (fp8_round(a), None),
    lambda _, g: (_round_to(g, jnp.float8_e5m2, F8_E5M2_MAX),),
)


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def bf16_round(a):
    """An operand rounded to bfloat16, and its gradient on the way back: the
    precision the configurations state. No control (it is not below the
    stated precision) but a witness: `tools/first_steps.py` reads how far
    rounding of that size alone moves each number compared."""
    return _bf16(a)


bf16_round.defvjp(lambda a: (_bf16(a), None), lambda _, g: (_bf16(g),))


QUANT = {None: _identity, "fp8": fp8_round, "bf16": bf16_round}


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def adamw(p, mu, nu, g, t, *, lr, wd, b1=0.9, b2=0.999, eps=1e-8):
    """optax.adamw's arithmetic: Adam's scaled moments, decoupled weight
    decay added to the update, then times -lr."""
    def one(p, mu, nu, g):
        mu = b1 * mu + (1.0 - b1) * g
        nu = b2 * nu + (1.0 - b2) * g * g
        u = (mu / (1.0 - b1**t)) / (jnp.sqrt(nu / (1.0 - b2**t)) + eps)
        return p - lr * (u + wd * p), mu, nu

    out = jax.tree_util.tree_map(one, p, mu, nu, g)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple)
    )
    return pick(0), pick(1), pick(2)


def follow_steps(params, opt: dict, batches, block_grad, leaf_norms, *,
                 rows_per_block: int = 1, fault: str | None = None):
    """Follow `len(batches)` AdamW steps from `params`, a step's gradient
    summed over blocks of rows so that one block's activations are all that
    lives beside parameters, both moments and one gradient tree.

    `batches` is a list of (x, y) int arrays (B, T). The family gives
    `block_grad(params, g_acc, x, y, scale)`, which returns the loss (times
    `scale`) of one block and its gradient added into `g_acc` (jitted,
    `g_acc` donated), and `leaf_norms(tree)`. Returns each step's loss and
    the per-leaf norm of the first gradient, and the parameters after the
    last step. `fault="half_batch"` leaves out the second half of every
    batch and takes the mean over the rest.
    """
    lr, wd = float(opt["learning_rate"]), float(opt.get("weight_decay", 0.0))
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)  # noqa: E731
    mu, nu = zeros(), zeros()
    update = jax.jit(functools.partial(adamw, lr=lr, wd=wd), donate_argnums=(0, 1, 2))
    losses, grad_norms = [], None
    for step, (x, y) in enumerate(batches, start=1):
        x, y = np.asarray(x), np.asarray(y)
        if fault == "half_batch":
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        rows = x.shape[0]
        scale = jnp.float32(1.0 / (rows * x.shape[1]))
        g = zeros()
        loss = 0.0
        for r0 in range(0, rows, rows_per_block):
            part, g = block_grad(
                params, g, jnp.asarray(x[r0:r0 + rows_per_block]),
                jnp.asarray(y[r0:r0 + rows_per_block]), scale,
            )
            loss += float(part)
        losses.append(loss)
        if grad_norms is None:
            grad_norms = leaf_norms(g)
        params, mu, nu = update(params, mu, nu, g, jnp.float32(step))
    return {"losses": losses, "grad_norms": grad_norms}, params


def teacher_forced_gaps(params, logits_fn, samples, positions: int, *, quant=None) -> dict:
    """Teacher-forced check of served tokens.

    `samples` is a list of (prompt ids, served ids); the family gives
    `logits_fn(params, tokens, quant)`, (1, T) ids -> (1, T, V) logits. For
    every served token the reference's logits at that position give the gap
    by which the served token lies below the reference's best. With
    `quant`, the gap of the token the lower precision puts first is read
    beside it (the control)."""

    @jax.jit
    def gaps(params, tokens):
        logits = logits_fn(params, tokens[None], None)[0]
        best = jnp.max(logits, axis=-1)
        nxt = jnp.concatenate([tokens[1:], tokens[:1]])
        served = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
        if quant is None:
            return best - served, best - served
        low = jnp.argmax(logits_fn(params, tokens[None], quant)[0], axis=-1)
        return best - served, best - jnp.take_along_axis(logits, low[:, None], axis=-1)[:, 0]

    widest = widest_low = 0.0
    n_tokens = 0
    for prompt, served in samples:
        prompt, served = np.asarray(prompt, np.int32), np.asarray(served, np.int32)
        seq = np.concatenate([prompt, served])[:positions]
        padded = np.zeros((positions,), np.int32)
        padded[: seq.size] = seq
        g_served, g_low = gaps(params, jnp.asarray(padded))
        # logits at position i predict token i+1: the served tokens sit at
        # positions len(prompt)-1 ... len(seq)-2.
        lo, hi = prompt.size - 1, seq.size - 1
        if hi > lo:
            widest = max(widest, float(jnp.max(g_served[lo:hi])))
            widest_low = max(widest_low, float(jnp.max(g_low[lo:hi])))
            n_tokens += hi - lo
    return {"widest_gap": widest, "widest_gap_low": widest_low, "tokens": n_tokens}
